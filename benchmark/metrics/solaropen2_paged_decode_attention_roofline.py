"""Kernels: the least time the chip could take for the traced batches'
paged decode attention in the Solar-Open2 family's GQA layers
(``counts_solar_open2.paged_attention_needs``: one layer in four, 8 query
heads a KV head and 8 KV heads; memory-bound at these contexts) over the
device time of the ``paged_decode_attention`` kernel's events."""
from benchmark.harness import counts_solar_open2 as counts
from benchmark.harness import xplane
from benchmark.harness.counts import roofline_seconds


def read(obs):
    trace = obs.get("trace")
    if not trace or "new_tokens" not in obs \
            or not counts.is_family(obs["config"]):
        return None
    seconds = xplane.kernel_seconds(trace, "paged_decode_attention")
    if seconds <= 0:
        return None
    flops, nbytes = counts.paged_attention_needs(
        obs["config"], obs["batch"], obs["prompt_len"], obs["new_tokens"])
    least, _ = roofline_seconds(flops * obs["batches"],
                                nbytes * obs["batches"], obs["peaks"])
    return 100.0 * least / seconds
