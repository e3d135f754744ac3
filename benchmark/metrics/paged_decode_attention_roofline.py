"""Kernels: the least time the chip could take for the traced batches'
paged decode attention (``counts.paged_attention_needs``: memory-bound at
these contexts) over the device time of the ``paged_decode_attention``
kernel's events."""
from benchmark.harness import counts, xplane


def read(obs):
    trace = obs.get("trace")
    if not trace or "new_tokens" not in obs:
        return None
    seconds = xplane.kernel_seconds(trace, "paged_decode_attention")
    if seconds <= 0:
        return None
    flops, nbytes = counts.paged_attention_needs(
        obs["config"], obs["batch"], obs["prompt_len"], obs["new_tokens"])
    least, _ = counts.roofline_seconds(flops * obs["batches"],
                                       nbytes * obs["batches"], obs["peaks"])
    return 100.0 * least / seconds
