"""Kernels: the least time the chip could take for the traced batches'
one-step delta rule in the Solar-Open2 family's KDA layers
(``counts_solar_open2.kda_update_needs``: each slot's 4 MB matrix state a
layer read once and written once a step; memory-bound, 7 operations for 8
bytes) over the device time of the ``kda_decode_update`` kernel's events. A
program that computes the step without the kernel has no such events and the
reader says nothing."""
from benchmark.harness import counts_solar_open2 as counts
from benchmark.harness import xplane
from benchmark.harness.counts import roofline_seconds


def read(obs):
    trace = obs.get("trace")
    if not trace or "new_tokens" not in obs \
            or not counts.is_family(obs["config"]):
        return None
    seconds = xplane.kernel_seconds(trace, "kda_decode_update")
    if seconds <= 0:
        return None
    flops, nbytes = counts.kda_update_needs(
        obs["config"], obs["batch"], obs["new_tokens"])
    least, _ = roofline_seconds(flops * obs["batches"],
                                nbytes * obs["batches"], obs["peaks"])
    return 100.0 * least / seconds
