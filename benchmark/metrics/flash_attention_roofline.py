"""Kernels: the least time a chip could take for its share of the three
flash-attention kernels' operations in the traced steps (compute-bound;
``counts.flash_kernel_flops``, inside the causal band only) over the device
time of those kernels' events, per chip."""
from benchmark.harness import counts, xplane


def read(obs):
    trace = obs.get("trace")
    if not trace or "seq" not in obs:
        return None
    need = counts.flash_kernel_flops(obs["config"], obs["batch"], obs["seq"])
    seconds = sum(xplane.kernel_seconds(trace, k) for k in need)
    if seconds <= 0:
        return None
    steps = obs["dispatches"] * obs["steps_per_dispatch"]
    flops = sum(need.values()) * steps / obs["chips"]
    return 100.0 * flops / obs["peaks"]["bf16_flops"] / seconds
