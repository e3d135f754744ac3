"""Device: bytes the traced batches' decode steps must read (the weights
once per token step, the live cache) over the device time of the jitted
decode quantum, against the chip's memory bandwidth."""
from benchmark.harness import counts

PROGRAM = "jit_quantum"  # the engine's jitted decode step, as the trace names it


def read(obs):
    trace = obs.get("trace")
    if not trace or "new_tokens" not in obs:
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    if seconds <= 0:
        return None
    nbytes = obs["batches"] * counts.decode_bytes_needed(
        obs["config"], obs["batch"], obs["prompt_len"], obs["new_tokens"])
    return 100.0 * nbytes / seconds / obs["peaks"]["hbm_bytes_per_s"]
