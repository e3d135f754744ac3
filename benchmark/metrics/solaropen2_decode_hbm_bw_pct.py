"""Device: bytes the traced decode steps must move (the weights outside the
routed experts once a step, each HELD expert the program's counter says got
a row, every live slot's matrix state and convolution tails read and written
once a KDA layer, the live keys and values of the GQA layer) over the device
time of the jitted decode quantum, against the chip's memory bandwidth. For
the Solar-Open2 family only."""
from benchmark.harness import counts_solar_open2 as counts
from benchmark.harness import moe_spans

PROGRAM = "jit_quantum"  # the engine's jitted decode step, as the trace names it


def read(obs):
    trace = obs.get("trace")
    if not trace or "new_tokens" not in obs \
            or not counts.is_family(obs["config"]):
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    moe = moe_spans.window_totals(obs)
    if seconds <= 0 or not moe:
        return None
    nbytes = counts.decode_bytes_needed(
        obs["config"], moe["moe_experts_touched"], obs["batches"],
        obs["batch"], obs["prompt_len"], obs["new_tokens"])
    return 100.0 * nbytes / seconds / obs["peaks"]["hbm_bytes_per_s"]
