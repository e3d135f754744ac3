"""Device: bytes the traced decode steps must move (every layer's weights,
the final norm and the head once a step, every live slot's recurrent state
read and written once a layer, the live keys and values of every layer:
``counts_falcon_h1.decode_bytes_needed``, needed bytes only) over the device
time of the jitted decode quantum, against the chip's memory bandwidth. For
the Falcon-H1 family only."""
from benchmark.harness import counts_falcon_h1 as counts

PROGRAM = "jit_quantum"  # the engine's jitted decode step, as the trace names it


def read(obs):
    trace = obs.get("trace")
    if not trace or "new_tokens" not in obs \
            or not counts.is_family(obs["config"]):
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    if seconds <= 0:
        return None
    nbytes = counts.decode_bytes_needed(
        obs["config"], obs["batches"], obs["batch"], obs["prompt_len"],
        obs["new_tokens"])
    return 100.0 * nbytes / seconds / obs["peaks"]["hbm_bytes_per_s"]
