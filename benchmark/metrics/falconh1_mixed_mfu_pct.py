"""Engine step: operations the traced mixed prefill steps need, the WHOLE
step's: 2 x the weights a token multiplies in both branches and the MLP, the
state-space sum as the recurrence, the attention's causal pairs
(``counts_falcon_h1.prefill_flops``, over the valid positions of the
``engine.mixed`` spans), over the device time of the jitted mixed step,
against the chip's bf16 peak. For the Falcon-H1 family only."""
from benchmark.harness import counts_falcon_h1 as counts
from benchmark.harness import program_spans

PROGRAM = "jit_mixed"  # the engine's jitted mixed step, as the trace names it


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("engine_steps") \
            or not counts.is_family(obs["config"]):
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    _, steps = program_spans.window_steps(obs)
    spans = [s[0]["args"] for s in steps["mixed"] if "bucket" in s[0]["args"]]
    if seconds <= 0 or not spans:
        return None
    slots = int(obs["config"]["engine"]["num_slots"])
    tokens = sum(slots * a["bucket"] - a["padded_tokens"] for a in spans)
    flops = counts.prefill_flops(
        obs["config"], tokens, obs["batches"] * obs["batch"],
        obs["prompt_len"])
    return 100.0 * flops / seconds / obs["peaks"]["bf16_flops"]
