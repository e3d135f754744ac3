"""Engine step: operations the traced mixed prefill steps need — 2 x the
weights a token multiplies for each valid position (every expert is held: a
token's ``num_experts_per_tok``), and the attended (query, key) pairs of the
prompts clamped by layer kind (``counts_afmoe.prefill_flops``) — over the
device time of the jitted mixed step, against the chip's bf16 peak: the
share of the WHOLE step. For the window / full attention expert family
only."""
from benchmark.harness import counts_afmoe as counts
from benchmark.harness import program_spans

PROGRAM = "jit_mixed"  # the engine's jitted mixed step, as the trace names it


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("engine_steps") \
            or not counts.is_family(obs["config"]):
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    _, steps = program_spans.window_steps(obs)
    spans = [s[0]["args"] for s in steps["mixed"]
             if "window_keys" in s[0]["args"]]
    if seconds <= 0 or not spans:
        return None
    slots = int(obs["config"]["engine"]["num_slots"])
    tokens = sum(slots * a["bucket"] - a["padded_tokens"] for a in spans)
    flops = counts.prefill_flops(
        obs["config"], tokens, obs["batches"] * obs["batch"],
        obs["prompt_len"])
    return 100.0 * flops / seconds / obs["peaks"]["bf16_flops"]
