"""Engine step: operations the traced mixed prefill steps need (2 x the
weights a token multiplies x the window's prompt tokens, and the causal
attention pairs un-absorbed; ``counts_deepseek_v3.prefill_flops``) over the
device time of the jitted mixed step, against the chip's bf16 peak. For the
latent-attention expert family only."""
from benchmark.harness import counts_deepseek_v3 as counts

PROGRAM = "jit_mixed"  # the engine's jitted mixed step, as the trace names it


def read(obs):
    trace = obs.get("trace")
    steps = obs.get("engine_steps")
    if not trace or not steps or not counts.is_family(obs["config"]):
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    if seconds <= 0:
        return None
    flops = counts.prefill_flops(
        obs["config"], steps["prefill_tokens"],
        obs["batches"] * obs["batch"], obs["prompt_len"])
    return 100.0 * flops / seconds / obs["peaks"]["bf16_flops"]
