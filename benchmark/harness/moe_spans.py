"""What the program's ``engine.decode`` spans carry about the routed
experts (``moe_rows``, ``moe_experts_touched``, ``moe_rows_max``,
``moe_layer_steps``: what it raised its ``serving_moe_*_total`` counters
by in that quantum), added up over the window's decode steps. A program
without routed experts, or without the spans, gives None."""
from benchmark.harness import program_spans

KEYS = ("moe_rows", "moe_experts_touched", "moe_rows_max", "moe_layer_steps")


def window_totals(obs):
    _, steps = program_spans.window_steps(obs)
    rows = [r for step in steps["decode"] for r in step
            if "moe_layer_steps" in r["args"]]
    if not rows:
        return None
    return {k: sum(r["args"][k] for r in rows) for k in KEYS}
