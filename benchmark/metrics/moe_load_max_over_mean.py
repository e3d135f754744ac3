"""Experts: the fullest expert's rows over the mean rows an expert got, per
expert layer and decode step of the window (the program's
``serving_moe_expert_rows_max_total`` over ``serving_moe_routed_rows_total``
/ experts, as its ``engine.decode`` spans carry them). 1 is an even load."""
from benchmark.harness import moe_spans


def read(obs):
    moe = moe_spans.window_totals(obs)
    if not moe or not moe["moe_rows"]:
        return None
    experts = int(obs["config"]["n_routed_experts"])
    return moe["moe_rows_max"] * experts / moe["moe_rows"]
