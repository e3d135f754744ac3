"""Experts: the fullest expert's rows over the mean rows an expert got, per
expert layer and decode step of the window (the program's
``serving_moe_expert_rows_max_total`` over ``serving_moe_routed_rows_total``
/ experts, as its ``engine.decode`` spans carry them). 1 is an even load.
For the family whose configuration counts its experts as ``num_experts``."""
from benchmark.harness import counts_afmoe as counts
from benchmark.harness import moe_spans


def read(obs):
    if not counts.is_family(obs["config"]):
        return None
    moe = moe_spans.window_totals(obs)
    if not moe or not moe["moe_rows"]:
        return None
    return moe["moe_rows_max"] * int(obs["config"]["num_experts"]) \
        / moe["moe_rows"]
