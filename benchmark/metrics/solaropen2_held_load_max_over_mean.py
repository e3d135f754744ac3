"""Experts: the fullest HELD expert's rows over the mean rows a held expert
got, per expert layer and decode step of the window (the program's
``serving_moe_expert_rows_max_total`` over ``serving_moe_routed_rows_total``
/ held experts, as its ``engine.decode`` spans carry them). 1 is an even
load. For the Solar-Open2 family, whose chip holds 40 of 320 experts."""
from benchmark.harness import counts_solar_open2 as counts
from benchmark.harness import moe_spans


def read(obs):
    if not counts.is_family(obs["config"]):
        return None
    moe = moe_spans.window_totals(obs)
    if not moe or not moe["moe_rows"]:
        return None
    held = int(obs["config"]["n_routed_experts"])
    return moe["moe_rows_max"] * held / moe["moe_rows"]
