"""Scheduler: mean share of the engine's slots that held a live request,
over the window's scheduler steps (``engine_stats()``: ``occupancy_sum`` /
``steps``)."""


def read(obs):
    steps = obs.get("engine_steps")
    if not steps or not steps["steps"]:
        return None
    return 100.0 * steps["occupancy_sum"] / steps["steps"]
