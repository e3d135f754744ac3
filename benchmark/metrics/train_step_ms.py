"""Trainer: median wall time of a dispatch (call plus wait) over the steps
it fuses."""
import statistics


def read(obs):
    if not obs.get("dispatch_seconds"):
        return None
    return (1e3 * statistics.median(obs["dispatch_seconds"])
            / obs["steps_per_dispatch"])
