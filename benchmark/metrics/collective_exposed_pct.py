"""Mesh: collective time during which no compute ran on that chip, over
the traced window, averaged over the chips."""


def read(obs):
    trace = obs.get("trace")
    if not trace or trace["collective_s"] <= 0:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
