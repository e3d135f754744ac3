"""Readings that more than one per-layer metric shares (each metric keeps
the file of its own name; cells of different kinds report different ones)."""


def device_idle_pct(obs):
    """Share of the traced window in which no operation ran on the device
    (1 - union of the device's op intervals over the window), averaged
    over the chips used."""
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def hbm_peak_gib(obs):
    """``memory_stats()['peak_bytes_in_use']`` of the fullest chip when the
    window closed, before the reference ran."""
    return obs["memory_peak_bytes"] / 2 ** 30
