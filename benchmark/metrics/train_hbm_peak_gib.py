"""Device: peak memory of the fullest chip, training."""
from benchmark.harness.readers import hbm_peak_gib as read  # noqa: F401
