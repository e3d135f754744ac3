"""Device: idle share of the traced training window."""
from benchmark.harness.readers import device_idle_pct as read  # noqa: F401
