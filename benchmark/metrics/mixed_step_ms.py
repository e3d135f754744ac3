"""Engine step: median wall time of a front-door pump that ran the eager
mixed prefill step (the benchmark's span round ``door.pump()``, joined with
``engine.stats['mixed_steps']``)."""
import statistics


def read(obs):
    pumps = obs.get("mixed_pump_seconds")
    return 1e3 * statistics.median(pumps) if pumps else None
