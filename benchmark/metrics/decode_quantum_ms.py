"""Engine step: median wall time of a front-door pump that ran one jitted
decode quantum."""
import statistics


def read(obs):
    pumps = obs.get("quantum_pump_seconds")
    return 1e3 * statistics.median(pumps) if pumps else None
