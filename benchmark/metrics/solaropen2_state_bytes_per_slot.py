"""KV cache: bytes of slot state one request holds over all KDA layers,
whatever its context, as the program publishes it at engine build (gauge
``serving_state_bytes_per_slot`` of its process registry). For the
Solar-Open2 family only; a reading that differs from the shapes' count
(``counts_solar_open2.state_bytes_per_slot``: per KDA layer the float32
matrix state, 64 x 128 x 128, and the three convolutions' last inputs; the
GQA layer none) fails the run loudly."""
from benchmark.harness import counts_solar_open2 as counts


def read(obs):
    if "pool" not in obs or not counts.is_family(obs["config"]):
        return None
    try:
        from paddle_tpu.obs.registry import MetricsRegistry
    except ImportError:
        return None
    gauge = MetricsRegistry.process().get("serving_state_bytes_per_slot")
    if gauge is None:
        return None
    value = gauge.value(pool="target")
    want = counts.state_bytes_per_slot(obs["config"])
    if value != want:
        raise RuntimeError(f"a slot holds {value} bytes of state, the "
                           f"configuration's shapes give {want}")
    return value
