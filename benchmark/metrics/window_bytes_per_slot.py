"""KV cache: bytes of window-attention rings one request holds over all
window layers, whatever its context, as the program publishes it at engine
build (gauge ``serving_window_bytes_per_slot`` of its process registry). For
the window / full attention family only; a reading that differs from the
shapes' count (``counts_afmoe.window_bytes_per_slot``: per window layer a K
and a V ring of ``sliding_window + prefill_chunk`` positions in whole
blocks) fails the run loudly."""
from benchmark.harness import counts_afmoe as counts


def read(obs):
    if "pool" not in obs or not counts.is_family(obs["config"]):
        return None
    try:
        from paddle_tpu.obs.registry import MetricsRegistry
    except ImportError:
        return None
    gauge = MetricsRegistry.process().get("serving_window_bytes_per_slot")
    if gauge is None:
        return None
    value = gauge.value(pool="target")
    want = counts.window_bytes_per_slot(obs["config"])
    if value != want:
        raise RuntimeError(f"a slot holds {value} bytes of window rings, "
                           f"the configuration's shapes give {want}")
    return value
