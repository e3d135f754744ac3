"""KV cache: bytes of slot state one request holds over all layers, whatever
its context, as the program publishes it at engine build (gauge
``serving_state_bytes_per_slot`` of its process registry). For the Falcon-H1
family only, whose every layer keeps a slot row BESIDE its key blocks; a
reading that differs from the shapes' count
(``counts_falcon_h1.state_bytes_per_slot``: per layer the float32 state and
the convolution's last inputs) fails the run loudly."""
from benchmark.harness import counts_falcon_h1 as counts


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
