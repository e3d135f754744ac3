"""KV cache: bytes of pool one cached token takes over all layers, as the
program publishes it at engine build (gauge ``serving_pool_bytes_per_token``
of its process registry: the pool's bytes over its token capacity). A latent
pool holds ``kv_lora_rank + qk_rope_head_dim`` values a token and layer and
no V array; a reading above that fails the run loudly: the cache has
stopped being latent."""
from benchmark.harness import counts_deepseek_v3 as counts


def read(obs):
    if "pool" not in obs:
        return None
    try:
        from paddle_tpu.obs.registry import MetricsRegistry
    except ImportError:
        return None
    gauge = MetricsRegistry.process().get("serving_pool_bytes_per_token")
    if gauge is None:
        return None
    value = gauge.value(pool="target")
    if value <= 0:
        return None
    cfg = obs["config"]
    if counts.is_family(cfg):
        itemsize = {"bfloat16": 2, "float32": 4}[cfg["torch_dtype"]]
        latent = counts.cache_bytes_per_token(cfg, itemsize)
        if value > latent:
            raise RuntimeError(
                f"the pool takes {value} bytes a token, a latent pool "
                f"{latent}: the cache is no longer latent")
    return value
