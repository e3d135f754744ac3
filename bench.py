"""Driver benchmark: single-chip Llama-block pretrain step under the
fully-jitted path (bf16 params + f32 master weights + bf16 Adam moments,
Pallas flash attention, no activation recompute), reporting MFU against
the BASELINE.md north-star (45% MFU).

Runs on a TPU only: no chip is a failure, and the configuration measured
is the one asked for (an out-of-memory error surfaces; nothing is
halved or rematerialised behind the caller's back).

Prints ONE JSON line to stdout; human detail goes to stderr.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache — the one place the
    entry scripts (bench.py, chip_smoke.py) do so. The directory comes
    from ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it
    itself; no other directory is set in code), else it is the fixed
    ``.jax_cache/`` of this checkout: the path is part of the cache key,
    so it is never built from a temp name, a pid or the time. Every
    program is kept, small ones included — the serving prefill is
    hundreds of sub-second compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def headline_config(**overrides):
    """Llama-2-7B at its published widths (h4096, 32 heads x d128, FFN
    11008, vocab 32000 — BASELINE config #3) with DEPTH cut to the L=4
    one v5e-16G holds (~1.07B params; bf16 params + f32 master + bf16
    Adam moments)."""
    from paddle_tpu.nlp import LlamaConfig

    kw = dict(num_hidden_layers=4, tensor_parallel=False,
              use_recompute=False)
    kw.update(overrides)
    return LlamaConfig.llama2_7b(**kw)


def build_step(cfg, batch, seq, lr=1e-4, moment_dtype="float32", **step_kw):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaForCausalLM, LlamaPretrainingCriterion
    from paddle_tpu.jit.train import JittedTrainStep

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.astype("bfloat16")
    fused = getattr(cfg, "fuse_linear_cross_entropy", False)
    crit = LlamaPretrainingCriterion(
        cfg, lm_head=model.lm_head if fused else None)

    if fused:
        # chunked fused lm-head+CE: model returns bf16 hidden; the op
        # accumulates in f32 — no full logits buffer ever exists
        def criterion(out, labels):
            return crit(out, labels)
    else:
        def criterion(out, labels):
            return crit(out.astype("float32"), labels)

    opt = paddle.optimizer.AdamW(
        lr, parameters=model.parameters(), weight_decay=0.01,
        multi_precision=True, moment_dtype=moment_dtype,
    )
    step = JittedTrainStep(model, criterion, opt, **step_kw)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq))
    )
    return model, step, ids


def count_params(model):
    import numpy as np

    return sum(int(np.prod(p._value.shape))
               for _, p in model.named_parameters())


def main():
    import jax

    enable_compile_cache()
    dev = jax.devices()[0]
    log(f"platform={dev.platform} device={dev.device_kind} "
        f"n={len(jax.devices())}")
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures on a TPU; jax found {dev.platform!r} "
                 f"({dev.device_kind}). No chip is a failure, not a "
                 "slower benchmark.")

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.profiler.mfu import (
        MFUMeter, transformer_train_flops, peak_flops_per_chip,
    )

    # END-TO-END training at Llama-2-7B dimensions. Measured sweep
    # (round 4, BENCH_NOTES): B1 S4096 no-remat beats B2 (HBM pressure)
    # and B2+attn-remat.
    cfg = headline_config()
    batch, seq, iters = 1, 4096, 3
    K = 10  # train steps fused into one dispatch

    model, step, ids = build_step(cfg, batch, seq, moment_dtype="bfloat16")
    n_params = count_params(model)
    tokens = batch * seq
    flops = transformer_train_flops(
        n_params, tokens, num_layers=cfg.num_hidden_layers,
        seq_len=seq, hidden=cfg.hidden_size, causal=True,
    )
    log(f"params={n_params/1e6:.1f}M tokens/step={tokens} K={K} "
        f"steps/dispatch model TFLOPs/step={flops/1e12:.2f} "
        f"peak={peak_flops_per_chip()/1e12:.0f}")

    # K different batches stacked along a leading scan dim
    ids_stacked = paddle.to_tensor(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (K, batch, seq)))

    t0 = time.perf_counter()
    meter = MFUMeter(flops * K, tokens * K)
    res = meter.measure(
        lambda: step.run_steps(ids_stacked, ids_stacked),
        warmup=1, iters=iters)
    # meter timed K-step dispatches; rescale to per-step
    res["step_time_s"] /= K
    log(f"compile+warmup+{iters}x{K}-step dispatches took "
        f"{time.perf_counter()-t0:.1f}s")
    log(json.dumps(res, indent=2))

    print(json.dumps({
        "metric": "llama_7b_shape_e2e_train_mfu",
        "value": round(res["mfu"] * 100, 2),
        "unit": "%MFU",
        "vs_baseline": round(res["mfu"] / 0.45, 3),
        "tokens_per_sec_per_chip": round(res["tokens_per_sec_per_chip"]),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "layers": cfg.num_hidden_layers, "batch": batch, "seq": seq,
    }))


if __name__ == "__main__":
    main()
