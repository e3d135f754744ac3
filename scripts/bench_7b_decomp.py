"""Section decomposition of the 7B-shape train step at B1/B2 (round-5
B2-cliff investigation): times fwd-only and fwd+bwd as separate
chained-fori_loop programs with a scalar fetch barrier and N-vs-2N
differencing (BENCH_NOTES methodology), to locate where the B2 MFU gap
lives. The full-step time comes from bench_7b_sweep.py.
"""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "/root/repo")


def timed(fn, n_lo=3, reps=3):
    """min over reps of (t(2n) - t(n)) / n, warm-compiled first; n varies
    per rep so no dispatch is byte-identical."""
    import jax

    float(jax.device_get(fn(1)))  # compile + warm
    best = None
    for r in range(reps):
        n = n_lo + r
        ts = {}
        for m in (n, 2 * n):
            t0 = time.perf_counter()
            out = fn(m)
            float(jax.device_get(out))
            ts[m] = time.perf_counter() - t0
        per = (ts[2 * n] - ts[n]) / n
        best = per if best is None else min(best, per)
    return best


def main(batch, fused):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle  # noqa: F401
    from paddle_tpu.nlp import LlamaConfig
    from bench import build_step

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=4, num_attention_heads=32,
        max_position_embeddings=4096, tensor_parallel=False,
        fuse_linear_cross_entropy=bool(fused),
    )
    cfg.lce_chunk_rows = 2048
    model, step, ids = build_step(cfg, batch, 4096, moment_dtype="bfloat16")
    ids_v = ids._value
    p_vals, b_vals = step._p_vals, step._b_vals
    criterion = step._criterion

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.core import autograd
    from paddle_tpu.core.random import next_key, traced_key_scope
    from paddle_tpu.jit import functional_call

    def loss_of(pv, rng):
        with autograd.no_grad(), traced_key_scope(rng):
            def fwd_and_loss(xt, yt):
                return criterion(model(xt), yt)

            out_t, _ = functional_call(
                model, fwd_and_loss,
                [Tensor(ids_v, stop_gradient=True),
                 Tensor(ids_v, stop_gradient=True)], {}, pv, b_vals)
        return out_t._value

    rng0 = next_key()

    # params must be jit ARGUMENTS — closed-over they become program
    # constants and all ~10 GB are embedded in every compile
    # iterations must be DATA-DEPENDENT or XLA hoists the loop-invariant
    # body and the loop times as free: thread acc into a param via a
    # numerically-negligible perturbation
    def chain(pv, acc):
        return [pv[0] + (acc * jnp.float32(1e-38)).astype(pv[0].dtype)] \
            + list(pv[1:])

    @jax.jit
    def fwd_n(pv, n):
        def body(i, acc):
            return acc + loss_of(chain(pv, acc),
                                 jax.random.fold_in(rng0, i))

        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))

    @jax.jit
    def grad_n(pv, n):
        def body(i, acc):
            g = jax.grad(loss_of)(chain(pv, acc),
                                  jax.random.fold_in(rng0, i))
            # consume EVERY grad — fetching one would let XLA prune the
            # other params' dW matmuls from the backward
            return acc + sum(x.astype(jnp.float32).sum() for x in g)

        return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))

    t_fwd = timed(lambda n: fwd_n(p_vals, n))
    print(f"B{batch} fused={int(bool(fused))}: fwd-only "
          f"{t_fwd*1e3:.1f} ms", flush=True)
    t_g = timed(lambda n: grad_n(p_vals, n))
    print(f"B{batch} fused={int(bool(fused))}: fwd+bwd "
          f"{t_g*1e3:.1f} ms", flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]), bool(int(sys.argv[2])))
