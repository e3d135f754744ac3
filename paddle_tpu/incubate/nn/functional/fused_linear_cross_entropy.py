"""Chunked fused lm-head + softmax cross-entropy (reference: PaddleNLP's
fused head-and-loss path used by large-vocab causal-LM training —
unverified, SURVEY.md §0).

At pretrain shapes the unfused loss path materializes the full
``(B*S, V)`` logits THREE times over — bf16 forward logits, the f32
log-softmax, and the f32 logits gradient (≈2.6 GB at B2/S4096/V32k) —
which is exactly the HBM-pressure regime where XLA's scheduler starts
serializing (PERF.md section 6, "before the ledger").

TPU-native fix: ``lax.scan`` over row chunks computing the loss AND the
(unscaled) gradients in the same pass — cross-entropy's logits gradient
``(softmax - onehot) / count`` does not depend on the upstream cotangent
except through a scalar scale, so the forward contracts each chunk's
gradient to ``dh`` (hidden-sized, bf16) and a running ``dW`` (f32) and
the custom-vjp backward just scales them. Matmul count is identical to
the unfused path (logits, dh, dW — no recompute); peak logits residency
drops from ``N*V`` to ``chunk_rows*V``.

Trade-offs: loss-only (no-grad) callers pay the two gradient matmuls,
and double backward through this op is unsupported (custom_vjp) — it is
a training criterion, not a general layer.
"""
from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from ....tensor._helpers import apply, ensure_tensor

__all__ = ["fused_linear_cross_entropy"]


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lce_core(hs, ys, w, bias, ignore_index):
    loss, _ = _lce_fwd_impl(hs, ys, w, bias, ignore_index)
    return loss


def _lce_fwd_impl(hs, ys, w, bias, ignore_index):
    v = w.shape[1]

    def body(carry, xs):
        s, cnt, dw, db = carry
        h_c, y_c = xs
        logits = jnp.dot(h_c, w, preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)[None, :]
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = y_c != ignore_index
        safe = jnp.where(valid, y_c, 0).astype(jnp.int32)
        picked = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        s = s + jnp.sum(jnp.where(valid, lse - picked, 0.0))
        cnt = cnt + jnp.sum(valid.astype(jnp.float32))
        # unscaled logits gradient: softmax - onehot, zero on ignored
        # rows; cast to the activation dtype so the two grad matmuls run
        # on the MXU at the same precision the unfused backward would
        p = jnp.exp(logits - lse[:, None])
        p = p - jax.nn.one_hot(safe, v, dtype=p.dtype)
        p = jnp.where(valid[:, None], p, 0.0).astype(h_c.dtype)
        dh_c = jnp.dot(p, w.T).astype(h_c.dtype)
        dw = dw + jnp.dot(h_c.T, p, preferred_element_type=jnp.float32)
        if bias is not None:
            db = db + jnp.sum(p.astype(jnp.float32), axis=0)
        return (s, cnt, dw, db), dh_c

    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = jnp.zeros((v,), jnp.float32) if bias is not None \
        else jnp.float32(0.0)
    (s, cnt, dw, db), dh = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0), dw0, db0), (hs, ys))
    cnt = jnp.maximum(cnt, 1.0)
    return s / cnt, (dh, dw, db, cnt, ys.shape)


def _lce_fwd(hs, ys, w, bias, ignore_index):
    loss, res = _lce_fwd_impl(hs, ys, w, bias, ignore_index)
    # empty dtype-carrier arrays: residual pytrees may hold arrays only
    w_dt = jnp.zeros((0,), w.dtype)
    b_dt = None if bias is None else jnp.zeros((0,), bias.dtype)
    return loss, (res, w_dt, b_dt)


def _lce_bwd(ignore_index, saved, g):
    (dh, dw, db, cnt, y_shape), w_dt, b_dt = saved
    scale = (g / cnt).astype(jnp.float32)
    dy = np.zeros(y_shape, jax.dtypes.float0)  # int labels: no tangent
    dbias = None if b_dt is None else (db * scale).astype(b_dt.dtype)
    return (dh * scale.astype(dh.dtype), dy,
            (dw * scale).astype(w_dt.dtype), dbias)


_lce_core.defvjp(_lce_fwd, _lce_bwd)


def _fused_lce(h, w, y, *maybe_bias, chunk_rows, ignore_index):
    bias = maybe_bias[0] if maybe_bias else None
    hd = h.shape[-1]
    h = h.reshape(-1, hd)
    y = y.reshape(-1)
    n = h.shape[0]
    pad = (-n) % chunk_rows
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad), constant_values=ignore_index)
    nch = h.shape[0] // chunk_rows
    hs = h.reshape(nch, chunk_rows, hd)
    ys = y.reshape(nch, chunk_rows)
    return _lce_core(hs, ys, w, bias, ignore_index)


def fused_linear_cross_entropy(hidden, weight, labels, bias=None,
                               ignore_index=-100, chunk_rows=1024):
    """Mean softmax cross-entropy of ``hidden @ weight (+ bias)`` against
    ``labels`` without materializing the full logits.

    Args:
        hidden: ``(..., N, H)`` final transformer hidden states (any
            leading batch dims; flattened internally). Typically already
            shifted: ``hidden[:, :-1]`` vs ``labels[:, 1:]``.
        weight: ``(H, V)`` lm-head weight (paddle Linear layout).
        labels: integer class ids broadcastable to ``hidden``'s leading
            dims; positions equal to ``ignore_index`` are excluded from
            both the sum and the mean's denominator.
        bias: optional ``(V,)`` lm-head bias.
        chunk_rows: rows per scan step — peak logits memory is
            ``chunk_rows * V * 4`` bytes.

    Returns the mean loss as a float32 scalar Tensor.
    """
    args = [ensure_tensor(hidden), ensure_tensor(weight),
            ensure_tensor(labels)]
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(
        _fused_lce, *args,
        chunk_rows=int(chunk_rows), ignore_index=int(ignore_index),
        op_name="fused_linear_cross_entropy",
    )
