"""Attention functionals.

``scaled_dot_product_attention`` mirrors paddle's API (reference:
python/paddle/nn/functional/flash_attention.py — unverified, SURVEY.md §0)
and routes to the Pallas flash-attention kernel on TPU (the analog of the
reference's vendored flash-attn CUDA kernel), falling back to a fused XLA
softmax-attention elsewhere.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...tensor._helpers import Tensor, apply, ensure_tensor
from ...core.flags import get_flags

# Imported eagerly so a broken kernel package fails loudly at import time
# instead of silently falling back at every call (round-1 advisor finding).
from ...ops.pallas.flash_attention import flash_attention as _pallas_flash
from ...ops.pallas.varlen_flash_attention import (
    varlen_flash_attention as _pallas_varlen_flash,
)


def _xla_attention(q, k, v, mask=None, causal=False, dropout_p=0.0, scale=None,
                   key=None):
    """Reference attention in pure XLA ops; layout (B, S, H, D)."""
    if k.shape[2] != q.shape[2]:  # GQA/MQA: repeat kv heads to q heads
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    # (B, H, Sq, Sk)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sc
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def sliding_window_attention(query, key, value, window_size,
                             training=True, name=None):
    """Causal sliding-window attention (Mistral semantics: each query
    attends to the last ``window_size`` keys, itself included). Routes
    to the Pallas flash kernel's banded tiles on TPU (cost
    O(S * window)); elsewhere an XLA banded-mask fallback."""
    query, key_, value = (ensure_tensor(query), ensure_tensor(key),
                          ensure_tensor(value))
    w = int(window_size)
    if w < 1:
        # the XLA path's empty band would softmax to NaN
        raise ValueError(f"window_size must be >= 1, got {window_size}")
    flags = get_flags(["FLAGS_use_pallas_kernels", "FLAGS_pallas_force"])
    use_pallas = (
        flags["FLAGS_use_pallas_kernels"]
        and (jax.default_backend() == "tpu" or flags["FLAGS_pallas_force"])
        and query._value.shape[-1] >= 64
    )
    if use_pallas:
        return apply(
            lambda q, k, v: _pallas_flash(q, k, v, causal=True,
                                          window_size=w),
            query, key_, value, op_name="sliding_window_attention",
        )

    def fn(q, k, v):
        sq, sk = q.shape[1], k.shape[1]
        qpos = jnp.arange(sq)[:, None] + (sk - sq)
        kpos = jnp.arange(sk)[None, :]
        band = (kpos <= qpos) & (kpos >= qpos - w + 1)
        return _xla_attention(q, k, v, mask=band[None, None], causal=False,
                              dropout_p=0.0, key=None)

    return apply(fn, query, key_, value,
                 op_name="sliding_window_attention")


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Layout (batch, seq, num_heads, head_dim) — paddle's flash-attn layout."""
    query, key_, value = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    flags = get_flags(["FLAGS_use_pallas_kernels", "FLAGS_pallas_force"])
    use_pallas = (
        flags["FLAGS_use_pallas_kernels"]
        and attn_mask is None
        and (dropout_p == 0.0 or not training)
        and (jax.default_backend() == "tpu" or flags["FLAGS_pallas_force"])
        and query._value.shape[-1] >= 64
    )
    if use_pallas:
        return apply(
            lambda q, k, v: _pallas_flash(q, k, v, causal=is_causal),
            query, key_, value, op_name="flash_attention",
        )

    rng_key = None
    if dropout_p > 0.0 and training:
        from ...core.random import next_key

        rng_key = next_key()

    def fn(q, k, v, *maybe_mask):
        m = maybe_mask[0] if maybe_mask else None
        return _xla_attention(
            q, k, v, mask=m, causal=is_causal,
            dropout_p=dropout_p if training else 0.0, key=rng_key,
        )

    args = [query, key_, value]
    if attn_mask is not None:
        args.append(ensure_tensor(attn_mask))
    return apply(fn, *args, op_name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(
        query, key, value, None, dropout, causal, training
    )
    if return_softmax:
        return out, None
    return out, None


def _xla_varlen_attention(q, k, v, cu_q, cu_k, scale, causal,
                          dropout_p=0.0, key=None, window=None):
    """Segment-masked XLA reference for packed varlen attention (O(T^2)
    memory) — the numeric oracle for the Pallas kernel and the off-TPU /
    dropout path. Supports GQA and unequal q/kv lengths (bottom-right
    causal)."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    pos_q = jnp.arange(q.shape[0])
    pos_k = jnp.arange(k.shape[0])
    seg_q = jnp.searchsorted(cu_q[1:], pos_q, side="right")
    seg_k = jnp.searchsorted(cu_k[1:], pos_k, side="right")
    logits = jnp.einsum(
        "qhd,khd->hqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    mask = seg_q[:, None] == seg_k[None, :]
    if causal:
        # bottom-right alignment per segment (kv coordinates)
        lq = cu_q[seg_q + 1] - cu_q[seg_q]
        lk = cu_k[seg_q + 1] - cu_k[seg_q]
        rel_q = pos_q - cu_q[seg_q] + lk - lq
        rel_k = pos_k - cu_k[seg_k]
        mask = mask & (rel_q[:, None] >= rel_k[None, :])
        if window is not None:
            mask = mask & (rel_k[None, :] > rel_q[:, None] - window)
    logits = jnp.where(mask[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows (empty segments) produce nan; zero them
    probs = jnp.where(jnp.isnan(probs), 0.0, probs)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("hqk,khd->qhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        window_size=None, name=None):
    """Varlen flash attention: (total_tokens, H, D) + cumulative seqlens.

    On TPU this runs the blockwise Pallas varlen kernel
    (`ops/pallas/varlen_flash_attention.py`): per-q-block kv-block
    skipping from the segment bounds, O(sum len_i^2) compute and O(T)
    memory. Off-TPU it falls back to segment-masked XLA attention.
    """
    query, key_, value = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    cu_q = ensure_tensor(cu_seqlens_q)
    cu_k = ensure_tensor(cu_seqlens_k)
    # validated HERE so the Pallas and XLA backends agree (the XLA
    # band mask is nested under causal and would silently ignore it)
    if window_size is not None:
        if not causal:
            raise ValueError(
                "flash_attn_unpadded: window_size requires causal=True")
        if window_size < 1:
            raise ValueError(
                f"flash_attn_unpadded: window_size must be >= 1, got "
                f"{window_size}")

    flags = get_flags(["FLAGS_use_pallas_kernels", "FLAGS_pallas_force"])
    use_pallas = (
        flags["FLAGS_use_pallas_kernels"]
        and (dropout == 0.0 or not training)
        and (jax.default_backend() == "tpu" or flags["FLAGS_pallas_force"])
    )
    if use_pallas:
        out = apply(
            lambda q, k, v, cq, ck: _pallas_varlen_flash(
                q, k, v, cq, ck, causal=causal, sm_scale=scale,
                window_size=window_size),
            query, key_, value, cu_q, cu_k, op_name="flash_attn_unpadded",
        )
        return out, None

    rng_key = None
    if dropout > 0.0 and training:
        from ...core.random import next_key

        rng_key = next_key()
    out = apply(
        lambda q, k, v, cq, ck: _xla_varlen_attention(
            q, k, v, cq, ck, scale, causal,
            dropout_p=dropout if training else 0.0, key=rng_key,
            window=window_size),
        query, key_, value, cu_q, cu_k, op_name="flash_attn_unpadded",
    )
    return out, None


__all__ = [
    "scaled_dot_product_attention", "flash_attention", "flash_attn_unpadded",
    "sliding_window_attention",
]
