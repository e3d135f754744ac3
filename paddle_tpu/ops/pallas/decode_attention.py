"""KV-cache decode attention — Pallas TPU kernel.

The heart of the reference's ``fused_multi_transformer`` inference op
(paddle/fluid/operators/fused/fused_multi_transformer_op.cu — unverified,
SURVEY.md §0/§2.5): one query step attends over a pre-filled KV cache with
per-batch valid lengths.

Layout choices for the MXU: all query heads sharing one KV head (the GQA
group) are processed together as the rows of the score matmul, so a
7B-class decode (32 q heads / 8 kv heads → G=4) still issues (G, D) x
(D, BK) matmuls instead of degenerate single-row ones. Per-batch lengths
ride in scalar-prefetch SMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from ._utils import (
    data_axes as _data_axes, head_axis as _head_axis,
    interpret_mode as _interpret_mode, per_shard as _per_shard,
    round_up as _round_up,
)

DEFAULT_BLOCK_K = 256
NEG_INF = -1e30




def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale, block_k, kv_steps,
                   group):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    length = lens_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_k < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (G, BK)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_k), 1
        )
        mask = k_pos < length
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, seq_lens, sm_scale=None,
                     block_k=DEFAULT_BLOCK_K):
    """One-step decode attention over a KV cache.

    Args:
        q: (B, H, D) or (B, 1, H, D) — the new token's query heads.
        k_cache, v_cache: (B, S_max, HK, D) paddle cache layout. HK may be
            smaller than H (GQA/MQA) as long as H % HK == 0.
        seq_lens: (B,) int32 — valid cache entries per batch row
            (including the token being decoded, already written).
    Returns (B, H, D) (or (B, 1, H, D) matching q's rank).
    """
    # batch rows and kv-head groups are independent: one kernel per shard
    b_ax = _data_axes(q.shape[0])
    h_ax = _head_axis(q.shape[-2], k_cache.shape[2])
    q_spec = P(b_ax, *([None] * (q.ndim - 3)), h_ax, None)
    c_spec = P(b_ax, None, h_ax, None)
    return _per_shard(
        functools.partial(_decode_attention, sm_scale=sm_scale,
                          block_k=block_k),
        (q_spec, c_spec, c_spec, P(b_ax)), q_spec,
    )(q, k_cache, v_cache, seq_lens)


def _decode_attention(q, k_cache, v_cache, seq_lens, *, sm_scale, block_k):
    squeeze = False
    if q.ndim == 4:
        q = q[:, 0]
        squeeze = True
    b, h, d = q.shape
    s_max, hk = k_cache.shape[1], k_cache.shape[2]
    if h % hk != 0:
        raise ValueError(f"query heads ({h}) must be a multiple of kv heads ({hk})")
    group = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    # (B, HK, G, D) queries; (B, HK, S, D) caches
    qg = q.reshape(b, hk, group, d)
    kt = jnp.swapaxes(k_cache, 1, 2)
    vt = jnp.swapaxes(v_cache, 1, 2)
    block_k = min(block_k, ((s_max + 7) // 8) * 8)
    pad_k = (-s_max) % block_k
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    kv_steps = pl.cdiv(s_max + pad_k, block_k)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hk, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, group, d), lambda b_, h_, ki, lens: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, lens: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, lens: (b_, h_, ki, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d), lambda b_, h_, ki, lens: (b_, h_, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, sm_scale=sm_scale, block_k=block_k,
            kv_steps=kv_steps, group=group,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, group, d), q.dtype),
        interpret=_interpret_mode(),
        name="decode_attention",
    )(seq_lens.astype(jnp.int32), qg, kt, vt)
    out = out.reshape(b, h, d)
    return out[:, None] if squeeze else out
