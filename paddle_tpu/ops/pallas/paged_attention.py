"""Paged (blocked) KV-cache decode attention — Pallas TPU kernel.

The reference's 2.6-era serving attention ``block_multihead_attention``
(paddle/incubate/nn/functional/block_multihead_attention.py + CUDA
kernels under paddle/fluid/operators/fused/ — unverified, SURVEY.md
§0/§2.5) keeps the KV cache as a POOL of fixed-size blocks shared by all
sequences, with a per-sequence block table — memory scales with live
tokens, not batch × max_seq.

TPU-native mechanics: the pool rides in HBM as (HK, num_blocks,
block_size, D); the per-sequence block tables and lengths ride in
scalar-prefetch SMEM, and the BlockSpec index map dereferences the table
directly — each grid step DMAs exactly one pool block, so the gather is
zero-copy (no jnp.take materialization of the cache). Query heads
sharing a KV head (the GQA group) form the rows of the score matmul, as
in the contiguous-cache decode kernel. Blocks past a sequence's length
re-point at pool block 0 (the DMA is elided) and are predicated off.
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
    head_axis as _head_axis, interpret_mode as _interpret_mode,
    per_shard as _per_shard,
)

NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, kscale_ref, vscale_ref, q_ref,
                  k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                  block_size, steps, group, has_scales):
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    length = lens_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * block_size < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)   # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)   # (BS, D)
        v = v_ref[0, 0].astype(jnp.float32)
        if has_scales:
            # int8 KV pools dequantize HERE, in VMEM — the cache stays
            # int8 in HBM (half the residency of a bf16 pool); static
            # flag so float pools keep the multiply-free hot loop
            k = k * kscale_ref[h]
            v = v * vscale_ref[h]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                           # (G, BS)
        pos = ki * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_size), 1
        )
        mask = pos < length
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

    @pl.when(ki == steps - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, seq_lens,
                           sm_scale=None, k_scale=None, v_scale=None):
    """One-step decode attention over a paged KV pool.

    Args:
        q: (B, H, D) or (B, 1, H, D) — the new token's query heads.
        k_pool, v_pool: (num_blocks, block_size, HK, D) — the shared
            block pool (paddle's cache layout, block-major). May be int8
            when per-head dequant scales are supplied.
        block_tables: (B, max_blocks) int32 — pool block ids per
            sequence, in order; entries past the sequence's length are
            ignored (any value).
        seq_lens: (B,) int32 — valid tokens per sequence (including the
            one being decoded).
        k_scale, v_scale: optional (HK,) f32 per-kv-head DEQUANT scales
            for int8 pools — applied inside the kernel so the int8 bytes
            are what rides HBM.
    Returns (B, H, D) (or (B, 1, H, D) matching q's rank), in the
    QUERY's dtype.
    """
    # kv-head groups are independent (a TP engine's pools are split
    # along that dim): one kernel per mp shard, slots replicated
    hk = k_pool.shape[2]
    h_ax = _head_axis(q.shape[-2], hk)
    q_spec = P(*([None] * (q.ndim - 2)), h_ax, None)
    pool_spec = P(None, None, h_ax, None)
    args = [q, k_pool, v_pool, block_tables, seq_lens]
    specs = [q_spec, pool_spec, pool_spec, P(None, None), P(None)]
    if k_scale is not None or v_scale is not None:
        one = jnp.ones((hk,), jnp.float32)
        args += [one if sc is None else
                 jnp.asarray(sc, jnp.float32).reshape(hk)
                 for sc in (k_scale, v_scale)]
        specs += [P(h_ax), P(h_ax)]
    return _per_shard(
        functools.partial(_paged_decode_attention, sm_scale=sm_scale),
        tuple(specs), q_spec)(*args)


def _paged_decode_attention(q, k_pool, v_pool, block_tables, seq_lens,
                            k_scale=None, v_scale=None, *, sm_scale):
    squeeze = False
    if q.ndim == 4:
        q = q[:, 0]
        squeeze = True
    b, h, d = q.shape
    num_blocks, block_size, hk = k_pool.shape[:3]
    if h % hk != 0:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({hk})")
    group = h // hk
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    steps = block_tables.shape[1]

    qg = q.reshape(b, hk, group, d)
    # (HK, NB, BS, D): head-major so one grid step pulls one (BS, D) tile
    kp = jnp.moveaxis(k_pool, 2, 0)
    vp = jnp.moveaxis(v_pool, 2, 0)

    lens = seq_lens.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    ks = (jnp.ones((hk,), jnp.float32) if k_scale is None
          else jnp.asarray(k_scale, jnp.float32).reshape(hk))
    vs = (jnp.ones((hk,), jnp.float32) if v_scale is None
          else jnp.asarray(v_scale, jnp.float32).reshape(hk))

    def pool_idx(b_, h_, ki, tables_ref, lens_ref, ks_ref, vs_ref):
        # dead step (past this sequence's blocks) → re-point at block 0;
        # the repeated DMA is elided and the body is predicated off
        live = ki * block_size < lens_ref[b_]
        blk = jax.lax.select(live, tables_ref[b_, ki], 0)
        return (h_, blk, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, hk, steps),
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda b_, h_, ki, t, ln, ks_, vs_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_size, d), pool_idx),
            pl.BlockSpec((1, 1, block_size, d), pool_idx),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, group, d),
            lambda b_, h_, ki, t, ln, ks_, vs_: (b_, h_, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, sm_scale=sm_scale, block_size=block_size,
            steps=steps, group=group,
            has_scales=k_scale is not None or v_scale is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, group, d), q.dtype),
        interpret=_interpret_mode(),
        name="paged_decode_attention",
    )(tables, lens, ks, vs, qg, kp, vp)
    out = out.reshape(b, h, d)
    return out[:, None] if squeeze else out


def paged_cache_write(k_pool, v_pool, k_new, v_new, block_tables, positions):
    """Write one new token's K/V per sequence into the pool.

    k_new/v_new: (B, HK, D); positions: (B,) int32 absolute token index
    (the block table must already map position // block_size).
    Returns the updated pools (functionally).
    """
    block_size = k_pool.shape[1]
    blk = jnp.take_along_axis(
        block_tables.astype(jnp.int32),
        (positions[:, None] // block_size).astype(jnp.int32), axis=1,
    )[:, 0]
    off = positions.astype(jnp.int32) % block_size
    k_pool = k_pool.at[blk, off].set(k_new)
    v_pool = v_pool.at[blk, off].set(v_new)
    return k_pool, v_pool
