"""Chunk (prefill) attention over the LATENT paged pool — Pallas TPU
kernel: what ``nlp/deepseek_v3.py::DeepseekV3Attention.paged_chunk``
runs on a TPU where ``_latent_chunk_attn`` (the XLA loop the parity
tests compare with) runs elsewhere.

Query j of slot s attends pool positions < ``base_lens[s] + j + 1``:
the slot's cached rows and the chunk's own up to itself. A cached row is
``[c | rope(k_rope)]`` (R + Dr values); head h's key is ``[c W_uk_h |
k_rope]`` and its value ``c W_uv_h`` — the un-absorbed form, built a
tile at a time INSIDE the kernel and never stored. What XLA did with a
tile (write the (S, H, C, keys) f32 scores, read them for the max, for
the exp, re-lay ``p`` out for the value product, read and write the f32
accumulator) stays in VMEM here.

Mechanics. The slots' table entries are gathered once by XLA into
contiguous rows (S, keys, R + Dr): the pool's bytes for the live
context, a few hundredths of what the scores were. The grid is (slot,
group of G heads, key tile); a step up-projects its key tile for each
head of the group on the MXU — (BK, R) x (R, Dn | Dv), full tiles —
and folds it into that head's running (m, l, acc) for the WHOLE chunk,
so no up-projection is ever repeated for a second query tile; a slot's
rows are read H / G times (not H). ``base_lens`` rides in scalar
prefetch: a row's key tiles past ``base + C`` are neither fetched (the
index map re-points them at its last live tile, which elides the DMA)
nor computed, and inside the chunk a (query tile, key tile) pair wholly
above the diagonal is skipped, wholly below it unmasked (the interior /
boundary split of ``flash_attention._fwd_kernel``, whose online-softmax
body this kernel shares). ``q_rope . k_rope`` uses the ONE shared rope
key of the row: it is never broadcast to the heads.

Operands are the chunk's queries per head, any (S, keys, width) rows
and a per-row causal offset, so the dense GQA chunk attention
(``nlp/paged_attention.py::_paged_chunk_attn``: a group's heads share
one key head, nothing to up-project) can take the same grid later.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from ._utils import (
    head_axis as _head_axis, interpret_mode as _interpret_mode,
    per_shard as _per_shard, round_up as _round_up,
)
from .flash_attention import (
    NEG_INF, _mask_for_block, _online_softmax_fold, _run_full,
)

# heads a grid step attends, keys it up-projects, queries a fold takes
# (shape rules: no knob). G heads keep G x C x (Dv + 2 x 128) f32 of
# statistics in VMEM and divide the re-reads of a slot's rows by G
_HEADS, _BLOCK_K, _BLOCK_Q = 8, 512, 512
_VMEM_LIMIT = 64 << 20


def _heads_per_step(h, dr):
    """The largest divisor of ``h`` up to ``_HEADS`` whose rope queries
    fill whole lanes (a (C, G x Dr) block), else all heads."""
    for g in range(min(h, _HEADS), 0, -1):
        if h % g == 0 and (g * dr) % 128 == 0:
            return g
    return h


def supports(q_nope, q_rope, pool, w_kvb):
    """Whether the compiled (Mosaic) kernel takes these operands: a
    float pool, and per-head widths that are whole lanes (the
    interpreter, off-TPU, takes any)."""
    if not jnp.issubdtype(pool.dtype, jnp.floating):
        return False
    if _interpret_mode():
        return True
    dn, dr = q_nope.shape[-1], q_rope.shape[-1]
    r = pool.shape[-1] - dr
    dv = w_kvb.shape[-1] // q_nope.shape[2] - dn
    return (dn % 128 == 0 and dv % 128 == 0 and r % 128 == 0
            and pool.shape[1] % 16 == 0)


def _chunk_kernel(base_ref, qn_ref, qr_ref, rows_ref, w_ref, o_ref,
                  m_scr, l_scr, acc_scr, k_scr, v_scr, *, sm_scale, chunk,
                  block_q, block_k, heads):
    ki = pl.program_id(2)
    base = base_ref[pl.program_id(0)]
    kv_len = base + chunk
    rank = w_ref.shape[0]
    dn, dr, dv = (ref.shape[-1] // heads for ref in (qn_ref, qr_ref, o_ref))

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # each region below is straight-line code over the group's heads, so
    # that the compiler can run one head's products beside another's
    # softmax: the MXU and the vector units would otherwise take turns
    @pl.when(ki * block_k < kv_len)
    def _up_project():
        # the tile's keys and values per head, in the pool's dtype like
        # the XLA loop's: once for the whole chunk
        lat = rows_ref[0, :, :rank]                      # (BK, R)
        for h in range(heads):
            w0 = h * (dn + dv)
            k_scr[h] = jnp.dot(
                lat, w_ref[:, w0:w0 + dn],
                preferred_element_type=jnp.float32).astype(k_scr.dtype)
            v_scr[h] = jnp.dot(
                lat, w_ref[:, w0 + dn:w0 + dn + dv],
                preferred_element_type=jnp.float32).astype(v_scr.dtype)

    def fold(qi, masked):
        rows = pl.ds(qi * block_q, block_q)
        k_rope = rows_ref[0, :, rank:]                   # (BK, Dr)
        mask = _mask_for_block(qi, ki, block_q, block_k, True, base,
                               kv_len) if masked else None
        for h in range(heads):
            s = (jax.lax.dot_general(
                qn_ref[0, rows, h * dn:(h + 1) * dn], k_scr[h],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    qr_ref[0, rows, h * dr:(h + 1) * dr], k_rope,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            ) * sm_scale                                 # (BQ, BK) f32
            _online_softmax_fold(s, v_scr[h], m_scr.at[h, rows],
                                 l_scr.at[h, rows], acc_scr.at[h, rows],
                                 mask)

    for qi in range(qn_ref.shape[1] // block_q):
        run, full = _run_full(qi, ki, block_q, block_k, True, base, kv_len)
        pl.when(run & full)(functools.partial(fold, qi, False))
        pl.when(run & ~full)(functools.partial(fold, qi, True))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        for h in range(heads):
            o_ref[0, :, h * dv:(h + 1) * dv] = (
                acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
            ).astype(o_ref.dtype)


def latent_chunk_attention(q_nope, q_rope, pool, tables, base_lens, w_kvb,
                           sm_scale, block_q=None, block_k=None):
    """Chunk attention over the latent pool, keys and values
    up-projected a tile at a time in VMEM.

    Args:
        q_nope: (S, C, H, Dn); q_rope: (S, C, H, Dr), already rotated.
        pool: (num_blocks, block_size, R + Dr), a float pool; the
            chunk's own rows are already written.
        tables: (S, W) int32 pool block ids per slot, in order; entries
            past a slot's ``base + C`` are never read by a live query
            (any valid block id: padding rows and entries name block 0
            or the scratch block).
        base_lens: (S,) int32 cached tokens a slot has before the chunk.
        w_kvb: (R, H x (Dn + Dv)), ``kv_b_proj``'s weight as stored:
            head h's ``[W_uk_h | W_uv_h]`` at columns h x (Dn + Dv).
    Operands are taken in the pool's dtype, products accumulate in f32,
    the softmax is f32 with the -1e30 mask, ``p`` is cast to the pool's
    dtype before the value product: ``_latent_chunk_attn``'s precision.
    Returns (S, C, H, Dv) in the queries' dtype.
    """
    h = q_nope.shape[2]
    h_ax = _head_axis(h)
    q_spec = P(None, None, h_ax, None)
    return _per_shard(
        functools.partial(_latent_chunk_attention, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k),
        (q_spec, q_spec, P(), P(), P(), P(None, h_ax)), q_spec,
    )(q_nope, q_rope, pool, tables, base_lens, w_kvb)


def _latent_chunk_attention(q_nope, q_rope, pool, tables, base_lens, w_kvb,
                            *, sm_scale, block_q, block_k):
    s_, c, h, dn = q_nope.shape
    dr = q_rope.shape[-1]
    bs, width = pool.shape[1], pool.shape[-1]
    rank = width - dr
    dv = w_kvb.shape[-1] // h - dn
    ct = pool.dtype
    g = _heads_per_step(h, dr)

    bq = min(block_q or _BLOCK_Q, _round_up(c, 16))
    cp = _round_up(c, bq)
    # whole key tiles of whole blocks: the padding entries name block 0
    # and lie past every row's length, so the mask hides them
    w = tables.shape[1]
    per_tile = max(1, min(w, (block_k or _BLOCK_K) // bs))
    bk = per_tile * bs
    kv_steps = -(-w // per_tile)
    tables = jnp.pad(tables.astype(jnp.int32),
                     ((0, 0), (0, kv_steps * per_tile - w)))
    rows = pool[tables].reshape(s_, kv_steps * bk, width)

    def fold_heads(q):
        q = q.astype(ct).reshape(s_, c, -1)
        return jnp.pad(q, ((0, 0), (0, cp - c), (0, 0))) if cp > c else q

    def last_live(s, base_ref):
        return (base_ref[s] + c - 1) // bk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_, h // g, kv_steps),
        in_specs=[
            pl.BlockSpec((1, cp, g * dn), lambda s, hg, ki, b: (s, 0, hg)),
            pl.BlockSpec((1, cp, g * dr), lambda s, hg, ki, b: (s, 0, hg)),
            # a row's tiles past its own base + C re-point at its last
            # live one: consecutive equal indices elide the DMA
            pl.BlockSpec((1, bk, width), lambda s, hg, ki, b: (
                s, jnp.minimum(ki, last_live(s, b)), 0)),
            pl.BlockSpec((rank, g * (dn + dv)),
                         lambda s, hg, ki, b: (0, hg)),
        ],
        out_specs=pl.BlockSpec((1, cp, g * dv),
                               lambda s, hg, ki, b: (s, 0, hg)),
        scratch_shapes=[
            pltpu.VMEM((g, cp, 1), jnp.float32),
            pltpu.VMEM((g, cp, 1), jnp.float32),
            pltpu.VMEM((g, cp, dv), jnp.float32),
            pltpu.VMEM((g, bk, dn), ct),
            pltpu.VMEM((g, bk, dv), ct),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel, sm_scale=sm_scale, chunk=c, block_q=bq,
            block_k=bk, heads=g),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, cp, h * dv), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_mode(),
        name="chunk_attention",
    )(base_lens.astype(jnp.int32), fold_heads(q_nope), fold_heads(q_rope),
      rows, w_kvb.astype(ct))
    return out[:, :c].reshape(s_, c, h, dv)
