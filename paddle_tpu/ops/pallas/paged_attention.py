"""Paged (blocked) KV-cache decode attention — Pallas TPU kernel.

The reference's 2.6-era serving attention ``block_multihead_attention``
(paddle/incubate/nn/functional/block_multihead_attention.py + CUDA
kernels under paddle/fluid/operators/fused/ — unverified, SURVEY.md
§0/§2.5) keeps the KV cache as a POOL of fixed-size blocks shared by all
sequences, with a per-sequence block table — memory scales with live
tokens, not batch × max_seq.

TPU-native mechanics: the pool stays in HBM exactly as it is stored,
(num_blocks, block_size, HK, D), and is never relaid out: the kernel
sees it as (num_blocks, block_size * HK, D), which in the stored tiling
is the same bytes (a bitcast — ``tests/test_tpu_aot_compile.py`` holds
the compiled program to it; (num_blocks, block_size, HK * D) would be
a physical copy of the whole pool). The per-sequence block tables and
lengths ride in scalar-prefetch SMEM. One grid step is one sequence:
it walks the LIVE entries of its table only, a chunk of blocks at a
time — one DMA a block brings its rows for every kv head (contiguous
in HBM), all of a chunk's DMAs in flight at once, the next chunk's (the
next sequence's first, at a row's end) started before this one is
waited for. Table entries past a sequence's length are never read. A
chunk is attended in ONE pair of products: its (rows x heads, D) tile
against all query heads, the scores of a query head against another kv
head's rows masked off with the positions past the length (the
products run on the MXU at the rate K and V stream through it whatever
the number of query rows, so the masked part costs nothing and the
kv heads need no loop and no strided read).

The LATENT pool (``nlp/deepseek_v3.py``: one row ``[c | rope(k_rope)]``
a token for all heads, stored (num_blocks, block_size, R + Dr)) has its
own entry, ``latent_decode_attention``: the same online softmax (the
fold ``chunk_attention`` shares, ``flash_attention._online_softmax_fold``),
one kv head and NO value pool — a row is head h's key against ``[q_lat_h
| q_rope_h]`` in ONE product, and its first R lanes are the value, out
of the SAME buffer: one DMA a block. Its blocks arrive another way:
R + Dr = 576 is not a whole number of 128-lane tiles, and Mosaic slices
an HBM array for a hand-made DMA only along whole tiles ("Slice shape
along dimension 2 must be aligned to tiling (128), but is 576"), while a
BlockSpec may take a dimension whole. So a grid step there is (sequence,
chunk): the chunk's blocks are as many BlockSpecs over the stored pool,
each indexed through a table of fetches in scalar prefetch
(``_latent_fetches``), all fetched while the step before computes; an
entry past a sequence's length names the block its BlockSpec fetched
last, which elides the copy.
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
from .flash_attention import _online_softmax_fold

NEG_INF = -1e30

# a chunk holds this many K (and V) rows — block_size * HK a block — or
# one block if a block has more: what one pair of products attends, and
# what is in flight while it does (a shape rule: no knob)
_CHUNK_ROWS = 1024


def _paged_kernel(tables_ref, lens_ref, kscale_ref, vscale_ref, q_ref,
                  k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, state, *, sm_scale,
                  block_size, kv_heads, group, chunk, has_scales):
    b = pl.program_id(0)
    rows = pl.num_programs(0)
    steps = tables_ref.shape[1]
    heads = kv_heads * group
    d = q_ref.shape[-1]
    width = chunk * block_size * kv_heads      # K rows of one chunk

    def live_blocks(row):
        return jnp.minimum(pl.cdiv(lens_ref[row], block_size), steps)

    length = lens_ref[b]
    n = live_blocks(b)
    chunks = pl.cdiv(n, chunk)
    nxt = jnp.minimum(b + 1, rows - 1)
    next_live = (b + 1 < rows) & (lens_ref[nxt] > 0)

    @pl.when(b == 0)
    def _reset():
        # state: the buffer half the next chunk lands in, and whether
        # the previous row already started this row's first chunk. The
        # halves start as zeros: a chunk's unused tail then only ever
        # holds zeros or older pool rows, which the mask multiplies by 0
        state[0] = 0
        state[1] = 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def block_copies(blk, half, t):
        return (pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[half, t],
                                      sem.at[0, half]),
                pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[half, t],
                                      sem.at[1, half]))

    def start_chunk(row, j, half):
        def start(t, _):
            for copy in block_copies(tables_ref[row, j * chunk + t],
                                     half, t):
                copy.start()
            return 0

        jax.lax.fori_loop(
            0, jnp.minimum(chunk, live_blocks(row) - j * chunk), start, 0)

    def wait_chunk(count, half):
        def wait(t, _):
            # a wait only needs the copy's shape: any block stands in
            for copy in block_copies(0, half, t):
                copy.wait()
            return 0

        jax.lax.fori_loop(0, count, wait, 0)

    @pl.when(n > 0)
    def _row():
        half0 = state[0]

        @pl.when(state[1] == 0)
        def _first():
            start_chunk(b, 0, half0)

        q = q_ref[0].astype(jnp.float32)                    # (H, D)
        # column c of a chunk's scores is K row c: token c // HK of the
        # chunk, kv head c % HK; query head r belongs to kv head r // G
        col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        col_head, tok = col % kv_heads, col // kv_heads
        row_head = jax.lax.broadcasted_iota(
            jnp.int32, (heads, 1), 0) // group
        own = col_head == row_head                          # (H, width)
        score_scale = sm_scale
        if has_scales:
            # int8 KV pools dequantize HERE, in VMEM — the cache stays
            # int8 in HBM (half the residency of a bf16 pool); static
            # flag so float pools keep the multiply-free hot loop. A
            # head's K scale multiplies its score columns, its V scale
            # its output rows (once, after the last chunk)
            ksc = jnp.zeros((1, width), jnp.float32)
            vsc = jnp.zeros((heads, 1), jnp.float32)
            for h in range(kv_heads):
                ksc = jnp.where(col_head == h, kscale_ref[h], ksc)
                vsc = jnp.where(row_head == h, vscale_ref[h], vsc)
            score_scale = sm_scale * ksc

        def attend(j, carry):
            m_prev, l_prev, acc = carry
            half = (half0 + j) % 2

            # the other half is free: fill it while this one is used
            @pl.when(j + 1 < chunks)
            def _next_chunk():
                start_chunk(b, j + 1, 1 - half)

            @pl.when((j + 1 == chunks) & next_live)
            def _next_row():
                start_chunk(nxt, 0, 1 - half)

            wait_chunk(jnp.minimum(chunk, n - j * chunk), half)
            k = kbuf[half].astype(jnp.float32).reshape(width, d)
            v = vbuf[half].astype(jnp.float32).reshape(width, d)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * score_scale                                 # (H, width)
            mask = own & (j * (chunk * block_size) + tok < length)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc * alpha + pv

        _, l, acc = jax.lax.fori_loop(0, chunks, attend, (
            jnp.full((heads, 1), NEG_INF, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, d), jnp.float32)))
        if has_scales:
            acc = acc * vsc
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        state[0] = (half0 + chunks) % 2
        state[1] = next_live.astype(jnp.int32)

    @pl.when(n == 0)
    def _empty():
        o_ref[0] = jnp.zeros_like(o_ref[0])


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
    chunk = max(1, min(steps, _CHUNK_ROWS // (block_size * hk)))

    lens = seq_lens.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    ks = (jnp.ones((hk,), jnp.float32) if k_scale is None
          else jnp.asarray(k_scale, jnp.float32).reshape(hk))
    vs = (jnp.ones((hk,), jnp.float32) if v_scale is None
          else jnp.asarray(v_scale, jnp.float32).reshape(hk))

    def slot_idx(b_, tables_ref, lens_ref, ks_ref, vs_ref):
        return (b_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), slot_idx),
            # the pools stay in HBM; the kernel DMAs the live blocks
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d), slot_idx),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, block_size * hk, d), k_pool.dtype),
            pltpu.VMEM((2, chunk, block_size * hk, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, sm_scale=sm_scale, block_size=block_size,
            kv_heads=hk, group=group, chunk=chunk,
            has_scales=k_scale is not None or v_scale is not None,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # rows run in order: each starts the next one's first chunk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret_mode(),
        name="paged_decode_attention",
    )(tables, lens, ks, vs, q,
      # the stored pool seen as (NB, BS * HK, D): a bitcast, no relayout
      k_pool.reshape(num_blocks, block_size * hk, d),
      v_pool.reshape(num_blocks, block_size * hk, d))
    return out[:, None] if squeeze else out


def _latent_kernel(fetch_ref, lens_ref, q_ref, *refs, sm_scale, block_size,
                   chunk, v_width):
    blocks = refs[:chunk]
    o_ref, m_scr, l_scr, acc_scr = refs[chunk:]
    j = pl.program_id(1)
    length = lens_ref[pl.program_id(0)]
    width = chunk * block_size                 # rows of one chunk

    @pl.when(j == 0)
    def _reset():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * width < length)
    def _attend():
        # the chunk's rows, keys AND values: a dead entry's rows are an
        # older fetch of the pool, which the mask multiplies by 0
        k = jnp.concatenate([blk[0] for blk in blocks], axis=0)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                        # (H, width)
        tok = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        _online_softmax_fold(s, k[:, :v_width], m_scr, l_scr, acc_scr,
                             j * width + tok < length)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype)


def supports_latent(pool, v_width):
    """Whether the compiled (Mosaic) kernel takes this latent pool: a
    float pool, values of whole lanes and blocks of whole sublane tiles
    (the interpreter, off-TPU, takes any)."""
    if not jnp.issubdtype(pool.dtype, jnp.floating):
        return False
    if _interpret_mode():
        return True
    return v_width % 128 == 0 and pool.shape[1] % 16 == 0


def latent_decode_attention(q, pool, block_tables, seq_lens, sm_scale,
                            v_width):
    """One-step decode attention over a LATENT paged pool, absorbed.

    Args:
        q: (B, H, D) — per head ``[q_lat | q_rope]``: the query carried
            into latent space beside its rotated rope part.
        pool: (num_blocks, block_size, D), a float pool, as stored: one
            row ``[c | rope(k_rope)]`` a token for all heads. A row is
            every head's key; its first ``v_width`` lanes are its value.
        block_tables: (B, max_blocks) int32; entries past a sequence's
            length are never read (any value).
        seq_lens: (B,) int32 valid tokens (the one being decoded too);
            a row of length 0 comes out as zeros.
    Operands are taken in the pool's dtype, products accumulate in
    float32, the softmax is float32 with the -1e30 mask, ``p`` is cast to
    the pool's dtype before the value product:
    ``deepseek_v3._latent_decode_attn``'s precision. Returns the context
    in latent space, (B, H, v_width) float32.
    """
    # heads are independent, the pool has none: it is replicated
    q_spec = P(None, _head_axis(q.shape[1]), None)
    return _per_shard(
        functools.partial(_latent_decode_attention, sm_scale=sm_scale,
                          v_width=v_width),
        (q_spec, P(), P(), P()), q_spec,
    )(q, pool, block_tables, seq_lens)


def _latent_fetches(block_tables, seq_lens, block_size, chunk):
    """(B, grid steps x chunk) int32: the pool block each of a grid
    step's BlockSpecs fetches. A position past the sequence's last block
    names the block its BlockSpec fetched last (an equal index elides
    the DMA), a sequence of length 0 block 0: no table entry past a
    length is ever read."""
    steps = block_tables.shape[1]
    n = jnp.minimum(-(-seq_lens // block_size), steps)[:, None]
    pos = jnp.arange(-(-steps // chunk) * chunk, dtype=jnp.int32)[None, :]
    i = pos % chunk
    last = jnp.maximum(n - 1 - i, 0) // chunk * chunk + i
    pos = jnp.where(pos < n, pos, jnp.where(i < n, last, 0))
    return jnp.where(n > 0, jnp.take_along_axis(block_tables, pos, axis=1),
                     0)


def _latent_decode_attention(q, pool, block_tables, seq_lens, *, sm_scale,
                             v_width):
    b, h, d = q.shape
    block_size = pool.shape[1]
    steps = block_tables.shape[1]
    chunk = max(1, min(steps, _CHUNK_ROWS // block_size))
    lens = seq_lens.astype(jnp.int32)
    fetches = _latent_fetches(block_tables.astype(jnp.int32), lens,
                              block_size, chunk)

    def block_idx(i):
        return lambda b_, j, fetch_ref, lens_ref: (
            fetch_ref[b_, j * chunk + i], 0, 0)

    def slot_idx(b_, j, fetch_ref, lens_ref):
        return (b_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, fetches.shape[1] // chunk),
        in_specs=[
            pl.BlockSpec((1, h, d), slot_idx),
            # the stored pool, once a block of the chunk
            *[pl.BlockSpec((1, block_size, d), block_idx(i))
              for i in range(chunk)],
        ],
        out_specs=pl.BlockSpec((1, h, v_width), slot_idx),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, v_width), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _latent_kernel, sm_scale=sm_scale, block_size=block_size,
            chunk=chunk, v_width=v_width),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, v_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret_mode(),
        name="latent_decode_attention",
    )(fetches, lens, q.astype(pool.dtype), *[pool] * chunk)


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
