"""Chunk (prefill) attention over the serving pools — the Pallas TPU
kernels of the mixed step, two bodies on one grid. On a TPU they are what
``nlp/deepseek_v3.py::DeepseekV3Attention.paged_chunk`` (the LATENT pool),
``nlp/paged_attention.py::_paged_chunk_attn`` (a K/V table) and
``ring_chunk_attn`` (a window layer's ring) run; elsewhere the XLA folds
the parity tests compare with (``_latent_chunk_attn``,
``_xla_paged_chunk_attn``, ``_xla_ring_chunk_attn``).

Query j of slot s attends pool positions < ``base_lens[s] + j + 1``:
the slot's cached rows and the chunk's own up to itself. What XLA did
with a tile (write the (S, H, C, keys) f32 scores, read them for the
max, for the exp, re-lay ``p`` out for the value product, read and write
the f32 accumulator) stays in VMEM here.

The grid is (slot, group of G heads, key tile); a step folds its key
tile into each head's running (m, l, acc) for the WHOLE chunk.
``base_lens`` rides in scalar prefetch: a row's key tiles past ``base +
C`` are neither fetched (the index map re-points them at its last live
tile, which elides the DMA) nor computed, and inside the chunk a (query
tile, key tile) pair wholly outside the band is skipped, wholly inside
it unmasked (the interior / boundary split of
``flash_attention._fwd_kernel``, whose online-softmax body these
kernels share).

**The latent pool** (``latent_chunk_attention``). A cached row is ``[c |
rope(k_rope)]`` (R + Dr values); head h's key is ``[c W_uk_h | k_rope]``
and its value ``c W_uv_h`` — the un-absorbed form, built a tile at a
time INSIDE the kernel and never stored. The slots' table entries are
gathered once by XLA into contiguous rows (S, keys, R + Dr): the pool's
bytes for the live context, a few hundredths of what the scores were. A
step up-projects its key tile for each head of the group on the MXU —
(BK, R) x (R, Dn | Dv), full tiles — so no up-projection is ever
repeated for a second query tile; a slot's rows are read H / G times
(not H). ``q_rope . k_rope`` uses the ONE shared rope key of the row: it
is never broadcast to the heads.

**Grouped-query K/V rows** (``paged_chunk_attention``,
``ring_chunk_attention``; one body, ``_gqa_chunk_kernel``). K and V rows
are (S, keys, HK x D), a KV head's D lanes side by side in a row; a
group is the G query heads of ONE KV head, which share the step's (BK,
D) key and value blocks: nothing is up-projected and nothing repeated,
and a slot's rows are read once. Beside the causal upper bound the body
takes a static lower bound, ``window`` (None = none): query j attends
``base + j - window < p <= base + j``. Keys are addressed by ABSOLUTE
position tile, so the body knows no operand: for a table's rows
(gathered once by XLA, in order) position tile t is row tile t; for a
ring as it is stored (position p at row ``p mod R``, no copy, no head
axis outside the rows) it is ring tile ``t mod (R / BK)``, whole and
contiguous because BK divides R. A ring row that holds ``p + R`` where
the body assumes ``p`` is one whose ``p`` lies below every query's
window; a row whose assumed position is past the last one written lies
above every query that counts (R >= window + C is ``ring_tokens``'
guarantee): the two bounds mask exactly what ``_ring_positions`` masks.
The first and last live position tile of a slot come from scalar
prefetch; tiles outside ``[base - window + 1, base + C - 1]`` are
neither fetched nor computed.
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


def _whole_key_tiles(tables, bs, block_k):
    """A block table (S, W) padded to whole key tiles of whole blocks, and
    the keys of one tile (at most ``block_k``, at most the table): the
    padding entries name block 0 and lie past every row's length, so the
    mask hides them."""
    w = tables.shape[1]
    per_tile = max(1, min(w, block_k // bs))
    return (jnp.pad(tables.astype(jnp.int32), ((0, 0), (0, -w % per_tile))),
            per_tile * bs)


def _fold_heads(q, dtype, cp):
    """(S, C, H, D) queries as rows (S, cp, H x D) in ``dtype``, a head's
    D lanes side by side, the chunk padded to ``cp`` queries."""
    s_, c = q.shape[:2]
    q = q.astype(dtype).reshape(s_, c, -1)
    return jnp.pad(q, ((0, 0), (0, cp - c), (0, 0))) if cp > c else q


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
    tables, bk = _whole_key_tiles(tables, bs, block_k or _BLOCK_K)
    kv_steps = tables.shape[1] * bs // bk
    rows = pool[tables].reshape(s_, kv_steps * bk, width)

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
    )(base_lens.astype(jnp.int32), _fold_heads(q_nope, ct, cp),
      _fold_heads(q_rope, ct, cp), rows, w_kvb.astype(ct))
    return out[:, :c].reshape(s_, c, h, dv)


# -- grouped-query K/V rows: a table's, or a window layer's ring -------------
# keys a grid step of the grouped-query kernel folds (a shape rule: no
# knob): with no up-projection beside them, 1,024 keys a step carry each
# head's (m, l, acc) through VMEM half as often as 512 do (a ring call at
# the window cell's shapes 3.8 ms against 4.8, the table's 11.4 against
# 17.1: PERF.md section 6, PR 36)
_GQA_BLOCK_K = 1024


def _key_tile(r):
    """A ring's key tile: the largest divisor of its ``r`` rows up to
    ``_GQA_BLOCK_K`` that is whole sublanes, so that a position tile lies
    whole and contiguous in the ring (any divisor for the interpreter's
    toy rings, which have none)."""
    divisors = [d for d in range(min(r, _GQA_BLOCK_K), 0, -1) if r % d == 0]
    return next((d for d in divisors if d % 16 == 0), divisors[0])


def supports_gqa(q, k_rows):
    """Whether the compiled (Mosaic) kernel takes these operands: float
    rows ``(..., HK x D)`` or pool arrays ``(..., HK, D)`` whose head
    width ``D = q.shape[-1]`` is whole lanes (the interpreter, off-TPU,
    takes any)."""
    if not jnp.issubdtype(k_rows.dtype, jnp.floating):
        return False
    return _interpret_mode() or q.shape[-1] % 128 == 0


def _first_tile(base, window, block_k):
    """The lowest position tile a row's queries see: that of ``base -
    window + 1``, query 0's lower bound (tile 0 without a window)."""
    if window is None:
        return 0
    return jnp.maximum(base - window + 1, 0) // block_k


def _gqa_chunk_kernel(base_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, sm_scale, block_q, block_k,
                      group, window):
    slot = pl.program_id(0)
    base, kv_len = base_ref[slot], len_ref[slot]
    # the ABSOLUTE position tile this step holds: which rows of which
    # operand that is, only the index map knows
    ti = _first_tile(base, window, block_k) + pl.program_id(2)
    d = k_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # the group's heads in a LOOP, not unrolled as `_chunk_kernel`'s are:
    # straight-line code over 8 heads ran 1.9 x slower here (9.1 against
    # 4.8 ms a ring call at the window cell's shapes) and took Mosaic 5 x
    # as long to compile (PERF.md section 6, PR 36)
    def fold(qi, masked):
        rows = pl.ds(qi * block_q, block_q)
        mask = _mask_for_block(qi, ti, block_q, block_k, True, base,
                               kv_len, window) if masked else None

        def head(h, carry):
            s = jax.lax.dot_general(
                q_ref[0, rows, pl.ds(pl.multiple_of(h * d, d), d)],
                k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            _online_softmax_fold(s, v_ref[0], m_scr.at[h, rows],
                                 l_scr.at[h, rows], acc_scr.at[h, rows],
                                 mask)
            return carry

        jax.lax.fori_loop(0, group, head, 0)

    for qi in range(q_ref.shape[1] // block_q):
        run, full = _run_full(qi, ti, block_q, block_k, True, base, kv_len,
                              window)
        pl.when(run & full)(functools.partial(fold, qi, False))
        pl.when(run & ~full)(functools.partial(fold, qi, True))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finalize():
        for h in range(group):
            o_ref[0, :, h * d:(h + 1) * d] = (
                acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)
            ).astype(o_ref.dtype)


def paged_chunk_attention(q, kp, vp, tables, base_lens, sm_scale=None,
                          block_q=None, block_k=None):
    """Chunk attention over a K/V table: query j of slot s attends pool
    positions ``<= base_lens[s] + j``.

    Args:
        q: (S, C, H, D), already rotated.
        kp, vp: (num_blocks, block_size, HK, D), float pools; the chunk's
            own rows are already written.
        tables: (S, W) int32 pool block ids per slot, in order; entries
            past a slot's ``base + C`` are never read by a live query.
        base_lens: (S,) int32 cached tokens a slot has before the chunk.
        sm_scale: multiplies the scores (default ``1 / sqrt(D)``).
    The slots' table entries are gathered once by XLA into rows (S,
    keys, HK x D). Precision is ``_xla_paged_chunk_attn``'s: operands in
    the pool's dtype, f32 accumulation, an f32 softmax with the -1e30
    mask, ``p`` cast to the values' dtype. Returns (S, C, H, D) in the
    queries' dtype.
    """
    h_ax = _head_axis(q.shape[2], kp.shape[2])
    q_spec = P(None, None, h_ax, None)
    return _per_shard(
        functools.partial(_paged_chunk_attention, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k),
        (q_spec, q_spec, q_spec, P(), P()), q_spec,
    )(q, kp, vp, tables, base_lens)


def _paged_chunk_attention(q, kp, vp, tables, base_lens, *, sm_scale,
                           block_q, block_k):
    s_, c = q.shape[:2]
    tables, bk = _whole_key_tiles(tables, kp.shape[1],
                                  block_k or _GQA_BLOCK_K)
    k_rows, v_rows = (pool[tables].reshape(s_, -1, math.prod(pool.shape[2:]))
                      for pool in (kp, vp))
    base_lens = base_lens.astype(jnp.int32)
    return _gqa_chunk_attention(
        q, k_rows, v_rows, base_lens, base_lens + c, sm_scale=sm_scale,
        window=None, ring=False, block_q=block_q, block_k=bk)


def ring_chunk_attention(q, ring_k, ring_v, base_lens, counts, window,
                         sm_scale=None, block_q=None, block_k=None):
    """Chunk attention over a window layer's ring AS IT IS STORED: query
    j of slot s attends positions ``base + j - window < p <= base + j``
    among those written, ``p < base + counts[s]``.

    Args:
        q: (S, C, H, D), already rotated.
        ring_k, ring_v: (slots, R, HK x D), position p at row ``p mod
            R``, the chunk's own rows already written; R >= window + C.
        base_lens, counts: (S,) int32 cached tokens before the chunk and
            valid positions of it.
        window: static int > 0.
    ``block_k`` must divide R. Precision and result as
    :func:`paged_chunk_attention`.
    """
    hk = ring_k.shape[2] // q.shape[3]
    h_ax = _head_axis(q.shape[2], hk)
    q_spec, ring_spec = P(None, None, h_ax, None), P(None, None, h_ax)
    base_lens = base_lens.astype(jnp.int32)
    return _per_shard(
        functools.partial(
            _gqa_chunk_attention, sm_scale=sm_scale, window=int(window),
            ring=True, block_q=block_q,
            block_k=block_k or _key_tile(ring_k.shape[1])),
        (q_spec, ring_spec, ring_spec, P(), P()), q_spec,
    )(q, ring_k, ring_v, base_lens, base_lens + counts.astype(jnp.int32))


def _gqa_chunk_attention(q, k_rows, v_rows, base_lens, kv_lens, *, sm_scale,
                         window, ring, block_q, block_k):
    """The one call behind both entry points: ``k_rows`` / ``v_rows``
    (S, keys, HK x D) hold position tile t at row tile t, or, a
    ``ring``, at row tile ``t mod (keys / block_k)``; ``kv_lens`` (S,)
    bounds what is written, ``window`` (None = none) what a query sees
    below itself."""
    s_, c, h, d = q.shape
    keys, bk = k_rows.shape[1], block_k
    hk = k_rows.shape[2] // d
    g = h // hk
    assert keys % bk == 0, (keys, bk)   # a position tile is a row tile
    row_tiles = keys // bk
    # position tiles a slot may touch: its band's, or the whole table's
    kv_steps = row_tiles if window is None else (window + c - 2) // bk + 2
    ct = jnp.promote_types(q.dtype, k_rows.dtype)
    bq = min(block_q or _BLOCK_Q, _round_up(c, 16))
    cp = _round_up(c, bq)

    def key_tile(s, hg, ki, base_ref, len_ref):
        # a slot's tiles past its last written position re-point at the
        # last live one: consecutive equal indices elide the DMA
        lo = _first_tile(base_ref[s], window, bk)
        ti = jnp.minimum(lo + ki, jnp.maximum((len_ref[s] - 1) // bk, lo))
        return s, ti % row_tiles if ring else ti, hg

    def heads(s, hg, ki, base_ref, len_ref):
        return s, 0, hg

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, hk, kv_steps),
        in_specs=[
            pl.BlockSpec((1, cp, g * d), heads),
            pl.BlockSpec((1, bk, d), key_tile),
            pl.BlockSpec((1, bk, d), key_tile),
        ],
        out_specs=pl.BlockSpec((1, cp, g * d), heads),
        scratch_shapes=[
            pltpu.VMEM((g, cp, 1), jnp.float32),
            pltpu.VMEM((g, cp, 1), jnp.float32),
            pltpu.VMEM((g, cp, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _gqa_chunk_kernel,
            sm_scale=1.0 / math.sqrt(d) if sm_scale is None else sm_scale,
            block_q=bq, block_k=bk, group=g, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_, cp, h * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_mode(),
        name="gqa_chunk_attention",
    )(base_lens, kv_lens, _fold_heads(q, ct, cp), k_rows.astype(ct),
      v_rows.astype(ct))
    return out[:, :c].reshape(s_, c, h, d)
