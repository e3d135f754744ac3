"""Varlen (packed / unpadded) flash attention — Pallas TPU kernel.

Replaces the reference's varlen path through its vendored flash-attn
library (reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu
`FlashAttnUnpaddedKernel` + third_party/flashattn — unverified,
SURVEY.md §0/§2.5): sequences are packed back-to-back into one
(total_tokens, heads, head_dim) buffer with `cu_seqlens` prefix sums,
and attention never crosses sequence boundaries.

TPU-first design (splash-attention structure, not a CUDA port):
- Tile predicates are precomputed in XLA from cu_seqlens and fed to the
  kernel via scalar prefetch (SMEM): `run[qi, ki]` (segment ranges
  overlap, and for causal some aligned pair is on/below the diagonal)
  and `full[qi, ki]` (every pair valid → mask-free MXU fast path).
  Dead tiles skip their KV DMA entirely — the BlockSpec index map
  consults `run` and re-points at block 0 — so compute AND bandwidth
  scale with O(sum len_i^2), not O(T^2).
- Partial (boundary) tiles mask via per-token int32 segment ids and
  bottom-right-aligned relative positions, streamed in Mosaic-friendly
  layouts: q-side (T, 128) broadcast along lanes, kv-side (8, T)
  broadcast along sublanes (the same trick jax's own flash kernel uses
  for segment ids).
- Unequal q/kv lengths per sequence use bottom-right causal alignment
  via the relative positions (the dense kernel's convention).
- GQA/MQA: the shared KV head is read zero-copy through the BlockSpec
  index map; only the dk/dv kernel sees KV repeated per query head.

Forward + recompute backward (dq and dk/dv kernels) under
``jax.custom_vjp``; integer aux arrays get ``None`` cotangents.
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

NEG_INF = -1e30
LANES = 128       # minor-dim tile for the q-side aux arrays
SUBLANES = 8      # second-minor tile for the kv-side aux arrays
_Q_PAD_SEG = -1   # padding segment ids chosen so q-pad never equals
_K_PAD_SEG = -2   # k-pad (and neither equals a real id >= 0)
_REL_LO = -(2 ** 30)
_REL_HI = 2 ** 30


def _default_blocks(head_dim):
    """(1024, 1024) matches the dense kernel since round 5: keeping the
    matmul operands in their storage dtype (bf16) freed the VMEM the old
    f32 tile copies consumed, so the dkv backward now fits at 1024 with
    the segment/relative aux tiles (measured: fwd 1.47x, fwd+bwd 1.22x
    over the old 512 ceiling on the round-3 ragged-16k workload;
    (2048, 1024) still exceeds v5e's 16 MB scoped VMEM)."""
    if head_dim <= 128:
        return 1024, 1024
    return 256, 256


def _partial_mask(qs_ref, qr_ref, ks_ref, kr_ref, causal, block_k,
                  window=None):
    """(BQ, BK) validity mask for a boundary tile."""
    reps = block_k // LANES
    qs_t = jnp.tile(qs_ref[...], (1, reps))   # (BQ, BK)
    mask = qs_t == ks_ref[0:1, :]
    if causal:
        qr_t = jnp.tile(qr_ref[...], (1, reps))
        mask = mask & (qr_t >= kr_ref[0:1, :])
        if window is not None:
            # sliding-window band in per-segment relative coordinates
            mask = mask & (kr_ref[0:1, :] > qr_t - window)
    return mask


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(run_ref, full_ref, q_ref, k_ref, v_ref,
                qs_ref, qr_ref, ks_ref, kr_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                causal, sm_scale, block_k, kv_steps, window=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = run_ref[qi, ki] == 1
    full = full_ref[qi, ki] == 1

    def accumulate(s, mask):
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        # storage-dtype matmul inputs + f32 accumulation (round-5: an
        # .astype(f32) on the operands forces quarter-rate f32 MXU)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    def scores():
        return jax.lax.dot_general(
            q_ref[0], k_ref[0],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale

    @pl.when(run & full)
    def _interior():  # mask-free fast path
        accumulate(scores(), None)

    @pl.when(run & ~full)
    def _boundary():
        mask = _partial_mask(qs_ref, qr_ref, ks_ref, kr_ref, causal, block_k, window)
        accumulate(scores(), mask)

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)


def _varlen_fwd(q, k, v, qs, qr, ks, kr, run_map, full_map,
                causal, sm_scale, block_q, block_k, window=None):
    """q: (H, Tq, D); k/v: (HK, Tk, D); aux pre-padded to block multiples."""
    h, tq, d = q.shape
    hk, tk = k.shape[0], k.shape[1]
    group = h // hk
    q_steps = pl.cdiv(tq, block_q)
    kv_steps = pl.cdiv(tk, block_k)

    def kv_idx(h_, qi, ki, run_ref, full_ref):
        # dead tile → re-point at block 0: Mosaic elides the repeated DMA
        return (h_ // group, jax.lax.select(run_ref[qi, ki] == 1, ki, 0), 0)

    def kv_aux_idx(h_, qi, ki, run_ref, full_ref):
        live = (run_ref[qi, ki] == 1) & (full_ref[qi, ki] == 0)
        return (0, jax.lax.select(live, ki, 0))

    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale,
        block_k=block_k, kv_steps=kv_steps, window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, q_steps, kv_steps),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda h_, qi, ki, r, f: (h_, qi, 0)),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((block_q, LANES),
                             lambda h_, qi, ki, r, f: (qi, 0)),
                pl.BlockSpec((block_q, LANES),
                             lambda h_, qi, ki, r, f: (qi, 0)),
                pl.BlockSpec((SUBLANES, block_k), kv_aux_idx),
                pl.BlockSpec((SUBLANES, block_k), kv_aux_idx),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda h_, qi, ki, r, f: (h_, qi, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda h_, qi, ki, r, f: (h_, qi, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, tq, d), q.dtype),
            jax.ShapeDtypeStruct((h, tq, 1), jnp.float32),
        ],
        interpret=_interpret_mode(),
        name="varlen_flash_attention_fwd",
    )(run_map, full_map, q, k, v, qs, qr, ks, kr)
    return out, lse


# --------------------------------------------------------------------------
# backward: dq kernel
# --------------------------------------------------------------------------
def _bwd_dq_kernel(run_ref, full_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, qs_ref, qr_ref, ks_ref, kr_ref,
                   dq_ref, dq_scr, *, causal, sm_scale, block_k, kv_steps,
                   window=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = run_ref[qi, ki] == 1
    full = full_ref[qi, ki] == 1

    def body(mask):
        # storage-dtype matmul inputs + f32 accumulation (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        p = jnp.exp(s - lse_ref[0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0]) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    @pl.when(run & full)
    def _interior():
        body(None)

    @pl.when(run & ~full)
    def _boundary():
        body(_partial_mask(qs_ref, qr_ref, ks_ref, kr_ref, causal, block_k, window))

    @pl.when(ki == kv_steps - 1)
    def _store():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# --------------------------------------------------------------------------
# backward: dk/dv kernel (grid over kv blocks, scan q blocks)
# --------------------------------------------------------------------------
def _bwd_dkv_kernel(run_ref, full_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, qs_ref, qr_ref, ks_ref, kr_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *,
                    causal, sm_scale, block_k, q_steps, window=None):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = run_ref[qi, ki] == 1
    full = full_ref[qi, ki] == 1

    def body(mask):
        # storage-dtype matmul inputs + f32 accumulation (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        p = jnp.exp(s - lse_ref[0])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0]) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    @pl.when(run & full)
    def _interior():
        body(None)

    @pl.when(run & ~full)
    def _boundary():
        body(_partial_mask(qs_ref, qr_ref, ks_ref, kr_ref, causal, block_k, window))

    @pl.when(qi == q_steps - 1)
    def _store():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _varlen_bwd(causal, sm_scale, block_q, block_k, window, residuals, g):
    q, k, v, qs, qr, ks, kr, run_map, full_map, out, lse = residuals
    do = g[0] if isinstance(g, tuple) else g
    h, tq, d = q.shape
    hk, tk = k.shape[0], k.shape[1]
    group = h // hk
    q_steps = pl.cdiv(tq, block_q)
    kv_steps = pl.cdiv(tk, block_k)

    if group > 1:
        k_r = jnp.repeat(k, group, axis=0)
        v_r = jnp.repeat(v, group, axis=0)
    else:
        k_r, v_r = k, v

    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )

    common = dict(causal=causal, sm_scale=sm_scale, block_k=block_k,
                  window=window)

    def kv_idx(h_, qi, ki, run_ref, full_ref):
        return (h_ // group, jax.lax.select(run_ref[qi, ki] == 1, ki, 0), 0)

    def kv_aux_idx(h_, qi, ki, run_ref, full_ref):
        live = (run_ref[qi, ki] == 1) & (full_ref[qi, ki] == 0)
        return (0, jax.lax.select(live, ki, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, kv_steps=kv_steps, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, q_steps, kv_steps),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda h_, qi, ki, r, f: (h_, qi, 0)),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((1, block_k, d), kv_idx),
                pl.BlockSpec((1, block_q, d),
                             lambda h_, qi, ki, r, f: (h_, qi, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda h_, qi, ki, r, f: (h_, qi, 0)),
                pl.BlockSpec((1, block_q, 1),
                             lambda h_, qi, ki, r, f: (h_, qi, 0)),
                pl.BlockSpec((block_q, LANES),
                             lambda h_, qi, ki, r, f: (qi, 0)),
                pl.BlockSpec((block_q, LANES),
                             lambda h_, qi, ki, r, f: (qi, 0)),
                pl.BlockSpec((SUBLANES, block_k), kv_aux_idx),
                pl.BlockSpec((SUBLANES, block_k), kv_aux_idx),
            ],
            out_specs=pl.BlockSpec(
                (1, block_q, d), lambda h_, qi, ki, r, f: (h_, qi, 0)
            ),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((h, tq, d), q.dtype),
        interpret=_interpret_mode(),
        name="varlen_flash_attention_bwd_dq",
    )(run_map, full_map, q, k, v, do, lse, delta, qs, qr, ks, kr)

    # dkv: grid (h, ki, qi); dead tiles skip the q-side DMAs instead
    def q_idx(h_, ki, qi, run_ref, full_ref):
        return (h_, jax.lax.select(run_ref[qi, ki] == 1, qi, 0), 0)

    def q_aux_idx(h_, ki, qi, run_ref, full_ref):
        live = (run_ref[qi, ki] == 1) & (full_ref[qi, ki] == 0)
        return (jax.lax.select(live, qi, 0), 0)

    dk_r, dv_r = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_steps=q_steps, **common),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h, kv_steps, q_steps),
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_idx),
                pl.BlockSpec((1, block_k, d),
                             lambda h_, ki, qi, r, f: (h_, ki, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda h_, ki, qi, r, f: (h_, ki, 0)),
                pl.BlockSpec((1, block_q, d), q_idx),
                pl.BlockSpec((1, block_q, 1), q_idx),
                pl.BlockSpec((1, block_q, 1), q_idx),
                pl.BlockSpec((block_q, LANES), q_aux_idx),
                pl.BlockSpec((block_q, LANES), q_aux_idx),
                pl.BlockSpec((SUBLANES, block_k),
                             lambda h_, ki, qi, r, f: (0, ki)),
                pl.BlockSpec((SUBLANES, block_k),
                             lambda h_, ki, qi, r, f: (0, ki)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d),
                             lambda h_, ki, qi, r, f: (h_, ki, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda h_, ki, qi, r, f: (h_, ki, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((h, tk, d), k.dtype),
            jax.ShapeDtypeStruct((h, tk, d), v.dtype),
        ],
        interpret=_interpret_mode(),
        name="varlen_flash_attention_bwd_dkv",
    )(run_map, full_map, q, k_r, v_r, do, lse, delta, qs, qr, ks, kr)

    if group > 1:
        dk = dk_r.reshape(hk, group, tk, d).sum(axis=1).astype(k.dtype)
        dv = dv_r.reshape(hk, group, tk, d).sum(axis=1).astype(v.dtype)
    else:
        dk, dv = dk_r, dv_r
    return dq, dk, dv, None, None, None, None, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12, 13))
def _varlen_htd(q, k, v, qs, qr, ks, kr, run_map, full_map,
                causal, sm_scale, block_q, block_k, window=None):
    out, _ = _varlen_fwd(q, k, v, qs, qr, ks, kr, run_map, full_map,
                         causal, sm_scale, block_q, block_k, window)
    return out


def _fwd_rule(q, k, v, qs, qr, ks, kr, run_map, full_map,
              causal, sm_scale, block_q, block_k, window=None):
    out, lse = _varlen_fwd(q, k, v, qs, qr, ks, kr, run_map, full_map,
                           causal, sm_scale, block_q, block_k, window)
    return out, (q, k, v, qs, qr, ks, kr, run_map, full_map, out, lse)


def _bwd_rule(causal, sm_scale, block_q, block_k, window, residuals, g):
    return _varlen_bwd(causal, sm_scale, block_q, block_k, window,
                       residuals, g)


_varlen_htd.defvjp(_fwd_rule, _bwd_rule)


def _aux_arrays(cu, pad_total, pad_seg, pad_rel, cu_other=None):
    """Per-token segment id and relative position from a prefix-sum.

    For the q side pass ``cu_other=cu_seqlens_k``: relative positions are
    then expressed in kv coordinates with bottom-right alignment
    (``pos - start_q + len_k - len_q``), so ``rel_q >= rel_k`` is exactly
    the dense kernel's ``tril(k=sk-sq)`` convention per segment."""
    pos = jnp.arange(pad_total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], pos, side="right").astype(jnp.int32)
    n_seg = cu.shape[0] - 1
    seg_c = jnp.clip(seg, 0, n_seg - 1)
    start = cu[seg_c]
    rel = pos - start
    if cu_other is not None:
        l_own = cu[seg_c + 1] - start
        l_other = cu_other[seg_c + 1] - cu_other[seg_c]
        rel = rel + l_other - l_own
    valid = pos < cu[n_seg]
    seg = jnp.where(valid, seg, pad_seg)
    rel = jnp.where(valid, rel, pad_rel)
    return seg, rel


def _block_stats(x, steps, block):
    """Per-block (min, max) of a padded per-token int32 array."""
    xb = x.reshape(steps, block)
    return xb.min(axis=1), xb.max(axis=1)


def _tile_maps(seg_q, rel_q, seg_k, rel_k, bq, bk, causal, window=None):
    """(q_steps, kv_steps) int32 run/full predicates from per-token aux."""
    q_steps = seg_q.shape[0] // bq
    kv_steps = seg_k.shape[0] // bk
    qs_lo, qs_hi = _block_stats(seg_q, q_steps, bq)
    ks_lo, ks_hi = _block_stats(seg_k, kv_steps, bk)
    qr_lo, qr_hi = _block_stats(rel_q, q_steps, bq)
    kr_lo, kr_hi = _block_stats(rel_k, kv_steps, bk)

    run = (ks_lo[None, :] <= qs_hi[:, None]) & (
        ks_hi[None, :] >= qs_lo[:, None])
    # any real token at all (an all-pad q block has hi = _Q_PAD_SEG)
    run = run & (qs_hi[:, None] >= 0) & (ks_hi[None, :] >= 0)
    full = (
        (qs_lo[:, None] == qs_hi[:, None])
        & (ks_lo[None, :] == ks_hi[None, :])
        & (qs_lo[:, None] == ks_lo[None, :])
        & (qs_lo[:, None] >= 0)
    )
    if causal:
        run = run & (kr_lo[None, :] <= qr_hi[:, None])
        full = full & (qr_lo[:, None] >= kr_hi[None, :])
        if window is not None:
            # band lower edge (per-segment relative coords): some pair
            # within window → run; every pair within window → full
            run = run & (kr_hi[None, :] > qr_lo[:, None] - window)
            full = full & (kr_lo[None, :] > qr_hi[:, None] - window)
    return run.astype(jnp.int32), full.astype(jnp.int32)


def varlen_flash_attention(q, k, v, cu_seqlens_q, cu_seqlens_k,
                           causal=False, sm_scale=None,
                           block_q=None, block_k=None, window_size=None):
    """Packed varlen flash attention.

    q: (total_q, H, D); k/v: (total_k, HK, D); cu_seqlens_*: (B+1,) int32
    prefix sums. Tokens of sequence i occupy rows cu[i]:cu[i+1]; attention
    never crosses sequence boundaries. Returns (total_q, H, D).
    ``window_size`` (causal only) applies the Mistral sliding-window band
    PER SEGMENT — band-exterior tiles are dead tiles (no compute, no KV
    DMA), like cross-segment tiles.
    """
    if window_size is not None and not causal:
        raise ValueError("window_size requires causal=True")
    if window_size is not None and window_size < 1:
        raise ValueError(f"window_size must be >= 1, got {window_size}")
    tq, h, d = q.shape
    tk, hk = k.shape[0], k.shape[1]
    if h % hk != 0:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({hk})")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    if block_q is None or block_k is None:
        dbq, dbk = _default_blocks(d)
        block_q = block_q or dbq
        block_k = block_k or dbk
    win = None if window_size is None else int(window_size)
    # heads are independent (packed tokens are not): one kernel per mp
    # shard of the head dim
    spec = P(None, _head_axis(h, hk), None)
    return _per_shard(
        functools.partial(_varlen_thd, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, win=win),
        (spec, spec, spec, P(None), P(None)), spec,
    )(q, k, v, cu_seqlens_q, cu_seqlens_k)


def _varlen_thd(q, k, v, cu_seqlens_q, cu_seqlens_k, *, causal, sm_scale,
                block_q, block_k, win):
    tq, tk = q.shape[0], k.shape[0]
    # lane-aligned blocks; cap at the (padded) token counts
    bq = min(block_q, _round_up(tq, LANES))
    bk = min(block_k, _round_up(tk, LANES))
    pad_q = (-tq) % bq
    pad_k = (-tk) % bk

    cu_q = cu_seqlens_q.astype(jnp.int32)
    cu_k = cu_seqlens_k.astype(jnp.int32)
    seg_q, rel_q = _aux_arrays(cu_q, tq + pad_q, _Q_PAD_SEG, _REL_LO,
                               cu_other=cu_k)
    seg_k, rel_k = _aux_arrays(cu_k, tk + pad_k, _K_PAD_SEG, _REL_HI)
    run_map, full_map = _tile_maps(seg_q, rel_q, seg_k, rel_k, bq, bk,
                                   causal, win)

    qs = jax.lax.broadcast_in_dim(seg_q, (tq + pad_q, LANES), (0,))
    qr = jax.lax.broadcast_in_dim(rel_q, (tq + pad_q, LANES), (0,))
    ks = jax.lax.broadcast_in_dim(seg_k, (SUBLANES, tk + pad_k), (1,))
    kr = jax.lax.broadcast_in_dim(rel_k, (SUBLANES, tk + pad_k), (1,))

    qt = jnp.moveaxis(q, 1, 0)  # (H, Tq, D)
    kt = jnp.moveaxis(k, 1, 0)
    vt = jnp.moveaxis(v, 1, 0)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_k), (0, 0)))

    out = _varlen_htd(qt, kt, vt, qs, qr, ks, kr, run_map, full_map,
                      causal, sm_scale, bq, bk, win)
    if pad_q:
        out = out[:, :tq]
    return jnp.moveaxis(out, 0, 1)
