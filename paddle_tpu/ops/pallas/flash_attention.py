"""Flash attention — the Pallas TPU kernel replacing the reference's
vendored flash-attn CUDA library (reference:
paddle/phi/kernels/gpu/flash_attn_kernel.cu + third_party/flashattn —
unverified, SURVEY.md §0/§2.5).

Blockwise online-softmax forward + recompute backward (dq and dk/dv
kernels), wrapped in jax.custom_vjp. Public layout is paddle's
(batch, seq, heads, head_dim); kernels run (batch, heads, seq, head_dim).

Notes on TPU legality (Mosaic lowering):
- LSE is carried as (B, H, S, 1): a (1, 1, block_q, 1) block has its last
  dim equal to the array dim (1) and second-to-last divisible by 8, which
  lowers; a (1, 1, block_q) block does not (second-to-last dim 1).
- Causal masking is bottom-right aligned (`q_pos + (sk - sq) >= k_pos`),
  matching paddle / the XLA fallback's `tril(k=sk-sq)` when seq_q != seq_k.
- Ragged sequence lengths are handled by padding to block multiples and
  masking `k_pos >= sk` inside the kernel; padded query rows are sliced
  off on exit.
- GQA/MQA: forward and dq index the shared KV head via the BlockSpec index
  map (no materialisation); only the dk/dv kernel sees KV repeated per
  query head, with the per-group sum applied after.
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

NEG_INF = -1e30


def _default_blocks(head_dim):
    """Measured on v5e: large blocks amortize the per-grid-step overhead —
    (1024, 1024) is ~9x faster than (128, 128) for d=64 fwd+bwd, and the
    round-3 min-of-3 sweep confirmed it also wins at d=128 (1.41 ms vs
    1.69 ms at (512, 512) for S=2048 fwd+bwd). Above d=128 drop to
    (256, 256) to stay within VMEM."""
    if head_dim <= 128:
        return 1024, 1024
    return 256, 256




def _run_full(qi, ki, block_q, block_k, causal, causal_offset, kv_len,
              window=None):
    """(run, full) tile validity: ``run`` = the tile contributes at all
    (not past the kv length / not entirely outside the causal band);
    ``full`` = every (q, k) pair in the tile is valid, i.e. exactly the
    condition under which _mask_for_block is all-true — interior tiles
    skip the mask build. Shared by fwd/dq/dkv so the boundary math can
    never desynchronize between forward and backward. ``window`` (with
    causal) restricts each query to the last ``window`` keys — tiles
    entirely BELOW the band are skipped too, making long-sequence
    sliding-window cost O(S * window)."""
    run = ki * block_k < kv_len
    full = (ki + 1) * block_k <= kv_len
    if causal:
        run = run & (ki * block_k <= (qi + 1) * block_q - 1 + causal_offset)
        full = full & (
            (ki + 1) * block_k - 1 <= qi * block_q + causal_offset)
        if window is not None:
            # band lower edge: k_pos >= q_pos + causal_offset - window + 1
            run = run & ((ki + 1) * block_k - 1
                         >= qi * block_q + causal_offset - window + 1)
            full = full & (
                ki * block_k
                >= (qi + 1) * block_q - 1 + causal_offset - window + 1)
    return run, full


def _kv_band_clamp(block_q, block_k, causal, causal_offset, window,
                   kv_steps):
    """Index-map clamp: re-point a dead kv tile at the nearest LIVE tile
    for its q row — consecutive repeated indices elide the DMA (the
    paged kernel's dead-step trick), so causal upper-triangle tiles and
    window below-band tiles cost no HBM traffic, not just no compute."""
    import jax.numpy as jnp

    def clamp(qi, ki):
        if not causal:
            return ki
        hi = jnp.minimum(kv_steps - 1,
                         ((qi + 1) * block_q - 1 + causal_offset)
                         // block_k)
        lo = 0
        if window is not None:
            lo = jnp.maximum(
                0, (qi * block_q + causal_offset - window + 1) // block_k)
        return jnp.clip(ki, lo, hi)

    return clamp


def _q_band_clamp(block_q, block_k, causal, causal_offset, window, q_steps):
    """Transpose of _kv_band_clamp for the dkv kernel's q-side fetches."""
    import jax.numpy as jnp

    def clamp(ki, qi):
        if not causal:
            return qi
        lo = jnp.maximum(0, (ki * block_k - causal_offset) // block_q)
        hi = q_steps - 1
        if window is not None:
            hi = jnp.minimum(
                q_steps - 1,
                ((ki + 1) * block_k - 1 + window - 1 - causal_offset)
                // block_q)
        return jnp.clip(qi, lo, hi)

    return clamp


def _mask_for_block(qi, ki, block_q, block_k, causal, causal_offset, kv_len,
                    window=None):
    """Boolean validity mask (BQ, BK) for one (q-block, kv-block) tile."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < kv_len
    if causal:
        mask = mask & (q_pos + causal_offset >= k_pos)
        if window is not None:
            mask = mask & (k_pos >= q_pos + causal_offset - window + 1)
    return mask


def _online_softmax_fold(s, v, m_ref, l_ref, acc_ref, mask=None):
    """Fold one (BQ, BK) tile of f32 scores ``s`` and its values ``v``
    (BK, Dv) into the running statistics ``m_ref`` / ``l_ref`` (BQ, 1)
    and ``acc_ref`` (BQ, Dv), all f32 refs: the online-softmax body of
    every forward attention kernel here. ``mask`` (BQ, BK) marks the
    valid pairs of a boundary tile; an interior tile passes None and
    builds none. Matmul INPUTS stay in the storage dtype (bf16 on TPU)
    with f32 ACCUMULATION via preferred_element_type: an .astype(f32)
    before a dot forces quarter-rate f32 MXU passes, so ``p`` is cast to
    the values' dtype and the caller hands ``s`` from such a product."""
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    if mask is not None:
        # fully-masked rows keep m=NEG_INF; mask p explicitly so
        # exp(NEG_INF - NEG_INF) = 1 cannot leak in
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, causal, causal_offset, kv_len,
                sm_scale, block_q, block_k, kv_steps, window=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # interior (fully-valid) tiles skip the mask build entirely — the
    # iota/compare/where work on a (BQ, BK) tile is pure VPU cost and
    # dominates diagonal-heavy causal grids (round-5 fix, mirroring the
    # varlen kernel's run/full split)
    run, full = _run_full(qi, ki, block_q, block_k, causal, causal_offset,
                          kv_len, window)

    def _accumulate(masked):
        q = q_ref[0, 0]  # (BQ, D)
        k = k_ref[0, 0]  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # (BQ, BK) f32
        mask = _mask_for_block(qi, ki, block_q, block_k, causal,
                               causal_offset, kv_len, window) \
            if masked else None
        _online_softmax_fold(s, v_ref[0, 0], m_scr, l_scr, acc_scr, mask)

    @pl.when(run & full)
    def _interior():
        _accumulate(False)

    @pl.when(run & ~full)
    def _boundary():
        _accumulate(True)

    @pl.when(ki == kv_steps - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:] + jnp.log(l)


def _flash_fwd(q, k, v, causal, causal_offset, kv_len, sm_scale,
               block_q, block_k, window=None):
    """q: (B,H,Sq,D) block-multiple padded; k/v: (B,HK,Sk,D)."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = h // hk
    q_steps = pl.cdiv(sq, block_q)
    kv_steps = pl.cdiv(sk, block_k)

    kernel = functools.partial(
        _fwd_kernel, causal=causal, causal_offset=causal_offset,
        kv_len=kv_len, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, kv_steps=kv_steps,
        window=window,
    )
    kvc = _kv_band_clamp(block_q, block_k, causal, causal_offset, window,
                         kv_steps)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki: (b_, h_ // group,
                                                 kvc(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki: (b_, h_ // group,
                                                 kvc(qi, ki), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=_interpret_mode(),
        name="flash_attention_fwd",
    )(q, k, v)
    return out, lse


# --------------------------------------------------------------------------
# backward: dq kernel (grid over q blocks, scan kv blocks)
# --------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, causal, causal_offset, kv_len, sm_scale,
                   block_q, block_k, kv_steps, window=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run, full = _run_full(qi, ki, block_q, block_k, causal, causal_offset,
                          kv_len, window)

    def _body(masked):
        # storage-dtype matmul inputs + f32 accumulation (see _fwd_kernel)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]    # (BQ, 1)
        delta = delta_ref[0, 0]  # (BQ, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        p = jnp.exp(s - lse)
        if masked:
            mask = _mask_for_block(qi, ki, block_q, block_k, causal,
                                   causal_offset, kv_len, window)
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    @pl.when(run & full)
    def _interior():
        _body(False)

    @pl.when(run & ~full)
    def _boundary():
        _body(True)

    @pl.when(ki == kv_steps - 1)
    def _store():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


# --------------------------------------------------------------------------
# backward: dk/dv kernel (grid over kv blocks, scan q blocks)
# --------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal, causal_offset,
                    kv_len, sm_scale, block_q, block_k, q_steps, window=None):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run, full = _run_full(qi, ki, block_q, block_k, causal, causal_offset,
                          kv_len, window)

    def _body(masked):
        # storage-dtype matmul inputs + f32 accumulation (see _fwd_kernel)
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        p = jnp.exp(s - lse)  # (BQ, BK) f32
        if masked:
            mask = _mask_for_block(qi, ki, block_q, block_k, causal,
                                   causal_offset, kv_len, window)
            p = jnp.where(mask, p, 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    @pl.when(run & full)
    def _interior():
        _body(False)

    @pl.when(run & ~full)
    def _boundary():
        _body(True)

    @pl.when(qi == q_steps - 1)
    def _store():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(causal, causal_offset, kv_len, sm_scale, block_q, block_k,
               window, residuals, g):
    q, k, v, out, lse = residuals
    do = g[0] if isinstance(g, tuple) else g
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = h // hk
    q_steps = pl.cdiv(sq, block_q)
    kv_steps = pl.cdiv(sk, block_k)

    # GQA: dq reads the shared KV head zero-copy via its index map (like
    # the forward); only the dk/dv kernel needs KV materialised per query
    # head, with the per-group reduction applied after.
    if group > 1:
        k_r = jnp.repeat(k, group, axis=1)
        v_r = jnp.repeat(v, group, axis=1)
    else:
        k_r, v_r = k, v

    # delta = rowsum(do * out) — tiny, do it in XLA; carried as (B,H,Sq,1)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )

    common = dict(causal=causal, causal_offset=causal_offset, kv_len=kv_len,
                  sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                  window=window)

    kvc = _kv_band_clamp(block_q, block_k, causal, causal_offset, window,
                         kv_steps)
    qc = _q_band_clamp(block_q, block_k, causal, causal_offset, window,
                       q_steps)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, kv_steps=kv_steps, **common),
        grid=(b, h, q_steps, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki: (b_, h_ // group,
                                                 kvc(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki: (b_, h_ // group,
                                                 kvc(qi, ki), 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret_mode(),
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)

    dk_r, dv_r = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_steps=q_steps, **common),
        grid=(b, h, kv_steps, q_steps),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, ki, qi: (b_, h_, qc(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, ki, qi: (b_, h_, qc(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, ki, qi: (b_, h_, qc(ki, qi), 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, ki, qi: (b_, h_, qc(ki, qi), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=_interpret_mode(),
        name="flash_attention_bwd_dkv",
    )(q, k_r, v_r, do, lse, delta)

    if group > 1:
        dk = dk_r.reshape(b, hk, group, sk, d).sum(axis=2).astype(k.dtype)
        dv = dv_r.reshape(b, hk, group, sk, d).sum(axis=2).astype(v.dtype)
    else:
        dk, dv = dk_r, dv_r
    return dq, dk, dv


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_attention_bhsd(q, k, v, causal, causal_offset, kv_len, sm_scale,
                          block_q, block_k, window=None):
    out, _ = _flash_fwd(q, k, v, causal, causal_offset, kv_len, sm_scale,
                        block_q, block_k, window)
    return out


def _fwd_rule(q, k, v, causal, causal_offset, kv_len, sm_scale,
              block_q, block_k, window=None):
    out, lse = _flash_fwd(q, k, v, causal, causal_offset, kv_len, sm_scale,
                          block_q, block_k, window)
    return out, (q, k, v, out, lse)


def _bwd_rule(causal, causal_offset, kv_len, sm_scale, block_q, block_k,
              window, residuals, g):
    return _flash_bwd(causal, causal_offset, kv_len, sm_scale,
                      block_q, block_k, window, residuals, g)


_flash_attention_bhsd.defvjp(_fwd_rule, _bwd_rule)


def flash_attention(q, k, v, causal=False, sm_scale=None,
                    block_q=None, block_k=None, window_size=None):
    """Flash attention over paddle layout (B, S, H, D).

    Supports GQA/MQA (H a multiple of HK), cross-attention lengths
    (bottom-right causal alignment), arbitrary sequence lengths
    (internally padded to block multiples), and causal SLIDING-WINDOW
    attention (``window_size`` = the number of most-recent keys each
    query may attend to, itself included — Mistral semantics; tiles
    entirely outside the band are skipped, so cost is O(S * window)).
    """
    if window_size is not None:
        if not causal:
            raise ValueError(
                "window_size requires causal=True (a non-causal window "
                "is ambiguous about its anchor)")
        if window_size < 1:
            raise ValueError(f"window_size must be >= 1, got {window_size}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if block_q is None or block_k is None:
        dbq, dbk = _default_blocks(q.shape[-1])
        block_q = block_q or dbq
        block_k = block_k or dbk
    h, hk = q.shape[2], k.shape[2]
    if h % hk != 0:
        raise ValueError(f"query heads ({h}) must be a multiple of kv heads ({hk})")
    win = None if window_size is None else int(window_size)
    # batch rows and heads are independent: one kernel per mesh shard
    spec = P(_data_axes(q.shape[0]), None, _head_axis(h, hk), None)
    return _per_shard(
        functools.partial(_flash_bshd, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, win=win),
        (spec, spec, spec), spec)(q, k, v)


def _flash_bshd(q, k, v, *, causal, sm_scale, block_q, block_k, win):
    qt = jnp.swapaxes(q, 1, 2)  # (B, H, Sq, D)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    sq, sk = qt.shape[2], kt.shape[2]
    bq = min(block_q, _round_up(sq, 8))
    bk = min(block_k, _round_up(sk, 8))
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    causal_offset = sk - sq  # bottom-right alignment, real lengths
    out = _flash_attention_bhsd(qt, kt, vt, causal, causal_offset, sk,
                                sm_scale, bq, bk, win)
    if pad_q:
        out = out[:, :, :sq]
    return jnp.swapaxes(out, 1, 2)
