"""RMSNorm — Pallas TPU kernel (fwd + bwd), the analog of the reference's
fused CUDA kernel (paddle/phi/kernels/fusion/gpu/rms_norm_kernel.cu —
unverified, SURVEY.md §0/§2.5).

Rows are all leading dims flattened; the feature dim is normalized.
Math (all in f32):
    m  = mean(x^2)          r = rsqrt(m + eps)
    y  = x * r * w
    g  = dy * w
    dx = g * r - x * r^3 * mean(g * x)
    dw = sum_rows(dy * x * r)
The dw reduction accumulates across row blocks in a VMEM scratch; the TPU
grid is sequential so this is race-free (and interpret mode preserves it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from ._utils import (
    data_axes as _data_axes, interpret_mode as _interpret_mode,
    per_shard as _per_shard, round_up as _round_up,
)

DEFAULT_BLOCK_ROWS = 256




def _fwd_kernel(x_ref, w_ref, y_ref, r_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)          # (BR, N)
    w = w_ref[...].astype(jnp.float32)          # (1, N)
    m = jnp.mean(x * x, axis=1, keepdims=True)  # (BR, 1)
    r = jax.lax.rsqrt(m + eps)
    y_ref[...] = (x * r * w).astype(y_ref.dtype)
    r_ref[...] = r


def _bwd_kernel(x_ref, w_ref, r_ref, dy_ref, dx_ref, dw_ref, dw_scr,
                *, row_steps):
    ri = pl.program_id(0)

    @pl.when(ri == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    r = r_ref[...]                               # (BR, 1)
    dy = dy_ref[...].astype(jnp.float32)
    g = dy * w
    mean_gx = jnp.mean(g * x, axis=1, keepdims=True)
    dx = g * r - x * (r * r * r) * mean_gx
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dw_scr[...] += jnp.sum(dy * x * r, axis=0, keepdims=True)

    @pl.when(ri == row_steps - 1)
    def _store():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)


def _rms_fwd(x2d, w, eps, block_rows):
    rows, n = x2d.shape
    block_rows = min(block_rows, rows)
    row_steps = pl.cdiv(rows, block_rows)
    y, r = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(row_steps,),
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), x2d.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=_interpret_mode(),
        name="rms_norm_fwd",
    )(x2d, w.reshape(1, n))
    return y, r


def _rms_bwd(x2d, w, r, dy2d, block_rows):
    rows, n = x2d.shape
    block_rows = min(block_rows, rows)
    row_steps = pl.cdiv(rows, block_rows)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, row_steps=row_steps),
        grid=(row_steps,),
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), x2d.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, n), jnp.float32)],
        interpret=_interpret_mode(),
        name="rms_norm_bwd",
    )(x2d, w.reshape(1, n), r, dy2d)
    return dx, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms_norm_2d(x2d, w, eps, block_rows):
    y, _ = _rms_fwd(x2d, w, eps, block_rows)
    return y


def _fwd_rule(x2d, w, eps, block_rows):
    y, r = _rms_fwd(x2d, w, eps, block_rows)
    return y, (x2d, w, r)


def _bwd_rule(eps, block_rows, residuals, dy):
    x2d, w, r = residuals
    dx, dw = _rms_bwd(x2d, w, r, dy, block_rows)
    return dx, dw.reshape(w.shape).astype(w.dtype)


_rms_norm_2d.defvjp(_fwd_rule, _bwd_rule)


def rms_norm(x, weight, epsilon=1e-6, block_rows=None, row_axis=None):
    """RMSNorm over the last axis; x (..., N), weight (N,). ``row_axis``:
    the mesh axis the caller keeps dim 1 split over, if any."""
    # rows are independent: one kernel per data shard of the leading dim,
    # and per member of ``row_axis`` along dim 1: the transpose then has
    # no dx to sum over that axis
    lead_spec = (_data_axes(x.shape[0]),) if x.ndim > 1 else ()
    if row_axis is not None:
        lead_spec += (row_axis,)
    x_spec = P(*lead_spec, *([None] * (x.ndim - len(lead_spec))))
    return _per_shard(
        functools.partial(_rms_norm_rows, epsilon=epsilon,
                          block_rows=block_rows),
        (x_spec, P(None)), x_spec)(x, weight)


def _rms_norm_rows(x, weight, *, epsilon, block_rows):
    n = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for s in lead:
        rows *= s
    if block_rows is None:
        # the bwd kernel's scoped-VMEM demand (double-buffered bf16
        # in/out tiles + f32 compute temporaries) scales ~linearly with
        # block*N and measures ~11MB at 256x2048 on v5e (22MB at
        # 256x4096 = compile OOM against the 16MB limit); cap the
        # product at the known-safe 256x2048
        budget = (256 * 2048) // max(n, 1)
        block_rows = max(8, min(DEFAULT_BLOCK_ROWS, _round_up(budget, 8) or 8))
    # pad rows to a full block multiple so no partial/garbage block ever
    # feeds the dw accumulation (padded rows are zeros → zero dy → no-op)
    block = min(block_rows, ((rows + 7) // 8) * 8)
    pad = (-rows) % block
    x2d = x.reshape(rows, n)
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    out = _rms_norm_2d(x2d, weight, epsilon, block)
    if pad:
        out = out[:rows]
    return out.reshape(*lead, n)
