"""One decode step of Kimi Delta Attention's delta rule — Pallas TPU kernel
(``nlp/solar_open2.py::kda_step`` is the plain form and the reference the
parity tests compare with).

Per slot and head, with the head's ``d x d`` float32 state S resident in
VMEM (64 KB at d = 128):

    S' = Diag(alpha) S;  u = beta (v - S'^T k);  S1 = S' + k u^T;
    o = S1^T q

XLA computes this as a reduction over S (``S'^T k``) and then an elementwise
pass over S again (the update needs the reduction's result), so every
slot's state is read from HBM twice and written once a layer and step. Here
the grid is (slot, head group): a group's states come in once, are updated
in place (the state array is aliased to the output), and go out once.

The vectors ride in ONE packed array ``(slots, heads, 8, d)`` whose rows
are ``alpha, k, q, v, beta (broadcast)`` and three rows of zeros: alpha, k
and q index the state's ROWS (sublanes), so the kernel needs them as
columns and transposes the group's tile once; v and beta multiply along
the lanes and are used as rows. So the kernel takes ``d_k == d_v``, both
whole lane tiles (:func:`supports`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ._utils import (head_axis as _head_axis,
                     interpret_mode as _interpret_mode,
                     per_shard as _per_shard)

F32 = jnp.float32
HEAD_GROUP = 8          # heads a grid step: 8 x 64 KB of state in, the same out
_ROWS = 8               # the packed vectors' rows, a sublane tile


def supports(state):
    """``state`` (slots, heads, d_k, d_v): the kernel takes square float32
    heads of whole lane tiles, ``HEAD_GROUP`` heads at a time."""
    _, h, dk, dv = state.shape
    return (state.dtype == F32 and dk == dv and dk % 128 == 0
            and h % HEAD_GROUP == 0)


def _kernel(vec_ref, s_ref, o_ref, s_out_ref):
    d = s_ref.shape[-1]
    for j in range(HEAD_GROUP):                 # static: unrolled
        rows = vec_ref[0, j]                                   # (8, d)
        # alpha, k, q as columns: the rows' tile padded to (d, d) and
        # transposed (an aligned transpose; a column is then a lane slice)
        cols = jnp.concatenate(
            [rows, jnp.zeros((d - _ROWS, d), F32)], axis=0).T
        alpha, k, q = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
        v, beta = rows[3:4], rows[4:5]                         # (1, d)
        decayed = s_ref[0, j] * alpha
        u = beta * (v - jnp.sum(decayed * k, axis=0, keepdims=True))
        new = decayed + k * u
        s_out_ref[0, j] = new
        o_ref[0, pl.ds(j, 1)] = jnp.sum(new * q, axis=0, keepdims=True)


def kda_decode_update(q, k, v, g, beta, state):
    """``q``, ``k`` (S, H, d) normalised, ``v`` (S, H, d), ``g`` (S, H, d)
    <= 0 the log decay, ``beta`` (S, H), ``state`` (S, H, d, d) float32 ->
    ``o`` (S, H, d) float32 and the new state (in the state's buffer where
    the caller donates it). A row with ``g = 0`` and ``beta = 0`` keeps its
    state bit for bit."""
    h = state.shape[1]
    h_ax = _head_axis(h // HEAD_GROUP)
    vec_spec, state_spec = P(None, h_ax, None), P(None, h_ax, None, None)
    return _per_shard(
        _kda_decode_update,
        (vec_spec, vec_spec, vec_spec, vec_spec, P(None, h_ax), state_spec),
        (vec_spec, state_spec))(q, k, v, g, beta, state)


def _kda_decode_update(q, k, v, g, beta, state):
    s_, h, d, _ = state.shape
    beta = jnp.broadcast_to(beta.astype(F32)[..., None], (s_, h, d))
    vec = jnp.stack(
        [jnp.exp(g.astype(F32)), k.astype(F32), q.astype(F32),
         v.astype(F32), beta] + [jnp.zeros((s_, h, d), F32)] * (_ROWS - 5),
        axis=2)                                            # (S, H, 8, d)

    def group(i, j):
        return (i, j, 0, 0)

    return pl.pallas_call(
        _kernel,
        grid=(s_, h // HEAD_GROUP),
        in_specs=[pl.BlockSpec((1, HEAD_GROUP, _ROWS, d), group),
                  pl.BlockSpec((1, HEAD_GROUP, d, d), group)],
        out_specs=[pl.BlockSpec((1, HEAD_GROUP, d), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, HEAD_GROUP, d, d), group)],
        out_shape=[jax.ShapeDtypeStruct((s_, h, d), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret_mode(),
        name="kda_decode_update",
    )(vec, state)
