"""Grouped matrix product — the Pallas TPU kernel of the routed experts'
two products in prefill (``incubate/.../moe/moe_layer.py::
grouped_expert_ffn``, scope ``moe.products``). Elsewhere, and wherever the
rule of :func:`supports` says so, ``jax.lax.ragged_dot``: the CPU path,
the decode path and the reference the parity tests compare with.

``grouped_matmul(lhs (rows, K), rhs (E, K, N), group_sizes (E,))``: the
rows are sorted by group, group ``g`` owns the next ``group_sizes[g]`` of
them and is multiplied by ``rhs[g]``; float32 accumulation, the result in
``lhs``'s dtype (what ``ragged_dot`` gives). Rows past the last group are
NEVER VISITED: what the result holds there is whatever the buffer held.

The grid is (N tile, visit). A visit is one (row tile, group) pair that
share rows, in sorted order: ``row tiles + groups - 1`` at most, counted
on the device (the grid's bound is dynamic), so an empty group and a row
tile past the last group cost nothing. The visit's group, row tile and the
group offsets ride in scalar prefetch. Inside a visit only the sub-tiles
of ``block_sub`` rows that hold rows of the group are multiplied — a row
tile that straddles groups costs its sub-tiles once each and at most one
sub-tile more a group — and the other group's rows are masked on the
store. K is whole: one product a sub-tile, no accumulator scratch.
``swiglu=True`` (N whole in a block) makes :func:`swiglu` the product's
epilogue: the (sub, N) accumulator is rounded as the plain form stores it,
split into gate | up and written as (sub, N / 2): ``h`` is never stored.
The roundings are the XLA fusion's (bit for bit on the interpreter; on the
chip Mosaic's sigmoid leaves a quarter of the elements one bf16 step off).

**An expert's weight block changes only when the group does**, and it is
fetched a whole group ahead: ``rhs`` stays in HBM and the kernel keeps two
``(K, block_n)`` buffers, starting the copy of the NEXT non-empty group's
block at a group's first visit (a (2048, 2048) bf16 block is 10 us of the
bandwidth, a group's ~512 rows 27 us of the MXU; BlockSpec pipelining
looks one VISIT ahead, and a group's last visit may hold a few rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...parallel import mesh as mesh_state
from ._utils import interpret_mode as _interpret_mode, round_up

__all__ = ["grouped_matmul", "supports", "swiglu"]

_BLOCK_M = 512            # rows a tile (what a visit fetches and stores)
_BLOCK_SUB = 128          # rows a product inside it
_RHS_BLOCK_BYTES = 16 << 20   # a (K, block_n) weight block may take this


def _block_n(k, n, itemsize):
    """N whole where a (K, N) block fits ``_RHS_BLOCK_BYTES``, else the
    largest multiple of 128 that divides N and does."""
    tn = n
    while k * tn * itemsize > _RHS_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def supports(rows, *weights):
    """The static rule, read from the shapes of a call's ``rows`` and its
    ``weights`` (E, K, N): the kernel where the mean rows a group, ``rows
    // E``, is at least one row tile (prefill; below it the products are
    bound by the weights' bytes and ``ragged_dot`` reads them at 65-79 %
    of the bandwidth), every K and N is whole lanes, and no mesh of more
    than one device is installed (a Mosaic kernel cannot be partitioned,
    and an expert axis over the weights has a schedule of its own,
    ``MoELayer._grouped_ep_fn``)."""
    mesh = mesh_state.get_mesh()
    return (mesh is None or mesh.size == 1) and all(
        rows // e >= _BLOCK_M and k % 128 == 0 and n % 128 == 0
        for e, k, n in (w.shape for w in weights))


def swiglu(h):
    """``silu(gate) * up`` over ``h = [gate | up]``: the sigmoid in
    float32, the product in ``h``'s dtype. The routed experts' activation
    in the three expert families, and the kernel's optional epilogue."""
    g, u = jnp.split(h, 2, axis=-1)
    return jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u


def _visits(group_sizes, rows, tm):
    """The metadata of a call: ``offsets`` (E + 1,) the first row of each
    group; per visit its group, its row tile, whether it is its group's
    first, the next non-empty group (E = none) and which of the two weight
    buffers holds its group's block; the number of visits. All int32,
    ``row tiles + E - 1`` visits at most."""
    e = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    offsets = jnp.concatenate([starts[:1], ends])
    first_tile = starts // tm
    live = group_sizes > 0
    n = jnp.where(live, (ends - 1) // tm - first_tile + 1, 0)
    v_end = jnp.cumsum(n)
    v = jnp.arange(rows // tm + e - 1, dtype=jnp.int32)
    gid = jnp.minimum(jnp.searchsorted(v_end, v, side="right"), e - 1)
    nth = v - (v_end - n)[gid]
    ids = jnp.arange(e, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(live, ids, e)[::-1])[::-1]
    nxt = jnp.concatenate([nxt[1:], jnp.full((1,), e, jnp.int32)])
    slot = (jnp.cumsum(live) - 1) % 2
    return tuple(x.astype(jnp.int32) for x in (
        offsets, gid, first_tile[gid] + nth, nth == 0, nxt[gid], slot[gid],
        v_end[-1]))


def _kernel(offs_ref, gid_ref, tile_ref, first_ref, next_ref, slot_ref,
            lhs_ref, rhs_hbm, out_ref, w_buf, sem, *, tm, sub, tn, groups,
            epilogue):
    ni, v = pl.program_id(0), pl.program_id(1)
    g, slot = gid_ref[v], slot_ref[v]

    def fetch(group, into):
        return pltpu.make_async_copy(
            rhs_hbm.at[group, :, pl.ds(pl.multiple_of(ni * tn, 128), tn)],
            w_buf.at[into], sem.at[into])

    @pl.when(v == 0)
    def _first_group():
        fetch(g, slot).start()

    @pl.when(first_ref[v] == 1)
    def _group_begins():
        fetch(g, slot).wait()

        @pl.when(next_ref[v] < groups)
        def _next_group():
            fetch(next_ref[v], 1 - slot).start()

    row0 = tile_ref[v] * tm
    lo = jnp.maximum(offs_ref[g], row0) - row0
    hi = jnp.minimum(offs_ref[g + 1], row0 + tm) - row0

    def product(i, carry):
        r = pl.multiple_of(i * sub, sub)
        acc = jnp.dot(lhs_ref[pl.ds(r, sub), :], w_buf[slot],
                      preferred_element_type=jnp.float32)
        new = epilogue(acc.astype(out_ref.dtype)).astype(jnp.float32)
        row = r + jax.lax.broadcasted_iota(jnp.int32, new.shape, 0)
        out_ref[pl.ds(r, sub), :] = jnp.where(
            (row >= lo) & (row < hi), new,
            out_ref[pl.ds(r, sub), :].astype(jnp.float32)
        ).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(lo // sub, (hi + sub - 1) // sub, product, 0)


def _reference(lhs, rhs, group_sizes, fused):
    h = jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return swiglu(h) if fused else h


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _grouped_matmul(lhs, rhs, group_sizes, fused, block_m, block_sub,
                    interpret):
    rows, k = lhs.shape
    e, _, n = rhs.shape
    tm = block_m
    sub = min(block_sub, tm)
    assert tm % sub == 0 and sub % 16 == 0, (tm, sub)
    padded = round_up(rows, tm)     # whole row tiles (no rows added where
    lhs = jnp.pad(lhs, ((0, padded - rows), (0, 0)))    # they already are)
    itemsize = jnp.dtype(lhs.dtype).itemsize
    tn = _block_n(k, n, itemsize)
    assert not fused or tn == n, (k, n)     # gate and up in one block
    tn_out, n_out = (tn // 2, n // 2) if fused else (tn, n)
    *meta, visits = _visits(group_sizes.astype(jnp.int32), padded, tm)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n // tn, visits),
        in_specs=[
            pl.BlockSpec((tm, k), lambda ni, v, o, g, t, *_: (t[v], 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((tm, tn_out),
                               lambda ni, v, o, g, t, *_: (t[v], ni)),
        scratch_shapes=[
            pltpu.VMEM((2, k, tn), lhs.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    vmem = (2 * k * tn + 2 * tm * k + 2 * tm * tn_out) * itemsize \
        + 3 * sub * tn * 4
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, sub=sub, tn=tn, groups=e,
                          epilogue=swiglu if fused else lambda h: h),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, n_out), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem + (8 << 20), 100 << 20)),
        interpret=interpret,
        name="grouped_matmul",
    )(*meta, lhs, rhs.astype(lhs.dtype))
    return out[:rows]


def _fwd(lhs, rhs, group_sizes, *static):
    return (_grouped_matmul(lhs, rhs, group_sizes, *static),
            (lhs, rhs, group_sizes))


def _bwd(fused, block_m, block_sub, interpret, saved, g):
    # the reference's own backward on the saved operands: gradients are
    # what they are on the ragged_dot path
    lhs, rhs, group_sizes = saved
    _, vjp = jax.vjp(lambda a, b: _reference(a, b, group_sizes, fused),
                     lhs, rhs)
    return (*vjp(g), None)


_grouped_matmul.defvjp(_fwd, _bwd)
# one trace and ONE Mosaic lowering for all the calls of a program that
# share shapes (an expert layer's two products are the same two calls in
# every layer): lowering a kernel costs ~0.25 s of every warm start
_jitted = jax.jit(_grouped_matmul, static_argnums=(3, 4, 5, 6))


def fuses_swiglu(rhs):
    """Whether ``grouped_matmul(..., swiglu=True)`` takes ``rhs`` (E, K,
    N): gate and up must sit in one weight block, each whole lanes."""
    _, k, n = rhs.shape
    return n % 256 == 0 and _block_n(k, n, rhs.dtype.itemsize) == n


def grouped_matmul(lhs, rhs, group_sizes, swiglu=False, block_m=None,
                   block_sub=None):
    """``lhs`` (rows, K) sorted by group, ``rhs`` (E, K, N),
    ``group_sizes`` (E,) int32 with ``sum <= rows`` -> (rows, N) in
    ``lhs``'s dtype: rows of group g times ``rhs[g]``, accumulated in
    float32; rows past the last group hold whatever the buffer held.
    ``swiglu=True`` (where :func:`fuses_swiglu`): -> (rows, N / 2),
    :func:`swiglu` of that result with the same roundings. Differentiable:
    the backward is that of ``jax.lax.ragged_dot`` (and the activation)."""
    return _jitted(lhs, rhs, group_sizes, bool(swiglu), block_m or _BLOCK_M,
                   block_sub or _BLOCK_SUB, _interpret_mode())
