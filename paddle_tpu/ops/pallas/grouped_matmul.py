"""Grouped matrix product — the Pallas TPU kernel of the routed experts'
two products (``incubate/.../moe/moe_layer.py::grouped_expert_ffn``, scope
``moe.products``), in prefill and in decode alike, wherever the rule of
:func:`supports` says its widths allow. Elsewhere ``jax.lax.ragged_dot``:
the CPU path, a mesh's path, a width the kernel cannot take, and the
reference the parity tests compare with.

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

**The row tile comes from the shape** (:func:`_tiles`): 512 rows in
sub-tiles of 128 where the mean rows a group fill it (prefill: the MXU
bounds the product), halved down to ONE sub-tile of 16 rows where they do
not. In decode a group holds a few rows, so a visit is those rows against
the group's ``(K, N)`` block, the block is fetched once a touched group and
an untouched expert costs nothing: the weights' bytes bound the product.

**An expert's weight block changes only when the group does**, and it is
fetched a whole group ahead: ``rhs`` stays in HBM and the kernel keeps two
``(K, block_n)`` buffers, starting the copy of the NEXT non-empty group's
block at a group's first visit (a (2048, 2048) bf16 block is 10 us of the
bandwidth, a group's ~512 rows 27 us of the MXU; BlockSpec pipelining
looks one VISIT ahead, and a group's last visit may hold a few rows). At
the 16-row tile the same schedule keeps the DMA engine busy: the next
block's copy runs under this group's one product.

**A width that is not whole lanes** (1856 = 116 x 16 = 14.5 lanes) is
taken where it is whole SUBLANE tiles of the dtype and the product's other
width is whole lanes; the stack is read AS THE DEVICE STORES IT, so no
program re-lays it out. *N ragged* (``(E, K, N)`` with K whole lanes): the
TPU keeps such an array with K minor, physically ``(E, N, K)`` row-major,
so the kernel takes ``swapaxes(rhs, 1, 2)`` (a bitcast), copies a group's
whole ``(N, K)`` block (N on sublanes: whole tiles; every copied row whole
lanes) and contracts on the block's MINOR axis (an NT product); the result
block ``(rows, N)`` is stored with its last lane tile masked. *K ragged*
(``(E, K, N)`` with N whole lanes, stored as it reads): the block lands in
the first K rows of a ``(Kp, N)`` buffer and each row sub-tile in the first
K lanes of a ``(sub, Kp)`` one, ``Kp`` = K in whole lanes; rows and lanes
K .. Kp are zeroed once a call and never written again, so they add exact
zeros to the contraction, not whatever VMEM held.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...parallel import mesh as mesh_state
from ._utils import interpret_mode as _interpret_mode, round_up

__all__ = ["grouped_matmul", "supports", "swiglu"]

_BLOCK_M = 512            # rows a tile (what a visit fetches and stores)
_BLOCK_SUB = 128          # rows a product inside it
_MIN_BLOCK_M = 16         # the least row tile: one sublane tile of bf16
_RHS_BLOCK_BYTES = 16 << 20   # a (K, block_n) weight block may take this


def _block_n(k, n, itemsize):
    """N whole where a (K, N) block fits ``_RHS_BLOCK_BYTES``, else the
    largest multiple of 128 that divides N and does."""
    tn = n
    while k * tn * itemsize > _RHS_BLOCK_BYTES and tn % 256 == 0:
        tn //= 2
    return tn


def _tiles(rows, groups):
    """``(block_m, block_sub)`` from the mean rows a group: ``_BLOCK_M`` /
    ``_BLOCK_SUB`` where a group fills a row tile (prefill), halved down to
    ``_MIN_BLOCK_M`` where it does not, so that decode's few rows a group
    are one visit of one sub-tile against the group's weight block."""
    tm = _BLOCK_M
    while tm > max(rows // groups, _MIN_BLOCK_M):
        tm //= 2
    return tm, min(_BLOCK_SUB, tm)


def _takes(k, n, itemsize):
    """One product's part of :func:`supports`."""
    sublanes = 32 // itemsize       # rows of one tile of the dtype
    ragged = [w for w in (k, n) if w % 128]
    if k % sublanes or n % sublanes or len(ragged) > 1:
        return False
    if ragged and round_up(k, 128) * round_up(n, 128) * itemsize \
            > _RHS_BLOCK_BYTES:
        return False    # the width padded to whole lanes, in ONE block
    return True


def supports(*weights):
    """The static rule, read from the shapes of a call's ``weights`` (E, K,
    N): the kernel takes a product whose K and N are whole sublane tiles
    of the dtype (16 for bf16), at most one of them not whole lanes (its
    padded block within ``_RHS_BLOCK_BYTES``), under no mesh of more than
    one device (a Mosaic kernel cannot be partitioned, and an expert axis
    over the weights has a schedule of its own,
    ``MoELayer._grouped_ep_fn``). The rows do not enter: :func:`_tiles`
    fits the row tile to them, and at every mean rows a group measured,
    from decode's 0.5 through 64, 128 and 256 to prefill's thousands, the
    kernel read the weights faster than ``ragged-dot`` at every draw of
    the routing (by 6 % at the least: PERF.md section 6, PR 47)."""
    mesh = mesh_state.get_mesh()
    return (mesh is None or mesh.size == 1) and all(
        _takes(*w.shape[1:], jnp.dtype(w.dtype).itemsize) for w in weights)


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
            lhs_ref, rhs_hbm, out_ref, w_buf, sem, *scratch, tm, sub, tn,
            groups, epilogue, nt):
    ni, v = pl.program_id(0), pl.program_id(1)
    g, slot = gid_ref[v], slot_ref[v]
    k = lhs_ref.shape[1]
    x_pad = scratch[0] if scratch else None     # (sub, Kp): K is ragged

    def fetch(group, into):
        if nt:      # the whole stored (N, K) block: N on sublanes
            src, dst = rhs_hbm.at[group], w_buf.at[into, pl.ds(0, tn), :]
        else:
            src = rhs_hbm.at[group, :, pl.ds(pl.multiple_of(ni * tn, 128),
                                             tn)]
            dst = w_buf.at[into] if x_pad is None \
                else w_buf.at[into, pl.ds(0, k), :]
        return pltpu.make_async_copy(src, dst, sem.at[into])

    if x_pad is not None:
        # K is not whole lanes: the lanes past K of a row sub-tile and the
        # rows past K of both weight buffers are zeroed once (no copy ever
        # writes them), so they add exact zeros to every contraction
        @pl.when(v == 0)
        def _zero_the_padding():
            _zero_pad(x_pad, w_buf, k)

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

    def rows_of(r):
        return lhs_ref[pl.ds(r, sub), :]

    def padded_rows_of(r):
        x_pad[:, pl.ds(0, k)] = lhs_ref[pl.ds(r, sub), :]
        return x_pad[...]

    def dot(x):
        return jnp.dot(x, w_buf[slot], preferred_element_type=jnp.float32)

    def dot_nt(x):
        # contract on the block's minor axis; the sublanes past N give
        # columns nobody stores
        return jax.lax.dot_general(
            x, w_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)[:, :tn]

    load = rows_of if x_pad is None else padded_rows_of
    times_block = dot_nt if nt else dot

    def product(i, carry):
        r = pl.multiple_of(i * sub, sub)
        acc = times_block(load(r))
        new = epilogue(acc.astype(out_ref.dtype)).astype(jnp.float32)
        row = r + jax.lax.broadcasted_iota(jnp.int32, new.shape, 0)
        out_ref[pl.ds(r, sub), :] = jnp.where(
            (row >= lo) & (row < hi), new,
            out_ref[pl.ds(r, sub), :].astype(jnp.float32)
        ).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(lo // sub, (hi + sub - 1) // sub, product, 0)


def _zero_pad(x_pad, w_buf, k):
    """Zeros in the lane tile of ``x_pad`` (sub, Kp) that holds lane K and
    in rows K .. Kp of both weight buffers ``w_buf`` (2, Kp, tn)."""
    kp = x_pad.shape[1]
    x_pad[:, pl.ds(kp - 128, 128)] = jnp.zeros((x_pad.shape[0], 128),
                                               x_pad.dtype)
    w_buf[:, pl.ds(k, kp - k), :] = jnp.zeros(
        (2, kp - k, w_buf.shape[2]), w_buf.dtype)


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
    rhs = rhs.astype(lhs.dtype)
    kp, nt = round_up(k, 128), n % 128 != 0
    assert kp == k or not nt, (k, n)
    if nt:
        # the stack as the device stores it: (E, N, K), a bitcast
        assert tn == n, (k, n)
        rhs = jnp.swapaxes(rhs, 1, 2)
        w_buf = (2, round_up(n, 128), k)
    else:
        w_buf = (2, kp, tn)
    x_pad = [pltpu.VMEM((sub, kp), lhs.dtype)] if kp != k else []

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
            pltpu.VMEM(w_buf, lhs.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            *x_pad,
        ],
    )
    lanes_out = round_up(tn_out, 128)
    vmem = (math.prod(w_buf) + 2 * tm * kp + 2 * tm * lanes_out
            + (sub * kp if x_pad else 0)) * itemsize \
        + 3 * sub * round_up(tn, 128) * 4
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, sub=sub, tn=tn, groups=e,
                          epilogue=swiglu if fused else lambda h: h, nt=nt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, n_out), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem + (8 << 20), 100 << 20)),
        interpret=interpret,
        name="grouped_matmul",
    )(*meta, lhs, rhs)
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
    tm, sub = _tiles(lhs.shape[0], rhs.shape[0])
    return _jitted(lhs, rhs, group_sizes, bool(swiglu), block_m or tm,
                   block_sub or sub, _interpret_mode())
