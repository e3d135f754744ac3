"""The grouped-matmul kernel of the routed experts' two products
(``ops/pallas/grouped_matmul.py``, ISSUE 38; a width that is not whole
lanes and decode's 16-row tile, ISSUE 40; every decode shape, ISSUE 47),
through the interpreter on the CPU: parity with ``jax.lax.ragged_dot``
(the reference it replaces), gradients through ``grouped_expert_ffn`` with
the kernel forced, and the static rule that selects it with the counter
that says so.
"""
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import moe_layer
from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
    grouped_expert_ffn, moe_products_programs, swiglu)
from paddle_tpu.ops.pallas import grouped_matmul as kernel

from family_harness import products_counts as _counts

F32, BF16 = jnp.float32, jnp.bfloat16

# id -> (rows, K, N, group sizes, dtype, block_m, block_sub)
CASES = {
    "even": (256, 128, 128, [64] * 4, F32, 64, 16),
    "even-bf16": (256, 128, 128, [64] * 4, BF16, 64, 16),
    "empty-first-middle-last": (256, 128, 128, [0, 0, 100, 0, 156, 0], F32,
                                64, 16),
    "all-empty-but-one": (128, 128, 128, [0, 128, 0], BF16, 32, 16),
    # max / mean = 200 / 32 = 6.25
    "one-group-holds-most": (256, 128, 128, [8, 8, 200, 8, 8, 8, 8, 8], F32,
                             64, 16),
    "one-group-holds-most-bf16": (256, 128, 128,
                                  [8, 8, 200, 8, 8, 8, 8, 8], BF16, 64, 64),
    # tile 0 (64 rows) holds groups 0, 1, 2 whole and the head of group 3
    "tile-straddles-three": (256, 128, 128, [20, 10, 20, 206], F32, 64, 16),
    "tile-straddles-three-bf16": (256, 128, 256, [20, 10, 20, 206], BF16,
                                  128, 32),
    # sum(group_sizes) < rows: the held= tail is never visited
    "held-tail": (256, 128, 256, [10, 20, 30, 40, 50], F32, 64, 16),
    "held-tail-whole-tiles": (512, 128, 128, [100, 0, 90], BF16, 128, 128),
    "rows-not-whole-tiles": (250, 128, 128, [10, 20, 30, 40, 50, 100], F32,
                             64, 16),
    # the three cells' (K, N) pairs at a few hundred rows, the kernel's
    # own prefill tiles
    "window-p1": (1024, 2048, 2048, [300, 0, 724], BF16, 512, 128),
    "window-p2": (1024, 1024, 2048, [300, 0, 724], BF16, 512, 128),
    "latent-p1": (1024, 2048, 1536, [511, 513], BF16, 512, 128),
    "latent-p2": (1024, 768, 2048, [511, 513], BF16, 512, 128),
    "state-space-p1": (1024, 4096, 1536, [200, 300], BF16, 512, 128),
    "state-space-p2": (1024, 768, 4096, [200, 300], BF16, 512, 128),
    # the Nemotron cell's two products, 1856 = 116 sublane tiles = 14.5
    # lanes: p1's N (the stack read as stored, contracted on the block's
    # minor axis) and p2's K (the contraction padded with exact zeros). At
    # the decode tile: empty first / middle / last groups, one group
    # holding most rows, a held= tail of 384 - 174 rows never visited
    "nemotron-decode-p1": (384, 2688, 1856, [0, 3, 150, 0, 5, 16, 0], BF16,
                           16, 16),
    "nemotron-decode-p2": (384, 1856, 2688, [0, 3, 150, 0, 5, 16, 0], BF16,
                           16, 16),
    # ... and at the prefill tiles
    "nemotron-prefill-p1": (1024, 2688, 1856, [300, 0, 600], BF16, 512,
                            128),
    "nemotron-prefill-p2": (1024, 1856, 2688, [300, 0, 600], BF16, 512,
                            128),
    # the Solar-Open2 cell's decode step (ISSUE 47): 768 sorted rows of
    # which 96 sit in groups and 672 (the choices on absent experts) past
    # the last one; a (4096, 2560) block is over 16 MiB, so p1 runs TWO N
    # tiles of 1280 and every visit is made once a tile
    "solar-open2-decode-p1": (768, 4096, 2560, [0, 3, 40, 0, 5, 16, 2, 30],
                              BF16, 16, 16),
    "solar-open2-decode-p2": (768, 1280, 4096, [0, 3, 40, 0, 5, 16, 2, 30],
                              BF16, 16, 16),
    # the same two forms in float32, whose sublane tile is 8 rows: the
    # tiles read from the shape (256 rows / 4 groups -> 64)
    "n-not-whole-lanes": (256, 128, 200, [30, 0, 100, 90], F32, None, None),
    "k-not-whole-lanes": (256, 200, 128, [30, 0, 100, 90], F32, None, None),
}


def _operands(rows, k, n, e, dtype, scale=1.0):
    ka, kb = jax.random.split(jax.random.PRNGKey(rows + k + n + e))
    lhs = (jax.random.normal(ka, (rows, k), F32) * scale).astype(dtype)
    rhs = (jax.random.normal(kb, (e, k, n), F32) * scale).astype(dtype)
    return lhs, rhs


@pytest.mark.parametrize("case", list(CASES))
def test_matches_ragged_dot(case):
    rows, k, n, sizes, dtype, tm, sub = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs = _operands(rows, k, n, sizes.shape[0], dtype, k ** -0.5)
    got = kernel.grouped_matmul(lhs, rhs, sizes, block_m=tm, block_sub=sub)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    assert got.shape == want.shape and got.dtype == want.dtype
    live = int(sum(CASES[case][3]))     # rows inside groups
    gap = jnp.max(jnp.abs(got[:live].astype(F32) - want[:live].astype(F32)))
    # float32 accumulation on both sides: a bf16 result may round the
    # last bit the other way. (The interpreter's uninitialised VMEM is NaN:
    # a padded lane that leaked into a contraction would read NaN here.)
    assert float(gap) <= (2e-5 if dtype == F32 else 2 ** -6), float(gap)


@pytest.mark.parametrize("case", ["solar-open2-decode-p1",
                                  "solar-open2-decode-p2"])
def test_a_decode_visit_over_two_n_tiles_is_ragged_dot_bit_for_bit(case):
    """Operands of small whole numbers: every partial sum is exact in
    float32 whatever the order a backend adds them in, so the two forms
    must agree to the bit on every row inside a group, the N-halved first
    product's second tile included."""
    rows, k, n, sizes, dtype, tm, sub = CASES[case]
    assert (kernel._block_n(k, n, 2) == n // 2) == case.endswith("p1")
    sizes = jnp.asarray(sizes, jnp.int32)
    ka, kb = jax.random.split(jax.random.PRNGKey(47))
    lhs = jax.random.randint(ka, (rows, k), -2, 3).astype(dtype)
    rhs = jax.random.randint(kb, (sizes.shape[0], k, n), -2, 3).astype(dtype)
    got = kernel.grouped_matmul(lhs, rhs, sizes, block_m=tm, block_sub=sub)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    live = int(sum(CASES[case][3]))
    assert bool(jnp.any(want[:live] != 0))
    assert bool(jnp.all(got[:live] == want[:live]))


def test_the_interpreters_vmem_is_poisoned_past_a_ragged_k(monkeypatch):
    """What keeps lanes K .. Kp of a row sub-tile and rows K .. Kp of a
    weight block out of the second product's contraction is the kernel's
    own zeroing (``_zero_pad``), and the parity cases would see it fail:
    the interpreter hands out scratch VMEM full of NaN, so without the
    zeroing every row of the result is NaN."""
    from jax._src.pallas.primitives import uninitialized_value

    assert bool(jnp.all(jnp.isnan(uninitialized_value((8, 128), BF16))))
    sizes = jnp.asarray([10, 0, 22], jnp.int32)
    lhs, rhs = _operands(48, 144, 128, 3, BF16, 144 ** -0.5)   # K = 9 x 16
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    got = kernel.grouped_matmul(lhs, rhs, sizes)
    assert float(jnp.max(jnp.abs(got[:32].astype(F32)
                                 - want[:32].astype(F32)))) <= 2 ** -6
    monkeypatch.setattr(kernel, "_zero_pad", lambda *a: None)
    kernel._jitted.clear_cache()
    try:
        leaked = kernel.grouped_matmul(lhs, rhs, sizes)
    finally:
        kernel._jitted.clear_cache()
    assert bool(jnp.all(jnp.isnan(leaked[:32].astype(F32))))


@pytest.mark.parametrize("case", ["tile-straddles-three-bf16",
                                  "held-tail", "window-p1", "latent-p1",
                                  "state-space-p1"])
def test_swiglu_epilogue_is_the_activation_bit_for_bit(case):
    rows, k, n, sizes, dtype, tm, sub = CASES[case]
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, rhs = _operands(rows, k, n, sizes.shape[0], dtype, k ** -0.5)
    assert kernel.fuses_swiglu(rhs)
    got = kernel.grouped_matmul(lhs, rhs, sizes, swiglu=True, block_m=tm,
                                block_sub=sub)
    want = swiglu(kernel.grouped_matmul(lhs, rhs, sizes, block_m=tm,
                                        block_sub=sub))
    live = int(sum(CASES[case][3]))
    assert got.shape == (rows, n // 2) and got.dtype == want.dtype
    assert bool(jnp.all(got[:live] == want[:live]))


def test_swiglu_epilogue_needs_gate_and_up_in_one_block():
    s = jax.ShapeDtypeStruct
    assert kernel.fuses_swiglu(s((36, 4096, 1536), BF16))
    assert not kernel.fuses_swiglu(s((4, 128, 384), BF16))    # N / 2 = 192
    assert not kernel.fuses_swiglu(s((4, 8192, 2048), BF16))  # 32 MiB block


def _ffn_inputs(t=64, k=2, e=4, m=128, f=128, published=None):
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    xt = jax.random.normal(keys[0], (t, m), F32)
    ids = jax.random.randint(keys[1], (t, k), 0, published or e)
    gates = jax.nn.softmax(jax.random.normal(keys[2], (t, k), F32))
    w1 = jax.random.normal(keys[3], (e, m, 2 * f), F32) * m ** -0.5
    w2 = jax.random.normal(keys[4], (e, f, m), F32) * f ** -0.5
    return xt, ids, gates, w1, w2


def _paths(fn):
    """``fn()`` on the reference path, then with the kernel forced; the
    counter says which form each call's program took."""
    before = _counts()
    want = fn()
    mid = _counts()
    assert mid["ragged_dot"] == before["ragged_dot"] + 1
    assert mid["kernel"] == before["kernel"]
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        got = fn()
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    assert _counts()["kernel"] == mid["kernel"] + 1
    return got, want


def _gelu_halves(h):
    g, u = jnp.split(h, 2, axis=-1)
    return jax.nn.gelu(g) * u


@pytest.mark.parametrize("act", [swiglu, _gelu_halves],
                         ids=["swiglu-epilogue", "any-callable"])
def test_gradients_are_the_ragged_dot_paths(monkeypatch, act):
    monkeypatch.setattr(kernel, "_BLOCK_M", 32)
    monkeypatch.setattr(kernel, "_BLOCK_SUB", 16)
    xt, ids, gates, w1, w2 = _ffn_inputs()

    def loss(xt, gates, w1, w2):
        y, _ = grouped_expert_ffn(xt, ids, gates, w1, w2, act)
        return jnp.sum(y * jnp.cos(y))

    got, want = _paths(lambda: jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3)))(xt, gates, w1, w2))
    assert abs(float(got[0]) - float(want[0])) < 1e-3
    for g, w in zip(got[1], want[1]):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4


def _kernel_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, in order, however deep
    (the kernel sits inside its ``custom_vjp`` call)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
            continue
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _kernel_calls(inner)


@pytest.mark.parametrize("act,widths", [(swiglu, [128, 128]),
                                        (_gelu_halves, [256, 128])],
                         ids=["swiglu-epilogue", "any-callable"])
def test_swiglu_rides_in_the_first_product(monkeypatch, pallas_forced, act,
                                           widths):
    """``act is swiglu``: the first kernel call writes F columns, not
    2 x F; any other callable gets the plain kernel and runs after it."""
    monkeypatch.setattr(kernel, "_BLOCK_M", 32)
    xt, ids, gates, w1, w2 = _ffn_inputs()
    jaxpr = jax.make_jaxpr(lambda *a: grouped_expert_ffn(*a, act))(
        xt, ids, gates, w1, w2)
    assert [e.outvars[0].aval.shape[-1]
            for e in _kernel_calls(jaxpr.jaxpr)] == widths


def test_held_absent_choices_add_exact_zeros(monkeypatch):
    monkeypatch.setattr(kernel, "_BLOCK_M", 16)
    monkeypatch.setattr(kernel, "_BLOCK_SUB", 16)
    # ids range over 8 published experts, this chip holds 2..5: about half
    # of the choices sort past every group, rows the kernel never visits
    xt, ids, gates, w1, w2 = _ffn_inputs(t=96, k=2, e=4, published=8)
    absent = jnp.all((ids < 2) | (ids >= 6), axis=1)
    assert 4 < int(absent.sum()) < 92

    def run():
        return jax.jit(lambda *a: grouped_expert_ffn(
            *a, swiglu, held=(2, 4)))(xt, ids, gates, w1, w2)

    (y, rows), (y_ref, rows_ref) = _paths(run)
    assert bool(jnp.all(rows == rows_ref))
    assert int(rows.sum()) == int(((ids >= 2) & (ids < 6)).sum())
    # a token none of whose choices is held gets EXACTLY zero, whatever
    # the never-visited rows of the kernel's buffers held
    assert bool(jnp.all(jnp.where(absent[:, None], y, 0) == 0))
    assert bool(jnp.all(jnp.isfinite(y)))
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4


def _relu2(h):
    return jnp.square(jax.nn.relu(h))


def _traced_path(t, k, e, m, f1, f2, held=None, layers=1, dtype=BF16):
    """The ``path`` one traced program of ``layers`` expert layers raised
    the counter by (shapes only: nothing is computed). Gated experts where
    F1 = 2 x F2, ungated relu^2 ones where F1 = F2."""
    before = _counts()
    s = jax.ShapeDtypeStruct
    act = swiglu if f1 == 2 * f2 else _relu2

    def program(xt, ids, gates, w1, w2):
        for _ in range(layers):
            xt, _ = grouped_expert_ffn(xt, ids, gates, w1, w2, act,
                                       held=held)
        return xt

    jax.eval_shape(program, s((t, m), dtype), s((t, k), jnp.int32),
                   s((t, k), F32), s((e, m, f1), dtype),
                   s((e, f2, m), dtype))
    raised = {p: v - before[p] for p, v in _counts().items()
              if v != before[p]}
    assert sum(raised.values()) == 1, raised     # once a program
    return next(iter(raised))


# positions a step, choices a token, (held) experts, M, F1, F2, held=
WINDOW = (8, 128, 2048, 2048, 1024)
LATENT = (6, 128, 2048, 1536, 768)
STATE_SPACE = (10, 36, 4096, 1536, 768)
NEMOTRON = (6, 64, 2688, 1856, 1856)
SOLAR_OPEN2 = (8, 40, 4096, 2560, 1280)


@pytest.mark.parametrize("shapes,positions,held,want", [
    # a mixed step's positions: 8 x 1,024, 32 x 512, 64 x 128 (two tiles)
    (WINDOW, 8192, None, "kernel"),
    (LATENT, 16384, None, "kernel"),
    (STATE_SPACE, 8192, (0, 36), "kernel"),
    # 64 x 128 positions, 768 rows a group; 1856 is 116 sublane tiles
    (NEMOTRON, 8192, (0, 64), "kernel"),
    # 96 x 128 positions, 2,457 rows a group; the first product's block
    # is over 16 MiB: two N tiles
    (SOLAR_OPEN2, 12288, (0, 40), "kernel"),
    # a decode step's rows, 4 (0.5 at the cell's 8 slots), 1.5, 17.8, 6
    # and 19.2 rows a group: the 16-row tile, whatever ragged-dot would
    # tile the widths by (ISSUE 47)
    (WINDOW, 64, None, "kernel"),
    (WINDOW, 8, None, "kernel"),
    (LATENT, 32, None, "kernel"),
    (STATE_SPACE, 64, (0, 36), "kernel"),
    (NEMOTRON, 64, (0, 64), "kernel"),
    (SOLAR_OPEN2, 96, (0, 40), "kernel"),
    # between decode and prefill (a short mixed step): 64, 128 and 256
    # rows a group, the row tiles of as many rows
    (STATE_SPACE, 231, (0, 36), "kernel"),
    (STATE_SPACE, 461, (0, 36), "kernel"),
    (STATE_SPACE, 922, (0, 36), "kernel"),
    # a K that is not whole sublane tiles (F2 = 1000 = 62.5 x 16)
    ((8, 128, 2048, 2000, 1000), 8192, None, "ragged_dot"),
    # both widths of a product not whole lanes
    ((6, 64, 1856, 1856, 1856), 8192, None, "ragged_dot"),
    # whole sublane tiles of bfloat16 are 16 rows, of float32 8
    ((6, 64, 2688, 1864, 1864), 8192, None, "ragged_dot"),
], ids=["window-prefill", "latent-prefill", "state-space-prefill",
        "nemotron-prefill", "solar-open2-prefill", "window-decode",
        "window-decode-8-slots", "latent-decode", "state-space-decode",
        "nemotron-decode", "solar-open2-decode",
        "state-space-64-rows-a-group",
        "state-space-128-rows-a-group", "state-space-256-rows-a-group",
        "k-not-whole-lanes", "k-and-n-not-whole-lanes",
        "half-a-sublane-tile"])
def test_the_rule_reads_the_shapes(pallas_forced, shapes, positions, held,
                                   want):
    k, e, m, f1, f2 = shapes
    assert _traced_path(positions, k, e, m, f1, f2, held, layers=3) == want


def test_the_tiles_read_the_shapes():
    """512 / 128 where a group fills a row tile (every prefill shape of
    the five expert cells), halved down to one 16-row sub-tile below it."""
    assert kernel._tiles(49152, 64) == (512, 128)
    assert kernel._tiles(65536, 128) == (512, 128)
    assert kernel._tiles(40960, 36) == (512, 128)
    assert kernel._tiles(384, 64) == (16, 16)      # the Nemotron decode
    assert kernel._tiles(640, 36) == (16, 16)
    assert kernel._tiles(64, 128) == (16, 16)
    assert kernel._tiles(768, 40) == (16, 16)      # the Solar-Open2 decode
    assert kernel._tiles(6400, 36) == (128, 128)
    assert kernel._tiles(2560, 36) == (64, 64)
    assert kernel._tiles(9220, 36) == (256, 128)


def test_the_rule_keeps_ragged_dot_off_the_tpu():
    """No TPU and nothing forced (the CPU backend of this suite): the
    prefill shapes take ``ragged_dot`` too."""
    k, e, m, f1, f2 = WINDOW
    assert _traced_path(8192, k, e, m, f1, f2) == "ragged_dot"


def test_the_rule_keeps_ragged_dot_under_a_mesh(pallas_forced):
    import numpy as np
    from jax.sharding import Mesh

    from paddle_tpu.parallel.mesh import MeshScope

    k, e, m, f1, f2 = WINDOW
    with MeshScope(Mesh(np.array(jax.devices()[:2]), ("dp",))):
        assert _traced_path(8192, k, e, m, f1, f2) == "ragged_dot"
    assert _traced_path(8192, k, e, m, f1, f2) == "kernel"


def test_the_counter_is_on_an_engines_registry():
    from paddle_tpu.obs.serving import ServingObs

    text = ServingObs().registry.prometheus()
    assert "moe_products_programs_total" in text
    assert moe_layer.moe_products_programs() is moe_products_programs()
