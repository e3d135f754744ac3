"""paddle_tpu.analysis.cost — static FLOP/byte accounting, roofline
floors, and the cross-source agreement gate (ISSUE 16).

Walker level: exact dot_general arithmetic, transcendental tracking,
scan unroll-vs-static views, per-token scaling.

Cross-check level: the backend-independent jaxpr walk agrees with
XLA's ``cost_analysis()`` within the pinned band on matmul and
attention micro-cases — the same gate `--cost` enforces per recipe.

Degradation level: a compiled object whose ``cost_analysis`` is
absent, raises, or returns partial/odd shapes yields ``source="jaxpr"``
(never an exception, never a guessed number).

Roofline level: classification flips exactly at the chip's ridge
intensity across a synthetic sweep, the device floor is
``max(flops/peak, bytes/bw)``, and the `--cost` CLI reads no record
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.analysis.cost import (
    AGREEMENT_BAND, CHIP_SPECS, CostReport, CostStats, DEFAULT_CHIP,
    analyze_cost, jaxpr_cost, roofline, xla_cost_stats,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- jaxpr walker

def test_matmul_walker_is_exact():
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    stats = jaxpr_cost(jax.make_jaxpr(jnp.matmul)(a, b))
    assert stats.source == "jaxpr"
    assert stats.flops == 2 * 64 * 128 * 32
    # bytes: both operands read + output written, 4B elements
    assert stats.bytes_accessed == 4 * (64 * 128 + 128 * 32 + 64 * 32)
    assert stats.transcendentals == 0


def test_transcendentals_counted_separately():
    x = jnp.ones((100,), jnp.float32)
    stats = jaxpr_cost(jax.make_jaxpr(lambda x: jnp.exp(x) + 1.0)(x))
    assert stats.transcendentals == 100
    # the add is flops, the exp is not
    assert stats.flops == 100


def test_scan_unrolled_vs_static_views():
    """The unrolled view multiplies the body by the trip count (device
    work per dispatch); the static view counts it once (XLA's
    cost-analysis convention) — the ratio between them is the trip
    count on a body-dominated program."""
    w = jnp.ones((32, 32), jnp.float32)
    xs = jnp.ones((10, 32), jnp.float32)

    def scanned(w, xs):
        def body(carry, x):
            return carry @ w + x, ()
        out, _ = jax.lax.scan(body, xs[0], xs)
        return out

    closed = jax.make_jaxpr(scanned)(w, xs)
    unrolled = jaxpr_cost(closed, unroll_loops=True)
    static = jaxpr_cost(closed, unroll_loops=False)
    body_matmul = 2 * 32 * 32  # (32,) @ (32, 32) vector-matrix
    assert static.flops >= body_matmul
    assert unrolled.flops >= 10 * body_matmul
    assert unrolled.flops == pytest.approx(10 * static.flops)


def test_free_primitives_cost_bytes_not_flops():
    x = jnp.ones((8, 8), jnp.float32)
    stats = jaxpr_cost(
        jax.make_jaxpr(lambda x: jnp.transpose(x).reshape(64))(x))
    assert stats.flops == 0
    assert stats.bytes_accessed > 0


# ------------------------------------------------ cross-source check

def _cross_check(f, *args):
    compiled = jax.jit(f).lower(*args).compile()
    xla = xla_cost_stats(compiled)
    jx = jaxpr_cost(jax.make_jaxpr(f)(*args), unroll_loops=False)
    assert xla is not None and xla.source == "xla"
    return xla, jx


def test_matmul_agreement_within_band():
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    xla, jx = _cross_check(lambda a, b: a @ b, a, b)
    assert xla.flops > 0
    ratio = jx.flops / xla.flops
    assert AGREEMENT_BAND[0] <= ratio <= AGREEMENT_BAND[1], ratio


def test_attention_agreement_within_band():
    q = jnp.ones((4, 16, 64), jnp.float32)
    k = jnp.ones((4, 16, 64), jnp.float32)
    v = jnp.ones((4, 16, 64), jnp.float32)

    def attn(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) / 8.0
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s), v)

    xla, jx = _cross_check(attn, q, k, v)
    ratio = jx.flops / xla.flops
    assert AGREEMENT_BAND[0] <= ratio <= AGREEMENT_BAND[1], ratio


# --------------------------------------------------- degraded sources

class _StubCompiled:
    def __init__(self, result=None, raise_=False):
        self._result = result
        self._raise = raise_

    def cost_analysis(self):
        if self._raise:
            raise RuntimeError("unimplemented on this backend")
        return self._result

    def __getattr__(self, name):
        raise AttributeError(name)


class _StubLowered:
    """LoweredTarget-shaped stub: .compiled() and .jaxpr()."""

    def __init__(self, compiled, jaxpr):
        self._compiled = compiled
        self._jaxpr = jaxpr

    def compiled(self):
        if self._compiled is None:
            raise RuntimeError("compile failed")
        return self._compiled

    def jaxpr(self):
        return self._jaxpr


@pytest.mark.parametrize("compiled", [
    None,                                        # compile raises
    _StubCompiled(result=None),                  # hook returns None
    _StubCompiled(raise_=True),                  # hook raises
    _StubCompiled(result=[]),                    # empty list
    _StubCompiled(result=[{"bytes accessed": 1.0}]),   # flops missing
    _StubCompiled(result=[{"flops": 2.0}]),      # bytes missing
    _StubCompiled(result=[{"flops": True,
                           "bytes accessed": 4.0}]),   # bool is not a count
], ids=["compile-raises", "returns-none", "hook-raises", "empty-list",
        "no-flops", "no-bytes", "bool-flops"])
def test_degrades_to_jaxpr_source(compiled):
    """Satellite: absent/None/partial/raising cost_analysis never
    fails the audit — the report degrades to the walker."""
    x = jnp.ones((8, 8), jnp.float32)
    closed = jax.make_jaxpr(lambda x: x @ x)(x)
    report = analyze_cost(_StubLowered(compiled, closed))
    assert report.xla is None
    assert report.source == "jaxpr"
    assert report.flops == 2 * 8 * 8 * 8
    # one source only: the cross-check is vacuous (None), not failing
    assert report.flops_ratio is None
    assert report.agreement_ok() is None


def test_no_views_at_all_is_empty_not_raising():
    report = analyze_cost(_StubLowered(None, None))
    assert report.source is None and report.flops is None


def test_per_token_scaling():
    x = jnp.ones((8, 8), jnp.float32)
    report = analyze_cost(
        _StubLowered(None, jax.make_jaxpr(lambda x: x @ x)(x)))
    f_tok, b_tok = report.per_token(8)
    assert f_tok == report.flops / 8
    assert b_tok == report.bytes_accessed / 8


# ------------------------------------------------------------ roofline

def test_roofline_classification_flips_at_ridge():
    """Synthetic sweep: fixed byte traffic, growing flops — the bound
    flips from memory to compute exactly at the chip's ridge."""
    spec = CHIP_SPECS[DEFAULT_CHIP]
    byts = 1e6
    seen = []
    for mult in (0.25, 0.5, 0.99, 1.01, 2.0, 8.0):
        rl = roofline(spec.ridge_intensity * byts * mult, byts)
        seen.append(rl.bound)
        expected = "compute" if mult >= 1.0 else "memory"
        assert rl.bound == expected, (mult, rl.intensity)
    assert seen == ["memory"] * 3 + ["compute"] * 3


def test_roofline_floor_is_max_of_both_terms():
    spec = CHIP_SPECS["v5e"]
    # memory-bound point: floor set by bytes/bw
    rl = roofline(1e6, 1e9, chip="v5e")
    assert rl.device_floor_s == pytest.approx(1e9 / spec.hbm_bytes_per_sec)
    # compute-bound point: floor set by flops/peak
    rl = roofline(1e15, 1e3, chip="v5e")
    assert rl.device_floor_s == pytest.approx(1e15 / spec.peak_flops)


def test_chip_table_sane():
    for name, spec in CHIP_SPECS.items():
        assert spec.peak_flops > 0 and spec.hbm_bytes_per_sec > 0
        assert spec.ridge_intensity == pytest.approx(
            spec.peak_flops / spec.hbm_bytes_per_sec)


def test_cost_cli_needs_no_record_file(capsys):
    """`--cost` reads nothing but the recipe: in a tree with no
    ``BENCH_*.json`` it prints the static roofline line, gates the
    cross-source agreement, exits 0 and prints no host-gap line (what a
    dispatch takes above its floor is the benchmark's to measure)."""
    import glob

    from paddle_tpu.analysis import __main__ as cli

    assert glob.glob(os.path.join(REPO, "BENCH_*.json")) == []
    assert cli.main(["--recipe", "serving_decode_step", "--cost"]) == 0
    out = capsys.readouterr().out
    assert "roofline [" in out and "cost gate: OK" in out
    assert "host gap" not in out and "measured" not in out
