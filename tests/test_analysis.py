"""paddle_tpu.analysis — graph auditor + budget mechanism + golden
fingerprint drift gate.

Each IR pass gets a KNOWN-BAD function it must flag and a KNOWN-CLEAN
function it must not, plus the registered real-recipe budgets AND
their checked-in golden fingerprints (tests/goldens/<recipe>.json)
which must hold on the current code — these are the machine-checked
"did not regress the compiled graph" guarantees every future perf PR
inherits. The serving recipes' budget+fingerprint gates live in
tests/test_serving.py next to the engine tests; the CLI (--check /
--fingerprint, success and failure paths) is exercised end-to-end
here."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.parallel import mesh as mesh_state


@pytest.fixture(autouse=True)
def reset_mesh():
    yield
    mesh_state.set_mesh(None)


def _mesh(shape, axes):
    return Mesh(np.array(jax.devices()).reshape(*shape), axes)


# ---------------------------------------------------------------- census

def test_collective_census_counts_and_bytes():
    mesh = _mesh((8,), ("dp",))

    def step(p, x):
        g = jnp.dot(x, p)
        return p - 0.1 * jnp.dot(x.T, g)

    p = jax.device_put(jnp.zeros((64, 64)), NamedSharding(mesh, P()))
    x = jax.device_put(jnp.ones((8, 64)),
                       NamedSharding(mesh, P("dp")))
    report = analysis.audit(jax.jit(step), p, x)
    # dp grads reduce over the mesh: exactly one all-reduce of the
    # (64, 64) f32 gradient
    st = report.collectives["all-reduce"]
    assert st.count == 1
    assert st.bytes == 64 * 64 * 4
    assert report.collectives["all-gather"].count == 0
    assert report.total_collectives == 1


def test_parse_shape_bytes_tuple_and_scalars():
    from paddle_tpu.analysis.collectives import parse_shape_bytes

    assert parse_shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
    assert parse_shape_bytes("(bf16[4,4], f32[2])") == 4 * 4 * 2 + 2 * 4
    assert parse_shape_bytes("pred[]") == 1


def test_census_known_clean_single_device():
    report = analysis.audit(lambda a, b: jnp.dot(a, b),
                            jnp.ones((4, 4)), jnp.ones((4, 4)))
    assert report.total_collectives == 0


# ----------------------------------------------------------------- remat

def test_remat_pass_flags_incompatible_reshard():
    """Known-bad: a mid-graph sharding flip between transposed device
    orders forces GSPMD into replicate-then-repartition."""
    mesh = _mesh((4, 2), ("sharding", "mp"))
    v = jax.device_put(jnp.zeros((64, 64)),
                       NamedSharding(mesh, P(None, "mp")))

    def bad(a):
        b = jax.lax.with_sharding_constraint(
            jnp.sin(a), NamedSharding(mesh, P("sharding", None)))
        return jnp.cos(b)

    report = analysis.audit(jax.jit(bad), v)
    assert len(report.remat_events) >= 1
    ev = report.remat_events[0]
    assert ev.from_sharding and ev.to_sharding
    with pytest.raises(analysis.BudgetViolation, match="remat"):
        analysis.check_budget(jax.jit(bad),
                              analysis.Budget(max_remat=0), v)


def test_remat_pass_clean_on_consistent_layout():
    mesh = _mesh((4, 2), ("sharding", "mp"))
    v = jax.device_put(jnp.zeros((64, 64)),
                       NamedSharding(mesh, P(None, "mp")))

    def clean(a):
        return jnp.cos(jnp.sin(a))

    report = analysis.check_budget(
        jax.jit(clean), analysis.Budget(max_remat=0), v)
    assert report.remat_events == []


# ----------------------------------------------------------------- dtype

def test_dtype_pass_flags_deliberate_f32_upcast():
    """Known-bad: bf16 operands promoted to f32 before the matmul —
    the exact mistake that silently halves MXU rate."""
    def bad(w, x):
        return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32))

    w = jnp.zeros((4, 4), jnp.bfloat16)
    x = jnp.zeros((2, 4), jnp.bfloat16)
    report = analysis.audit(bad, w, x)
    assert len(report.dtype.f32_compute) == 1
    assert report.dtype.f32_compute[0].primitive == "dot_general"
    assert report.dtype.upcasts == 2
    with pytest.raises(analysis.BudgetViolation, match="f32"):
        analysis.check_budget(
            bad, analysis.Budget(max_f32_matmuls=0), w, x)


def test_dtype_pass_clean_on_bf16_matmul():
    def clean(w, x):
        y = jnp.dot(x, w)          # stays bf16
        return y.sum(dtype=jnp.float32)  # f32 REDUCTION is fine

    w = jnp.zeros((4, 4), jnp.bfloat16)
    x = jnp.zeros((2, 4), jnp.bfloat16)
    report = analysis.check_budget(
        clean, analysis.Budget(max_f32_matmuls=0), w, x)
    assert report.dtype.f32_compute == []


def test_dtype_pass_sees_through_scan():
    """Taint must follow bf16 values into sub-jaxprs (scan bodies are
    where decode-loop upcasts hide)."""
    def bad(w, xs):
        def body(c, x):
            y = jnp.dot(x.astype(jnp.float32),
                        w.astype(jnp.float32))
            return c + y.sum(), y
        return jax.lax.scan(body, jnp.float32(0), xs)

    w = jnp.zeros((4, 4), jnp.bfloat16)
    xs = jnp.zeros((3, 2, 4), jnp.bfloat16)
    report = analysis.audit(bad, w, xs)
    assert any(ev.path for ev in report.dtype.f32_compute), \
        report.dtype.f32_compute


# -------------------------------------------------------------- donation

def test_donation_pass_flags_undonated_train_state():
    """Known-bad: an update step whose state rides through undonated —
    XLA must double-buffer the params."""
    def update(p, g):
        return p - 0.1 * g

    p = jnp.zeros((128, 128))
    g = jnp.ones((128, 128))
    bad = jax.jit(update)                       # nothing donated
    good = jax.jit(update, donate_argnums=(0,))

    rep_bad = analysis.audit(bad, p, g)
    assert rep_bad.donation.donated_count == 0
    rep_good = analysis.audit(good, p, g)
    assert rep_good.donation.args[0].donated
    assert not rep_good.donation.args[1].donated


def test_donation_budget_on_jitted_train_step():
    """JittedTrainStep declares its donatable leaves; require_donated
    passes with donate=True and fails with donate=False."""
    from paddle_tpu.jit.train import JittedTrainStep
    import paddle_tpu.nn as nn

    def build(donate):
        paddle.seed(0)
        model = nn.Linear(16, 16)
        opt = paddle.optimizer.AdamW(1e-3,
                                     parameters=model.parameters())
        mse = nn.MSELoss()
        return JittedTrainStep(model, lambda o, y: mse(o, y), opt,
                               donate=donate)

    x = paddle.to_tensor(np.ones((4, 16), np.float32))
    good = build(donate=True)
    report = analysis.check_budget(
        good, analysis.Budget(require_donated=True, max_remat=0), x, x)
    assert report.donation.undonated() == []

    bad = build(donate=False)
    with pytest.raises(analysis.BudgetViolation, match="donat"):
        analysis.check_budget(
            bad, analysis.Budget(require_donated=True), x, x)


# ---------------------------------------------------------------- budget

def test_budget_rejects_unknown_fields():
    with pytest.raises(TypeError, match="unknown budget field"):
        analysis.Budget(max_all_gather=3)  # typo'd name


def test_budget_violations_aggregate():
    mesh = _mesh((8,), ("dp",))

    def step(p, x):
        g = jnp.dot(x, p)
        return p - 0.1 * jnp.dot(x.T, g)

    p = jax.device_put(jnp.zeros((64, 64)), NamedSharding(mesh, P()))
    x = jax.device_put(jnp.ones((8, 64)),
                       NamedSharding(mesh, P("dp")))
    jitted = jax.jit(step)
    with pytest.raises(analysis.BudgetViolation) as ei:
        analysis.check_budget(
            jitted,
            analysis.Budget(name="toy", max_all_reduces=0,
                            max_collective_bytes=0), p, x)
    msg = str(ei.value)
    assert "all-reduce count" in msg and "collective bytes" in msg
    assert ei.value.report.total_collectives == 1


# --------------------------------------------------- real-recipe budgets

def test_recipe_budget_tp_zero_fused_lce():
    """The round-5 hybrid recipe compiles within its declared budget:
    0 involuntary remats, the stage-2 reduce-scatter decision present,
    every param/state/buffer leaf donated, bounded all-gather count,
    peak live bytes capped, no replicated weight leaves — and the full
    fingerprint matches the checked-in TP2 x ZeRO golden (same report,
    no extra compile)."""
    report = analysis.run_recipe("llama_tp_zero_fused_lce")
    assert report.remat_events == []
    assert report.collectives["all-gather"].count > 0  # TP really talks
    assert report.donation.undonated() == []
    # the sharding pass sees the layout: params + moments carry a real
    # axis, only the 1-D norm scales (256 B) replicate
    assert report.sharding.sharded_param_count >= 40
    assert report.sharding.max_replicated_param_bytes <= 4096
    analysis.check_recipe_fingerprint("llama_tp_zero_fused_lce", report)


def test_recipe_budget_decode_greedy():
    """The single-chip bf16 serving loop: no collectives (any would be
    an accidental mesh dependency), the bf16 graph stays bf16, temp and
    output allocations stay tiny — and the fingerprint matches its
    golden."""
    report = analysis.run_recipe("llama_decode_greedy")
    assert report.total_collectives == 0
    assert report.dtype is not None
    assert report.dtype.f32_compute == []
    assert report.memory.temp_bytes is not None
    analysis.check_recipe_fingerprint("llama_decode_greedy", report)


def test_audit_summary_is_printable():
    report = analysis.audit(lambda a: a * 2, jnp.ones((4,)))
    text = report.summary()
    assert "collectives" in text and "remat" in text
    assert "memory" in text and "sharding" in text


def test_audit_summary_is_dict_order_independent():
    """The summary text must not depend on dict insertion order —
    fingerprint diffs and capfd assertions read it verbatim."""
    report = analysis.audit(lambda a: a * 2, jnp.ones((4,)))
    base = report.summary()
    report.collectives = dict(
        sorted(report.collectives.items(), reverse=True))
    assert report.summary() == base


# ---------------------------------------------------------------- memory

def test_liveness_walk_donation_savings():
    """A donated input that dies early shrinks peak live bytes; an
    undonated one is held for the whole program."""
    from paddle_tpu.analysis import jaxpr_liveness

    def f(p, g):
        a = p * 2.0          # p's last use: dies here if donated
        b = a + g
        c = b * g
        return c

    args = (jnp.ones((256, 256)), jnp.ones((256, 256)))
    closed = jax.make_jaxpr(f)(*args)
    donated = jaxpr_liveness(closed, donated=(0,))
    held = jaxpr_liveness(closed, donated=())
    assert donated.donation_savings_bytes > 0
    assert donated.peak_live_bytes < held.peak_live_bytes
    assert held.donation_savings_bytes == 0
    assert donated.largest_buffer_bytes == 256 * 256 * 4
    # the walk sees through the single pjit eqn jax.jit wraps around
    closed_jit = jax.make_jaxpr(jax.jit(f))(*args)
    assert jaxpr_liveness(closed_jit, donated=(0,)).peak_live_bytes \
        == donated.peak_live_bytes


def test_memory_budget_caps_enforced():
    """max_temp_bytes / max_peak_live_bytes / max_output_bytes trip on
    a known-fat program and pass with honest headroom."""
    def fat(a):
        return jnp.dot(a, a)

    a = jnp.ones((64, 64))
    with pytest.raises(analysis.BudgetViolation) as ei:
        analysis.check_budget(
            fat, analysis.Budget(name="toy-mem", max_temp_bytes=0,
                                 max_peak_live_bytes=1,
                                 max_output_bytes=1), a)
    msg = str(ei.value)
    assert "peak live bytes" in msg and "output bytes" in msg
    report = analysis.check_budget(
        fat, analysis.Budget(max_peak_live_bytes=10 * 64 * 64 * 4), a)
    assert report.memory.peak_live_bytes >= 2 * 64 * 64 * 4
    assert report.memory.compiler is not None  # CPU backend reports


# -------------------------------------------------------------- sharding

def test_sharding_attr_classification():
    """_classify returns (replicated, unknown): every recognized syntax
    parses with unknown=False; unrecognized syntax is classified
    replicated (strict fallback) but COUNTED unknown so a report can
    tell a parser gap from an actually-replicated leaf."""
    from paddle_tpu.analysis.sharding import _classify

    assert _classify("") == (True, False)
    assert _classify(None) == (True, False)
    assert _classify("{replicated}") == (True, False)
    assert _classify("{maximal device=0}") == (True, False)
    assert _classify(
        "{devices=[1,1,8]<=[8] last_tile_dim_replicate}") == (True, False)
    assert _classify("{devices=[2,4]<=[8]}") == (False, False)
    assert _classify(
        "{devices=[2,1,4]<=[8] last_tile_dim_replicate}") == (False, False)
    # unknown syntax: strict (replicated) AND counted
    assert _classify("{v2_tuple_shardings_from_the_future}") == (True, True)


def test_sharding_unknown_syntax_counted_in_report():
    """An entry arg carrying unparseable sharding syntax lands in the
    report as replicated (the audit stays strict) with unknown_count
    nonzero — and summary_dict only GROWS the unknown_shardings key in
    that case, so every existing golden (all-parsed) stays
    byte-identical."""
    from paddle_tpu.analysis.sharding import audit_sharding

    hlo = (
        'func.func public @main('
        '%arg0: tensor<4x4xf32> {mhlo.sharding = "{devices=[2,1]<=[2]}"}, '
        '%arg1: tensor<4x4xf32> {mhlo.sharding = "{weird_future_repr}"}, '
        '%arg2: tensor<4xf32>) -> tensor<4xf32> {'
    )
    rep = audit_sharding(hlo)
    assert rep.sharded_count == 1
    assert rep.unknown_count == 1
    unk = [a for a in rep.args if a.unknown]
    assert len(unk) == 1 and unk[0].replicated  # strict fallback holds
    assert "unknown syntax" in repr(unk[0])
    assert rep.summary_dict()["unknown_shardings"] == 1
    # the common fully-parsed case: key absent -> goldens untouched
    clean = audit_sharding(hlo.replace("{weird_future_repr}",
                                       "{replicated}"))
    assert clean.unknown_count == 0
    assert "unknown_shardings" not in clean.summary_dict()


def test_sharding_shardy_attrs_classified():
    """The installed JAX lowers with the Shardy partitioner: layouts
    are ``#sdy.sharding<@mesh, [dims]>`` over a module-level mesh. A
    dim that names an axis of size > 1 is sharded; empty dims, or an
    axis of size 1, are replicated."""
    from paddle_tpu.analysis.sharding import audit_sharding

    hlo = (
        'sdy.mesh @mesh = <["dp"=1, "mp"=2, "sharding"=4]>\n'
        'func.func public @main('
        '%arg0: tensor<64x128xf32> {sdy.sharding = #sdy.sharding<@mesh, '
        '[{"mp", "sharding"}, {}]>, tf.aliasing_output = 0 : i32}, '
        '%arg1: tensor<64xf32> {sdy.sharding = #sdy.sharding<@mesh, '
        '[{}]>}, '
        '%arg2: tensor<8x4xf32> {sdy.sharding = #sdy.sharding<@mesh, '
        '[{"dp"}, {}]>}, '
        '%arg3: tensor<4xf32>) -> tensor<4xf32> {'
    )
    rep = audit_sharding(hlo, n_donatable=2)
    assert [a.replicated for a in rep.args] == [False, True, True, True]
    assert rep.sharded_param_count == 1 and rep.unknown_count == 0
    assert rep.max_replicated_param_bytes == 64 * 4


def test_sharding_pass_flags_replicated_param():
    """Known-bad: a large param left replicated over a real mesh while
    the mesh is in play; max_replicated_param_bytes catches it, and the
    sharded variant passes the same budget."""
    mesh = _mesh((8,), ("dp",))

    class _Declared:
        """jitted target + n_donatable (the param is arg 0)."""

        def __init__(self, jitted):
            self._jitted = jitted
            self.n_donatable = 1
            self.__name__ = "declared_step"

        def lower(self, *args):
            return self._jitted.lower(*args)

    def step(p, x):
        return p, (x @ p).sum()

    p_rep = jax.device_put(jnp.zeros((128, 128)),
                           NamedSharding(mesh, P()))
    p_shard = jax.device_put(jnp.zeros((128, 128)),
                             NamedSharding(mesh, P("dp", None)))
    x = jax.device_put(jnp.ones((8, 128)), NamedSharding(mesh, P("dp")))
    budget = analysis.Budget(name="no-fat-replicas",
                             max_replicated_param_bytes=1024,
                             min_sharded_params=1)
    target = _Declared(jax.jit(step, donate_argnums=(0,)))
    with pytest.raises(analysis.BudgetViolation) as ei:
        analysis.check_budget(target, budget, p_rep, x)
    assert "replicated donatable leaves" in str(ei.value)
    report = analysis.check_budget(target, budget, p_shard, x)
    assert report.sharding.sharded_param_count == 1


# ----------------------------------------------------------- fingerprint

def test_fingerprint_mutation_produces_readable_diff():
    """Acceptance: dropping donate_argnums in a test-local copy of a
    step drifts the fingerprint with a field-level, human-readable
    diff."""
    def update(p, g):
        return p - 0.1 * g

    p, g = jnp.zeros((64, 64)), jnp.ones((64, 64))
    golden_report = analysis.audit(
        jax.jit(update, donate_argnums=(0,)), p, g)
    golden = analysis.fingerprint_report(golden_report, name="toy")
    mutated_report = analysis.audit(jax.jit(update), p, g)  # donation lost
    mutated = analysis.fingerprint_report(mutated_report, name="toy")
    diff = analysis.compare_fingerprint(golden, mutated)
    assert diff, "dropped donation must drift the fingerprint"
    text = "\n".join(diff)
    assert "donation.donated: golden 1 != current 0 (-1)" in text
    # identical audits do NOT drift
    assert analysis.compare_fingerprint(golden, golden) == []


def test_fingerprint_golden_roundtrip(tmp_path):
    report = analysis.audit(lambda a: a * 2, jnp.ones((64,)))
    fp = analysis.fingerprint_report(report, name="roundtrip")
    analysis.save_golden(fp, "roundtrip", goldens_dir=str(tmp_path))
    assert analysis.load_golden("roundtrip",
                                goldens_dir=str(tmp_path)) == fp
    assert analysis.check_recipe_fingerprint(
        "roundtrip", report, goldens_dir=str(tmp_path)) == fp
    with pytest.raises(analysis.FingerprintMismatch, match="no golden"):
        analysis.check_recipe_fingerprint(
            "never_saved", report, goldens_dir=str(tmp_path))


# ------------------------------------------------- CLI (serving recipes)

def test_cli_check_and_fingerprint_serving_recipe(capsys):
    """`python -m paddle_tpu.analysis --recipe serving_decode_step
    --check --fingerprint` end-to-end: budget enforced and golden
    compared in one invocation, exit 0, readable output."""
    from paddle_tpu.analysis.__main__ import main

    rc = main(["--recipe", "serving_decode_step", "--check",
               "--fingerprint"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "budget [serving decode quantum" in out and "OK" in out
    assert "fingerprint: OK" in out
    assert "memory (compiler):" in out and "sharding:" in out


def test_cli_failure_paths_print_readable_diff(tmp_path, capsys,
                                               monkeypatch):
    """Injected violation + doctored golden: the CLI exits 1 and prints
    BOTH the budget violation and the per-field fingerprint diff."""
    from paddle_tpu.analysis import fingerprint as fpm
    from paddle_tpu.analysis import recipes
    from paddle_tpu.analysis.__main__ import main

    orig = recipes.RECIPES["serving_decode_step"]

    def tightened():
        recipe = orig()
        recipe.budget.max_temp_bytes = 1  # impossible: injected violation
        return recipe

    monkeypatch.setitem(recipes.RECIPES, "serving_decode_step",
                        tightened)
    golden = fpm.load_golden("serving_decode_step")
    assert golden is not None, "checked-in golden missing"
    golden["involuntary_remat"] = 7  # doctored: force a drift
    fpm.save_golden(golden, "serving_decode_step",
                    goldens_dir=str(tmp_path))

    rc = main(["--recipe", "serving_decode_step", "--check",
               "--fingerprint", "--goldens-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "VIOLATED" in out
    assert "compiled temp bytes" in out  # the injected budget breach
    assert "fingerprint: drift" in out
    assert "involuntary_remat: golden 7 != current 0 (-7)" in out
