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
import math

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


# ------------------------- the hidden stream moves once each way over mp

def _stream_collectives(hlo_text, rows, width):
    """``{kind: count}`` of the collectives (the census's definitions) one
    of whose results holds ``rows`` rows of the hidden stream (any shape
    of ``rows * width`` elements whose last dim is ``width``), the
    embedding's own excepted: its lookup's partial sums are reduced into
    the stream and its backward gathers the stream's cotangent."""
    found = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0}
    for line in hlo_text.splitlines():
        m = collectives._DEF_RE.search(line)
        if not m or m.group(3) == "-done" or "(embed)" in line:
            continue
        for _, dims in collectives._SHAPE_RE.findall(m.group(1)):
            shape = [int(d) for d in dims.split(",") if d]
            if shape[-1:] == [width] and math.prod(shape) == rows * width:
                found[m.group(2)] += 1
    return found


def _stream_count(path):
    from paddle_tpu.distributed.fleet.layers.mpu.mp_layers import (
        mp_hidden_stream_programs,
    )

    return mp_hidden_stream_programs().value(path=path)


@pytest.mark.parametrize("mp, sharding, batch, seq", [
    pytest.param(2, 2, 4, 48, id="mp2-sharding2"),       # the cell's layout
    pytest.param(2, 4, 4, 48, id="mp2-sharding4"),
    pytest.param(1, 4, 4, 48, id="sharding4"),
    pytest.param(2, 1, 4, 48, id="mp2"),
    pytest.param(2, 2, 3, 48, id="mp2-sharding2-batch3"),
    pytest.param(2, 2, 4, 47, id="mp2-sharding2-seq47"),  # 47 rows over 2
])
def test_hidden_stream_moves_once_each_way_over_mp(mp, sharding, batch, seq):
    """Training's forward on a mesh with ``mp`` > 1 keeps the hidden
    stream split over ``mp`` along the sequence: a tensor-parallel
    half-layer (two a decoder layer, and the head) gathers it once and
    reduce-scatters it once in the forward, and once each in the
    backward; NO all-reduce carries it (the replicated stream's backward
    all-reduced it once a column-parallel product). A length ``mp`` does
    not divide keeps the replicated stream; without ``mp`` neither is
    counted. Every layout computes the single-device step's loss."""
    layers, width = 2, 64
    serial = _serial_first_loss(batch, seq)
    divides = batch % sharding == 0
    step = _tiny_llama_step(mp, sharding, split_batch=divides)
    ids = _cell_ids(batch, seq)
    before = {p: _stream_count(p) for p in ("sequence", "replicated")}
    report = analysis.audit(step._jitted_multi, *step._steps_args(ids, ids))
    counted = {p: _stream_count(p) - n for p, n in before.items()}
    split = mp > 1 and seq % mp == 0
    assert counted == {"sequence": int(split),
                       "replicated": int(mp > 1 and not split)}
    assert report.remat_events == []
    if split:
        rows = (batch // sharding if divides else batch) * seq
        whole = _stream_collectives(report.hlo_text, rows, width)
        half = _stream_collectives(report.hlo_text, rows // mp, width)
        assert whole["all-reduce"] == half["all-reduce"] == 0, (whole, half)
        half_layers = 2 * layers + 1          # the head's group is one
        assert 0 < whole["all-gather"] <= 2 * half_layers, whole
        assert 0 < half["reduce-scatter"] <= 2 * half_layers, half
        assert report.cost.flops_ratio >= 0.9, report.cost.flops_ratio
    np.testing.assert_allclose(step.run_steps(ids, ids).numpy()[0], serial,
                               rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("biased, dtype", [
    pytest.param(False, "float32", id="plain"),
    pytest.param(True, "float32", id="biased"),
    pytest.param(True, "bfloat16", id="bf16"),
])
def test_sequence_split_half_layer_gradients_match_linear(biased, dtype):
    """``column_parallel_group`` and ``row_parallel_scatter`` on the
    8-device mesh (mp 2 x sharding 4) against plain ``F.linear`` on the
    same leaves: the outputs and the gradient of EVERY leaf (the input,
    each weight, each bias). The weights are whole on the data axes
    inside the manual regions; the program asks for the sum of each
    one's gradient over ``sharding`` in FLOAT32 whatever the weight's
    dtype (``_member_linear``), as the partitioner reduces
    ``F.linear``'s: pinned on the lowered program, since the CPU
    compiler widens every bf16 collective and would hide a bf16 one."""
    import re

    import jax.numpy as jnp

    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.fleet.layers.mpu import mp_layers

    mesh_state.set_mesh(jax.sharding.Mesh(
        np.array(jax.devices()).reshape(1, 4, 1, 2),
        ("dp", "sharding", "sep", "mp")))
    paddle.seed(0)
    group = [mp_layers.ColumnParallelLinear(16, 32, has_bias=biased,
                                            gather_output=False),
             mp_layers.ColumnParallelLinear(16, 8, has_bias=False,
                                            gather_output=False),
             mp_layers.ColumnParallelLinear(16, 8, has_bias=biased,
                                            gather_output=True)]
    row = mp_layers.RowParallelLinear(32, 16, has_bias=biased,
                                      input_is_parallel=True)
    x = paddle.randn([4, 8, 16])
    leaves = [x] + [p for m in (*group, row) for p in (m.weight, m.bias)
                    if p is not None]
    for t in leaves:
        t._value = t._value.astype(dtype)
    x.stop_gradient = False

    def forward(axis):
        if axis:
            a, b, c = mp_layers.column_parallel_group(x, group, axis)
            return mp_layers.row_parallel_scatter(a, row, axis), b, c
        a, b, c = [F.linear(x, m.weight, m.bias) for m in group]
        return F.linear(a, row.weight, row.bias), b, c

    def run(axis):
        y, b, c = forward(axis)
        outs = [t.numpy() for t in (y, b, c)]
        ((y * y).sum() + (b * b).sum() + (c * c).sum()).backward()
        grads = [t.grad.numpy().copy() for t in leaves]
        for t in leaves:
            t.clear_grad()
        return [np.asarray(v, np.float32) for v in outs + grads]

    tol = 1e-5 if dtype == "float32" else 4e-2    # bf16: 8 bits, twice
    for got, want in zip(run("mp"), run(None)):
        np.testing.assert_allclose(got, want, rtol=tol,
                                   atol=tol * np.abs(want).max())

    def loss(*values):
        held = [t._value for t in leaves]
        for t, v in zip(leaves, values):
            t._value = v
        try:
            with paddle.no_grad():
                return sum((t._value.astype(jnp.float32) ** 2).sum()
                           for t in forward("mp"))
        finally:
            for t, v in zip(leaves, held):
                t._value = v

    weights = [i for i, t in enumerate(leaves) if t._value.ndim == 2]
    text = jax.jit(jax.grad(loss, argnums=weights)).lower(
        *[t._value for t in leaves]).as_text()
    summed = re.findall(
        r"stablehlo\.all_reduce.*?\n\s*\}\) : \(tensor<([^>]*)>\)", text, re.S)
    assert sorted(summed) == sorted(
        "x".join(map(str, m.weight._value.sharding.shard_shape(
            tuple(m.weight.shape)))) + "xf32" for m in (*group, row)), summed
