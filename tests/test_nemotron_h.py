"""The Nemotron-H-shaped decoder (layers of ONE part each: a Mamba-2 mixer
with groups, attention without positions, or ungated relu^2 routed experts
of which a chip holds its share) on the normal serving path, against the
benchmark's plain reference (``benchmark/reference/nemotron_h.py``: float32,
HIGHEST, the RECURRENCE form of the mixer, a loop over the held experts, no
cache), on the toy configuration in float32.

The weights are the test's own, as ``tests/test_granitemoehybrid.py`` draws
them: the benchmark's seeded ones make ``A`` about -1 and ``dt`` about 0.69,
so the state forgets within ~10 tokens and a wrong carry over a chunk
boundary would hide. Here ``dt_bias`` is about -4 and ``A_log`` in 0..2.7.

Tolerances: program and reference compute the same float32 numbers in
another order (a chunk at a time through decay matrices, rows sorted by
expert, attention folded in tiles), so they differ by summation order only:
logits of magnitude ~1 agree to 5e-5. The reference's int8-operand control
moves the same logits by > 100 x that and a served token's gap to ~1e-2, so
each tolerance below is asserted to be tight enough that the control fails
it.
"""
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import moe_layer
from paddle_tpu.nlp import granitemoehybrid as G
from paddle_tpu.nlp import nemotron_h as N
from paddle_tpu.nlp import routed_experts as R
from paddle_tpu.obs.trace import TraceRecorder
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import nemotron_h as family  # noqa: E402
# the sibling state-space family's helpers, as they are: a device value on
# the host, the door drained, and the engine's two bodies driven by hand
from test_granitemoehybrid import (  # noqa: E402
    _Paged, _drain, _host, _max_abs, _prompts, _serve, _stamp)
# moe_products_programs_total{path}, read as a dict
from test_grouped_matmul import _counts as _products_counts  # noqa: E402

reference = family.reference
LOGIT_TOL = 5e-5     # summation order in float32, logits of magnitude ~1
GAP_TOL = 2e-4       # a served token lies this close to the reference's best


def _slow_leaves(cfg, seed=0):
    """name -> float32 array for every leaf of the reference's table:
    matrices of standard deviation 1/sqrt(fan-in), norms near 1, and a
    state that decays SLOWLY (see the module docstring)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape, kind in reference.leaf_table(cfg):
        short = name.split(".")[-1]
        if short == "dt_bias":
            v = rng.uniform(-4.5, -3.5, shape)
        elif short == "A_log":
            v = np.linspace(0.0, 2.7, shape[0])
        elif kind == "norm":
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif kind == "bias":
            v = 0.1 * rng.standard_normal(shape)
        elif short == "conv_w":
            v = 0.5 * rng.standard_normal(shape)
        elif short == "embed":
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) / np.sqrt(shape[-2])
        out[name] = jnp.asarray(v, jnp.float32)
    return out


def _toy_cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "toy-ssm-relu2-moe.json")) as f:
        return json.load(f)


def _model(cfg, leaves):
    model = family.build_model(cfg)
    _, params = family.parameters(model, cfg)
    for p, (name, _, _) in zip(params, reference.leaf_table(cfg)):
        p._value = leaves[name]
    model.eval()
    return model


@pytest.fixture(scope="module")
def toy():
    cfg = _toy_cfg()
    leaves = _slow_leaves(cfg)
    return cfg, _model(cfg, leaves), leaves.__getitem__


# ------------------------------------------------------ forward, reference
def test_forward_matches_the_reference_logits(toy):
    """Two sequences of 40 tokens, chunks of chunk_size 16 (an uneven last
    chunk) against the recurrence; eight layers ``MEM*EMEM``, each ONE
    part."""
    cfg, model, get_leaf = toy
    ids = np.stack(_prompts(cfg, (40, 40)))
    ref = reference.logits(cfg, get_leaf, ids)
    got = model(paddle.to_tensor(ids))._value
    assert _max_abs(ref) > 0.5
    assert _max_abs(ref, got) < LOGIT_TOL
    # the tolerance is earned: the int8-operand control fails it
    control = reference.logits(cfg, get_leaf, ids, control=True)
    assert _max_abs(ref, control) > 100 * LOGIT_TOL


def test_the_state_decays_slowly_and_the_groups_matter(toy):
    """What the docstring promises of the test's weights: changing the
    FIRST token still moves the logits 30 positions on. And a mixer that
    read group 0's B and C for every head would be seen: with the second
    group's columns of ``in_proj`` swapped for the first's the logits move
    by far more than the tolerance."""
    cfg, _, get_leaf = toy
    only_ssm = dict(cfg, num_hidden_layers=3, hybrid_override_pattern="MEM")
    ids = np.stack(_prompts(cfg, (31,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1
    a, b = (reference.logits(only_ssm, get_leaf, x)[0, -1]
            for x in (ids, other))
    assert _max_abs(a, b) > 100 * LOGIT_TOL
    m = reference.dims(cfg)
    d_in, n = m["d_in"], m["n"]

    def one_group(name):
        w = get_leaf(name)
        if name.endswith("in_w"):       # z | xs | B0 B1 | C0 C1 | dt
            lo = 2 * d_in
            w = w.at[:, lo + n:lo + 2 * n].set(w[:, lo:lo + n])
            w = w.at[:, lo + 3 * n:lo + 4 * n].set(w[:, lo + 2 * n:lo + 3 * n])
        return w

    assert _max_abs(a, reference.logits(only_ssm, one_group, ids)[0, -1]) \
        > 100 * LOGIT_TOL


def test_chunked_prefill_then_decode_through_the_slot_state(toy):
    """A 37-token prompt in chunks that split it unevenly (counts 1, C - 1,
    C, then the rest; C = 16), then 10 decode steps, teacher-forced:
    every logit the program hands out is the reference's full pass's.
    Beside it a row that is never live and a row that is masked in decode
    keep their state bit for bit, and a new request in a used slot starts
    from zero."""
    cfg, model, get_leaf = toy
    seq, other = _prompts(cfg, (47, 21), seed=3)
    ref = reference.logits(cfg, get_leaf, seq[None])[0]
    ref_other = reference.logits(cfg, get_leaf, other[None])[0]
    run = _Paged(model)
    # four M layers on the slot side, one * layer's blocks, three E layers
    # nowhere
    assert len(run.pool.state) == 4 and len(run.pool.k_pools) == 1
    _stamp(run.pool, 1, 7.0)
    at = 0
    for n in (1, 15, 16, 5):
        ids = np.zeros((3, 16), np.int32)
        ids[0, :n] = seq[at:at + n]
        ids[2, :n] = other[at:at + n] if at + n <= 21 else 0
        counts = [n, 0, n if at + n <= 21 else 0]
        logits = run.chunk(ids, counts)
        at += n
        assert _max_abs(logits[0], ref[at - 1]) < LOGIT_TOL
        if counts[2]:
            assert _max_abs(logits[2], ref_other[at - 1]) < LOGIT_TOL
    held = jax.tree_util.tree_map(lambda a: _host(a[2]), run.pool.state)
    for j in range(10):          # row 2 rides along masked, row 1 idle
        logits = run.decode([seq[37 + j], 0, 5], [True, False, False])
        assert _max_abs(logits[0], ref[37 + j]) < LOGIT_TOL
    for layer, want in zip(run.pool.state, held):
        for a, w in zip(layer, want):
            np.testing.assert_array_equal(_host(a[2]), w)   # masked
            assert float(_host(a[1]).min()) == 7.0 == float(
                _host(a[1]).max())                          # never live
    # the slot of row 0 is handed to a new request: its first chunk has
    # base length 0, so the program starts its state from zeros
    run.pool.free("r0")
    run.lens[0] = 0
    ids = np.zeros((3, 16), np.int32)
    ids[0] = other[:16]
    logits = run.chunk(ids, [16, 0, 0])
    assert _max_abs(logits[0], ref_other[15]) < LOGIT_TOL


@pytest.mark.parametrize("chunk,quantum", [(16, 4), (8, 1), (32, 8)])
def test_served_tokens_are_the_references_best(toy, chunk, quantum):
    """Prefill in chunks, then decode, through the engine: every served
    token is the reference's best to within GAP_TOL. Three prompts in four
    slots: an idle slot rides every step."""
    cfg, model, get_leaf = toy
    prompts = _prompts(cfg, (37, 20, 9), seed=chunk)
    door = _serve(model, prefill_chunk=chunk, decode_quantum=quantum)
    served = _drain(door, prompts, 12)
    gaps, _ = reference.gap_below_best(cfg, get_leaf,
                                       list(zip(prompts, served)))
    assert gaps.shape == (36,) and float(_host(gaps).max()) < GAP_TOL
    pool = door.engine.pool
    assert len(pool.k_pools) == 1 == len(pool.v_pools)   # one * layer
    assert tuple(pool.k_pools[0].shape) == (64, 8, 2, 48)
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (4, 8, 16, 32), (4, 3, 8 * 16 + 2 * 2 * 32)]
    assert len(pool.state) == 4                          # four M layers
    assert pool.state[0][0].dtype == jnp.float32


def test_the_int8_control_fails_the_gap_tolerance(toy):
    cfg, model, get_leaf = toy
    prompts = _prompts(cfg, (24, 24, 24, 24), seed=7)
    served = _drain(_serve(model), prompts, 40)
    gaps, cgaps = reference.gap_below_best(
        cfg, get_leaf, list(zip(prompts, served)), control=True)
    assert float(_host(gaps).max()) < GAP_TOL < 10 * GAP_TOL \
        < float(_host(cgaps).max())


def test_a_reused_slot_and_a_preempted_request_continue_exactly(toy):
    """One slot: the second request takes the slot the first left (its
    state starts from zero inside the program). Then a request preempted
    in mid-decode: the slot is freed, recompute-on-resume rebuilds the
    state from prompt + tokens, and the stream is bit for bit the
    uninterrupted one."""
    cfg, model, _ = toy
    prompts = _prompts(cfg, (30, 18), seed=11)
    want = _drain(_serve(model), prompts, 12)
    one = _serve(model, num_slots=1)
    got = [_drain(one, [p], 12)[0] for p in prompts]
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert one.engine.obs.registry.get(
        "serving_state_resets_total").value() == 2

    eng = _serve(model).engine
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    while len(reqs[0].tokens) < 5:
        eng.step()
    eng.preempt(reqs[0])
    eng.run()
    assert reqs[0].preemptions == 1
    for r, b in zip(reqs, want):
        assert np.array_equal(np.asarray(r.tokens, np.int32), b)
    assert eng.obs.registry.get("serving_state_resets_total").value() == 3


# ------------------------------------------------- the mixer and its groups
def test_the_grouped_mixer_at_one_group_is_the_granite_mixer():
    """``Mamba2Mixer`` with ``groups=1`` IS what the Granite family's
    tests hold: ``GraniteMoeHybridMamba`` is that class at its config's
    keys, its parameters have the shapes they had (``in_proj`` ``d_in | d_in
    + 2N | heads``), and the same weights through a mixer built by sizes
    give the same numbers bit for bit, whole-sequence and one step."""
    gcfg = G.GraniteMoeHybridConfig.tiny()
    paddle.seed(3)
    granite = G.GraniteMoeHybridMamba(gcfg)
    assert isinstance(granite, G.Mamba2Mixer) and granite.groups == 1
    assert tuple(granite.in_proj.weight.shape) == (32, 64 + 64 + 2 * 16 + 8)
    assert granite.state_arrays() == [((8, 8, 16), "float32"),
                                      ((3, 64 + 2 * 16), None)]
    plain = G.Mamba2Mixer(32, 8, 8, 16, 4, 1, 8, gcfg.rms_norm_eps)
    for (ka, a), (kb, b) in zip(granite.named_parameters(),
                                plain.named_parameters()):
        assert ka == kb
        b._value = a._value
    u = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 19, 32)).astype(np.float32))
    np.testing.assert_array_equal(_host(granite(u)._value),
                                  _host(plain(u)._value))
    step = {"live": jnp.asarray([True, True])}
    cache = tuple(jnp.ones((2, *shape), jnp.float32)
                  for shape, _ in granite.state_arrays())
    for a, b in zip(jax.tree_util.tree_leaves(
            granite.paged_decode(u[:, :1], step, cache)),
            jax.tree_util.tree_leaves(
                plain.paged_decode(u[:, :1], step, cache))):
        np.testing.assert_array_equal(_host(a), _host(b))


def test_the_mixer_takes_its_inner_width_from_the_heads():
    """``d_inner`` is heads x head width whatever ``expand x hidden`` would
    be, ``conv_dim`` counts B and C a group, and the gated norm runs per
    group: scaling ONE group's channels of ``y z`` leaves the other
    group's output untouched (a norm over all of ``d_in`` would not)."""
    cfg = N.NemotronHConfig.nemotron_3_nano_30b_a3b()
    shapes = jax.eval_shape(lambda: {
        k: p._value for k, p in G.Mamba2Mixer(
            cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.ssm_state_size, cfg.conv_kernel, cfg.n_groups
        ).named_parameters()})
    assert tuple(shapes["in_proj.weight"].shape) == (2688, 4096 + 6144 + 64)
    assert tuple(shapes["conv1d.weight"].shape) == (6144, 4)
    assert tuple(shapes["out_proj.weight"].shape) == (4096, 2688)
    assert 2 * cfg.hidden_size == 5376 != 4096   # the source's expand 2
    paddle.seed(0)
    mixer = G.Mamba2Mixer(16, 4, 4, 8, groups=2)
    mixer.out_proj.weight._value = jnp.eye(16, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.standard_normal((1, 3, 4, 4)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((1, 3, 16)), jnp.float32)
    base = _host(mixer._out(y, z)._value)
    scaled = _host(mixer._out(y.at[:, :, :2].multiply(50.0), z)._value)
    np.testing.assert_allclose(scaled[..., 8:], base[..., 8:], rtol=1e-6)
    # group 0's own output is its normed direction: unchanged up to eps
    np.testing.assert_allclose(scaled[..., :8], base[..., :8], rtol=1e-3)


# ------------------------------------------- an expert layer that is told
def test_the_shares_add_up_to_the_uncut_layer(toy):
    """The tie of the share to the model: the routed parts of shares
    [0, 4) and [4, 8), with what every chip computes alike, the shared
    expert, counted once, equal the uncut reference layer's part."""
    cfg, _, _ = toy
    whole = dict(cfg, n_routed_experts=8, held_experts=[0, 8])
    leaves = _slow_leaves(whole, seed=5)
    m = reference.dims(whole)
    lp = {n.split(".", 1)[1]: leaves[n]
          for n in reference.layer_leaves(whole, 1)}
    assert set(lp) == {"ln", "router_w", "router_b", "e_up", "e_down",
                       "s_up", "s_down"}
    v = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 128)),
                    jnp.float32)
    flat = v.reshape(18, 128)
    shared = reference.relu2_mlp(flat, lp["s_up"], lp["s_down"], False)
    uncut = reference.routed_experts(flat, lp, m) + shared
    # the reference's own shares
    parts = [reference.routed_experts(
        flat, dict(lp, e_up=lp["e_up"][lo:lo + 4],
                   e_down=lp["e_down"][lo:lo + 4]), m, held=(lo, lo + 4))
        for lo in (0, 4)]
    assert _max_abs(parts[0] + parts[1] + shared, uncut) < 1e-5
    # the program's: two chips' blocks, each told what it holds
    total = shared
    chosen = _host(reference.route(flat, lp, m)[0])
    for lo in (0, 4):
        pcfg = N.NemotronHConfig.tiny(
            hidden_size=128, moe_intermediate_size=40,
            moe_shared_expert_intermediate_size=72, held_experts=(lo, 4))
        block = N.NemotronHMoE(pcfg)
        assert (block.num_experts, block.published_experts) == (4, 8)
        block.gate.weight._value = lp["router_w"]
        block.gate.e_score_correction_bias._value = lp["router_b"]
        block.experts.up_proj._value = lp["e_up"][lo:lo + 4]
        block.experts.down_proj._value = lp["e_down"][lo:lo + 4]
        block.shared_experts.up_proj.weight._value = lp["s_up"]
        block.shared_experts.down_proj.weight._value = lp["s_down"]
        # the block adds its shared expert: take it off, count it once
        part = block(paddle.to_tensor(v))._value.reshape(18, 128) - shared
        assert _max_abs(part, parts[lo // 4]) < 2e-5
        assert _host(block.rows_per_expert).sum() == (
            (chosen // 4) == lo // 4).sum()
        total = total + part
    assert _max_abs(total, uncut) < 4e-5 and _max_abs(uncut) > 0.1


def test_one_block_serves_gated_and_ungated_experts():
    """``nlp/routed_experts.py``: the activation, the shared expert's form
    and ``held=`` are arguments of ONE block. Gated (the latent and window
    families' form) it keeps the leaf names and shapes it had; ungated it
    has two matrices an expert and no gate; a held share reads
    ``num_experts`` held and ``published_experts`` routed over."""
    class Block(R.SigmoidRoutedExperts):
        def _build_router(self, hidden_size, num_experts):
            self.w = self.create_parameter((hidden_size, num_experts))
            self.b = self.create_parameter((num_experts,), is_bias=True)

        def _router_leaves(self):
            return self.w, self.b

    paddle.seed(0)
    gated = Block(16, 8, 6, 2, 12)
    assert {k: tuple(p.shape) for k, p in gated.named_parameters()} == {
        "w": (16, 6), "b": (6,), "experts.gate_up_proj": (6, 16, 16),
        "experts.down_proj": (6, 8, 16),
        "shared_experts.gate_proj.weight": (16, 12),
        "shared_experts.up_proj.weight": (16, 12),
        "shared_experts.down_proj.weight": (12, 16)}
    assert gated.act is moe_layer.swiglu and gated.held == (0, 6)
    assert gated.inactive_params_per_token() == (6 - 2) * 3 * 16 * 8
    plain = Block(16, 8, 6, 2, 12, act="relu2", held=(2, 3))
    assert {k: tuple(p.shape) for k, p in plain.named_parameters()} == {
        "w": (16, 6), "b": (6,), "experts.up_proj": (3, 16, 8),
        "experts.down_proj": (3, 8, 16),
        "shared_experts.up_proj.weight": (16, 12),
        "shared_experts.down_proj.weight": (12, 16)}
    assert (plain.num_experts, plain.published_experts) == (3, 6)
    assert plain.inactive_params_per_token() == (3 - 1) * 2 * 16 * 8
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (5, 16)).astype(np.float32))
    y = plain(x)._value
    # by hand: sigmoid scores, top 2, renormalised; experts 2..4 held
    xv = _host(x._value)
    s = 1.0 / (1.0 + np.exp(-(xv @ _host(plain.w._value))))
    top = np.argsort(-(s + _host(plain.b._value)), axis=1)[:, :2]
    want = np.maximum(xv @ _host(
        plain.shared_experts.up_proj.weight._value), 0) ** 2 @ _host(
        plain.shared_experts.down_proj.weight._value)
    rows = np.zeros(3, int)
    for t in range(5):
        w = s[t, top[t]] / (s[t, top[t]].sum() + 1e-20)
        for e, we in zip(top[t], w):
            if 2 <= e < 5:
                rows[e - 2] += 1
                h = np.maximum(xv[t] @ _host(
                    plain.experts.up_proj._value)[e - 2], 0) ** 2
                want[t] += we * (h @ _host(
                    plain.experts.down_proj._value)[e - 2])
    np.testing.assert_allclose(_host(y), want, atol=2e-5)
    assert _host(plain.rows_per_expert).tolist() == rows.tolist()
    with pytest.raises(ValueError, match="no range"):
        Block(16, 8, 6, 2, 12, held=(4, 3))
    with pytest.raises(KeyError):
        Block(16, 8, 6, 2, 12, act="gelu")


def test_the_ungated_products_take_the_kernel_by_the_rule(monkeypatch):
    """``grouped_matmul.supports`` answers by shapes: a width of 1856 is
    14.5 lanes but 116 whole sublane tiles of bfloat16, so both products
    take the kernel with rows enough for its 512-row tile (prefill) AND at
    decode's 6 rows a group (``ragged-dot`` tiles 1856 by 64 and 2688 by
    128), and ``moe_products_programs_total`` says so; a width that is not
    whole sublane tiles keeps ``ragged_dot``. No second kernel, no flag:
    the tiles come from the shapes."""
    from paddle_tpu.ops.pallas import grouped_matmul as kernel

    bf16 = jnp.bfloat16
    w_up = jax.ShapeDtypeStruct((64, 2688, 1856), bf16)
    w_down = jax.ShapeDtypeStruct((64, 1856, 2688), bf16)
    assert kernel.supports(64 * 128 * 6, w_up, w_down)      # prefill
    assert kernel._tiles(64 * 128 * 6, 64) == (512, 128)
    assert kernel.supports(64 * 6, w_up, w_down)            # decode
    assert kernel._tiles(64 * 6, 64) == (16, 16)
    assert not kernel.supports(
        64 * 128 * 6, jax.ShapeDtypeStruct((64, 2688, 1864), bf16),
        jax.ShapeDtypeStruct((64, 1864, 2688), bf16))
    for positions in (64 * 128, 64):
        before = _products_counts()
        paddle.set_flags({"FLAGS_pallas_force": True})
        try:
            jax.eval_shape(
                lambda x, i, g, a, b: moe_layer.grouped_expert_ffn(
                    x, i, g, a, b, R.relu2, held=(0, 64)),
                jax.ShapeDtypeStruct((positions, 2688), bf16),
                jax.ShapeDtypeStruct((positions, 6), jnp.int32),
                jax.ShapeDtypeStruct((positions, 6), jnp.float32),
                w_up, w_down)
        finally:
            paddle.set_flags({"FLAGS_pallas_force": False})
        after = _products_counts()
        assert after["kernel"] == before["kernel"] + 1, positions
        assert after["ragged_dot"] == before["ragged_dot"], positions


def test_served_tokens_with_the_kernel_are_the_ragged_dot_paths(monkeypatch):
    """The toy with experts 144 wide (18 sublane tiles of float32, 1.125
    lanes: ``ragged-dot`` would tile it by 16), through the engine with
    the kernel forced onto the interpreter: the first product reads the
    stack as stored (N not whole lanes), the second contracts over a
    padded K; the mixed program takes the kernel at a row tile (patched
    down to the toy's 48 rows a group) and the quantum at the 16-row tile
    by the rule itself. The served tokens of the prefill and two quanta
    are the ``ragged_dot`` path's, token for token."""
    from paddle_tpu.ops.pallas import grouped_matmul as kernel

    cfg = dict(_toy_cfg(), moe_intermediate_size=144)
    model = _model(cfg, _slow_leaves(cfg))
    prompts = _prompts(cfg, (37, 20, 9), seed=3)
    want = _drain(_serve(model), prompts, 9)     # the first token + 2 x 4
    before = _products_counts()
    monkeypatch.setattr(kernel, "_BLOCK_M", 32)
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        got = _drain(_serve(model), prompts, 9)
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    after = _products_counts()
    assert after["ragged_dot"] == before["ragged_dot"]
    assert after["kernel"] >= before["kernel"] + 2   # mixed and quantum
    for g, w in zip(got, want):
        assert g.shape == (9,) and bool(np.all(g == w))


# -------------------------------------------- a layer that caches nothing
def test_a_layer_that_caches_nothing_in_the_layer_protocol(toy):
    """``paged_cache_layout()["layers"]`` has a kind of its own for an
    ``E`` layer; ``_layer_caches`` hands it an empty tuple,
    ``_collect_caches`` gives it no place on either side, the pool counts
    one block layer and four state layers of eight, and the programs'
    pool arguments are exactly those."""
    cfg, model, _ = toy
    layout = model.paged_cache_layout()
    assert layout["layers"] == ("state", "none", "state", "kv", "none",
                                "state", "none", "state")
    pools = (["k0"], ["v0"], (), ())
    state = tuple((f"h{i}", f"t{i}") for i in range(4))
    caches = engine_mod._layer_caches(model, pools, state)
    assert caches == [("h0", "t0"), (), ("h1", "t1"),
                      ("k0", "v0", None, None), (), ("h2", "t2"), (),
                      ("h3", "t3")]
    k, v, ks, vs, st = engine_mod._collect_caches(model, caches)
    assert (k, v, ks, vs) == (["k0"], ["v0"], (), ()) and st == state
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    assert len(eng.pool.k_pools) == 1 and len(eng.pool.state) == 4
    stats = eng.engine_stats()["pool"]
    assert stats["bytes_per_token"] == 2 * 2 * 48 * 4    # the one * layer
    per_slot = 4 * (8 * 16 * 32 * 4 + 3 * (8 * 16 + 2 * 2 * 32) * 4)
    assert stats["state_bytes_per_slot"] == per_slot
    assert eng.obs.registry.get("serving_state_bytes_per_slot").value(
        pool="target") == per_slot
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        n_pool = len(jax.tree_util.tree_leaves(args[:5]))
        assert n_pool == 2 * 1 + 2 * 4 == step.n_donatable
    # three expert layers are found, over the HELD experts
    blocks = engine_mod._expert_blocks(model)
    assert len(blocks) == 3 and blocks[0].num_experts == 4
    assert eng._moe_top_k == 3
    eng.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=5)
    eng.run()
    assert stats["num_blocks"] == eng.engine_stats()["pool"]["num_blocks"]
    assert eng.engine_stats()["pool"]["peak_blocks_in_use"] == 1 + 3


@pytest.mark.parametrize("kwargs,name", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"tp": 2}, "tp > 1"),
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"spec_draft": "self"}, "spec_draft"),
])
def test_refusals_by_name(kwargs, name):
    """Decided by what the layers cache (a slot's recurrent state), never
    by the model's class or a config attribute."""
    paddle.seed(0)
    model = N.NemotronHForCausalLM(N.NemotronHConfig.tiny())
    if kwargs.get("spec_draft"):
        kwargs = {"spec_draft": N.NemotronHForCausalLM(
            N.NemotronHConfig.tiny())}
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(model, num_slots=2, block_size=8, max_context=32,
                      **kwargs)
    assert name in str(err.value) and "state-space" in str(err.value)


@pytest.mark.parametrize("overrides,what", [
    ({"hybrid_override_pattern": "MEM-EMEM"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEM*"}, "hybrid_override_pattern"),
    ({"n_groups": 3}, "n_groups"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"mamba_proj_bias": True}, "bias"),
    ({"use_conv_bias": False}, "convolution"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"residual_in_fp32": True}, "residual_in_fp32"),
    ({"sliding_window": 16}, "sliding_window"),
    ({"time_step_limit": (0.0, 1.0)}, "time_step_limit"),
    ({"model_type": "mamba2"}, "model_type"),
    ({"n_group": 2}, "group-limited"),
])
def test_the_config_refuses_what_the_model_does_not_compute(overrides, what):
    with pytest.raises(NotImplementedError, match=what):
        N.NemotronHForCausalLM(N.NemotronHConfig.tiny(**overrides))


def test_the_published_preset_counts_the_issues_parameters():
    """``nemotron_3_nano_30b_a3b()`` is the source's config: whole it
    counts the card's 31.6 B; the issue's cut (16 layers, experts 0-63
    held) 5,634,855,744, and the cut the chip's memory allowed (14 layers:
    PERF.md section 6, PR 39) 4,937,225,472 (from shapes: nothing is
    allocated)."""
    def count(cfg):
        shapes = jax.eval_shape(lambda: [
            p._value for _, p in
            N.NemotronHForCausalLM(cfg).named_parameters()])
        return sum(int(np.prod(s.shape)) for s in shapes)

    full = N.NemotronHConfig.nemotron_3_nano_30b_a3b()
    pattern = full.hybrid_override_pattern
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)
    assert count(full) == 31_577_940_288
    for depth, pattern, want in ((16, "MEMEM*EMEMEM*EME", 5_634_855_744),
                                 (14, "MEMEM*EMEMEM*E", 4_937_225_472)):
        cut = N.NemotronHConfig.nemotron_3_nano_30b_a3b(
            num_hidden_layers=depth, held_experts=(0, 64))
        assert cut.hybrid_override_pattern == pattern
        assert count(cut) == want


# ------------------------------------------------------ spans and counters
def test_counters_spans_and_scopes(toy):
    cfg, model, _ = toy
    rec = TraceRecorder.process()
    first = rec.next_id()
    door = _serve(model)
    _drain(door, _prompts(cfg, (20, 9)), 9)
    eng = door.engine
    reg = eng.obs.registry
    rows, off, steps = (reg.get(f"serving_moe_{k}_total").value()
                        for k in ("routed_rows", "offshare_rows",
                                  "layer_steps"))
    quanta = eng.stats["decode_quanta"]
    # three expert layers, four steps a quantum, four slots x top 3 choices
    # of which the held half got `rows`
    assert steps == quanta * 4 * 3 and rows + off == steps * 4 * 3
    assert 0 < rows < steps * 4 * 3
    spans = [e for e in rec.events
             if e.get("args", {}).get("id", -1) >= first]
    collect = [e["args"] for e in spans if e["name"] == "engine.decode"
               and e["args"].get("half") == "collect"]
    assert sum(a["moe_rows"] for a in collect) == rows
    assert sum(a["moe_offshare_rows"] for a in collect) == off
    mixed = [e["args"] for e in spans if e["name"] == "engine.mixed"]
    assert mixed and all(
        a["moe_rows"] + a["moe_offshare_rows"] == 3 * a["bucket"] * 3 * 4
        for a in mixed)
    assert reg.get("serving_state_resets_total").value() == 2
    # the cost ledger's 2N counts, of a layer's 4 held experts, the
    # 3 x 4 / 8 = 1 a token multiplies on average (untied head: the
    # embedding alone is a lookup)
    n = sum(int(p._value.size) for _, p in model.named_parameters())
    assert eng.obs.ledger.flops_per_token == 2.0 * (
        n - 2048 * 128 - 3 * 3 * 2 * 128 * 40)
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        text = step.lower(*args).as_text(debug_info=True)
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out",
                      "moe.router", "moe.experts", "moe.shared",
                      "attn.proj", "attn.full", "norm"):
            assert scope in text, scope
