"""The Nemotron-H-shaped decoder (layers of ONE part each: a Mamba-2 mixer
with groups, attention without positions, or ungated relu^2 routed experts
of which a chip holds its share): what is this family's own. The contract
every served family holds is ``tests/test_family_contract.py`` over this
family's row of ``tests/family_harness.py`` (which says how the test's
weights are drawn, a state that decays SLOWLY, and why the tolerances are
what they are). Here: the groups of B and C, the mixer shared with the
Granite family, the one expert block gated and ungated, the products' kernel
by the rule, and a layer that caches nothing.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import moe_layer
from paddle_tpu.nlp import granitemoehybrid as G
from paddle_tpu.nlp import nemotron_h as N
from paddle_tpu.nlp import routed_experts as R
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod

from family_harness import (
    FAMILIES, drain, host, max_abs, products_counts, prompts, serve)

ROW = FAMILIES["nemotron_h"]
reference = ROW.reference
LOGIT_TOL = ROW.logit_tol


def test_the_state_decays_slowly_and_the_groups_matter(toy):
    """What the docstring promises of the test's weights: changing the
    FIRST token still moves the logits 30 positions on. And a mixer that
    read group 0's B and C for every head would be seen: with the second
    group's columns of ``in_proj`` swapped for the first's the logits move
    by far more than the tolerance."""
    cfg, _, get_leaf = toy
    only_ssm = dict(cfg, num_hidden_layers=3, hybrid_override_pattern="MEM")
    ids = np.stack(prompts(cfg, (31,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1
    a, b = (reference.logits(only_ssm, get_leaf, x)[0, -1]
            for x in (ids, other))
    assert max_abs(a, b) > 100 * LOGIT_TOL
    m = reference.dims(cfg)
    d_in, n = m["d_in"], m["n"]

    def one_group(name):
        w = get_leaf(name)
        if name.endswith("in_w"):       # z | xs | B0 B1 | C0 C1 | dt
            lo = 2 * d_in
            w = w.at[:, lo + n:lo + 2 * n].set(w[:, lo:lo + n])
            w = w.at[:, lo + 3 * n:lo + 4 * n].set(w[:, lo + 2 * n:lo + 3 * n])
        return w

    assert max_abs(a, reference.logits(only_ssm, one_group, ids)[0, -1]) \
        > 100 * LOGIT_TOL


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
    np.testing.assert_array_equal(host(jax.jit(granite)(u)._value),
                                  host(jax.jit(plain)(u)._value))
    step = {"live": jnp.asarray([True, True])}
    cache = tuple(jnp.ones((2, *shape), jnp.float32)
                  for shape, _ in granite.state_arrays())
    for a, b in zip(jax.tree_util.tree_leaves(
            jax.jit(granite.paged_decode)(u[:, :1], step, cache)),
            jax.tree_util.tree_leaves(
                jax.jit(plain.paged_decode)(u[:, :1], step, cache))):
        np.testing.assert_array_equal(host(a), host(b))


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
    base = host(mixer._out(y, z)._value)
    scaled = host(mixer._out(y.at[:, :, :2].multiply(50.0), z)._value)
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
    leaves = ROW.leaves(whole, seed=5)
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
    assert max_abs(parts[0] + parts[1] + shared, uncut) < 1e-5
    # the program's: two chips' blocks, each told what it holds
    total = shared
    chosen = host(reference.route(flat, lp, m)[0])
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
        part, rows = jax.jit(lambda v: (
            block(paddle.to_tensor(v))._value, block.rows_per_expert))(v)
        part = part.reshape(18, 128) - shared
        assert max_abs(part, parts[lo // 4]) < 2e-5
        assert host(rows).sum() == ((chosen // 4) == lo // 4).sum()
        total = total + part
    assert max_abs(total, uncut) < 4e-5 and max_abs(uncut) > 0.1


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
    y, served = jax.jit(lambda x: (plain(x)._value, plain.rows_per_expert))(x)
    # by hand: sigmoid scores, top 2, renormalised; experts 2..4 held
    xv = host(x._value)
    s = 1.0 / (1.0 + np.exp(-(xv @ host(plain.w._value))))
    top = np.argsort(-(s + host(plain.b._value)), axis=1)[:, :2]
    want = np.maximum(xv @ host(
        plain.shared_experts.up_proj.weight._value), 0) ** 2 @ host(
        plain.shared_experts.down_proj.weight._value)
    rows = np.zeros(3, int)
    for t in range(5):
        w = s[t, top[t]] / (s[t, top[t]].sum() + 1e-20)
        for e, we in zip(top[t], w):
            if 2 <= e < 5:
                rows[e - 2] += 1
                h = np.maximum(xv[t] @ host(
                    plain.experts.up_proj._value)[e - 2], 0) ** 2
                want[t] += we * (h @ host(
                    plain.experts.down_proj._value)[e - 2])
    np.testing.assert_allclose(host(y), want, atol=2e-5)
    assert host(served).tolist() == rows.tolist()
    with pytest.raises(ValueError, match="no range"):
        Block(16, 8, 6, 2, 12, held=(4, 3))
    with pytest.raises(KeyError):
        Block(16, 8, 6, 2, 12, act="gelu")


def test_the_ungated_products_take_the_kernel_by_the_rule(monkeypatch):
    """``grouped_matmul.supports`` answers by shapes: a width of 1856 is
    14.5 lanes but 116 whole sublane tiles of bfloat16, so both products
    take the kernel with rows enough for its 512-row tile (prefill) AND at
    decode's 6 rows a group (the 16-row tile), and
    ``moe_products_programs_total`` says so; a width that is not
    whole sublane tiles keeps ``ragged_dot``. No second kernel, no flag:
    the tiles come from the shapes."""
    from paddle_tpu.ops.pallas import grouped_matmul as kernel

    bf16 = jnp.bfloat16
    w_up = jax.ShapeDtypeStruct((64, 2688, 1856), bf16)
    w_down = jax.ShapeDtypeStruct((64, 1856, 2688), bf16)
    assert kernel.supports(w_up, w_down)
    assert kernel._tiles(64 * 128 * 6, 64) == (512, 128)    # prefill
    assert kernel._tiles(64 * 6, 64) == (16, 16)            # decode
    assert not kernel.supports(
        jax.ShapeDtypeStruct((64, 2688, 1864), bf16),
        jax.ShapeDtypeStruct((64, 1864, 2688), bf16))
    for positions in (64 * 128, 64):
        before = products_counts()
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
        after = products_counts()
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

    cfg = ROW.toy_cfg(moe_intermediate_size=144)
    model, _ = ROW.build(cfg)
    rows = prompts(cfg, (37, 20, 9), seed=3)
    want = drain(serve(model), rows, 9)          # the first token + 2 x 4
    before = products_counts()
    monkeypatch.setattr(kernel, "_BLOCK_M", 32)
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        got = drain(serve(model), rows, 9)
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    after = products_counts()
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
