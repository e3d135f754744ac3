"""The Granite-4.0-H-shaped decoder (Mamba-2 state-space layers beside
attention without positions, routed experts of which a chip holds its share):
what is this family's own. The contract every served family holds (the
reference's logits, the engine's bodies by hand, the served tokens, the
refusals, the preset, counters and scopes) is ``tests/test_family_contract.py``
over this family's row of ``tests/family_harness.py``, which also says how the
test's weights are drawn (a state that decays SLOWLY) and why the tolerances
are what they are. Here: the state's decay, the chunked form of the mixer
against the recurrence, the gate, an expert layer that is told what it holds,
the pool's slot side, and a snapshot restored by recompute.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import SoftmaxTopKGate
from paddle_tpu.incubate.distributed.models.moe import moe_layer
from paddle_tpu.nlp import PagedKVCachePool
from paddle_tpu.nlp import granitemoehybrid as G
from paddle_tpu.serving import ServingEngine

from family_harness import (
    FAMILIES, host, llama_tiny, max_abs, prompts)

ROW = FAMILIES["granitemoehybrid"]
reference = ROW.reference
LOGIT_TOL = ROW.logit_tol


def test_the_state_decays_slowly(toy):
    """What the docstring promises of the test's weights: changing the
    FIRST token still moves the logits 30 positions on (with the
    benchmark's seeded weights it would not)."""
    cfg, _, get_leaf = toy
    only_ssm = dict(cfg, num_hidden_layers=2, layer_types=["mamba"] * 2)
    ids = np.stack(prompts(cfg, (31,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1
    a, b = (reference.logits(only_ssm, get_leaf, x)[0, -1]
            for x in (ids, other))
    assert max_abs(a, b) > 100 * LOGIT_TOL


def _recurrence(xs, b, c, dt, a, d, h0):
    """The module's first equation block, position by position, float64;
    ``b`` / ``c`` (S, C, G, N), head ``h`` using group ``h // (H / G)``."""
    xs, b, c, dt, a, d, h = (np.asarray(v, np.float64)
                             for v in (xs, b, c, dt, a, d, h0))
    per = xs.shape[2] // b.shape[2]
    b, c = np.repeat(b, per, axis=2), np.repeat(c, per, axis=2)
    ys = np.zeros(xs.shape)
    for t in range(xs.shape[1]):
        h = (h * np.exp(dt[:, t] * a)[..., None, None]
             + (dt[:, t, :, None] * xs[:, t])[..., None]
             * b[:, t, :, None, :])
        ys[:, t] = (h * c[:, t, :, None, :]).sum(-1) + d[:, None] * xs[:, t]
    return ys, h


@pytest.mark.parametrize("groups,decay_bytes", [
    (1, None), (1, 4 * 2 * 12 * 12), (3, None), (3, 4 * 2 * 12 * 12),
    (3, 4 * 2 * 12 * 12 // 2), (2, 4 * 3 * 12 * 12), (6, None)],
    ids=["one_group", "head_groups_of_two", "three_groups",
         "three_groups_streamed_whole", "three_groups_streamed_in_halves",
         "two_groups_streamed_whole", "a_group_a_head"])
def test_chunked_form_equals_the_recurrence(groups, decay_bytes,
                                            monkeypatch):
    """``ssd_chunk`` over 12 positions with an incoming state, positions
    of dt = 0 (past a row's count) included, against the recurrence, for
    one group of B and C (this family's) and for several (``nemotron_h``'s:
    head ``h`` reads group ``h // (H / G)``); the heads stream under a byte
    bound, whole groups or an equal part of one at a time, to the same
    numbers."""
    if decay_bytes:
        monkeypatch.setattr(G, "_DECAY_BYTES", decay_bytes)
    rng = np.random.default_rng(0)
    s_, cl, h, p, n = 2, 12, 6, 4, 5
    xs = rng.standard_normal((s_, cl, h, p))
    b, c = rng.standard_normal((2, s_, cl, groups, n))
    dt = rng.uniform(0.01, 0.3, (s_, cl, h))
    dt[1, 7:] = 0.0                       # row 1 brings 7 positions
    a = -np.exp(np.linspace(0.0, 2.7, h))
    d = rng.standard_normal(h)
    h0 = rng.standard_normal((s_, h, p, n))
    y, h1 = G.ssd_chunk(*(jnp.asarray(v, jnp.float32)
                          for v in (xs, b, c, dt, a, d, h0)))
    want_y, want_h = _recurrence(xs, b, c, dt, a, d, h0)
    assert max_abs(y[0], want_y[0]) < 2e-5
    assert max_abs(y[1, :7], want_y[1, :7]) < 2e-5
    assert max_abs(h1, want_h) < 2e-5
    # row 1's state is where its 7th position put it
    assert max_abs(h1[1], _recurrence(xs[:, :7], b[:, :7], c[:, :7],
                                       dt[:, :7], a, d, h0)[1][1]) < 2e-5
    if groups > 1:
        # the groups matter: every head on group 0's B and C reads otherwise
        same = np.repeat(b[:, :, :1], groups, 2), np.repeat(c[:, :, :1],
                                                            groups, 2)
        assert max_abs(y[0], _recurrence(xs, *same, dt, a, d, h0)[0][0]) \
            > 0.1


# ---------------------------------------------------------------- the gate
def test_gate_against_the_reference_on_hand_made_logits():
    """The two largest LOGITS are chosen; the weights are the softmax over
    the chosen values alone."""
    gate = SoftmaxTopKGate(2)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0],
                          [0.0, 0.1, 0.2, 0.3]], jnp.float32)
    sel, w, aux = gate.topk_assignments(logits)
    assert aux is None and host(sel).tolist() == [[0, 1], [3, 2]]
    e = np.exp([[2.0, 1.0], [0.3, 0.2]])
    np.testing.assert_allclose(host(w), e / e.sum(1, keepdims=True),
                               rtol=1e-6)
    m = {"top_k": 2}
    rsel, rw = reference.route(
        logits, {"router_w": jnp.eye(4, dtype=jnp.float32)}, m)
    np.testing.assert_array_equal(host(rsel), host(sel))
    np.testing.assert_allclose(host(rw), host(w), rtol=1e-6)
    with pytest.raises(NotImplementedError, match="bias"):
        gate.topk_assignments(logits, jnp.zeros(4))


# ------------------------------------------- an expert layer that is told
def _core_case(t=12, k=3, e=8, width=4, seed=0):
    rng = np.random.default_rng(seed)
    xt = jnp.asarray(rng.standard_normal((t, width)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(e)[:k] for _ in range(t)]))
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((e, width, width)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((e, width, width)), jnp.float32)
    return xt, ids, w, w1, w2


@pytest.mark.parametrize("split", [(0, 8), (0, 4), (4, 4), (2, 5), (7, 1)],
                         ids=lambda s: f"held_{s[0]}_{s[1]}")
def test_grouped_core_computes_only_what_it_holds(split):
    """``held=(lo, n)``: ids and weights range over all 8 experts, the two
    products over the ``n`` held slabs; a row routed elsewhere adds
    exactly zero; the rows reported are the held experts'."""
    lo, n = split
    xt, ids, w, w1, w2 = _core_case()
    y, rows = moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[lo:lo + n], w2[lo:lo + n], jnp.tanh, held=(lo, n))
    inside = (ids >= lo) & (ids < lo + n)
    want = sum(jnp.where(inside[:, j, None], w[:, j, None] * jnp.einsum(
        "tf,tfm->tm", jnp.tanh(jnp.einsum("tm,tmf->tf", xt, w1[ids[:, j]])),
        w2[ids[:, j]]), 0.0) for j in range(3))
    np.testing.assert_allclose(host(y), host(want), atol=1e-5)
    assert host(rows).tolist() == [
        int((host(ids) == e).sum()) for e in range(lo, lo + n)]
    none = ~host(inside).any(axis=1)
    assert (host(y)[none] == 0.0).all()


def test_grouped_core_shares_add_up_and_all_held_is_the_default():
    xt, ids, w, w1, w2 = _core_case(seed=1)
    full, rows = moe_layer.grouped_expert_ffn(xt, ids, w, w1, w2, jnp.tanh)
    same, _ = moe_layer.grouped_expert_ffn(xt, ids, w, w1, w2, jnp.tanh,
                                           held=(0, 8))
    np.testing.assert_array_equal(host(full), host(same))
    parts = [moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[lo:lo + 4], w2[lo:lo + 4], jnp.tanh, held=(lo, 4))
        for lo in (0, 4)]
    np.testing.assert_allclose(host(parts[0][0] + parts[1][0]),
                               host(full), atol=1e-5)
    assert host(jnp.concatenate([p[1] for p in parts])).tolist() \
        == host(rows).tolist()
    with pytest.raises(ValueError, match="weight slabs"):
        moe_layer.grouped_expert_ffn(xt, ids, w, w1, w2, jnp.tanh,
                                     held=(0, 4))


def test_grouped_core_tiles_the_positions_and_caps_no_row(monkeypatch):
    """Past the byte bound of the sorted-rows buffer the positions go
    through in equal tiles: the same numbers, every row counted."""
    xt, ids, w, w1, w2 = _core_case(t=12, seed=2)
    want, rows = moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[:4], w2[:4], jnp.tanh, held=(0, 4))
    # 12 x 3 rows x 4 values x 4 bytes = 576: five tiles asked, six taken
    monkeypatch.setattr(moe_layer, "_SORTED_ROWS_BYTES", 128)
    got, trows = moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[:4], w2[:4], jnp.tanh, held=(0, 4))
    np.testing.assert_allclose(host(got), host(want), atol=1e-6)
    assert host(trows).tolist() == host(rows).tolist()


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """The tie of the share to the model: the routed parts of shares
    [0, 4) and [4, 8), with what every chip computes alike, the shared
    MLP, counted once, equal the uncut reference layer's feed-forward."""
    cfg, _, _ = toy
    whole = dict(cfg, num_local_experts=8, held_experts=[0, 8])
    leaves = ROW.leaves(whole, seed=5)
    m = reference.dims(whole)
    lp = {n.split(".", 1)[1]: leaves[n]
          for n in reference.layer_leaves(whole, 0)}
    v = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 128)),
                    jnp.float32)
    flat = v.reshape(18, 128)
    shared = reference.swiglu(flat, lp["s_in"], lp["s_out"], False)
    uncut = reference.routed_experts(flat, lp, m) + shared
    # the reference's own shares
    parts = [reference.routed_experts(
        flat, dict(lp, e_in=lp["e_in"][lo:lo + 4],
                   e_out=lp["e_out"][lo:lo + 4]), m, held=(lo, lo + 4))
        for lo in (0, 4)]
    assert max_abs(parts[0] + parts[1] + shared, uncut) < 1e-5
    # the program's: two chips' blocks, each told what it holds
    total = shared
    for lo in (0, 4):
        pcfg = G.GraniteMoeHybridConfig.tiny(
            hidden_size=128, intermediate_size=32, mamba_n_heads=16,
            mamba_d_head=16, held_experts=(lo, 4))
        block = G.GraniteMoeHybridMoE(pcfg)
        block.router.layer.weight._value = lp["router_w"]
        block.input_linear.weight._value = lp["e_in"][lo:lo + 4]
        block.output_linear.weight._value = lp["e_out"][lo:lo + 4]
        part, rows = jax.jit(lambda v: (
            block(paddle.to_tensor(v))._value, block.rows_per_expert))(v)
        part = part.reshape(18, 128)
        assert max_abs(part, parts[lo // 4]) < 1e-5
        chosen = host(reference.route(flat, lp, m)[0])
        assert host(rows).sum() == ((chosen // 4) == lo // 4).sum()
        total = total + part
    assert max_abs(total, uncut) < 2e-5 and max_abs(uncut) > 0.1


# ---------------------------------------------------------------- the pool
def _state_pool(**kw):
    return PagedKVCachePool(
        num_blocks=8, block_size=4, num_kv_heads=2, head_dim=6,
        num_layers=1, dtype=jnp.bfloat16,
        state={"slots": 3, "layers": 2,
               "arrays": [((4, 2, 5), "float32"), ((3, 7), None)]}, **kw)


def test_slot_state_accounting():
    pool = _state_pool()
    assert [[(tuple(a.shape), str(a.dtype)) for a in layer]
            for layer in pool.state] == [
        [((3, 4, 2, 5), "float32"), ((3, 3, 7), "bfloat16")]] * 2
    per_slot = 2 * (4 * 2 * 5 * 4 + 3 * 7 * 2)
    assert pool.state_bytes_per_slot() == per_slot
    assert pool.bytes_per_token() == 2 * 2 * 6 * 2     # the K/V side alone
    pool.ensure("__scratch__", 1)
    pool.ensure("a", 9)                                # three blocks
    st = pool.fragmentation_stats()
    assert st["state_bytes_per_slot"] == per_slot and st["state_slots"] == 3
    assert st["bytes_in_use"] == 4 * 4 * 48 + per_slot  # scratch holds none
    # adopt / commit_like / the donated sides carry the slot side
    sides = pool.arrays()
    assert len(jax.tree_util.tree_leaves(sides)) == 2 + 4
    pool.adopt(*sides)
    pool.commit_like(pool.k_pools[0])
    assert len(pool.state) == 2
    pool.free("a")
    assert pool.bytes_in_use() == 4 * 48


def test_a_pool_without_state_layers_has_an_empty_side():
    pool = PagedKVCachePool(num_blocks=8, block_size=4, num_kv_heads=2,
                            head_dim=6, num_layers=3, dtype=jnp.float32)
    assert pool.state == () and pool.state_bytes_per_slot() == 0
    assert len(jax.tree_util.tree_leaves(pool.arrays())) == 6
    pool.ensure("a", 5)
    assert pool.bytes_in_use() == 2 * 4 * pool.bytes_per_token()
    pool.adopt(*pool.arrays()[:4])
    assert pool.state == ()


@pytest.mark.parametrize("kwargs", [{"kv_dtype": "int8"}, {"mesh": True},
                                    {"prefix_cache": True}])
def test_state_pool_refusals(kwargs):
    if kwargs.get("mesh"):
        kwargs = {"mesh": jax.sharding.Mesh(host(jax.devices()[:2]),
                                            ("mp",))}
    with pytest.raises(NotImplementedError, match="slot state"):
        _state_pool(**kwargs)


def test_a_model_without_state_layers_adds_no_aval():
    """Llama's programs take an empty slot side: no aval, so its graphs
    are what they were (the goldens hold that), and no counter moves."""
    model = llama_tiny()
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        assert args[4] == () and args[2] == () == args[3]
        n_pool = len(jax.tree_util.tree_leaves(args[:5]))
        assert n_pool == 2 * model.config.num_hidden_layers
        assert step.n_donatable == n_pool
    assert eng.engine_stats()["pool"]["state_bytes_per_slot"] == 0
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    eng.run()
    assert eng.obs.registry.get("serving_state_resets_total").value() == 0
