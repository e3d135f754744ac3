"""The Falcon-H1-shaped decoder (every layer a Mamba-2 mixer AND rotary
grouped-query attention side by side on one normed input, fourteen muP
multipliers, a dense SwiGLU MLP): what is this family's own. A layer keeps key
blocks AND a slot's state: the first family whose layers have a place on both
sides of the pool. The contract every served family holds is
``tests/test_family_contract.py`` over this family's row of
``tests/family_harness.py`` (which says how the test's weights are drawn: a
state that decays SLOWLY, and every leaf a multiplier scales sized so that
leaf x multiplier is what it would be without one; and why the tolerances
are what they are). Here: both branches and the positions, each of the
fourteen multipliers, the mixer without multipliers, and the layer protocol
with a part on each side.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import falcon_h1 as F
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod

from family_harness import (
    FAMILIES, forward, llama_tiny, max_abs, prompts)

ROW = FAMILIES["falcon_h1"]
reference = ROW.reference
LOGIT_TOL = ROW.logit_tol
MULTIPLIERS = ["embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               *(f"ssm_multipliers.{i}" for i in range(5)),
               "mlp_multipliers.0", "mlp_multipliers.1"]


def test_both_branches_and_the_positions_matter(toy):
    """What the docstring promises of the test's weights: changing the
    FIRST token still moves the logits 30 positions on through the mixer
    alone (attention's output projection zeroed); a program without the
    attention branch, or whose keys are not rotated, is far outside the
    tolerance."""
    cfg, model, get_leaf = toy
    ids = np.stack(prompts(cfg, (31,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1

    def no_attention(name):
        leaf = get_leaf(name)
        return jnp.zeros_like(leaf) if name.endswith(".o_w") else leaf

    a, b = (reference.logits(cfg, no_attention, x)[0, -1]
            for x in (ids, other))
    assert max_abs(a, b) > 50 * LOGIT_TOL
    ref = reference.logits(cfg, get_leaf, ids)
    assert max_abs(ref, reference.logits(cfg, no_attention, ids)) \
        > 1000 * LOGIT_TOL
    flat = reference.logits(dict(cfg, rope_theta=1e30), get_leaf, ids)
    assert max_abs(ref, flat) > 1000 * LOGIT_TOL


# ------------------------------------------------------- the muP multipliers
def _with(cfg, name, value):
    """``cfg`` with one multiplier (``key`` or ``key.index``) set."""
    key, _, index = name.partition(".")
    if not index:
        return dict(cfg, **{key: value})
    values = list(cfg[key])
    values[int(index)] = value
    return dict(cfg, **{key: values})


_UNMOVED = {}


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_every_multiplier_is_applied_where_the_reference_applies_it(
        toy, name):
    """Each of the fourteen multipliers at a value of its own (1.7 x the
    published one): the program agrees with the reference, and the
    agreement says something: with THAT multiplier alone left where it
    was the reference's logits are far outside the tolerance."""
    cfg, _, get_leaf = toy
    # ONE layer places every multiplier (the three-layer toy at the
    # published values is the contract's forward scenario): a third of
    # the program to compile, fourteen times
    cfg = dict(cfg, num_hidden_layers=1)
    key, _, index = name.partition(".")
    was = cfg[key][int(index)] if index else cfg[key]
    moved = _with(cfg, name, 1.7 * was)
    leaves = {n: get_leaf(n) for n, _, _ in reference.leaf_table(cfg)}
    ids = np.stack(prompts(cfg, (24,), seed=5))
    ref = reference.logits(moved, get_leaf, ids)
    got = forward(ROW.build(moved, leaves)[0])(ids)
    assert max_abs(ref, got) < LOGIT_TOL * max(1.0, max_abs(ref))
    if "left" not in _UNMOVED:      # one reference pass for the fourteen
        _UNMOVED["left"] = reference.logits(cfg, get_leaf, ids)
    assert max_abs(ref, _UNMOVED["left"]) > 100 * LOGIT_TOL


def test_the_mixer_without_multipliers_adds_no_operation():
    """``Mamba2Mixer(multipliers=None)`` (the Granite and Nemotron
    families') traces to the equations it had: the section scale is one
    convert, one multiply and one convert more, and only where asked."""
    from paddle_tpu.nlp.granitemoehybrid import Mamba2Mixer

    def count(multipliers):
        paddle.seed(0)
        mixer = Mamba2Mixer(16, 4, 4, 8, groups=2, chunk_size=4,
                            multipliers=multipliers)
        return len(jax.make_jaxpr(lambda u: mixer(paddle.to_tensor(u))._value
                                  )(jnp.zeros((1, 8, 16))).jaxpr.eqns)

    # two chunks of four positions, at most three equations each
    assert 0 < count((0.5, 2.0, 0.25, 4.0, 1.5)) - count(None) <= 2 * 3


# ------------------------------------------------ the layer protocol, pool
def test_a_layer_with_a_place_on_both_sides_of_the_pool(toy):
    """``paged_cache_layout()["layers"]`` names two parts a layer;
    ``_layer_caches`` hands a layer ``(block arrays, slot arrays)``,
    ``_collect_caches`` puts each back on its side, the pool counts three
    block layers AND three state layers of three, and the programs' pool
    arguments, the gauges and the accounting are exactly those."""
    cfg, model, _ = toy
    layout = model.paged_cache_layout()
    assert layout["layers"] == (("kv", "state"),) * 3
    assert engine_mod.layout_parts(layout["layers"]) == ["kv", "state"] * 3
    assert engine_mod.layout_parts(("state", "none", ("kv", "state"))) \
        == ["state", "none", "kv", "state"]
    pools = (["k0", "k1", "k2"], ["v0", "v1", "v2"], (), ())
    state = tuple((f"h{i}", f"t{i}") for i in range(3))
    caches = engine_mod._layer_caches(model, pools, state)
    assert caches == [((f"k{i}", f"v{i}", None, None), (f"h{i}", f"t{i}"))
                      for i in range(3)]
    k, v, ks, vs, st = engine_mod._collect_caches(model, caches)
    assert (k, v, ks, vs) == (pools[0], pools[1], (), ()) and st == state
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=64)
    assert len(eng.pool.k_pools) == 3 == len(eng.pool.state)
    stats = eng.engine_stats()["pool"]
    per_token = 3 * 2 * 2 * 32 * 4              # K and V, 2 x 32, float32
    per_slot = 3 * (8 * 16 * 32 * 4 + 3 * (8 * 16 + 2 * 2 * 32) * 4)
    assert stats["bytes_per_token"] == per_token
    assert stats["state_bytes_per_slot"] == per_slot
    reg = eng.obs.registry
    assert reg.get("serving_pool_bytes_per_token").value(
        pool="target") == per_token
    assert reg.get("serving_state_bytes_per_slot").value(
        pool="target") == per_slot
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        n_pool = len(jax.tree_util.tree_leaves(args[:5]))
        assert n_pool == 2 * 3 + 2 * 3 == step.n_donatable
    assert engine_mod._expert_blocks(model) == []
    req = eng.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=12)
    while len(req.tokens) < 2:
        eng.step()
    assert eng.pool.blocks_in_use > 1
    # a live request holds its blocks AND its slot row
    assert eng.pool.bytes_in_use() == (
        per_token * 8 * eng.pool.blocks_in_use + per_slot)
    eng.run()
    assert eng.engine_stats()["pool"]["peak_blocks_in_use"] == 1 + 4
    assert eng.pool.bytes_in_use() == per_token * 8     # the scratch block


def test_a_state_model_is_refused_as_a_draft_whatever_its_layers_name():
    llama = llama_tiny()
    with pytest.raises(NotImplementedError, match="spec_draft"):
        ServingEngine(llama, num_slots=2, block_size=8, max_context=32,
                      spec_draft=F.FalconH1ForCausalLM(
                          F.FalconH1Config.tiny(vocab_size=llama.config
                                                .vocab_size)))
