"""The Falcon-H1-shaped decoder (every layer a Mamba-2 mixer AND rotary
grouped-query attention side by side on one normed input, fourteen muP
multipliers, a dense SwiGLU MLP) on the normal serving path, against the
benchmark's plain reference (``benchmark/reference/falcon_h1.py``: float32,
HIGHEST, the RECURRENCE form of the mixer, no cache), on the toy
configuration in float32. A layer keeps key blocks AND a slot's state: the
first family whose layers have a place on both sides of the pool.

The weights are the test's own: the benchmark's seeded ones make ``A`` about
-1 and ``dt`` about 0.69, so the state forgets within ~10 tokens and a wrong
carry over a chunk boundary would hide; here ``dt_bias`` is about -4 and
``A_log`` in 0..2.7. And every leaf a multiplier scales is drawn so that
leaf x multiplier has the size it would have without one (the published
0.011 on the keys would make the softmax a plain mean, 0.0375 / 0.011 on
the branches and the MLP would leave the stream to the embedding).

Tolerances: program and reference compute the same float32 numbers in
another order (a chunk at a time through decay matrices, attention folded
in tiles, a multiplier before or after a rounding), so they differ by
summation order only: logits of magnitude ~1 agree to 5e-5. The reference's
int8-operand control moves the same logits by > 100 x that and a served
token's gap to ~1e-2, so each tolerance below is asserted to be tight enough
that the control fails it.
"""
import hashlib
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import falcon_h1 as F
from paddle_tpu.obs.trace import TraceRecorder
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import falcon_h1 as family  # noqa: E402
# the sibling state-space family's helpers, as they are: a device value on
# the host, the door drained, and the engine's two bodies driven by hand
from test_granitemoehybrid import (  # noqa: E402
    _Paged, _drain, _host, _max_abs, _prompts, _serve, _stamp)
# every family's tiny preset, built and in eval mode
from test_profiler_scopes import _model as _tiny_model  # noqa: E402

reference = family.reference
LOGIT_TOL = 5e-5     # summation order in float32, logits of magnitude ~1
GAP_TOL = 2e-4       # a served token lies this close to the reference's best
GOLDEN = os.path.join(ROOT, "tests", "goldens", "family_step_programs.json")
# the leaf each scalar multiplier scales the product of
_SCALED = {"k_w": "key_multiplier", "o_w": "attention_out_multiplier",
           "out_w": "ssm_out_multiplier", "head": "lm_head_multiplier",
           "embed": "embedding_multiplier"}
MULTIPLIERS = ["embedding_multiplier", "lm_head_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier",
               *(f"ssm_multipliers.{i}" for i in range(5)),
               "mlp_multipliers.0", "mlp_multipliers.1"]


def _slow_leaves(cfg, seed=0):
    """name -> float32 array for every leaf of the reference's table:
    matrices of standard deviation 1/sqrt(fan-in) over the multiplier that
    scales their product, norms near 1, and a state that decays SLOWLY (see
    the module docstring)."""
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
        if short in _SCALED:
            v = v / cfg[_SCALED[short]]
        elif short == "in_w":
            v = v / cfg["ssm_in_multiplier"]
        elif short in ("gate_w", "down_w"):
            v = v / cfg["mlp_multipliers"][short == "down_w"]
        out[name] = jnp.asarray(v, jnp.float32)
    return out


def _toy_cfg():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "toy-parallel-ssm.json")) as f:
        return json.load(f)


def _model(cfg, leaves):
    dtype_was = paddle.get_default_dtype()
    try:
        model = family.build_model(cfg)
    finally:
        paddle.set_default_dtype(dtype_was)
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
    """Two sequences of 40 tokens, chunks of mamba_chunk_size 16 (an uneven
    last chunk) against the recurrence; three layers, each BOTH branches."""
    cfg, model, get_leaf = toy
    ids = np.stack(_prompts(cfg, (40, 40)))
    ref = reference.logits(cfg, get_leaf, ids)
    got = model(paddle.to_tensor(ids))._value
    assert _max_abs(ref) > 0.5
    assert _max_abs(ref, got) < LOGIT_TOL
    # the tolerance is earned: the int8-operand control fails it
    control = reference.logits(cfg, get_leaf, ids, control=True)
    assert _max_abs(ref, control) > 100 * LOGIT_TOL


def test_both_branches_and_the_positions_matter(toy):
    """What the docstring promises of the test's weights: changing the
    FIRST token still moves the logits 30 positions on through the mixer
    alone (attention's output projection zeroed); a program without the
    attention branch, or whose keys are not rotated, is far outside the
    tolerance."""
    cfg, model, get_leaf = toy
    ids = np.stack(_prompts(cfg, (31,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1

    def no_attention(name):
        leaf = get_leaf(name)
        return jnp.zeros_like(leaf) if name.endswith(".o_w") else leaf

    a, b = (reference.logits(cfg, no_attention, x)[0, -1]
            for x in (ids, other))
    assert _max_abs(a, b) > 50 * LOGIT_TOL
    ref = reference.logits(cfg, get_leaf, ids)
    assert _max_abs(ref, reference.logits(cfg, no_attention, ids)) \
        > 1000 * LOGIT_TOL
    flat = reference.logits(dict(cfg, rope_theta=1e30), get_leaf, ids)
    assert _max_abs(ref, flat) > 1000 * LOGIT_TOL


# ------------------------------------------- both sides of the pool at once
def test_chunked_prefill_then_decode_through_both_sides(toy):
    """A 37-token prompt in chunks that split it unevenly (counts 1, C - 1,
    C, then the rest; C = 16), then 10 decode steps, teacher-forced: every
    logit the program hands out is the reference's full pass's, so the keys
    written to the blocks AND the state left in the slot row are both
    right. Beside it a row that is never live and a row that is masked in
    decode keep their state and their blocks bit for bit, and a new request
    in a used slot starts from zero."""
    cfg, model, get_leaf = toy
    seq, other = _prompts(cfg, (47, 21), seed=3)
    ref = reference.logits(cfg, get_leaf, seq[None])[0]
    ref_other = reference.logits(cfg, get_leaf, other[None])[0]
    run = _Paged(model)
    # three layers, each on BOTH sides
    assert len(run.pool.state) == 3 == len(run.pool.k_pools)
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
    blocks = run.pool._tables["r2"]
    keys = [_host(k[jnp.asarray(blocks)]) for k in run.pool.k_pools]
    for j in range(10):          # row 2 rides along masked, row 1 idle
        logits = run.decode([seq[37 + j], 0, 5], [True, False, False])
        assert _max_abs(logits[0], ref[37 + j]) < LOGIT_TOL
    for layer, want in zip(run.pool.state, held):
        for a, w in zip(layer, want):
            np.testing.assert_array_equal(_host(a[2]), w)   # masked
            assert float(_host(a[1]).min()) == 7.0 == float(
                _host(a[1]).max())                          # never live
    for k, want in zip(run.pool.k_pools, keys):
        np.testing.assert_array_equal(_host(k[jnp.asarray(blocks)]), want)
    # the slot of row 0 is handed to a new request: its first chunk has
    # base length 0, so the program starts its state from zeros, and its
    # attention sees none of the blocks' old keys
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
    assert len(pool.k_pools) == 3 == len(pool.v_pools) == len(pool.state)
    assert tuple(pool.k_pools[0].shape) == (64, 8, 2, 32)
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (4, 8, 16, 32), (4, 3, 8 * 16 + 2 * 2 * 32)]
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
    state starts from zero inside the program, its blocks are new). Then a
    request preempted in mid-decode: the slot AND its blocks are freed,
    recompute-on-resume rebuilds the state and the keys from prompt +
    tokens, and the stream is bit for bit the uninterrupted one."""
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
    in_use = eng.pool.blocks_in_use
    eng.preempt(reqs[0])
    assert eng.pool.blocks_in_use < in_use          # the keys went too
    eng.run()
    assert reqs[0].preemptions == 1
    for r, b in zip(reqs, want):
        assert np.array_equal(np.asarray(r.tokens, np.int32), b)
    assert eng.obs.registry.get("serving_state_resets_total").value() == 3


# ------------------------------------------------------- the muP multipliers
def _with(cfg, name, value):
    """``cfg`` with one multiplier (``key`` or ``key.index``) set."""
    key, _, index = name.partition(".")
    if not index:
        return dict(cfg, **{key: value})
    values = list(cfg[key])
    values[int(index)] = value
    return dict(cfg, **{key: values})


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_every_multiplier_is_applied_where_the_reference_applies_it(
        toy, name):
    """Each of the fourteen multipliers at a value of its own (1.7 x the
    published one): the program agrees with the reference; a program in
    which THAT multiplier alone is left at 1 does not."""
    cfg, _, get_leaf = toy
    key, _, index = name.partition(".")
    was = cfg[key][int(index)] if index else cfg[key]
    moved = _with(cfg, name, 1.7 * was)
    leaves = {n: get_leaf(n) for n, _, _ in reference.leaf_table(cfg)}
    ids = np.stack(_prompts(cfg, (24,), seed=5))
    ref = reference.logits(moved, get_leaf, ids)
    got = _model(moved, leaves)(paddle.to_tensor(ids))._value
    assert _max_abs(ref, got) < LOGIT_TOL * max(1.0, _max_abs(ref))
    left = _model(_with(moved, name, 1.0), leaves)(
        paddle.to_tensor(ids))._value
    assert _max_abs(ref, left) > 100 * LOGIT_TOL


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


@pytest.mark.parametrize("kwargs,name", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"tp": 2}, "tp > 1"),
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"spec_draft": "self"}, "spec_draft"),
])
def test_refusals_by_name(kwargs, name):
    """Decided by what the layers' PARTS cache (a slot's recurrent state
    among them), never by the model's class or a config attribute."""
    paddle.seed(0)
    model = F.FalconH1ForCausalLM(F.FalconH1Config.tiny())
    if kwargs.get("spec_draft"):
        kwargs = {"spec_draft": F.FalconH1ForCausalLM(
            F.FalconH1Config.tiny())}
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(model, num_slots=2, block_size=8, max_context=32,
                      **kwargs)
    assert name in str(err.value) and "state-space" in str(err.value)


def test_a_state_model_is_refused_as_a_draft_whatever_its_layers_name():
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    llama = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    with pytest.raises(NotImplementedError, match="spec_draft"):
        ServingEngine(llama, num_slots=2, block_size=8, max_context=32,
                      spec_draft=F.FalconH1ForCausalLM(
                          F.FalconH1Config.tiny(vocab_size=llama.config
                                                .vocab_size)))


@pytest.mark.parametrize("overrides,what", [
    ({"attention_bias": True}, "bias"),
    ({"projectors_bias": True}, "bias"),
    ({"mamba_conv_bias": False}, "convolution"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"mamba_d_ssm": 64}, "mamba_d_ssm"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"mamba_rms_norm": False}, "gated norm"),
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
    ({"mamba_use_mlp": False}, "MLP"),
    ({"attn_layer_indices": [0]}, "attn_layer_indices"),
    ({"rope_scaling": {"type": "linear"}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"sliding_window": 16}, "sliding_window"),
    ({"ssm_multipliers": (1.0, 1.0)}, "ssm_multipliers"),
    ({"model_type": "mamba2"}, "model_type"),
])
def test_the_config_refuses_what_the_model_does_not_compute(overrides, what):
    with pytest.raises(NotImplementedError, match=what):
        F.FalconH1Config.tiny(**overrides)


def test_the_published_preset_counts_the_issues_parameters():
    """``falcon_h1_34b()`` is the source's config: a layer counts ISSUE
    41's 430,120,032 parameters, the cut of six 5,254,594,112 and the
    whole model 33,642,516,224 (from shapes: nothing is allocated)."""
    def count(cfg):
        shapes = jax.eval_shape(lambda: [
            p._value for _, p in
            F.FalconH1ForCausalLM(cfg).named_parameters()])
        return sum(int(np.prod(s.shape)) for s in shapes)

    top = 2 * 261120 * 5120 + 5120
    assert count(F.FalconH1Config.falcon_h1_34b(num_hidden_layers=1)) \
        == 430_120_032 + top
    assert count(F.FalconH1Config.falcon_h1_34b(num_hidden_layers=6)) \
        == 6 * 430_120_032 + top == 5_254_594_112
    assert count(F.FalconH1Config.falcon_h1_34b()) \
        == 72 * 430_120_032 + top == 33_642_516_224


# ------------------------------------------------------ spans and counters
def test_counters_spans_and_scopes(toy):
    cfg, model, _ = toy
    rec = TraceRecorder.process()
    first = rec.next_id()
    door = _serve(model)
    _drain(door, _prompts(cfg, (20, 9)), 9)
    eng = door.engine
    reg = eng.obs.registry
    assert reg.get("serving_state_resets_total").value() == 2
    assert reg.get("serving_moe_layer_steps_total").value() == 0
    spans = [e for e in rec.events
             if e.get("args", {}).get("id", -1) >= first]
    mixed = [e["args"] for e in spans if e["name"] == "engine.mixed"]
    assert mixed and all("moe_rows" not in a for a in mixed)
    # the cost ledger's 2N: every parameter but the embedding (a lookup)
    n = sum(int(p._value.size) for _, p in model.named_parameters())
    assert eng.obs.ledger.flops_per_token == 2.0 * (n - 2048 * 128)
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        text = step.lower(*args).as_text(debug_info=True)
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out",
                      "attn.proj", "attn.full", "cache.write", "mix.sum",
                      "mlp", "norm", "embed", "head"):
            assert scope in text, scope


# ------------------------------- the other five families' programs, as were
FAMILIES = ("llama", "deepseek_v3", "granitemoehybrid", "afmoe",
            "nemotron_h")


def step_program_hashes(name):
    """sha256 of the two step programs' StableHLO text (no locations) of
    family ``name`` at its tiny preset, as the engine lowers them."""
    eng = ServingEngine(_tiny_model(name), num_slots=2, block_size=8,
                        max_context=64,
                        prefill_chunk=16, decode_quantum=4)
    eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
    eng._admit()
    return {program: hashlib.sha256(
        step.lower(*args).as_text().encode()).hexdigest()
        for program, (step, args) in (
            ("mixed", eng.mixed_step_target()),
            ("quantum", eng.decode_step_target()))}


@pytest.mark.parametrize("name", FAMILIES)
def test_the_other_families_step_programs_are_byte_for_byte(name):
    """ISSUE 41 changed ``Mamba2Mixer``, ``NoPositionAttention``'s base
    and the engine's ``_layer_caches`` / ``_collect_caches``, all shared:
    the five older families' two jitted programs lower to the text they
    lowered to at the parent commit (``tests/goldens/
    family_step_programs.json``, written there by ``python
    tests/test_falcon_h1.py``; after an INTENDED change to a family's
    programs, write it again and review the diff)."""
    with open(GOLDEN) as f:
        golden = json.load(f)
    assert step_program_hashes(name) == golden[name]


if __name__ == "__main__":
    with open(GOLDEN, "w") as f:
        json.dump({name: step_program_hashes(name) for name in FAMILIES},
                  f, indent=1, sort_keys=True)
        f.write("\n")
