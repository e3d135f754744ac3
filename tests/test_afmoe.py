"""The AFMoE-shaped decoder (window and full attention layers mixed three to
one, gated attention, per-head q/k norms, four norms a layer, routed experts
beside a shared one): what is this family's own. The contract every served
family holds is ``tests/test_family_contract.py`` over this family's row of
``tests/family_harness.py`` (the benchmark's seeded weights; the tolerances
and their reasons are there). Here: the window's bounds to one key, the ring
and its kernel, the router, the expert block shared with the latent family,
the pool's ring accounting, and what the other families' engines do not
carry.

Sizes: window 8, prefill chunk 4, blocks of 4, so a window layer's ring has
12 rows; contexts reach 40 and more, so a ring wraps three times.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import SigmoidTopKGate
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM, PagedKVCachePool
from paddle_tpu.nlp import afmoe as A
from paddle_tpu.nlp import paged_attention as PA
from paddle_tpu.nlp.deepseek_v3 import DeepseekV3Config, DeepseekV3MoE
from paddle_tpu.nlp.llama import _paged_write
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod

from family_harness import (
    FAMILIES, RING, WINDOW, drain, host, llama_tiny, max_abs, prompts,
    tiny_model)

ROW = FAMILIES["afmoe"]
reference = ROW.reference
LOGIT_TOL = ROW.logit_tol
CHUNK, BLOCK = 4, 4


def test_the_window_is_what_the_reference_computes(toy):
    """Changing the FIRST token moves no logit of a window-only stack eight
    or more positions on, and moves them with a full layer in it: the
    reference's window is a window."""
    cfg, _, get_leaf = toy
    ids = np.stack(prompts(cfg, (20,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1
    only = dict(cfg, num_hidden_layers=1, layer_types=["sliding_attention"])
    a, b = (reference.logits(only, get_leaf, x)[0] for x in (ids, other))
    assert max_abs(a[WINDOW:], b[WINDOW:]) == 0.0 < max_abs(a[:WINDOW],
                                                             b[:WINDOW])
    full = dict(only, layer_types=["full_attention"])
    a, b = (reference.logits(full, get_leaf, x)[0] for x in (ids, other))
    assert max_abs(a[WINDOW:], b[WINDOW:]) > 100 * LOGIT_TOL


@pytest.mark.parametrize("q_block", [512, 7])
def test_the_references_blocks_are_a_dense_mask(q_block):
    """``reference.attention`` in blocks of queries against the one dense
    mask it stands for, both layer kinds."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 29, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 29, 2, 8)), jnp.float32)
            for _ in range(2))
    t, u = np.arange(29)[:, None], np.arange(29)[None, :]
    for window in (0, 5):
        seen = (u <= t) & ((u > t - window) if window else True)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(2, 29, 2, 2, 8), k,
                        precision="highest") / np.sqrt(8.0)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        want = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                          precision="highest").reshape(2, 29, 32)
        got = reference.attention(q, k, v, window, q_block=q_block)
        assert max_abs(got, want) < 1e-6


# ------------------------------------------------- what a query may see
def _one_hot_keys(slots, positions, hk, d):
    """Keys of zeros (every score equal) and values that name their
    position: position ``s`` holds the unit vector ``e_s`` in every head,
    so an attention output reads 1 / |seen| at every seen position."""
    v = np.zeros((slots, positions, hk, d), np.float32)
    v[:, np.arange(positions), :, np.arange(positions)] = 1.0
    return jnp.zeros((slots, positions, hk, d), jnp.float32), jnp.asarray(v)


def _seen(out):
    """The positions an output of `_one_hot_keys` values saw, per row."""
    out = host(out)
    sets = []
    for row in out.reshape(out.shape[0], -1, out.shape[-1]):
        at = np.nonzero(row[0] > 0)[0]
        np.testing.assert_allclose(row[:, at], 1.0 / len(at), rtol=1e-6)
        sets.append(at.tolist())
    return sets


def test_a_query_sees_exactly_its_window_and_a_full_layer_everything():
    """Hand-made at the window's edge, W = 8 over a ring of 12: after 30
    positions the decode query ``t`` = 29 sees 22 .. 29 and not 21; the
    chunk queries 26 .. 29 see ``t - 7 .. t`` each, the first of them a key
    the chunk's own writes came within a row of overwriting. A full layer
    over blocks sees 0 .. t. A row one position long sees itself alone,
    whatever an earlier request left in the ring."""
    slots, hk, g, d = 2, 2, 2, 32
    k, v = _one_hot_keys(slots, 30, hk, d)
    ring_k = jnp.full((slots, RING, hk * d), 9.0)    # an old request's keys
    ring_v = jnp.full((slots, RING, hk * d), 9.0)
    pos = jnp.broadcast_to(jnp.arange(30)[None, :], (slots, 30))
    lens = jnp.asarray([26, 1], jnp.int32)           # row 1: one position
    # row 0 writes 0 .. 25, row 1 position 0 only
    ring_k, ring_v = PA.ring_write(ring_k, ring_v, k, v, pos,
                                   pos < lens[:, None])
    q = jnp.ones((slots, hk * g, d), jnp.float32)
    assert _seen(PA.ring_decode_attn(q, ring_k, ring_v, lens, WINDOW)) == [
        list(range(18, 26)), [0]]
    # the chunk 26 .. 29 of row 0 (row 1 brings nothing), then its queries
    base, counts = jnp.asarray([26, 1], jnp.int32), jnp.asarray([4, 0])
    at = base[:, None] + jnp.arange(4)[None, :]
    ring_k, ring_v = PA.ring_write(
        ring_k, ring_v, k[:, 26:30], v[:, 26:30], at,
        jnp.arange(4)[None, :] < counts[:, None])
    out = PA.ring_chunk_attn(jnp.ones((slots, 4, hk * g, d), jnp.float32),
                             ring_k, ring_v, base, counts, WINDOW)
    for j in range(4):
        assert _seen(out[:1, j])[0] == list(range(26 + j - 7, 26 + j + 1))
    assert _seen(PA.ring_decode_attn(q, ring_k, ring_v,
                                     jnp.asarray([30, 1]), WINDOW)) == [
        list(range(22, 30)), [0]]
    # a tile a query sees nothing of does not count (one ring row a tile)
    tiled = PA._CHUNK_SCORE_BYTES
    PA._CHUNK_SCORE_BYTES = slots * hk * g * 4 * 4
    try:
        again = PA.ring_chunk_attn(
            jnp.ones((slots, 4, hk * g, d), jnp.float32), ring_k, ring_v,
            base, counts, WINDOW)
    finally:
        PA._CHUNK_SCORE_BYTES = tiled
    assert max_abs(again[0], out[0]) < 1e-6
    # the full layer: blocks of 4, the same keys, every position <= t
    pool = PagedKVCachePool(16, BLOCK, hk, d, num_layers=1,
                            dtype=jnp.float32)
    pool.ensure("a", 30)
    pool.ensure("b", 30)
    tables = pool.block_table_array(["a", "b"], pad_to=8)
    blk = jnp.take_along_axis(tables, pos // BLOCK, axis=1)
    kc, vc, _, _ = _paged_write(k, v, blk, pos % BLOCK,
                                (pool.k_pools[0], pool.v_pools[0], None, None))
    out = PA._paged_chunk_attn(jnp.ones((slots, 4, hk * g, d), jnp.float32),
                               kc, vc, tables, jnp.asarray([26, 26]))
    for j in range(4):
        assert _seen(out[:1, j])[0] == list(range(26 + j + 1))
    assert _seen(PA._xla_paged_decode_attn(
        q, kc, vc, tables, jnp.asarray([30, 30])))[0] == list(range(30))


# -------------------- the Pallas chunk kernel over a ring (interpreted here)
# by case: (C, HK, G, ring rows, cached tokens a slot, valid positions a
# slot, key tile, query tile); W = 8 throughout
_RING_CASES = {
    "g1": (4, 2, 1, 12, (13, 38), (4, 4), 4, None),
    "g4": (4, 2, 4, 12, (13, 38), (4, 4), 4, None),
    "g7": (4, 1, 7, 12, (13, 38), (4, 4), 4, None),
    "g8": (4, 1, 8, 12, (13, 38), (4, 4), 4, None),
    "wrapped_three_times_over_stale_rows": (4, 2, 2, 12, (40, 37), (4, 3),
                                            4, None),
    "a_base_of_zero": (4, 2, 2, 12, (0, 0), (4, 2), 4, None),
    "an_idle_slot": (4, 2, 2, 12, (17, 0, 40), (4, 0, 4), 4, None),
    "bases_off_the_tiles": (4, 2, 2, 12, (5, 9, 22), (4, 4, 1), 4, None),
    "a_chunk_off_the_sublanes": (5, 2, 2, 16, (7, 30), (5, 4), 4, None),
    "one_key_tile": (4, 2, 2, 12, (13, 38), (4, 4), None, None),
    "two_query_tiles": (24, 2, 2, 32, (0, 45), (24, 17), 8, 16),
}


def _written_ring(rng, slots, r, hk, d, upto, dtype):
    """Rings that an EARLIER request filled with its own rows, then
    positions ``0 .. upto[s] - 1`` of each slot written in turn, so that a
    long context wraps the ring over stale rows and over its own; beside
    them every position's key and value (S, P, HK, D) for a dense
    reference."""
    top = max(max(upto), 1)
    k = jnp.asarray(rng.standard_normal((slots, top, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((slots, top, hk, d)), dtype)
    ring_k = jnp.asarray(rng.standard_normal((slots, r, hk * d)), dtype)
    ring_v = jnp.asarray(rng.standard_normal((slots, r, hk * d)), dtype)
    lens = jnp.asarray(upto)[:, None]
    for lo in range(0, top, r):
        pos = jnp.broadcast_to(jnp.arange(lo, min(lo + r, top))[None, :],
                               (slots, min(lo + r, top) - lo))
        ring_k, ring_v = jax.jit(PA.ring_write)(
            ring_k, ring_v, k[:, lo:lo + r], v[:, lo:lo + r], pos, pos < lens)
    return ring_k, ring_v, k, v


def _dense_window_attention(q, k, v, base, window):
    """(S, C, H, D) queries over every position's keys (S, P, HK, D):
    query j of a row sees ``base + j - window < p <= base + j``; a plain
    float64 softmax, K and V repeated over the query heads."""
    q, k, v = (host(a.astype(jnp.float32)).astype(np.float64)
               for a in (q, k, v))
    rep = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, rep, axis=2), np.repeat(v, rep, axis=2)
    logits = np.einsum("schd,sphd->shcp", q, k) / np.sqrt(q.shape[-1])
    t = np.asarray(base)[:, None] + np.arange(q.shape[1])[None, :]
    p = np.arange(k.shape[1])[None, None, :]
    seen = (p <= t[..., None]) & (p > t[..., None] - window)
    logits = np.where(seen[:, None], logits, -np.inf)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    return np.einsum("shcp,sphd->schd", w / w.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_RING_CASES))
def test_the_chunk_kernel_over_a_ring_matches_the_xla_fold(case, dtype):
    """``ring_chunk_attention`` over the ring AS STORED against the fold it
    replaces on a TPU, at the same precision on EVERY query (one past a
    row's count reads what the fold reads), and on the queries that count
    against a dense float64 softmax over the positions themselves: float32
    only reorders sums, bf16 rounds the operands and ``p`` to 2**-8."""
    from paddle_tpu.ops.pallas.chunk_attention import ring_chunk_attention

    c, hk, g, r, base, counts, bk, bq = _RING_CASES[case]
    d, slots = 16, len(base)
    rng = np.random.default_rng(len(case))
    upto = [b + n for b, n in zip(base, counts)]
    ring_k, ring_v, k, v = _written_ring(rng, slots, r, hk, d, upto, dtype)
    q = jnp.asarray(rng.standard_normal((slots, c, hk * g, d)), dtype)
    base, counts = jnp.asarray(base, jnp.int32), jnp.asarray(counts, jnp.int32)
    got = ring_chunk_attention(q, ring_k, ring_v, base, counts, WINDOW,
                               1.0 / np.sqrt(d), block_q=bq, block_k=bk)
    fold = jax.jit(PA._xla_ring_chunk_attn, static_argnums=5)(
        q, ring_k, ring_v, base, counts, WINDOW)
    assert got.shape == fold.shape == q.shape and got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    got = host(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    assert max_abs(got, fold.astype(jnp.float32)) < tol
    dense = _dense_window_attention(
        q, jnp.pad(k, ((0, 0), (0, c), (0, 0), (0, 0))),
        jnp.pad(v, ((0, 0), (0, c), (0, 0), (0, 0))), base, WINDOW)
    counted = host(jnp.arange(c)[None, :] < counts[:, None])
    assert np.abs(dense[counted]).max() > 0.5
    assert np.abs(got[counted] - dense[counted]).max() < tol


@pytest.mark.parametrize("tiling", ["ring_of_three_tiles", "ring_of_one_tile",
                                    "ring_of_six_tiles", "table_by_blocks",
                                    "table_in_one_tile"])
def test_the_kernel_sees_its_bounds_to_one_key(tiling):
    """The hand-made edge again, through the kernel: W = 8 over a ring of
    12 whose old rows another request left, the chunk's queries 26 .. 29
    see ``t - 7 .. t`` each and not ONE key more on either side, however
    the ring is cut into key tiles; over a table they see ``0 .. t``. A
    row that brings nothing (count 0) sees nothing."""
    from paddle_tpu.ops.pallas import chunk_attention as kernel

    slots, hk, g, d = 2, 2, 2, 32
    k, v = _one_hot_keys(slots, 30, hk, d)
    pos = jnp.broadcast_to(jnp.arange(30)[None, :], (slots, 30))
    q = jnp.ones((slots, 4, hk * g, d), jnp.float32)
    base = jnp.asarray([26, 1], jnp.int32)
    if tiling.startswith("ring"):
        ring_k = jnp.full((slots, RING, hk * d), 9.0)
        ring_v = jnp.full((slots, RING, hk * d), 9.0)
        upto = jnp.asarray([30, 0])[:, None]          # row 1: an idle slot
        for lo in (0, 12, 24):
            ring_k, ring_v = PA.ring_write(
                ring_k, ring_v, k[:, lo:lo + 12], v[:, lo:lo + 12],
                pos[:, lo:lo + 12], pos[:, lo:lo + 12] < upto)
        out = kernel.ring_chunk_attention(
            q, ring_k, ring_v, jnp.asarray([26, 0]), jnp.asarray([4, 0]),
            WINDOW,
            1.0 / np.sqrt(d), block_k={"three": 4, "one": 12, "six": 2}[
                tiling.split("_")[2]])
        for j in range(4):
            assert _seen(out[:1, j])[0] == list(range(26 + j - 7,
                                                      26 + j + 1))
        assert max_abs(out[1]) == 0.0
        return
    pool = PagedKVCachePool(16, BLOCK, hk, d, num_layers=1,
                            dtype=jnp.float32)
    pool.ensure("a", 30)
    pool.ensure("b", 30)
    tables = pool.block_table_array(["a", "b"], pad_to=8)
    blk = jnp.take_along_axis(tables, pos // BLOCK, axis=1)
    kc, vc, _, _ = _paged_write(k, v, blk, pos % BLOCK,
                                (pool.k_pools[0], pool.v_pools[0], None, None))
    out = kernel.paged_chunk_attention(
        q, kc, vc, tables, base, 1.0 / np.sqrt(d),
        block_k=BLOCK if tiling == "table_by_blocks" else None)
    for j in range(4):
        assert _seen(out[:1, j])[0] == list(range(26 + j + 1))
        assert _seen(out[1:, j])[0] == list(range(1 + j + 1))


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_a_window_models_mixed_program_counts_its_chunk_attention_route(
        toy, route, request, chunk_programs):
    """Tracing one mixed program raises the counter by ONE on its route's
    label, however many ring and table layers ask (four and one here)."""
    _, model, _ = toy
    if route == "kernel":
        request.getfixturevalue("pallas_forced")
    before = chunk_programs()
    step, args = ROW.serve().engine.mixed_step_target()
    step.lower(*args)
    other = "xla" if route == "kernel" else "kernel"
    after = chunk_programs()
    assert after[route] == before[route] + 1
    assert after[other] == before[other]


def test_the_mixed_step_through_the_kernel_serves_the_folds_streams(
        toy, request, chunk_programs):
    """The engine's mixed step with the kernel route forced (prompts of
    several chunks that wrap the rings three times, rows of uneven
    length, an idle slot, a slot reused over another request's rows)
    serves, token for token, what the XLA route serves; each engine's
    programs are counted under their own route."""
    cfg, _, _ = toy
    rows = prompts(cfg, (41, 22, 9), seed=31)

    def serve():
        door = ROW.serve(num_slots=3)
        first = drain(door, rows, 6)
        return door, first + drain(door, rows[:1], 6)

    before = chunk_programs()
    _, want = serve()
    assert chunk_programs()["kernel"] == before["kernel"]
    request.getfixturevalue("pallas_forced")
    before = chunk_programs()
    door, got = serve()
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    after = chunk_programs()
    assert after["kernel"] > before["kernel"] and after["xla"] == before["xla"]
    assert door.engine.obs.registry.get(
        "serving_chunk_attention_programs_total").value(
            path="kernel") == after["kernel"]


def test_a_dropped_write_leaves_the_ring_bit_for_bit():
    ring = jnp.arange(2 * RING * 2 * 4, dtype=jnp.float32).reshape(
        2, RING, 2 * 4)
    new = jnp.full((2, 3, 2, 4), -1.0)
    pos = jnp.asarray([[10, 11, 12], [0, 1, 2]])
    ok = jnp.asarray([[True, True, False], [False, False, False]])
    got, _ = PA.ring_write(ring, ring, new, new, pos, ok)
    want = host(ring).copy()
    want[0, 10:12] = -1.0
    np.testing.assert_array_equal(host(got), want)
    assert PA.ring_tokens(8, 4, 4) == 12 and PA.ring_tokens(2048, 1024, 32) \
        == 3072 and PA.ring_tokens(8, 5, 4) == 16


# -------------------------------------------------------------- the router
def test_router_against_hand_made_scores():
    """The selection bias moves the CHOICE, never the weight; the chosen
    scores are normalised to one and scaled."""
    gate = SigmoidTopKGate(2, True, 2.826)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    sel, w, _ = gate.topk_assignments(logits, jnp.zeros(4))
    assert host(sel).tolist() == [[0, 1]]
    np.testing.assert_allclose(host(w)[0], 2.826 * s[:2] / s[:2].sum(),
                               rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.7], jnp.float32)   # lifts expert 3
    sel, w, _ = gate.topk_assignments(logits, bias)
    assert host(sel).tolist() == [[3, 0]]
    np.testing.assert_allclose(
        host(w)[0], 2.826 * s[[3, 0]] / s[[3, 0]].sum(), rtol=1e-6)
    m = {"top_k": 2, "norm_topk": True, "scaling": 2.826}
    rsel, rw = reference.route(
        logits, {"router_w": jnp.eye(4, dtype=jnp.float32),
                 "router_b": bias}, m)
    np.testing.assert_array_equal(host(rsel), host(sel))
    np.testing.assert_allclose(host(rw), host(w), rtol=1e-6)
    with pytest.raises(NotImplementedError, match="group"):
        A.AfmoeForCausalLM(A.AfmoeConfig.tiny(n_group=2))


def test_the_lifted_block_is_the_latent_models_bit_for_bit():
    """``nlp/routed_experts.py`` under this family's names computes what
    ``DeepseekV3MoE`` computes on the latent model's toy weights, bit for
    bit, rows counted alike; each block keeps its source's leaf names."""
    paddle.seed(0)
    dcfg = DeepseekV3Config.tiny()
    theirs = DeepseekV3MoE(dcfg)
    theirs.gate.e_score_correction_bias._value = jnp.asarray(
        np.random.default_rng(0).standard_normal(8) * 0.1, jnp.float32)
    ours = A.AfmoeMoE(A.AfmoeConfig.tiny(
        hidden_size=dcfg.hidden_size,
        moe_intermediate_size=dcfg.moe_intermediate_size,
        num_experts=dcfg.n_routed_experts,
        num_experts_per_tok=dcfg.num_experts_per_tok,
        num_shared_experts=dcfg.n_shared_experts,
        route_scale=dcfg.routed_scaling_factor))
    ours.router.gate.weight._value = theirs.gate.weight._value
    ours.expert_bias._value = theirs.gate.e_score_correction_bias._value
    for name in ("gate_up_proj", "down_proj"):
        getattr(ours.experts, name)._value = getattr(
            theirs.experts, name)._value
    for name in ("gate_proj", "up_proj", "down_proj"):
        getattr(ours.shared_experts, name).weight._value = getattr(
            theirs.shared_experts, name).weight._value
    x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (2, 9, dcfg.hidden_size)).astype(np.float32))
    np.testing.assert_array_equal(host(ours(x)._value),
                                  host(theirs(x)._value))
    np.testing.assert_array_equal(host(ours.rows_per_expert),
                                  host(theirs.rows_per_expert))
    assert ours.inactive_params_per_token() \
        == theirs.inactive_params_per_token()
    assert sorted(n for n, _ in theirs.named_parameters()) == [
        "experts.down_proj", "experts.gate_up_proj",
        "gate.e_score_correction_bias", "gate.weight",
        "shared_experts.down_proj.weight", "shared_experts.gate_proj.weight",
        "shared_experts.up_proj.weight"]
    assert sorted(n for n, _ in ours.named_parameters()) == [
        "expert_bias", "experts.down_proj", "experts.gate_up_proj",
        "router.gate.weight", "shared_experts.down_proj.weight",
        "shared_experts.gate_proj.weight", "shared_experts.up_proj.weight"]
    assert theirs.router.top_k == ours.router.top_k == 3


# ---------------------------------------------------------------- the pool
def _ring_pool(**kw):
    return PagedKVCachePool(
        num_blocks=8, block_size=4, num_kv_heads=2, head_dim=6,
        num_layers=1, dtype=jnp.bfloat16,
        state={"slots": 3, "layers": 2, "ring_tokens": 12,
               "arrays": [((None, 2 * 6), None), ((None, 2 * 6), None)]},
        **kw)


def test_ring_accounting():
    pool = _ring_pool()
    assert [[(tuple(a.shape), str(a.dtype)) for a in layer]
            for layer in pool.state] == [[((3, 12, 2 * 6), "bfloat16")] * 2] * 2
    per_slot = 2 * 2 * 12 * 2 * 6 * 2
    assert pool.window_bytes_per_slot() == per_slot \
        == pool.state_bytes_per_slot()
    assert pool.bytes_per_token() == 2 * 2 * 6 * 2     # the block layer alone
    pool.ensure("__scratch__", 1)
    pool.ensure("a", 9)                                # three blocks
    st = pool.fragmentation_stats()
    assert st["window_bytes_per_slot"] == per_slot
    assert st["window_ring_tokens"] == 12 and st["state_slots"] == 3
    assert st["bytes_in_use"] == 4 * 4 * 48 + per_slot  # scratch holds none
    sides = pool.arrays()
    assert len(jax.tree_util.tree_leaves(sides)) == 2 + 4
    pool.adopt(*sides)
    pool.free("a")
    assert pool.bytes_in_use() == 4 * 48
    # a state-space slot side counts no window bytes
    other = PagedKVCachePool(
        num_blocks=8, block_size=4, num_kv_heads=2, head_dim=6,
        dtype=jnp.bfloat16, state={"slots": 3, "layers": 2,
                                   "arrays": [((4, 2, 5), "float32")]})
    assert other.window_bytes_per_slot() == 0 == other.ring_tokens
    assert other.state_bytes_per_slot() == 2 * 4 * 2 * 5 * 4


@pytest.mark.parametrize("kwargs", [{"kv_dtype": "int8"}, {"mesh": True},
                                    {"prefix_cache": True}])
def test_ring_pool_refusals(kwargs):
    if kwargs.get("mesh"):
        kwargs = {"mesh": jax.sharding.Mesh(host(jax.devices()[:2]),
                                            ("mp",))}
    with pytest.raises(NotImplementedError, match="window layer's ring"):
        _ring_pool(**kwargs)


@pytest.mark.parametrize("name", ["llama", "deepseek_v3", "granitemoehybrid"])
def test_the_other_families_have_no_ring_and_no_extra_aval(name):
    """The three families that were served before this one name no window
    in their layouts: their pools hold no ring, their programs take the
    avals they took (blocks, and the state-space family's slot side), and
    no key counter moves. (Every family's two programs are held to their
    text in ``tests/test_family_contract.py``.)"""
    model = tiny_model(name)
    layout = model.paged_cache_layout()
    assert not layout.get("window")
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    pool = eng.engine_stats()["pool"]
    assert pool["window_bytes_per_slot"] == 0 == pool["window_ring_tokens"]
    kinds = engine_mod.layout_parts(layout["layers"])
    per_block = 1 if layout["layout"] == "latent" else 2
    n_state = sum(len(layer) for layer in eng.pool.state)
    assert n_state == kinds.count("state") * len(layout.get("state", ()))
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        assert len(jax.tree_util.tree_leaves(args[:5])) == n_state + (
            per_block * (len(kinds) - kinds.count("state")
                         - kinds.count("none")))
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    eng.run()
    reg = eng.obs.registry
    assert reg.get("serving_window_keys_attended_total").value() == 0
    assert reg.get("serving_full_keys_attended_total").value() == 0
    assert reg.get("serving_window_bytes_per_slot").value(pool="target") == 0


@pytest.mark.parametrize("as_draft", [False, True])
def test_one_uniform_window_is_refused_by_the_model_that_has_it(as_draft):
    """Mistral's shape: the engine reads no config attribute; the model's
    own layout says that its layers cannot serve a window."""
    paddle.seed(0)
    windowed = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False,
                                                 sliding_window=8))
    with pytest.raises(NotImplementedError, match="one uniform window"):
        windowed.paged_cache_layout()
    plain = llama_tiny()
    kwargs = {"spec_draft": windowed} if as_draft else {}
    with pytest.raises(NotImplementedError, match="sliding_window"):
        ServingEngine(plain if as_draft else windowed, num_slots=2,
                      block_size=8, max_context=32, **kwargs)
    src = open(engine_mod.__file__).read()
    assert '"sliding_window"' not in src and "Afmoe" not in src \
        and "afmoe" not in src.replace("nlp/afmoe.py", "")
