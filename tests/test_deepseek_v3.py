"""The DeepSeek-V3-shaped decoder (latent attention, routed experts beside
shared ones): what is this family's own. The contract every served family
holds is ``tests/test_family_contract.py`` over this family's row of
``tests/family_harness.py`` (the benchmark's seeded weights; the tolerances
and their reasons are there). Here: the absorbed decode form, the gate, no
token dropped at any imbalance, the latent pool with its prefix cache, and
the latent pool's two kernels (chunk attention, PR 31; paged decode, PR 33).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import SigmoidTopKGate
from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
    grouped_expert_ffn)
from paddle_tpu.nlp import DeepseekV3Config, PagedKVCachePool
from paddle_tpu.nlp.deepseek_v3 import DeepseekV3MoE
from paddle_tpu.serving import ServingEngine

from family_harness import (
    FAMILIES, door, drain, host, llama_tiny, max_abs, prompts)

ROW = FAMILIES["deepseek_v3"]
family = ROW.module
LOGIT_TOL = ROW.logit_tol


def test_absorbed_decode_equals_the_unabsorbed_form(toy):
    """The decode form (query carried into latent space, the pool read
    once) against the whole-sequence form (per-head keys and values from
    ``c``) at the same position."""
    cfg, model, _ = toy
    attn = model.model.layers[1].self_attn
    s, n, bs = 2, 21, 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((s, n, cfg["hidden_size"])),
                    jnp.float32)
    want = jax.jit(attn)(paddle.to_tensor(x))._value[:, -1]
    pool = jnp.zeros((8, bs, 64 + 16), jnp.float32)
    tables = jnp.asarray(1 + np.arange(s * 3).reshape(s, 3), jnp.int32)
    pos = jnp.arange(n - 1)
    rope = attn.paged_rope(jnp.broadcast_to(pos, (s, n - 1)).astype(
        jnp.float32))
    _, (pool, *_) = jax.jit(attn.paged_chunk)(
        paddle.to_tensor(x[:, :-1]), rope, tables, jnp.zeros(s, jnp.int32),
        tables[jnp.arange(s)[:, None], pos[None, :] // bs],
        jnp.broadcast_to(pos % bs, (s, n - 1)), (pool, None, None, None))
    last = jnp.full((s,), n - 1)
    got, (pool2, v, ks, vs) = jax.jit(attn.paged_decode)(
        paddle.to_tensor(x[:, -1:]), attn.paged_rope(last.astype(
            jnp.float32)), tables, last + 1, tables[:, (n - 1) // bs],
        last % bs, (pool, None, None, None))
    assert v is None and ks is None and vs is None
    assert max_abs(got._value[:, 0], want) < LOGIT_TOL


# ---------------------------------------------------------------- the gate
def test_gate_against_the_reference_on_hand_made_scores():
    """The bias moves the choice, not the weight; weights sum to the
    scaling factor."""
    gate = SigmoidTopKGate(2, True, 2.448)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0],
                          [0.0, 0.1, 0.2, 0.3]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0], jnp.float32)
    sel, w, aux = gate.topk_assignments(logits, bias)
    s = jax.nn.sigmoid(logits)
    assert aux is None
    # without the bias row 0 picks experts 0, 1; with it 0, 2
    assert sorted(host(gate.topk_assignments(logits)[0][0])) == [0, 1]
    assert sorted(host(sel[0])) == [0, 2]
    picked = np.take_along_axis(host(s), host(sel), 1)
    np.testing.assert_allclose(
        host(w), 2.448 * picked / picked.sum(1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(host(w).sum(1), 2.448, rtol=1e-6)
    m = {"top_k": 2, "norm_topk": True, "scaling": 2.448}
    eye = {"router_w": jnp.eye(4, dtype=jnp.float32), "router_b": bias}
    rsel, rw = family.reference.route(logits, eye, m)
    order = np.argsort(host(sel), 1), np.argsort(host(rsel), 1)
    np.testing.assert_array_equal(
        np.take_along_axis(host(sel), order[0], 1),
        np.take_along_axis(host(rsel), order[1], 1))
    np.testing.assert_allclose(
        np.take_along_axis(host(w), order[0], 1),
        np.take_along_axis(host(rw), order[1], 1), rtol=1e-6)


def test_gate_refuses_groups_by_name():
    with pytest.raises(NotImplementedError, match="n_group"):
        SigmoidTopKGate(2, n_group=4, topk_group=2)


@pytest.mark.parametrize("spread", ["one_expert_set", "even"])
def test_no_token_is_dropped_at_any_imbalance(spread):
    """All rows to one set of experts: every row is still multiplied by
    all of its experts (no capacity), and the block equals a dense sum
    over the chosen experts."""
    paddle.seed(5)
    cfg = DeepseekV3Config.tiny()
    block = DeepseekV3MoE(cfg)
    if spread == "one_expert_set":
        # a selection bias that outweighs every score: experts 5, 6, 7
        block.gate.e_score_correction_bias._value = jnp.asarray(
            [0, 0, 0, 0, 0, 9, 9, 9], jnp.float32)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((3, 11, 64)),
                    jnp.float32)
    got = block(paddle.to_tensor(x))._value
    rows = host(block.rows_per_expert)
    assert rows.sum() == 33 * cfg.num_experts_per_tok
    if spread == "one_expert_set":
        assert list(rows) == [0, 0, 0, 0, 0, 33, 33, 33]
    xt = x.reshape(-1, 64)
    s = jax.nn.sigmoid(xt @ block.gate.weight._value)
    sel = jax.lax.top_k(s + block.gate.e_score_correction_bias._value, 3)[1]
    w = jnp.take_along_axis(s, sel, 1)
    w = 2.448 * w / w.sum(1, keepdims=True)
    sel_host = host(sel).tolist()
    w1, w2 = (block.experts.gate_up_proj._value,
              block.experts.down_proj._value)
    want = block.shared_experts(paddle.to_tensor(xt))._value
    for t in range(33):
        for j in range(3):
            e = sel_host[t][j]
            gu = xt[t] @ w1[e]
            want = want.at[t].add(
                w[t, j] * ((jax.nn.silu(gu[:32]) * gu[32:]) @ w2[e]))
    np.testing.assert_allclose(host(got.reshape(-1, 64)),
                               host(want), atol=2e-5)


def test_grouped_core_counts_every_row():
    """The factored sort + ragged_dot core hands every row to its expert
    whatever the weights, and reports the rows each expert got."""
    rng = np.random.default_rng(0)
    xt = jnp.asarray(rng.standard_normal((6, 4)), jnp.float32)
    ids = jnp.asarray([[1, 1], [1, 2], [1, 0], [1, 3], [1, 1], [1, 2]])
    w = jnp.ones((6, 2), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((4, 4, 4)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((4, 4, 4)), jnp.float32)
    y, rows = grouped_expert_ffn(xt, ids, w, w1, w2, jnp.tanh)
    assert list(host(rows)) == [1, 8, 2, 1]
    want = sum(jnp.einsum("tf,tfm->tm", jnp.tanh(jnp.einsum(
        "tm,tmf->tf", xt, w1[ids[:, j]])), w2[ids[:, j]]) for j in range(2))
    np.testing.assert_allclose(host(y), host(want), atol=1e-5)


# ---------------------------------------------------------------- the pool
def _latent_pool(**kw):
    return PagedKVCachePool(num_blocks=8, block_size=4, num_kv_heads=1,
                            head_dim=12, num_layers=3, dtype=jnp.float32,
                            layout="latent", **kw)


def test_latent_pool_accounting():
    pool = _latent_pool()
    assert pool.v_pools == [] and pool.k_scales == [] == pool.v_scales
    assert [a.shape for a in pool.k_pools] == [(8, 4, 12)] * 3
    assert pool.arrays_per_layer == 1
    assert pool.bytes_per_token() == 3 * 12 * 4
    pool.ensure("a", 9)                       # three blocks
    st = pool.fragmentation_stats()
    assert st["blocks_in_use"] == 3 and st["bytes_per_token"] == 144
    assert st["bytes_in_use"] == 3 * 4 * 144
    kv = PagedKVCachePool(num_blocks=8, block_size=4, num_kv_heads=1,
                          head_dim=12, num_layers=3, dtype=jnp.float32)
    assert kv.bytes_per_token() == 2 * pool.bytes_per_token()
    # adopt / commit_like take the empty V side as they take empty scales
    pool.adopt(list(pool.k_pools), [], (), ())
    pool.commit_like(pool.k_pools[0])
    assert pool.v_pools == []


def test_latent_pool_copy_on_write_and_prefix_publication():
    pool = _latent_pool(prefix_cache=True)
    toks = np.arange(8, dtype=np.int32)
    table = pool.ensure("a", 8)
    for i in range(3):
        pool.k_pools[i] = pool.k_pools[i].at[jnp.asarray(table)].set(
            float(i + 1))
    assert pool.publish_prefix("a", toks) == 2
    assert pool.attach_prefix("b", toks) == 8
    assert pool._tables["b"] == table
    assert pool.make_writable("b", 4, 8) == 1 and pool.cow_copies == 1
    fresh = pool._tables["b"][1]
    assert fresh != table[1] and pool._tables["b"][0] == table[0]
    for i in range(3):                       # the copy carries the rows
        np.testing.assert_array_equal(host(pool.k_pools[i][fresh]),
                                      host(pool.k_pools[i][table[1]]))
    st = pool.fragmentation_stats()          # raises on accounting drift
    assert st["shared_blocks"] >= 1 and st["cached_blocks"] == 2


def test_engine_serves_a_shared_prefix_from_the_latent_pool(toy):
    """Prefix publication and copy-on-write through the engine: the second
    request aliases the first's blocks and both streams are what an
    unshared engine serves."""
    cfg, model, _ = toy
    base = prompts(cfg, (32,))[0]
    rows = [np.concatenate([base, t]) for t in prompts(cfg, (5, 7), 9)]
    plain = drain(door(ROW.name), rows, 8)
    cached = ROW.serve(prefix_cache=True)
    first = drain(cached, rows[:1], 8)
    second = drain(cached, rows[1:], 8)
    assert cached.engine.pool.prefix_hits >= 4
    for got, want in zip(first + second, plain):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("kwargs", [{"kv_dtype": "int8"}, {"mesh": True}])
def test_latent_pool_refusals(kwargs):
    if kwargs.get("mesh"):
        kwargs = {"mesh": jax.sharding.Mesh(host(jax.devices()[:2]),
                                            ("mp",))}
    with pytest.raises(NotImplementedError, match="latent pool"):
        _latent_pool(**kwargs)


def test_a_model_without_experts_returns_no_rows():
    """Llama's programs return an empty tuple where the rows would be: no
    aval, so its graphs are what they were (the goldens hold that)."""
    model = llama_tiny()
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    step, args = eng.decode_step_target()
    assert jax.eval_shape(step._jitted, *args)[-1] == ()
    assert eng.obs.registry.get(
        "serving_moe_layer_steps_total").value() == 0


def test_multi_quantum_carries_the_rows(toy):
    cfg, model, _ = toy
    rows = prompts(cfg, (20, 9))
    want = drain(door(ROW.name), rows, 14)
    multi = ROW.serve(multi_quantum=2)
    got = drain(multi, rows, 14)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    reg = multi.engine.obs.registry
    assert reg.get("serving_moe_layer_steps_total").value() \
        == multi.engine.stats["decode_quanta"] * 4 * 2


# ------------------------------------- the chunk-attention kernel (PR 31)
_KW = dict(h=4, dn=16, dr=8, dv=16, r=32, bs=8)   # 192 : 128 as 24 : 16


def _chunk_case(name, dtype):
    """(q_nope, q_rope, pool, tables, base_lens, w_kvb, kernel keywords)
    of one case: toy widths with the real ratio of key to value width,
    every slot's blocks its own, shuffled over the pool."""
    h, dn, dr, dv, r, bs = (_KW[k] for k in ("h", "dn", "dr", "dv", "r",
                                             "bs"))
    c, w, base, kw, short = {
        # a base of 0, one in the middle of a block, one whose chunk
        # ends in the table's last block
        "uneven_base_lens": (16, 6, [0, 13, 32], {}, None),
        # 24 queries in tiles of 16, keys in tiles of two blocks
        "chunk_not_a_multiple_of_the_query_tile": (
            24, 7, [5, 0, 30], {"block_q": 16, "block_k": 16}, None),
        # entries past a row's need are block 0, as the engine pads them
        "table_with_padding_entries": (
            16, 9, [3, 17, 0], {"block_k": 24}, [3, 5, 2]),
        # an idle slot: base 0, every entry the scratch block
        "padding_row": (16, 6, [9, 0, 20], {"block_q": 8}, [4, 0, 5]),
    }[name]
    s_ = len(base)
    nb = s_ * w + 2
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    q_nope = jax.random.normal(ks[0], (s_, c, h, dn)).astype(dtype)
    q_rope = jax.random.normal(ks[1], (s_, c, h, dr)).astype(dtype)
    pool = jax.random.normal(ks[2], (nb, bs, r + dr)).astype(dtype)
    w_kvb = (jax.random.normal(ks[3], (r, h * (dn + dv))) * 0.3).astype(dtype)
    tables = np.random.default_rng(0).permutation(
        np.arange(2, nb))[:s_ * w].reshape(s_, w).astype(np.int32)
    for row, n in enumerate(short or ()):
        tables[row, n:] = 1 if n == 0 else 0
    return (q_nope, q_rope, pool, jnp.asarray(tables),
            jnp.asarray(base, jnp.int32), w_kvb, kw)


def _plain_chunk_attention(q_nope, q_rope, pool, tables, base_lens, w_kvb,
                           scale):
    """A masked softmax over the whole table's up-projected keys and
    values, in float32: nothing tiled, nothing folded."""
    f32 = jnp.float32
    s_, c, h, dn = q_nope.shape
    rows = pool[tables].reshape(s_, -1, pool.shape[-1]).astype(f32)
    r = rows.shape[-1] - q_rope.shape[-1]
    w = w_kvb.astype(f32).reshape(r, h, -1)
    k_nope = jnp.einsum("skr,rhd->skhd", rows[..., :r], w[..., :dn])
    v = jnp.einsum("skr,rhd->skhd", rows[..., :r], w[..., dn:])
    logits = (jnp.einsum("schd,skhd->shck", q_nope.astype(f32), k_nope)
              + jnp.einsum("schd,skd->shck", q_rope.astype(f32),
                           rows[..., r:])) * scale
    seen = (jnp.arange(rows.shape[1])[None, None, :]
            <= (base_lens[:, None] + jnp.arange(c)[None, :])[..., None])
    p = jax.nn.softmax(jnp.where(seen[:, None], logits, -jnp.inf), axis=-1)
    return jnp.einsum("shck,skhd->schd", p, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("name", [
    "uneven_base_lens", "chunk_not_a_multiple_of_the_query_tile",
    "table_with_padding_entries", "padding_row"])
def test_chunk_attention_kernel_matches_the_xla_loop_and_a_plain_softmax(
        name, dtype):
    """The kernel (interpreted here) against ``_latent_chunk_attn`` at the
    same precision and against a plain float32 softmax; bf16 operands
    round the keys, values and ``p`` to 2**-8, float32 only reorders."""
    from paddle_tpu.nlp.deepseek_v3 import _latent_chunk_attn
    from paddle_tpu.ops.pallas.chunk_attention import (
        latent_chunk_attention)

    *args, w_kvb, kw = _chunk_case(name, dtype)
    scale = 1.0 / np.sqrt(_KW["dn"] + _KW["dr"])
    got = latent_chunk_attention(*args, w_kvb, scale, **kw)
    w3 = w_kvb.reshape(_KW["r"], _KW["h"], -1)
    loop = jax.jit(_latent_chunk_attn, static_argnums=7)(
        *args, w3[..., :_KW["dn"]], w3[..., _KW["dn"]:], scale)
    plain = jax.jit(_plain_chunk_attention, static_argnums=6)(
        *args, w_kvb, scale)
    assert got.shape == loop.shape and got.dtype == loop.dtype == dtype
    assert np.isfinite(host(got.astype(jnp.float32))).all()
    assert max_abs(plain) > 0.5
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    assert max_abs(got.astype(jnp.float32), loop.astype(jnp.float32)) < tol
    assert max_abs(got.astype(jnp.float32), plain) < tol


def test_mixed_step_through_the_kernel_serves_the_xla_routes_tokens(
        toy, request, chunk_programs):
    """The engine's mixed step with the kernel route forced (prompts of
    several chunks, rows of uneven length, idle slots) picks the tokens
    of the XLA route, and each engine's programs are counted under their
    own path, on the engine's registry too."""
    cfg, model, _ = toy
    rows = prompts(cfg, (37, 20, 9), seed=31)
    before = chunk_programs()
    want = drain(ROW.serve(), rows, 6)
    mid = chunk_programs()
    assert mid["xla"] > before["xla"] and mid["kernel"] == before["kernel"]
    request.getfixturevalue("pallas_forced")
    forced = ROW.serve()
    got = drain(forced, rows, 6)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    after = chunk_programs()
    assert after["kernel"] > mid["kernel"] and after["xla"] == mid["xla"]
    assert forced.engine.obs.registry.get(
        "serving_chunk_attention_programs_total").value(
            path="kernel") == after["kernel"]


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_a_traced_mixed_program_counts_its_chunk_attention_path(
        toy, path, request, chunk_programs):
    """Tracing one mixed program raises the counter by ONE on its path's
    label, however many layers the model has (three here)."""
    if path == "kernel":
        request.getfixturevalue("pallas_forced")
    before = chunk_programs()
    step, args = ROW.serve().engine.mixed_step_target()
    step.lower(*args)
    other = "xla" if path == "kernel" else "kernel"
    after = chunk_programs()
    assert after[path] == before[path] + 1
    assert after[other] == before[other]


def test_a_dense_models_mixed_program_counts_the_same_counter(
        pallas_forced, chunk_programs):
    """The counter is the mixed step's, not the latent pool's: since the
    K/V table has a chunk kernel too (``gqa_chunk_attention``), a dense
    model's traced program raises it on its own route, once."""
    model = llama_tiny()
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    before = chunk_programs()
    step, args = eng.mixed_step_target()
    step.lower(*args)
    assert chunk_programs() == {"kernel": before["kernel"] + 1,
                                "xla": before["xla"]}


# ---------------------------- the paged decode kernel over the latent pool
_DECODE_WIDTHS = {"kanana": dict(h=32, r=512, dr=64, bs=32, w=36, rows=1024),
                  "toy": dict(h=4, r=32, dr=8, bs=8, w=11, rows=32)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("widths", list(_DECODE_WIDTHS))
def test_latent_decode_kernel_matches_the_xla_gather(widths, dtype,
                                                     monkeypatch):
    """The latent pool's decode kernel (interpreted here) against ``_latent_decode_attn`` at the same precision, ragged lengths
    in ONE call: one token, a block's edge and the next, one past it, a
    partial last chunk, the table's full width, and an idle slot (zeros
    out, never a NaN). Table tails name blocks far outside the pool:
    never read."""
    from paddle_tpu.nlp.deepseek_v3 import _latent_decode_attn
    from paddle_tpu.ops.pallas import paged_attention as kernel

    h, r, dr, bs, w, rows = (_DECODE_WIDTHS[widths][k] for k in (
        "h", "r", "dr", "bs", "w", "rows"))
    monkeypatch.setattr(kernel, "_CHUNK_ROWS", rows)
    chunk = rows // bs
    assert chunk < w < 3 * chunk      # a table of whole chunks and a part
    lens = np.asarray([1, bs, 2 * bs, 2 * bs + 1, chunk * bs + bs + 5,
                       w * bs, 0], np.int32)
    s_ = len(lens)
    nb = s_ * w + 1
    rng = np.random.default_rng(h + bs)
    tables = (rng.permutation(nb - 1)[:s_ * w].reshape(s_, w) + 1).astype(
        np.int32)
    for i, n in enumerate(lens):
        tables[i, -(-n // bs):] = 10 ** 6
    ks = jax.random.split(jax.random.PRNGKey(r), 3)
    q_lat = jax.random.normal(ks[0], (s_, h, r), jnp.float32) * 0.3
    q_rope = jax.random.normal(ks[1], (s_, h, dr), jnp.float32)
    pool = jax.random.normal(ks[2], (nb, bs, r + dr)).astype(dtype)
    scale = 1.0 / np.sqrt(24.0)
    assert kernel.supports_latent(pool, r)
    got = kernel.latent_decode_attention(
        jnp.concatenate([q_lat, q_rope], -1), pool, jnp.asarray(tables),
        jnp.asarray(lens), scale, r)
    want = _latent_decode_attn(
        q_lat, q_rope, pool, jnp.asarray(np.where(tables >= nb, 0, tables)),
        jnp.asarray(lens), scale)
    assert got.shape == want.shape == (s_, h, r)
    assert got.dtype == want.dtype == jnp.float32
    got, want = host(got), host(want)
    assert np.isfinite(got).all() and not got[-1].any()
    assert np.abs(want[:-1]).max() > 0.5
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=tol, atol=tol)


def _decode_programs(path):
    from paddle_tpu.nlp.paged_attention import latent_decode_programs

    return latent_decode_programs().value(path=path)


def _serve_with_a_shared_prefix_and_a_preemption(cfg, model):
    """A request whose prompt is four whole blocks of an earlier one's
    (prefix reuse, and copy-on-write where it recomputes its last token
    into a shared block) beside one that is preempted in mid-decode and
    resumed: every stream's tokens."""
    base = prompts(cfg, (32,), seed=5)[0]
    rows = [np.concatenate([base, prompts(cfg, (5,), seed=6)[0]]), base,
            prompts(cfg, (21,))[0]]
    cached = ROW.serve(prefix_cache=True)
    first = cached.submit(rows[0], max_new_tokens=10)
    while cached.engine.has_work:
        cached.pump()
    rest = [cached.submit(p, max_new_tokens=10) for p in rows[1:]]
    while len(rest[1].request.tokens) < 3:
        cached.pump()
    cached.engine.preempt(rest[1].request)
    while cached.engine.has_work:
        cached.pump()
    pool = cached.engine.pool
    assert pool.prefix_hits >= 4 and pool.cow_copies >= 1
    assert cached.engine.scheduler.preempted_total >= 1
    return [host(s.request.tokens, np.int32) for s in [first] + rest]


def test_engine_decodes_the_latent_pool_through_the_kernel(toy, request):
    """The decode quantum with the kernel routed in (interpreted) serves
    the greedy tokens of the XLA route over the reshaped latent pool:
    prefix reuse, copy-on-write and a preempted request resumed. Each
    engine's quantum is counted under its own path, on the engine's
    registry too."""
    cfg, model, _ = toy
    xla0, kernel0 = _decode_programs("xla"), _decode_programs("kernel")
    want = _serve_with_a_shared_prefix_and_a_preemption(cfg, model)
    assert _decode_programs("xla") > xla0
    assert _decode_programs("kernel") == kernel0
    xla1 = _decode_programs("xla")
    request.getfixturevalue("pallas_forced")
    got = _serve_with_a_shared_prefix_and_a_preemption(cfg, model)
    assert all(len(t) == 10 for t in want)
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert _decode_programs("kernel") > kernel0
    assert _decode_programs("xla") == xla1


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_a_traced_quantum_counts_its_latent_decode_path(toy, path, request):
    """Tracing one quantum raises the counter by ONE on its path's label,
    however many layers and scanned steps it has, and ``/metrics`` of the
    engine shows the process's count."""
    _, model, _ = toy
    if path == "kernel":
        request.getfixturevalue("pallas_forced")
    before = {p: _decode_programs(p) for p in ("xla", "kernel")}
    engine = ROW.serve().engine
    step, args = engine.decode_step_target()
    step.lower(*args)
    other = "xla" if path == "kernel" else "kernel"
    assert _decode_programs(path) == before[path] + 1
    assert _decode_programs(other) == before[other]
    assert engine.obs.registry.get(
        "serving_latent_decode_programs_total").value(
            path=path) == _decode_programs(path)
    assert (f'serving_latent_decode_programs_total{{path="{path}"}}'
            in engine.obs.registry.prometheus())


def test_a_dense_models_quantum_counts_no_latent_decode_path(pallas_forced):
    model = llama_tiny()
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    before = {p: _decode_programs(p) for p in ("xla", "kernel")}
    step, args = eng.decode_step_target()
    step.lower(*args)
    assert before == {p: _decode_programs(p) for p in ("xla", "kernel")}
