"""The jitted mixed step (ISSUE 27, ROADMAP S1): ``_mixed_step``
dispatches ONE pool-donating program per step (``paged_chunk_math`` with
per-row counts, the head at each row's last valid position, the
quantum's own ``_select_device`` at its end) where it walked the model
layer by layer through eager ops.

Held here, on the CPU in f32: greedy streams stay what per-request
sequential generation gives in every arm the engine has (a prompt of
several chunks, prefill and decode rows in one step, an int8 pool, a
speculative draft in lockstep, a prefix-cached offset, ``tp=2``), and
per-request temperatures sample what a one-request engine at that
temperature samples; a warm bucket compiles nothing; the pools are
donated; a fault at the step's boundary leaves them usable; the bucket
is a pure function of the step's rows and is counted; the chunk
attention gives one answer whether it streams over key blocks or not.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp.generation import generate_on_device
from paddle_tpu.obs import MetricsRegistry, TraceRecorder
from paddle_tpu.serving import (
    FaultInjector, FaultSpec, InjectedFault, ServingEngine,
)
from paddle_tpu.serving import engine as engine_mod

ENGINE_KW = dict(num_slots=3, block_size=4, prefill_chunk=4,
                 decode_quantum=3)


def build_model(tensor_parallel=False, seed=0, **cfg_kw):
    paddle.seed(seed)
    cfg = LlamaConfig.tiny(tensor_parallel=tensor_parallel, **cfg_kw)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


@pytest.fixture(scope="module")
def tiny():
    return build_model()


def prompts_of(cfg, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def sequential(model, prompt, max_new):
    """The reference: one request, generated alone."""
    out = generate_on_device(model, paddle.to_tensor(prompt[None, :]),
                             max_new_tokens=max_new)
    return np.asarray(out._value)[0]


def assert_streams(engine, model, reqs):
    for req in reqs:
        np.testing.assert_array_equal(
            engine.output_tokens(req),
            sequential(model, req.prompt, req.max_new_tokens))


def mixed_rows(mark):
    return [e for e in TraceRecorder.process().spans("engine.mixed")
            if e["args"]["id"] > mark]


# ------------------------------------------- streams, arm by arm
def arm_several_chunks():
    """A 14-token prompt through chunks of 4: four steps, the last of
    two tokens (bucket 2), beside a one-chunk prompt."""
    cfg, model = build_model()
    engine = ServingEngine(model, **ENGINE_KW)
    reqs = [engine.submit(p, max_new_tokens=5)
            for p in prompts_of(cfg, (14, 3))]
    mark = TraceRecorder.process().next_id()
    engine.run()
    assert_streams(engine, model, reqs)
    buckets = [r["args"]["bucket"] for r in mixed_rows(mark)]
    assert buckets == [4, 4, 4, 2]


def arm_prefill_and_decode_rows():
    """A second request arrives while the first decodes: its chunks
    share their steps with the first one's decode row."""
    cfg, model = build_model()
    engine = ServingEngine(model, **ENGINE_KW)
    first, second = prompts_of(cfg, (5, 9))
    reqs = [engine.submit(first, max_new_tokens=12)]
    while not reqs[0].tokens:
        engine.step()
    engine.step()                      # one quantum: the row decodes
    reqs.append(engine.submit(second, max_new_tokens=6))
    # the arrival finds the next quantum in flight behind that one
    # (steady decode runs one ahead): this step collects it
    assert engine._inflight is not None
    engine.step()
    assert engine._inflight is None and engine.scheduler.waiting
    before = len(reqs[0].tokens)
    mark = TraceRecorder.process().next_id()
    engine.step()                      # chunk 1 of 3 + one decode row
    assert len(reqs[0].tokens) == before + 1
    row = mixed_rows(mark)[0]["args"]
    assert row["rows"] == 2 and row["prefill_tokens"] == 4
    # 3 slots x 4 positions, a chunk of 4 and one decode token
    assert row["bucket"] == 4 and row["padded_tokens"] == 12 - 5
    engine.run()
    assert_streams(engine, model, reqs)


def arm_int8_pool():
    """An int8 pool (per-row scale pools threaded through the program
    and donated with it): the streams do not depend on how a prompt is
    cut into chunks, and on this fixture they are the float ones."""
    cfg, model = build_model()
    prompts = prompts_of(cfg, (13, 6))
    got = []
    for chunk in (4, 8):
        engine = ServingEngine(model, kv_dtype="int8",
                               **{**ENGINE_KW, "prefill_chunk": chunk})
        reqs = [engine.submit(p, max_new_tokens=6) for p in prompts]
        engine.run()
        assert engine.pool.quantized
        got.append([list(r.tokens) for r in reqs])
    assert got[0] == got[1]
    assert_streams(engine, model, reqs)


def arm_speculative_draft():
    """The draft's program ingests the same rows in the same step: with
    a draft that IS the target (same seed), the draft pool holds the
    target pool's rows after every mixed step."""
    cfg, model = build_model()
    _, draft = build_model()
    engine = ServingEngine(model, spec_draft=draft, spec_gamma=2,
                           **ENGINE_KW)
    reqs = [engine.submit(p, max_new_tokens=6)
            for p in prompts_of(cfg, (11, 5))]
    checked = 0
    while engine.scheduler.prefilling() or engine.scheduler.waiting:
        engine.step()
        for req in engine.scheduler.live():
            n = int(engine._seq_lens[req.slot])
            assert engine.d_pool.seq_len(req.req_id) \
                == engine.pool.seq_len(req.req_id)
            for pos in range(n):
                t_blk = engine.pool._tables[req.req_id][pos // 4]
                d_blk = engine.d_pool._tables[req.req_id][pos // 4]
                for t_l, d_l in zip(engine.pool.k_pools + engine.pool.v_pools,
                                    engine.d_pool.k_pools
                                    + engine.d_pool.v_pools):
                    np.testing.assert_array_equal(
                        np.asarray(t_l[t_blk, pos % 4]),
                        np.asarray(d_l[d_blk, pos % 4]))
                checked += 1
    assert checked >= 16
    engine.run()
    assert engine.stats["spec_rounds"] > 0
    assert_streams(engine, model, reqs)
    # a bucket's first use built two programs: the target's, the draft's
    built = engine.obs.registry.get("serving_mixed_programs_total")
    assert built.value(bucket="4") == 2


def arm_prefix_cached_offset():
    """A row that starts at a cached offset (bucket 4 for its 3 novel
    tokens), then the one-token re-prefill of a fully cached prompt:
    bucket 1, which copies the shared tail block before it writes."""
    cfg, model = build_model()
    shared, tail = prompts_of(cfg, (8, 3), seed=2)
    engine = ServingEngine(model, prefix_cache=True, **ENGINE_KW)
    reqs = [engine.submit(shared, max_new_tokens=5)]
    engine.run()
    mark = TraceRecorder.process().next_id()
    reqs.append(engine.submit(np.concatenate([shared, tail]),
                              max_new_tokens=5))
    engine.run()
    reqs.append(engine.submit(shared, max_new_tokens=5))
    engine.run()
    assert_streams(engine, model, reqs)
    assert engine.pool.prefix_hits >= 2 and engine.pool.cow_copies >= 1
    rows = [r["args"] for r in mixed_rows(mark)]
    # 3 novel tokens, then the re-prefilled last token: not 11 + 8
    assert [r["prefill_tokens"] for r in rows] == [3, 1]
    assert [r["bucket"] for r in rows] == [4, 1]


def arm_tp2():
    """Head-sharded pools and params over a 2-device mesh: the program
    runs under the engine's MeshScope with its small inputs committed
    replicated, and hands the pools back on their layout."""
    cfg, model = build_model(tensor_parallel=True)
    engine = ServingEngine(model, tp=2, **ENGINE_KW)
    reqs = [engine.submit(p, max_new_tokens=5)
            for p in prompts_of(cfg, (9, 5, 3))]
    engine.run()
    assert_streams(engine, model, reqs)
    assert engine.pool.tp_shards == 2
    spec = engine.pool.k_pools[0].sharding.spec
    assert tuple(spec)[2] == "mp"


def arm_per_request_temperatures():
    """Each row's temperature rides into the program's selection: a
    request samples what a one-request engine built at that temperature
    samples from the same seed."""
    cfg, model = build_model()
    kw = dict(decode_strategy="sampling", top_k=20, top_p=0.9)
    prompts = prompts_of(cfg, (9, 5, 6), seed=4)
    temps, seeds = (0.3, 1.0, 1.7), (11, 12, 13)
    engine = ServingEngine(model, **kw, **ENGINE_KW)
    reqs = [engine.submit(p, max_new_tokens=6, seed=sd, temperature=t)
            for p, t, sd in zip(prompts, temps, seeds)]
    engine.run()
    for req, p, t, sd in zip(reqs, prompts, temps, seeds):
        alone = ServingEngine(model, temperature=t, **kw, **ENGINE_KW)
        want = alone.submit(p, max_new_tokens=6, seed=sd)
        alone.run()
        assert list(req.tokens) == list(want.tokens)
    assert len({tuple(r.tokens) for r in reqs}) == 3


ARMS = [arm_several_chunks, arm_prefill_and_decode_rows, arm_int8_pool,
        arm_speculative_draft, arm_prefix_cached_offset, arm_tp2,
        arm_per_request_temperatures]


@pytest.mark.parametrize("arm", ARMS,
                         ids=[a.__name__[4:] for a in ARMS])
def test_streams_are_the_sequential_reference(arm):
    arm()


# ----------------------------------------- one program a bucket, kept
def _requests():
    return MetricsRegistry.process().get(
        "jax_compile_requests_total").value(step="mixed")


@pytest.mark.parametrize("committed", [False, True],
                         ids=["uncommitted_weights", "committed_weights"])
def test_a_warm_bucket_compiles_nothing(committed):
    """The second step of a bucket adds 0 to
    ``jax_compile_requests_total{step="mixed"}``, whatever the rows
    (other slots, other lengths inside the bucket, a decode row), and
    after decode quanta have handed the pools back too: beside weights
    committed to their device (as a checkpoint loader leaves them) the
    pools are committed from the start, not from the first dispatch
    on."""
    cfg, model = build_model()
    if committed:
        for _, p in model.named_parameters():
            p._value = jax.device_put(p._value, jax.devices()[0])
    # shapes no other test of this process builds
    engine = ServingEngine(model, num_slots=5, block_size=4,
                           prefill_chunk=8, decode_quantum=3)
    assert engine.pool.k_pools[0].committed == committed
    reg = engine.obs.registry
    built = reg.get("serving_mixed_programs_total")
    a, b, c, d, e = prompts_of(cfg, (16, 7, 6, 5, 3), seed=5)
    engine.submit(a, max_new_tokens=4)
    cold = _requests()
    engine.step()                                   # bucket 8: compiles
    assert _requests() > cold and built.value(bucket="8") == 1
    warm = _requests()
    engine.submit(b, max_new_tokens=4)              # 8 + 7 tokens
    engine.step()
    engine.submit(c, max_new_tokens=4)              # 6 tokens + 2 decode
    engine.step()
    engine.run()                                    # quanta in between
    engine.submit(d, max_new_tokens=2)              # 5 tokens
    engine.step()
    assert engine.stats["mixed_steps"] == 4
    assert engine.stats["decode_quanta"] > 0
    assert _requests() == warm
    assert built.value(bucket="8") == 1 and built.value(bucket="4") == 0
    engine.run()
    engine.submit(e, max_new_tokens=2)              # 3 tokens: bucket 4
    engine.step()
    assert _requests() > warm and built.value(bucket="4") == 1
    # 5 slots x bucket positions, less the tokens the rows brought
    assert reg.get("serving_mixed_padded_tokens_total").value() \
        == (40 - 8) + (40 - 15) + (40 - 8) + (40 - 5) + (20 - 3)


@pytest.mark.parametrize("longest,chunk,bucket", [
    (1, 128, 1), (2, 128, 2), (3, 128, 4), (65, 128, 128),
    (128, 128, 128), (9, 11, 11), (5, 11, 8), (11, 11, 11)])
def test_the_bucket_is_a_function_of_the_rows(tiny, longest, chunk, bucket):
    """The smallest power of two that holds the step's longest row, at
    most ``prefill_chunk``."""
    cfg, model = tiny
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=chunk, max_context=160)
    for n in (longest, 1):
        engine.submit(np.ones(n, np.int32), max_new_tokens=2)
    engine._admit()
    args, d_args, got, lens = engine._mixed_args(
        engine.scheduler.prefilling(), [], False)
    assert got == bucket and lens == [longest, 1] and d_args is None
    ids, counts = np.asarray(args[7]), np.asarray(args[9])
    assert ids.shape == (2, bucket) and list(counts) == [longest, 1]


# ------------------------------------------------- donation, faults
def test_the_pools_are_donated(tiny):
    """Through the step the engine dispatches: the buffers that went in
    are gone, and the audited target declares every pool leaf."""
    cfg, model = tiny
    engine = ServingEngine(model, kv_dtype="int8", **ENGINE_KW)
    engine.submit(prompts_of(cfg, (6,))[0], max_new_tokens=3)
    before = (engine.pool.k_pools + engine.pool.v_pools
              + engine.pool.k_scales + engine.pool.v_scales)
    engine.step()
    assert all(b.is_deleted() for b in before)
    after = engine.pool.k_pools + engine.pool.k_scales
    assert not any(a.is_deleted() for a in after)
    step, args = engine.mixed_step_target()
    assert step.n_donatable == 4 * cfg.num_hidden_layers \
        == sum(len(a) for a in args[:4])


def test_serving_mixed_step_budget():
    """The recipe: zero host callbacks (the tokens are picked in the
    program), zero collectives at ``tp=1``, no involuntary remat, every
    KV pool leaf donated (``require_donated`` through ``_AuditedStep``),
    bf16 stays bf16, and its golden fingerprint."""
    from paddle_tpu import analysis

    report = analysis.run_recipe("serving_mixed_step")
    assert len(report.remat_events) == 0
    assert report.host_sync is not None and report.host_sync.count == 0
    assert report.total_collectives == 0
    assert report.donation.undonated() == []
    analysis.check_recipe_fingerprint("serving_mixed_step", report)


def test_a_fault_at_the_boundary_leaves_the_pools_usable(tiny):
    """``before_dispatch("mixed")`` fires before anything is donated:
    the step that raised changed nothing, and the retry serves the
    reference's stream from the same buffers."""
    cfg, model = tiny
    faults = FaultInjector(plan=[FaultSpec("mixed", "raise", times=1)])
    engine = ServingEngine(model, faults=faults, **ENGINE_KW)
    req = engine.submit(prompts_of(cfg, (7,))[0], max_new_tokens=4)
    engine._admit()
    pools = engine.pool.k_pools + engine.pool.v_pools
    with pytest.raises(InjectedFault):
        engine._mixed_step()
    assert req.prefill_pos == 0 and not req.tokens
    assert engine.pool.k_pools + engine.pool.v_pools == pools
    assert not any(p.is_deleted() for p in pools)
    engine.run()                      # the injector is spent: retried
    assert engine.stats["mixed_steps"] == 1 + 2
    assert_streams(engine, model, [req])


# ------------------------------------------------ the chunk attention
def dense_chunk_attention(q, k_ctx, v_ctx, base):
    """(S, C, H, D) queries over per-row contexts (S, K, HK, D): plain
    f32 softmax with K and V repeated over the query heads."""
    s, c, h, d = q.shape
    rep = h // k_ctx.shape[2]
    k = np.repeat(k_ctx, rep, axis=2).astype(np.float64)
    v = np.repeat(v_ctx, rep, axis=2).astype(np.float64)
    logits = np.einsum("bchd,bkhd->bhck", q.astype(np.float64), k) \
        / np.sqrt(d)
    lens = base[:, None] + np.arange(c)[None, :] + 1
    mask = np.arange(k.shape[1])[None, None, :] < lens[:, :, None]
    logits = np.where(mask[:, None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhck,bkhd->bchd", p, v)


@pytest.mark.parametrize("quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("budget", [256 << 20, 1024, 1],
                         ids=["one_tile", "three_tiles", "block_tiles"])
def test_chunk_attention_streams_to_the_same_answer(monkeypatch, budget,
                                                    quant):
    """Grouped heads, never a repeated K or V; whether the block table
    is one tile or streamed in tiles of key blocks (the rule reads
    shapes only: ``_CHUNK_SCORE_BYTES`` against S x H x C x keys x 4),
    the answer is the dense reference's."""
    from paddle_tpu.nn.quant import quantize_kv_rows

    s, c, h, hk, d, bs, w = 2, 4, 4, 2, 8, 4, 5
    rng = np.random.RandomState(7)
    q = rng.randn(s, c, h, d).astype(np.float32)
    pool_k = rng.randn(1 + s * w, bs, hk, d).astype(np.float32)
    pool_v = rng.randn(1 + s * w, bs, hk, d).astype(np.float32)
    tables = 1 + np.arange(s * w, dtype=np.int32).reshape(s, w)
    tables[1, 3:] = 0                 # row 1 holds three blocks only
    base = np.asarray([13, 5], np.int32)
    ks = vs = None
    kp, vp = jnp.asarray(pool_k), jnp.asarray(pool_v)
    if quant:
        kp, ks = quantize_kv_rows(kp)
        vp, vs = quantize_kv_rows(vp)
        pool_k = np.asarray(kp, np.float32) * np.asarray(ks)[..., None]
        pool_v = np.asarray(vp, np.float32) * np.asarray(vs)[..., None]
    from paddle_tpu.nlp import paged_attention

    monkeypatch.setattr(paged_attention, "_CHUNK_SCORE_BYTES", budget)
    got = paged_attention._paged_chunk_attn(
        jnp.asarray(q), kp, vp, jnp.asarray(tables), jnp.asarray(base),
        ks=ks, vs=vs)
    want = dense_chunk_attention(
        q, pool_k[tables].reshape(s, w * bs, hk, d),
        pool_v[tables].reshape(s, w * bs, hk, d), base)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5,
                               atol=2e-5)
    # how many tiles that was: 2 x 4 x 4 x 4 x 4 = 512 bytes a block
    tile = max(1, min(w, budget // 512))
    assert -(-w // tile) == {256 << 20: 1, 1024: 3, 1: 5}[budget]


# the Pallas kernel over a K/V table (interpreted here), by case: (S, C,
# HK, G, block table width, cached tokens a slot, key tile, query tile)
_TABLE_CASES = {
    "g1": (2, 8, 2, 1, 6, (5, 11), 8, None),
    "g4": (2, 8, 2, 4, 6, (5, 11), 8, None),
    "g7": (2, 8, 1, 7, 6, (5, 11), 8, None),
    "g8": (2, 8, 1, 8, 6, (5, 11), 8, None),
    "bases_off_the_tiles": (3, 8, 2, 2, 9, (3, 13, 26), 8, None),
    "a_base_of_zero": (2, 8, 2, 2, 4, (0, 0), 8, None),
    "an_idle_slot": (3, 8, 2, 2, 6, (9, 0, 14), 8, None),
    "a_chunk_off_the_sublanes": (2, 5, 2, 2, 6, (7, 18), 8, None),
    "two_query_tiles": (2, 24, 2, 2, 12, (0, 21), 8, 16),
    "a_table_longer_than_the_context": (2, 8, 2, 2, 16, (2, 9), 16, None),
    "one_key_tile": (2, 8, 2, 2, 5, (3, 11), None, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_TABLE_CASES))
def test_the_chunk_kernel_over_a_table_matches_the_xla_fold(case, dtype):
    """``paged_chunk_attention`` against the fold it replaces on a TPU at
    the same precision, and against the dense float64 softmax: float32
    only reorders sums, bf16 rounds the operands and ``p`` to 2**-8. An
    idle slot's table names block 0 throughout (its queries are nobody's,
    but the fold and the kernel read the same rows for them)."""
    from paddle_tpu.nlp import paged_attention
    from paddle_tpu.ops.pallas.chunk_attention import paged_chunk_attention

    s, c, hk, g, w, base, bk, bq = _TABLE_CASES[case]
    d, bs = 16, 4
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(s, c, hk * g, d), dtype)
    kp = jnp.asarray(rng.randn(1 + s * w, bs, hk, d), dtype)
    vp = jnp.asarray(rng.randn(1 + s * w, bs, hk, d), dtype)
    tables = 1 + rng.permutation(s * w).astype(np.int32).reshape(s, w)
    if case == "an_idle_slot":
        tables[1] = 0
    base = np.asarray(base, np.int32)
    got = paged_chunk_attention(q, kp, vp, jnp.asarray(tables),
                                jnp.asarray(base), 1.0 / np.sqrt(d),
                                block_q=bq, block_k=bk)
    fold = paged_attention._xla_paged_chunk_attn(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(base))
    assert got.shape == fold.shape == q.shape and got.dtype == dtype

    def host(a):
        return np.asarray(jax.device_get(a.astype(jnp.float32)))

    dense = dense_chunk_attention(
        host(q), host(kp[tables]).reshape(s, w * bs, hk, d),
        host(vp[tables]).reshape(s, w * bs, hk, d), base)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    got = host(got)
    assert np.abs(dense).max() > 0.5
    assert np.abs(got - host(fold)).max() < tol
    assert np.abs(got - dense).max() < tol


@pytest.mark.parametrize("route", ["kernel", "xla", "int8"])
def test_a_dense_models_mixed_program_counts_its_chunk_attention_route(
        tiny, route, request, chunk_programs):
    """Tracing one mixed program of the dense model raises
    ``serving_chunk_attention_programs_total`` by ONE on its route's
    label, whatever the model's depth: ``kernel`` where the kernels are
    forced, ``xla`` where they are not, and ``xla`` for an int8 pool's
    per-row scales whatever the flag says."""
    cfg, model = tiny
    if route == "int8":
        model = build_model()[1]      # the sweep rewrites a model in place
    kw = {"kv_dtype": "int8"} if route == "int8" else {}
    engine = ServingEngine(model, **kw, **ENGINE_KW)
    engine.submit(prompts_of(cfg, (7,))[0], max_new_tokens=2)
    engine._admit()
    step, args = engine.mixed_step_target()
    before = chunk_programs()
    if route != "xla":
        request.getfixturevalue("pallas_forced")
    step.lower(*args)
    took = "kernel" if route == "kernel" else "xla"
    after = chunk_programs()
    assert after[took] == before[took] + 1
    other = "xla" if took == "kernel" else "kernel"
    assert after[other] == before[other]
    assert engine.obs.registry.get(
        "serving_chunk_attention_programs_total").value(
            path=took) == after[took]


@pytest.mark.parametrize("arm", ARMS[:-1],
                         ids=[a.__name__[4:] for a in ARMS[:-1]])
def test_streams_are_the_sequential_reference_through_the_kernels(
        arm, pallas_forced):
    """Every arm again with the kernel routes forced (interpreted here):
    the mixed step's chunk attention, the verify pass's (draft + 1
    queries, padded to the sublanes) and, under ``tp=2``, the kernel per
    shard over the head axis serve the sequential reference's streams;
    the int8 pool keeps the fold."""
    arm()


def test_the_head_runs_at_the_last_valid_position_only(tiny):
    """(S, V) logits come out of the chunk body when rows bring their
    counts, never (S, C, V); the verify pass still gets every
    position's."""
    cfg, model = tiny
    engine = ServingEngine(model, **ENGINE_KW)
    engine.submit(prompts_of(cfg, (7,))[0], max_new_tokens=2)
    engine._admit()
    step, args = engine.mixed_step_target()
    out = jax.eval_shape(step._jitted, *args)
    assert out[-1].shape == (3,) and out[-1].dtype == jnp.int32

    def logits_shape(counts):
        def fwd(kc, vc, ids, seq_lens, tables):
            return engine_mod.paged_chunk_math(
                model, 0, paddle.Tensor(ids), seq_lens, tables, kc, vc,
                seq_lens >= 0, counts=counts)[0]
        return jax.eval_shape(fwd, args[0], args[1], args[7], args[8],
                              args[6]).shape

    assert logits_shape(None) == (3, 4, cfg.vocab_size)
    assert logits_shape(jnp.asarray([4, 0, 1])) == (3, cfg.vocab_size)
