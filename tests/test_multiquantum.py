"""Multi-quantum on-device decode driver (ISSUE 17): the K-quanta
``lax.while_loop`` driver must be BIT-EXACT vs the per-quantum engine
across the whole serving matrix — greedy, fixed-seed sampling,
speculative rounds (where K is deliberately ignored: acceptance counts
live on the host), prefix-cache hits with copy-on-write, int8
weights + int8 KV, and mid-run preemption — because between
steady-state quanta the host only round-trips device state through
untouched int32 mirrors, so folding K round-trips on-device changes no
math. The
``Scheduler.steady_state`` predicate that gates K is unit-tested, the
K-token dispatch must account K quanta (token attribution conserved),
the ``serving_host_gap_fraction`` gauge must be live, and the
``serving_multiquantum_step`` recipe budget + golden pin the compiled
driver (zero host callbacks, pools donated)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def _ragged(cfg, rng, n=5, p_lens=(5, 9, 3, 12, 7),
            max_new=(9, 6, 11, 7, 8)):
    prompts = [rng.randint(1, cfg.vocab_size, p).astype(np.int32)
               for p in p_lens[:n]]
    return list(zip(prompts, max_new[:n]))


def _run_streams(engine, requests, seeds=None):
    reqs = [engine.submit(p, max_new_tokens=mn,
                          seed=0 if seeds is None else seeds[i])
            for i, (p, mn) in enumerate(requests)]
    engine.run()
    return [list(map(int, engine.output_tokens(r))) for r in reqs]


# ------------------------------------------------- bit-exactness matrix
def test_multiquantum_greedy_matrix(tiny_model):
    """Greedy ragged requests over 2 slots (retirement + slot reuse
    mid-run): the K=4 streams are bit-exact vs the per-quantum
    engine's."""
    cfg, model = tiny_model
    rng = np.random.RandomState(0)
    requests = _ragged(cfg, rng)
    kw = dict(num_slots=2, block_size=4, prefill_chunk=4,
              decode_quantum=3)
    base = _run_streams(ServingEngine(model, **kw), requests)
    got = _run_streams(ServingEngine(model, multi_quantum=4, **kw),
                       requests)
    assert got == base


def test_multiquantum_sampling_fixed_seed(tiny_model):
    """Fixed-seed per-request sampling: the K=4 driver replays the
    per-quantum sampling engine bit-for-bit (the per-slot PRNG fold-in
    is part of the carried on-device state)."""
    cfg, model = tiny_model
    rng = np.random.RandomState(1)
    requests = _ragged(cfg, rng)
    seeds = [3, 1, 4, 1, 5]
    kw = dict(num_slots=2, block_size=4, prefill_chunk=4,
              decode_quantum=3, decode_strategy="sampling",
              temperature=0.8, top_k=8)
    base = _run_streams(ServingEngine(model, **kw), requests, seeds)
    got = _run_streams(ServingEngine(model, multi_quantum=4, **kw),
                       requests, seeds)
    assert got == base


def test_multiquantum_spec_round_ignores_k(tiny_model):
    """Speculative engines deliberately DON'T build the K-quanta
    driver — acceptance counts must cross the host every round — so
    ``multi_quantum`` is silently inert there and the streams are
    trivially identical to the per-round spec engine."""
    cfg, model = tiny_model
    paddle.seed(11)
    draft = LlamaForCausalLM(
        LlamaConfig.tiny(tensor_parallel=False, num_hidden_layers=1))
    draft.eval()
    rng = np.random.RandomState(2)
    requests = _ragged(cfg, rng, n=3)
    kw = dict(num_slots=2, block_size=4, prefill_chunk=4,
              spec_draft=draft, spec_gamma=3)
    base = _run_streams(ServingEngine(model, **kw), requests)
    mq_eng = ServingEngine(model, multi_quantum=4, **kw)
    assert mq_eng._mq_quantum is None  # never built for spec engines
    assert _run_streams(mq_eng, requests) == base


def test_multiquantum_prefix_hit_cow(tiny_model):
    """Prefix-cache hits + copy-on-write under the K driver: shared
    system prompt across requests (one request is the BARE prompt, so
    its capped re-prefill lands in a shared block and COW fires) —
    streams bit-exact vs the per-quantum prefix engine, with real
    cache hits in both arms."""
    cfg, model = tiny_model
    rng = np.random.RandomState(3)
    sys_prompt = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)
    requests = [
        (np.concatenate([sys_prompt,
                         rng.randint(1, cfg.vocab_size, t)
                         .astype(np.int32)]), mn)
        for t, mn in ((3, 8), (5, 6), (2, 9))
    ] + [(sys_prompt.copy(), 7)]
    kw = dict(num_slots=2, block_size=4, prefill_chunk=4,
              decode_quantum=3, prefix_cache=True)

    def arm(mq):
        eng = ServingEngine(model, multi_quantum=mq, **kw)
        streams = _run_streams(eng, requests)
        stats = eng.pool.prefix_cache_stats()
        assert stats["hits"] > 0, "the hit path must actually run"
        return streams

    assert arm(4) == arm(1)


def test_multiquantum_int8(tiny_model):
    """int8 weights + int8 KV pool under the K driver: streams
    bit-exact vs the per-quantum int8 engine (fresh models per arm —
    quantization sweeps the params in place)."""
    cfg, _ = tiny_model
    rng = np.random.RandomState(4)
    requests = _ragged(cfg, rng, n=4)
    kw = dict(num_slots=2, block_size=4, prefill_chunk=4,
              decode_quantum=3, quantize="weight_only_int8",
              kv_dtype="int8")

    def arm(mq):
        paddle.seed(0)
        model = LlamaForCausalLM(LlamaConfig.tiny(
            tensor_parallel=False))
        return _run_streams(
            ServingEngine(model, multi_quantum=mq, **kw), requests)

    assert arm(4) == arm(1)


def test_multiquantum_preemption(tiny_model):
    """Mid-run preemption: evict a request while the K=4 engine is
    decoding, resume via re-prefill — the stream must still be
    bit-exact vs the per-quantum engine given the same eviction (a
    preempted slot forces admission churn, so the driver must fall
    back to K=1 until steady state returns)."""
    cfg, model = tiny_model
    rng = np.random.RandomState(5)
    requests = _ragged(cfg, rng, n=4, p_lens=(5, 9, 3, 12),
                       max_new=(16, 12, 14, 10))
    kw = dict(num_slots=2, block_size=4, prefill_chunk=4,
              decode_quantum=3)

    def arm(mq):
        eng = ServingEngine(model, multi_quantum=mq, **kw)
        reqs = [eng.submit(p, max_new_tokens=mn)
                for p, mn in requests]
        while len(reqs[0].tokens) < 2:
            eng.step()
        assert not reqs[0].finished
        eng.preempt(reqs[0])
        eng.run()
        return [list(map(int, eng.output_tokens(r))) for r in reqs]

    assert arm(4) == arm(1)


# ------------------------------------------- scheduling + accounting
def test_steady_state_predicate(tiny_model):
    """``Scheduler.steady_state()`` — the K gate — is True exactly
    when the batch composition cannot change before the next dispatch:
    no waiting requests, no slot mid-prefill, at least one decoding."""
    cfg, model = tiny_model
    eng = ServingEngine(model, num_slots=2, block_size=4,
                        prefill_chunk=4, decode_quantum=3)
    sched = eng.scheduler
    assert not sched.steady_state()  # idle: nothing decoding
    rng = np.random.RandomState(6)
    r0 = eng.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                    max_new_tokens=12)
    assert not sched.steady_state()  # waiting for admission
    while sched.waiting or sched.prefilling():
        eng.step()
    assert sched.steady_state()      # one slot, pure decode
    eng.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
               max_new_tokens=4)
    assert not sched.steady_state()  # admission pending again
    eng.run()
    assert not sched.steady_state()  # drained
    assert r0.finished


def test_multiquantum_accounting_conserved(tiny_model):
    """A K-token dispatch is accounted as K quanta: with K=4 live the
    engine retires more decode quanta than it takes host steps, and
    token attribution stays conserved — every emitted token lands in
    the registry exactly once (the obs/attribution seams see K
    sub-quanta, not one fat quantum)."""
    cfg, model = tiny_model
    rng = np.random.RandomState(7)
    eng = ServingEngine(model, num_slots=2, block_size=4,
                        prefill_chunk=4, decode_quantum=3,
                        multi_quantum=4)
    reqs = [eng.submit(rng.randint(1, cfg.vocab_size, 5)
                       .astype(np.int32), max_new_tokens=24)
            for _ in range(2)]
    steps = 0
    while eng.has_work:
        eng.step()
        steps += 1
    assert eng.stats["decode_quanta"] > steps, \
        "K>1 folding never engaged"
    emitted = sum(len(r.tokens) for r in reqs)
    assert int(eng.obs.registry.get(
        "serving_tokens_emitted_total").value()) == emitted


def test_host_gap_gauge_live(tiny_model):
    """The decode collect half feeds the dispatch-boundary host-gap
    gauge: after a run the fraction is a sane [0, 1) value on the
    /metrics surface."""
    cfg, model = tiny_model
    rng = np.random.RandomState(8)
    eng = ServingEngine(model, num_slots=2, block_size=4,
                        prefill_chunk=4, decode_quantum=3,
                        multi_quantum=4)
    eng.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
               max_new_tokens=12)
    eng.run()
    g = eng.obs.registry.get("serving_host_gap_fraction")
    assert 0.0 <= g.value() < 1.0
    text = eng.obs.registry.prometheus()
    assert "serving_host_gap_fraction" in text


# -------------------------------------------------- recipe budget gate
def test_serving_multiquantum_step_budget():
    """ISSUE 17 acceptance: the EXACT K=4 while-loop driver the
    multi-quantum engine dispatches has zero
    host callbacks, zero involuntary remat, no collectives, every KV
    pool leaf donated — and its golden fingerprint matches, while the
    K=1 engines' goldens stay untouched (their tests compare against
    the same checked-in files as before)."""
    from paddle_tpu import analysis

    report = analysis.run_recipe("serving_multiquantum_step")
    assert len(report.remat_events) == 0
    assert report.host_sync is not None and report.host_sync.count == 0
    assert report.total_collectives == 0
    assert report.donation.undonated() == []
    assert report.memory.temp_bytes is not None
    analysis.check_recipe_fingerprint("serving_multiquantum_step",
                                      report)


def test_multiquantum_rejects_bad_args(tiny_model):
    cfg, model = tiny_model
    with pytest.raises(ValueError):
        ServingEngine(model, multi_quantum=0)
    eng = ServingEngine(model, num_slots=2, block_size=4)
    with pytest.raises(ValueError):
        eng.multiquantum_step_target()  # K=1 engine has no mq program
