"""Continuous-batching serving engine (reference: the serving loop
around AnalysisPredictor / ``Predictor.run``'s fused_multi_transformer
decode HOT LOOP — SURVEY.md §2.6/§3.5): the greedy arm is oracle-tested
BIT-EXACT against per-request sequential ``generate_on_device`` under
ragged arrivals with slot reuse, plus pool-allocator lifecycle
(free-list reuse after retirement, exhaustion refusal, fragmentation
counters), scheduler admission gating, and the registered
``serving_decode_step`` analysis budget (zero involuntary remat, zero
host syncs in the jitted quantum, KV pool leaves donated).

The SPECULATIVE serving arm (ISSUE 3) gets the same treatment: the
greedy drafter/verifier round is bit-exact vs sequential generate with
an arbitrary independent draft (exactness by construction), the
rejection-sampling arm replays the plain sampling engine bit-for-bit
when draft == target on fixed seeds, eos/max-new retirement composes
with variable per-round yield, admission accounts for the draft pool,
and the ``speculative_verify_step`` budget pins the one-dispatch
round.

The FRONT DOOR's engine tier (ISSUE 7): the preemption correctness
oracle — a preempted-then-resumed request's stream is BIT-EXACT vs an
undisturbed run in both the greedy and fixed-seed sampling arms, with
TTFT observed exactly once despite the re-prefill — plus per-request
temperature threading (a request that names the engine-wide temperature
replays one that names none bit-for-bit), host-side stop rules,
refcount-safe pool release (shared blocks survive one holder's
eviction), a 100-round ragged preempt/resume leak hunt at the
scheduler level, priority admission ordering, and the
``serving_frontdoor_step`` budget + golden pinning the sampling
quantum with its per-slot temperature input."""
import numpy as np
import pytest
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp import PagedKVCachePool
from paddle_tpu.nlp.generation import (
    generate_on_device, speculative_generate,
)
from paddle_tpu.serving import Request, Scheduler, SchedulerConfig
from paddle_tpu.serving import ServingEngine


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


@pytest.fixture(scope="module")
def tiny_draft():
    """An INDEPENDENT (random-init, shallower) draft: near-floor
    acceptance, which is exactly the adversarial case for greedy
    exactness-by-construction."""
    paddle.seed(11)
    draft = LlamaForCausalLM(
        LlamaConfig.tiny(tensor_parallel=False, num_hidden_layers=1))
    draft.eval()
    return draft


def _oracle_row(model, prompt, max_new, eos_token_id=None):
    """Sequential single-request reference; returns the generated ids
    TRUNCATED at eos (generate_on_device pads the tail with eos, the
    engine retires the slot instead)."""
    out = generate_on_device(model, paddle.to_tensor(prompt[None, :]),
                             max_new_tokens=max_new,
                             eos_token_id=eos_token_id)
    row = np.asarray(out._value)[0]
    gen = row[prompt.shape[0]:]
    if eos_token_id is not None:
        hits = np.nonzero(gen == eos_token_id)[0]
        if hits.size:
            gen = gen[:hits[0] + 1]
    return np.concatenate([prompt, gen])


# ------------------------------------------------ engine vs sequential
def test_engine_greedy_oracle_ragged(tiny_model):
    """The correctness oracle: 5 ragged requests over 3 slots (so
    retirement + slot/block reuse happens mid-run), chunked prefill
    interleaved with decode — outputs bit-exact vs per-request
    sequential generate. The same run carries the ISSUE 7 preemption
    oracle (request 0 is evicted mid-decode and resumes by re-prefill
    of prompt+tokens: its stream must STILL be bit-exact, with TTFT
    observed exactly once despite the re-prefill) and the host-side
    stop-token rule (request 4 stops at a token its own oracle row
    predicts — truncate-at-stop, finish_reason "stop")."""
    cfg, model = tiny_model
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 3, 12, 7)]
    max_new = [6, 4, 8, 5, 7]
    wants = [_oracle_row(model, p, mn)
             for p, mn in zip(prompts, max_new)]
    # request 4 additionally carries a stop rule on its 3rd generated
    # token; its expected output is the oracle row truncated there
    stop_tok = int(wants[4][prompts[4].shape[0] + 2])
    wants[4] = wants[4][:prompts[4].shape[0] + 3]
    engine = ServingEngine(model, num_slots=3, block_size=4,
                           prefill_chunk=4, decode_quantum=3)
    reqs = [engine.submit(p, max_new_tokens=mn,
                          stop_token_ids=[stop_tok] if i == 4 else None)
            for i, (p, mn) in enumerate(zip(prompts, max_new))]
    # evict request 0 mid-decode: blocks back to the pool, requeued at
    # the head of its class, resumed via re-prefill
    while len(reqs[0].tokens) < 2:
        engine.step()
    assert not reqs[0].finished
    engine.preempt(reqs[0])
    assert reqs[0].slot is None and reqs[0].prefill_pos == 0
    assert reqs[0].prefill_target == prompts[0].shape[0] + len(
        reqs[0].tokens)
    done = engine.run()
    assert len(done) == len(reqs)
    assert engine.scheduler.finished_total == len(reqs)
    for req, want in zip(reqs, wants):
        np.testing.assert_array_equal(engine.output_tokens(req), want)
    assert reqs[4].finish_reason == "stop"
    # TTFT observed exactly once per request despite req0's re-prefill
    assert engine.obs.registry.get(
        "serving_ttft_seconds").count() == len(reqs)
    st = engine.engine_stats()
    assert st["preempted"] == 1 and st["resumed"] == 1
    assert engine.obs.registry.get(
        "serving_tokens_recomputed_total").value() >= 2
    # every request retired -> all its blocks are back on the free list
    stats = engine.pool.fragmentation_stats()
    assert stats["blocks_in_use"] == 1  # only the engine scratch block
    assert stats["blocks_freed_total"] > 0
    assert engine.engine_stats()["decode_quanta"] > 0


def test_engine_eos_retirement(tiny_model):
    """Device-computed eos masks retire slots mid-quantum; outputs stay
    bit-exact (truncated-at-eos convention) and blocks free."""
    cfg, model = tiny_model
    rng = np.random.RandomState(1)
    probe = rng.randint(1, cfg.vocab_size, 6).astype(np.int32)
    row = _oracle_row(model, probe, 10)
    eos = int(row[6 + 3])  # the 4th greedy token becomes "eos"
    prompts = [probe,
               rng.randint(1, cfg.vocab_size, 4).astype(np.int32),
               rng.randint(1, cfg.vocab_size, 8).astype(np.int32)]
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=3, decode_quantum=4,
                           eos_token_id=eos)
    reqs = [engine.submit(p, max_new_tokens=10) for p in prompts]
    engine.run()
    assert reqs[0].finish_reason == "eos"
    for req, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            engine.output_tokens(req),
            _oracle_row(model, p, 10, eos_token_id=eos))
    assert engine.pool.fragmentation_stats()["blocks_in_use"] == 1


def test_engine_sampling_smoke(tiny_model, sampling_prompts,
                               plain_sampling_outputs):
    """The sampling arm drives to completion with per-request seeds and
    in-vocab tokens (selection math shared with generation's
    _filter_logits; distributional parity is its own test tier). The
    run itself is the module-shared plain_sampling_outputs fixture —
    the same run is the speculative parity test's oracle."""
    cfg, _ = tiny_model
    assert len(plain_sampling_outputs) == 3
    for out, p in zip(plain_sampling_outputs, sampling_prompts):
        gen = out[p.shape[0]:]
        assert gen.shape[0] == 5
        assert all(0 <= t < cfg.vocab_size for t in gen)


def test_engine_rejects_oversize_and_bad_strategy(tiny_model):
    cfg, model = tiny_model
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           max_context=32)
    with pytest.raises(ValueError, match="max_context"):
        engine.submit(np.arange(1, 30, dtype=np.int32),
                      max_new_tokens=8)
    with pytest.raises(ValueError, match="greedy|sampling"):
        ServingEngine(model, decode_strategy="beam")


# ------------------------------------------------ preemption oracle
def test_preemption_and_temperature_sampling_bit_exact(
        tiny_model, sampling_prompts, plain_sampling_outputs):
    """ISSUE 7 oracle, fixed-seed sampling arm — one sampling engine
    proves two bit-exactness claims against the module-shared plain
    sampling run at once: (a) per-request TEMPERATURE threads through
    the per-slot temps input of the quantum (every request names the
    temperature the fixture's requests took from the engine — it must
    replay them bit-for-bit), and (b) the fold_in(key, n_emitted) token-stream
    discipline survives EVICTION — a preempted request re-prefills and
    continues the SAME sample stream, with TTFT observed once."""
    cfg, model = tiny_model
    engine = ServingEngine(model, decode_quantum=3, **_SAMPLING_KW)
    reqs = [engine.submit(p, max_new_tokens=5, seed=i,
                          temperature=_SAMPLING_KW["temperature"])
            for i, p in enumerate(sampling_prompts)]
    while len(reqs[0].tokens) < 2:
        engine.step()
    assert not reqs[0].finished
    engine.preempt(reqs[0])
    engine.run()
    for req, want in zip(reqs, plain_sampling_outputs):
        np.testing.assert_array_equal(engine.output_tokens(req), want)
    assert engine.scheduler.preempted_total == 1
    assert engine.scheduler.resumed_total == 1
    assert engine.obs.registry.get("serving_ttft_seconds").count() == 3


@pytest.mark.parametrize("kind", ["sampling", "greedy", "spec_draft"])
def test_request_temperature(tiny_model, sampling_prompts,
                             plain_sampling_outputs, kind):
    """``submit(temperature=)`` with no flag at build: a sampling engine
    takes it and it changes the stream; a greedy engine and a
    speculative one refuse it by name (their programs have no per-slot
    temperature input)."""
    cfg, model = tiny_model
    prompt = np.arange(1, 5, dtype=np.int32)
    if kind == "greedy":
        engine = ServingEngine(model, num_slots=2, block_size=4)
        with pytest.raises(ValueError, match="decode_strategy='sampling'"):
            engine.submit(prompt, temperature=0.7)
        return
    if kind == "spec_draft":
        engine = ServingEngine(model, spec_draft=model, spec_gamma=2,
                               **_SAMPLING_KW)
        with pytest.raises(NotImplementedError, match="spec_draft"):
            engine.submit(prompt, temperature=0.7)
        assert engine.submit(prompt, max_new_tokens=2).temperature is None
        return
    # the fixture's engine, with one request at a far higher temperature:
    # its stream leaves the fixture's, its neighbours' do not
    engine = ServingEngine(model, decode_quantum=3, **_SAMPLING_KW)
    hot = 0
    reqs = [engine.submit(p, max_new_tokens=5, seed=i,
                          temperature=50.0 if i == hot else None)
            for i, p in enumerate(sampling_prompts)]
    engine.run()
    for i, (req, want) in enumerate(zip(reqs, plain_sampling_outputs)):
        same = np.array_equal(engine.output_tokens(req), want)
        assert same == (i != hot), (i, engine.output_tokens(req), want)


def test_restore_ignores_retired_snapshot_keys(tiny_model):
    """A snapshot written before ``per_request_sampling`` was retired
    carries the key; ``restore()`` does not read it, and the restored
    sampling engine still takes a per-request temperature."""
    cfg, model = tiny_model
    engine = ServingEngine(model, **_SAMPLING_KW)
    engine.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4,
                  seed=3, temperature=0.5)
    snap = engine.snapshot()
    assert "per_request_sampling" not in snap
    old = dict(snap, per_request_sampling=True)
    restored = ServingEngine.restore(old, model)
    assert restored.decode_strategy == "sampling"
    (req,) = restored.scheduler.waiting
    assert req.temperature == 0.5
    restored.run()
    assert len(restored.completed) == 1
    assert len(restored.completed[0].tokens) == 4


def test_per_request_param_validation(tiny_model):
    """Stop rules are pure host checks."""
    # stop-sequence rule, host-side (no engine run needed)
    req = Request(np.arange(1, 5), max_new_tokens=10,
                  stop_sequences=[[7, 8]])
    for t in (5, 7, 8):
        req.record(t)
    assert req.finished and req.finish_reason == "stop"
    assert req.tokens == [5, 7, 8]


# ------------------------------------------------ speculative arm
def test_spec_engine_greedy_oracle_ragged_eos(tiny_model, tiny_draft):
    """ISSUE 3 acceptance: the greedy speculative round is EXACT BY
    CONSTRUCTION — an arbitrary independent (near-floor-acceptance)
    draft leaves the served outputs bit-identical to target-only
    sequential generate, under ragged arrivals over fewer slots
    (retirement + slot/block reuse mid-run) with device-computed eos
    truncating the round's variable yield in-graph. Prompt shapes
    match the plain-engine eos test so the sequential oracle compiles
    are cache hits."""
    cfg, model = tiny_model
    rng = np.random.RandomState(1)
    probe = rng.randint(1, cfg.vocab_size, 6).astype(np.int32)
    row = _oracle_row(model, probe, 10)
    eos = int(row[6 + 3])  # the 4th greedy token becomes "eos"
    prompts = [probe,
               rng.randint(1, cfg.vocab_size, 4).astype(np.int32),
               rng.randint(1, cfg.vocab_size, 8).astype(np.int32)]
    engine = ServingEngine(model, spec_draft=tiny_draft, spec_gamma=2,
                           num_slots=2, block_size=4, prefill_chunk=3,
                           eos_token_id=eos)
    reqs = [engine.submit(p, max_new_tokens=10) for p in prompts]
    done = engine.run()
    assert len(done) == len(reqs)
    assert reqs[0].finish_reason == "eos"
    for req, p in zip(reqs, prompts):
        np.testing.assert_array_equal(
            engine.output_tokens(req),
            _oracle_row(model, p, 10, eos_token_id=eos))
    st = engine.engine_stats()
    assert st["spec_rounds"] > 0
    assert st["spec_proposed"] >= st["spec_accepted"] >= 0
    # retirement drains BOTH pools back to their scratch block
    assert engine.pool.fragmentation_stats()["blocks_in_use"] == 1
    assert engine.d_pool.fragmentation_stats()["blocks_in_use"] == 1


_SAMPLING_KW = dict(num_slots=2, block_size=4, prefill_chunk=4,
                    decode_strategy="sampling", top_k=8,
                    temperature=0.9)


@pytest.fixture(scope="module")
def sampling_prompts(tiny_model):
    cfg, _ = tiny_model
    rng = np.random.RandomState(2)
    return [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in (5, 7, 3)]


@pytest.fixture(scope="module")
def plain_sampling_outputs(tiny_model, sampling_prompts):
    """One PLAIN sampling-engine run (max_new 5, per-request seed i)
    shared by the smoke test and the speculative parity oracle — one
    compile, one execution."""
    _, model = tiny_model
    engine = ServingEngine(model, decode_quantum=3, **_SAMPLING_KW)
    reqs = [engine.submit(p, max_new_tokens=5, seed=i)
            for i, p in enumerate(sampling_prompts)]
    engine.run()
    assert len(engine.completed) == len(reqs)
    return [engine.output_tokens(r) for r in reqs]


def test_spec_engine_sampling_parity_fixed_seeds(tiny_model,
                                                 sampling_prompts,
                                                 plain_sampling_outputs):
    """Rejection-sampling arm with draft == target: q == p, so every
    proposal accepts, and the fold_in(key, n_emitted) token-stream
    discipline makes the speculative engine replay the PLAIN sampling
    engine's output bit-for-bit on fixed seeds — the deterministic
    oracle the sampling arm has (the greedy arm's is sequential
    generate)."""
    cfg, model = tiny_model
    spec = ServingEngine(model, spec_draft=model, spec_gamma=2,
                         **_SAMPLING_KW)
    reqs = [spec.submit(p, max_new_tokens=5, seed=i)
            for i, p in enumerate(sampling_prompts)]
    spec.run()
    for req, want in zip(reqs, plain_sampling_outputs):
        np.testing.assert_array_equal(spec.output_tokens(req), want)
    st = spec.engine_stats()
    assert st["spec_proposed"] > 0
    assert st["spec_accepted"] == st["spec_proposed"]  # q == p


@pytest.mark.slow
def test_speculative_generate_facade(tiny_model, tiny_draft):
    """nlp.generation.speculative_generate: batch rows ride serving
    slots; greedy output equals target-only generate row-for-row."""
    cfg, model = tiny_model
    rng = np.random.RandomState(0)
    prompts = np.stack([rng.randint(1, cfg.vocab_size, 5)
                        .astype(np.int32) for _ in range(2)])
    out, rate = speculative_generate(model, tiny_draft, prompts,
                                     max_new_tokens=6, gamma=3)
    out = np.asarray(out._value)
    for i in range(2):
        np.testing.assert_array_equal(out[i],
                                      _oracle_row(model, prompts[i], 6))
    assert 0.0 <= rate <= 1.0


def test_spec_engine_rejects_bad_draft(tiny_model, tiny_draft):
    cfg, model = tiny_model
    with pytest.raises(ValueError, match="vocab"):
        ServingEngine(model, spec_draft=LlamaForCausalLM(
            LlamaConfig.tiny(tensor_parallel=False, vocab_size=64)))
    with pytest.raises(ValueError, match="spec_gamma"):
        ServingEngine(model, spec_draft=tiny_draft, spec_gamma=0)


# ------------------------------------------------ pool lifecycle
def _pool(num_blocks=8, bs=4):
    return PagedKVCachePool(num_blocks=num_blocks, block_size=bs,
                            num_kv_heads=2, head_dim=8,
                            dtype=jnp.float32)


def test_pool_free_list_reuse_after_retirement():
    """A retiring sequence's blocks go straight to the next admission
    (LIFO free list — immediate reuse, no compaction pass)."""
    pool = _pool()
    t_a = list(pool.ensure("a", 9))   # 3 blocks
    pool.ensure("b", 4)               # 1 block
    assert pool.blocks_in_use == 4
    pool.free("a")
    assert pool.free_blocks == 7
    assert pool.seq_len("a") == 0
    t_c = list(pool.ensure("c", 12))  # 3 blocks: exactly a's, reused
    assert set(t_c) == set(t_a)
    assert pool.fragmentation_stats()["blocks_freed_total"] == 3


def test_pool_exhaustion_refusal():
    pool = _pool(num_blocks=4)
    pool.ensure("a", 12)  # 3 blocks
    assert not pool.can_allocate(8)
    assert pool.can_allocate(4)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.ensure("b", 8)
    pool.free("a")
    assert pool.can_allocate(8)
    pool.ensure("b", 8)  # now fits


def test_pool_fragmentation_counters():
    """Only INTERNAL fragmentation exists (tail waste in each last
    block); utilization is live tokens over allocated capacity."""
    pool = _pool(bs=4)
    pool.ensure("a", 5)  # 2 blocks, 3 tail-waste tokens
    pool.ensure("b", 4)  # 1 block, 0 waste
    s = pool.fragmentation_stats()
    assert s["blocks_in_use"] == 3
    assert s["live_tokens"] == 9
    assert s["tail_waste_tokens"] == 3
    assert s["utilization"] == pytest.approx(9 / 12)
    assert s["peak_blocks_in_use"] == 3
    pool.free("a")
    s2 = pool.fragmentation_stats()
    assert s2["peak_blocks_in_use"] == 3  # high-water mark sticks
    assert s2["utilization"] == pytest.approx(1.0)


def test_pool_trim_releases_tail_blocks():
    """trim() is the rollback/realloc path: shrink a live sequence,
    tail blocks return to the free list, table order preserved."""
    pool = _pool(bs=4)
    table = list(pool.ensure("a", 15))  # 4 blocks
    released = pool.trim("a", 6)        # keep 2 blocks
    assert released == table[2:]
    assert pool.seq_len("a") == 6
    assert pool.free_blocks == 6
    assert pool.trim("a", 100) == []    # growing is ensure()'s job
    assert pool.seq_len("a") == 6
    assert pool.trim("missing", 3) == []


def test_pool_refcount_share_release():
    """Refcount-safe release (the eviction/prefix-sharing primitive):
    a block shared by two holders survives the first free and only
    returns to the free list — and counts as freed — when the LAST
    holder releases it; double-release of an untracked block raises."""
    pool = _pool(num_blocks=8, bs=4)
    t_a = list(pool.ensure("a", 8))       # 2 blocks
    t_b = pool.share("a", "b")            # aliases, refcount 2 each
    assert t_b == t_a
    assert pool.blocks_in_use == 2
    pool.free("a")
    # b still holds the blocks: nothing returned to the free list
    assert pool.blocks_in_use == 2
    assert pool.fragmentation_stats()["blocks_freed_total"] == 0
    pool.free("b")
    assert pool.blocks_in_use == 0
    assert pool.fragmentation_stats()["blocks_freed_total"] == 2
    pool.ensure("c", 4)
    with pytest.raises(ValueError, match="already exists"):
        pool.share("a", "c")
    with pytest.raises(KeyError):
        pool.share("missing", "d")
    with pytest.raises(RuntimeError, match="double free"):
        pool._release([t_a[0]])
    # trim decrements too: a shared tail block is not freed early
    pool2 = _pool(num_blocks=8, bs=4)
    pool2.ensure("x", 8)
    pool2.share("x", "y")
    pool2.trim("x", 4)                    # x drops its tail block
    assert pool2.blocks_in_use == 2       # y still maps it
    pool2.free("y")
    assert pool2.blocks_in_use == 1       # x's head block remains


def test_preemption_no_block_leak_100_ragged_rounds():
    """ISSUE 7 acceptance: 100 rounds of ragged admit / partial-ensure
    / preempt / resume / retire churn at the scheduler+pool level —
    blocks_in_use must return to zero every round and the free list
    must be whole at the end (an off-by-one in eviction release would
    leak monotonically and fail fast here)."""
    rng = np.random.RandomState(0)
    pool = _pool(num_blocks=24, bs=4)
    sched = Scheduler(SchedulerConfig(num_slots=4), pool)
    for round_i in range(100):
        reqs = [Request(np.arange(1, 1 + rng.randint(2, 12)),
                        max_new_tokens=int(rng.randint(1, 12)),
                        priority=int(rng.randint(0, 3)))
                for _ in range(rng.randint(1, 6))]
        for r in reqs:
            sched.submit(r)
        live = sched.try_admit()
        # simulate partial prefill/decode pool growth per live request
        for r in live:
            grown = min(r.prompt_len + rng.randint(0, r.max_new_tokens
                                                   + 1),
                        r.prompt_len + r.max_new_tokens)
            pool.ensure(r.req_id, grown)
        # preempt a random subset, resume them, then retire everything
        for r in list(live):
            if rng.rand() < 0.5:
                sched.preempt(r)
        sched.try_admit()  # resumed + any still-waiting requests
        for r in [x for x in sched.slots if x is not None]:
            pool.ensure(r.req_id, r.prompt_len + r.max_new_tokens)
            r.finished = True
            sched.retire(r)
        # anything left waiting (slots exhausted) drains next round;
        # flush it now so every round starts clean
        while sched.waiting:
            for r in sched.try_admit():
                r.finished = True
                sched.retire(r)
        assert pool.blocks_in_use == 0, f"leak at round {round_i}"
        assert sched.reserved_blocks == 0
    assert pool.free_blocks == pool.num_blocks
    assert sched.preempted_total > 0 and sched.resumed_total > 0


def test_scheduler_priority_admission_and_preempt_requeue():
    """Priority-then-FIFO admission: the highest class admits first
    (stable within a class), a preempted request re-enters at the head
    of its class, and ``can_admit`` reports slot/block pressure the
    preemption policy keys on."""
    pool = _pool(num_blocks=12, bs=4)
    sched = Scheduler(SchedulerConfig(num_slots=2), pool)
    lo = sched.submit(Request(np.arange(1, 5), max_new_tokens=4,
                              priority=0))
    mid = sched.submit(Request(np.arange(1, 5), max_new_tokens=4,
                               priority=1))
    hi = sched.submit(Request(np.arange(1, 5), max_new_tokens=4,
                              priority=2))
    assert sched.next_waiting() is hi
    assert sched.try_admit() == [hi, mid]     # strict priority order
    assert lo.slot is None
    assert not sched.can_admit(lo)            # both slots taken
    sched.preempt(mid)
    assert sched.preempted_total == 1
    assert mid.prefill_target == mid.prompt_len  # no tokens yet
    # mid (priority 1) outranks lo in the queue again; lo keeps
    # waiting for a slot
    assert sched.next_waiting() is mid
    assert sched.can_admit(mid)
    assert sched.try_admit() == [mid]
    assert sched.resumed_total == 1
    hi.finished = True
    sched.retire(hi)
    assert sched.try_admit() == [lo]
    assert sched.admitted_total == 3          # resume is not a new admit


# ------------------------------------------------ scheduler accounting
def test_scheduler_admission_gating():
    """Admission is gated on WORST-CASE demand (prompt + max_new) so the
    pool can never exhaust mid-decode; FIFO order holds, and a request
    that can never fit raises instead of wedging the queue."""
    pool = _pool(num_blocks=6, bs=4)
    sched = Scheduler(SchedulerConfig(num_slots=4), pool)
    a = sched.submit(Request(np.arange(1, 9), max_new_tokens=8))   # 4 blk
    b = sched.submit(Request(np.arange(1, 5), max_new_tokens=4))   # 2 blk
    c = sched.submit(Request(np.arange(1, 5), max_new_tokens=4))   # 2 blk
    admitted = sched.try_admit()
    assert admitted == [a, b]          # c: 4+2+2 > 6 blocks
    assert sched.reserved_blocks == 6
    assert c.slot is None
    # retiring a releases its reservation; c admits into the freed slot
    a.finished = True
    sched.retire(a)
    assert sched.try_admit() == [c]
    with pytest.raises(ValueError, match="blocks"):
        sched.submit(Request(np.arange(1, 20), max_new_tokens=20))
        sched.try_admit()


def test_scheduler_companion_pool_and_margin():
    """Speculative admission accounts for the DRAFT pool too: capacity
    gates on the tightest pool, demand carries the γ token margin (the
    verify step's worst-case writes), and retirement frees blocks in
    every pool."""
    pool = _pool(num_blocks=8, bs=4)
    d_pool = _pool(num_blocks=4, bs=4)  # the tighter pool gates
    sched = Scheduler(SchedulerConfig(num_slots=4), pool,
                      companion_pools=[d_pool], token_margin=3)
    a = sched.submit(Request(np.arange(1, 6), max_new_tokens=8))
    # demand = ceil((5 + 8 + 3) / 4) = 4 blocks — fills d_pool exactly
    assert sched.try_admit() == [a]
    assert sched.reserved_blocks == 4
    b = sched.submit(Request(np.arange(1, 3), max_new_tokens=2))
    assert sched.try_admit() == []      # draft-pool capacity exhausted
    pool.ensure(a.req_id, 5)
    d_pool.ensure(a.req_id, 5)
    a.finished = True
    sched.retire(a)                      # frees BOTH pools
    assert pool.blocks_in_use == 0 and d_pool.blocks_in_use == 0
    assert sched.try_admit() == [b]
    with pytest.raises(ValueError, match="block_size"):
        Scheduler(SchedulerConfig(), pool,
                  companion_pools=[_pool(bs=8)])


# ------------------------------------------------ the analysis budget
def test_serving_decode_step_budget():
    """The machine-checked single-dispatch invariant (ISSUE 2
    acceptance): the EXACT quantum the engine dispatches has zero
    involuntary remat, zero host callbacks/transfers, no collectives,
    bf16 stays bf16, every KV pool leaf is donated, and temp/peak-live
    memory stays inside the budget — then the full fingerprint must
    match the checked-in golden (the ISSUE 4 drift gate; same audited
    report, no extra compile)."""
    from paddle_tpu import analysis

    report = analysis.run_recipe("serving_decode_step")
    assert len(report.remat_events) == 0
    assert report.host_sync is not None and report.host_sync.count == 0
    assert report.total_collectives == 0
    assert report.donation.undonated() == []
    assert report.memory.temp_bytes is not None
    analysis.check_recipe_fingerprint("serving_decode_step", report)


def test_serving_frontdoor_step_budget():
    """ISSUE 7 acceptance: the front-door quantum variant (per-slot
    temperature input, sampling selection in-graph), built through an
    engine that just served a priority preemption + resume with the
    FULL policy/obs tier attached, still has zero host callbacks, zero
    involuntary remat, no collectives, every KV pool leaf donated —
    and its own golden fingerprint matches, while the plain engines'
    goldens are untouched (their tests above compare against the same
    checked-in files as before). The whole policy layer provably never
    enters the compiled program."""
    from paddle_tpu import analysis

    recipe = analysis.build_recipe("serving_frontdoor_step")
    try:
        report = recipe.check()
        # the audited engine really went through the front door's
        # overload path before the audit
        assert recipe.engine.scheduler.preempted_total == 1
        assert recipe.engine.scheduler.resumed_total == 1
        assert len(report.remat_events) == 0
        assert report.host_sync is not None \
            and report.host_sync.count == 0
        assert report.total_collectives == 0
        assert report.donation.undonated() == []
        analysis.check_recipe_fingerprint("serving_frontdoor_step",
                                          report)
    finally:
        recipe.close()


def test_speculative_verify_step_budget():
    """ISSUE 3 acceptance: the EXACT speculative round the engine
    dispatches — draft-γ scan + target verify + in-graph acceptance —
    has zero involuntary remat, zero host callbacks/transfers, no
    collectives, bf16 stays bf16, and BOTH pools' KV leaves (2L_target
    + 2L_draft) are donated."""
    from paddle_tpu import analysis

    report = analysis.run_recipe("speculative_verify_step")
    assert len(report.remat_events) == 0
    assert report.host_sync is not None and report.host_sync.count == 0
    assert report.total_collectives == 0
    assert report.donation.undonated() == []
    assert report.donation.n_donatable == 6  # 2*2 target + 2*1 draft
    # the liveness walk must see the donation actually saving HBM:
    # both pools roll in-place rather than double-buffering
    assert report.memory.liveness.donation_savings_bytes > 0
    analysis.check_recipe_fingerprint("speculative_verify_step", report)
