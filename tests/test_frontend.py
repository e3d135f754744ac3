"""The serving front door (ISSUE 7: the system tier around the
continuous-batching engine — reference: the deployed serving story
around AnalysisPredictor / ``Predictor.run``, PAPER.md §2.6/§3.5):

- policy units: the shedding ladder (ok/warn/critical x priority
  class), queue backpressure, and preemption victim selection — pure
  host logic, no engine.
- the streaming API: sync pull and ``async for`` under ``run_async``,
  per-token delivery matching the request stream exactly, shed streams
  arriving already closed.
- SLO-burn-rate shedding against a forced-critical health report,
  flight-journal capture for shed requests, and the obs overload
  counters.
- the graceful-drain contract: stop admitting (submissions shed with
  reason ``draining``), finish everything accepted, flush the flight
  recorder to schema-valid JSONL.

Engine-level preemption correctness (the bit-exact oracle) lives in
tests/test_serving.py; the full pump-driven preemption e2e is also
exercised by ``python -m paddle_tpu.obs check`` (check_graphs.sh) and
kept ``slow`` here to protect the tier-1 budget. Tests in this file
use ``max_new_tokens=1`` so prefill completion emits the only token
and the jitted decode quantum never compiles."""
import asyncio
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.obs.flight import load_flight_records
from paddle_tpu.serving import (
    BATCH, INTERACTIVE, NORMAL, FrontDoorPolicy, Request,
    ServingEngine, ServingFrontDoor, choose_victim, no_shed_policy,
)


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


# ------------------------------------------------ policy units
def test_policy_shedding_ladder():
    pol = FrontDoorPolicy()  # stock: warn sheds BATCH, critical +NORMAL
    assert pol.admission(BATCH, "ok", 0) == (True, None)
    assert pol.admission(BATCH, "warn", 0) == (False, "slo_warn")
    assert pol.admission(NORMAL, "warn", 0) == (True, None)
    assert pol.admission(NORMAL, "critical", 0) == (False,
                                                    "slo_critical")
    # the stock ladder never sheds INTERACTIVE
    assert pol.admission(INTERACTIVE, "critical", 10 ** 6)[0]
    # warn set is implied at critical even if passed disjoint
    pol2 = FrontDoorPolicy(shed_on_warn=(BATCH,),
                           shed_on_critical=(NORMAL,))
    assert pol2.admission(BATCH, "critical", 0) == (False,
                                                    "slo_critical")


def test_policy_backpressure_and_passthrough():
    pol = FrontDoorPolicy(max_waiting=4)
    assert pol.admission(NORMAL, "ok", 3) == (True, None)
    assert pol.admission(NORMAL, "ok", 4) == (False, "backpressure")
    assert pol.admission(INTERACTIVE, "ok", 100) == (True, None)
    ns = no_shed_policy()
    assert ns.admission(BATCH, "critical", 10 ** 6) == (True, None)
    assert ns.preempt is False


def test_choose_victim_rules():
    def req(pri, admit_t, slot=0):
        r = Request(np.arange(1, 4), max_new_tokens=2, priority=pri)
        r.admit_time = admit_t
        r.slot = slot
        return r

    lo_old = req(BATCH, 1.0)
    lo_new = req(BATCH, 2.0)
    mid = req(NORMAL, 0.5)
    live = [mid, lo_old, lo_new]
    # lowest class first, newest admission within the class
    assert choose_victim(live, INTERACTIVE) is lo_new
    assert choose_victim([mid], INTERACTIVE) is mid
    # equal priority never preempts
    assert choose_victim([mid], NORMAL) is None
    # finished / slotless requests are not victims
    mid.finished = True
    lo_old.slot = None
    lo_new.slot = None
    assert choose_victim(live, INTERACTIVE) is None


# ------------------------------------------------ streaming + shed
def test_frontdoor_stream_backpressure_drain(tmp_path, tiny_model):
    """One quantum-free pass over the whole front-door surface:
    sync streaming delivers exactly the emitted tokens, backpressure
    sheds the queue tail (exempting INTERACTIVE), shed streams arrive
    closed with journals captured, drain finishes accepted work,
    refuses new work with reason ``draining``, and flushes schema-valid
    flight JSONL."""
    cfg, model = tiny_model
    rng = np.random.RandomState(0)
    fd = inference.serve(model, num_slots=2, block_size=4,
                         prefill_chunk=8,
                         policy=FrontDoorPolicy(max_waiting=1))
    prompts = [rng.randint(1, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(4)]
    # 2 admit (slots), 1 queues (depth 0 -> ok... depth 1 at 4th), rest
    # shed: submissions see waiting depth 0,1,1,... with max_waiting=1
    s0 = fd.submit(prompts[0], max_new_tokens=1, priority=NORMAL)
    s1 = fd.submit(prompts[1], max_new_tokens=1, priority=NORMAL)
    s2 = fd.submit(prompts[2], max_new_tokens=1, priority=BATCH)
    s3 = fd.submit(prompts[3], max_new_tokens=1,
                   priority=INTERACTIVE)  # exempt from backpressure
    shed = [s for s in (s0, s1, s2, s3) if s.shed]
    kept = [s for s in (s0, s1, s2, s3) if not s.shed]
    assert s2 in shed and s3 not in shed
    for s in shed:
        assert s.closed and list(s) == [] and s.result().size == 0
    # sync streaming: each pull pumps the engine until tokens land
    for s in kept:
        toks = list(s)
        assert toks == s.request.tokens and len(toks) == 1
        assert s.finish_reason == "length"
    # drain: flush journals, then refuse new work
    out = fd.drain(flight_path=str(tmp_path / "flight.jsonl"))
    assert out["drained"] and out["completed"] == len(kept)
    records = load_flight_records(tmp_path / "flight.jsonl")
    shed_recs = [r for r in records
                 if r["events"][-1]["kind"] == "shed"]
    assert len(shed_recs) == len(shed)
    assert all(r["events"][-1]["reason"] == "backpressure"
               for r in shed_recs)
    late = fd.submit(prompts[0], max_new_tokens=1)
    assert late.shed
    assert json.loads(json.dumps(fd.stats()))["draining"] is True
    reg = fd.engine.obs.registry
    assert reg.get("serving_requests_shed_total").value() == \
        len(shed) + 1
    assert reg.get("serving_drains_total").value() == 1


def test_frontdoor_slo_shedding_forced_critical(tiny_model):
    """Burn-rate-driven admission: poison the TTFT sample series so
    both windows burn far past the critical gate — BATCH and NORMAL
    shed with reason ``slo_critical``, INTERACTIVE still admits; the
    health report is cached between submissions."""
    cfg, model = tiny_model
    fd = inference.serve(model, num_slots=2, block_size=4,
                         policy=FrontDoorPolicy(health_interval_s=0.0))
    eng = fd.engine
    now = eng.obs.now()
    # every recent TTFT sample blows the 0.5s stock objective
    eng.obs._series["ttft_seconds"].extend(
        [(now - i * 0.1, 10.0) for i in range(20)])
    assert eng.health(now=now)["state"] == "critical"
    p = np.arange(1, 6, dtype=np.int32)
    assert fd.submit(p, max_new_tokens=1, priority=BATCH).shed
    assert fd.submit(p, max_new_tokens=1, priority=NORMAL).shed
    hi = fd.submit(p, max_new_tokens=1, priority=INTERACTIVE)
    assert not hi.shed
    reasons = {r.req_id: None for r in fd.shed_requests}
    assert len(reasons) == 2
    # shed outcomes burned the error-rate objective too
    outcomes = eng.obs.timeseries()["request_outcomes"]
    assert [v for _, v in outcomes].count(1.0) == 2
    fd.drain()


def test_frontdoor_async_streaming(tiny_model):
    """The asyncio facade: a run_async task pumps the engine while
    consumers ``async for`` their streams; stop() ends the loop."""
    cfg, model = tiny_model
    rng = np.random.RandomState(1)
    fd = inference.serve(model, num_slots=2, block_size=4,
                         prefill_chunk=8)

    async def client(prompt, priority):
        stream = fd.submit(prompt, max_new_tokens=1, priority=priority)
        return [tok async for tok in stream]

    async def main():
        task = asyncio.create_task(fd.run_async(idle_s=0.001))
        outs = await asyncio.gather(
            client(rng.randint(1, cfg.vocab_size, 5)
                   .astype(np.int32), INTERACTIVE),
            client(rng.randint(1, cfg.vocab_size, 7)
                   .astype(np.int32), NORMAL),
            client(rng.randint(1, cfg.vocab_size, 3)
                   .astype(np.int32), BATCH))
        fd.stop()
        await asyncio.wait_for(task, timeout=30)
        return outs

    outs = asyncio.run(main())
    assert [len(o) for o in outs] == [1, 1, 1]
    done = {r.req_id: r for r in fd.engine.completed}
    assert len(done) == 3
    for toks in outs:
        assert all(0 <= t < cfg.vocab_size for t in toks)


def test_serve_facade_wiring(tiny_model):
    """inference.serve(): SLOs + flight recorder default ON, a sampling
    door takes a per-request temperature, one front door per engine
    enforced."""
    cfg, model = tiny_model
    fd = inference.serve(model, num_slots=2, block_size=4)
    assert fd.engine.slo is not None and fd.engine.flight is not None
    assert fd.engine.token_sink is not None
    with pytest.raises(ValueError, match="one front door"):
        ServingFrontDoor(fd.engine)
    fd2 = inference.serve(model, num_slots=2, block_size=4,
                          decode_strategy="sampling", top_k=4)
    assert fd2.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=2,
                      temperature=0.7).request.temperature == 0.7
    # engine without SLOs: health reads vacuous ok, shedding rests on
    # backpressure alone
    eng = ServingEngine(model, num_slots=2, block_size=4)
    fd3 = ServingFrontDoor(eng, policy=FrontDoorPolicy())
    assert fd3._health_state(eng.obs.now()) == "ok"


@pytest.mark.slow
def test_frontdoor_pump_preemption_e2e(tiny_model):
    """Pump-driven preemption under slot pressure: an INTERACTIVE
    arrival evicts the newest BATCH victim mid-decode, both finish,
    and the victim's stream continues across the eviction (also
    exercised by `python -m paddle_tpu.obs check` in check_graphs.sh;
    slow-marked to keep the tier-1 compile budget flat)."""
    cfg, model = tiny_model
    rng = np.random.RandomState(2)
    fd = inference.serve(model, num_slots=1, block_size=4,
                         prefill_chunk=4, decode_quantum=2)
    low = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                    .astype(np.int32), max_new_tokens=6,
                    priority=BATCH)
    while len(low.request.tokens) < 2:
        fd.pump()
    hi = fd.submit(rng.randint(1, cfg.vocab_size, 4)
                   .astype(np.int32), max_new_tokens=4,
                   priority=INTERACTIVE)
    fd.run_until_idle()
    assert fd.engine.scheduler.preempted_total == 1
    assert fd.engine.scheduler.resumed_total == 1
    assert len(hi.request.tokens) == 4
    assert len(low.request.tokens) == 6
    assert low.request.preemptions == 1
    assert fd.engine.pool.fragmentation_stats()["blocks_in_use"] == 1


# ------------------------------------------------ resilience (ISSUE 13)
def test_stream_timeout_kwarg(tiny_model):
    """``submit(..., timeout=)`` bounds each token wait: a starved
    stream raises TimeoutError instead of pumping forever, and a
    stream whose tokens keep arriving never notices its timeout."""
    cfg, model = tiny_model
    rng = np.random.RandomState(3)
    # no_shed: the first pump's jit-compile TTFT would otherwise read
    # critical and shed the NORMAL submissions below
    fd = inference.serve(model, num_slots=1, block_size=4,
                         prefill_chunk=8, policy=no_shed_policy())
    busy = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                     .astype(np.int32), max_new_tokens=3,
                     priority=NORMAL)
    starved = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                        .astype(np.int32), max_new_tokens=1,
                        priority=NORMAL, timeout=1e-4)
    with pytest.raises(TimeoutError, match="no token"):
        list(starved)
    # the raise is per-gap, not terminal: once the slot frees, the
    # same stream drains normally
    ok = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                   .astype(np.int32), max_new_tokens=1,
                   priority=NORMAL, timeout=30.0)
    assert list(busy) == busy.request.tokens
    assert len(list(starved)) + len(starved.request.tokens) >= 1
    assert len(list(ok)) == 1
    fd.drain()


def test_quarantined_stream_reaped(tiny_model):
    """A poisoned request emits no closing token — the front door's
    finished-stream reap must close its stream anyway (consumer loop
    ends, finish_reason="error"), while other streams drain normally."""
    from paddle_tpu.serving import FaultInjector

    cfg, model = tiny_model
    rng = np.random.RandomState(4)
    inj = FaultInjector(seed=0)
    fd = inference.serve(model, num_slots=2, block_size=4,
                         prefill_chunk=8, faults=inj, resilience=True)
    good = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                     .astype(np.int32), max_new_tokens=2)
    bad = fd.submit(rng.randint(1, cfg.vocab_size, 7)
                    .astype(np.int32), max_new_tokens=2)
    inj.poison(bad.request.req_id)
    fd.run_until_idle()
    assert bad.request.finish_reason == "error"
    assert bad.closed and list(bad) == []
    assert good.finish_reason == "length"
    assert len(good.request.tokens) == 2
    assert fd.engine.resilience_report()["quarantined"] == [
        str(bad.request.req_id)]
    fd.drain()


def test_pump_failure_fails_open_streams(tiny_model, monkeypatch):
    """A REAL engine exception out of a pump fails every open stream
    terminally (finish_reason="error") and re-raises to the pumping
    consumer — nobody blocks on a dead engine."""
    cfg, model = tiny_model
    rng = np.random.RandomState(5)
    fd = inference.serve(model, num_slots=2, block_size=4,
                         prefill_chunk=8)
    s0 = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                   .astype(np.int32), max_new_tokens=1)
    s1 = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                   .astype(np.int32), max_new_tokens=1)

    def boom():
        raise RuntimeError("engine died")
    monkeypatch.setattr(fd.engine, "step", boom)
    with pytest.raises(RuntimeError, match="engine died"):
        list(s0)
    assert s0.closed and s1.closed
    assert s0.finish_reason == "error" and s1.finish_reason == "error"
    assert fd._streams == {}


def test_orphaned_stream_error_closes(tiny_model):
    """A stream whose request fell out of an IDLE engine closes with
    finish_reason="error" instead of spinning on pump forever."""
    cfg, model = tiny_model
    rng = np.random.RandomState(6)
    fd = inference.serve(model, num_slots=2, block_size=4,
                         prefill_chunk=8)
    s = fd.submit(rng.randint(1, cfg.vocab_size, 5)
                  .astype(np.int32), max_new_tokens=1)
    fd.engine.scheduler.waiting.remove(s.request)   # simulate the drop
    assert list(s) == []
    assert s.closed and s.finish_reason == "error"


def test_frontdoor_snapshot_restore_streams(tiny_model):
    """Crash recovery through the front door: restore() re-opens every
    in-flight stream pre-loaded with its already-emitted tokens, and
    consumers of the restored streams see the FULL bit-exact
    sequences."""
    cfg, model = tiny_model
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab_size, 5).astype(np.int32)
               for _ in range(2)]
    ref = ServingEngine(model, num_slots=2, block_size=4,
                        prefill_chunk=8, decode_quantum=2)
    want = [list(ref.submit(p, max_new_tokens=4).tokens) or None
            for p in prompts]
    ref.run()
    want = [list(r.tokens) for r in ref.completed]

    fd = inference.serve(model, num_slots=2, block_size=4,
                         prefill_chunk=8, decode_quantum=2)
    streams = [fd.submit(p, max_new_tokens=4) for p in prompts]
    while not any(s.request.tokens for s in streams):
        fd.pump()
    snap = json.loads(json.dumps(fd.snapshot()))
    fd2 = ServingFrontDoor.restore(snap, model)
    restored = list(fd2._streams.values())
    assert len(restored) == 2
    got = {str(s.request.req_id): list(s) for s in restored}
    ids = [str(s.request.req_id) for s in streams]
    assert [got[i] for i in ids] == want
    assert all(s.finish_reason == "length" for s in restored)
    fd2.drain()
