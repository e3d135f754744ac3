"""paddle_tpu.obs — runtime observability (ISSUE 5).

Three tiers: pure-host unit tests (histogram bucket math vs the
prometheus cumulative definition, stable-sorted snapshots, Chrome
trace-event schema round-trip), engine-integration tests (metrics
correctness under ragged arrivals with slot reuse and spec decode:
TTFT observed exactly once per request, the token counter matching the
emitted streams token-for-token), and the train-side wrapper
(step time / tokens-per-second into the same registry, analysis hooks
passing through untouched). The no-graph-change half of the story —
instrumented engines keeping byte-identical golden fingerprints — is
asserted where the fingerprints live (tests/test_serving.py budget
tests audit engines that now build with ``trace=True``, plus
``python -m paddle_tpu.obs check`` in scripts/check_graphs.sh)."""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.obs import (
    InstrumentedTrainStep, MetricsRegistry, ServingObs, TraceRecorder,
    load_chrome_trace, prometheus_from_snapshot, validate_chrome_trace,
)


# ------------------------------------------------------------ registry
def test_counter_and_gauge_basics():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    c.inc(1, route="spec")
    assert c.value() == 3.5
    assert c.value(route="spec") == 1.0
    with pytest.raises(ValueError, match="cannot decrease"):
        c.inc(-1)
    g = r.gauge("depth")
    g.set(7, pool="target")
    g.set(3, pool="draft")
    assert g.value(pool="target") == 7.0
    # same name, different kind -> loud failure
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("reqs_total")
    # create-or-get returns the same instrument
    assert r.counter("reqs_total") is c


def test_histogram_bucket_math_vs_reference():
    """Bucket placement vs the prometheus DEFINITION (le is <=,
    cumulative over buckets, +Inf overflow), computed independently
    with numpy over the raw observations."""
    buckets = (0.01, 0.1, 1.0, 5.0)
    r = MetricsRegistry()
    h = r.histogram("lat_seconds", buckets=buckets)
    rng = np.random.RandomState(0)
    values = np.concatenate([
        rng.exponential(0.5, 200),
        np.asarray(buckets),          # exact bounds land IN the bucket
        [7.5, 100.0],                 # +Inf overflow
    ])
    for v in values:
        h.observe(float(v))
    counts = h.bucket_counts()
    cum = np.cumsum(counts)
    for i, le in enumerate(buckets):
        assert cum[i] == int((values <= le).sum()), f"le={le}"
    assert cum[-1] == len(values)
    assert h.count() == len(values)
    assert h.sum() == pytest.approx(values.sum())
    q50 = h.quantile(0.5)
    assert 0 < q50 <= buckets[-1]
    # exposition: cumulative _bucket lines + +Inf + _sum/_count
    prom = r.prometheus()
    assert f'lat_seconds_bucket{{le="+Inf"}} {len(values)}' in prom
    assert "lat_seconds_count 206" in prom
    with pytest.raises(ValueError, match="increasing"):
        r2 = MetricsRegistry()
        r2.histogram("bad", buckets=(1.0, 1.0))


def test_snapshot_stable_sorted_and_prom_roundtrip():
    r = MetricsRegistry()
    # register in non-sorted order with label permutations
    r.gauge("zz").set(1, b="2", a="1")
    r.counter("aa").inc(3)
    r.histogram("mm", buckets=(1.0, 2.0)).observe(1.5)
    s1, s2 = r.snapshot_json(), r.snapshot_json()
    assert s1 == s2
    snap = json.loads(s1)
    assert [m["name"] for m in snap["metrics"]] == ["aa", "mm", "zz"]
    # offline re-render == live exposition (the CLI snapshot path)
    assert prometheus_from_snapshot(snap) == r.prometheus()
    assert 'zz{a="1",b="2"} 1' in r.prometheus()


# ------------------------------------------------------------ tracing
def test_trace_event_schema_roundtrip(tmp_path):
    t = TraceRecorder(epoch=100.0)
    t.thread_name(1, "slot0")
    t.complete("prefill", 100.001, 100.003, tid=1,
               args={"tokens": 4})
    t.instant("first_token", 100.0035, tid=1)
    t.counter("occupancy", 100.004, {"live": 2, "free": 1})
    path = str(tmp_path / "trace.json")
    t.save(path)
    obj = load_chrome_trace(path)  # validates on load
    evs = obj["traceEvents"]
    assert len(evs) == 4
    x = [e for e in evs if e["ph"] == "X"][0]
    assert x["ts"] == pytest.approx(1000.0)   # µs after epoch
    assert x["dur"] == pytest.approx(2000.0)
    assert x["args"]["tokens"] == 4
    assert obj["otherData"]["dropped_events"] == 0
    # schema violations are loud
    with pytest.raises(ValueError, match="missing 'traceEvents'"):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError, match="missing 'dur'"):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "tid": 0, "ts": 0}]})


def test_trace_bounded_buffer_drops_not_grows():
    t = TraceRecorder(max_events=3, epoch=0.0)
    for i in range(10):
        t.instant(f"e{i}", 0.001 * i)
    assert len(t.events) == 3
    assert t.dropped == 7
    assert t.chrome_trace()["otherData"]["dropped_events"] == 7


# ---------------------------------------------- engine metrics (plain)
@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def test_engine_metrics_ragged_slot_reuse(tiny_model):
    """5 ragged requests over 2 slots (retirement + slot reuse
    mid-run): TTFT observed exactly once per request, the emitted-token
    counter matches the streams token-for-token, latency histograms see
    every request, and the legacy stats view mirrors the registry."""
    from paddle_tpu.serving import ServingEngine

    cfg, model = tiny_model
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 3, 7, 4)]
    max_new = [4, 3, 6, 2, 5]
    # the trace buffer is the process's one: start it empty
    TraceRecorder.process().clear()
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=4, decode_quantum=3,
                           trace=True)
    assert engine.obs.tracer is TraceRecorder.process()
    reqs = [engine.submit(p, max_new_tokens=mn)
            for p, mn in zip(prompts, max_new)]
    done = engine.run()
    assert len(done) == len(reqs)
    r = engine.obs.registry
    n_req = len(reqs)
    total_tokens = sum(len(q.tokens) for q in done)
    assert r.get("serving_requests_submitted_total").value() == n_req
    assert r.get("serving_requests_admitted_total").value() == n_req
    assert r.get("serving_requests_finished_total").value() == n_req
    # TTFT: once per request, never re-observed on slot reuse
    assert r.get("serving_ttft_seconds").count() == n_req
    assert r.get("serving_queue_wait_seconds").count() == n_req
    assert r.get("serving_e2e_latency_seconds").count() == n_req
    # token accounting matches the emitted streams exactly
    assert r.get("serving_tokens_emitted_total").value() == total_tokens
    assert engine.stats["generated_tokens"] == total_tokens
    # every request here emits >=2 tokens -> inter-token recorded
    assert r.get("serving_inter_token_seconds").count() == n_req
    # per-dispatch histogram saw mixed steps AND decode quanta
    hq = r.get("serving_quantum_seconds")
    assert hq.count(kind="mixed") == engine.stats["mixed_steps"]
    assert hq.count(kind="decode") == engine.stats["decode_quanta"]
    # legacy view IS the registry (one source of truth)
    assert (engine.stats["decode_quanta"]
            == r.get("serving_decode_quanta_total").value())
    # windowed throughput + pool gauges moved
    assert r.get("serving_tokens_per_second_window").value() > 0
    assert len(engine.obs.timeseries()["tokens_per_s"]) > 0
    assert r.get("serving_pool_utilization").value(pool="target") >= 0
    # trace: valid, with per-slot request spans and quantum spans
    obj = validate_chrome_trace(engine.obs.tracer.chrome_trace())
    names = [e["name"] for e in obj["traceEvents"]]
    assert sum(1 for n in names if n.startswith("req ")) == n_req
    # the per-dispatch events are the engine's own spans (ISSUE 26), not
    # a push from on_quantum: one a mixed step, and one for each half of
    # a quantum (dispatched, then collected)
    assert names.count("engine.mixed") == engine.stats["mixed_steps"]
    assert names.count("engine.decode") \
        == 2 * engine.stats["decode_quanta"]
    assert names.count("request.queued") == n_req
    assert "decode" not in names and "mixed" not in names
    # engine_stats keeps its historical dict shape
    st = engine.engine_stats()
    for key in ("steps", "mixed_steps", "decode_quanta", "pool",
                "admitted", "finished", "mean_occupancy"):
        assert key in st


def test_engine_metrics_spec_decode(tiny_model):
    """The speculative arm: same invariants (TTFT once, streams match)
    plus acceptance-rate instrumentation consistent with the legacy
    spec counters, and draft-pool gauges labeled separately. The
    flight recorder rides along (ISSUE 6) with a forced e2e trigger so
    every journal captures — its spec_round events must reconcile with
    the engine's acceptance counters."""
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.obs import FlightRecorder
    from paddle_tpu.serving import ServingEngine

    cfg, model = tiny_model
    paddle.seed(11)
    draft = LlamaForCausalLM(
        LlamaConfig.tiny(tensor_parallel=False, num_hidden_layers=1))
    draft.eval()
    engine = ServingEngine(model, spec_draft=draft, spec_gamma=2,
                           num_slots=2, block_size=4, prefill_chunk=3,
                           trace=True, slo=True,
                           flight=FlightRecorder(e2e_threshold=1e-9))
    rng = np.random.RandomState(5)
    reqs = [engine.submit(rng.randint(1, cfg.vocab_size, n)
                          .astype(np.int32), max_new_tokens=5)
            for n in (6, 4, 8)]
    done = engine.run()
    assert len(done) == len(reqs)
    r = engine.obs.registry
    total_tokens = sum(len(q.tokens) for q in done)
    assert r.get("serving_ttft_seconds").count() == len(reqs)
    assert r.get("serving_tokens_emitted_total").value() == total_tokens
    assert (r.get("serving_quantum_seconds").count(kind="spec_round")
            == engine.stats["spec_rounds"])
    assert (r.get("serving_spec_proposed_total").value()
            == engine.stats["spec_proposed"])
    rate = r.get("serving_spec_acceptance_rate").value()
    assert 0.0 <= rate <= 1.0
    assert len(engine.obs.timeseries()["spec_acceptance_rate"]) \
        == engine.stats["spec_rounds"]
    assert r.get("serving_pool_blocks_in_use").value(pool="draft") >= 0
    validate_chrome_trace(engine.obs.tracer.chrome_trace())
    # flight journals: every request captured (forced trigger), and
    # their spec_round events reconcile with the engine's counters
    recs = engine.flight.records()  # schema-validates
    assert len(recs) == len(reqs)
    spec_evs = [e for rec in recs for e in rec["events"]
                if e["kind"] == "spec_round"]
    assert spec_evs, "speculative rounds must be journaled"
    assert all(0 <= e["accepted"] <= e["proposed"] == 2
               for e in spec_evs)
    assert (sum(e["accepted"] for e in spec_evs)
            == engine.stats["spec_accepted"])
    # health evaluates over the same run (state depends on wall clock;
    # the report shape is the contract here)
    assert {o["name"] for o in engine.health()["objectives"]} \
        == {"ttft_p95", "inter_token_p99", "e2e_p99", "error_rate"}


def test_engine_obs_off_is_inert(tiny_model):
    """The overhead-bench baseline arm: rich hooks fully short-circuit
    (no histogram observations, no tracer), while the engine still
    runs and the legacy counters behind ``stats`` tick. One mixed step
    only — the decode quantum never compiles here."""
    from paddle_tpu.serving import ServingEngine

    cfg, model = tiny_model
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=3,
                           obs="off")
    rng = np.random.RandomState(7)
    req = engine.submit(rng.randint(1, cfg.vocab_size, 5)
                        .astype(np.int32), max_new_tokens=4)
    engine.step()  # admit + full prefill -> first token emitted
    assert len(req.tokens) == 1
    r = engine.obs.registry
    assert r.get("serving_ttft_seconds").count() == 0
    assert r.get("serving_tokens_emitted_total").value() == 0
    assert r.get("serving_requests_submitted_total").value() == 0
    assert engine.obs.tracer is None
    assert engine.stats["steps"] == 1  # legacy counters still live
    assert engine.stats["mixed_steps"] == 1


# ------------------------------------------------------------ training
def test_instrumented_train_step():
    """Wrap a JittedTrainStep: step histogram/counters/gauges tick in
    the shared registry, report() summarizes, and the analysis hooks
    (lower/donatable_leaf_count) pass through to the SAME wrapped
    step."""
    from paddle_tpu.jit.train import JittedTrainStep

    paddle.seed(0)
    model = paddle.nn.Linear(8, 8)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    def crit(out, label):
        d = out - label
        return (d * d).mean()

    step = JittedTrainStep(model, crit, opt)
    reg = MetricsRegistry()
    tracer = TraceRecorder()
    inst = InstrumentedTrainStep(step, registry=reg,
                                 tokens_per_step=16, tracer=tracer)
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(2, 8).astype("f4"))
    y = paddle.to_tensor(rng.randn(2, 8).astype("f4"))
    inst(x, y)
    l2 = inst(x, y)
    assert np.isfinite(float(np.asarray(l2._value)))
    assert reg.get("train_steps_total").value() == 2
    assert reg.get("train_step_seconds").count() == 2
    assert reg.get("train_tokens_total").value() == 32
    assert reg.get("train_tokens_per_second").value() > 0
    rep = inst.report()
    assert rep["n_steps_timed"] == 2 and rep["tokens_per_sec"] > 0
    # analysis hooks reach the wrapped step untouched
    assert inst.donatable_leaf_count() == step.donatable_leaf_count()
    assert inst.lower(x, y) is not None
    assert len(tracer.events) >= 2
    # serving + train can share one registry namespace-free
    assert "train_step_seconds" in reg.prometheus()


def test_for_transformer_flops_accounting():
    reg = MetricsRegistry()

    calls = []

    class FakeStep:
        def __call__(self, inputs, labels):
            calls.append(1)

            class L:
                _value = np.float32(0.5)

            return L()

    inst = InstrumentedTrainStep.for_transformer(
        FakeStep(), n_params=1000, tokens_per_step=64, registry=reg,
        sync=False)
    assert inst.model_flops_per_step == pytest.approx(6.0 * 1000 * 64)
    inst([], [])
    assert reg.get("train_model_tflops_per_second").value() > 0


# ------------------------------------------------------------ CLI
def test_obs_cli_offline_snapshot_and_trace(tmp_path, capsys):
    """The offline CLI paths (no engine, tier-1-cheap): `snapshot
    --in` re-renders a saved registry dump as prometheus text, and
    `export --in` validates a saved chrome trace."""
    from paddle_tpu.obs.__main__ import main

    reg = MetricsRegistry()
    reg.counter("serving_requests_finished_total").inc(4)
    reg.histogram("serving_ttft_seconds",
                  buckets=(0.01, 0.1)).observe(0.05)
    snap_path = str(tmp_path / "metrics.json")
    with open(snap_path, "w") as f:
        f.write(reg.snapshot_json())
    assert main(["snapshot", "--in", snap_path,
                 "--format", "prom"]) == 0
    out = capsys.readouterr().out
    assert "# TYPE serving_ttft_seconds histogram" in out
    assert "serving_requests_finished_total 4" in out
    t = TraceRecorder(epoch=0.0)
    t.complete("decode", 0.001, 0.002)
    trace_path = str(tmp_path / "trace.json")
    t.save(trace_path)
    assert main(["export", "--in", trace_path]) == 0
    # missing-input paths exit 2, not a stack trace
    assert main(["snapshot"]) == 2
    assert main(["export"]) == 2


@pytest.mark.slow
def test_obs_cli_demo_export_and_snapshot(tmp_path, capsys):
    """`python -m paddle_tpu.obs export --demo` end to end: drives a
    tiny engine and writes a Perfetto-valid trace + metrics snapshot
    (slow tier: one extra engine compile)."""
    from paddle_tpu.obs.__main__ import main

    trace_path = str(tmp_path / "trace.json")
    snap_path = str(tmp_path / "metrics.json")
    rc = main(["export", "--demo", "--out", trace_path,
               "--metrics-out", snap_path])
    assert rc == 0
    obj = load_chrome_trace(trace_path)
    assert len(obj["traceEvents"]) > 10
    rc = main(["snapshot", "--in", snap_path, "--format", "prom"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "serving_requests_finished_total 4" in out
