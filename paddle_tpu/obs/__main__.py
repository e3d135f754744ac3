"""Observability CLI::

    python -m paddle_tpu.obs snapshot --in metrics.json [--format prom]
    python -m paddle_tpu.obs snapshot --demo [--format prom|json]
    python -m paddle_tpu.obs export --demo --out trace.json \
        [--metrics-out metrics.json] [--spec]
    python -m paddle_tpu.obs export --in trace.json      # validate
    python -m paddle_tpu.obs serve --demo [--port 9100] [--duration S]
    python -m paddle_tpu.obs slo --demo [--out series.json]
    python -m paddle_tpu.obs slo --in series.json [--fail-on critical]
    python -m paddle_tpu.obs watch --url http://127.0.0.1:9100
    python -m paddle_tpu.obs watch --in metrics.json [--slo-in rep.json]
    python -m paddle_tpu.obs check                       # CI gate
    python -m paddle_tpu.obs profile --in <trace dir or .xplane.pb> \
        [--mesh-axes axes.json] [--format json]

``snapshot`` renders a metrics snapshot (live from the ``--demo``
engine run, or re-rendered offline from a saved ``--in`` JSON dump) as
Prometheus text or stable-sorted JSON. ``export`` writes/validates the
Chrome trace-event JSON (open in Perfetto / chrome://tracing); with
``--demo`` it drives a tiny CPU serving engine (``--spec`` switches it
to the speculative arm) so the artifact carries real request spans.

``profile`` reads a saved DEVICE trace (what a
``paddle_tpu.profiler.Profiler`` session or ``jax.profiler.start_trace``
wrote: a log directory, the newest trace in it, or one ``.xplane.pb``)
through ``paddle_tpu.profiler.load_profiler_result`` and prints its four
tables: programs, device seconds by named scope and phase, collectives
by mesh axis (``--mesh-axes``: a JSON file ``{axis: groups of partition
ids}`` as ``parallel.mesh.axis_groups()`` gave them when the trace was
taken), idle gaps by ``RecordEvent`` span. No chip and no engine needed.

The operability tier (ISSUE 6): ``serve`` runs the live HTTP exporter
(obs/export.py — ``/metrics`` ``/healthz`` ``/slo`` ``/snapshot``
``/anomalies``) over the demo engine; ``slo`` evaluates the burn-rate
health report (live from ``--demo``, or offline from a saved
``series_snapshot`` via ``--in``; ``--fail-on warn|critical`` turns
the state into an exit code for scripts); ``watch`` renders the
terminal dashboard — polling a running exporter's ``/snapshot`` +
``/slo`` with ``--url``, or one frame from saved files with ``--in``.

``check`` is the instrumentation-can't-change-the-graph gate used by
``scripts/check_graphs.sh``: it builds the serving + speculative +
front-door + prefix-cache analysis recipes — whose engines run with
FULL observability (registry + tracer + SLOs + flight recorder) —
re-checks their budgets, compares the golden fingerprints, and asserts
the instrumentation actually recorded (metrics counted, trace
validates). It then runs the SLO smoke on the demo engine (lenient
objectives must read ``ok``, impossible ones ``critical``, forced
threshold crossings must produce schema-valid anomaly journals), the
FRONT-DOOR smoke (ISSUE 7: a forced priority preemption must fire the
preempted/resumed/recomputed counters, resume bit-continuously, drain
must flush the flight journals, and the dashboard must render the
overload line), and the PREFIX-CACHE smoke (ISSUE 9: a forced cache
hit + copy-on-write must fire the prefix counters, keep the streams
bit-identical to an unshared engine, and render the dashboard's
prefix line), the QUANTIZED-SERVING smoke (ISSUE 14: a forced hit +
COW on a weight-int8/kv-int8 engine must keep shared streams
bit-identical to an unshared int8 engine and show the dtype-aware
pool-bytes gauge well under half a float engine's), and the
ATTRIBUTION smoke (ISSUE 10: the cost ledger
must conserve — phase token buckets sum to the emitted-token counter
token-for-token, and per-phase seconds sum to the measured quantum
walls within float tolerance), and the RESILIENCE smoke (ISSUE 13: a
bounded seeded chaos soak — faults x preemption x COW — must keep
every non-poisoned stream bit-exact vs the fault-free arm with zero
leaked blocks), and the CLUSTER smoke (ISSUE 15: a 2-replica router
run on a shared-prefix trace must land affinity hits, fire the
``serving_router_*`` counters, stream bit-identically to a
cluster-of-1, and render the merged dashboard's cluster line). Exit
non-zero on drift.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def _demo_engine(spec=False, trace=True, slo=None, flight=None):
    """A tiny CPU serving run with full instrumentation: a handful of
    ragged requests through prefill/decode (+ the speculative arm),
    enough to populate every serving metric and trace track."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    kw = {}
    if spec:
        paddle.seed(7)
        kw = dict(
            spec_draft=LlamaForCausalLM(LlamaConfig.tiny(
                tensor_parallel=False, num_hidden_layers=1)),
            spec_gamma=2)
    engine = ServingEngine(model, num_slots=3, block_size=4,
                           prefill_chunk=4, decode_quantum=3,
                           trace=trace, slo=slo, flight=flight, **kw)
    rng = np.random.RandomState(0)
    for n, mn in ((5, 6), (9, 4), (3, 8), (12, 5)):
        engine.submit(rng.randint(1, cfg.vocab_size, n)
                      .astype(np.int32), max_new_tokens=mn)
    engine.run()
    return engine


def _cmd_snapshot(args):
    from .registry import prometheus_from_snapshot

    if args.demo:
        snap = _demo_engine(spec=args.spec,
                            trace=False).obs.registry.snapshot()
    elif args.infile:
        with open(args.infile) as f:
            snap = json.load(f)
    else:
        print("snapshot: need --demo or --in FILE", file=sys.stderr)
        return 2
    text = (prometheus_from_snapshot(snap) if args.format == "prom"
            else json.dumps(snap, indent=2, sort_keys=True) + "\n")
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"wrote {args.out}", file=sys.stderr)
    else:
        sys.stdout.write(text)
    return 0


def _cmd_export(args):
    from .trace import load_chrome_trace

    if args.demo:
        if not args.out:
            print("export --demo: need --out FILE", file=sys.stderr)
            return 2
        engine = _demo_engine(spec=args.spec, trace=True)
        engine.obs.tracer.save(args.out)
        n = len(engine.obs.tracer.events)
        print(f"wrote {args.out}: {n} trace events "
              f"({engine.obs.tracer.dropped} dropped)", file=sys.stderr)
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(engine.obs.registry.snapshot_json(indent=2))
            print(f"wrote {args.metrics_out}", file=sys.stderr)
        return 0
    if args.infile:
        obj = load_chrome_trace(args.infile)
        print(f"{args.infile}: valid chrome trace, "
              f"{len(obj['traceEvents'])} events", file=sys.stderr)
        return 0
    print("export: need --demo or --in FILE", file=sys.stderr)
    return 2


def _cmd_serve(args):
    """Live exporter over the demo engine: the zero-to-scrape path —
    run it, point a browser / curl / Prometheus at the printed URLs."""
    from .export import MetricsExporter

    engine = _demo_engine(spec=args.spec, trace=False, slo=True,
                          flight=True)
    exporter = MetricsExporter.for_engine(
        engine, host=args.host, port=args.port).start()
    for route in exporter.routes():
        print(f"serving {exporter.url(route)}", file=sys.stderr)
    try:
        if args.duration is not None:
            time.sleep(args.duration)
        else:
            print("Ctrl-C to stop", file=sys.stderr)
            while True:
                time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        exporter.stop()
    return 0


def _cmd_slo(args):
    """Burn-rate health report: live from the demo engine, or offline
    from a saved ``ServingObs.series_snapshot()`` dump."""
    from .slo import SLOSet, state_of

    if args.demo:
        engine = _demo_engine(spec=args.spec, trace=False, slo=True,
                              flight=True)
        report = engine.health()
        if args.out:
            with open(args.out, "w") as f:
                json.dump(engine.obs.series_snapshot(), f,
                          sort_keys=True)
            print(f"wrote {args.out}", file=sys.stderr)
    elif args.infile:
        with open(args.infile) as f:
            snap = json.load(f)
        if snap.get("version") != 1 or "series" not in snap:
            print(f"slo: {args.infile} is not a series snapshot "
                  f"(need version=1 + 'series'; write one with "
                  f"`slo --demo --out FILE`)", file=sys.stderr)
            return 2
        report = SLOSet().evaluate(snap["series"], now=snap.get("now"))
    else:
        print("slo: need --demo or --in FILE", file=sys.stderr)
        return 2
    print(json.dumps(report, indent=2, sort_keys=True))
    if args.fail_on and state_of(report["state"]) >= args.fail_on:
        print(f"slo: state {report['state']} >= --fail-on "
              f"{args.fail_on}", file=sys.stderr)
        return 1
    return 0


def _cmd_watch(args):
    """Terminal dashboard: poll a live exporter (``--url``) or render
    one frame from saved snapshot/report files (``--in``)."""
    from .export import render_dashboard

    def frame():
        if args.url:
            from urllib.request import urlopen

            base = args.url.rstrip("/")
            with urlopen(base + "/snapshot") as r:
                snap = json.load(r)
            with urlopen(base + "/slo") as r:
                report = json.load(r)
            return snap, report
        with open(args.infile) as f:
            snap = json.load(f)
        report = None
        if args.slo_in:
            with open(args.slo_in) as f:
                report = json.load(f)
        return snap, report

    if not args.url and not args.infile:
        print("watch: need --url BASE or --in metrics.json",
              file=sys.stderr)
        return 2
    frames = args.frames if args.frames is not None \
        else (0 if args.url else 1)  # 0 == until interrupted
    n = 0
    try:
        while True:
            snap, report = frame()
            if n and args.url:
                sys.stdout.write("\x1b[2J\x1b[H")  # clear between polls
            sys.stdout.write(render_dashboard(snap, report))
            sys.stdout.flush()
            n += 1
            if frames and n >= frames:
                break
            time.sleep(args.interval)
    except KeyboardInterrupt:
        pass
    return 0


_CHECK_RECIPES = ("serving_decode_step", "speculative_verify_step",
                  "serving_frontdoor_step", "serving_prefix_step",
                  "serving_int8_step", "serving_tp_step",
                  "serving_multiquantum_step", "serving_mixed_step")

_REEXEC_GUARD = "_PADDLE_TPU_OBS_REEXEC"


def _ensure_check_devices(argv, need=8):
    """``check`` now audits the tp=2 serving recipe, which needs a
    multi-device mesh; on a 1-device host platform, re-exec with the
    virtual-device flag set before jax initializes (the same conftest
    trick analysis/__main__.py uses). Inert when enough devices are
    already visible."""
    import os

    import jax

    if jax.device_count() >= need or os.environ.get(_REEXEC_GUARD):
        return
    flag = f"--xla_force_host_platform_device_count={need}"
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    env[_REEXEC_GUARD] = "1"
    cmd = [sys.executable, "-m", "paddle_tpu.obs"] + list(
        argv if argv is not None else sys.argv[1:])
    os.execve(sys.executable, cmd, env)


def _check_slo_smoke():
    """The operability-tier smoke `check` appends to the fingerprint
    gate: drive the demo engine with SLOs + a flight recorder whose
    triggers are impossible to satisfy, then assert the burn-rate
    evaluation orders states correctly on BOTH sides of a threshold
    and every forced crossing produced a schema-valid journal."""
    from .flight import FlightRecorder
    from .slo import SLOSet, default_serving_slos

    engine = _demo_engine(
        trace=False, slo=True,
        flight=FlightRecorder(ttft_threshold=1e-9, e2e_threshold=1e-9))
    finished = len(engine.completed)
    lenient = SLOSet(default_serving_slos(
        ttft_p95_s=1e9, inter_token_p99_s=1e9, e2e_p99_s=1e9))
    tight = SLOSet(default_serving_slos(
        ttft_p95_s=1e-9, inter_token_p99_s=1e-9, e2e_p99_s=1e-9))
    ok = lenient.evaluate(engine.obs)["state"]
    crit = tight.evaluate(engine.obs)["state"]
    if ok != "ok":
        raise AssertionError(
            f"lenient SLOs read {ok!r}, expected 'ok'")
    if crit != "critical":
        raise AssertionError(
            f"impossible SLOs read {crit!r}, expected 'critical'")
    records = engine.flight.records()  # schema-validates
    if len(records) != finished:
        raise AssertionError(
            f"{len(records)} anomaly journals for {finished} forced "
            f"threshold crossings")
    report = engine.health()  # stock objectives, real state
    print(f"slo smoke: lenient=ok impossible=critical "
          f"stock={report['state']}, {len(records)} schema-valid "
          f"anomaly journals for {finished} requests")


def _check_frontdoor_smoke():
    """The front-door smoke (ISSUE 7): drive a one-slot engine through
    a FORCED preemption — a BATCH request mid-decode evicted by an
    INTERACTIVE arrival — then assert the overload counters fired
    (preempted/resumed/recomputed + a drain), the resumed stream is
    the right length, the pool fully reclaimed its blocks, and the
    dashboard frame renders the overload line from a live snapshot."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (
        BATCH, INTERACTIVE, FrontDoorPolicy, ServingEngine,
        ServingFrontDoor,
    )
    from .export import render_dashboard

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    engine = ServingEngine(model, num_slots=1, block_size=4,
                           prefill_chunk=4, decode_quantum=2,
                           slo=True, flight=True)
    door = ServingFrontDoor(engine, policy=FrontDoorPolicy())
    rng = np.random.RandomState(0)
    low = door.submit(rng.randint(1, cfg.vocab_size, 5)
                      .astype(np.int32), max_new_tokens=6,
                      priority=BATCH)
    while len(low.request.tokens) < 2:  # batch request mid-decode
        door.pump()
    hi = door.submit(rng.randint(1, cfg.vocab_size, 4)
                     .astype(np.int32), max_new_tokens=4,
                     priority=INTERACTIVE)
    summary = door.drain()  # finish everything, flush the recorder
    reg = engine.obs.registry
    if reg.get("serving_requests_preempted_total").value() < 1 \
            or reg.get("serving_requests_resumed_total").value() < 1:
        raise AssertionError(
            "forced preemption did not fire: "
            f"{summary}")
    if reg.get("serving_tokens_recomputed_total").value() < 1:
        raise AssertionError("preemption recorded no recompute debt")
    if reg.get("serving_drains_total").value() != 1:
        raise AssertionError("drain counter did not fire")
    if len(hi.request.tokens) != 4 or len(low.request.tokens) != 6:
        raise AssertionError(
            f"streams wrong after preempt/resume: hi="
            f"{len(hi.request.tokens)} low={len(low.request.tokens)}")
    if engine.pool.fragmentation_stats()["blocks_in_use"] != 1:
        raise AssertionError("pool leaked blocks across preemption")
    frame = render_dashboard(reg.snapshot(), engine.health())
    if "preempted" not in frame or "shed" not in frame:
        raise AssertionError("dashboard frame missing overload line")
    print(f"front-door smoke: preempted="
          f"{engine.scheduler.preempted_total} resumed="
          f"{engine.scheduler.resumed_total} recomputed="
          f"{int(reg.get('serving_tokens_recomputed_total').value())} "
          f"tokens, drain flushed "
          f"{summary['flight']['captured_total']} journals")


def _check_prefix_smoke():
    """The prefix-cache smoke (ISSUE 9): force a cache hit and a
    copy-on-write on a tiny engine — one request publishes its prompt
    blocks, an identical prompt aliases them (capped one token short,
    so its re-prefill COWs the tail block) — then assert the registry
    counters fired, the streams are bit-identical to an UNSHARED
    engine's, the per-request cached-token count surfaced, pool
    accounting stayed sane (utilization <= 1 with sharing live), and
    the dashboard renders the prefix line."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine
    from .export import render_dashboard
    from .flight import FlightRecorder

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)

    def drive(prefix):
        engine = ServingEngine(model, num_slots=2, block_size=4,
                               prefill_chunk=8, decode_quantum=2,
                               prefix_cache=prefix, slo=True,
                               # impossible thresholds: every journal
                               # captures, so the admit events (with
                               # their cached/novel block counts) stay
                               # inspectable after retirement
                               flight=FlightRecorder(
                                   ttft_threshold=1e-9,
                                   e2e_threshold=1e-9))
        first = engine.submit(prompt.copy(), max_new_tokens=4)
        engine.step()  # prefill + publish before the twin arrives
        second = engine.submit(prompt.copy(), max_new_tokens=4)
        engine.run()
        return engine, first, second

    plain, p1, p2 = drive(False)
    cached, c1, c2 = drive(True)
    if (p1.tokens, p2.tokens) != (c1.tokens, c2.tokens):
        raise AssertionError(
            f"prefix-cached streams diverged: {c1.tokens}/{c2.tokens} "
            f"vs unshared {p1.tokens}/{p2.tokens}")
    if c2.cached_prefix_tokens != 8:
        raise AssertionError(
            f"twin aliased {c2.cached_prefix_tokens} tokens, "
            f"expected its full 8-token prompt")
    pool = cached.pool
    if pool.prefix_hits < 2 or pool.cow_copies < 1:
        raise AssertionError(
            f"forced hit/COW did not fire: hits={pool.prefix_hits} "
            f"cow={pool.cow_copies}")
    reg = cached.obs.registry
    for m in ("serving_prefix_cache_hits_total",
              "serving_prefix_cache_cow_copies_total",
              "serving_prefix_cache_shared_blocks_total"):
        if reg.get(m).value(pool="target") < 1:
            raise AssertionError(f"registry counter {m} never fired")
    st = pool.fragmentation_stats()
    if st["utilization"] > 1.0:
        raise AssertionError(
            f"refcount-aware utilization broke: {st}")
    frame = render_dashboard(reg.snapshot())
    if "prefix[" not in frame:
        raise AssertionError("dashboard frame missing prefix line")
    admits = [e for j in cached.flight.records()
              for e in j["events"] if e["kind"] == "admit"]
    if not any(e.get("cached_blocks") for e in admits):
        raise AssertionError(
            "flight admit events carry no cached-block counts")
    print(f"prefix smoke: hits={pool.prefix_hits} "
          f"misses={pool.prefix_misses} cow={pool.cow_copies} "
          f"cached_blocks={pool.cached_blocks}, streams bit-identical "
          f"to the unshared engine")


def _check_int8_smoke():
    """The quantized-serving smoke (ISSUE 14): force a prefix-cache
    hit and a copy-on-write on an int8 engine (weight-only int8 +
    int8 KV with per-row scale pools) and assert sharing composes
    with quantization — the shared streams stay bit-identical to an
    UNSHARED int8 engine's, the hit/COW counters fire on the
    quantized pool, and the dtype-aware ``serving_pool_bytes`` gauge
    shows the int8 pool pinning well under half the bytes of a float
    engine holding the same blocks."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = LlamaConfig.tiny(tensor_parallel=False)
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)

    def drive(prefix, quant):
        # a fresh model per engine: the quantize sweep rewrites the
        # Linear layers in place, so engines must not share one
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        kw = (dict(quantize="weight_only_int8", kv_dtype="int8")
              if quant else {})
        engine = ServingEngine(model, num_slots=2, block_size=4,
                               prefill_chunk=8, decode_quantum=2,
                               prefix_cache=prefix, **kw)
        first = engine.submit(prompt.copy(), max_new_tokens=4)
        engine.step()  # prefill + publish before the twin arrives
        mid_bytes = engine.pool.bytes_in_use()
        second = engine.submit(prompt.copy(), max_new_tokens=4)
        engine.run()
        return engine, first, second, mid_bytes

    shared, s1, s2, q_bytes = drive(True, True)
    plain, p1, p2, _ = drive(False, True)
    flt, _, _, f_bytes = drive(True, False)
    if (s1.tokens, s2.tokens) != (p1.tokens, p2.tokens):
        raise AssertionError(
            f"int8 prefix-shared streams diverged from the unshared "
            f"int8 engine: {s1.tokens}/{s2.tokens} vs "
            f"{p1.tokens}/{p2.tokens}")
    pool = shared.pool
    if not pool.quantized:
        raise AssertionError("kv_dtype='int8' engine built a float "
                             "pool")
    if pool.prefix_hits < 2 or pool.cow_copies < 1:
        raise AssertionError(
            f"forced hit/COW did not fire on the int8 pool: "
            f"hits={pool.prefix_hits} cow={pool.cow_copies}")
    if not q_bytes or q_bytes > 0.5 * f_bytes:
        raise AssertionError(
            f"int8 pool residency win missing: {q_bytes} B vs float "
            f"{f_bytes} B for the same allocated blocks")
    g = shared.obs.registry.get("serving_pool_bytes")
    if g.value(pool="target", kv_dtype="int8") <= 0:
        raise AssertionError(
            "serving_pool_bytes{kv_dtype=int8} gauge never fired "
            "(the prefix index holds cached blocks, so the final "
            "step's residency must be non-zero)")
    print(f"int8 smoke: hits={pool.prefix_hits} "
          f"cow={pool.cow_copies}, shared streams bit-identical to "
          f"the unshared int8 engine, pool bytes {q_bytes} vs float "
          f"{f_bytes} ({f_bytes / q_bytes:.2f}x residency win)")


def _check_attribution_smoke():
    """The cost-ledger smoke (ISSUE 10): drive the demo engine through
    its speculative arm and assert the ledger is CONSERVATIVE — every
    emitted token lands in exactly one phase bucket (ledger totals ==
    the legacy registry counters token-for-token), prefill work
    decomposes into novel + recompute, spec-verify waste equals
    proposed − accepted, and the per-phase wall seconds sum back to
    the measured quantum walls within float tolerance."""
    engine = _demo_engine(spec=True)
    reg = engine.obs.registry
    ledger = engine.obs.ledger

    emitted = ledger.emitted_tokens()
    total_emitted = reg.get("serving_tokens_emitted_total").value()
    if sum(emitted.values()) != total_emitted:
        raise AssertionError(
            f"ledger lost tokens: phase buckets {emitted} sum to "
            f"{sum(emitted.values())}, engine emitted {total_emitted}")
    work = ledger.prefill_work()
    prefill_total = reg.get("serving_prefill_tokens_total").value()
    if work["novel"] + work["recompute"] != prefill_total:
        raise AssertionError(
            f"prefill work {work} does not decompose the legacy "
            f"counter {prefill_total}")
    proposed = reg.get("serving_spec_proposed_total").value()
    accepted = reg.get("serving_spec_accepted_total").value()
    if proposed <= 0 or emitted["spec_verify"] <= 0:
        raise AssertionError(
            f"spec arm never exercised: proposed={proposed} "
            f"spec_verify emitted={emitted['spec_verify']}")
    rejected = ledger.waste_tokens()["spec_rejected"]
    if rejected != proposed - accepted:
        raise AssertionError(
            f"spec waste drifted: ledger rejected={rejected}, "
            f"engine proposed-accepted={proposed - accepted}")
    hist = reg.get("serving_quantum_seconds")
    wall = sum(hist.sum(kind=k) for k in ("mixed", "decode",
                                          "spec_round"))
    attributed = sum(ledger.phase_seconds().values())
    if abs(attributed - wall) > 1e-6 * max(1.0, wall):
        raise AssertionError(
            f"phase seconds {attributed:.9f} do not sum to measured "
            f"quantum wall {wall:.9f}")
    rep = engine.attribution()
    if not 0.0 < rep["useful_token_fraction"] <= 1.0:
        raise AssertionError(
            f"useful-token fraction out of range: {rep}")
    if rep["mfu"]["flops_per_token"] <= 0:
        raise AssertionError(
            f"ledger never configured with model FLOPs: {rep['mfu']}")
    print(f"attribution smoke: {int(total_emitted)} tokens conserved "
          f"across {emitted}, useful="
          f"{rep['useful_token_fraction']:.3f}, "
          f"{attributed:.3f}s attributed == quantum wall")


def _check_resilience_smoke():
    """The chaos-soak smoke (ISSUE 13): a bounded seeded run of the
    two-arm resilience soak — same workload fault-free and under an
    armed injector + seeded preemptions — asserting faults actually
    fired and every non-poisoned stream stayed bit-exact. run_soak
    hard-asserts drain, definite finish reasons and zero leaked blocks
    internally; replay any failure from the printed seed alone."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from ..serving.soak import run_soak

    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    model.eval()
    # 6 rounds keep the smoke short (~30 s on the CPU when the mixed
    # step was eager) while still landing a couple of injected faults
    rep = run_soak(model, rounds=6, seed=2)
    if rep["faults_injected"] < 1:
        raise AssertionError(
            f"soak injected no faults — plan/seed drifted: {rep}")
    if rep["requests"] < 1:
        raise AssertionError(f"soak submitted nothing: {rep}")
    expect_exact = rep["requests"] - len(rep["poisoned"])
    if rep["bitexact_streams"] != expect_exact:
        raise AssertionError(
            f"soak lost streams: {rep['bitexact_streams']} bit-exact "
            f"of {expect_exact} non-poisoned")
    print(f"resilience smoke: seed={rep['seed']} "
          f"rounds={rep['rounds']} requests={rep['requests']} "
          f"faults={rep['faults_injected']} "
          f"retries={rep['retries']} skips={rep['step_skips']}, "
          f"{rep['bitexact_streams']} non-poisoned streams bit-exact, "
          f"pools drained clean")


def _check_cluster_smoke():
    """The cluster smoke (ISSUE 15): route a shared-prefix trace
    through a 2-replica ClusterFrontDoor — the twin prompts must
    re-land on their prefix owner (affinity hits > 0), the router
    counters must fire, the streams must be bit-identical to a
    cluster-of-1 run of the same trace, and the merged ClusterExporter
    snapshot must render the dashboard's cluster line."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (
        ClusterFrontDoor, ClusterReplica, ClusterRouter, ServingEngine,
        no_shed_policy,
    )
    from .export import ClusterExporter, render_dashboard

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    shared = rng.randint(1, cfg.vocab_size, 8).tolist()
    prompts = [shared + rng.randint(1, cfg.vocab_size,
                                    2 + i).tolist()
               for i in range(4)]

    def drive(n_replicas):
        reps = [ClusterReplica(
                    f"r{i}",
                    ServingEngine(model, num_slots=2, block_size=4,
                                  prefix_cache=True),
                    policy=no_shed_policy())
                for i in range(n_replicas)]
        cfd = ClusterFrontDoor(ClusterRouter(reps, affinity_blocks=2))
        streams = [cfd.submit(p, max_new_tokens=2, seed=0)
                   for p in prompts]
        cfd.run_until_idle()
        return cfd, [list(s.result()) for s in streams]

    cfd2, out2 = drive(2)
    cfd1, out1 = drive(1)
    if out2 != out1:
        raise AssertionError(
            f"cluster-of-2 streams diverged from cluster-of-1: "
            f"{out2} vs {out1}")
    st = cfd2.router.affinity_stats()
    if st["keyed_requests"] != len(prompts) or st["affinity_hits"] < 1:
        raise AssertionError(
            f"shared prefixes never re-landed on their owner: {st}")
    reqs = cfd2.router._c_requests
    routed = int(sum(reqs.value(replica=r.name, reason=reason)
                     for r in cfd2.router.replicas
                     for reason in ("affinity", "balance", "failover")))
    if routed != len(prompts):
        raise AssertionError(
            f"router accounted {routed} placements for "
            f"{len(prompts)} requests")
    exp = ClusterExporter.for_cluster(cfd2)
    frame = render_dashboard(exp.registry.snapshot())
    if " cluster " not in frame:
        raise AssertionError("dashboard frame missing cluster line")
    print(f"cluster smoke: routed={routed} "
          f"affinity_hits={st['affinity_hits']} "
          f"hit_rate={st['hit_rate']:.2f}, 2-replica streams "
          f"bit-identical to cluster-of-1, merged dashboard ok")


def _cmd_check(args):
    """Instrumented-fingerprint gate: the serving recipes construct
    their engines with full observability ON (analysis/recipes.py);
    budgets + goldens must hold anyway, and the instrumentation must
    have actually observed the prefill step it rode along with."""
    from paddle_tpu import analysis
    from .trace import validate_chrome_trace

    failed = False
    for name in (args.recipe or _CHECK_RECIPES):
        recipe = analysis.build_recipe(name)
        try:
            report = recipe.check()  # budget (incl. 0 host callbacks)
            analysis.check_recipe_fingerprint(name, report)
            engine = getattr(recipe, "engine", None)
            if engine is None:
                raise AssertionError(
                    f"{name}: recipe carries no engine handle")
            if engine.obs.tracer is None:
                raise AssertionError(
                    f"{name}: engine built without tracing — the gate "
                    f"must audit the INSTRUMENTED engine")
            if engine.stats["steps"] < 1 \
                    or engine.obs.registry.get(
                        "serving_requests_admitted_total").value() < 1:
                raise AssertionError(
                    f"{name}: instrumentation recorded nothing")
            validate_chrome_trace(engine.obs.tracer.chrome_trace())
            print(f"{name}: budget ok, fingerprint ok, "
                  f"{len(engine.obs.tracer.events)} trace events, "
                  f"{report.host_sync.count} host callbacks")
        except (analysis.BudgetViolation, analysis.FingerprintMismatch,
                AssertionError, ValueError) as e:
            failed = True
            print(f"{name}: FAIL — {e}", file=sys.stderr)
        finally:
            recipe.close()
    try:
        _check_slo_smoke()
    except (AssertionError, ValueError) as e:
        failed = True
        print(f"slo smoke: FAIL — {e}", file=sys.stderr)
    try:
        _check_frontdoor_smoke()
    except (AssertionError, ValueError) as e:
        failed = True
        print(f"front-door smoke: FAIL — {e}", file=sys.stderr)
    try:
        _check_prefix_smoke()
    except (AssertionError, ValueError) as e:
        failed = True
        print(f"prefix smoke: FAIL — {e}", file=sys.stderr)
    try:
        _check_int8_smoke()
    except (AssertionError, ValueError) as e:
        failed = True
        print(f"int8 smoke: FAIL — {e}", file=sys.stderr)
    try:
        _check_attribution_smoke()
    except (AssertionError, ValueError, KeyError) as e:
        failed = True
        print(f"attribution smoke: FAIL — {e}", file=sys.stderr)
    try:
        _check_resilience_smoke()
    except (AssertionError, ValueError, RuntimeError) as e:
        failed = True
        print(f"resilience smoke: FAIL — {e}", file=sys.stderr)
    try:
        _check_cluster_smoke()
    except (AssertionError, ValueError) as e:
        failed = True
        print(f"cluster smoke: FAIL — {e}", file=sys.stderr)
    if failed:
        return 1
    print("obs check: instrumentation-enabled fingerprints unchanged")
    return 0


def _cmd_profile(args):
    from paddle_tpu.profiler import load_profiler_result

    axes = None
    if args.mesh_axes:
        with open(args.mesh_axes) as f:
            axes = json.load(f)
    result = load_profiler_result(args.infile, mesh_axes=axes)
    if args.format == "json":
        print(json.dumps(result.to_dict(), sort_keys=True))
    else:
        print(result.tables())
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.obs",
        description="runtime observability CLI (see module docstring)")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("snapshot", help="render a metrics snapshot")
    p.add_argument("--in", dest="infile", default=None,
                   help="saved snapshot JSON to re-render")
    p.add_argument("--demo", action="store_true",
                   help="drive a tiny CPU serving engine instead")
    p.add_argument("--spec", action="store_true",
                   help="demo uses the speculative arm")
    p.add_argument("--format", choices=("prom", "json"),
                   default="prom")
    p.add_argument("--out", default=None)
    p.set_defaults(fn=_cmd_snapshot)

    p = sub.add_parser("export",
                       help="write/validate a Chrome trace JSON")
    p.add_argument("--in", dest="infile", default=None,
                   help="existing trace to validate")
    p.add_argument("--demo", action="store_true")
    p.add_argument("--spec", action="store_true")
    p.add_argument("--out", default=None)
    p.add_argument("--metrics-out", default=None,
                   help="also dump the demo registry snapshot here")
    p.set_defaults(fn=_cmd_export)

    p = sub.add_parser("serve",
                       help="live HTTP exporter over the demo engine")
    p.add_argument("--demo", action="store_true", default=True,
                   help="(implied) drive the demo engine")
    p.add_argument("--spec", action="store_true")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=9100)
    p.add_argument("--duration", type=float, default=None,
                   help="serve for N seconds then exit "
                        "(default: until Ctrl-C)")
    p.set_defaults(fn=_cmd_serve)

    p = sub.add_parser("slo",
                       help="evaluate the burn-rate health report")
    p.add_argument("--demo", action="store_true")
    p.add_argument("--spec", action="store_true")
    p.add_argument("--in", dest="infile", default=None,
                   help="saved series snapshot (slo --demo --out)")
    p.add_argument("--out", default=None,
                   help="with --demo: also dump the series snapshot")
    p.add_argument("--fail-on", choices=("warn", "critical"),
                   default=None,
                   help="exit 1 when the state reaches this level")
    p.set_defaults(fn=_cmd_slo)

    p = sub.add_parser("watch", help="terminal health dashboard")
    p.add_argument("--url", default=None,
                   help="base URL of a running exporter (serve)")
    p.add_argument("--in", dest="infile", default=None,
                   help="saved registry snapshot JSON")
    p.add_argument("--slo-in", dest="slo_in", default=None,
                   help="saved /slo report JSON (with --in)")
    p.add_argument("--interval", type=float, default=1.0)
    p.add_argument("--frames", type=int, default=None,
                   help="stop after N frames (default: loop on --url, "
                        "1 on --in)")
    p.set_defaults(fn=_cmd_watch)

    p = sub.add_parser("check",
                       help="instrumented-fingerprint CI gate "
                            "+ SLO/flight smoke")
    p.add_argument("--recipe", action="append", default=None,
                   choices=_CHECK_RECIPES)
    p.set_defaults(fn=_cmd_check)

    p = sub.add_parser("profile",
                       help="tables of a saved device trace")
    p.add_argument("--in", dest="infile", required=True,
                   help="profiler log directory or .xplane.pb")
    p.add_argument("--mesh-axes", default=None,
                   help="JSON file {axis: groups of partition ids}")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.set_defaults(fn=_cmd_profile)

    args = ap.parse_args(argv)
    if args.cmd == "check":
        _ensure_check_devices(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
