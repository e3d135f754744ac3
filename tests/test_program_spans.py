"""The program's host spans (ISSUE 26): ``profiler.RecordEvent`` in the
serving pump and the train dispatch, one recorder, compile events charged
to the step that caused them.

A toy engine behind a front door and a toy train step, on the CPU. What
is held here: the rows nest as ``PERF.md`` section 3 says, children never
sum past their parent, there is one step row per step made (two halves
for a step that is dispatched and collected; in steady decode ``step()``
dispatches the next quantum before it collects the last), a profiler session sees
the same spans on its own clock, JAX's compile events land on the span
its caller marked as the step, the buffer is bounded, threads keep their
own stacks, no name can be mistaken for one of the benchmark's own rows,
and the spans change nothing of what the engine does.
"""
import glob
import os
import re
import statistics
import threading
import time

import numpy as np
import pytest

import jax
import paddle_tpu as paddle
from paddle_tpu.obs import MetricsRegistry, TraceRecorder
from paddle_tpu.profiler import RecordEvent, count_compile_events

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the whole vocabulary (PERF.md section 3 has the same table)
VOCABULARY = [
    "door.pump", "engine.step", "engine.admit", "request.queued",
    "engine.mixed", "engine.mixed.prepare", "engine.mixed.forward",
    "engine.mixed.select", "engine.mixed.emit",
    "engine.decode", "engine.decode.prepare", "engine.decode.args",
    "engine.decode.enqueue", "engine.decode.sync", "engine.decode.emit",
    "engine.spec_round",
    "train.run_steps", "train.args", "train.enqueue",
]
# benchmark/harness/xplane.reduce_trace pairs host events to the
# benchmark's rows by these base names
BENCHMARK_BASES = ("window", "batch", "submit", "pump", "dispatch", "wait")
PARENTS = {
    "door.pump": {None},
    "engine.step": {"door.pump"},
    "engine.admit": {"engine.step"},
    "request.queued": {None},
    "engine.mixed": {"engine.step"},
    "engine.mixed.prepare": {"engine.mixed"},
    "engine.mixed.forward": {"engine.mixed"},
    "engine.mixed.select": {"engine.mixed"},
    "engine.mixed.emit": {"engine.mixed"},
    "engine.decode": {"engine.step"},
    "engine.decode.prepare": {"engine.decode"},
    "engine.decode.args": {"engine.decode.enqueue"},
    "engine.decode.enqueue": {"engine.decode"},
    "engine.decode.sync": {"engine.decode"},
    "engine.decode.emit": {"engine.decode"},
    "train.run_steps": {None},
    "train.args": {"train.run_steps"},
    "train.enqueue": {"train.run_steps"},
}

PROMPT_LENS, MAX_NEW = (5, 9, 3, 7, 4), (4, 3, 6, 2, 5)
# what the parent commit of ISSUE 26 gives for the run below (CPU, f32,
# paddle.seed(0)): the spans must change none of it
PARENT_TOKENS = [[81, 73, 112, 112], [32, 21, 34],
                 [37, 109, 117, 99, 121, 34], [13, 102],
                 [109, 21, 94, 109, 117]]
PARENT_STATS = {"steps": 10, "mixed_steps": 6, "decode_quanta": 4,
                "quantum_tokens": 24, "prefill_tokens": 28,
                "generated_tokens": 20, "occupancy_sum": 9.0,
                "spec_rounds": 0, "spec_proposed": 0, "spec_accepted": 0,
                # ISSUE 48: how often step() ran a quantum ahead; every
                # other count is the serial pump's
                "quanta_ahead": 1}


def rows_since(mark):
    """The process recorder's span rows with an id above ``mark``."""
    return [e for e in TraceRecorder.process().spans()
            if e["args"]["id"] > mark]


def mark():
    return TraceRecorder.process().next_id()


def build_door(**kw):
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import no_shed_policy

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    model.eval()
    engine_kw = dict(num_slots=2, block_size=4, prefill_chunk=4,
                     decode_quantum=3)
    engine_kw.update(kw)
    return cfg, paddle.inference.serve(model, policy=no_shed_policy(),
                                       **engine_kw)


def submit_ragged(cfg, door):
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    return [door.submit(p, max_new_tokens=mn)
            for p, mn in zip(prompts, MAX_NEW)]


def build_train_step():
    from paddle_tpu.jit.train import JittedTrainStep

    paddle.seed(0)
    model = paddle.nn.Linear(8, 8)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    def crit(out, label):
        d = out - label
        return (d * d).mean()

    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(1, 2, 8).astype("f4"))
    return JittedTrainStep(model, crit, opt), x


@pytest.fixture(scope="module")
def run():
    """One ragged run through the front door and three train dispatches;
    everything the recorder took meanwhile."""
    cfg, door = build_door()
    m0 = mark()
    streams = submit_ragged(cfg, door)
    door.run_until_idle()
    step, x = build_train_step()
    for _ in range(3):
        step.run_steps(x, x)
    return {"door": door, "streams": streams, "rows": rows_since(m0),
            "train_calls": 3}


# ------------------------------------------------------------ the rows
def test_rows_nest_as_the_table_says(run):
    by_id = {r["args"]["id"]: r for r in run["rows"]}
    seen = set()
    for r in run["rows"]:
        parent = by_id.get(r["args"]["parent"])
        pname = None if parent is None else parent["name"]
        assert pname in PARENTS[r["name"]], (r["name"], pname)
        seen.add(r["name"])
    # every engine.mixed.* row hangs under an engine.mixed under an
    # engine.step under a door.pump
    for r in run["rows"]:
        if r["name"].startswith("engine.mixed."):
            up = r
            while up["name"] != "engine.mixed":
                up = by_id[up["args"]["parent"]]
            step = by_id[up["args"]["parent"]]
            assert step["name"] == "engine.step"
            assert by_id[step["args"]["parent"]]["name"] == "door.pump"
    # the run met every name but the speculative round's
    assert seen == set(VOCABULARY) - {"engine.spec_round"}


def test_children_never_sum_past_their_parent(run):
    kids = {}
    for r in run["rows"]:
        kids.setdefault(r["args"]["parent"], []).append(r)
    checked = 0
    for r in run["rows"]:
        mine = kids.get(r["args"]["id"], [])
        if not mine:
            continue
        assert sum(k["dur"] for k in mine) <= r["dur"] + 1e-3, r["name"]
        for k in mine:  # and each lies inside it
            assert k["ts"] >= r["ts"] - 1e-3
            assert k["ts"] + k["dur"] <= r["ts"] + r["dur"] + 1e-3
        checked += 1
    assert checked > 20


def halves(rows, name):
    return [r["args"].get("half") for r in rows if r["name"] == name]


def test_one_step_row_per_step_made(run):
    names = [r["name"] for r in run["rows"]]
    stats = run["door"].engine.stats
    assert names.count("engine.mixed") == stats["mixed_steps"]
    # a quantum is dispatched, then collected: a row for each half; in
    # steady decode the next is dispatched before the last is collected,
    # never more than that one
    got = halves(run["rows"], "engine.decode")
    assert got.count("dispatch") == got.count("collect") \
        == stats["decode_quanta"]
    in_flight = [got[:i + 1].count("dispatch") - got[:i + 1].count("collect")
                 for i in range(len(got))]
    assert set(in_flight) == {0, 1, 2} and in_flight[-1] == 0
    assert sum(bool(r["args"].get("ahead")) for r in run["rows"]
               if r["name"] == "engine.decode") == stats["quanta_ahead"]
    # a step whose dispatch half left nothing pending has no other half
    assert halves(run["rows"], "engine.step").count("dispatch") \
        == stats["steps"]
    assert halves(run["rows"], "engine.step").count("collect") \
        == stats["decode_quanta"]
    assert halves(run["rows"], "door.pump") == [None] * stats["steps"]
    assert names.count("train.run_steps") == run["train_calls"]
    assert names.count("request.queued") == len(run["streams"])
    # one program a step: one forward span (no draft here), no layers
    assert names.count("engine.mixed.forward") == stats["mixed_steps"]
    mixed = [r for r in run["rows"] if r["name"] == "engine.mixed"]
    assert all(r["args"]["rows"] >= 1 and "prefill_tokens" in r["args"]
               and "step" in r["args"] for r in mixed)
    assert sum(r["args"]["prefill_tokens"] for r in mixed) \
        == stats["prefill_tokens"]
    # the row says which program ran and what it padded: 2 slots of
    # `bucket` positions, less the tokens the step's rows brought
    reg = run["door"].engine.obs.registry
    assert {r["args"]["bucket"] for r in mixed} <= {1, 2, 4}
    assert all(0 <= r["args"]["padded_tokens"] < 2 * r["args"]["bucket"]
               for r in mixed)
    assert sum(r["args"]["padded_tokens"] for r in mixed) \
        == reg.get("serving_mixed_padded_tokens_total").value()


def test_admission_is_a_span_of_the_dispatch_half(run):
    """ISSUE 37: ``_admit`` is spanned every step, under the dispatch
    half of ``engine.step`` and before the step's own span, so its time
    (a device round trip a request for its key) is no longer the step's
    unread self time."""
    by_id = {r["args"]["id"]: r for r in run["rows"]}
    admits = [r for r in run["rows"] if r["name"] == "engine.admit"]
    assert len(admits) == run["door"].engine.stats["steps"]
    for r in admits:
        step = by_id[r["args"]["parent"]]
        assert step["name"] == "engine.step"
        assert step["args"]["half"] == "dispatch"
        after = [k for k in run["rows"]
                 if k["args"]["parent"] == step["args"]["id"]
                 and k["name"] in ("engine.mixed", "engine.decode")]
        assert all(r["ts"] + r["dur"] <= k["ts"] + 1e-3 for k in after)


def test_step_rows_carry_their_cpu_seconds(run):
    """The three step-level rows, and only they, carry ``cpu_s``: the
    thread's CPU seconds over the span, never more than its duration
    (plus the clocks' grain). A freeze then reads off-CPU or on-CPU from
    the slowest row alone."""
    with_cpu = {r["name"] for r in run["rows"] if "cpu_s" in r["args"]}
    assert with_cpu == {"door.pump", "engine.step", "train.run_steps"}
    for r in run["rows"]:
        if r["name"] in with_cpu:
            assert 0.0 <= r["args"]["cpu_s"] <= r["dur"] * 1e-6 + 2e-3
    # a span that sleeps is off the CPU; one that spins is on it
    m0 = mark()
    with RecordEvent("engine.step"):
        time.sleep(0.05)
    with RecordEvent("engine.step"):
        # until the THREAD has had 30 ms of CPU (not 50 ms of wall: under
        # six loaded xdist workers a spin of 50 ms was given 13)
        c0, t0 = time.thread_time(), time.perf_counter()
        while time.thread_time() - c0 < 0.03 \
                and time.perf_counter() - t0 < 5.0:
            pass
    asleep, spinning = rows_since(m0)
    assert asleep["args"]["cpu_s"] < 0.02 < spinning["args"]["cpu_s"]


def test_mixed_host_ms_is_the_pump_less_the_forward(run, monkeypatch):
    """The benchmark's ``mixed_host_ms`` (ISSUE 37) on this run's rows:
    the median over the mixed steps of the ``door.pump`` row that ran the
    step less its ``engine.mixed.forward``, admission inside it; silent
    on a program without the rows."""
    monkeypatch.syspath_prepend(ROOT)
    from benchmark.harness import program_spans
    from benchmark.metrics import mixed_host_ms

    rows = program_spans.from_events(run["rows"])
    by_id = {r["id"]: r for r in rows}
    want = []
    for fwd in rows:
        if fwd["name"] != "engine.mixed.forward":
            continue
        step = by_id[by_id[fwd["parent"]]["parent"]]
        pump = by_id[step["parent"]]
        assert pump["name"] == "door.pump"
        admit = [r for r in rows if r["name"] == "engine.admit"
                 and r["parent"] == step["id"]]
        assert len(admit) == 1
        assert pump["seconds"] - fwd["seconds"] >= admit[0]["seconds"]
        want.append(1e3 * (pump["seconds"] - fwd["seconds"]))
    stats = run["door"].engine.stats
    assert len(want) == stats["mixed_steps"]
    obs = {"engine_steps": {"mixed_steps": stats["mixed_steps"],
                            "decode_quanta": stats["decode_quanta"]}}
    monkeypatch.setattr(program_spans, "rows", lambda: rows)
    assert mixed_host_ms.read(obs) == pytest.approx(
        statistics.median(want), abs=1e-9)
    monkeypatch.setattr(program_spans, "rows", lambda: [])
    assert mixed_host_ms.read(obs) is None


def decode_quanta(rows):
    """A run's ``engine.decode`` rows as (dispatch half, collect half)
    pairs: the halves of one quantum carry one ``step``."""
    decode = [r for r in rows if r["name"] == "engine.decode"]
    by_step = {}
    for r in decode:
        by_step.setdefault(r["args"]["step"], {})[r["args"]["half"]] = r
    assert all(set(h) == {"dispatch", "collect"} for h in by_step.values())
    assert len(decode) == 2 * len(by_step)
    return [(h["dispatch"], h["collect"])
            for _, h in sorted(by_step.items())]


def test_the_halves_of_a_quantum_pair_by_their_step(run):
    """A quantum's two halves carry its step's number, the dispatch half
    ends before the collect half begins, and the device's wait (the sync
    span) is in the second. ``step()`` runs the halves ONE QUANTUM APART
    in steady decode: a quantum marked ``ahead=1`` was dispatched under
    the pump before the one that collects it, before the quantum ahead of
    it was collected (D, D, C, D, C, ..., C); every other quantum's halves
    lie under one pump, as the halves driven apart always do
    (``test_halves_are_rows_of_their_own``)."""
    by_id = {r["args"]["id"]: r for r in run["rows"]}
    pump_of = lambda r: by_id[by_id[r["args"]["parent"]]["args"]["parent"]]
    end = lambda r: r["ts"] + r["dur"]
    quanta = decode_quanta(run["rows"])
    assert quanta
    ahead = 0
    for i, (first, second) in enumerate(quanta):
        assert first["args"]["rows"] >= 1 and first["args"]["k"] == 1
        assert end(first) <= second["ts"] + 1e-3
        pumps = [pump_of(first), pump_of(second)]
        assert all(p["name"] == "door.pump" for p in pumps)
        if first["args"].get("ahead"):
            ahead += 1
            before = quanta[i - 1]
            # behind the quantum before it: after that one's dispatch,
            # before its collect, under its collect's pump
            assert end(before[0]) <= first["ts"] + 1e-3
            assert end(first) <= before[1]["ts"] + 1e-3
            assert pumps[0] is pump_of(before[1])
            assert pumps[0] is not pumps[1]
        else:
            assert pumps[0] is pumps[1]
    assert ahead == run["door"].engine.stats["quanta_ahead"] == 1
    # collects come in the order of their dispatches
    assert [end(c) for _, c in quanta] == sorted(end(c) for _, c in quanta)


def test_the_benchmarks_readers_read_a_pipelined_run(monkeypatch):
    """ISSUE 48: a closed batch in steady decode (nothing waits: every
    quantum but the first is dispatched ahead) through the benchmark's
    own ``window_steps`` and the readers of its ``engine.decode`` rows. A
    collect half joins the dispatch half BEFORE it, so a step is (the
    next quantum's dispatch, this one's collect) and the batch's last
    collect is left out: each reader still returns a number."""
    monkeypatch.syspath_prepend(ROOT)
    from benchmark.harness import program_spans
    from benchmark.metrics import (compiles_in_decode, quanta_ahead_pct,
                                   quantum_args_ms, quantum_host_ms)

    cfg, door = build_door()
    rng = np.random.RandomState(5)

    def batch():
        streams = [door.submit(rng.randint(1, cfg.vocab_size, 6)
                               .astype(np.int32), max_new_tokens=32)
                   for _ in range(2)]
        door.run_until_idle()
        return streams

    batch()                                       # warm: compiles
    m0 = mark()
    stats0 = dict(door.engine.stats)
    assert all(len(s.request.tokens) == 32 for s in batch())
    stats = {k: v - stats0[k] for k, v in door.engine.stats.items()}
    rows = program_spans.from_events(rows_since(m0))
    obs = {"engine_steps": {"mixed_steps": stats["mixed_steps"],
                            "decode_quanta": stats["decode_quanta"]}}
    _, steps = program_spans.window_steps(obs, rows)
    # 31 tokens after the prefill's, 3 a quantum: 11 quanta, 10 ahead
    assert stats["decode_quanta"] == 11 and stats["quanta_ahead"] == 10
    assert len(steps["decode"]) == 11
    assert [len(s) for s in steps["decode"]] == [1] + [2] * 10
    assert all(s[0]["args"]["half"] == "dispatch" for s in steps["decode"])
    monkeypatch.setattr(program_spans, "rows", lambda: rows)
    assert quantum_host_ms.read(obs) > 0
    assert quantum_args_ms.read(obs) > 0
    assert compiles_in_decode.read(obs) == 0
    assert quanta_ahead_pct.read(obs) == pytest.approx(100 * 10 / 11)
    assert quanta_ahead_pct.read(obs) >= 80
    # a program without the mark (the parent) reads 0, a window without
    # decode steps nothing
    for r in rows:
        r["args"].pop("ahead", None)
    assert quanta_ahead_pct.read(obs) == 0.0
    monkeypatch.setattr(program_spans, "rows", lambda: [])
    assert quanta_ahead_pct.read(obs) is None


def test_queue_wait_is_the_histogram_sample(run):
    """``request.queued`` is built from the two stamps ``on_admit``
    already has: its durations are the queue-wait histogram's samples."""
    queued = [r for r in run["rows"] if r["name"] == "request.queued"]
    h = run["door"].engine.obs.registry.get("serving_queue_wait_seconds")
    assert sum(r["dur"] for r in queued) * 1e-6 == pytest.approx(h.sum())
    assert {r["args"]["req_id"] for r in queued} \
        == {str(s.request.req_id) for s in run["streams"]}


def test_spans_change_no_behaviour(run):
    """Greedy streams and ``engine.stats`` of the fixed-seed run are the
    parent commit's."""
    assert [[int(t) for t in s.request.tokens] for s in run["streams"]] \
        == PARENT_TOKENS
    assert dict(run["door"].engine.stats) == PARENT_STATS


def test_sync_span_feeds_the_host_gap_gauge(run):
    """``device_s`` is the enqueue span's end to the sync span's end: no
    second pair of stamps, and the gauge stays a fraction. A quantum that
    was dispatched ahead starts, for the gauge as for every seam
    downstream, where the quantum before it ended (its sync span's end):
    the device went from one into the other, the gap reads 0."""
    gap = run["door"].engine.obs.registry.get(
        "serving_host_gap_fraction").value()
    assert 0.0 <= gap < 1.0
    by_id = {r["args"]["id"]: r for r in run["rows"]}

    def kid(of, name):
        return next(r for r in run["rows"] if r["name"] == name
                    and r["args"]["parent"] == of["args"]["id"])

    def sync_end(collect):
        sync = kid(collect, "engine.decode.sync")
        return sync["ts"] + sync["dur"]

    quanta = decode_quanta(run["rows"])
    (dispatch, collect), before = quanta[-1], quanta[-2]
    assert dispatch["args"].get("ahead") == 1   # the run's last quantum
    enqueue = kid(dispatch, "engine.decode.enqueue")
    floor = sync_end(before[1])
    assert dispatch["ts"] < floor
    wall = sync_end(collect) - max(dispatch["ts"], floor)
    device_us = sync_end(collect) - max(enqueue["ts"] + enqueue["dur"],
                                        floor)
    assert gap == pytest.approx(max(wall - device_us, 0.0) / wall,
                                rel=1e-6, abs=1e-9) == 0.0
    assert by_id[dispatch["args"]["parent"]]["name"] == "engine.step"


# ---------------------------------------------- driven apart: two halves
def test_halves_are_rows_of_their_own():
    """``pump_dispatch`` / ``pump_collect`` (the cluster's pump) leave no
    span open between the halves: each half is a row with ``half=``."""
    cfg, door = build_door()
    m0 = mark()
    streams = submit_ragged(cfg, door)
    while door.engine.has_work:
        pending = door.pump_dispatch()
        # nothing of this thread stays open across another engine's work
        with RecordEvent("test.between") as between:
            pass
        assert between.parent is None
        door.pump_collect(pending)
    assert [[int(t) for t in s.request.tokens] for s in streams] \
        == PARENT_TOKENS
    rows = rows_since(m0)
    stats = door.engine.stats
    for name in ("door.pump", "engine.step", "engine.decode"):
        got = halves(rows, name)
        assert None not in got, name
        assert got.count("dispatch") >= got.count("collect") > 0
    decode = [r for r in rows if r["name"] == "engine.decode"]
    assert sum(r["args"]["half"] == "dispatch" for r in decode) \
        == stats["decode_quanta"]
    # the serial pump: one dispatch, one collect, nothing in flight
    # across them, no quantum ahead; the halves pair by their step
    assert halves(rows, "engine.decode") \
        == ["dispatch", "collect"] * stats["decode_quanta"]
    assert stats["quanta_ahead"] == 0
    assert not any("ahead" in r["args"] for r in decode)
    assert [d["args"]["step"] for d, _ in decode_quanta(rows)] \
        == [r["args"]["step"] for r in decode[::2]]
    by_id = {r["args"]["id"]: r for r in rows}
    for r in rows:
        if r["name"] == "engine.decode.sync":
            assert by_id[r["args"]["parent"]]["args"]["half"] == "collect"
        if r["name"] == "engine.decode.enqueue":
            assert by_id[r["args"]["parent"]]["args"]["half"] == "dispatch"


# ------------------------------------------ the profiler's clock agrees
def test_profiler_session_holds_the_same_spans(run, tmp_path):
    """Under ``jax.profiler.start_trace`` the ``/host:`` plane holds the
    same names with the same counts and nesting, each duration within a
    millisecond of its in-memory row."""
    from jax.profiler import ProfileData

    cfg, door = build_door()  # the run fixture warmed these shapes
    step, x = build_train_step()
    step.run_steps(x, x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        m0 = mark()
        submit_ragged(cfg, door)
        door.run_until_idle()
        step.run_steps(x, x)
        rows = rows_since(m0)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[0]
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events if e.name in VOCABULARY]
    mem = [r for r in rows if r["name"] != "request.queued"]
    assert sorted(n for n, _, _ in events) == sorted(r["name"] for r in mem)
    # same order on both clocks, durations within 1 ms
    events.sort(key=lambda e: (e[1], -e[2]))
    mem.sort(key=lambda r: (r["ts"], -r["dur"]))
    assert [e[0] for e in events] == [r["name"] for r in mem]
    for (name, _, dur_ns), r in zip(events, mem):
        assert abs(dur_ns * 1e-3 - r["dur"]) < 1000.0, name
    # same nesting: a row's parent is the innermost event that holds it
    index = {r["args"]["id"]: i for i, r in enumerate(mem)}
    for i, r in enumerate(mem):
        _, s, d = events[i]
        holders = [j for j, (_, s2, d2) in enumerate(events)
                   if j != i and s2 <= s and s + d <= s2 + d2]
        inner = min(holders, key=lambda j: events[j][2], default=None)
        assert inner == index.get(r["args"]["parent"]), r["name"]


# ----------------------------------------- compile events, by their step
def _requests(step):
    return MetricsRegistry.process().get(
        "jax_compile_requests_total").value(step=step)


def test_compile_events_are_charged_to_the_open_step():
    """A fresh shape inside a mixed step raises the counter under
    ``step="mixed"`` and lands on that step's row; the first decode
    quantum compiles under ``step="decode"``, the second, warm, adds 0."""
    cfg, door = build_door(num_slots=3, prefill_chunk=11, decode_quantum=5)
    reg = door.engine.obs.registry
    assert reg.get("jax_compile_requests_total") \
        is MetricsRegistry.process().get("jax_compile_requests_total")
    rng = np.random.RandomState(1)
    door.submit(rng.randint(1, cfg.vocab_size, 11).astype(np.int32),
                max_new_tokens=14)
    m0 = mark()
    mixed0, decode0 = _requests("mixed"), _requests("decode")
    door.pump()                                   # the mixed step
    assert door.engine.stats["mixed_steps"] == 1
    assert _requests("mixed") > mixed0
    assert _requests("decode") == decode0
    row = [r for r in rows_since(m0) if r["name"] == "engine.mixed"][0]
    assert row["args"]["compile_requests"] == _requests("mixed") - mixed0
    assert row["args"]["compile_backend_s"] > 0
    assert row["args"]["compile_trace_s"] > 0
    assert row["args"]["compile_lower_s"] > 0
    door.pump()                      # first quantum: compiles; the
    assert door.engine.stats["decode_quanta"] == 1  # second goes ahead
    decode1 = _requests("decode")
    assert decode1 > decode0
    door.pump()                      # collects the second behind a third
    assert door.engine.stats["decode_quanta"] == 2
    assert _requests("decode") == decode1
    quanta = [r for r in rows_since(m0) if r["name"] == "engine.decode"]
    assert [r["args"]["half"] for r in quanta] \
        == ["dispatch", "dispatch", "collect", "dispatch", "collect"]
    assert [r["args"].get("ahead") for r in quanta] \
        == [None, 1, None, 1, None]
    # the first dispatch built the ONE executable: the quanta that took
    # their carry from the device asked for none
    assert quanta[0]["args"]["compile_requests"] == decode1 - decode0
    assert not any("compile_requests" in r["args"] for r in quanta[1:])
    assert door.engine._quantum._cache_size() == 1
    # on the engine's own scrape like every other counter
    assert 'jax_compile_requests_total{step="mixed"}' in reg.prometheus()
    assert 'stage="trace",step="mixed"' in reg.prometheus()


def test_train_compiles_are_charged_to_train():
    from paddle_tpu.jit.train import JittedTrainStep

    paddle.seed(0)
    model = paddle.nn.Linear(6, 10)  # a shape no other test builds
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = JittedTrainStep(
        model, lambda out, label: ((out - label) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((1, 3, 6), "f4"))
    y = paddle.to_tensor(np.ones((1, 3, 10), "f4"))
    m0 = mark()
    before = _requests("train")
    step.run_steps(x, y)
    first = _requests("train")
    assert first > before
    step.run_steps(x, y)
    assert _requests("train") == first
    rows = [r for r in rows_since(m0) if r["name"] == "train.run_steps"]
    assert rows[0]["args"]["compile_requests"] == first - before
    assert "compile_requests" not in rows[1]["args"]
    assert [r["args"]["step"] for r in rows] == [0, 1]


def test_the_caller_names_the_step_not_the_profiler():
    """``step_kind`` on the span is all that marks a step: the profiler
    keeps no list of the engine's or the trainer's span names."""
    import paddle_tpu.profiler as profiler

    assert not hasattr(profiler, "_STEP_OF")
    count_compile_events()
    before, inner0 = _requests("test_kind"), _requests("inner_kind")
    with RecordEvent("test.outer", step_kind="test_kind") as outer:
        with RecordEvent("test.inner", step_kind="inner_kind"):
            with RecordEvent("test.plain"):
                jax.jit(lambda v: v * 5 + 2)(np.arange(9, dtype="f4"))
    # the OUTERMOST marked span is the step
    assert _requests("test_kind") == before + 1
    assert _requests("inner_kind") == inner0
    assert outer.args["compile_requests"] == 1
    assert "step_kind" not in outer.args


def test_the_listener_is_registered_once():
    a = count_compile_events()
    b = count_compile_events(MetricsRegistry())
    assert all(x is y for x, y in zip(a, b))
    before = _requests("none")
    jax.jit(lambda v: v * 3 + 1)(np.arange(7, dtype="f4"))
    assert _requests("none") == before + 1  # once, not once per call


# ------------------------------------------------- bounded; per thread
def test_buffer_stays_bounded_and_counts_its_drops():
    rec = TraceRecorder(max_events=4)
    for i in range(10):
        rec.span(f"s{i}", 0.0, 1.0)
    assert len(rec.events) == 4 and rec.dropped == 6
    assert [e["name"] for e in rec.spans()] == ["s6", "s7", "s8", "s9"]
    assert rec.chrome_trace()["otherData"]["dropped_events"] == 6
    # the process's recorder is that class, bounded the same way
    proc = TraceRecorder.process()
    assert proc is TraceRecorder.process()
    assert proc.events.maxlen == proc.max_events == 65536
    n0, d0 = len(proc.events), proc.dropped
    with RecordEvent("test.bounded"):
        pass
    assert len(proc.events) + proc.dropped == n0 + d0 + 1
    assert len(proc.events) <= proc.max_events


def test_threads_do_not_adopt_each_others_open_span():
    opened, release, seen = threading.Event(), threading.Event(), {}

    def holder():
        with RecordEvent("test.holder") as span:
            seen["holder"] = span
            opened.set()
            release.wait(10)

    t = threading.Thread(target=holder, name="span-holder")
    t.start()
    assert opened.wait(10)
    with RecordEvent("test.other") as other:   # holder's span is open
        with RecordEvent("test.other.child") as child:
            pass
    release.set()
    t.join(10)
    assert other.parent is None
    assert child.parent == other.id
    assert seen["holder"].parent is None
    rows = {r["name"]: r for r in TraceRecorder.process().spans()
            if r["name"].startswith("test.")}
    assert rows["test.holder"]["tid"] != rows["test.other"]["tid"]
    names = TraceRecorder.process().chrome_trace()["traceEvents"]
    assert any(e["ph"] == "M" and e["args"]["name"] == "span-holder"
               for e in names)


def test_a_span_ended_on_another_thread_leaves_its_own_stack():
    """``begin()`` / ``end()`` are public: ended on another thread than
    it began on, out of order, or twice, a span raises nothing, is
    recorded once, and is no parent to what its own thread opens next."""
    span = RecordEvent("test.handed_over")
    span.begin()
    errors = []

    def finish():
        try:
            span.end()
        except Exception as e:  # pragma: no cover - the failure itself
            errors.append(e)

    t = threading.Thread(target=finish)
    t.start()
    t.join(10)
    assert not errors and span.t1 is not None
    span.end()                           # a second end is a no-op
    assert len(TraceRecorder.process().spans("test.handed_over")) == 1
    with RecordEvent("test.after") as after:
        pass
    assert after.parent == span.parent
    outer = RecordEvent("test.out_of_order")
    outer.begin()
    inner = RecordEvent("test.out_of_order.inner")
    inner.begin()
    outer.end()
    inner.end()
    with RecordEvent("test.after") as after:
        pass
    assert after.parent == span.parent


def test_every_thread_gets_a_track_of_its_own():
    rec = TraceRecorder()
    tids, barrier = [], threading.Barrier(8)

    def ask():
        barrier.wait(10)
        tids.append(rec.thread_tid())

    threads = [threading.Thread(target=ask) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert len(set(tids)) == 8
    assert rec.thread_tid() not in tids
    assert min(tids) >= TraceRecorder._THREAD_TID0


def test_a_span_costs_microseconds():
    """Always on, so it has to be cheap: an order of magnitude of room
    over the few microseconds measured (PERF.md section 6)."""
    n, best = 400, float("inf")
    for _ in range(5):  # the best of five: a collector's pause or a
        t0 = time.perf_counter()  # busy machine is not the span's cost
        for _ in range(n):
            with RecordEvent("test.cost", step=1):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 100e-6


# --------------------------------- the jitted quantum's stack offset
def test_the_quantum_keeps_its_stack_offset():
    """CPython keeps Python frames in 16 KiB chunks of a data stack; a
    call whose frame does not fit maps a new chunk and its return unmaps
    it. JAX's tracer makes millions of calls under the quantum's first
    trace and lowering (11 s of a run's set-up, which moved by 4 s with
    ten words; ``PERF.md`` section 6, PR 26), and where the chunks end
    among them follows from the words (locals + stack) of the frames
    above it, so the frames this repo owns there keep the sum they had
    before they were spanned. (The eager mixed forward, whose every
    step hung on the same thing, went with ROADMAP S1, and its sum with
    it.) The path is ``step()``'s: the first quantum of a run, the one
    that is traced, is dispatched through ``_step_dispatch`` with nothing
    in flight (ISSUE 48; the public ``step_dispatch`` drains and calls
    it)."""
    from paddle_tpu.serving.engine import ServingEngine
    from paddle_tpu.serving.frontend import ServingFrontDoor

    def words(f):
        c = f.__code__
        return (len(c.co_varnames) + len(c.co_cellvars)
                + len(c.co_freevars) + c.co_stacksize)

    path = [ServingFrontDoor.pump, ServingEngine.step,
            ServingEngine._step_dispatch, ServingEngine._decode_dispatch,
            ServingEngine._guarded_dispatch, ServingEngine._dispatch_quantum]
    assert sum(words(f) for f in path) == 88, [words(f) for f in path]


# ----------------------------------------------------- the vocabulary
@pytest.mark.parametrize("name", VOCABULARY)
def test_no_name_can_be_taken_for_a_benchmark_row(name):
    assert ":" not in name
    assert name.split(".")[0] not in BENCHMARK_BASES
    assert name.split(":")[0] not in BENCHMARK_BASES
    assert re.fullmatch(r"[a-z_]+(\.[a-z_]+)+", name)


def test_the_vocabulary_is_what_the_program_spans():
    """Every ``RecordEvent("...")`` in the program's hot path, and the
    ``request.queued`` row, is in the vocabulary; nothing else is."""
    found = set()
    for sub in ("serving", "jit", "obs"):
        for path in glob.glob(os.path.join(ROOT, "paddle_tpu", sub, "*.py")):
            with open(path) as f:
                text = f.read()
            found |= set(re.findall(
                r'RecordEvent\(\s*"([^"]+)"', text))
            found |= set(re.findall(
                r'\.span\(\s*"([^"]+)"', text))
    assert found == set(VOCABULARY)


def test_perf_md_names_every_span():
    with open(os.path.join(ROOT, "PERF.md")) as f:
        text = f.read()
    missing = [n for n in VOCABULARY if f"`{n}`" not in text]
    assert not missing, missing
