"""The readers of the program's own spans (``benchmark/harness/
program_spans.py`` and the seven per-layer metrics that stand on it), on
hand-made rows with known sums, and once through the toy cells of
``benchmark/rehearsal.json``:

* self time is a step's duration less what its direct children cover;
* a decode quantum is the two halves the engine records for it;
* the window is the last ``n`` steps by count, whatever came before;
* a program without the recorder, or a kind without such steps, reads
  ``None`` and raises nothing.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
import selfcheck  # noqa: E402
from benchmark.harness import program_spans  # noqa: E402

SEED = 2147483777
NEW = ("queue_wait_ms", "mixed_forward_ms", "mixed_trace_lower_ms",
       "quantum_host_ms", "quantum_args_ms", "compiles_in_decode",
       "train_host_ms")


def ev(name, span_id, parent, start_ms, ms, **args):
    return {"name": name, "ph": "X", "pid": 1, "tid": 1000,
            "ts": start_ms * 1e3, "dur": ms * 1e3,
            "args": dict(args, id=span_id, parent=parent)}


def serving_events():
    """A warm-up batch and a window of two mixed steps and three quanta,
    every duration chosen by hand (milliseconds)."""
    e, i = [], iter(range(1, 1000))
    t = 0.0

    def mixed(total, forwards, layer_ms, **compile_):
        nonlocal t
        m = next(i)
        e.append(ev("engine.mixed", m, None, t, total, **compile_))
        at = t
        e.append(ev("engine.mixed.prepare", next(i), m, at, 1.0))
        at += 1.0
        for f_ms in forwards:
            f = next(i)
            e.append(ev("engine.mixed.forward", f, m, at, f_ms))
            e.append(ev("engine.mixed.layer", next(i), f, at, layer_ms))
            at += f_ms
        e.append(ev("engine.mixed.select", next(i), m, at, 2.0))
        t += total

    def quantum(total, args_ms, sync_ms, **compile_):
        """Two halves, as the engine records them: the uploads inside the
        enqueue span of the dispatch half, the sync in the collect half."""
        nonlocal t
        d, c, enq = next(i), next(i), next(i)
        first = args_ms + 1.0        # the call itself: 1 ms
        e.append(ev("engine.decode.args", next(i), enq, t, args_ms))
        e.append(ev("engine.decode.enqueue", enq, d, t, first))
        e.append(ev("engine.decode", d, None, t, first, half="dispatch",
                    **compile_))
        e.append(ev("engine.decode.sync", next(i), c, t + first, sync_ms))
        e.append(ev("engine.decode", c, None, t + first, total - first,
                    half="collect"))
        t += total

    # a collect half whose dispatch half the ring let go is no step
    e.append(ev("engine.decode", next(i), None, t, 77.0, half="collect"))
    # warm-up: everything compiles, nothing of it may be read
    for k in range(4):
        e.append(ev("request.queued", next(i), None, t, 500.0, req_id=k))
    mixed(9000.0, [8000.0], 100.0, compile_requests=70,
          compile_backend_s=5.0)
    quantum(900.0, 50.0, 100.0, compile_requests=3, compile_backend_s=0.5)
    # the window
    for k, wait in enumerate((1.0, 2.0, 3.0, 10.0)):
        e.append(ev("request.queued", next(i), None, t, wait, req_id=k))
    mixed(100.0, [60.0, 20.0], 10.0, compile_requests=68,
          compile_cache_hits=68, compile_trace_s=0.010,
          compile_lower_s=0.020, compile_cache_load_s=0.030)
    quantum(30.0, 4.0, 20.0)
    quantum(40.0, 6.0, 25.0)
    mixed(120.0, [90.0], 10.0, compile_trace_s=0.040)
    quantum(50.0, 5.0, 30.0)
    return e


SERVING_OBS = {"engine_steps": {"mixed_steps": 2, "decode_quanta": 3},
               "batches": 1, "batch": 4}


def train_events():
    e, i, t = [], iter(range(1, 100)), 0.0
    for args_ms, enq_ms in ((30.0, 900.0), (1.0, 2.0), (3.0, 2.0),
                            (2.0, 5.0)):
        r = next(i)
        e.append(ev("train.run_steps", r, None, t, args_ms + enq_ms + 0.5))
        e.append(ev("train.args", next(i), r, t, args_ms))
        e.append(ev("train.enqueue", next(i), r, t + args_ms, enq_ms))
        t += 400.0
    return e


TRAIN_OBS = {"dispatch_seconds": [0.33, 0.33, 0.33], "batch": 1}


@pytest.fixture
def recorder(monkeypatch):
    """Hand the readers a recorder of hand-made events."""
    from paddle_tpu.obs.trace import TraceRecorder

    def install(events):
        rec = TraceRecorder()
        rec.events.extend(events)
        monkeypatch.setattr(TraceRecorder, "_process", rec)
        return rec

    return install


def read(run, name, obs):
    return run.load_by_name("metrics", name).read(obs)


@pytest.fixture(scope="module")
def run():
    return selfcheck.load_run()


# ------------------------------------------------------- the arithmetic
def test_self_time_is_duration_less_direct_children():
    rows = program_spans.from_events(serving_events())
    _, steps = program_spans.window_steps(SERVING_OBS, rows)
    first = steps["mixed"][0]
    # 100 - (prepare 1 + forwards 60 + 20 + select 2); the layers are
    # grandchildren and are not taken off twice
    assert program_spans.self_seconds(first, rows) == pytest.approx(0.017)
    assert program_spans.covered_share(first, rows) == pytest.approx(0.83)
    forward = program_spans.children(rows, first, "engine.mixed.forward")
    assert [program_spans.self_seconds([f], rows) for f in forward] \
        == [pytest.approx(0.050), pytest.approx(0.010)]
    # a quantum's two halves are one step: 30 ms, of which the enqueue
    # span (4 + 1) and the sync (20) are covered
    quantum = steps["decode"][0]
    assert [r["args"]["half"] for r in quantum] == ["dispatch", "collect"]
    assert program_spans.seconds(quantum) == pytest.approx(0.030)
    assert program_spans.self_seconds(quantum, rows) == pytest.approx(0.005)


def test_the_window_is_the_last_rows_by_count():
    rows = program_spans.from_events(serving_events())
    _, steps = program_spans.window_steps(SERVING_OBS, rows)
    ms = [[round(program_spans.seconds(s) * 1e3) for s in steps[k]]
          for k in ("mixed", "decode")]
    assert ms == [[100, 120], [30, 40, 50]]
    assert steps["train"] == []
    queued = program_spans.window_requests(SERVING_OBS, rows)
    assert [r["args"]["req_id"] for r in queued] == [0, 1, 2, 3]
    assert [round(r["seconds"] * 1e3) for r in queued] == [1, 2, 3, 10]
    # more asked for than held: what is held
    many = dict(SERVING_OBS, engine_steps={"mixed_steps": 9,
                                           "decode_quanta": 9})
    _, steps = program_spans.window_steps(many, rows)
    # (the orphaned collect half at the start is no quantum)
    assert len(steps["mixed"]) == 3 and len(steps["decode"]) == 4
    assert program_spans.last(rows, "engine.mixed", 0) == []


def test_serving_readers_on_known_sums(run, recorder):
    recorder(serving_events())
    assert read(run, "queue_wait_ms", SERVING_OBS) == pytest.approx(2.5)
    # per step 60 + 20 and 90: median 85
    assert read(run, "mixed_forward_ms", SERVING_OBS) == pytest.approx(85.0)
    # trace 10 + 40 and lower 20 ms over two steps (the 30 ms of cache
    # loads are not tracing or lowering)
    assert read(run, "mixed_trace_lower_ms", SERVING_OBS) \
        == pytest.approx(35.0)
    # 30 - 20, 40 - 25, 50 - 30
    assert read(run, "quantum_host_ms", SERVING_OBS) == pytest.approx(15.0)
    assert read(run, "quantum_args_ms", SERVING_OBS) == pytest.approx(5.0)
    assert read(run, "compiles_in_decode", SERVING_OBS) == 0
    assert read(run, "train_host_ms", SERVING_OBS) is None
    # the warm-up quantum's three requests show if the window reaches it
    wide = dict(SERVING_OBS, engine_steps={"mixed_steps": 3,
                                           "decode_quanta": 4})
    assert read(run, "compiles_in_decode", wide) == 3
    stages = program_spans.compile_seconds(
        program_spans.window_steps(SERVING_OBS)[1]["mixed"])
    assert stages == {"trace": pytest.approx(0.050),
                      "lower": pytest.approx(0.020), "backend": 0.0,
                      "cache_load": pytest.approx(0.030)}


def test_train_reader_on_known_sums(run, recorder):
    recorder(train_events())
    # the last three dispatches: 3, 5, 7 ms; the first (compiling) is out
    assert read(run, "train_host_ms", TRAIN_OBS) == pytest.approx(5.0)
    for name in NEW:
        if name != "train_host_ms":
            assert read(run, name, TRAIN_OBS) is None, name


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_is_none(run, recorder, name, monkeypatch):
    """An empty recorder, and a program that has no recorder at all (the
    parent commit of PR 26): ``None``, and no reader raises."""
    from paddle_tpu.obs.trace import TraceRecorder

    recorder([])
    for obs in (SERVING_OBS, TRAIN_OBS, {}):
        assert read(run, name, obs) is None
    monkeypatch.delattr(TraceRecorder, "process")
    assert program_spans.rows() == []
    for obs in (SERVING_OBS, TRAIN_OBS):
        assert read(run, name, obs) is None


def test_the_index_lists_the_new_metrics(run):
    bench = run.load_json("BENCHMARK.json")
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert set(mine) == set(NEW)
    assert [m["name"] for m in bench["per_layer"][-7:]] == list(NEW)
    layers = {m["layer"] for m in bench["per_layer"][:-7]}
    assert {m["layer"] for m in mine.values()} <= layers
    assert mine["train_host_ms"]["workloads"] == [
        "mistral-7b.train-8k", "mistral-7b.train-8k-mesh4"]


# --------------------------------------------- through the toy cells
@pytest.mark.parametrize("workload,reads,none", [
    ("toy.batches", ("queue_wait_ms", "mixed_forward_ms",
                     "mixed_trace_lower_ms", "quantum_host_ms",
                     "quantum_args_ms", "compiles_in_decode"),
     ("train_host_ms",)),
    ("toy.train", ("train_host_ms",),
     ("queue_wait_ms", "mixed_forward_ms", "mixed_trace_lower_ms",
      "quantum_host_ms", "quantum_args_ms", "compiles_in_decode")),
])
def test_through_the_toy_cells(run, workload, reads, none):
    """A traced rehearsal calls every reader on what the program really
    recorded: each kind's own metrics are read, the other kind's are not,
    and the window's steps are covered by their children."""
    out = selfcheck.rehearse_cell(run, selfcheck.rehearsal_index(run),
                                  workload, SEED, trace=1, control=0)
    assert out["correct"] is True
    assert set(reads) <= set(out["metrics_read"])
    assert not set(none) & set(out["metrics_read"])
    rows = program_spans.rows()
    if workload == "toy.batches":
        # toy-batches: one traced batch of 4 requests, one mixed step
        obs = {"engine_steps": {"mixed_steps": 1, "decode_quanta": 10}}
        _, steps = program_spans.window_steps(obs, rows)
        assert len(steps["mixed"]) == 1 and len(steps["decode"]) == 10
        for s in steps["mixed"] + steps["decode"]:
            assert program_spans.covered_share(s, rows) > 0.9
        assert program_spans.compile_requests(steps["decode"]) == 0
    else:
        _, steps = program_spans.window_steps(
            {"dispatch_seconds": [0.0, 0.0]}, rows)
        assert len(steps["train"]) == 2
        for s in steps["train"]:
            assert program_spans.covered_share(s, rows) > 0.9
