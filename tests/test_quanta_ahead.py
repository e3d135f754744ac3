"""``ServingEngine.step()`` keeps one decode quantum in flight (ISSUE 48): in
steady decode it dispatches quantum *n+1*, on the device-resident carry of
*n*, BEFORE it collects *n*. What is held here, for every served family of
``family_harness.FAMILIES`` and the dense Llama, on the CPU:

the same requests through ``step()`` and through the serial pump
``step_collect(step_dispatch())`` give the same tokens, finish reasons,
retire order and counts, whatever happens meanwhile (a row that stops on the
stop token inside an ahead quantum, requests waiting behind full slots, a
``max_new_tokens`` that is no multiple of the quantum, a preemption with a
quantum in flight, a host-side stop rule, an armed fault injector); the rows
of ``engine.decode`` end in the order D, D, C, D, C, ..., C with ``ahead=1``
where it belongs; the counters count; no ahead quantum follows a batch's
last; the jitted quantum keeps ONE executable; and nothing the host writes
while a quantum is in flight is memory that quantum was handed (on the CPU
``jnp.asarray`` aliases a numpy buffer and a jitted dispatch returns before
it has run: ROADMAP D6).

One engine a family, built once a worker and driven both ways: its two
programs compile once. A second one stops on a token the first one's streams
hold, so that a row finishes early INSIDE a quantum that ran ahead.
"""
import functools
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.obs import MetricsRegistry, TraceRecorder
from paddle_tpu.serving import ServingEngine

from family_harness import FAMILIES, SERVE, toy

NAMES = ["llama", *FAMILIES]
QUANTUM = SERVE["decode_quantum"]
# ragged prompts (two chunks of 16, or one and a decode row's token in the
# second mixed step), lengths that are no multiple of the quantum
PROMPTS, MAX_NEW = (23, 9, 17, 12), (13, 22, 9, 18)


@functools.lru_cache(maxsize=None)
def model_of(name):
    if name != "llama":
        cfg, model, _ = toy(name)
        return cfg["vocab_size"], model, FAMILIES[name].engine
    cfg, model = _llama()
    return cfg.vocab_size, model, {}


def _llama(tensor_parallel=False):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=tensor_parallel)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def build(name, **kw):
    _, model, engine_kw = model_of(name)
    return ServingEngine(model, **{**SERVE, **engine_kw, **kw})


@functools.lru_cache(maxsize=None)
def plain(name):
    """The family's engine without a stop token."""
    return build(name)


@functools.lru_cache(maxsize=None)
def stopping(name):
    """The family's engine with a stop token the plain engine's streams
    hold in mid-decode: (engine, the token). The token is the one a row of
    the plain run emits at its 7th position: inside the second quantum,
    which is the first that runs ahead."""
    run = drive(plain(name), serial, requests(name))
    eos = int(run["tokens"][1][6])
    return build(name, eos_token_id=eos), eos


def requests(name, lengths=PROMPTS, max_new=MAX_NEW, seed=0, **kw):
    vocab, _, _ = model_of(name)
    rng = np.random.default_rng(seed)
    return [dict(prompt=rng.integers(1, vocab, (n,), dtype=np.int32),
                 max_new_tokens=m, **kw)
            for n, m in zip(lengths, max_new)]


def serial(eng):
    """The serial pump: the two halves, nothing in flight across them."""
    return eng.step_collect(eng.step_dispatch())


def ahead(eng):
    return eng.step()


def process_counts():
    reg = MetricsRegistry.process()
    return tuple(reg.counter(name).value() for name in (
        "serving_quanta_ahead_total", "serving_quanta_ahead_dropped_total"))


def drive(eng, pump, reqs, meanwhile=None):
    """Submit ``reqs`` and pump until idle; ``meanwhile(eng, handles, call
    number)`` runs before every pump. What a comparison needs, as plain
    data."""
    mark = TraceRecorder.process().next_id()
    stats0, done0 = dict(eng.stats), len(eng.completed)
    process0 = process_counts()
    dropped = eng.obs.registry.get("serving_quanta_ahead_dropped_total")
    dropped0 = dropped.value()
    handles = [eng.submit(**r) for r in reqs]
    calls, admitted0 = [], eng.scheduler.admitted_total
    while eng.has_work:
        if meanwhile is not None:
            meanwhile(eng, handles, len(calls))
        waiting = len(eng.scheduler.waiting)
        n_ahead = eng.stats["quanta_ahead"]
        pump(eng)
        calls.append({"waiting": waiting,
                      "admitted": eng.scheduler.admitted_total - admitted0,
                      "went_ahead": eng.stats["quanta_ahead"] - n_ahead})
    assert eng._inflight is None       # nothing outlives has_work
    decode = [e for e in TraceRecorder.process().spans("engine.decode")
              if e["args"]["id"] > mark]
    return {
        "tokens": [[int(t) for t in h.tokens] for h in handles],
        "reasons": [h.finish_reason for h in handles],
        "retired": [handles.index(r) for r in eng.completed[done0:]],
        "stats": {k: eng.stats[k] - v for k, v in stats0.items()},
        "dropped": dropped.value() - dropped0,
        "process": tuple(a - b for a, b in zip(process_counts(), process0)),
        "order": "".join(
            ("D" if e["args"]["half"] == "dispatch" else
             "x" if e["args"].get("dropped") else "C")
            + ("a" if e["args"].get("ahead") else "") for e in decode),
        "decode": decode, "calls": calls, "handles": handles}


SAME = ("tokens", "reasons", "retired")
COUNTS = ("mixed_steps", "decode_quanta", "quantum_tokens", "prefill_tokens",
          "generated_tokens")


def both(eng, reqs, meanwhile=None):
    """The requests through the serial pump, then through ``step()``, on
    the one engine: everything a client or a count can see is equal."""
    want = drive(eng, serial, reqs, meanwhile)
    got = drive(eng, ahead, reqs, meanwhile)
    for key in SAME:
        assert got[key] == want[key], key
    for key in COUNTS:
        assert got["stats"][key] == want["stats"][key], key
    # the serial pump never runs ahead
    assert want["stats"]["quanta_ahead"] == 0 == want["dropped"]
    assert want["order"] == "DC" * want["stats"]["decode_quanta"]
    # the one executable: a quantum took its carry from an upload or from
    # the device, its tables from a kept upload or a new one
    assert eng._quantum._cache_size() == 1
    return want, got


def check_rows(got):
    """The rows of a ``step()`` run: a collect half for every dispatch
    half, paired by their step and in the order of their dispatches; an
    ahead dispatch lies between the dispatch and the collect of the quantum
    before it; the counters count the rows."""
    rows = got["decode"]
    dispatches = [e for e in rows if e["args"]["half"] == "dispatch"]
    collects = [e for e in rows if e["args"]["half"] == "collect"]
    assert [e["args"]["step"] for e in dispatches] \
        == [e["args"]["step"] for e in collects]
    order = [(e["args"]["step"], e["args"]["half"]) for e in rows]
    for d, before in zip(dispatches[1:], dispatches):
        if d["args"].get("ahead"):
            at = order.index((d["args"]["step"], "dispatch"))
            assert order[at - 1] == (before["args"]["step"], "dispatch") \
                or order[at - 1][1] == "collect"
            assert order[at + 1] == (before["args"]["step"], "collect")
    n_ahead = sum(bool(e["args"].get("ahead")) for e in dispatches)
    n_dropped = sum(bool(e["args"].get("dropped")) for e in collects)
    assert got["stats"]["quanta_ahead"] == n_ahead
    assert got["dropped"] == n_dropped
    assert got["process"] == (n_ahead, n_dropped)
    assert all(e["args"]["k"] == 1 for e in dispatches)
    return n_ahead, n_dropped


# ------------------------------------------------------------- the cases
def case_closed_batch(name):
    """Every slot taken at once, nothing waits: after the mixed steps every
    quantum but the first runs ahead, until no row can outlive the quantum
    in flight: the batch's last quantum is followed by none. Lengths are no
    multiple of the quantum; tables grow (blocks of 8, quanta of 4) for a
    quantum dispatched while the one before it still runs."""
    eng = plain(name)
    want, got = both(eng, requests(name))
    assert got["reasons"] == ["length"] * 4
    assert [len(t) for t in got["tokens"]] == list(MAX_NEW)
    # D, D, C, D, C, ..., C: every quantum but the first ran ahead
    quanta = want["stats"]["decode_quanta"]
    assert quanta >= 5
    assert got["order"] == "D" + "DaC" * (quanta - 1) + "C"
    assert check_rows(got) == (quanta - 1, 0)
    # a step() is a scheduler iteration as the serial pump's is: as many,
    # with the same rows live
    assert got["stats"]["steps"] == want["stats"]["steps"]
    assert got["stats"]["occupancy_sum"] == want["stats"]["occupancy_sum"]
    assert len(got["calls"]) == len(want["calls"])
    # an ahead row counts the rows that outlive the quantum before it:
    # those the serial pump finds unfinished once that one is collected
    rows = [[e["args"]["rows"] for e in run["decode"]
             if e["args"]["half"] == "dispatch"] for run in (want, got)]
    assert rows[0] == rows[1] and rows[0][0] == 4 and rows[0][-1] == 1


def case_stop_token_inside_an_ahead_quantum(name):
    """A row emits the stop token inside a quantum that ran ahead, with the
    next one already in flight: it rides through that one done-masked on
    the device, its stream ends where the serial pump ends it, and the rows
    beside it go on. Then a request alone: its stop leaves an ahead quantum
    with no row, which is collected and dropped in the same step."""
    eng, eos = stopping(name)
    want, got = both(eng, requests(name))
    assert "eos" in got["reasons"]
    for toks, reason in zip(got["tokens"], got["reasons"]):
        assert (toks[-1] == eos) == (reason == "eos")
        assert eos not in toks[:-1]
    stopped = got["reasons"].index("eos")
    assert len(got["tokens"][stopped]) < MAX_NEW[stopped]
    n_ahead, n_dropped = check_rows(got)
    assert n_ahead >= 1
    assert got["stats"]["decode_quanta"] + n_dropped \
        == got["order"].count("D")
    # alone: prompt 1 of the batch, which meets the stop token within its
    # first seven; by its length it outlives that quantum, so the next one
    # is in flight when it stops, and is dropped
    want, got = both(eng, requests(name)[1:2])
    assert got["reasons"] == ["eos"] and len(got["tokens"][0]) <= 7
    assert got["order"] in ("DDaCx", "DDaCDaCx")
    n_ahead, n_dropped = check_rows(got)
    assert n_dropped == 1
    assert got["stats"]["decode_quanta"] == n_ahead == want["stats"][
        "decode_quanta"]


def case_waiting_behind_full_slots(name):
    """Six requests, four slots: while a request waits the step after a
    quantum may be an admission, so nothing runs ahead, and every request
    is admitted in the call the serial pump admits it in."""
    eng = plain(name)
    reqs = requests(name, PROMPTS + (11, 20), MAX_NEW + (7, 10), seed=2)
    want, got = both(eng, reqs)
    assert [c["admitted"] for c in got["calls"]] \
        == [c["admitted"] for c in want["calls"]]
    assert any(c["waiting"] for c in got["calls"])
    assert not any(c["went_ahead"] for c in got["calls"] if c["waiting"])
    # once the queue is empty the rest of the batch runs ahead
    assert check_rows(got)[0] >= 1
    assert got["stats"]["steps"] == want["stats"]["steps"]


def case_preempt_with_a_quantum_in_flight(name):
    """``preempt`` collects the quantum in flight before it frees the
    slot: the victim keeps that quantum's tokens, resumes by recompute, and
    every stream is the undisturbed one. A victim that the collected
    quantum finished has nothing left to evict."""
    eng = plain(name)
    base = drive(eng, serial, requests(name))
    seen = {}

    def evict(eng, handles, call):
        victim = handles[1]
        if "preempted" in seen or len(victim.tokens) < 5:
            return
        seen["in_flight"] = eng._inflight is not None
        seen["tokens"] = len(victim.tokens)
        eng.preempt(victim)
        seen["preempted"] = len(victim.tokens)
        assert eng._inflight is None

    got = drive(eng, ahead, requests(name), evict)
    assert seen["in_flight"] and seen["preempted"] == seen["tokens"] + QUANTUM
    assert got["handles"][1].preemptions == 1
    assert got["tokens"] == base["tokens"]
    assert got["reasons"] == base["reasons"]
    check_rows(got)
    # 9 tokens: one from the prefill, two quanta; with the first collected
    # the second is in flight and finishes the request
    last = {}

    def evict_the_finishing(eng, handles, call):
        if "done" not in last and len(handles[0].tokens) == 5:
            assert eng._inflight is not None
            last["done"] = eng.preempt(handles[0])
            assert last["done"].finished and eng._inflight is None

    got = drive(eng, ahead, requests(name, (12,), (9,)),
                evict_the_finishing)
    assert last["done"].preemptions == 0
    assert got["reasons"] == ["length"] and len(got["tokens"][0]) == 9


def case_host_stop_rule(name):
    """A request finished by the HOST (a stop rule of its own:
    ``finish_reason="stop"``; this engine's nearest to a cancel) while a
    quantum is in flight: the device still runs the row, so nothing more
    runs ahead until its mask has gone up, the quantum in flight gives the
    row no token, and its slot's blocks are written by no later quantum
    (the streams beside it are the serial pump's)."""
    eng = plain(name)
    base = drive(eng, serial, requests(name))
    stop = base["tokens"][1][9]           # inside the third quantum
    reqs = requests(name)
    reqs[1]["stop_token_ids"] = [stop]
    want, got = both(eng, reqs)
    assert got["reasons"][1] == "stop"
    assert got["tokens"][1] == base["tokens"][1][
        :base["tokens"][1].index(stop) + 1]
    for i in (0, 2, 3):
        assert got["tokens"][i] == base["tokens"][i]
    n_ahead, _ = check_rows(got)
    # the quantum in flight when the rule was met is collected alone (C, C)
    # and the one after it is not ahead: its mask went up first
    after = re.search(r"CCD(a?)", got["order"])
    assert after is not None and after.group(1) == ""
    assert n_ahead < got["stats"]["decode_quanta"] - 1


def case_armed_fault_injector(name):
    """An armed injector wants the host between every two quanta (a fault
    fires before a dispatch, attributed to its rows): nothing runs ahead,
    the rows are the serial pump's."""
    eng = plain(name)
    eng.faults.poison("a request that is not here")
    try:
        assert eng.faults.armed
        want, got = both(eng, requests(name))
    finally:
        eng.faults.cure("a request that is not here")
    assert got["order"] == want["order"]
    assert check_rows(got) == (0, 0)


CASES = [case_closed_batch, case_stop_token_inside_an_ahead_quantum,
         case_waiting_behind_full_slots,
         case_preempt_with_a_quantum_in_flight, case_host_stop_rule,
         case_armed_fault_injector]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[5:])
@pytest.mark.parametrize("name", NAMES)
def test_step_is_the_serial_pump_one_quantum_ahead(name, case):
    case(name)


# ------------------------------------------- what a quantum in flight holds
@pytest.mark.parametrize("name", NAMES)
def test_the_host_writes_nothing_a_quantum_in_flight_was_handed(name):
    """ROADMAP D6, closed: with quantum *n* in flight ``step()`` grows the
    table rows for *n+1* and refreshes the mirrors at *n*'s collect. Every
    small argument of a dispatched quantum is memory of its own: on the CPU
    a device array's host view IS its buffer, so none may share memory
    with a mirror the host writes. (The streams over those growths are the
    serial pump's: ``closed_batch`` above.)"""
    eng = plain(name)
    for r in requests(name, seed=4):
        eng.submit(**r)
    checked = 0
    while eng.has_work:
        eng.step()
        if eng._inflight is None:
            continue
        mirrors = [eng._tables, eng._seq_lens, eng._last_tok, eng._n_gen,
                   eng._done, eng._max_new, eng._keys]
        handed = [dev for _, dev in eng._kept.values()] + list(eng._carry)
        for dev in handed:
            view = np.asarray(dev)
            assert not any(np.shares_memory(view, m) for m in mirrors)
        # the table the quantum in flight reads is the kept copy, which
        # equals the mirror now and is not written when the mirror is
        kept, _ = eng._kept["tables"]
        assert np.array_equal(kept, eng._tables)
        before = kept.copy()
        eng._tables[0, -1] += 1
        assert np.array_equal(kept, before)
        eng._tables[0, -1] -= 1
        checked += 1
    assert checked >= 4


# ------------------------------------------------------- the accounting
@pytest.mark.parametrize("name", ["llama"])
def test_the_accounting_stays_a_partition(name):
    """An ahead quantum's wall starts where the quantum before it ended,
    not at its own dispatch (which lies before that): over a closed batch
    the decode histogram's sum is the wall the quanta covered, first
    dispatch to last sync, no second counted twice; the host-gap gauge
    stays a fraction."""
    eng = plain(name)
    drive(eng, ahead, requests(name))              # warm
    hist = eng.obs.registry.get("serving_quantum_seconds")
    sum0, count0 = hist.sum(kind="decode"), hist.count(kind="decode")
    kinds = ("decode", "mixed")
    all0 = sum(hist.sum(kind=k) for k in kinds)
    ledger0 = eng.obs.ledger.report()["attributed_seconds"]
    got = drive(eng, ahead, requests(name, max_new=(40, 37, 33, 29)))
    assert check_rows(got)[0] >= 8
    rows = got["decode"]
    syncs = [e for e in TraceRecorder.process().spans("engine.decode.sync")
             if e["args"]["parent"] in {r["args"]["id"] for r in rows}]
    wall = (max(e["ts"] + e["dur"] for e in syncs)
            - min(r["ts"] for r in rows)) * 1e-6
    assert hist.count(kind="decode") - count0 \
        == got["stats"]["decode_quanta"]
    assert hist.sum(kind="decode") - sum0 == pytest.approx(wall, rel=0.01)
    # the cost ledger's conservation: its phases' seconds are the
    # histogram's, every kind of step together
    assert eng.obs.ledger.report()["attributed_seconds"] - ledger0 \
        == pytest.approx(sum(hist.sum(kind=k) for k in kinds) - all0,
                         rel=1e-6)
    assert 0.0 <= eng.obs.registry.get(
        "serving_host_gap_fraction").value() <= 1.0


# ------------------------------------------------------ the one executable
def test_committed_weights_keep_one_executable():
    """Beside COMMITTED weights (as the benchmark installs them) a jitted
    step's outputs are committed too: a carry mirror uploaded the plain
    way would be a second kind of argument and the quantum would be traced
    a second time, seconds of a cell's set-up. The mirrors go up where the
    outputs live, so whichever the quantum is handed it has ONE executable
    (and the mixed step its two buckets)."""
    import jax

    _, model = _llama()
    for _, p in model.named_parameters():
        p._value = jax.device_put(p._value, jax.devices()[0])
    eng = ServingEngine(model, **SERVE)
    assert eng._carry_sharding == model.lm_head.weight._value.sharding
    for seed in (0, 1):
        got = drive(eng, ahead, requests("llama", seed=seed))
        assert check_rows(got)[0] >= 4
        assert all(c.committed for c in eng._carry)
        assert eng._quantum._cache_size() == 1
    # uncommitted weights: plain uploads, uncommitted outputs, one too
    eng = plain("llama")
    drive(eng, ahead, requests("llama"))
    assert eng._carry_sharding is None
    assert not any(c.committed for c in eng._carry)


def test_the_carry_is_replicated_under_a_mesh():
    """Tensor-parallel serving takes the small per-slot state committed
    REPLICATED (the build-time executable's layouts): the quantum's
    outputs are put so before they are the next one's arguments, and the
    ahead path engages under ``tp=2`` with the streams of one chip."""
    from jax.sharding import PartitionSpec

    # built WITHOUT a mesh the mp layers are their serial twins: the
    # same seed gives one chip and the tp=2 engine the same weights
    _, model = _llama(tensor_parallel=True)
    want = drive(ServingEngine(model, **SERVE), serial, requests("llama"))
    _, model = _llama(tensor_parallel=True)
    eng = ServingEngine(model, tp=2, **SERVE)
    got = drive(eng, ahead, requests("llama"))
    assert got["tokens"] == want["tokens"]
    assert check_rows(got)[0] >= 4
    assert all(c.sharding.spec == PartitionSpec() and c.committed
               and len(c.sharding.device_set) == 2 for c in eng._carry)
