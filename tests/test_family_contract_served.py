"""The served families' contract, the scenarios that go through the ENGINE
(``tests/test_family_contract.py`` has the others and says what the contract
is): each family's toy door, built once a set of keywords
(``family_harness.door``) and drained by every test that only serves through
it, against the benchmark's plain float32 reference; a reused slot, a
preemption, a snapshot; counters, spans and scopes. A file of its own so that
neither is one worker's long pole.
"""
import json

import numpy as np
import pytest
import jax.numpy as jnp

from paddle_tpu.obs.trace import TraceRecorder
from paddle_tpu.serving import ServingEngine

from family_harness import (
    FAMILIES, SLOTS, TOP_K, VOCAB_X_HIDDEN, cases, door, drain, host,
    prompts, toy, toy_forward)

NAMES = list(FAMILIES)


# ------------------------------------------------------ through the engine
@pytest.mark.parametrize("name,served", cases(
    "served", lambda pair: "-".join(map(str, pair))))
def test_served_tokens_are_the_references_best(name, served):
    """Prefill in chunks, then decode, through the engine: every served
    token is the reference's best to within the family's gap tolerance, and
    the stream is the one the program's own whole-sequence forward would
    pick greedily. Three prompts in four slots: an idle slot rides every
    step; contexts reach 49."""
    row = FAMILIES[name]
    cfg, _, get_leaf = toy(name)
    chunk, quantum = served
    rows = prompts(cfg, (37, 20, 9), seed=chunk)
    served_door = door(name, prefill_chunk=chunk, decode_quantum=quantum)
    tokens = drain(served_door, rows, 12)
    gaps, _ = row.reference.gap_below_best(cfg, get_leaf,
                                           list(zip(rows, tokens)))
    assert gaps.shape == (36,) and float(host(gaps).max()) < row.gap_tol
    # right-padded to one length (nothing causal sees the padding): one
    # program for the three rows
    ids = np.zeros((3, 48), np.int32)
    for r, (p, toks) in enumerate(zip(rows, tokens)):
        ids[r, :len(p) + 11] = np.concatenate([p, toks[:-1]])
    picked = host(jnp.argmax(toy_forward(name)(ids), -1))
    for r, (p, toks) in enumerate(zip(rows, tokens)):
        assert np.array_equal(picked[r, len(p) - 1:len(p) + 11], toks)
    row.pool_shapes(served_door.engine.pool, SLOTS, chunk)


@pytest.mark.parametrize("name", NAMES)
def test_the_int8_control_fails_the_gap_tolerance(name):
    """The gap tolerance is earned: over 160 served positions the reference
    with int8 operands, standing in the program's place, lies further below
    the best than any served token may."""
    row = FAMILIES[name]
    cfg, _, get_leaf = toy(name)
    # 9 + 40 tokens a row, one row a block: the reference's programs for
    # a row of 48 positions are the ones the served cases compiled
    rows = prompts(cfg, (9, 9, 9, 9), seed=7)
    tokens = drain(door(name), rows, 40)
    gaps, cgaps = row.reference.gap_below_best(
        cfg, get_leaf, list(zip(rows, tokens)), control=True, block_rows=1)
    assert float(host(gaps).max()) < row.gap_tol < 10 * row.gap_tol \
        < float(host(cgaps).max())


@pytest.mark.parametrize("name", NAMES)
def test_a_reused_slot_and_a_preempted_request_continue_exactly(name):
    """One slot: the second request takes the slot the first left (a state
    starts from zero inside the program, a ring stays as it was: positions
    decide what is seen, blocks are new). Then a request preempted in
    mid-decode: the slot AND its blocks are freed, recompute-on-resume
    rebuilds state, ring and keys from prompt + tokens, and the stream is
    bit for bit the uninterrupted one."""
    row = FAMILIES[name]
    cfg, _, _ = toy(name)
    rows = prompts(cfg, (30, 18), seed=11)
    shared = door(name)
    want = drain(shared, rows, 12)
    one = row.serve(num_slots=1)
    got = [drain(one, [p], 12)[0] for p in rows]
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    # a row that begins at position 0 on a pool with a slot side
    resets = 1 if one.engine.pool.state else 0
    assert one.engine.obs.registry.get(
        "serving_state_resets_total").value() == 2 * resets

    eng = shared.engine
    counter = eng.obs.registry.get("serving_state_resets_total")
    before = counter.value()
    reqs = [eng.submit(p, max_new_tokens=12) for p in rows]
    while len(reqs[0].tokens) < 5:
        eng.step()
    in_use = eng.pool.blocks_in_use
    eng.preempt(reqs[0])
    assert eng.pool.blocks_in_use < in_use          # the keys went too
    eng.run()
    assert reqs[0].preemptions == 1
    for r, b in zip(reqs, want):
        assert np.array_equal(np.asarray(r.tokens, np.int32), b)
    # prompt 0 began at position 0 twice, prompt 1 once
    assert counter.value() - before == 3 * resets


@pytest.mark.parametrize("name", NAMES)
def test_a_snapshot_restores_by_recompute(name):
    """``snapshot()`` carries no device state for any model (no state, no
    ring to refuse); a restored engine re-prefills ``prompt + tokens``,
    which rebuilds blocks, state and rings: the streams go on bit for
    bit."""
    cfg, model, _ = toy(name)
    rows = prompts(cfg, (26, 14), seed=13)
    shared = door(name)
    want = drain(shared, rows, 10)
    eng = shared.engine
    reqs = [eng.submit(p, max_new_tokens=10) for p in rows]
    while len(reqs[0].tokens) < 4:
        eng.step()
    snap = json.loads(json.dumps(eng.snapshot()))
    eng.run()
    fresh = ServingEngine.restore(snap, model)
    fresh.run()
    by_id = {r.req_id: r for r in fresh.completed}
    for r, b in zip(reqs, want):
        assert np.array_equal(
            np.asarray(by_id[str(r.req_id)].tokens, np.int32), b)


# ------------------------------------------------------ spans and counters
_COUNTERS = {"routed_rows": "serving_moe_routed_rows_total",
             "offshare_rows": "serving_moe_offshare_rows_total",
             "layer_steps": "serving_moe_layer_steps_total",
             "experts_touched": "serving_moe_experts_touched_total",
             "expert_rows_max": "serving_moe_expert_rows_max_total",
             "window_keys": "serving_window_keys_attended_total",
             "full_keys": "serving_full_keys_attended_total",
             "resets": "serving_state_resets_total"}


@pytest.mark.parametrize("name", NAMES)
def test_counters_spans_and_scopes(name):
    """Two requests through the family's toy door: what the engine's
    registry counted (read as a difference: other tests drain the same
    door), what its spans carry, what the cost ledger charges a token, and
    the scopes both step programs name."""
    row = FAMILIES[name]
    cfg, model, _ = toy(name)
    eng = door(name).engine
    reg, rec = eng.obs.registry, TraceRecorder.process()

    def read():
        return {k: reg.get(v).value() for k, v in _COUNTERS.items()}

    first, before, quanta = rec.next_id(), read(), eng.stats["decode_quanta"]
    drain(door(name), prompts(cfg, (20, 9)), 9)
    moved = {k: v - before[k] for k, v in read().items()}
    quanta = eng.stats["decode_quanta"] - quanta
    spans = [e for e in rec.events
             if e.get("args", {}).get("id", -1) >= first]
    collect = [e["args"] for e in spans if e["name"] == "engine.decode"
               and e["args"].get("half") == "collect"]
    mixed = [e["args"] for e in spans if e["name"] == "engine.mixed"]
    assert collect and mixed
    # expert layers x four steps a quantum, four slots x top 3 choices, of
    # which a chip that holds a share of the experts got `rows`
    rows, off, steps = (moved[k] for k in ("routed_rows", "offshare_rows",
                                           "layer_steps"))
    choices = steps * SLOTS * TOP_K
    assert steps == quanta * 4 * row.expert_layers and rows + off == choices
    assert (0 < rows < choices) if row.offshare else off == 0
    if row.expert_layers:
        assert sum(a["moe_rows"] for a in collect) == rows
        assert sum(a.get("moe_offshare_rows", 0) for a in collect) == off
        assert all(a["moe_rows"] + a.get("moe_offshare_rows", 0)
                   == SLOTS * a["bucket"] * TOP_K * row.expert_layers
                   for a in mixed)
    else:
        assert all("moe_rows" not in a for a in mixed)
    assert moved["resets"] == (2 if eng.pool.state else 0)
    # the cost ledger's 2N: every parameter but the embedding (a lookup)
    # and the experts a token does not multiply
    n = sum(int(p._value.size) for _, p in model.named_parameters())
    assert eng.obs.ledger.flops_per_token == 2.0 * (
        n - VOCAB_X_HIDDEN - row.inactive)
    if row.counters is not None:
        row.counters(eng, moved, collect, mixed, model)
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        text = step.lower(*args).as_text(debug_info=True)
        for scope in row.scopes:
            assert scope in text, scope
