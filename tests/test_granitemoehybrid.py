"""The Granite-4.0-H-shaped decoder (Mamba-2 state-space layers beside
attention without positions, routed experts of which a chip holds its share)
on the normal serving path, against the benchmark's plain reference
(``benchmark/reference/granitemoehybrid.py``: float32, HIGHEST, the
RECURRENCE form of the mixer, no cache), on the toy configuration in float32.

The weights are the test's own: the benchmark's seeded ones make ``A`` about
-1 and ``dt`` about 0.69, so the state forgets within ~10 tokens and a wrong
carry of the state over a chunk boundary would hide. Here ``dt_bias`` is
about -4 and ``A_log`` in 0..2.7: ``dt A`` runs from -0.02 to -0.3 a token
and a state still holds a tenth of what it held 8 to 130 tokens ago.

Tolerances: program and reference compute the same float32 numbers in
another order (the program sums a chunk at a time through decay matrices,
fuses gate|up, sorts rows by expert, folds attention tiles), so they differ
by summation order only: logits of magnitude ~0.3 agree to 2e-5 (the decay
matrix multiplies exponentials of differences where the recurrence
multiplies step by step). The reference's int8-operand control moves the
same logits by > 100 x that and a served token's gap to ~1e-2, so each
tolerance below is asserted to be tight enough that the control fails it.
"""
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import autograd
from paddle_tpu.incubate.distributed.models.moe import SoftmaxTopKGate
from paddle_tpu.incubate.distributed.models.moe import moe_layer
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM, PagedKVCachePool
from paddle_tpu.nlp import granitemoehybrid as G
from paddle_tpu.obs.trace import TraceRecorder
from paddle_tpu.serving import ServingEngine, no_shed_policy
from paddle_tpu.serving import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import granitemoehybrid as family  # noqa: E402

reference = family.reference
LOGIT_TOL = 2e-5     # summation order in float32, logits of magnitude ~0.3
GAP_TOL = 1e-4       # a served token lies this close to the reference's best


def _host(x, dtype=None):
    """A device value on the host, said out loud."""
    return np.asarray(jax.device_get(x), dtype)


def _max_abs(a, b=0.0):
    return float(np.abs(_host(a) - _host(b)).max())


def _slow_leaves(cfg, seed=0):
    """name -> float32 array for every leaf of the reference's table:
    matrices of standard deviation 1/sqrt(fan-in), norms near 1, and a
    state that decays SLOWLY (see the module docstring)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape, kind in reference.leaf_table(cfg):
        short = name.split(".")[-1]
        if short == "dt_bias":
            v = rng.uniform(-4.5, -3.5, shape)
        elif short == "A_log":
            v = np.linspace(0.0, 2.7, shape[0])
        elif kind == "norm":
            v = 1.0 + 0.1 * rng.standard_normal(shape)
        elif kind == "bias":
            v = 0.1 * rng.standard_normal(shape)
        elif short == "conv_w":
            v = 0.5 * rng.standard_normal(shape)
        elif short == "embed":
            v = rng.standard_normal(shape) / 12.0
        else:
            v = rng.standard_normal(shape) / np.sqrt(shape[-2])
            if short in ("out_w", "o_w", "e_out", "s_out"):
                # what enters the residual stream: x 16, so that the layers
                # and not the (tied) embedding of the last token decide
                # the next one
                v *= 16.0
        out[name] = jnp.asarray(v, jnp.float32)
    return out


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "toy-ssm-moe.json")) as f:
        cfg = json.load(f)
    # the toy file's head is scaled for the benchmark's seeded weights;
    # the test's own weights are sized for the source's logits / 16
    cfg["logits_scaling"] = 16
    leaves = _slow_leaves(cfg)
    model = family.build_model(cfg)
    _, params = family.parameters(model, cfg)
    for p, (name, _, _) in zip(params, reference.leaf_table(cfg)):
        p._value = leaves[name]
    model.eval()
    return cfg, model, leaves.__getitem__


def _serve(model, **kw):
    kw = {"num_slots": 4, "block_size": 8, "num_blocks": 64,
          "max_context": 96, "prefill_chunk": 16, "decode_quantum": 4, **kw}
    return paddle.inference.serve(model, policy=no_shed_policy(), **kw)


def _drain(door, prompts, new_tokens):
    streams = [door.submit(p, max_new_tokens=new_tokens) for p in prompts]
    while door.engine.has_work:
        door.pump()
    return [_host(s.request.tokens, np.int32) for s in streams]


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], (n,), dtype=np.int32)
            for n in lengths]


# ------------------------------------------------------ forward, reference
def test_forward_matches_the_reference_logits(toy):
    """Two sequences of 40 tokens, chunks of mamba_chunk_size 16 (an
    uneven last chunk) against the recurrence."""
    cfg, model, get_leaf = toy
    ids = np.stack(_prompts(cfg, (40, 40)))
    ref = reference.logits(cfg, get_leaf, ids)
    got = model(paddle.to_tensor(ids))._value
    assert _max_abs(ref) > 0.2
    assert _max_abs(ref, got) < LOGIT_TOL
    # the tolerance is earned: the int8-operand control fails it
    control = reference.logits(cfg, get_leaf, ids, control=True)
    assert _max_abs(ref, control) > 100 * LOGIT_TOL


def test_the_state_decays_slowly(toy):
    """What the docstring promises of the test's weights: changing the
    FIRST token still moves the logits 30 positions on (with the
    benchmark's seeded weights it would not)."""
    cfg, _, get_leaf = toy
    only_ssm = dict(cfg, num_hidden_layers=2, layer_types=["mamba"] * 2)
    ids = np.stack(_prompts(cfg, (31,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1
    a, b = (reference.logits(only_ssm, get_leaf, x)[0, -1]
            for x in (ids, other))
    assert _max_abs(a, b) > 100 * LOGIT_TOL


class _Paged:
    """The engine's two bodies driven by hand over a pool of three slots,
    so that a test reads LOGITS where the engine hands out tokens: row 0
    serves, row 1 is never live, row 2 is live in chunks and masked in
    decode."""

    def __init__(self, model, slots=3):
        layout = model.paged_cache_layout()
        kinds = engine_mod.layout_parts(layout["layers"])
        self.model, self.slots = model, slots
        self.pool = PagedKVCachePool(
            32, 8, layout["num_kv_heads"], layout["head_dim"],
            num_layers=kinds.count("kv"), dtype=jnp.float32,
            state={"slots": slots, "layers": kinds.count("state"),
                   "arrays": layout["state"]})
        self.scratch = self.pool.ensure("__scratch__", 1)[0]
        self.lens = np.zeros(slots, np.int32)

    def _tables(self, rows, grow):
        for r, n in zip(rows, grow):
            self.pool.ensure(f"r{r}", int(self.lens[r]) + n)
        return self.pool.block_table_array(
            [f"r{r}" for r in range(self.slots)], pad_to=12)

    def chunk(self, ids, counts):
        """ids (S, C), counts (S,): one mixed step; logits (S, V)."""
        counts = np.asarray(counts, np.int32)
        tables = self._tables(range(self.slots), counts)
        with autograd.no_grad():
            logits, *pools = engine_mod.paged_chunk_math(
                self.model, self.scratch, paddle.to_tensor(ids),
                jnp.asarray(self.lens), tables, *self.pool.arrays()[:2],
                jnp.asarray(counts > 0), counts=jnp.asarray(counts),
                st=self.pool.state)
        self.pool.adopt(*pools)
        self.lens += counts
        return logits

    def decode(self, toks, live):
        """toks (S,), live (S,): one decode step; logits (S, V)."""
        live = np.asarray(live, bool)
        tables = self._tables(range(self.slots), live.astype(np.int32))
        with autograd.no_grad():
            logits, *pools = engine_mod.paged_decode_math(
                self.model, self.scratch,
                paddle.to_tensor(np.asarray(toks, np.int32)[:, None]),
                jnp.asarray(self.lens), tables, *self.pool.arrays()[:2],
                jnp.asarray(live), st=self.pool.state)
        self.pool.adopt(*pools)
        self.lens += live
        return logits


def _stamp(pool, row, value):
    """Fill slot ``row``'s state with ``value`` in every state layer."""
    pool.state = tuple(tuple(a.at[row].set(value) for a in layer)
                       for layer in pool.state)


def test_chunked_prefill_then_decode_through_the_slot_state(toy):
    """A 37-token prompt in chunks that split it unevenly (counts 1, C - 1,
    C, then the rest; C = 16), then 10 decode steps, teacher-forced:
    every logit the program hands out is the reference's full pass's.
    Beside it a row that is never live and a row that is masked in decode
    keep their state bit for bit, and a new request in a used slot starts
    from zero."""
    cfg, model, get_leaf = toy
    seq, other = _prompts(cfg, (47, 21), seed=3)
    ref = reference.logits(cfg, get_leaf, seq[None])[0]
    ref_other = reference.logits(cfg, get_leaf, other[None])[0]
    run = _Paged(model)
    _stamp(run.pool, 1, 7.0)
    at = 0
    for n in (1, 15, 16, 5):
        ids = np.zeros((3, 16), np.int32)
        ids[0, :n] = seq[at:at + n]
        ids[2, :n] = other[at:at + n] if at + n <= 21 else 0
        counts = [n, 0, n if at + n <= 21 else 0]
        logits = run.chunk(ids, counts)
        at += n
        assert _max_abs(logits[0], ref[at - 1]) < LOGIT_TOL
        if counts[2]:
            assert _max_abs(logits[2], ref_other[at - 1]) < LOGIT_TOL
    held = jax.tree_util.tree_map(lambda a: _host(a[2]), run.pool.state)
    for j in range(10):          # row 2 rides along masked, row 1 idle
        logits = run.decode([seq[37 + j], 0, 5], [True, False, False])
        assert _max_abs(logits[0], ref[37 + j]) < LOGIT_TOL
    for layer, want in zip(run.pool.state, held):
        for a, w in zip(layer, want):
            np.testing.assert_array_equal(_host(a[2]), w)   # masked
            assert float(_host(a[1]).min()) == 7.0 == float(
                _host(a[1]).max())                          # never live
    # the slot of row 0 is handed to a new request: its first chunk has
    # base length 0, so the program starts its state from zeros
    run.pool.free("r0")
    run.lens[0] = 0
    ids = np.zeros((3, 16), np.int32)
    ids[0] = other[:16]
    logits = run.chunk(ids, [16, 0, 0])
    assert _max_abs(logits[0], ref_other[15]) < LOGIT_TOL


def _recurrence(xs, b, c, dt, a, d, h0):
    """The module's first equation block, position by position, float64;
    ``b`` / ``c`` (S, C, G, N), head ``h`` using group ``h // (H / G)``."""
    xs, b, c, dt, a, d, h = (np.asarray(v, np.float64)
                             for v in (xs, b, c, dt, a, d, h0))
    per = xs.shape[2] // b.shape[2]
    b, c = np.repeat(b, per, axis=2), np.repeat(c, per, axis=2)
    ys = np.zeros(xs.shape)
    for t in range(xs.shape[1]):
        h = (h * np.exp(dt[:, t] * a)[..., None, None]
             + (dt[:, t, :, None] * xs[:, t])[..., None]
             * b[:, t, :, None, :])
        ys[:, t] = (h * c[:, t, :, None, :]).sum(-1) + d[:, None] * xs[:, t]
    return ys, h


@pytest.mark.parametrize("groups,decay_bytes", [
    (1, None), (1, 4 * 2 * 12 * 12), (3, None), (3, 4 * 2 * 12 * 12),
    (3, 4 * 2 * 12 * 12 // 2), (2, 4 * 3 * 12 * 12), (6, None)],
    ids=["one_group", "head_groups_of_two", "three_groups",
         "three_groups_streamed_whole", "three_groups_streamed_in_halves",
         "two_groups_streamed_whole", "a_group_a_head"])
def test_chunked_form_equals_the_recurrence(groups, decay_bytes,
                                            monkeypatch):
    """``ssd_chunk`` over 12 positions with an incoming state, positions
    of dt = 0 (past a row's count) included, against the recurrence, for
    one group of B and C (this family's) and for several (``nemotron_h``'s:
    head ``h`` reads group ``h // (H / G)``); the heads stream under a byte
    bound, whole groups or an equal part of one at a time, to the same
    numbers."""
    if decay_bytes:
        monkeypatch.setattr(G, "_DECAY_BYTES", decay_bytes)
    rng = np.random.default_rng(0)
    s_, cl, h, p, n = 2, 12, 6, 4, 5
    xs = rng.standard_normal((s_, cl, h, p))
    b, c = rng.standard_normal((2, s_, cl, groups, n))
    dt = rng.uniform(0.01, 0.3, (s_, cl, h))
    dt[1, 7:] = 0.0                       # row 1 brings 7 positions
    a = -np.exp(np.linspace(0.0, 2.7, h))
    d = rng.standard_normal(h)
    h0 = rng.standard_normal((s_, h, p, n))
    y, h1 = G.ssd_chunk(*(jnp.asarray(v, jnp.float32)
                          for v in (xs, b, c, dt, a, d, h0)))
    want_y, want_h = _recurrence(xs, b, c, dt, a, d, h0)
    assert _max_abs(y[0], want_y[0]) < 2e-5
    assert _max_abs(y[1, :7], want_y[1, :7]) < 2e-5
    assert _max_abs(h1, want_h) < 2e-5
    # row 1's state is where its 7th position put it
    assert _max_abs(h1[1], _recurrence(xs[:, :7], b[:, :7], c[:, :7],
                                       dt[:, :7], a, d, h0)[1][1]) < 2e-5
    if groups > 1:
        # the groups matter: every head on group 0's B and C reads otherwise
        same = np.repeat(b[:, :, :1], groups, 2), np.repeat(c[:, :, :1],
                                                            groups, 2)
        assert _max_abs(y[0], _recurrence(xs, *same, dt, a, d, h0)[0][0]) \
            > 0.1


@pytest.mark.parametrize("chunk,quantum", [(16, 4), (8, 1), (32, 8)])
def test_served_tokens_are_the_references_best(toy, chunk, quantum):
    """Prefill in chunks, then decode, through the engine: every served
    token is the reference's best to within GAP_TOL. Three prompts in four
    slots: an idle slot rides every step."""
    cfg, model, get_leaf = toy
    prompts = _prompts(cfg, (37, 20, 9), seed=chunk)
    door = _serve(model, prefill_chunk=chunk, decode_quantum=quantum)
    served = _drain(door, prompts, 12)
    gaps, _ = reference.gap_below_best(cfg, get_leaf,
                                       list(zip(prompts, served)))
    assert gaps.shape == (36,) and float(_host(gaps).max()) < GAP_TOL
    pool = door.engine.pool
    assert len(pool.k_pools) == 1 == len(pool.v_pools)   # one attention layer
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (4, 16, 16, 32), (4, 3, 16 * 16 + 2 * 32)]
    assert len(pool.state) == 3
    assert pool.state[0][0].dtype == jnp.float32


def test_the_int8_control_fails_the_gap_tolerance(toy):
    cfg, model, get_leaf = toy
    prompts = _prompts(cfg, (24, 24, 24, 24), seed=7)
    served = _drain(_serve(model), prompts, 40)
    gaps, cgaps = reference.gap_below_best(
        cfg, get_leaf, list(zip(prompts, served)), control=True)
    assert float(_host(gaps).max()) < GAP_TOL < 10 * GAP_TOL \
        < float(_host(cgaps).max())


def test_a_reused_slot_and_a_preempted_request_continue_exactly(toy):
    """One slot: the second request takes the slot the first left (its
    state starts from zero inside the program). Then a request preempted
    in mid-decode: the slot is freed, recompute-on-resume rebuilds the
    state from prompt + tokens, and the stream is bit for bit the
    uninterrupted one."""
    cfg, model, _ = toy
    prompts = _prompts(cfg, (30, 18), seed=11)
    want = _drain(_serve(model), prompts, 12)
    one = _serve(model, num_slots=1)
    got = [_drain(one, [p], 12)[0] for p in prompts]
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    assert one.engine.obs.registry.get(
        "serving_state_resets_total").value() == 2

    eng = _serve(model).engine
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    while len(reqs[0].tokens) < 5:
        eng.step()
    eng.preempt(reqs[0])
    eng.run()
    assert reqs[0].preemptions == 1
    for r, b in zip(reqs, want):
        assert np.array_equal(np.asarray(r.tokens, np.int32), b)
    # prompt 0 began at position 0 twice, prompt 1 once
    assert eng.obs.registry.get("serving_state_resets_total").value() == 3


def test_a_snapshot_restores_by_recompute(toy):
    """``snapshot()`` carries no device state for any model; a restored
    engine re-prefills ``prompt + tokens``, which rebuilds the slot state:
    the streams go on bit for bit."""
    cfg, model, _ = toy
    prompts = _prompts(cfg, (26, 14), seed=13)
    want = _drain(_serve(model), prompts, 10)
    eng = _serve(model).engine
    reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    while len(reqs[0].tokens) < 4:
        eng.step()
    snap = json.loads(json.dumps(eng.snapshot()))
    fresh = ServingEngine.restore(snap, model)
    fresh.run()
    by_id = {r.req_id: r for r in fresh.completed}
    for r, b in zip(reqs, want):
        assert np.array_equal(
            np.asarray(by_id[str(r.req_id)].tokens, np.int32), b)


# ---------------------------------------------------------------- the gate
def test_gate_against_the_reference_on_hand_made_logits():
    """The two largest LOGITS are chosen; the weights are the softmax over
    the chosen values alone."""
    gate = SoftmaxTopKGate(2)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0],
                          [0.0, 0.1, 0.2, 0.3]], jnp.float32)
    sel, w, aux = gate.topk_assignments(logits)
    assert aux is None and _host(sel).tolist() == [[0, 1], [3, 2]]
    e = np.exp([[2.0, 1.0], [0.3, 0.2]])
    np.testing.assert_allclose(_host(w), e / e.sum(1, keepdims=True),
                               rtol=1e-6)
    m = {"top_k": 2}
    rsel, rw = reference.route(
        logits, {"router_w": jnp.eye(4, dtype=jnp.float32)}, m)
    np.testing.assert_array_equal(_host(rsel), _host(sel))
    np.testing.assert_allclose(_host(rw), _host(w), rtol=1e-6)
    with pytest.raises(NotImplementedError, match="bias"):
        gate.topk_assignments(logits, jnp.zeros(4))


# ------------------------------------------- an expert layer that is told
def _core_case(t=12, k=3, e=8, width=4, seed=0):
    rng = np.random.default_rng(seed)
    xt = jnp.asarray(rng.standard_normal((t, width)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.permutation(e)[:k] for _ in range(t)]))
    w = jnp.asarray(rng.uniform(0.1, 1.0, (t, k)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((e, width, width)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((e, width, width)), jnp.float32)
    return xt, ids, w, w1, w2


@pytest.mark.parametrize("split", [(0, 8), (0, 4), (4, 4), (2, 5), (7, 1)],
                         ids=lambda s: f"held_{s[0]}_{s[1]}")
def test_grouped_core_computes_only_what_it_holds(split):
    """``held=(lo, n)``: ids and weights range over all 8 experts, the two
    products over the ``n`` held slabs; a row routed elsewhere adds
    exactly zero; the rows reported are the held experts'."""
    lo, n = split
    xt, ids, w, w1, w2 = _core_case()
    y, rows = moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[lo:lo + n], w2[lo:lo + n], jnp.tanh, held=(lo, n))
    inside = (ids >= lo) & (ids < lo + n)
    want = sum(jnp.where(inside[:, j, None], w[:, j, None] * jnp.einsum(
        "tf,tfm->tm", jnp.tanh(jnp.einsum("tm,tmf->tf", xt, w1[ids[:, j]])),
        w2[ids[:, j]]), 0.0) for j in range(3))
    np.testing.assert_allclose(_host(y), _host(want), atol=1e-5)
    assert _host(rows).tolist() == [
        int((_host(ids) == e).sum()) for e in range(lo, lo + n)]
    none = ~_host(inside).any(axis=1)
    assert (_host(y)[none] == 0.0).all()


def test_grouped_core_shares_add_up_and_all_held_is_the_default():
    xt, ids, w, w1, w2 = _core_case(seed=1)
    full, rows = moe_layer.grouped_expert_ffn(xt, ids, w, w1, w2, jnp.tanh)
    same, _ = moe_layer.grouped_expert_ffn(xt, ids, w, w1, w2, jnp.tanh,
                                           held=(0, 8))
    np.testing.assert_array_equal(_host(full), _host(same))
    parts = [moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[lo:lo + 4], w2[lo:lo + 4], jnp.tanh, held=(lo, 4))
        for lo in (0, 4)]
    np.testing.assert_allclose(_host(parts[0][0] + parts[1][0]),
                               _host(full), atol=1e-5)
    assert _host(jnp.concatenate([p[1] for p in parts])).tolist() \
        == _host(rows).tolist()
    with pytest.raises(ValueError, match="weight slabs"):
        moe_layer.grouped_expert_ffn(xt, ids, w, w1, w2, jnp.tanh,
                                     held=(0, 4))


def test_grouped_core_tiles_the_positions_and_caps_no_row(monkeypatch):
    """Past the byte bound of the sorted-rows buffer the positions go
    through in equal tiles: the same numbers, every row counted."""
    xt, ids, w, w1, w2 = _core_case(t=12, seed=2)
    want, rows = moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[:4], w2[:4], jnp.tanh, held=(0, 4))
    # 12 x 3 rows x 4 values x 4 bytes = 576: five tiles asked, six taken
    monkeypatch.setattr(moe_layer, "_SORTED_ROWS_BYTES", 128)
    got, trows = moe_layer.grouped_expert_ffn(
        xt, ids, w, w1[:4], w2[:4], jnp.tanh, held=(0, 4))
    np.testing.assert_allclose(_host(got), _host(want), atol=1e-6)
    assert _host(trows).tolist() == _host(rows).tolist()


def test_the_shares_add_up_to_the_uncut_layer(toy):
    """The tie of the share to the model: the routed parts of shares
    [0, 4) and [4, 8), with what every chip computes alike, the shared
    MLP, counted once, equal the uncut reference layer's feed-forward."""
    cfg, _, _ = toy
    whole = dict(cfg, num_local_experts=8, held_experts=[0, 8])
    leaves = _slow_leaves(whole, seed=5)
    m = reference.dims(whole)
    lp = {n.split(".", 1)[1]: leaves[n]
          for n in reference.layer_leaves(whole, 0)}
    v = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 128)),
                    jnp.float32)
    flat = v.reshape(18, 128)
    shared = reference.swiglu(flat, lp["s_in"], lp["s_out"], False)
    uncut = reference.routed_experts(flat, lp, m) + shared
    # the reference's own shares
    parts = [reference.routed_experts(
        flat, dict(lp, e_in=lp["e_in"][lo:lo + 4],
                   e_out=lp["e_out"][lo:lo + 4]), m, held=(lo, lo + 4))
        for lo in (0, 4)]
    assert _max_abs(parts[0] + parts[1] + shared, uncut) < 1e-5
    # the program's: two chips' blocks, each told what it holds
    total = shared
    for lo in (0, 4):
        pcfg = G.GraniteMoeHybridConfig.tiny(
            hidden_size=128, intermediate_size=32, mamba_n_heads=16,
            mamba_d_head=16, held_experts=(lo, 4))
        block = G.GraniteMoeHybridMoE(pcfg)
        block.router.layer.weight._value = lp["router_w"]
        block.input_linear.weight._value = lp["e_in"][lo:lo + 4]
        block.output_linear.weight._value = lp["e_out"][lo:lo + 4]
        part = block(paddle.to_tensor(v))._value.reshape(18, 128)
        assert _max_abs(part, parts[lo // 4]) < 1e-5
        chosen = _host(reference.route(flat, lp, m)[0])
        assert _host(block.rows_per_expert).sum() == (
            (chosen // 4) == lo // 4).sum()
        total = total + part
    assert _max_abs(total, uncut) < 2e-5 and _max_abs(uncut) > 0.1


# ---------------------------------------------------------------- the pool
def _state_pool(**kw):
    return PagedKVCachePool(
        num_blocks=8, block_size=4, num_kv_heads=2, head_dim=6,
        num_layers=1, dtype=jnp.bfloat16,
        state={"slots": 3, "layers": 2,
               "arrays": [((4, 2, 5), "float32"), ((3, 7), None)]}, **kw)


def test_slot_state_accounting():
    pool = _state_pool()
    assert [[(tuple(a.shape), str(a.dtype)) for a in layer]
            for layer in pool.state] == [
        [((3, 4, 2, 5), "float32"), ((3, 3, 7), "bfloat16")]] * 2
    per_slot = 2 * (4 * 2 * 5 * 4 + 3 * 7 * 2)
    assert pool.state_bytes_per_slot() == per_slot
    assert pool.bytes_per_token() == 2 * 2 * 6 * 2     # the K/V side alone
    pool.ensure("__scratch__", 1)
    pool.ensure("a", 9)                                # three blocks
    st = pool.fragmentation_stats()
    assert st["state_bytes_per_slot"] == per_slot and st["state_slots"] == 3
    assert st["bytes_in_use"] == 4 * 4 * 48 + per_slot  # scratch holds none
    # adopt / commit_like / the donated sides carry the slot side
    sides = pool.arrays()
    assert len(jax.tree_util.tree_leaves(sides)) == 2 + 4
    pool.adopt(*sides)
    pool.commit_like(pool.k_pools[0])
    assert len(pool.state) == 2
    pool.free("a")
    assert pool.bytes_in_use() == 4 * 48


def test_a_pool_without_state_layers_has_an_empty_side():
    pool = PagedKVCachePool(num_blocks=8, block_size=4, num_kv_heads=2,
                            head_dim=6, num_layers=3, dtype=jnp.float32)
    assert pool.state == () and pool.state_bytes_per_slot() == 0
    assert len(jax.tree_util.tree_leaves(pool.arrays())) == 6
    pool.ensure("a", 5)
    assert pool.bytes_in_use() == 2 * 4 * pool.bytes_per_token()
    pool.adopt(*pool.arrays()[:4])
    assert pool.state == ()


@pytest.mark.parametrize("kwargs", [{"kv_dtype": "int8"}, {"mesh": True},
                                    {"prefix_cache": True}])
def test_state_pool_refusals(kwargs):
    if kwargs.get("mesh"):
        kwargs = {"mesh": jax.sharding.Mesh(_host(jax.devices()[:2]),
                                            ("mp",))}
    with pytest.raises(NotImplementedError, match="slot state"):
        _state_pool(**kwargs)


# ------------------------------------------------------------ the refusals
def _tiny():
    paddle.seed(0)
    return G.GraniteMoeHybridForCausalLM(G.GraniteMoeHybridConfig.tiny())


@pytest.mark.parametrize("kwargs,name", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"tp": 2}, "tp > 1"),
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"spec_draft": "llama"}, "spec_draft"),
    ({"spec_draft": "state"}, "spec_draft"),
])
def test_refusals_by_name(kwargs, name):
    """What a slot's recurrent state cannot do yet is refused by name;
    nothing is silently ignored."""
    model, kwargs = _tiny(), dict(kwargs)
    if kwargs.get("spec_draft") == "llama":
        kwargs["spec_draft"] = LlamaForCausalLM(
            LlamaConfig.tiny(tensor_parallel=False))
    elif kwargs.get("spec_draft") == "state":
        model, kwargs["spec_draft"] = LlamaForCausalLM(
            LlamaConfig.tiny(tensor_parallel=False)), _tiny()
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(model, num_slots=2, block_size=8, max_context=32,
                      **kwargs)
    assert name in str(err.value) and "state-space" in str(err.value)


@pytest.mark.parametrize("overrides,what", [
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"position_embedding_type": "rope"}, "position"),
    ({"tie_word_embeddings": False}, "untied"),
    ({"mamba_proj_bias": True}, "bias"),
    ({"layer_types": ("mamba", "window", "attention", "mamba")},
     "layer_types"),
    ({"sliding_window": 16}, "sliding_window"),
])
def test_the_config_refuses_what_the_model_does_not_compute(overrides, what):
    with pytest.raises(NotImplementedError, match=what):
        G.GraniteMoeHybridForCausalLM(
            G.GraniteMoeHybridConfig.tiny(**overrides))


def test_the_published_preset_counts_the_issues_parameters():
    """``granite_4_0_h_small()`` is the source's config: one period with
    experts 0-35 held counts the cut's 4,962,732,672 parameters (from
    shapes: nothing is allocated)."""
    cfg = G.GraniteMoeHybridConfig.granite_4_0_h_small(
        num_hidden_layers=10, held_experts=(0, 36))
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    full = G.GraniteMoeHybridConfig.granite_4_0_h_small()
    assert full.layer_types.count("attention") == 4
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert cfg.mamba_d_inner == 8192 and cfg.mamba_conv_dim == 8448
    shapes = jax.eval_shape(lambda: [
        p._value for _, p in
        G.GraniteMoeHybridForCausalLM(cfg).named_parameters()])
    assert sum(int(np.prod(s.shape)) for s in shapes) == 4_962_732_672


# ------------------------------------------------------ spans and counters
def test_counters_spans_and_scopes(toy):
    cfg, model, _ = toy
    rec = TraceRecorder.process()
    first = rec.next_id()
    door = _serve(model)
    _drain(door, _prompts(cfg, (20, 9)), 9)
    eng = door.engine
    reg = eng.obs.registry
    rows, off, steps = (reg.get(f"serving_moe_{k}_total").value()
                        for k in ("routed_rows", "offshare_rows",
                                  "layer_steps"))
    quanta = eng.stats["decode_quanta"]
    # four expert layers, four steps a quantum, four slots x top 3 choices
    # of which the held half got `rows`
    assert steps == quanta * 4 * 4 and rows + off == steps * 4 * 3
    assert 0 < rows < steps * 4 * 3
    spans = [e for e in rec.events
             if e.get("args", {}).get("id", -1) >= first]
    collect = [e["args"] for e in spans if e["name"] == "engine.decode"
               and e["args"].get("half") == "collect"]
    assert sum(a["moe_rows"] for a in collect) == rows
    assert sum(a["moe_offshare_rows"] for a in collect) == off
    mixed = [e["args"] for e in spans if e["name"] == "engine.mixed"]
    assert mixed and all(
        a["moe_rows"] + a["moe_offshare_rows"] == 4 * a["bucket"] * 3 * 4
        for a in mixed)
    stats = eng.engine_stats()["pool"]
    per_slot = 3 * (16 * 16 * 32 * 4 + 3 * 320 * 4)
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["state_slots"] == 4
    assert stats["bytes_per_token"] == 2 * 2 * 32 * 4   # one K/V layer
    assert reg.get("serving_state_bytes_per_slot").value(
        pool="target") == per_slot
    assert reg.get("serving_state_resets_total").value() == 2
    # the cost ledger's 2N counts, of a layer's 4 held experts, the
    # 3 x 4 / 8 = 1 a token multiplies on average
    n = sum(int(p._value.size) for _, p in model.named_parameters())
    assert eng.obs.ledger.flops_per_token == 2.0 * (
        n - 2048 * 128 - 4 * 3 * 3 * 128 * 32)
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        text = step.lower(*args).as_text(debug_info=True)
        for scope in ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out",
                      "moe.router", "moe.experts", "moe.shared"):
            assert scope in text, scope


def test_a_model_without_state_layers_adds_no_aval():
    """Llama's programs take an empty slot side: no aval, so its graphs
    are what they were (the goldens hold that), and no counter moves."""
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        assert args[4] == () and args[2] == () == args[3]
        n_pool = len(jax.tree_util.tree_leaves(args[:5]))
        assert n_pool == 2 * model.config.num_hidden_layers
        assert step.n_donatable == n_pool
    assert eng.engine_stats()["pool"]["state_bytes_per_slot"] == 0
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    eng.run()
    assert eng.obs.registry.get("serving_state_resets_total").value() == 0
