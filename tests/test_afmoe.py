"""The AFMoE-shaped decoder (window and full attention layers mixed three to
one, gated attention, per-head q/k norms, four norms a layer, routed experts
beside a shared one) on the normal serving path, against the benchmark's
plain reference (``benchmark/reference/afmoe.py``: float32, HIGHEST, dense
masks by position, no cache, no ring), on the toy configuration in float32
with the benchmark's seeded weights.

Sizes: window 8, prefill chunk 4, blocks of 4, so a window layer's ring has
12 rows; contexts reach 40 and more, so a ring wraps three times.

Tolerances: program and reference compute the same float32 numbers in
another order (the program fuses gate|up, sorts rows by expert, folds
attention tiles, reads keys out of a ring), so they differ by summation
order only: logits of magnitude ~1 agree to 2e-5. The reference's
int8-operand control moves the same logits by > 100 x that and a served
token's gap to ~1e-2, so each tolerance below is asserted to be tight enough
that the control fails it.
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
from paddle_tpu.incubate.distributed.models.moe import SigmoidTopKGate
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM, PagedKVCachePool
from paddle_tpu.nlp import afmoe as A
from paddle_tpu.nlp import paged_attention as PA
from paddle_tpu.nlp.deepseek_v3 import (
    DeepseekV3Config, DeepseekV3ForCausalLM, DeepseekV3MoE)
from paddle_tpu.nlp.granitemoehybrid import (
    GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)
from paddle_tpu.nlp.llama import _paged_write
from paddle_tpu.obs.registry import MetricsRegistry
from paddle_tpu.obs.trace import TraceRecorder
from paddle_tpu.serving import ServingEngine, no_shed_policy
from paddle_tpu.serving import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.families import afmoe as family  # noqa: E402

reference = family.reference
SEED = 2147483777
LOGIT_TOL = 2e-5     # summation order in float32, logits of magnitude ~1
GAP_TOL = 1e-4       # a served token lies this close to the reference's best
WINDOW, CHUNK, BLOCK, RING = 8, 4, 4, 12


def _host(x, dtype=None):
    """A device value on the host, said out loud."""
    return np.asarray(jax.device_get(x), dtype)


def _max_abs(a, b=0.0):
    return float(np.abs(_host(a) - _host(b)).max())


@pytest.fixture(scope="module")
def toy():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "toy-window-moe.json")) as f:
        cfg = json.load(f)
    cfg["sliding_window"] = WINDOW
    model = family.build_model(cfg)
    family.install_weights(model, cfg, SEED)
    model.eval()
    return cfg, model, family.leaf_reader(cfg, SEED)


def _serve(model, **kw):
    kw = {"num_slots": 4, "block_size": BLOCK, "num_blocks": 96,
          "max_context": 96, "prefill_chunk": CHUNK, "decode_quantum": 4,
          **kw}
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
    """Two sequences of 40 tokens (five windows) by the model's dense masks
    against the reference's blocked ones."""
    cfg, model, get_leaf = toy
    ids = np.stack(_prompts(cfg, (40, 40)))
    ref = reference.logits(cfg, get_leaf, ids)
    got = model(paddle.to_tensor(ids))._value
    assert _max_abs(ref) > 0.5
    assert _max_abs(ref, got) < LOGIT_TOL
    # the tolerance is earned: the int8-operand control fails it
    control = reference.logits(cfg, get_leaf, ids, control=True)
    assert _max_abs(ref, control) > 100 * LOGIT_TOL


def test_the_window_is_what_the_reference_computes(toy):
    """Changing the FIRST token moves no logit of a window-only stack eight
    or more positions on, and moves them with a full layer in it: the
    reference's window is a window."""
    cfg, _, get_leaf = toy
    ids = np.stack(_prompts(cfg, (20,)))
    other = ids.copy()
    other[0, 0] = (ids[0, 0] + 1) % cfg["vocab_size"] or 1
    only = dict(cfg, num_hidden_layers=1, layer_types=["sliding_attention"])
    a, b = (reference.logits(only, get_leaf, x)[0] for x in (ids, other))
    assert _max_abs(a[WINDOW:], b[WINDOW:]) == 0.0 < _max_abs(a[:WINDOW],
                                                             b[:WINDOW])
    full = dict(only, layer_types=["full_attention"])
    a, b = (reference.logits(full, get_leaf, x)[0] for x in (ids, other))
    assert _max_abs(a[WINDOW:], b[WINDOW:]) > 100 * LOGIT_TOL


@pytest.mark.parametrize("q_block", [512, 7])
def test_the_references_blocks_are_a_dense_mask(q_block):
    """``reference.attention`` in blocks of queries against the one dense
    mask it stands for, both layer kinds."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 29, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 29, 2, 8)), jnp.float32)
            for _ in range(2))
    t, u = np.arange(29)[:, None], np.arange(29)[None, :]
    for window in (0, 5):
        seen = (u <= t) & ((u > t - window) if window else True)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", q.reshape(2, 29, 2, 2, 8), k,
                        precision="highest") / np.sqrt(8.0)
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        want = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                          precision="highest").reshape(2, 29, 32)
        got = reference.attention(q, k, v, window, q_block=q_block)
        assert _max_abs(got, want) < 1e-6


class _Paged:
    """The engine's two bodies driven by hand over a pool of three slots,
    so that a test reads LOGITS where the engine hands out tokens."""

    def __init__(self, model, slots=3):
        layout = model.paged_cache_layout()
        kinds = layout["layers"]
        self.model, self.slots = model, slots
        self.pool = PagedKVCachePool(
            48, BLOCK, layout["num_kv_heads"], layout["head_dim"],
            num_layers=kinds.count("kv"), dtype=jnp.float32,
            state={"slots": slots, "layers": kinds.count("state"),
                   "arrays": layout["state"],
                   "ring_tokens": PA.ring_tokens(layout["window"], CHUNK,
                                                 BLOCK)})
        self.scratch = self.pool.ensure("__scratch__", 1)[0]
        self.lens = np.zeros(slots, np.int32)

    def _tables(self, grow):
        for r, n in enumerate(grow):
            self.pool.ensure(f"r{r}", int(self.lens[r]) + int(n))
        return self.pool.block_table_array(
            [f"r{r}" for r in range(self.slots)], pad_to=16)

    def chunk(self, ids, counts):
        """ids (S, C), counts (S,): one mixed step; logits (S, V)."""
        counts = np.asarray(counts, np.int32)
        tables = self._tables(counts)
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
        tables = self._tables(live.astype(np.int32))
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
    """Fill slot ``row``'s rings with ``value`` in every window layer."""
    pool.state = tuple(tuple(a.at[row].set(value) for a in layer)
                       for layer in pool.state)


@pytest.mark.parametrize("sizes", [(4,) * 9, (1, 3, 4, 2, 4, 4, 3, 4, 4, 4, 3),
                                   (2,) * 18],
                         ids=["divides", "uneven", "half_chunks"])
def test_chunked_prefill_then_decode_through_rings_and_blocks(toy, sizes):
    """A 36-token prompt in chunks (of the whole chunk length, of lengths
    that do not divide it, of half the length), then 12 decode steps,
    teacher-forced, to a context of 48 (four times the ring): every logit
    the program hands out is the reference's full pass's. Beside it a row
    that is never live keeps its rings bit for bit, a row rides along
    DECODING in the mixed steps (one position a step), and a new request in
    a used slot needs no reset."""
    cfg, model, get_leaf = toy
    assert sum(sizes) == 36
    seq, other = _prompts(cfg, (48, 30), seed=3)
    ref = reference.logits(cfg, get_leaf, seq[None])[0]
    ref_other = reference.logits(cfg, get_leaf, other[None])[0]
    run = _Paged(model)
    assert [tuple(a.shape) for a in run.pool.state[0]] == [
        (3, RING, 2 * 32)] * 2 and len(run.pool.state) == 4
    _stamp(run.pool, 1, 7.0)
    # row 2 is prefilled first, alone, so that it DECODES beside row 0
    ids = np.zeros((3, CHUNK), np.int32)
    for lo in range(0, 12, CHUNK):
        ids[2] = other[lo:lo + CHUNK]
        logits = run.chunk(ids, [0, 0, CHUNK])
    assert _max_abs(logits[2], ref_other[11]) < LOGIT_TOL
    at = 0
    for j, n in enumerate(sizes):
        ids = np.zeros((3, CHUNK), np.int32)
        ids[0, :n] = seq[at:at + n]
        riding = 12 + j < 30
        ids[2, 0] = other[12 + j] if riding else 0
        logits = run.chunk(ids, [n, 0, int(riding)])
        at += n
        assert _max_abs(logits[0], ref[at - 1]) < LOGIT_TOL
        if riding:
            assert _max_abs(logits[2], ref_other[12 + j]) < LOGIT_TOL
    held = jax.tree_util.tree_map(lambda a: _host(a[2]), run.pool.state)
    for j in range(12):          # row 2 rides along masked, row 1 idle
        logits = run.decode([seq[36 + j], 0, 5], [True, False, False])
        assert _max_abs(logits[0], ref[36 + j]) < LOGIT_TOL
    for layer, want in zip(run.pool.state, held):
        for a, w in zip(layer, want):
            np.testing.assert_array_equal(_host(a[2]), w)   # masked
            assert float(_host(a[1]).min()) == 7.0 == float(
                _host(a[1]).max())                          # never live
    # the slot of row 0 is handed to a new request with no reset: its rings
    # still hold the old request's keys, which positions alone keep unseen
    run.pool.free("r0")
    run.lens[0] = 0
    for lo in range(0, 16, CHUNK):
        ids = np.zeros((3, CHUNK), np.int32)
        ids[0] = other[lo:lo + CHUNK]
        logits = run.chunk(ids, [CHUNK, 0, 0])
        assert _max_abs(logits[0], ref_other[lo + CHUNK - 1]) < LOGIT_TOL


@pytest.mark.parametrize("chunk,quantum", [(4, 4), (8, 1), (16, 8)])
def test_served_tokens_are_the_references_best(toy, chunk, quantum):
    """Prefill in chunks, then decode, through the engine: every served
    token is the reference's best to within GAP_TOL. Three prompts in four
    slots: an idle slot rides every step; contexts reach 49."""
    cfg, model, get_leaf = toy
    prompts = _prompts(cfg, (37, 20, 9), seed=chunk)
    door = _serve(model, prefill_chunk=chunk, decode_quantum=quantum)
    served = _drain(door, prompts, 12)
    gaps, _ = reference.gap_below_best(cfg, get_leaf,
                                       list(zip(prompts, served)))
    assert gaps.shape == (36,) and float(_host(gaps).max()) < GAP_TOL
    pool = door.engine.pool
    assert len(pool.k_pools) == 1 == len(pool.v_pools)   # one full layer
    ring = -(-(WINDOW + chunk) // BLOCK) * BLOCK
    assert [tuple(a.shape) for a in pool.state[0]] == [(4, ring, 2 * 32)] * 2
    assert len(pool.state) == 4 and pool.ring_tokens == ring


def test_the_int8_control_fails_the_gap_tolerance(toy):
    cfg, model, get_leaf = toy
    prompts = _prompts(cfg, (24, 24, 24, 24), seed=7)
    served = _drain(_serve(model), prompts, 40)
    gaps, cgaps = reference.gap_below_best(
        cfg, get_leaf, list(zip(prompts, served)), control=True)
    assert float(_host(gaps).max()) < GAP_TOL < 10 * GAP_TOL \
        < float(_host(cgaps).max())


# ------------------------------------------------- what a query may see
def _one_hot_keys(slots, positions, hk, d):
    """Keys of zeros (every score equal) and values that name their
    position: position ``s`` holds the unit vector ``e_s`` in every head,
    so an attention output reads 1 / |seen| at every seen position."""
    v = np.zeros((slots, positions, hk, d), np.float32)
    v[:, np.arange(positions), :, np.arange(positions)] = 1.0
    return jnp.zeros((slots, positions, hk, d), jnp.float32), jnp.asarray(v)


def _seen(out):
    """The positions an output of `_one_hot_keys` values saw, per row."""
    out = _host(out)
    sets = []
    for row in out.reshape(out.shape[0], -1, out.shape[-1]):
        at = np.nonzero(row[0] > 0)[0]
        np.testing.assert_allclose(row[:, at], 1.0 / len(at), rtol=1e-6)
        sets.append(at.tolist())
    return sets


def test_a_query_sees_exactly_its_window_and_a_full_layer_everything():
    """Hand-made at the window's edge, W = 8 over a ring of 12: after 30
    positions the decode query ``t`` = 29 sees 22 .. 29 and not 21; the
    chunk queries 26 .. 29 see ``t - 7 .. t`` each, the first of them a key
    the chunk's own writes came within a row of overwriting. A full layer
    over blocks sees 0 .. t. A row one position long sees itself alone,
    whatever an earlier request left in the ring."""
    slots, hk, g, d = 2, 2, 2, 32
    k, v = _one_hot_keys(slots, 30, hk, d)
    ring_k = jnp.full((slots, RING, hk * d), 9.0)    # an old request's keys
    ring_v = jnp.full((slots, RING, hk * d), 9.0)
    pos = jnp.broadcast_to(jnp.arange(30)[None, :], (slots, 30))
    lens = jnp.asarray([26, 1], jnp.int32)           # row 1: one position
    # row 0 writes 0 .. 25, row 1 position 0 only
    ring_k, ring_v = PA.ring_write(ring_k, ring_v, k, v, pos,
                                   pos < lens[:, None])
    q = jnp.ones((slots, hk * g, d), jnp.float32)
    assert _seen(PA.ring_decode_attn(q, ring_k, ring_v, lens, WINDOW)) == [
        list(range(18, 26)), [0]]
    # the chunk 26 .. 29 of row 0 (row 1 brings nothing), then its queries
    base, counts = jnp.asarray([26, 1], jnp.int32), jnp.asarray([4, 0])
    at = base[:, None] + jnp.arange(4)[None, :]
    ring_k, ring_v = PA.ring_write(
        ring_k, ring_v, k[:, 26:30], v[:, 26:30], at,
        jnp.arange(4)[None, :] < counts[:, None])
    out = PA.ring_chunk_attn(jnp.ones((slots, 4, hk * g, d), jnp.float32),
                             ring_k, ring_v, base, counts, WINDOW)
    for j in range(4):
        assert _seen(out[:1, j])[0] == list(range(26 + j - 7, 26 + j + 1))
    assert _seen(PA.ring_decode_attn(q, ring_k, ring_v,
                                     jnp.asarray([30, 1]), WINDOW)) == [
        list(range(22, 30)), [0]]
    # a tile a query sees nothing of does not count (one ring row a tile)
    tiled = PA._CHUNK_SCORE_BYTES
    PA._CHUNK_SCORE_BYTES = slots * hk * g * 4 * 4
    try:
        again = PA.ring_chunk_attn(
            jnp.ones((slots, 4, hk * g, d), jnp.float32), ring_k, ring_v,
            base, counts, WINDOW)
    finally:
        PA._CHUNK_SCORE_BYTES = tiled
    assert _max_abs(again[0], out[0]) < 1e-6
    # the full layer: blocks of 4, the same keys, every position <= t
    pool = PagedKVCachePool(16, BLOCK, hk, d, num_layers=1,
                            dtype=jnp.float32)
    pool.ensure("a", 30)
    pool.ensure("b", 30)
    tables = pool.block_table_array(["a", "b"], pad_to=8)
    blk = jnp.take_along_axis(tables, pos // BLOCK, axis=1)
    kc, vc, _, _ = _paged_write(k, v, blk, pos % BLOCK,
                                (pool.k_pools[0], pool.v_pools[0], None, None))
    out = PA._paged_chunk_attn(jnp.ones((slots, 4, hk * g, d), jnp.float32),
                               kc, vc, tables, jnp.asarray([26, 26]))
    for j in range(4):
        assert _seen(out[:1, j])[0] == list(range(26 + j + 1))
    assert _seen(PA._xla_paged_decode_attn(
        q, kc, vc, tables, jnp.asarray([30, 30])))[0] == list(range(30))


# -------------------- the Pallas chunk kernel over a ring (interpreted here)
# by case: (C, HK, G, ring rows, cached tokens a slot, valid positions a
# slot, key tile, query tile); W = 8 throughout
_RING_CASES = {
    "g1": (4, 2, 1, 12, (13, 38), (4, 4), 4, None),
    "g4": (4, 2, 4, 12, (13, 38), (4, 4), 4, None),
    "g7": (4, 1, 7, 12, (13, 38), (4, 4), 4, None),
    "g8": (4, 1, 8, 12, (13, 38), (4, 4), 4, None),
    "wrapped_three_times_over_stale_rows": (4, 2, 2, 12, (40, 37), (4, 3),
                                            4, None),
    "a_base_of_zero": (4, 2, 2, 12, (0, 0), (4, 2), 4, None),
    "an_idle_slot": (4, 2, 2, 12, (17, 0, 40), (4, 0, 4), 4, None),
    "bases_off_the_tiles": (4, 2, 2, 12, (5, 9, 22), (4, 4, 1), 4, None),
    "a_chunk_off_the_sublanes": (5, 2, 2, 16, (7, 30), (5, 4), 4, None),
    "one_key_tile": (4, 2, 2, 12, (13, 38), (4, 4), None, None),
    "two_query_tiles": (24, 2, 2, 32, (0, 45), (24, 17), 8, 16),
}


def _written_ring(rng, slots, r, hk, d, upto, dtype):
    """Rings that an EARLIER request filled with its own rows, then
    positions ``0 .. upto[s] - 1`` of each slot written in turn, so that a
    long context wraps the ring over stale rows and over its own; beside
    them every position's key and value (S, P, HK, D) for a dense
    reference."""
    top = max(max(upto), 1)
    k = jnp.asarray(rng.standard_normal((slots, top, hk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((slots, top, hk, d)), dtype)
    ring_k = jnp.asarray(rng.standard_normal((slots, r, hk * d)), dtype)
    ring_v = jnp.asarray(rng.standard_normal((slots, r, hk * d)), dtype)
    lens = jnp.asarray(upto)[:, None]
    for lo in range(0, top, r):
        pos = jnp.broadcast_to(jnp.arange(lo, min(lo + r, top))[None, :],
                               (slots, min(lo + r, top) - lo))
        ring_k, ring_v = PA.ring_write(
            ring_k, ring_v, k[:, lo:lo + r], v[:, lo:lo + r], pos, pos < lens)
    return ring_k, ring_v, k, v


def _dense_window_attention(q, k, v, base, window):
    """(S, C, H, D) queries over every position's keys (S, P, HK, D):
    query j of a row sees ``base + j - window < p <= base + j``; a plain
    float64 softmax, K and V repeated over the query heads."""
    q, k, v = (_host(a.astype(jnp.float32)).astype(np.float64)
               for a in (q, k, v))
    rep = q.shape[2] // k.shape[2]
    k, v = np.repeat(k, rep, axis=2), np.repeat(v, rep, axis=2)
    logits = np.einsum("schd,sphd->shcp", q, k) / np.sqrt(q.shape[-1])
    t = np.asarray(base)[:, None] + np.arange(q.shape[1])[None, :]
    p = np.arange(k.shape[1])[None, None, :]
    seen = (p <= t[..., None]) & (p > t[..., None] - window)
    logits = np.where(seen[:, None], logits, -np.inf)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    return np.einsum("shcp,sphd->schd", w / w.sum(-1, keepdims=True), v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_RING_CASES))
def test_the_chunk_kernel_over_a_ring_matches_the_xla_fold(case, dtype):
    """``ring_chunk_attention`` over the ring AS STORED against the fold it
    replaces on a TPU, at the same precision on EVERY query (one past a
    row's count reads what the fold reads), and on the queries that count
    against a dense float64 softmax over the positions themselves: float32
    only reorders sums, bf16 rounds the operands and ``p`` to 2**-8."""
    from paddle_tpu.ops.pallas.chunk_attention import ring_chunk_attention

    c, hk, g, r, base, counts, bk, bq = _RING_CASES[case]
    d, slots = 16, len(base)
    rng = np.random.default_rng(len(case))
    upto = [b + n for b, n in zip(base, counts)]
    ring_k, ring_v, k, v = _written_ring(rng, slots, r, hk, d, upto, dtype)
    q = jnp.asarray(rng.standard_normal((slots, c, hk * g, d)), dtype)
    base, counts = jnp.asarray(base, jnp.int32), jnp.asarray(counts, jnp.int32)
    got = ring_chunk_attention(q, ring_k, ring_v, base, counts, WINDOW,
                               1.0 / np.sqrt(d), block_q=bq, block_k=bk)
    fold = PA._xla_ring_chunk_attn(q, ring_k, ring_v, base, counts, WINDOW)
    assert got.shape == fold.shape == q.shape and got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    got = _host(got.astype(jnp.float32))
    assert np.isfinite(got).all()
    assert _max_abs(got, fold.astype(jnp.float32)) < tol
    dense = _dense_window_attention(
        q, jnp.pad(k, ((0, 0), (0, c), (0, 0), (0, 0))),
        jnp.pad(v, ((0, 0), (0, c), (0, 0), (0, 0))), base, WINDOW)
    counted = _host(jnp.arange(c)[None, :] < counts[:, None])
    assert np.abs(dense[counted]).max() > 0.5
    assert np.abs(got[counted] - dense[counted]).max() < tol


@pytest.mark.parametrize("tiling", ["ring_of_three_tiles", "ring_of_one_tile",
                                    "ring_of_six_tiles", "table_by_blocks",
                                    "table_in_one_tile"])
def test_the_kernel_sees_its_bounds_to_one_key(tiling):
    """The hand-made edge again, through the kernel: W = 8 over a ring of
    12 whose old rows another request left, the chunk's queries 26 .. 29
    see ``t - 7 .. t`` each and not ONE key more on either side, however
    the ring is cut into key tiles; over a table they see ``0 .. t``. A
    row that brings nothing (count 0) sees nothing."""
    from paddle_tpu.ops.pallas import chunk_attention as kernel

    slots, hk, g, d = 2, 2, 2, 32
    k, v = _one_hot_keys(slots, 30, hk, d)
    pos = jnp.broadcast_to(jnp.arange(30)[None, :], (slots, 30))
    q = jnp.ones((slots, 4, hk * g, d), jnp.float32)
    base = jnp.asarray([26, 1], jnp.int32)
    if tiling.startswith("ring"):
        ring_k = jnp.full((slots, RING, hk * d), 9.0)
        ring_v = jnp.full((slots, RING, hk * d), 9.0)
        upto = jnp.asarray([30, 0])[:, None]          # row 1: an idle slot
        for lo in (0, 12, 24):
            ring_k, ring_v = PA.ring_write(
                ring_k, ring_v, k[:, lo:lo + 12], v[:, lo:lo + 12],
                pos[:, lo:lo + 12], pos[:, lo:lo + 12] < upto)
        out = kernel.ring_chunk_attention(
            q, ring_k, ring_v, jnp.asarray([26, 0]), jnp.asarray([4, 0]),
            WINDOW,
            1.0 / np.sqrt(d), block_k={"three": 4, "one": 12, "six": 2}[
                tiling.split("_")[2]])
        for j in range(4):
            assert _seen(out[:1, j])[0] == list(range(26 + j - 7,
                                                      26 + j + 1))
        assert _max_abs(out[1]) == 0.0
        return
    pool = PagedKVCachePool(16, BLOCK, hk, d, num_layers=1,
                            dtype=jnp.float32)
    pool.ensure("a", 30)
    pool.ensure("b", 30)
    tables = pool.block_table_array(["a", "b"], pad_to=8)
    blk = jnp.take_along_axis(tables, pos // BLOCK, axis=1)
    kc, vc, _, _ = _paged_write(k, v, blk, pos % BLOCK,
                                (pool.k_pools[0], pool.v_pools[0], None, None))
    out = kernel.paged_chunk_attention(
        q, kc, vc, tables, base, 1.0 / np.sqrt(d),
        block_k=BLOCK if tiling == "table_by_blocks" else None)
    for j in range(4):
        assert _seen(out[:1, j])[0] == list(range(26 + j + 1))
        assert _seen(out[1:, j])[0] == list(range(1 + j + 1))


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_a_window_models_mixed_program_counts_its_chunk_attention_route(
        toy, route, request, chunk_programs):
    """Tracing one mixed program raises the counter by ONE on its route's
    label, however many ring and table layers ask (four and one here)."""
    _, model, _ = toy
    if route == "kernel":
        request.getfixturevalue("pallas_forced")
    before = chunk_programs()
    step, args = _serve(model).engine.mixed_step_target()
    step.lower(*args)
    other = "xla" if route == "kernel" else "kernel"
    after = chunk_programs()
    assert after[route] == before[route] + 1
    assert after[other] == before[other]


def test_the_mixed_step_through_the_kernel_serves_the_folds_streams(
        toy, request, chunk_programs):
    """The engine's mixed step with the kernel route forced (prompts of
    several chunks that wrap the rings three times, rows of uneven
    length, an idle slot, a slot reused over another request's rows)
    serves, token for token, what the XLA route serves; each engine's
    programs are counted under their own route."""
    cfg, model, _ = toy
    prompts = _prompts(cfg, (41, 22, 9), seed=31)

    def serve():
        door = _serve(model, num_slots=3)
        first = _drain(door, prompts, 6)
        return door, first + _drain(door, prompts[:1], 6)

    before = chunk_programs()
    _, want = serve()
    assert chunk_programs()["kernel"] == before["kernel"]
    request.getfixturevalue("pallas_forced")
    before = chunk_programs()
    door, got = serve()
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    after = chunk_programs()
    assert after["kernel"] > before["kernel"] and after["xla"] == before["xla"]
    assert door.engine.obs.registry.get(
        "serving_chunk_attention_programs_total").value(
            path="kernel") == after["kernel"]


def test_a_dropped_write_leaves_the_ring_bit_for_bit():
    ring = jnp.arange(2 * RING * 2 * 4, dtype=jnp.float32).reshape(
        2, RING, 2 * 4)
    new = jnp.full((2, 3, 2, 4), -1.0)
    pos = jnp.asarray([[10, 11, 12], [0, 1, 2]])
    ok = jnp.asarray([[True, True, False], [False, False, False]])
    got, _ = PA.ring_write(ring, ring, new, new, pos, ok)
    want = _host(ring).copy()
    want[0, 10:12] = -1.0
    np.testing.assert_array_equal(_host(got), want)
    assert PA.ring_tokens(8, 4, 4) == 12 and PA.ring_tokens(2048, 1024, 32) \
        == 3072 and PA.ring_tokens(8, 5, 4) == 16


# ------------------------------------------ slots, preemption, snapshots
def test_a_reused_slot_and_a_preempted_request_continue_exactly(toy):
    """One slot: the second request takes the slot the first left, rings as
    they were (no host reset: positions decide what is seen). Then a
    request preempted in mid-decode: the slot is freed, recompute-on-resume
    rebuilds the rings from prompt + tokens, and the stream is bit for bit
    the uninterrupted one."""
    cfg, model, _ = toy
    prompts = _prompts(cfg, (30, 18), seed=11)
    want = _drain(_serve(model), prompts, 12)
    one = _serve(model, num_slots=1)
    got = [_drain(one, [p], 12)[0] for p in prompts]
    for a, b in zip(got, want):
        assert np.array_equal(a, b)

    eng = _serve(model).engine
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    while len(reqs[0].tokens) < 5:
        eng.step()
    eng.preempt(reqs[0])
    eng.run()
    assert reqs[0].preemptions == 1
    for r, b in zip(reqs, want):
        assert np.array_equal(np.asarray(r.tokens, np.int32), b)


def test_a_snapshot_restores_by_recompute(toy):
    """``snapshot()`` carries no device state for any model, so there is no
    ring in it to refuse; a restored engine re-prefills ``prompt +
    tokens``, which rebuilds the rings: the streams go on bit for bit."""
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


# -------------------------------------------------------------- the router
def test_router_against_hand_made_scores():
    """The selection bias moves the CHOICE, never the weight; the chosen
    scores are normalised to one and scaled."""
    gate = SigmoidTopKGate(2, True, 2.826)
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 1.0, 0.0, -1.0])))
    sel, w, _ = gate.topk_assignments(logits, jnp.zeros(4))
    assert _host(sel).tolist() == [[0, 1]]
    np.testing.assert_allclose(_host(w)[0], 2.826 * s[:2] / s[:2].sum(),
                               rtol=1e-6)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.7], jnp.float32)   # lifts expert 3
    sel, w, _ = gate.topk_assignments(logits, bias)
    assert _host(sel).tolist() == [[3, 0]]
    np.testing.assert_allclose(
        _host(w)[0], 2.826 * s[[3, 0]] / s[[3, 0]].sum(), rtol=1e-6)
    m = {"top_k": 2, "norm_topk": True, "scaling": 2.826}
    rsel, rw = reference.route(
        logits, {"router_w": jnp.eye(4, dtype=jnp.float32),
                 "router_b": bias}, m)
    np.testing.assert_array_equal(_host(rsel), _host(sel))
    np.testing.assert_allclose(_host(rw), _host(w), rtol=1e-6)
    with pytest.raises(NotImplementedError, match="group"):
        A.AfmoeForCausalLM(A.AfmoeConfig.tiny(n_group=2))


def test_the_lifted_block_is_the_latent_models_bit_for_bit():
    """``nlp/routed_experts.py`` under this family's names computes what
    ``DeepseekV3MoE`` computes on the latent model's toy weights, bit for
    bit, rows counted alike; each block keeps its source's leaf names."""
    paddle.seed(0)
    dcfg = DeepseekV3Config.tiny()
    theirs = DeepseekV3MoE(dcfg)
    theirs.gate.e_score_correction_bias._value = jnp.asarray(
        np.random.default_rng(0).standard_normal(8) * 0.1, jnp.float32)
    ours = A.AfmoeMoE(A.AfmoeConfig.tiny(
        hidden_size=dcfg.hidden_size,
        moe_intermediate_size=dcfg.moe_intermediate_size,
        num_experts=dcfg.n_routed_experts,
        num_experts_per_tok=dcfg.num_experts_per_tok,
        num_shared_experts=dcfg.n_shared_experts,
        route_scale=dcfg.routed_scaling_factor))
    ours.router.gate.weight._value = theirs.gate.weight._value
    ours.expert_bias._value = theirs.gate.e_score_correction_bias._value
    for name in ("gate_up_proj", "down_proj"):
        getattr(ours.experts, name)._value = getattr(
            theirs.experts, name)._value
    for name in ("gate_proj", "up_proj", "down_proj"):
        getattr(ours.shared_experts, name).weight._value = getattr(
            theirs.shared_experts, name).weight._value
    x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (2, 9, dcfg.hidden_size)).astype(np.float32))
    np.testing.assert_array_equal(_host(ours(x)._value),
                                  _host(theirs(x)._value))
    np.testing.assert_array_equal(_host(ours.rows_per_expert),
                                  _host(theirs.rows_per_expert))
    assert ours.inactive_params_per_token() \
        == theirs.inactive_params_per_token()
    assert sorted(n for n, _ in theirs.named_parameters()) == [
        "experts.down_proj", "experts.gate_up_proj",
        "gate.e_score_correction_bias", "gate.weight",
        "shared_experts.down_proj.weight", "shared_experts.gate_proj.weight",
        "shared_experts.up_proj.weight"]
    assert sorted(n for n, _ in ours.named_parameters()) == [
        "expert_bias", "experts.down_proj", "experts.gate_up_proj",
        "router.gate.weight", "shared_experts.down_proj.weight",
        "shared_experts.gate_proj.weight", "shared_experts.up_proj.weight"]
    assert theirs.router.top_k == ours.router.top_k == 3


# ---------------------------------------------------------------- the pool
def _ring_pool(**kw):
    return PagedKVCachePool(
        num_blocks=8, block_size=4, num_kv_heads=2, head_dim=6,
        num_layers=1, dtype=jnp.bfloat16,
        state={"slots": 3, "layers": 2, "ring_tokens": 12,
               "arrays": [((None, 2 * 6), None), ((None, 2 * 6), None)]},
        **kw)


def test_ring_accounting():
    pool = _ring_pool()
    assert [[(tuple(a.shape), str(a.dtype)) for a in layer]
            for layer in pool.state] == [[((3, 12, 2 * 6), "bfloat16")] * 2] * 2
    per_slot = 2 * 2 * 12 * 2 * 6 * 2
    assert pool.window_bytes_per_slot() == per_slot \
        == pool.state_bytes_per_slot()
    assert pool.bytes_per_token() == 2 * 2 * 6 * 2     # the block layer alone
    pool.ensure("__scratch__", 1)
    pool.ensure("a", 9)                                # three blocks
    st = pool.fragmentation_stats()
    assert st["window_bytes_per_slot"] == per_slot
    assert st["window_ring_tokens"] == 12 and st["state_slots"] == 3
    assert st["bytes_in_use"] == 4 * 4 * 48 + per_slot  # scratch holds none
    sides = pool.arrays()
    assert len(jax.tree_util.tree_leaves(sides)) == 2 + 4
    pool.adopt(*sides)
    pool.free("a")
    assert pool.bytes_in_use() == 4 * 48
    # a state-space slot side counts no window bytes
    other = PagedKVCachePool(
        num_blocks=8, block_size=4, num_kv_heads=2, head_dim=6,
        dtype=jnp.bfloat16, state={"slots": 3, "layers": 2,
                                   "arrays": [((4, 2, 5), "float32")]})
    assert other.window_bytes_per_slot() == 0 == other.ring_tokens
    assert other.state_bytes_per_slot() == 2 * 4 * 2 * 5 * 4


@pytest.mark.parametrize("kwargs", [{"kv_dtype": "int8"}, {"mesh": True},
                                    {"prefix_cache": True}])
def test_ring_pool_refusals(kwargs):
    if kwargs.get("mesh"):
        kwargs = {"mesh": jax.sharding.Mesh(_host(jax.devices()[:2]),
                                            ("mp",))}
    with pytest.raises(NotImplementedError, match="window layer's ring"):
        _ring_pool(**kwargs)


def _others():
    paddle.seed(0)
    return [LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False)),
            DeepseekV3ForCausalLM(DeepseekV3Config.tiny()),
            GraniteMoeHybridForCausalLM(GraniteMoeHybridConfig.tiny())]


@pytest.mark.parametrize("which", [0, 1, 2],
                         ids=["llama", "deepseek_v3", "granitemoehybrid"])
def test_the_other_families_have_no_ring_and_no_extra_aval(which):
    """The three other families' layouts name no window: their pools hold
    no ring, their programs take the avals they took (blocks, and the
    state-space family's slot side), and no key counter moves."""
    model = _others()[which]
    layout = model.paged_cache_layout()
    assert not layout.get("window")
    eng = ServingEngine(model, num_slots=2, block_size=8, max_context=32)
    pool = eng.engine_stats()["pool"]
    assert pool["window_bytes_per_slot"] == 0 == pool["window_ring_tokens"]
    kinds = layout["layers"]
    per_block = 1 if layout["layout"] == "latent" else 2
    n_state = sum(len(layer) for layer in eng.pool.state)
    assert n_state == kinds.count("state") * len(layout.get("state", ()))
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        assert len(jax.tree_util.tree_leaves(args[:5])) == (
            per_block * (len(kinds) - kinds.count("state")) + n_state)
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    eng.run()
    reg = eng.obs.registry
    assert reg.get("serving_window_keys_attended_total").value() == 0
    assert reg.get("serving_full_keys_attended_total").value() == 0
    assert reg.get("serving_window_bytes_per_slot").value(pool="target") == 0


# ------------------------------------------------------------ the refusals
def _tiny():
    paddle.seed(0)
    return A.AfmoeForCausalLM(A.AfmoeConfig.tiny())


@pytest.mark.parametrize("kwargs,name", [
    ({"kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"tp": 2}, "tp > 1"),
    ({"mesh": True}, "tp > 1"),
    ({"prefix_cache": True}, "prefix_cache=True"),
    ({"spec_draft": "llama"}, "spec_draft"),
    ({"spec_draft": "ring"}, "spec_draft"),
])
def test_refusals_by_name(kwargs, name):
    """What a window layer's ring cannot do yet is refused by name; nothing
    is silently ignored."""
    model, kwargs = _tiny(), dict(kwargs)
    if kwargs.get("mesh"):
        kwargs = {"mesh": jax.sharding.Mesh(_host(jax.devices()[:2]),
                                            ("mp",))}
    if kwargs.get("spec_draft") == "llama":
        kwargs["spec_draft"] = LlamaForCausalLM(
            LlamaConfig.tiny(tensor_parallel=False))
    elif kwargs.get("spec_draft") == "ring":
        model, kwargs["spec_draft"] = LlamaForCausalLM(
            LlamaConfig.tiny(tensor_parallel=False)), _tiny()
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(model, num_slots=2, block_size=8, max_context=32,
                      **kwargs)
    assert name in str(err.value) and "window-ring" in str(err.value)


@pytest.mark.parametrize("as_draft", [False, True])
def test_one_uniform_window_is_refused_by_the_model_that_has_it(as_draft):
    """Mistral's shape: the engine reads no config attribute; the model's
    own layout says that its layers cannot serve a window."""
    paddle.seed(0)
    windowed = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False,
                                                 sliding_window=8))
    with pytest.raises(NotImplementedError, match="one uniform window"):
        windowed.paged_cache_layout()
    plain = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    kwargs = {"spec_draft": windowed} if as_draft else {}
    with pytest.raises(NotImplementedError, match="sliding_window"):
        ServingEngine(plain if as_draft else windowed, num_slots=2,
                      block_size=8, max_context=32, **kwargs)
    src = open(engine_mod.__file__).read()
    assert '"sliding_window"' not in src and "Afmoe" not in src \
        and "afmoe" not in src.replace("nlp/afmoe.py", "")


@pytest.mark.parametrize("overrides,what", [
    ({"score_func": "softmax"}, "score function"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"mup_enabled": False}, "mup_enabled"),
    ({"layer_types": ["sliding_attention"] * 5}, "full_attention"),
    ({"layer_types": ["full_attention"] * 4 + ["chunked"]}, "layer_types"),
    ({"sliding_window": None}, "sliding_window"),
])
def test_the_config_refuses_what_the_model_does_not_compute(overrides, what):
    with pytest.raises(NotImplementedError, match=what):
        A.AfmoeConfig.tiny(**overrides)


def test_the_published_preset_counts_the_issues_parameters():
    """``trinity_mini()`` is the source's config: 26.1 B parameters whole,
    and the benchmark's cut (5 layers, one dense, the first five layer
    types) 4,241,534,720 (from shapes: nothing is allocated)."""
    def count(cfg):
        shapes = jax.eval_shape(lambda: [
            p._value for _, p in A.AfmoeForCausalLM(cfg).named_parameters()])
        return sum(int(np.prod(s.shape)) for s in shapes)

    full = A.AfmoeConfig.trinity_mini()
    assert full.layer_types == (("sliding_attention",) * 3
                                + ("full_attention",)) * 8
    assert count(full) == 2 * 65_020_160 + 30 * 839_131_520 + 819_988_480 \
        == 26_123_974_400
    cut = A.AfmoeConfig.trinity_mini(
        num_hidden_layers=5, num_dense_layers=1,
        layer_types=full.layer_types[:5])
    assert count(cut) == 4_241_534_720


# ------------------------------------------------------ spans and counters
def _keys(n, w):
    m = min(n, w)
    return m * (m + 1) // 2 + (n - m) * w


def test_counters_spans_and_scopes(toy):
    cfg, model, _ = toy
    rec = TraceRecorder.process()
    first = rec.next_id()
    door = _serve(model)
    _drain(door, _prompts(cfg, (20, 9)), 9)
    eng = door.engine
    reg = eng.obs.registry
    window, full = (reg.get(f"serving_{k}_keys_attended_total").value()
                    for k in ("window", "full"))
    # every position of a request but its last served token was computed:
    # 28 and 17 positions, each attending min(p + 1, 8) / p + 1 keys
    assert full == _keys(28, 10 ** 9) + _keys(17, 10 ** 9)
    assert window == _keys(28, WINDOW) + _keys(17, WINDOW)
    spans = [e for e in rec.events
             if e.get("args", {}).get("id", -1) >= first]
    collect = [e["args"] for e in spans if e["name"] == "engine.decode"
               and e["args"].get("half") == "collect"]
    mixed = [e["args"] for e in spans if e["name"] == "engine.mixed"]
    assert collect and mixed
    assert sum(a["window_keys"] for a in collect + mixed) == window
    assert sum(a["full_keys"] for a in collect + mixed) == full
    assert all("moe_rows" in a for a in collect + mixed)
    rows, steps = (reg.get(f"serving_moe_{k}_total").value()
                   for k in ("routed_rows", "layer_steps"))
    assert rows == steps * 4 * 3       # every expert is held: no off-share
    assert reg.get("serving_moe_offshare_rows_total").value() == 0
    stats = eng.engine_stats()["pool"]
    per_slot = 4 * 2 * RING * 2 * 32 * 4
    assert stats["window_bytes_per_slot"] == per_slot \
        == stats["state_bytes_per_slot"]
    assert stats["window_ring_tokens"] == RING
    assert stats["bytes_per_token"] == 2 * 2 * 32 * 4   # the one full layer
    for registry in (reg, MetricsRegistry.process()):
        assert registry.get("serving_window_bytes_per_slot").value(
            pool="target") == per_slot
    # the cost ledger's 2N counts a token's top 3 of a layer's 8 experts
    n = sum(int(p._value.size) for _, p in model.named_parameters())
    assert eng.obs.ledger.flops_per_token == 2.0 * (
        n - 2048 * 128 - 4 * 5 * 3 * 128 * 64)
    for step, args in (eng.decode_step_target(), eng.mixed_step_target()):
        text = step.lower(*args).as_text(debug_info=True)
        for scope in ("attn.window", "attn.full", "attn.gate",
                      "moe.router", "moe.experts", "moe.shared"):
            assert scope in text, scope
