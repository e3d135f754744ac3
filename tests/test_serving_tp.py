"""TP-sharded serving (ISSUE 11): the whole quantum family on the mesh.

Tier-1 keeps the cheap-but-sharp end: the tp2 greedy engine with the
prefix cache ON must stream BIT-EXACT against the per-request
sequential oracle (the same single-chip reference test_serving pins the
tp=1 engine to), including full-prompt prefix hits and a COW re-prefill
— one engine build covers the greedy, prefix-hit and COW arms at once.
The same run asserts the build-time collective census (gauges +
``engine_stats()`` + dashboard line) and the per-chip pool residency
split. Around it: the mesh-aware paged-pool adversarial suite (sharded
COW, preempt/resume aliasing, and the refcount-granularity ragged
churn from tests/test_prefix_cache.py re-run on a tp2 pool layout —
pure host allocator work, no compiles) and the mesh-kwarg error paths
(all raise before any tracing).

The expensive engine-vs-engine parities (fixed-seed sampling with
per-request temperatures, the speculative draft+verify round) are
``slow``: each builds two engines. Run them with ``-m slow``.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.nlp import PagedKVCachePool
from paddle_tpu.nlp.generation import generate_on_device
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import _resolve_tp_mesh


def _mesh(n=2):
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n]), ("mp",))


@pytest.fixture(scope="module")
def tp_model():
    """A tensor-parallel tiny llama built WITHOUT a mesh: mp layers
    degrade to their serial twins at init, so the same seed gives the
    single-chip reference and the tp2 engine identical weights — the
    bit-exactness oracle's foundation."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=True)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def _oracle_row(model, prompt, max_new):
    out = generate_on_device(model, paddle.to_tensor(prompt[None, :]),
                             max_new_tokens=max_new)
    return np.asarray(out._value)[0]


# ------------------------------------------------ tp2 parity (tier-1)
def test_tp2_greedy_prefix_stream_parity(tp_model):
    """The headline oracle: a tp=2 engine with ``prefix_cache=True``
    streams bit-exact vs sequential single-chip generation — 5
    requests where two share an 8-token (2-block) prefix and one is an
    exact resubmit, so the run exercises a full-prompt prefix hit AND
    the COW copy its capped re-prefill forces, all through the SHARDED
    pool. The same build carries the obs satellite: the collective
    census lands in the gauges, ``engine_stats()`` and the dashboard,
    and pool residency reports per-chip bytes."""
    cfg, model = tp_model
    rng = np.random.RandomState(0)
    ragged = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
              for n in (5, 9)]
    shared = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)
    sp = [np.concatenate(
        [shared, rng.randint(1, cfg.vocab_size, 3).astype(np.int32)])
        for _ in range(2)]
    # wave 1 publishes ``shared``'s two full blocks; wave 2 re-submits
    # the exact 8-token prompt (full-prompt hit whose capped one-token
    # re-prefill COWs the shared tail block) plus a second extension
    # (2-block prefix hit) — all against the sequential oracle
    wave1, wave2 = ragged + [sp[0], shared], [shared, sp[1]]
    max_new = {id(p): mn for p, mn in
               zip(wave1 + wave2, (6, 4, 5, 5, 5, 5))}
    wants = {id(p): _oracle_row(model, p, max_new[id(p)])
             for p in wave1 + wave2}

    engine = ServingEngine(model, num_slots=3, block_size=4,
                           prefill_chunk=4, decode_quantum=3,
                           prefix_cache=True, tp=2)
    reqs = []
    for wave in (wave1, wave2):
        batch = [(engine.submit(p, max_new_tokens=max_new[id(p)]), p)
                 for p in wave]
        engine.run()
        reqs += batch
    for req, p in reqs:
        np.testing.assert_array_equal(engine.output_tokens(req),
                                      wants[id(p)])
    # the sharded pool really took the prefix-cache fast paths
    assert engine.pool.prefix_hits >= 2
    assert engine.pool.cow_copies >= 1
    assert engine.pool.tp_shards == 2
    assert engine.pool.per_chip_bytes_in_use() * 2 == \
        engine.pool.bytes_in_use()

    # obs satellite: census from the COMPILED quantum at build time
    qc = engine.quantum_collectives
    assert qc["tp"] == 2 and qc["count_total"] > 0
    assert qc["bytes_total"] > 0
    assert "all-reduce" in qc["by_kind"]
    st = engine.engine_stats()
    assert st["tp"] == 2
    assert st["quantum_collectives"]["bytes_total"] == qc["bytes_total"]
    assert st["pool_bytes_per_chip"] == engine.pool.per_chip_bytes_in_use()
    reg = engine.obs.registry
    assert reg.get("serving_collective_bytes_total").value() == \
        qc["bytes_total"]
    assert reg.get("serving_collective_count_total").value(
        kind="all-reduce") == qc["by_kind"]["all-reduce"]["count"]
    from paddle_tpu.obs.export import render_dashboard
    dash = render_dashboard(reg.snapshot())
    assert "collectives/quantum" in dash


# ----------------------------------------------- slow engine parities
@pytest.mark.slow
def test_tp2_per_request_sampling_parity(tp_model):
    """Fixed-seed sampling through the sampling quantum:
    per-slot temperatures + per-request seeds, tp1 vs tp2 engines on
    the SAME weights — streams must match bit-for-bit (the collectives
    change where the math runs, not what it computes)."""
    cfg, model = tp_model
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 3)]

    def run(tp):
        eng = ServingEngine(model, num_slots=3, block_size=4,
                            prefill_chunk=4, decode_quantum=3,
                            decode_strategy="sampling", temperature=0.8,
                            **({"tp": tp} if tp else {}))
        reqs = [eng.submit(p, max_new_tokens=5, seed=i,
                           temperature=0.7 if i % 2 else 1.2)
                for i, p in enumerate(prompts)]
        eng.run()
        return [eng.output_tokens(r) for r in reqs]

    for a, b in zip(run(0), run(2)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_tp2_spec_round_parity(tp_model):
    """The speculative draft+verify round under tp2: BOTH models shard
    onto the same mesh, both paged pools split along kv heads, the
    round stays one dispatch — and greedy spec output is bit-exact vs
    the tp1 spec engine (which is itself exact by construction)."""
    cfg, model = tp_model
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 7)]

    def draft():
        paddle.seed(11)
        d = LlamaForCausalLM(LlamaConfig.tiny(
            tensor_parallel=True, num_hidden_layers=1))
        d.eval()
        return d

    def run(tp):
        eng = ServingEngine(model, num_slots=2, block_size=4,
                            prefill_chunk=4, spec_draft=draft(),
                            spec_gamma=3, **({"tp": tp} if tp else {}))
        reqs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        eng.run()
        assert eng.engine_stats()["spec_rounds"] > 0
        return eng, [eng.output_tokens(r) for r in reqs]

    _, o1 = run(0)
    e2, o2 = run(2)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    assert e2.quantum_collectives["count_total"] > 0
    assert e2.d_pool.tp_shards == 2


# -------------------------------------- mesh-aware paged pool (host)
def _mesh_pool(num_blocks=16, bs=4, kv_heads=2):
    return PagedKVCachePool(num_blocks=num_blocks, block_size=bs,
                            num_kv_heads=kv_heads, head_dim=8,
                            dtype=jnp.float32, prefix_cache=True,
                            mesh=_mesh(2))


def _audit(pool):
    """Refcount-granularity leak oracle — the same invariant walk as
    tests/test_prefix_cache.py::_audit, re-run here against the
    SHARDED pool: every block's refcount equals its holder count, free
    list and held set partition the pool, stats stay sane."""
    expect = {}
    for table in pool._tables.values():
        for b in table:
            expect[b] = expect.get(b, 0) + 1
    for b in pool._cached_blocks:
        expect[b] = expect.get(b, 0) + 1
    assert expect == pool._refcounts
    assert len(pool._free) + len(expect) == pool.num_blocks
    assert not (set(pool._free) & set(expect))
    st = pool.fragmentation_stats()
    assert 0.0 <= st["utilization"] <= 1.0
    assert st["blocks_in_use"] == len(expect)


def _assert_sharded(pool):
    """Every layer's K/V pool array still carries the kv-head split —
    COW writes and publishes must never silently decay to replicated."""
    from jax.sharding import PartitionSpec

    want = PartitionSpec(None, None, "mp", None)
    for arr in pool.k_pools + pool.v_pools:
        assert arr.sharding.spec == want, arr.sharding


def test_mesh_pool_layout_and_fallback():
    """kv_heads divisible by mp -> pools split along the head axis and
    residency reports per-chip bytes; a non-divisible head count falls
    back to replicated (tp_shards == 1) instead of failing."""
    pool = _mesh_pool()
    assert pool.tp_shards == 2
    _assert_sharded(pool)
    pool.ensure("a", 8)
    assert pool.per_chip_bytes_in_use() * 2 == pool.bytes_in_use()
    odd = PagedKVCachePool(num_blocks=4, block_size=4, num_kv_heads=3,
                           head_dim=8, dtype=jnp.float32,
                           mesh=_mesh(2))
    assert odd.tp_shards == 1
    assert odd.per_chip_bytes_in_use() == odd.bytes_in_use()


def test_mesh_pool_cow_keeps_rows_and_sharding():
    """COW under the tp2 layout: the writer moves to a fresh block,
    the survivor keeps the original device rows, refcounts rebalance —
    and every pool array KEEPS its NamedSharding through the
    ``.at[].set`` copy (the _pin re-commit)."""
    pool = _mesh_pool()
    toks = np.array([1, 2, 3, 4, 5, 6, 7, 8], dtype=np.int32)
    assert pool.attach_prefix("a", toks) == 0
    pool.ensure("a", 8)
    pool.make_writable("a", 0, 8)
    marker = jnp.full((2, 8), 7.0)
    blk = pool._tables["a"][0]
    pool.k_pools[0] = pool._pin(
        pool.k_pools[0].at[blk, 0].set(marker))
    pool.publish_prefix("a", toks)
    assert pool.attach_prefix("b", toks) == 8
    pool.ensure("b", 8)
    shared = list(pool._tables["b"])
    pool.make_writable("b", 4, 8)  # COW the tail block only
    assert pool._tables["b"][0] == shared[0]
    assert pool._tables["b"][1] != shared[1]
    assert pool.cow_copies >= 1
    _assert_sharded(pool)
    # the survivor's rows are untouched by b's copy
    np.testing.assert_array_equal(
        np.asarray(pool.k_pools[0][pool._tables["a"][0], 0]),
        np.asarray(marker))
    _audit(pool)


def test_mesh_pool_preempt_resume_aliasing():
    """Preempt/resume under tp2: freeing a sharer mid-run releases only
    its holds (the index + survivor keep the blocks), and the resumed
    sequence re-attaches through the prefix index — the aliasing
    bookkeeping is layout-independent, and the audit proves it."""
    pool = _mesh_pool()
    toks = np.arange(1, 9, dtype=np.int32)
    pool.attach_prefix("a", toks)
    pool.ensure("a", 8)
    pool.make_writable("a", 0, 8)
    pool.publish_prefix("a", toks)
    assert pool.attach_prefix("b", toks) == 8
    pool.ensure("b", 8)
    _audit(pool)
    pool.free("b")  # preemption: drop the sharer's holds
    _audit(pool)
    hits = pool.prefix_hits
    assert pool.attach_prefix("b", toks) == 8  # resume re-aliases
    pool.ensure("b", 8)
    assert pool.prefix_hits > hits
    _audit(pool)
    _assert_sharded(pool)
    pool.free("a")
    pool.free("b")
    pool.clear_prefix_cache()
    assert pool.free_blocks == pool.num_blocks


def test_mesh_pool_ragged_churn_zero_leaks():
    """The 100-round seeded ragged churn from test_prefix_cache re-run
    on the SHARDED pool: admit/attach/publish/COW/trim/free/evict with
    the refcount audit after every round, plus the sharding invariant —
    teardown returns the pool to pristine."""
    rng = np.random.RandomState(42)
    pool = _mesh_pool(num_blocks=16, bs=4)
    live, counter = {}, 0
    for _ in range(100):
        op = rng.rand()
        if op < 0.55 and len(live) < 6:
            sid = f"s{counter}"
            counter += 1
            toks = rng.randint(0, 3,
                               rng.randint(1, 21)).astype(np.int32)
            try:
                matched = pool.attach_prefix(sid, toks)
                pool.ensure(sid, len(toks))
                if rng.rand() < 0.25:
                    pool.make_writable(sid, 0, len(toks))
                else:
                    pool.make_writable(sid, matched, len(toks))
                pool.publish_prefix(sid, toks)
                live[sid] = toks
            except RuntimeError:
                pool.free(sid)
                if live:
                    victim = list(live)[rng.randint(len(live))]
                    live.pop(victim)
                    pool.free(victim)
        elif op < 0.75 and live:
            victim = list(live)[rng.randint(len(live))]
            live.pop(victim)
            pool.free(victim)
        elif op < 0.85 and live:
            sid = list(live)[rng.randint(len(live))]
            keep = rng.randint(0, len(live[sid]) + 1)
            pool.trim(sid, keep)
        else:
            pool.evict_prefix(rng.randint(0, 3))
        _audit(pool)
    assert pool.prefix_hits > 0 and pool.cow_copies > 0
    _assert_sharded(pool)
    for sid in list(live):
        pool.free(sid)
    pool.clear_prefix_cache()
    assert pool.free_blocks == pool.num_blocks
    assert not pool._refcounts and not pool._tables


# --------------------------------------- mesh kwarg error paths (host)
def test_tp_too_many_devices_is_actionable():
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        _resolve_tp_mesh(None, 64)


def test_tp_mesh_needs_mp_axis():
    from jax.sharding import Mesh

    data = Mesh(np.array(jax.devices()[:2]), ("data",))
    with pytest.raises(ValueError, match="no 'mp' axis"):
        _resolve_tp_mesh(data, None)


def test_tp_mesh_tp_disagreement():
    with pytest.raises(ValueError, match="disagrees"):
        _resolve_tp_mesh(_mesh(2), 4)


def test_tp_mesh_size_one_is_single_chip():
    mesh, tp = _resolve_tp_mesh(_mesh(1), None)
    assert mesh is None and tp == 1
    mesh, tp = _resolve_tp_mesh(None, 2)
    assert tp == 2 and mesh.shape["mp"] == 2


def test_tp_head_divisibility_checked_before_tracing(tp_model):
    cfg, model = tp_model
    with pytest.raises(ValueError, match="must divide by tp=8"):
        ServingEngine(model, num_slots=2, block_size=4, tp=8)


def test_tp_rejects_serial_model_before_tracing():
    paddle.seed(3)
    serial = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    serial.eval()
    with pytest.raises(ValueError, match="tensor_parallel=True"):
        ServingEngine(serial, num_slots=2, block_size=4, tp=2)
