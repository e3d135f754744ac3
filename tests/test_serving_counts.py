"""Counts the serving tier fixes by construction, computed from fixed
traces and held to their exact values.

Each case reads a number twice: with the mechanism on, and with it off
on the same trace, so a mechanism that silently falls back (a prefix
cache that never hits, a pool that lost its sharding or its int8 rows, a
router that stopped following keys or spreading load, a cost source that
went missing) fails its case. Nothing here is paced by a clock: requests
enter at fixed step indices, so no count can depend on the machine. A
CPU run can say what the program counts; it says nothing about speed
(that is ``benchmark/run.py`` on the chip).
"""
import functools

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (
    ClusterFrontDoor, ClusterReplica, ClusterRouter, FrontDoorPolicy,
    ServingEngine, no_shed_policy)

BLOCK = 8


def _model(tensor_parallel=False):
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=tensor_parallel)
    model = LlamaForCausalLM(cfg)
    model.eval()
    return cfg, model


def _log_uniform(rng, lo, hi, n):
    return np.exp(rng.uniform(np.log(lo), np.log(hi), n)).astype(int)


def _ragged_requests(cfg, rng, n=12):
    """Ragged prompts and output lengths, log-uniform (short-head heavy)."""
    return [(rng.randint(1, cfg.vocab_size, int(p)).astype(np.int32), int(m))
            for p, m in zip(_log_uniform(rng, 4, 16, n),
                            _log_uniform(rng, 6, 16, n))]


# ------------------------------------------- the shared-system-prompt trace
def _shared_prompt_trace(cfg, slots=4, n_req=12, sys_len=16):
    """Twelve requests over one 16-token system prompt (two full cache
    blocks) with ragged unique tails; every sixth is the BARE system
    prompt, a whole-chain hit whose one-token re-prefill lands in a shared
    block (copy-on-write). Then a burst of ``slots`` fresh 8-token tails,
    submitted together so that every slot is resident at once."""
    rng = np.random.RandomState(0)
    sys_prompt = rng.randint(1, cfg.vocab_size, sys_len).astype(np.int32)
    tails = _log_uniform(rng, 2, 8, n_req)
    news = _log_uniform(rng, 4, 10, n_req)

    def with_tail(n):
        return np.concatenate(
            [sys_prompt, rng.randint(1, cfg.vocab_size, n).astype(np.int32)])

    requests = [(sys_prompt.copy() if i % 6 == 5 else with_tail(int(u)),
                 int(n)) for i, (u, n) in enumerate(zip(tails, news))]
    burst = [(with_tail(8), 4) for _ in range(slots)]
    return requests, burst


def _run_shared_prompt_arm(model, requests, burst, prefix, slots=4):
    longest = max(p.shape[0] + n for p, n in requests + burst)
    max_ctx = -(-longest // BLOCK) * BLOCK
    # twice the slot-saturated demand: residency is read, never clipped
    engine = ServingEngine(
        model, num_slots=slots, block_size=BLOCK,
        num_blocks=2 * slots * (max_ctx // BLOCK) + 1, prefill_chunk=8,
        decode_quantum=4, max_context=max_ctx, prefix_cache=prefix)
    # request i enters before step 2 * i
    handles, step = [], 0
    while len(handles) < len(requests) or engine.has_work:
        while len(handles) < len(requests) and 2 * len(handles) <= step:
            p, n = requests[len(handles)]
            handles.append(engine.submit(p, max_new_tokens=n))
        engine.step()
        step += 1
    prefill_tokens = engine.engine_stats()["prefill_tokens"]
    # re-arm the high-water mark: the burst's residency alone
    engine.pool._peak_blocks = engine.pool.blocks_in_use
    handles += [engine.submit(p, max_new_tokens=n) for p, n in burst]
    engine.run()
    peak = engine.pool.fragmentation_stats()["peak_blocks_in_use"]
    return {
        "prefill_tokens": prefill_tokens,
        "burst_peak_bytes": (peak * BLOCK * engine.pool.bytes_per_token()),
        "streams": [list(map(int, r.tokens)) for r in handles],
        "hits": (engine.pool.prefix_cache_stats()["hits"] if prefix else 0),
    }


@functools.lru_cache(maxsize=None)
def _shared_prompt_arms():
    """Both arms of the trace, run once for the two cases that read them."""
    cfg, model = _model()
    requests, burst = _shared_prompt_trace(cfg)
    arms = {prefix: _run_shared_prompt_arm(model, requests, burst, prefix)
            for prefix in (True, False)}
    assert arms[True]["streams"] == arms[False]["streams"]
    assert arms[True]["hits"] > 0
    prompt_tokens = sum(int(p.shape[0]) for p, _ in requests)
    return arms[True], arms[False], prompt_tokens


def _prefill_tokens():
    """Without the cache every prompt token is prefilled: 232. With it a
    request that finds the system prompt published brings its tail alone
    (a bare system prompt its capped last position), and one that arrives
    before the first publication still computes it: 74 on this trace, of
    which 56 are unique."""
    shared, unshared, prompt_tokens = _shared_prompt_arms()
    assert prompt_tokens == 232
    return (shared["prefill_tokens"], unshared["prefill_tokens"]), (74, 232)


def _burst_pool_bytes():
    """Four slots resident at once on fresh tails: the unshared arm holds
    the two system-prompt blocks once a slot, the shared arm once."""
    shared, unshared, _ = _shared_prompt_arms()
    block_bytes = BLOCK * 2 * 2 * 2 * 16 * 4     # K+V x 2 layers x 2 x 16 f32
    return ((shared["burst_peak_bytes"], unshared["burst_peak_bytes"]),
            (11 * block_bytes, 17 * block_bytes))


# ------------------------------------------------ residency after one step
def _step1_pool(model, requests, **engine_kw):
    """Submit the whole slate, take one step (a full slate is admitted and
    its first chunks allocated), and read the pool there: block demand is
    set by prompt lengths alone."""
    engine = ServingEngine(model, num_slots=4, block_size=BLOCK,
                           prefill_chunk=8, decode_quantum=8, **engine_kw)
    for p, n in requests:
        engine.submit(p, max_new_tokens=n)
    engine.step()
    return engine.pool


def _tp_pool_bytes():
    """The KV-head split halves what a chip holds of every block: tp1 over
    tp2 is 2 exactly. Read from the pool's accounting AND from the bytes
    of the first device's shard of a pool array."""
    def arm(**kw):
        cfg, model = _model(tensor_parallel=True)
        pool = _step1_pool(model, _ragged_requests(
            cfg, np.random.RandomState(0)), **kw)
        shard = pool.k_pools[0].addressable_shards[0].data
        return pool.per_chip_bytes_in_use(), int(shard.nbytes)

    (tp1, tp1_shard), (tp2, tp2_shard) = arm(), arm(tp=2)
    assert tp1_shard == 2 * tp2_shard
    return (tp1, tp2, tp1 / tp2), (20480, 10240, 2.0)


def _int8_pool_bytes():
    """int8 rows plus one f32 scale a row and head against f32 rows:
    4d / (d + 4) = 3.2 at head_dim 16, at the same block count."""
    cfg, model = _model()
    assert cfg.hidden_size // cfg.num_attention_heads == 16
    requests = _ragged_requests(cfg, np.random.RandomState(0))
    float_pool = _step1_pool(model, requests)
    int8_pool = _step1_pool(model, requests, kv_dtype="int8")
    assert int8_pool.quantized and not float_pool.quantized
    assert int8_pool.k_pools[0].dtype == np.int8
    assert int8_pool.blocks_in_use == float_pool.blocks_in_use
    got = (float_pool.bytes_in_use(), int8_pool.bytes_in_use())
    return (*got, got[0] / got[1]), (20480, 6400, 4 * 16 / (16 + 4))


# ------------------------------------------------------------- the cluster
def _cluster(model, n, strategy, policy, max_ctx, affinity_blocks=2):
    replicas = [ClusterReplica(f"r{i}", ServingEngine(
        model, num_slots=2, block_size=BLOCK,
        num_blocks=2 * 2 * (max_ctx // BLOCK) + 1, prefill_chunk=8,
        decode_quantum=4, max_context=max_ctx, prefix_cache=True),
        policy=policy) for i in range(n)]
    return ClusterFrontDoor(ClusterRouter(
        replicas, affinity_blocks=affinity_blocks, strategy=strategy))


def _router_affinity_hits():
    """Six tenants, each a 16-token system prompt, four requests a tenant,
    arrivals interleaved by tenant, four replicas. Following the prefix
    key, a tenant's three later requests land where its first one did:
    18 of 24. Round-robin walks request i to replica i mod 4, and tenant
    t's requests are i = t, t + 6, t + 12, t + 18: never the same replica
    twice running, 0 of 24."""
    cfg, model = _model()
    rng = np.random.RandomState(0)
    tenants = [rng.randint(1, cfg.vocab_size, 2 * BLOCK).astype(np.int32)
               for _ in range(6)]
    prompts = [np.concatenate([tenants[t], rng.randint(
        1, cfg.vocab_size, int(rng.randint(2, 7))).astype(np.int32)])
        for _ in range(4) for t in range(6)]

    def arm(strategy):
        door = _cluster(model, 4, strategy, no_shed_policy(), max_ctx=32)
        streams = [door.submit(p, max_new_tokens=4, seed=0) for p in prompts]
        door.run_until_idle()
        stats = door.router.affinity_stats()
        return ((stats["affinity_hits"], stats["keyed_requests"]),
                [list(s.result()) for s in streams])

    (followed, f_streams), (walked, w_streams) = (
        arm("affinity"), arm("round_robin"))
    assert f_streams == w_streams
    return (followed, walked), ((18, 24), (0, 24))


def _admitted_by_replicas():
    """Forty requests, two submitted a fleet pump, each door refusing
    past two waiting: one replica admits 16, four admit all 40 (2.5x).
    Admission depends on queue depths at the submission points alone."""
    cfg, model = _model()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, 10).astype(np.int32)
               for _ in range(40)]

    def admitted(n_replicas):
        door = _cluster(model, n_replicas, "affinity",
                        FrontDoorPolicy(max_waiting=2, preempt=False),
                        max_ctx=16)
        count = 0
        for i, p in enumerate(prompts):
            count += not door.submit(p, max_new_tokens=4, seed=0).shed
            if i % 2 == 1:
                door.pump()
        door.run_until_idle()
        assert count == sum(len(r.engine.completed) for r in door.replicas)
        return count

    return (admitted(1), admitted(4)), (16, 40)


# ------------------------------------------------- the two cost sources
def _decode_step_flops_agreement():
    """The decode quantum's flops by the jaxpr walk over XLA's own count:
    both sources present, the walk's count the one its shapes fix, the
    ratio inside the pinned band."""
    from paddle_tpu import analysis

    cost = analysis.run_recipe("serving_decode_step").cost
    assert cost.xla is not None and cost.jaxpr is not None
    lo, hi = analysis.AGREEMENT_BAND
    assert lo <= cost.flops_ratio <= hi
    return ((cost.jaxpr.flops, round(cost.flops_ratio, 3)),
            (2490600, 0.981))


CASES = {
    "prefix_prefill_tokens": _prefill_tokens,
    "prefix_burst_pool_bytes": _burst_pool_bytes,
    "tp_per_chip_pool_bytes": _tp_pool_bytes,
    "int8_pool_bytes": _int8_pool_bytes,
    "router_affinity_hits": _router_affinity_hits,
    "admitted_by_replicas": _admitted_by_replicas,
    "decode_step_flops_agreement": _decode_step_flops_agreement,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_exact_count(case):
    got, want = CASES[case]()
    assert got == want
