"""Real-recipe budget registry: the compiled programs the system
actually dispatches, each paired with the budget that pins its current
known-good graph shape.

- ``llama_tp_zero_fused_lce``: the TP(mp=2) x ZeRO(sharding=4)
  fused-LCE train step, the hybrid recipe. Budget: 0
  involuntary remats, the stage-2 reduce-scatter decision present,
  every param/state/buffer leaf donated, and a hard cap on per-step
  all-gather traffic.
- ``llama_decode_greedy``: the whole-loop on-device greedy decode
  (one-dispatch serving shape) on a bf16 tiny llama. Budget: a
  single-chip program stays collective-free, and the bf16 graph stays
  bf16 — 0 f32 matmuls reachable from the bf16 params.
- ``serving_decode_step``: the continuous-batching engine's jitted
  decode quantum (``ServingEngine.decode_step_target`` — the EXACT
  compiled program the serving hot loop dispatches, audited with the
  engine's live post-prefill state). Budget: 0 involuntary remats, 0
  host callbacks/transfers (the no-per-token-host-sync invariant), the
  KV pool leaves all donated, collective-free, and bf16 stays bf16.
- ``speculative_verify_step``: the speculative serving arm's ONE-
  dispatch round (draft-γ ``lax.scan`` + single target verify forward
  + in-graph acceptance/rollback, ``serving/speculative.py``), audited
  with the engine's live post-prefill state. Budget: same caps as the
  plain quantum, with BOTH the draft and target KV pool leaves
  donated.
- ``serving_frontdoor_step``: the FRONT DOOR's sampling quantum
  (per-slot temperature rides the per-slot state as one extra (S,) f32
  input; sampling selection in-graph), built through an engine carrying
  the whole policy tier (priorities, a forced preemption, SLOs, flight recorder, full
  instrumentation). Budget: the same zero-host-callback /
  pools-donated caps — the machine proof that streaming, preemption,
  shedding and drain are ALL host-side policy that never enters the
  compiled program.
- ``serving_prefix_step``: the PREFIX-CACHED engine's decode quantum
  (``prefix_cache=True`` — content-addressed block reuse +
  copy-on-write in the paged pool), audited after a real cache hit
  and a real COW. Budget: identical caps to ``serving_decode_step`` —
  the machine proof that the whole cache policy (chain-hash index,
  attach/publish, COW, refcount eviction) is host-side allocator work
  that never changes the compiled program.
- ``serving_int8_step``: the QUANTIZED engine's decode quantum
  (``quantize="weight_only_int8"`` + ``kv_dtype="int8"`` — int8
  weights dequantized into the matmul, int8 KV pool with per-row
  scale pools in the donated signature). Budget: the serving caps
  plus ``min_int8_matmuls`` — positive, machine-checked evidence the
  contractions are fed from int8 storage, so "quantization silently
  disabled" cannot pass tier-1 even though it would be bit-identical.

- ``serving_mixed_step``: the engine's MIXED prefill step
  (``ServingEngine.mixed_step_target`` — the one jitted program a step
  with prefilling rows dispatches, audited with a prefill chunk and a
  decode row riding along). Budget: the serving caps (0 host callbacks:
  the next tokens are picked in the program; KV pool leaves donated;
  collective-free; bf16 stays bf16) plus a temp cap that (S, C, V)
  logits or a lost donation would blow.

``build(name)`` constructs the recipe (installing the mesh it needs)
and returns a :class:`Recipe`; call ``recipe.check()`` for the audited
report and ``recipe.close()`` (or use ``run(name)``) to restore global
mesh state. Every registered recipe also carries a checked-in golden
fingerprint (``tests/goldens/<name>.json``, see :mod:`.fingerprint`)
compared against the live audit in tier-1 and by ``--fingerprint`` /
``scripts/check_graphs.sh``. Used by tests/test_zero_ir.py,
tests/test_analysis.py, tests/test_serving.py, the
and the ``python -m paddle_tpu.analysis`` CLI.
"""
from __future__ import annotations

from .budget import Budget, check_budget, audit

__all__ = ["Recipe", "RECIPES", "build", "run"]


class Recipe:
    """One auditable (target, example-args, budget) triple plus the
    teardown that undoes any global state its builder installed."""

    def __init__(self, name, target, args, budget, teardown=None):
        self.name = name
        self.target = target
        self.args = tuple(args)
        self.budget = budget
        self._teardown = teardown

    def audit(self):
        return audit(self.target, *self.args)

    def check(self):
        return check_budget(self.target, self.budget, *self.args)

    def close(self):
        if self._teardown is not None:
            self._teardown()
            self._teardown = None


def _mesh_teardown():
    from ..parallel import mesh as mesh_state

    def teardown():
        mesh_state.set_mesh(None)

    return teardown


def _build_llama_tp_zero_fused_lce():
    import numpy as np
    import paddle_tpu as paddle
    from ..distributed import fleet
    from ..jit.train import JittedTrainStep
    from ..nlp import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": 2, "pp_degree": 1,
        "sharding_degree": 4,
    }
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=True,
                           fuse_linear_cross_entropy=True)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg, lm_head=model.lm_head)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = JittedTrainStep(
        model, lambda out, labels: crit(out, labels), opt,
        state_sharding_axis="sharding",
    )
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (4, 32)))
    budget = Budget(
        name="llama tp2 x zero4 fused-LCE train step",
        max_remat=0,
        require_reduce_scatter=True,
        require_donated=True,
        # pinned ~25% above the audited graph (28: see test_analysis; 18
        # ZeRO / criterion gathers + the sequence-split hidden stream's
        # 8, one a tensor-parallel half-layer each way, + the fused
        # criterion's 2): headroom for benign partitioner drift, but a
        # structural regression (per-layer re-gather, lost fusion) blows
        # through it
        max_all_gathers=35,
        # the batch stays split over ``sharding`` inside the layers and
        # the hidden stream over ``mp`` between them: audited 676,088 B
        # of collectives (a gather counts its whole result, so the
        # stream's 8 gathers + 8 reduce-scatters, 98,304 B, read like
        # the 114,688 B of all-reduces they replace; the rest is the
        # vocabulary-parallel embedding, which the CPU partitioner now
        # feeds by gathering its 128-row table) and 452,904 B of temps
        # (CPU backend). A constraint that pins the batch replicated
        # over the axis again (946,176 B and 1,759,000 B: every member
        # all-reduces and holds the WHOLE batch) blows through both
        max_collective_bytes=845_000,
        max_temp_bytes=566_000,
        max_f32_matmuls=0,
        # audited 3.93 MB trace-level peak (3.80 MB before the hidden
        # stream was split: a half-layer keeps its GATHERED input for
        # the backward); a lost donation or a full-logits buffer
        # reappearing blows through the headroom
        max_peak_live_bytes=4_900_000,
        # norm scales (256 B) replicate by design; any 2-D leaf —
        # a weight or its moments — losing its TP/ZeRO axis is >4 KB
        max_replicated_param_bytes=4096,
        # 48 sharded leaves audited: params + both moments actually
        # carry the axis, not just the sharding rule table
        min_sharded_params=40,
        # static cost model (analysis/cost.py): 119.1M flops / 80.7 MB
        # accessed per step over the 4x32-token batch — ~930k flops
        # and ~630 KB per token audited; a lost fusion or an
        # accidental f32 re-materialization of the state blows the
        # byte cap, a duplicated forward blows the flop cap. The bytes
        # rose from 61.4 MB (~480 KB a token) with the manual regions,
        # and that rise is the walker's VIEW, not the hardware's: a
        # region's body is counted once a member (8 x 2.98 MB = 23.9
        # MB), so each ``mp`` member reads the whole gathered stream
        # and each ``sharding`` member its own copy of the weight
        # shards, where the partitioner's program, which did the same
        # reads on every device, was counted at global shapes (~7.5 MB
        # for the same products). What is real in it: 2.4 MB, each
        # member's explicit float32 sum of its weight gradients
        # (``_member_linear``; inside the products before). Tighten
        # the two pins below again when the walker counts an operand a
        # region's members share once (765,000 is 21 % above)
        cost_tokens_per_dispatch=128,
        max_flops_per_token=1_200_000,
        max_hbm_bytes_per_token=765_000,
        min_arithmetic_intensity=1.2,
    )
    return Recipe("llama_tp_zero_fused_lce", step, (ids, ids), budget,
                  teardown=_mesh_teardown())


def _build_llama_decode_greedy():
    import numpy as np
    import jax
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..nlp.generation import generate_on_device

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    model.eval()
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (1, 8)))
    max_new = 8
    # populate the per-model compiled-program cache, then audit the
    # EXACT program the serving path dispatches
    generate_on_device(model, ids, max_new_tokens=max_new)
    (jitted,) = [
        fn for key, fn in model._generate_jit_cache.items()
        if key[0] == "greedy"
    ]
    p_vals = [p._value for _, p in model.named_parameters()]
    args = (p_vals, ids._value, jax.random.PRNGKey(0))
    budget = Budget(
        name="llama on-device greedy decode (bf16, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip program: any collective
                                  # means an accidental mesh dependency
        max_f32_matmuls=0,        # bf16 serving graph stays bf16
        # audited 22.9 KB temp / 64 B output on the tier-1 backend: a
        # decode loop that starts materializing per-step logits or
        # full-cache copies is a structural regression
        max_temp_bytes=64_000,
        max_output_bytes=1024,
        # cost model: 2.63M flops / 5.09 MB over the 8 decoded tokens
        # (329k flops, 636 KB per token audited) — the whole-loop
        # decode must keep amortizing weight reads across its scan
        cost_tokens_per_dispatch=8,
        max_flops_per_token=450_000,
        max_hbm_bytes_per_token=850_000,
        min_arithmetic_intensity=0.35,
    )
    return Recipe("llama_decode_greedy", jitted, args, budget)


def _build_serving_decode_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import FaultInjector, ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    # FULL observability on (metrics + tracer + SLOs + flight
    # recorder): instrumentation lives at host boundaries only, so the
    # audited program and its golden fingerprint must be byte-identical
    # to the uninstrumented engine — this recipe IS that proof (tier-1
    # + `python -m paddle_tpu.obs check` + scripts/check_graphs.sh)
    # resilience tier on with a DISARMED injector: the watchdog,
    # retry policy and fault hooks are host-side no-ops until a plan
    # arms them, so this golden also pins that the resilience tier
    # cannot perturb the compiled quantum
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=4,
                           trace=True, slo=True, flight=True,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    rng = np.random.RandomState(0)
    engine.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                  max_new_tokens=8)
    engine.step()  # admit + prefill so the audited state is live
    target, args = engine.decode_step_target()
    budget = Budget(
        name="serving decode quantum (bf16, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip serving program
        max_f32_matmuls=0,        # bf16 pool/params stay bf16
        max_host_callbacks=0,     # host scheduler only at boundaries
        require_donated=True,     # the 2L KV pool leaves
        # audited 330 KB temp / 891 KB trace peak: the quantum works
        # in-place over the donated pool — a lost donation or an
        # unrolled scan materializing per-token buffers blows this
        max_temp_bytes=430_000,
        max_peak_live_bytes=1_300_000,
        # cost model: 2.49M flops / 18.8 MB accessed per quantum over
        # 2 slots x 4 decode steps = 8 tokens (311k flops / 2.36 MB
        # per token audited; the quantum re-reads the weights each
        # scan step, hence the deeply memory-bound 0.13 FLOP/B)
        cost_tokens_per_dispatch=8,
        max_flops_per_token=420_000,
        max_hbm_bytes_per_token=3_100_000,
        min_arithmetic_intensity=0.09,
    )
    recipe = Recipe("serving_decode_step", target, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    return recipe


def _build_speculative_verify_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import FaultInjector, ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    target = LlamaForCausalLM(cfg)
    draft = LlamaForCausalLM(
        LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16",
                         num_hidden_layers=1))
    # observability + SLO/flight on, same rationale as
    # serving_decode_step
    engine = ServingEngine(target, spec_draft=draft, spec_gamma=2,
                           num_slots=2, block_size=4, prefill_chunk=8,
                           trace=True, slo=True, flight=True,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    rng = np.random.RandomState(0)
    engine.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                  max_new_tokens=6)
    engine.step()  # admit + prefill so the audited state is live
    step, args = engine.decode_step_target()
    budget = Budget(
        name="speculative verify round (bf16, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip serving program
        max_f32_matmuls=0,        # bf16 pools/params stay bf16
        max_host_callbacks=0,     # host scheduler only at boundaries
        require_donated=True,     # draft AND target KV pool leaves
        # audited 335 KB temp / 1.18 MB trace peak (draft + target
        # pools both in flight; donation saves 402 KB of that)
        max_temp_bytes=440_000,
        max_peak_live_bytes=2_000_000,
        # cost model: 2.86M flops / 10.7 MB per round over 2 slots x
        # (gamma+1)=3 tokens = 6 tokens at full acceptance (477k
        # flops / 1.79 MB per token audited)
        cost_tokens_per_dispatch=6,
        max_flops_per_token=640_000,
        max_hbm_bytes_per_token=2_900_000,
        min_arithmetic_intensity=0.15,
    )
    recipe = Recipe("speculative_verify_step", step, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    return recipe


def _build_serving_frontdoor_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import (
        BATCH, INTERACTIVE, FaultInjector, FrontDoorPolicy,
        ServingEngine, ServingFrontDoor,
    )

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    # the front-door engine: a sampling engine (the quantum whose
    # per-slot temperature input this recipe's golden pins) with
    # the FULL policy + observability tier on — and a forced
    # preemption before the audit, so the audited state is one a real
    # overloaded front door reaches (evict, resume, re-prefill)
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=4,
                           decode_strategy="sampling", top_k=8,
                           trace=True, slo=True, flight=True,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    door = ServingFrontDoor(engine, policy=FrontDoorPolicy())
    rng = np.random.RandomState(0)
    low = door.submit(rng.randint(1, cfg.vocab_size, 6)
                      .astype(np.int32), max_new_tokens=8,
                      priority=BATCH, temperature=1.3)
    door.pump()  # admit + prefill the batch request
    engine.preempt(low.request)  # pool-pressure eviction, host-side
    door.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                max_new_tokens=8, priority=INTERACTIVE,
                temperature=0.7)
    door.pump()  # interactive admits; batch resumes into slot 2
    door.pump()  # re-prefill completes; audited state is live
    target, args = engine.decode_step_target()
    budget = Budget(
        name="front-door sampling quantum (bf16, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip serving program
        max_f32_matmuls=0,        # bf16 pool/params stay bf16
        max_host_callbacks=0,     # ALL front-door policy is host-side
        require_donated=True,     # the 2L KV pool leaves
        # audited 333 KB temp / 891 KB trace peak — the sampling filter
        # (top-k cut + per-slot temperature scale) fuses into the
        # greedy quantum's existing (S, V) temporaries; caps leave
        # ~30% headroom like the other serving recipes
        max_temp_bytes=430_000,
        max_peak_live_bytes=1_300_000,
        # cost model: the sampling filter adds ~14k flops to the plain
        # quantum (2.50M / 19.0 MB over 8 tokens audited) — same caps
        cost_tokens_per_dispatch=8,
        max_flops_per_token=420_000,
        max_hbm_bytes_per_token=3_100_000,
        min_arithmetic_intensity=0.09,
    )
    recipe = Recipe("serving_frontdoor_step", target, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    recipe.frontdoor = door
    return recipe


def _build_serving_prefix_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import FaultInjector, ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    # the PREFIX-CACHED engine (content-addressed block reuse +
    # copy-on-write, nlp/paged_cache.py) with full observability on.
    # The audited state is reached through a REAL cache hit: the first
    # request publishes its two full prompt blocks at prefill
    # completion, the second (identical prompt) aliases both at
    # admission and copy-on-writes the tail block when its capped
    # one-token re-prefill lands. All of that is host allocator
    # policy — this recipe's golden proves the compiled quantum stays
    # byte-identical to serving_decode_step's shape: 0 host callbacks,
    # pools donated, collective-free, bf16 end to end.
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=4,
                           prefix_cache=True,
                           trace=True, slo=True, flight=True,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, cfg.vocab_size, 8).astype(np.int32)
    engine.submit(prompt.copy(), max_new_tokens=8)
    engine.step()  # admit + full prefill -> publish both blocks
    engine.submit(prompt.copy(), max_new_tokens=8)
    engine.step()  # attach (2-block hit) + capped re-prefill -> COW
    assert engine.pool.prefix_hits >= 2, engine.pool.prefix_hits
    assert engine.pool.cow_copies >= 1, engine.pool.cow_copies
    target, args = engine.decode_step_target()
    budget = Budget(
        name="prefix-cached serving quantum (bf16, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip serving program
        max_f32_matmuls=0,        # bf16 pool/params stay bf16
        max_host_callbacks=0,     # cache policy is host-side only
        require_donated=True,     # the 2L KV pool leaves
        # same caps as serving_decode_step: the prefix cache must not
        # change the compiled quantum at all
        max_temp_bytes=430_000,
        max_peak_live_bytes=1_300_000,
        # cost model: identical numbers to serving_decode_step (2.49M
        # flops / 18.8 MB over 8 tokens) — the cache must be free in
        # the compiled program's cost exactly like in its structure
        cost_tokens_per_dispatch=8,
        max_flops_per_token=420_000,
        max_hbm_bytes_per_token=3_100_000,
        min_arithmetic_intensity=0.09,
    )
    recipe = Recipe("serving_prefix_step", target, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    return recipe


def _build_serving_int8_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import FaultInjector, ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    # the QUANTIZED serving quantum: weight-only int8 (per-out-channel
    # scales, dequant INTO the matmul) + int8 KV pool with per-row f32
    # scale pools riding the quantum signature. Same observability /
    # resilience tier as serving_decode_step. The budget adds the
    # INVERSE dtype direction: ``min_int8_matmuls`` asserts the
    # contractions really are fed from int8 storage — a refactor that
    # silently dequantizes weights at build (or floats the pool) keeps
    # every stream bit-identical yet blows this budget.
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=4,
                           quantize="weight_only_int8",
                           kv_dtype="int8",
                           trace=True, slo=True, flight=True,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    rng = np.random.RandomState(0)
    engine.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                  max_new_tokens=8)
    engine.step()  # admit + prefill so the audited state is live
    target, args = engine.decode_step_target()
    budget = Budget(
        name="int8 serving decode quantum (w8 + kv8, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip serving program
        max_host_callbacks=0,     # host scheduler only at boundaries
        require_donated=True,     # KV pools AND their scale pools
        # every decode-step matmul (qkv/out/ffn x layers + lm head)
        # must trace back to int8 weights or the int8 KV pool. Audited
        # 19 int8-fed contractions; the floor catches "quantization
        # silently off" (=0) and any per-layer partial disable
        min_int8_matmuls=10,
        # audited 613 KB temp / 286 KB trace peak: the gather-dequant
        # attention fallback plus in-graph per-row quant temporaries
        # cost more compiled scratch than the bf16 quantum's Pallas
        # path; same ~30% headroom discipline as the other recipes
        max_temp_bytes=800_000,
        max_peak_live_bytes=450_000,
        # cost model: 3.09M flops / 22.6 MB over 8 tokens (387k flops
        # / 2.82 MB per token audited) — the in-graph dequant work
        # costs ~24% more flops than the bf16 quantum, pinned here so
        # a silently widening dequant path (per-element f32 blow-up)
        # cannot ride in under the structural caps
        cost_tokens_per_dispatch=8,
        max_flops_per_token=520_000,
        max_hbm_bytes_per_token=3_700_000,
        min_arithmetic_intensity=0.09,
    )
    recipe = Recipe("serving_int8_step", target, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    return recipe


def _build_serving_tp_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import FaultInjector, ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=True, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    # the TP-SHARDED serving quantum (tp=2 over the "mp" axis): params
    # split along heads/ffn through the SAME mp layers the training
    # recipes pin, KV pool leaves split along the kv-head axis (so
    # prefix aliasing/COW stay pure block-table ops under TP), and the
    # quantum still ONE jitted dispatch — its collectives live IN the
    # graph, and the census caps below pin their count and byte
    # volume. The tp=1 recipes' goldens must stay byte-identical: the
    # mesh enters only through this builder's engine.
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=4,
                           trace=True, slo=True, flight=True, tp=2,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    rng = np.random.RandomState(0)
    engine.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                  max_new_tokens=8)
    engine.step()  # admit + prefill so the audited state is live
    target, args = engine.decode_step_target()
    budget = Budget(
        name="TP2 serving decode quantum (bf16, 2-chip mesh)",
        max_remat=0,
        max_f32_matmuls=0,        # bf16 pool/params stay bf16
        max_host_callbacks=0,     # scheduler stays at host boundaries
        require_donated=True,     # the 2L KV pool leaves, still donated
        # the quantum's collective shape: one lm-head all-gather plus
        # one all-reduce per row-parallel matmul (2/layer) and the
        # embedding constraint — audited 6 ops / 35 328 B; the byte cap
        # leaves ~30% headroom, a per-layer re-gather of params or a
        # full-logits broadcast blows through it
        max_total_collectives=8,
        max_collective_bytes=46_000,
        # the donatable pool leaves must CARRY the mp axis (kv-head
        # split) — a refactor that drops the NamedSharding silently
        # replicates the pool per chip and doubles its HBM cost
        min_sharded_params=4,
        max_replicated_param_bytes=0,
        # audited 199 KB compiled temp (per-chip shares of the tp1
        # quantum's buffers) / 891 KB jaxpr trace peak — the liveness
        # walk is LOGICAL (pre-partitioning), so the peak cap matches
        # serving_decode_step's; same ~30% headroom on both
        max_temp_bytes=260_000,
        max_peak_live_bytes=1_300_000,
        # cost model: 2.77M flops / 21.1 MB LOGICAL (pre-partitioning
        # jaxpr) over 8 tokens — the tp collectives add ~11% flops of
        # in-graph reduction work over the tp1 quantum; per-chip cost
        # is half (the cross-check scales XLA's per-shard report by
        # the 2 partitions)
        cost_tokens_per_dispatch=8,
        max_flops_per_token=460_000,
        max_hbm_bytes_per_token=3_500_000,
        min_arithmetic_intensity=0.09,
    )
    recipe = Recipe("serving_tp_step", target, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    return recipe


def _build_serving_multiquantum_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import FaultInjector, ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    # the MULTI-QUANTUM while_loop driver (K=4 quanta per dispatch),
    # audited under the same full instrumentation + disarmed-injector +
    # resilience build as serving_decode_step: 0 host callbacks proves
    # the whole K-quantum loop (retirement masks, early all-done exit,
    # token buffer) stays on device, and the golden pins the driver.
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=4,
                           multi_quantum=4,
                           trace=True, slo=True, flight=True,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    rng = np.random.RandomState(0)
    engine.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                  max_new_tokens=8)
    engine.step()  # admit + prefill so the audited state is live
    target, args = engine.multiquantum_step_target()
    budget = Budget(
        name="serving multi-quantum driver (K=4, bf16, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip serving program
        max_f32_matmuls=0,        # bf16 pool/params stay bf16
        max_host_callbacks=0,     # K quanta, ZERO host re-entries
        require_donated=True,     # the 2L KV pool leaves
        # audited 330 KB temp / 891 KB trace peak: the quantum's own
        # buffers plus the (K, T, S) token buffer; same caps, same ~30%
        # headroom as serving_decode_step, whose scan this loop wraps
        max_temp_bytes=430_000,
        max_peak_live_bytes=1_300_000,
        # cost model: both walkers count the while_loop body ONCE, so
        # the numbers are serving_decode_step's one-quantum dispatch
        # (2 slots x 4 steps = 8 tokens; audited 2.49M flops / 18.8 MB:
        # 311k flops / 2.36 MB per token, 0.13 FLOP/B) and so are the
        # caps
        cost_tokens_per_dispatch=8,
        max_flops_per_token=420_000,
        max_hbm_bytes_per_token=3_100_000,
        min_arithmetic_intensity=0.09,
    )
    recipe = Recipe("serving_multiquantum_step", target, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    return recipe


def _build_serving_mixed_step():
    import numpy as np
    import paddle_tpu as paddle
    from ..nlp import LlamaConfig, LlamaForCausalLM
    from ..serving import FaultInjector, ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, dtype="bfloat16")
    model = LlamaForCausalLM(cfg)
    # the engine of serving_decode_step (full observability, the
    # resilience tier over a DISARMED injector), caught with BOTH kinds
    # of row in one step: the first request has finished its prefill
    # and rides along as a decode row while the second brings a chunk
    engine = ServingEngine(model, num_slots=2, block_size=4,
                           prefill_chunk=8, decode_quantum=4,
                           trace=True, slo=True, flight=True,
                           faults=FaultInjector(seed=0),
                           resilience=True)
    rng = np.random.RandomState(0)
    engine.submit(rng.randint(1, cfg.vocab_size, 6).astype(np.int32),
                  max_new_tokens=8)
    engine.step()  # admit + prefill: the first request decodes now
    engine.submit(rng.randint(1, cfg.vocab_size, 7).astype(np.int32),
                  max_new_tokens=8)
    engine._admit()  # the second one takes its slot; nothing dispatched
    target, args = engine.mixed_step_target()
    budget = Budget(
        name="serving mixed prefill step (bf16, single chip)",
        max_remat=0,
        max_total_collectives=0,  # single-chip serving program
        max_f32_matmuls=0,        # bf16 pool/params stay bf16
        max_host_callbacks=0,     # tokens are picked in the program
        require_donated=True,     # the 2L KV pool leaves
        # audited 205 KB temp / 837 KB trace peak over 2 slots x 8
        # positions; a lost donation (+263 KB) or (S, C, V) logits
        # instead of (S, V) blow these
        max_temp_bytes=270_000,
        max_peak_live_bytes=1_100_000,
        # cost model: 4.71M flops / 5.27 MB per step over 16 positions
        # (294k flops / 330 KB per position audited): the weights are
        # read once for the whole chunk, not once a token, hence seven
        # times the quantum's 0.13 FLOP/B
        cost_tokens_per_dispatch=16,
        max_flops_per_token=380_000,
        max_hbm_bytes_per_token=430_000,
        min_arithmetic_intensity=0.6,
    )
    recipe = Recipe("serving_mixed_step", target, args, budget)
    recipe.engine = engine  # obs CLI asserts the instrumented engine
    return recipe


RECIPES = {
    "llama_tp_zero_fused_lce": _build_llama_tp_zero_fused_lce,
    "llama_decode_greedy": _build_llama_decode_greedy,
    "serving_decode_step": _build_serving_decode_step,
    "speculative_verify_step": _build_speculative_verify_step,
    "serving_frontdoor_step": _build_serving_frontdoor_step,
    "serving_prefix_step": _build_serving_prefix_step,
    "serving_int8_step": _build_serving_int8_step,
    "serving_tp_step": _build_serving_tp_step,
    "serving_multiquantum_step": _build_serving_multiquantum_step,
    "serving_mixed_step": _build_serving_mixed_step,
}


def build(name):
    try:
        builder = RECIPES[name]
    except KeyError:
        raise KeyError(
            f"unknown recipe {name!r}; available: {sorted(RECIPES)}")
    return builder()


def run(name):
    """Build + budget-check one recipe; returns the AuditReport and
    restores global mesh state."""
    recipe = build(name)
    try:
        return recipe.check()
    finally:
        recipe.close()
