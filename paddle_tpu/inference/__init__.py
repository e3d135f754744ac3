"""paddle.inference — the Predictor serving facade (reference:
paddle/fluid/inference/api/analysis_predictor.cc, python surface
python/paddle/inference/ — unverified, SURVEY.md §0/§2.6).

The reference's AnalysisPredictor loads a program, runs IR fusion passes,
and serves via ZeroCopy tensors; on TPU the "analysis" is XLA compilation
of the jax.export artifact written by ``paddle.jit.save``, and zero-copy
handles are thin views over device arrays. TensorRT-style subgraphing has
no analog — XLA is the engine.
"""
from __future__ import annotations

import numpy as np

__all__ = [
    "Config", "Predictor", "create_predictor", "PrecisionType", "PlaceType",
]


class PrecisionType:
    Float32 = 0
    Half = 1
    Bfloat16 = 2
    Int8 = 3


class PlaceType:
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM = 3
    TPU = 4


class Config:
    """paddle.inference.Config parity (the knobs that matter here:
    model path prefix; everything GPU/TRT/MKLDNN is accepted and ignored
    with a record in ``ignored_options``)."""

    def __init__(self, prog_file=None, params_file=None):
        # paddle accepts Config(prefix) or Config(model_file, params_file)
        self._prefix = None
        if prog_file is not None:
            p = str(prog_file)
            self._prefix = p[:-8] if p.endswith(".pdmodel") else p
        self.ignored_options = []

    def set_prog_file(self, path):
        p = str(path)
        self._prefix = p[:-8] if p.endswith(".pdmodel") else p

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def __getattr__(self, name):
        # accept-and-record every enable_*/set_*/switch_* tuning knob
        if name.startswith(("enable_", "set_", "switch_", "disable_")):
            def sink(*a, **k):
                self.ignored_options.append(name)
            return sink
        raise AttributeError(name)


class _Handle:
    """Zero-copy tensor handle."""

    def __init__(self):
        self._value = None

    def copy_from_cpu(self, arr):
        import jax.numpy as jnp

        self._value = jnp.asarray(arr)

    def reshape(self, shape):
        if self._value is not None:
            self._value = self._value.reshape(shape)

    def copy_to_cpu(self):
        return np.asarray(self._value)

    def shape(self):
        return list(self._value.shape) if self._value is not None else []


class Predictor:
    def __init__(self, config: Config):
        from ..jit import load

        self._layer = load(config._prefix)
        n_in = self._n_inputs()
        self._inputs = {f"input_{i}": _Handle() for i in range(n_in)}
        self._outputs = {}

    def _n_inputs(self):
        # exact: recorded in the artifact at save time (older artifacts
        # derive it from the export signature) — no guessing
        return self._layer._n_inputs

    def get_input_names(self):
        return list(self._inputs)

    def get_input_handle(self, name):
        return self._inputs[name]

    def run(self, inputs=None):
        """paddle_infer::Predictor::Run. With ``inputs`` (list of arrays)
        returns outputs directly; else consumes the input handles."""
        if inputs is not None:
            vals = list(inputs)
        else:
            vals = [h._value for h in self._inputs.values()]
        out = self._layer(*vals)
        outs = out if isinstance(out, (list, tuple)) else [out]
        self._outputs = {}
        for i, o in enumerate(outs):
            h = _Handle()
            h._value = o._value if hasattr(o, "_value") else o
            self._outputs[f"output_{i}"] = h
        if inputs is not None:
            return [np.asarray(h._value) for h in self._outputs.values()]
        return True

    def get_output_names(self):
        return list(self._outputs)

    def get_output_handle(self, name):
        return self._outputs[name]

    def clone(self):
        """Per-thread clone (reference: AnalysisPredictor::Clone): the
        compiled program + params are immutable and shared; the
        input/output HANDLES are fresh so concurrent clones never race
        on each other's tensors."""
        new = object.__new__(Predictor)
        new._layer = self._layer
        new._inputs = {name: _Handle() for name in self._inputs}
        new._outputs = {}
        return new


def create_predictor(config: Config) -> Predictor:
    return Predictor(config)


def create_serving_engine(model, **kwargs):
    """Continuous-batching entry point next to ``create_predictor``:
    wrap a causal LM in a :class:`~paddle_tpu.serving.ServingEngine`
    (shared paged KV pool, chunked prefill, single-dispatch decode
    quantum). This is the LIBRARY LOOP — for the serving *system*
    (streaming, priorities, shedding, drain) use :func:`serve`, which
    wraps this engine in the front door.

    Keyword args forward to the engine — num_slots, block_size,
    decode_quantum, decode_strategy, eos_token_id, ...; pass
    ``spec_draft=<draft LM>`` (and ``spec_gamma``) to switch the
    quantum to the one-dispatch SPECULATIVE drafter/verifier round,
    ``decode_strategy="sampling"`` for the quantum whose per-slot
    temperature input carries each request's ``temperature``, and
    ``trace=True`` (or ``obs=<ServingObs>``) for
    the runtime observability layer — metrics registry + Chrome-trace
    request spans via :mod:`paddle_tpu.obs`, all recorded at host
    scheduler boundaries (the jitted quantum's fingerprint is
    unchanged). The operability tier rides the same boundaries:
    ``slo=True`` (or an :class:`~paddle_tpu.obs.slo.SLOSet` / list of
    :class:`~paddle_tpu.obs.slo.SLO`) attaches serving objectives —
    ``engine.health()`` evaluates them with multi-window burn rates,
    and :class:`~paddle_tpu.obs.export.MetricsExporter` serves the
    report live over ``/metrics`` / ``/healthz`` / ``/slo`` — and
    ``flight=True`` (or a
    :class:`~paddle_tpu.obs.flight.FlightRecorder`) journals every
    request's lifecycle (including preempt/resume events), dumping the
    journal on SLO-threshold crossings. ``prefix_cache=True``
    (DEFAULT OFF this release) turns on content-addressed prefix
    caching in the paged pool: admissions alias the longest cached
    chain of full prompt blocks instead of re-prefilling them
    (copy-on-write protects sharers; prefill compute and novel pool
    residency scale with UNIQUE tokens — the shared-system-prompt
    TTFT win), with streams bit-identical to the unshared engine.
    Per-request knobs ride ``engine.submit`` — priority, temperature,
    stop_token_ids, stop_sequences, max_new_tokens, seed.

    RESILIENCE: ``resilience=True`` (or a
    :class:`~paddle_tpu.serving.ResiliencePolicy`) arms the per-quantum
    watchdog, injected-fault retry with backoff, batch-bisect poison
    quarantine, the degradation ladders (spec auto-disable, prefix
    quarantine, pool accounting rebuild), and snapshot-based crash
    recovery (``engine.snapshot()`` / ``ServingEngine.restore()``);
    ``faults=`` threads a seeded
    :class:`~paddle_tpu.serving.FaultInjector` through the host
    boundaries for deterministic chaos testing (default disarmed —
    byte-identical goldens).

    QUANTIZED SERVING: ``quantize="weight_only_int8"`` sweeps every
    Linear (incl. the TP column/row-parallel splits) to the
    weight-only int8 kernel at build — the dequant multiplies INTO
    the matmul per element, so streams are BIT-IDENTICAL to a float
    engine holding the dequantized matrices — and ``kv_dtype="int8"``
    stores the paged KV pool as int8 rows + per-row f32 scale pools
    (quantized in-graph at every write, dequantized in-kernel at
    attention; ~4x less pool residency per block at large head_dim).
    The two axes are independent and COMPOUND with everything above:
    prefix sharing/COW, preemption, speculation (the draft pool
    quantizes in lockstep) and TP's per-chip split all operate on the
    smaller blocks, and the dtype-labeled ``serving_pool_bytes``
    gauges report the live residency. NOTE: the quantize sweep
    rewrites the model's Linears in place — hand each quantized
    engine its own freshly built model.

    TENSOR-PARALLEL SERVING: pass ``tp=2`` (or an explicit ``mesh=``
    with an ``"mp"`` axis) to shard the whole quantum family over the
    device mesh — params split along heads/ffn, paged KV pools split
    along the kv-head axis, the quantum stays ONE jitted dispatch with
    in-graph collectives, and streams stay bit-exact vs the tp=1
    engine. The model must be built ``tensor_parallel=True`` and its
    head counts must divide ``tp``; requesting ``tp>1`` with fewer
    visible devices raises with the CPU virtual-device setup
    (``XLA_FLAGS='--xla_force_host_platform_device_count=N'``). See
    :mod:`paddle_tpu.serving` and the README "TP-sharded serving"
    section.

    CLUSTER TIER: to scale past one engine, build N of these (each
    with its own freshly built model) and front them with
    :class:`~paddle_tpu.serving.ClusterRouter` +
    :class:`~paddle_tpu.serving.ClusterFrontDoor` — prefix-affinity
    routing on the pool's own
    :func:`~paddle_tpu.serving.prompt_prefix_key`, health-weighted
    balancing, prefill/decode disaggregation, and fleet
    snapshot/restore, all behind the exact same
    :class:`~paddle_tpu.serving.TokenStream` API (streams
    bit-identical to a single engine — see the README "Cluster
    serving" section)."""
    from ..serving import ServingEngine

    return ServingEngine(model, **kwargs)


def serve(model, policy=None, slo=True, flight=True, **kwargs):
    """The production front door (reference: the deployed serving
    system around AnalysisPredictor / ``Predictor.run`` — PAPER.md
    §2.6/§3.5): build a :class:`~paddle_tpu.serving.ServingEngine` and
    wrap it in a :class:`~paddle_tpu.serving.ServingFrontDoor` —
    token-by-token streaming (sync or ``async for`` under
    ``run_async()``), per-request generation params, priority classes
    with pool-pressure preemption (recompute-on-resume, bit-exact
    continuation), SLO-burn-rate load shedding + queue backpressure
    (``policy=`` a :class:`~paddle_tpu.serving.FrontDoorPolicy`), and
    graceful ``drain()``.

    ``slo`` / ``flight`` default ON (shedding needs the health report;
    drain flushes the journals); with ``decode_strategy="sampling"``
    ``submit(..., temperature=)`` works per request.
    ``prefix_cache=True`` (DEFAULT OFF this release) enables content-addressed prefix caching —
    shared system prompts alias cached KV blocks instead of
    re-prefilling, ``TokenStream.cached_prefix_tokens`` reports the
    per-request win. ``tp=2`` / ``mesh=`` shard the engine's quantum
    over the device mesh (tensor-parallel model required; streams stay
    bit-exact — :func:`create_serving_engine` documents the setup).
    ``quantize="weight_only_int8"`` / ``kv_dtype="int8"`` serve int8
    weights and an int8 KV pool (bit-identical streams vs the
    dequantized-float engine; residency compounds with prefix sharing
    and TP — :func:`create_serving_engine` documents the sweep).
    ``resilience=True`` arms the watchdog/retry/quarantine tier and
    makes the front door crash-recoverable
    (``fd.snapshot()`` / ``ServingFrontDoor.restore(snap, model)``
    re-opens every in-flight stream via recompute-on-resume);
    ``submit(..., timeout=)`` bounds each token wait. Remaining
    keyword args forward to the engine
    (:func:`create_serving_engine` documents them).

    CLUSTER: for a multi-replica fleet, wrap N engines (each a
    :class:`~paddle_tpu.serving.ClusterReplica`, which builds or
    accepts a door like this one) in a
    :class:`~paddle_tpu.serving.ClusterRouter` and submit through
    :class:`~paddle_tpu.serving.ClusterFrontDoor` — the same
    ``submit``/``TokenStream``/``drain``/``snapshot`` surface with
    prefix-affinity routing, health-weighted balancing, coordinated
    shedding, and optional prefill/decode role specialization
    (``role="prefill"`` / ``"decode"`` replicas, hand-off via
    recompute-on-resume). Streams stay bit-identical to this
    single-door path.

    ::

        fd = paddle.inference.serve(model, num_slots=8,
                                    eos_token_id=2)
        stream = fd.submit(prompt, priority=serving.INTERACTIVE,
                           max_new_tokens=128)
        for tok in stream:          # pumps the engine as it pulls
            ...
        fd.drain("flight.jsonl")    # stop admitting, finish, flush
    """
    from ..serving import ServingEngine, ServingFrontDoor

    engine = ServingEngine(model, slo=slo, flight=flight, **kwargs)
    return ServingFrontDoor(engine, policy=policy)


__all__ += ["create_serving_engine", "serve"]


def __getattr__(name):
    # lazy: serving imports the nlp tier, which loads after inference
    # during package init
    if name == "ServingEngine":
        from ..serving import ServingEngine

        return ServingEngine
    raise AttributeError(name)
