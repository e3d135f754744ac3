"""Continuous-batching serving engine over the paged KV pool.

The reference's serving story is the decode HOT LOOP that admits and
retires ragged requests against a shared KV cache (AnalysisPredictor /
``Predictor.run`` -> fused_multi_transformer, SURVEY.md §2.6/§3.5;
the blocked-cache serving predictor is unverified, SURVEY §0). The TPU
shape of that loop:

- **fixed-capacity slot batch**: the decode step is compiled ONCE for
  ``num_slots`` rows (the padded active set). Requests occupy slots;
  empty/finished slots ride along masked. No recompiles as traffic
  ebbs and flows.
- **single-dispatch decode quantum**: ``decode_quantum`` tokens for
  every live slot run inside ONE jitted program — a ``lax.scan`` of
  single-token steps over the shared
  :class:`~paddle_tpu.nlp.paged_cache.PagedKVCachePool`, with
  eos/max-len retirement masks computed ON DEVICE and the pool buffers
  donated (audited by the ``serving_decode_step`` analysis Budget: zero
  involuntary remat, zero host callbacks, pools donated). The host
  scheduler runs only at quantum boundaries, and in steady decode
  BESIDE the device: ``step()`` keeps one quantum in flight, the next
  enqueued on the last one's device-resident carry before the last is
  read back.
- **chunked prefill interleaved with decode**: new arrivals push their
  prompt through the pool in ``prefill_chunk``-token slices, sharing
  MIXED batches with the in-flight slots' decode rows — admission never
  stalls the running requests. A mixed step is ONE jitted,
  pool-donating program too (``paged_chunk_math`` with per-row counts,
  a program per power-of-two chunk length), and it ends in the
  quantum's own token selection: the host reads ``num_slots`` int32s.
- **block accounting**: retirement returns blocks to the pool free
  list for immediate reuse; admission is gated on worst-case demand so
  the pool cannot exhaust mid-flight (scheduler.py).

WHAT IS ASKED OF A MODEL (the layer protocol). The two jitted bodies
(``paged_decode_math``, ``paged_chunk_math``) keep the layer loop, the
embedding call, the positions, the write addresses, the final norm and the
head call, and know nothing of what a layer computes. Of the MODEL they ask

- ``model.decoder``: ``embed_tokens`` (a call: ids -> the hidden stream,
  any multiplier inside), ``paged_rope(positions)`` (what the layers'
  rotary embedding needs, once a step; None for a model without
  positions), ``layers`` and ``norm``; ``model.lm_head`` (a call);
- ``model.paged_cache_layout()``: the block pool's geometry (``layout``
  ``"kv"``: K and V rows; ``"latent"``: ONE row a token) and, per layer,
  WHAT IT CACHES (``layers``: per layer ONE part, a string, or SEVERAL, a
  tuple of them, as ``("kv", "state")`` for a layer that keeps key blocks
  and a slot's state at once, ``nlp/falcon_h1.py``; the parts are
  ``"kv"`` | ``"latent"``: block arrays, in
  the order of such parts; ``"state"``: a row of the pool's SLOT side,
  whose per-slot arrays ``state`` lists as ``(shape, dtype)``; a None in a
  shape is the length of a window layer's RING of keys, ``window`` (the
  layout's, 0 or absent without such layers) plus ``prefill_chunk`` in
  whole blocks; ``"none"``: NOTHING, a layer that is a feed-forward alone,
  as ``nlp/nemotron_h.py``'s expert layers are: it is handed an empty
  tuple, hands one back, and takes no place on either side of the pool).
  The pool's two sides, what is donated, committed and accounted
  (``bytes_per_token``, ``state_bytes_per_slot``), admission's demand and
  what preemption frees follow from the PARTS alone (``layout_parts``).
  A model whose layers all cache blocks has an empty slot side: no aval. What a model's layers cannot serve (one uniform window
  over the block path) the layout refuses, by raising; the engine reads no
  attribute of a model's config to decide it.

Of each LAYER they ask ``paged_decode(hidden, step, cache)`` and
``paged_chunk(hidden, step, cache)``: given the hidden stream, the step's
addressing and that layer's cache arrays, the new hidden stream and the
layer's new cache arrays. ``step`` holds ``rope``, ``tables``, ``lens``
(decode: each live row's length with this token; chunk: each row's base
length), ``write_blk`` / ``write_off``, ``live`` and, in a chunk, ``valid``
(S, C): the positions that bring a token. ``cache`` is ``(k, v, k_scale,
v_scale)`` for a block part (a side the pool lacks None) and the tuple of
``(num_slots, ...)`` arrays for a state part, row ``s`` slot ``s``'s; a
layer of one part is handed (and hands back) that part's arrays, a layer
of several a tuple of them in the order its layout entry names them. A
state part leaves a row's state where the row's last valid position put
it, starts a row whose base length is 0 from zeros (no host-side reset),
and keeps the state of a row that is not live; preemption frees the slot
and the blocks, and recompute-on-resume rebuilds the state AND the keys
from ``prompt + tokens``.
``nlp/paged_attention.PagedResidualLayer`` is the pre-norm residual
layer over K/V or latent attention (``nlp/llama.py``,
``nlp/deepseek_v3.py``: ``self_attn.paged_decode`` / ``paged_chunk``);
``nlp/granitemoehybrid.py`` has state-space layers beside attention,
``nlp/afmoe.py`` window layers (rings: position ``p`` at row ``p mod R``,
what a row may see decided by positions alone) beside full ones,
``nlp/nemotron_h.py`` layers of ONE part each: a state-space mixer, an
attention or routed experts (``"none"``) under one norm,
``nlp/falcon_h1.py`` layers that run a state-space mixer AND rotary
attention on one normed input (``("kv", "state")``),
``nlp/solar_open2.py`` delta-rule layers whose state part is SEVERAL
arrays of different dtypes a layer (a float32 matrix state a head and
three convolution tails) beside one block layer in four. A
feed-forward that routes rows to experts shows ``rows_per_expert``; both
programs hand back the rows the experts HELD here got beside the tokens
(``moe_rows``; an empty tuple, no aval, without experts).

Token selection reuses the generation tier's ``_filter_logits``
(greedy argmax or temperature/top-k/top-p sampling with per-slot key
fold-in); the greedy arm is oracle-tested bit-exact against
per-request sequential ``generate`` (tests/test_serving.py).

With ``spec_draft`` the decode quantum becomes the ON-DEVICE
speculative round (serving/speculative.py): a second (draft) paged
pool rides the same scheduler — admission gates on both pools plus the
verify-write margin, chunked prefill pushes the same mixed batches
through the draft, and one jitted dispatch per round covers draft-γ
scan + target verify + in-graph acceptance with BOTH pools donated.

Runtime observability rides the SAME boundaries the host scheduler
already owns (paddle_tpu/obs): ``engine.obs`` carries the metrics
registry (TTFT/e2e/inter-token histograms, windowed tok/s, acceptance
rate, pool gauges — ``engine.stats`` is a thin compatibility view over
its counters) and, with ``trace=True``, a Chrome trace-event recorder
(per-slot request spans + quantum spans, Perfetto-loadable). Because
every hook runs at a quantum/step boundary on the host, the jitted
programs keep ``max_host_callbacks=0`` and byte-identical golden
fingerprints with observability enabled — asserted by the
``serving_decode_step`` / ``speculative_verify_step`` recipes, which
build THIS engine with full instrumentation on.

The operability tier rides the same boundaries: ``slo=`` attaches
declarative objectives evaluated with multi-window burn rates
(``engine.health()``, served live by obs/export.py's ``/healthz`` /
``/slo``), and ``flight=`` a per-request flight recorder whose
journals dump on SLO-threshold crossings (obs/flight.py) — so a slow
tail request is explainable, not just a histogram bucket.

The FRONT DOOR (serving/frontend.py + serving/policy.py) wraps this
engine into the serving *system*: token-by-token streaming (the
``token_sink`` hook below fires per emitted token), priority classes
with :meth:`preempt` (evict a victim's blocks back to the pool,
recompute-on-resume), SLO-burn-rate load shedding through
``engine.health()`` and the obs ``on_shed`` hook, and graceful drain.
Every one of those mechanisms is host-side policy at the same
scheduler boundaries: the compiled quantum's ``max_host_callbacks=0``
budget and golden fingerprint are unchanged (the
``serving_frontdoor_step`` recipe pins the sampling quantum, per-slot
temperature input and all, with its own golden).

TENSOR-PARALLEL SERVING (``mesh=`` / ``tp=``): the whole quantum
family — greedy and sampling, the speculative draft+verify round, and
the mixed chunked-prefill batches — runs head/ffn-sharded over a 1-axis
``("mp",)`` mesh. Params are re-placed at engine build with the same tp2
layouts the training recipes pin (column: out-dim, row: in-dim, vocab-parallel
embedding), the paged pools go head-sharded (each chip holds every
block for ITS KV heads, so refcounted prefix sharing and COW stay pure
host bookkeeping), and each quantum remains ONE jitted dispatch whose
collectives GSPMD inserts in-graph — pools still donated, zero host
callbacks. The static collective profile (count/bytes by kind, read
from the compiled module at build) feeds the obs gauges and
``engine_stats()``; the ``serving_tp_step`` recipe pins the sharded
graph with ``min_sharded_params`` + a collective-byte cap and its own
golden. With no mesh (the default) every graph is byte-identical to
the single-chip engine — the tp parity tests exploit exactly that:
same seed, no mesh at model build, identical weights either way.
"""
from __future__ import annotations

import contextlib
import itertools

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import autograd
from ..jit import functional_call
from ..nlp.generation import _filter_logits
from ..nlp.paged_attention import ring_tokens
from ..nlp.paged_cache import PagedKVCachePool
from ..nn.quant import quantize_for_serving
from ..obs.flight import FlightRecorder
from ..obs.serving import ServingObs
from ..obs.slo import SLOSet
from ..parallel import mesh as mesh_state
from ..parallel.mesh import MeshScope
from ..profiler import RecordEvent, count_compile_events
from .faults import FaultInjector, InjectedFault
from .resilience import QuantumWatchdog, ResiliencePolicy
from .scheduler import Request, Scheduler, SchedulerConfig

__all__ = ["ServingEngine"]


def _resolve_tp_mesh(mesh, tp):
    """Normalize the engine's ``mesh=``/``tp=`` kwargs into
    ``(Mesh | None, tp_size)``. ``tp=1`` (or both None) is the
    single-chip engine — no mesh, byte-identical graphs. A bare ``tp=N``
    builds a 1-axis ``("mp",)`` mesh over the first N visible devices;
    an explicit mesh must carry an ``"mp"`` axis (and agree with ``tp``
    when both are given)."""
    if mesh is None and (tp is None or int(tp) <= 1):
        return None, 1
    from jax.sharding import Mesh

    if mesh is not None:
        if "mp" not in mesh.shape:
            raise ValueError(
                f"serving mesh has axes {tuple(mesh.shape)} but no 'mp' "
                f"axis: the quantum family shards params and KV pools "
                f"along 'mp' — build the mesh with an 'mp' axis (e.g. "
                f"Mesh(np.array(jax.devices()[:2]), ('mp',)))")
        size = int(mesh.shape["mp"])
        if tp is not None and int(tp) != size:
            raise ValueError(
                f"tp={tp} disagrees with the mesh's 'mp' axis size "
                f"{size}: pass only one, or make them match")
        return (mesh, size) if size > 1 else (None, 1)
    tp = int(tp)
    devs = jax.devices()
    if tp > len(devs):
        raise ValueError(
            f"tp={tp} needs {tp} visible devices but jax sees only "
            f"{len(devs)} ({devs[0].platform}). On CPU, expose virtual "
            f"devices BEFORE jax initializes — either "
            f"XLA_FLAGS='--xla_force_host_platform_device_count={tp}' "
            f"in the environment or "
            f"jax.config.update('jax_num_cpu_devices', {tp}) at startup "
            f"— then rebuild the engine")
    return Mesh(np.array(devs[:tp]), ("mp",)), tp


def _check_tp_divisible(cfg, tp, role):
    """The head-sharded layout needs both head counts to divide by tp:
    attention is computed per head, so a non-divisible count would force
    replicated attention and the pool could not shard at all."""
    if cfg.num_attention_heads % tp or cfg.num_key_value_heads % tp:
        raise ValueError(
            f"{role} model has num_attention_heads="
            f"{cfg.num_attention_heads}, num_key_value_heads="
            f"{cfg.num_key_value_heads}; both must divide by tp={tp} "
            f"for the head-sharded quantum layout")


def _tp_shard_params(model):
    """Re-place a tensor-parallel model's params onto the INSTALLED
    mesh (call under ``MeshScope``): mp-layer weights split along their
    parallel dim — the same tp2 layout the training recipes pin — and
    every other param committed replicated, so all quantum inputs are
    mesh-addressed. The model must have been BUILT with
    ``tensor_parallel=True`` but WITHOUT a mesh: mp layers then
    initialize exactly like their serial twins (same seed -> identical
    weights), which is what makes tp-vs-single-chip streams comparable
    bit-for-bit. Returns the number of mp-layer weights sharded (0
    means the model has no tensor-parallel structure)."""
    from ..distributed.fleet.layers.mpu.mp_layers import (
        ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
    from ..nn.quant import (
        QuantizedColumnParallelLinear, QuantizedRowParallelLinear)

    placed = set()

    def put(param, *spec):
        param._value = mesh_state.shard_value(param._value, *spec)
        placed.add(id(param))

    n_sharded = 0
    for _, layer in model.named_sublayers(include_self=True):
        if isinstance(layer, QuantizedColumnParallelLinear):
            # int8 weight splits like its float twin; the per-out-channel
            # scale vector rides the same "mp" split as the out dim.
            put(layer.quant_weight, None, "mp")
            put(layer.weight_scale, "mp")
            n_sharded += 1
            if layer.bias is not None:
                put(layer.bias, "mp")
        elif isinstance(layer, QuantizedRowParallelLinear):
            put(layer.quant_weight, "mp", None)
            put(layer.weight_scale)  # out-channel scales: replicated
            n_sharded += 1
            if layer.bias is not None:
                put(layer.bias)  # replicated: added after the all-reduce
        elif isinstance(layer, ColumnParallelLinear):
            put(layer.weight, None, "mp")
            n_sharded += 1
            if layer.bias is not None:
                put(layer.bias, "mp")
        elif isinstance(layer, RowParallelLinear):
            put(layer.weight, "mp", None)
            n_sharded += 1
            if layer.bias is not None:
                put(layer.bias)  # replicated: added after the all-reduce
        elif isinstance(layer, VocabParallelEmbedding):
            put(layer.weight, "mp", None)
            n_sharded += 1
    for _, p in model.named_parameters():
        if id(p) not in placed:
            p._value = mesh_state.replicate_value(p._value)
    return n_sharded


def layout_parts(layers):
    """``paged_cache_layout()["layers"]`` as one flat list of parts: a
    layer names one part (a string) or several (a tuple of them)."""
    return [part for kind in layers
            for part in ((kind,) if isinstance(kind, str) else kind)]


def pool_geometry(layout, num_slots, block_size, prefill_chunk):
    """What a model's ``paged_cache_layout()`` decides of its
    ``PagedKVCachePool``, as that class's keywords: block arrays for the
    parts that cache every key, a slot side for the parts that carry a
    state or a window's ring of keys (none: an empty side, no aval; a layer
    may name a part on each side); a ring holds the window and one chunk,
    in whole blocks."""
    kinds = layout_parts(layout["layers"])
    n_state = kinds.count("state")
    window = int(layout.get("window", 0))
    return {
        "num_kv_heads": layout["num_kv_heads"],
        "head_dim": layout["head_dim"],
        "num_layers": len(kinds) - n_state - kinds.count("none"),
        "layout": layout["layout"],
        "state": {"slots": num_slots, "layers": n_state,
                  "arrays": layout["state"],
                  "ring_tokens": ring_tokens(window, prefill_chunk,
                                             block_size) if window else 0}
        if n_state else None}


def _layer_caches(model, pools, state):
    """Per layer of ``model``, the cache arrays the step hands it, by
    what ``model.paged_cache_layout()["layers"]`` says it caches: a part
    with block arrays gets ``(k, v, k_scale, v_scale)`` of its place among
    such parts (a side the pool lacks, the scales of a float pool or the
    V side of a latent pool, is an empty tuple and reads None); a
    ``"state"`` part gets its tuple of per-slot arrays, a layer that
    caches nothing (``"none"``) an empty tuple. A layer of ONE part is
    handed that part's arrays; a layer that names several (``("kv",
    "state")``) a tuple of them, in the order it names them."""
    slot_rows, block_places = iter(state), itertools.count()

    def one(part):
        if part == "none":
            return ()
        if part == "state":
            return next(slot_rows)
        i = next(block_places)
        return tuple(p[i] if len(p) else None for p in pools)

    return [one(kind) if isinstance(kind, str)
            else tuple(one(part) for part in kind)
            for kind in model.paged_cache_layout()["layers"]]


def _collect_caches(model, new):
    """The layers' new cache arrays back in the step's order: the four
    block sides (a None side stays empty) and the state side; a part
    that caches nothing has no place on either, a layer of several parts
    a place on each side it names."""
    sides, state = ([], [], [], []), []
    for kind, arrays in zip(model.paged_cache_layout()["layers"], new):
        for part, got in (((kind, arrays),) if isinstance(kind, str)
                          else zip(kind, arrays)):
            if part == "state":
                state.append(tuple(got))
            elif part != "none":
                for side, arr in zip(sides, got):
                    if arr is not None:
                        side.append(arr)
    return (sides[0], sides[1], tuple(sides[2]), tuple(sides[3]),
            tuple(state))


def _expert_blocks(model):
    """The feed-forward blocks of ``model`` that route rows to experts
    (each with ``rows_per_expert`` over the ``num_experts`` it holds,
    ``router.top_k`` and ``inactive_params_per_token()``): a layer shows
    its own as ``mlp`` (None, or no such attribute, without one)."""
    return [layer.mlp for layer in model.decoder.layers
            if hasattr(getattr(layer, "mlp", None), "rows_per_expert")]


def moe_rows(model):
    """Rows each expert of each expert layer was handed in the forward
    just traced: a ``(expert layers, experts)`` int32 array, or ``()``
    for a model without routed experts (zero avals: such a model's
    programs are what they were). Read inside the same trace as the
    forward, as ``MoELayer.l_aux`` is."""
    blocks = _expert_blocks(model)
    return jnp.stack([b.rows_per_expert for b in blocks]) if blocks else ()


def _moe_rows_buffer(model, *lead):
    """Zeros for ``lead`` stacked :func:`moe_rows` results (``()`` for a
    model without experts)."""
    blocks = _expert_blocks(model)
    if not blocks:
        return ()
    return jnp.zeros((*lead, len(blocks), blocks[0].num_experts), jnp.int32)


def paged_decode_math(model, scratch_block, ids_t, seq_lens, tables,
                      kc, vc, live, ks=(), vs=(), st=()):
    """One token for every slot over a paged pool (the quantum's
    per-step body; mirrors generation._manual_decode with block-table
    writes instead of dense-cache slice updates). Parameterized by
    ``model`` so the plain quantum (target) and the speculative DRAFT
    scan (serving/speculative.py) share one decode-step definition.

    The body keeps the layer loop, the embedding call, the positions,
    the write addresses, the final norm and the head call; the rest is
    asked of each LAYER (``layer.paged_decode(hidden, step, cache)``:
    the new hidden stream and the layer's new cache arrays; see the
    module docstring). ``step`` is the step's addressing: ``rope``,
    ``tables``, ``lens`` (each live row's length with this token),
    ``write_blk`` / ``write_off`` and ``live``.

    ``ks``/``vs`` are the per-layer per-row scale pools of an int8
    pool (empty tuples on a float pool — zero extra avals, so the
    unquantized quantum graph and its golden are byte-identical),
    ``vc`` is empty on a latent pool and ``st``, the slot side (per
    state layer a tuple of arrays whose row ``s`` is slot ``s``'s), for
    a model without state layers. Returns
    ``(logits, new_kc, new_vc, new_ks, new_vs, new_st)``; a side that
    came in empty goes out empty."""
    core = model.decoder
    bs = kc[0].shape[1]
    w = tables.shape[1]

    with jax.named_scope("embed"):
        hidden = core.embed_tokens(ids_t)            # (S, 1, E)
    with jax.named_scope("attn.proj"):
        rope = core.paged_rope(seq_lens.astype(jnp.float32))

    with jax.named_scope("cache.write"):     # the step's addressing
        blk_idx = jnp.clip(seq_lens // bs, 0, w - 1)
        own_blk = jnp.take_along_axis(tables, blk_idx[:, None],
                                      axis=1)[:, 0]
        step = {"rope": rope, "tables": tables, "live": live,
                "write_blk": jnp.where(live, own_blk, scratch_block),
                "write_off": jnp.where(live, seq_lens % bs, 0),
                "lens": jnp.where(live, seq_lens + 1, 1)}

    new = []
    for layer, cache in zip(core.layers, _layer_caches(
            model, (kc, vc, ks, vs), st)):
        hidden, arrays = layer.paged_decode(hidden, step, cache)
        new.append(arrays)
    with jax.named_scope("head"):
        logits = model.lm_head(core.norm(hidden))._value[:, 0]
    return (logits, *_collect_caches(model, new))


def paged_chunk_math(model, scratch_block, ids_t, seq_lens, tables,
                     kc, vc, live, ks=(), vs=(), counts=None, st=()):
    """C tokens for every slot over a paged pool — ONE body for the
    speculative round's TARGET verify pass (reference: the speculative
    verify forward of the reference's serving stack — unverified,
    SURVEY.md §0) and for the engine's mixed prefill step. Chunk
    position j writes its KV at ``seq_lens + j`` (masked rows go to the
    scratch block) and attends its own prefix; one batched forward
    covers all slots and all C positions. Everything between the
    embedding and the final norm is each layer's ``paged_chunk`` (see
    ``paged_decode_math``); ``step`` carries ``lens`` (each row's BASE
    length: what it had cached before this chunk) and ``valid``, (S, C),
    the positions that bring a token. A state layer leaves a row's state
    where the row's last valid position put it, starts a row whose base
    length is 0 from zeros, and keeps the state of a row that is not
    live.

    ``counts=None`` is the verify pass: every position of a live row is
    valid and the logits of all of them come back, (S, C, V). Stale
    tail slots from rejected proposals are rolled back by LENGTH MASK:
    the caller shrinks ``seq_lens`` and the next round's writes simply
    overwrite them. ``counts`` (S,) is the mixed step: row s brings
    ``counts[s] <= C`` tokens (a prefill chunk, one token for a decode
    row riding along, 0 for an idle slot); the positions past a row's
    count write to the scratch block, so no valid position ever reads
    them, and the head runs only at each row's last valid position:
    (S, V) logits, never (S, C, V)."""
    core = model.decoder
    c = ids_t.shape[1]
    bs = kc[0].shape[1]
    w = tables.shape[1]

    with jax.named_scope("embed"):
        hidden = core.embed_tokens(ids_t)            # (S, C, E)
    with jax.named_scope("attn.proj"):
        rope = core.paged_rope(
            (seq_lens[:, None]
             + jnp.arange(c)[None, :]).astype(jnp.float32))

    with jax.named_scope("cache.write"):     # the step's addressing
        valid = live[:, None]
        if counts is not None:
            valid = valid & (jnp.arange(c)[None, :] < counts[:, None])
        wpos = seq_lens[:, None] + jnp.arange(c)[None, :]
        blk_idx = jnp.clip(wpos // bs, 0, w - 1)
        own_blk = jnp.take_along_axis(tables, blk_idx, axis=1)
        step = {"rope": rope, "tables": tables, "live": live,
                "valid": valid,
                "write_blk": jnp.where(valid, own_blk, scratch_block),
                "write_off": jnp.where(valid, wpos % bs, 0),
                "lens": jnp.where(live, seq_lens, 0)}

    new = []
    for layer, cache in zip(core.layers, _layer_caches(
            model, (kc, vc, ks, vs), st)):
        hidden, arrays = layer.paged_chunk(hidden, step, cache)
        new.append(arrays)
    with jax.named_scope("head"):
        if counts is None:
            logits = model.lm_head(core.norm(hidden))._value
        else:
            last = jnp.maximum(counts - 1, 0)[:, None, None]
            logits = model.lm_head(core.norm(Tensor(
                jnp.take_along_axis(hidden._value, last, axis=1),
                stop_gradient=True)))._value[:, 0]
    return (logits, *_collect_caches(model, new))


class _AuditedStep:
    """Callable+lowerable wrapper handed to ``analysis.check_budget``:
    declares how many LEADING flat args the quantum donates (the KV
    pool leaves — 2L for the plain quantum, 2L_target + 2L_draft for
    the speculative round) so ``require_donated`` audits the right
    set. A TP engine also carries its mesh: the audit re-traces the
    quantum OUTSIDE the engine's dispatch path, so trace and lowering
    here must run under the same ``MeshScope`` the engine uses (mp
    layers degrade to serial math when no mesh is installed)."""

    def __init__(self, jitted, n_donatable, name="serving_decode_quantum",
                 mesh=None):
        self._jitted = jitted
        self.n_donatable = int(n_donatable)
        self.__name__ = name
        self._mesh = mesh

    def _scope(self):
        return (MeshScope(self._mesh) if self._mesh is not None
                else contextlib.nullcontext())

    def __call__(self, *args):
        with self._scope():
            return self._jitted(*args)

    def lower(self, *args):
        with self._scope():
            return self._jitted.lower(*args)


class ServingEngine:
    """Multiplex many in-flight generation requests over one shared
    paged KV pool and one jitted decode step.

    Args:
        model: a LlamaForCausalLM-shaped causal LM (eval mode; params
            define the cache dtype).
        num_slots: fixed decode batch capacity (padded active set).
        block_size: KV pool block size in tokens.
        num_blocks: pool capacity; default sizes the pool for
            ``num_slots`` full-context sequences plus the scratch block.
        max_context: per-request prompt+generation bound (defaults to
            the model's max_position_embeddings).
        prefill_chunk / decode_quantum: see SchedulerConfig.
        decode_strategy: "greedy" | "sampling" (engine-wide; sampling
            knobs via top_k/top_p/temperature, per-request seeds). A
            sampling engine without ``spec_draft`` takes each slot's
            temperature as an (S,) f32 input of both programs, so
            ``submit(..., temperature=)`` works per request and a
            request that names none gets the engine-wide one.
        eos_token_id: retire a slot the step after it emits this id.
        spec_draft: optional DRAFT causal LM (same vocab) switching the
            decode quantum to the speculative drafter/verifier round
            (serving/speculative.py): the draft scans ``spec_gamma``
            proposals, the target verifies all γ+1 positions in one
            forward, and acceptance/bonus/resample + both caches' roll
            forward/back happen in-graph — ONE dispatch per round. The
            greedy arm emits exactly the target's greedy stream; the
            sampling arm is distribution-exact rejection sampling.
        spec_gamma: proposals per speculative round (default 4).
        prefix_cache: enable CONTENT-ADDRESSED PREFIX CACHING
            (default OFF this release): full prompt blocks are
            published into the pool's chain-hash index at prefill
            completion, and admission aliases the longest cached chain
            into the new request's block tables (target + draft pool in
            lockstep) — prefill then skips the aliased tokens, so
            prefill compute and novel pool residency scale with UNIQUE
            tokens (the shared-system-prompt TTFT win). The first
            token written into a still-shared block copy-on-writes it;
            eviction under pool pressure reclaims cached blocks only
            at refcount one. All of it is host-side allocator policy:
            the compiled quantum, its golden fingerprint, and the
            emitted streams are bit-identical either way (the
            ``serving_prefix_step`` recipe gates this).
        obs: observability sink — ``None`` builds a fresh
            :class:`~paddle_tpu.obs.serving.ServingObs` (metrics
            registry always on), ``"off"`` disables the rich hooks
            (histograms/gauges/tracer; the legacy ``stats`` counters
            keep working — the overhead-bench baseline), or pass a
            :class:`ServingObs` to share a registry across engines.
            Every hook fires at host scheduler boundaries only: the
            jitted quantum keeps its ``max_host_callbacks=0`` budget
            and byte-identical golden fingerprint (tier-1 gated).
        trace: record Chrome trace events (request lifecycle spans,
            quantum spans, occupancy/pool counter tracks) into
            ``engine.obs.tracer`` — export with
            ``engine.obs.tracer.save(path)``, open in Perfetto.
        slo: serving objectives (:mod:`paddle_tpu.obs.slo`) —
            ``True`` attaches the stock set (p95 TTFT, p99 inter-token,
            p99 e2e, error/shed rate), or pass an
            :class:`~paddle_tpu.obs.slo.SLOSet` / list of
            :class:`~paddle_tpu.obs.slo.SLO`. ``engine.health()``
            evaluates them with multi-window burn rates over the obs
            sample series; the exporter's ``/healthz`` & ``/slo``
            endpoints (obs/export.py) serve the same report live.
        flight: per-request flight recorder
            (:mod:`paddle_tpu.obs.flight`) — ``True`` builds one whose
            dump-on-anomaly thresholds come from ``slo``, or pass a
            :class:`~paddle_tpu.obs.flight.FlightRecorder`. Journals
            every lifecycle event (submit/admit/prefill chunks/first
            token/quantum yields/spec rounds/retire) at host scheduler
            boundaries; a request crossing its TTFT/e2e SLO threshold
            dumps its full journal to ``engine.flight.anomalies``.
            Like every obs hook, the compiled quantum is untouched
            (fingerprint-gated).
        mesh / tp: TENSOR-PARALLEL SERVING. ``tp=N`` (N > 1) builds a
            1-axis ``("mp",)`` mesh over the first N visible devices;
            ``mesh=`` passes an explicit ``jax.sharding.Mesh`` with an
            ``"mp"`` axis instead (both together must agree). The model
            (and draft) must be BUILT with ``tensor_parallel=True`` but
            WITHOUT a global mesh — mp layers then initialize exactly
            like their serial twins, so a tp engine and a single-chip
            engine seeded identically hold identical weights and their
            streams compare bit-for-bit (the tier-1 parity oracle). At
            engine build the params are re-placed head/ffn-sharded
            (Column/Row-parallel + vocab-parallel layouts, the same tp2
            placement the training recipes pin), the paged KV pools go
            head-sharded (``P(None, None, 'mp', None)`` — block ids and
            refcounted prefix sharing/COW stay plain host bookkeeping),
            and every quantum variant remains ONE jitted dispatch with
            in-graph collectives, pools still donated. The quantum's
            static collective profile (count + bytes by kind, from the
            compiled module at build — never runtime callbacks) lands
            in ``engine_stats()['quantum_collectives']`` and the obs
            registry. Default ``tp=None`` (single chip): no mesh, and
            every compiled graph — and golden fingerprint — is
            byte-identical to previous releases. On CPU expose virtual
            devices BEFORE jax initializes (e.g.
            ``XLA_FLAGS='--xla_force_host_platform_device_count=8'``).
        faults: a :class:`~paddle_tpu.serving.faults.FaultInjector`
            threaded through the engine's host boundaries (quantum
            dispatch, pool allocation, cached-KV corruption). Default:
            a fresh DISARMED injector — every hook is a constant-time
            no-op and all compiled goldens stay byte-identical (the
            serving recipes build with exactly this to pin it).
        resilience: ``True`` (stock
            :class:`~paddle_tpu.serving.resilience.ResiliencePolicy`)
            or a policy instance arms the resilience tier: injected
            faults retry with exponential backoff then contain at the
            step boundary (poison requests are isolated by batch
            bisect and finished with ``finish_reason="error"``), a
            wall-clock watchdog self-calibrated from the quantum
            latency histogram feeds the degradation ladders (repeated
            spec-round faults fall back to the plain quantum — same
            compiled family, no new golden), prefix chain-hash content
            verify quarantines corrupted cached subtrees, and pool
            accounting drift rebuilds the allocator from the live
            block tables. Default ``None``: fail-stop exactly as
            before.
        quantize: ``"weight_only_int8"`` (or ``"llm.int8"``) sweeps the
            target — and draft — stacks through
            :func:`~paddle_tpu.nn.quant.quantize_for_serving` at build,
            BEFORE AOT lowering: every quantum arm's executable carries
            int8 weights + per-out-channel scales, and the dequant
            multiply fuses into each matmul (weights stay int8 in HBM).
            The per-element dequant is IEEE-exact, so greedy streams are
            BIT-IDENTICAL to a float engine holding the dequantized
            weights — the parity oracle the tests pin. TP-composable:
            quantized mp layers shard their scales with the layer's
            split. Default ``None``: float weights, graphs untouched.
        kv_dtype: ``"int8"`` builds both paged pools quantized: int8
            block buffers plus per-row f32 scale pools ((NB, BS, HK),
            one scale per written row), symmetric abs-max quant at
            every KV-write site IN-GRAPH and dequant inside the
            attention gather — still one dispatch, all four pool
            pytrees donated. A row's scale depends only on its own
            values, so prefix sharing, COW (scale rows copy with the
            block), LRU eviction, preemption, and snapshot/restore work
            unchanged, and shared-vs-unshared streams stay
            bit-identical. Halves KV residency (int8 + d-wide scale vs
            2-byte floats). Default ``None``: float pools, every
            existing golden byte-identical (the scale tuples are empty
            pytrees — zero extra avals in the quantum signature).
        multi_quantum: MULTI-QUANTUM DECODE DRIVER. ``K > 1`` builds a
            second quantum-family variant that runs UP TO K decode
            quanta per dispatch under ``lax.while_loop``, re-entering
            the host only when the scheduler's ``steady_state()``
            predicate says admission could change (waiting queue
            non-empty, a slot mid-prefill) or every row retired — the
            on-device eos/max-len masks the quantum already carries
            both retire rows mid-flight AND short-circuit the loop when
            the whole batch is done. The driver accounts a K-quantum
            dispatch as K quanta (obs histograms, cost ledger, flight
            journals, watchdog normalization), so every conservation
            invariant holds exactly, and its streams are BIT-IDENTICAL
            to the per-quantum driver: between steady-state quanta the
            host round-trips device state through int32 mirrors without
            touching it, so folding K round-trips into the device loop
            changes no math (tests pin greedy/sampling/prefix/int8/
            preemption arms). Admission reservations already cover each
            row's worst-case growth (``prompt + max_new + margin``), so
            the K-wide block-table pre-growth can never oversubscribe
            the pool. A speculative engine ignores K: each spec round
            needs its acceptance counts on the host. Default ``1``: the
            variant isn't built, nothing changes.
    """

    def __init__(self, model, num_slots=8, block_size=32, num_blocks=None,
                 max_context=None, prefill_chunk=64, decode_quantum=8,
                 decode_strategy="greedy", top_k=0, top_p=1.0,
                 temperature=1.0, eos_token_id=None, spec_draft=None,
                 spec_gamma=4, prefix_cache=False, obs=None,
                 trace=False, slo=None, flight=None, mesh=None, tp=None,
                 faults=None, resilience=None, quantize=None,
                 kv_dtype=None, multi_quantum=1):
        cfg = model.config
        if decode_strategy not in ("greedy", "sampling"):
            raise ValueError(
                f"decode_strategy must be greedy|sampling, got "
                f"{decode_strategy!r}")
        self._mq_max = int(multi_quantum)
        if self._mq_max < 1:
            raise ValueError(
                f"multi_quantum must be >= 1, got {multi_quantum}")
        self.mesh, self.tp = _resolve_tp_mesh(mesh, tp)
        layout = model.paged_cache_layout()
        kinds = layout_parts(layout["layers"])
        # nothing is silently ignored: what a latent pool, latent
        # attention, a slot's recurrent state or a window layer's ring
        # cannot do yet is refused by name (what can be served at all is
        # the layout's to say: a model whose layers cannot raises there)
        refused = {"kv_dtype='int8'": kv_dtype == "int8",
                   "tp > 1": self.tp > 1,
                   "spec_draft": spec_draft is not None}
        self._window = int(layout.get("window", 0))
        if "state" in kinds:
            what = "a window-ring" if self._window else "a state-space"
            refused["prefix_cache=True"] = bool(prefix_cache)
        elif "latent" in kinds:
            what = "a latent-attention"
        else:
            refused = {}
        for name, asked in refused.items():
            if asked:
                raise NotImplementedError(
                    f"ServingEngine does not compose {name} with {what} "
                    f"model ({type(model).__name__}) yet")
        if spec_draft is not None and set(layout_parts(
                spec_draft.paged_cache_layout()["layers"])) != {"kv"}:
            raise NotImplementedError(
                "ServingEngine does not take a latent-attention, "
                "state-space or window-ring model "
                f"({type(spec_draft).__name__}) as spec_draft yet")
        if self.tp > 1:
            _check_tp_divisible(cfg, self.tp, "target")
            if spec_draft is not None:
                _check_tp_divisible(spec_draft.config, self.tp, "draft")
        if spec_draft is not None:
            d_cfg = spec_draft.config
            if d_cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {d_cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}: acceptance compares token ids")
            if int(spec_gamma) < 1:
                raise ValueError(
                    f"spec_gamma must be >= 1, got {spec_gamma}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"unsupported kv_dtype {kv_dtype!r} (None or 'int8')")
        self.quantize = quantize
        self.kv_dtype = kv_dtype
        self.model = model
        if quantize is not None:
            # sweep BEFORE .eval()/tp-shard/_p_vals snapshot: the
            # quantized params must be what every arm lowers against
            quantize_for_serving(model, algo=quantize)
            if spec_draft is not None:
                quantize_for_serving(spec_draft, algo=quantize)
        model.eval()
        self.spec_draft = spec_draft
        self.spec_gamma = int(spec_gamma)
        self.config = SchedulerConfig(num_slots=num_slots,
                                      prefill_chunk=prefill_chunk,
                                      decode_quantum=decode_quantum)
        self.decode_strategy = decode_strategy
        self.top_k = 0 if top_k is None else int(top_k)
        self.top_p = 1.0 if top_p is None else float(top_p)
        self.temperature = 1.0 if temperature is None else float(temperature)
        self.eos_token_id = (None if eos_token_id is None
                             else int(eos_token_id))

        self.max_context = int(max_context
                               or cfg.max_position_embeddings)
        if self.tp > 1:
            with MeshScope(self.mesh):
                if _tp_shard_params(model) == 0:
                    raise ValueError(
                        "tp>1 needs a tensor-parallel model: build it "
                        "with config.tensor_parallel=True (Column/Row-"
                        "parallel layers) — this model has no mp layers "
                        "to shard")
        self._p_vals = [p._value for _, p in model.named_parameters()]
        # the model dtype the float pools inherit: first FLOATING param
        # (a quantized stack's first param may be an int8 weight)
        cache_dtype = next(
            (v.dtype for v in self._p_vals
             if jnp.issubdtype(v.dtype, jnp.floating)),
            self._p_vals[0].dtype)
        s = self.config.num_slots
        bs = int(block_size)
        # the speculative verify writes up to gamma slots past the
        # accepted history before the length mask rolls them back, so
        # tables (and the worst-case admission demand) carry that margin
        margin = self.spec_gamma if spec_draft is not None else 0
        w = -(-(self.max_context + margin) // bs)
        if num_blocks is None:
            num_blocks = s * w + 1  # +1: the masked-write scratch block
        self.prefix_cache = bool(prefix_cache)
        self.pool = PagedKVCachePool(
            num_blocks, bs, dtype=cache_dtype,
            prefix_cache=self.prefix_cache, mesh=self.mesh,
            kv_dtype=kv_dtype,
            **pool_geometry(layout, s, bs, self.config.prefill_chunk))
        self.pool.commit_like(self._p_vals[0])
        # masked (retired/empty) rows dump their KV writes here
        self._scratch_block = self.pool.ensure("__scratch__", 1)[0]
        self.d_pool = None
        if spec_draft is not None:
            spec_draft.eval()
            if self.tp > 1:
                with MeshScope(self.mesh):
                    if _tp_shard_params(spec_draft) == 0:
                        raise ValueError(
                            "tp>1 needs a tensor-parallel DRAFT model: "
                            "build it with config.tensor_parallel=True "
                            "— this draft has no mp layers to shard")
            self._d_p_vals = [p._value
                              for _, p in spec_draft.named_parameters()]
            d_cfg = spec_draft.config
            d_cache_dtype = next(
                (v.dtype for v in self._d_p_vals
                 if jnp.issubdtype(v.dtype, jnp.floating)),
                self._d_p_vals[0].dtype)
            # the draft pool quantizes too: spec decoding doubles pool
            # pressure, so the residency win must cover both pools
            d_layout = spec_draft.paged_cache_layout()
            self.d_pool = PagedKVCachePool(
                num_blocks, bs, d_layout["num_kv_heads"],
                d_layout["head_dim"], num_layers=d_cfg.num_hidden_layers,
                dtype=d_cache_dtype,
                prefix_cache=self.prefix_cache, mesh=self.mesh,
                kv_dtype=kv_dtype)
            self.d_pool.commit_like(self._d_p_vals[0])
            self._d_scratch_block = self.d_pool.ensure("__scratch__",
                                                       1)[0]
        self.scheduler = Scheduler(
            self.config, self.pool, reserved_blocks=1,
            companion_pools=[self.d_pool] if self.d_pool is not None
            else [], token_margin=margin)
        self._table_width = w

        # host mirrors of the per-slot device state
        self._tables = np.zeros((s, w), np.int32)
        self._seq_lens = np.zeros(s, np.int32)
        self._last_tok = np.zeros(s, np.int32)
        self._n_gen = np.zeros(s, np.int32)
        self._done = np.ones(s, bool)
        self._max_new = np.zeros(s, np.int32)
        self._keys = np.zeros((s, 2), np.uint32)
        # per-slot temperature: an input of both programs of a sampling
        # engine; a greedy engine's programs never see it, and the
        # speculative round's acceptance math takes the engine-wide one
        self._temps = (np.ones(s, np.float32)
                       if decode_strategy == "sampling"
                       and spec_draft is None else None)
        # what of that state is ON the device between two quanta: the
        # last dispatched quantum's own outputs (seq_lens, last_tok,
        # n_gen, done), which are the next one's arguments while the
        # four mirrors still read what they read when host and device
        # last agreed (``_carry_seen``: at an upload, at a collect); and,
        # by name, the last upload of each mirror the device never
        # writes (tables, max_new, keys, temps), kept while it is equal
        self._carry = None
        self._carry_seen = None
        self._kept = {}
        # step()'s one-deep pipeline: the quantum dispatched AHEAD of the
        # last collect, and where the last collected quantum ended (an
        # ahead quantum's wall starts there, not at its dispatch)
        self._inflight = None
        self._decode_end = 0.0
        # front-door streaming hook: called (req, token) for EVERY
        # token appended to a request's stream, at the same host
        # boundary obs.on_token fires on
        self.token_sink = None

        n_pool = (4 if self.pool.quantized
                  else self.pool.arrays_per_layer)
        # every pool leaf is donated: the block arrays and the slot side
        n_donated = len(jax.tree_util.tree_leaves(self.pool.arrays()))
        # the mixed step: ONE jitted, pool-donating program per model
        # (the draft's writes its KV only); jit keeps an executable per
        # chunk-length bucket, built on the bucket's first use
        self._mixed = _AuditedStep(
            jax.jit(self._make_mixed(model, self._scratch_block, True),
                    donate_argnums=(0, 1, 2, 3, 4)),
            n_donatable=n_donated,
            name="serving_mixed_step", mesh=self.mesh)
        self._mixed_buckets = set()
        if spec_draft is not None:
            from .speculative import make_spec_round

            self._d_tables = np.zeros((s, w), np.int32)
            self._d_mixed = _AuditedStep(
                jax.jit(self._make_mixed(spec_draft,
                                         self._d_scratch_block, False),
                        donate_argnums=(0, 1, 2, 3, 4)),
                n_donatable=n_pool * d_cfg.num_hidden_layers,
                name="serving_mixed_step_draft", mesh=self.mesh)
            # argnums 0..7 = target kc/vc/ks/vs + draft kc/vc/ks/vs; on
            # a float engine the scale tuples are EMPTY pytrees, so
            # donating them is a no-op and the flat donated set — and
            # every existing golden — is unchanged
            self._quantum = jax.jit(
                make_spec_round(self),
                donate_argnums=(0, 1, 2, 3, 4, 5, 6, 7))
            self._audited = _AuditedStep(
                self._quantum,
                n_donatable=n_pool
                * (cfg.num_hidden_layers + d_cfg.num_hidden_layers),
                name="speculative_verify_step", mesh=self.mesh)
        else:
            self._quantum = jax.jit(self._make_quantum(),
                                    donate_argnums=(0, 1, 2, 3, 4))
            self._audited = _AuditedStep(
                self._quantum, n_donatable=n_donated, mesh=self.mesh)
        # the multi-quantum while_loop variant: built ONLY when asked
        # for (K > 1, non-speculative) — same signature as the plain
        # quantum, so `_quantum_args()` feeds both; the default
        # engine's compiled family and goldens never see it
        self._mq_quantum = None
        self._mq_audited = None
        if self._mq_max > 1 and spec_draft is None:
            self._mq_quantum = jax.jit(
                self._make_quantum(multi=self._mq_max),
                donate_argnums=(0, 1, 2, 3, 4))
            self._mq_audited = _AuditedStep(
                self._mq_quantum, n_donatable=n_donated,
                name="serving_multiquantum_step", mesh=self.mesh)
        # under tp the small per-slot state rides every dispatch
        # committed replicated, so the compiled quantum's input layouts
        # are pinned (never re-inferred per call)
        self._rep_sharding = None
        if self.tp > 1:
            from jax.sharding import NamedSharding, PartitionSpec

            self._rep_sharding = NamedSharding(self.mesh,
                                               PartitionSpec())
        # where a quantum's small outputs live: committed to the pools'
        # device beside committed weights (a jitted step's outputs are
        # committed when any input is), else wherever JAX puts them; a
        # carry mirror is uploaded THERE, so the quantum has one
        # executable whichever of the two its arguments come from
        leaf = jax.tree_util.tree_leaves(self.pool.arrays())[0]
        self._carry_sharding = (leaf.sharding if self.mesh is None
                                and leaf.committed else self._rep_sharding)
        # build-time collective census (tp > 1 only): lower + compile
        # the quantum ONCE under the mesh, census the post-GSPMD module
        # for the obs gauges, and KEEP the compiled executable as the
        # dispatch target — the census compile IS the engine's compile,
        # so the profile costs no extra compile and needs no runtime
        # callbacks. tp=1 engines honestly report zeros (their recipes
        # already pin max_total_collectives=0).
        self._quantum_compiled = None
        self.quantum_collectives = {"tp": self.tp, "count_total": 0,
                                    "bytes_total": 0, "by_kind": {}}
        if self.tp > 1:
            from ..analysis.collectives import collective_census

            with MeshScope(self.mesh):
                self._quantum_compiled = self._quantum.lower(
                    *self._quantum_args()).compile()
            census = collective_census(self._quantum_compiled.as_text())
            by_kind = {k: {"count": st.count, "bytes": st.bytes}
                       for k, st in census.items() if st.count}
            self.quantum_collectives = {
                "tp": self.tp,
                "count_total": sum(d["count"] for d in by_kind.values()),
                "bytes_total": sum(d["bytes"] for d in by_kind.values()),
                "by_kind": by_kind,
            }
        self.completed: list = []
        # observability: metrics registry (always on unless "off") +
        # optional tracer; `stats` is the legacy dict READ/WRITE view
        # over the same registry counters (one source of truth)
        if obs == "off":
            self.obs = ServingObs(enabled=False)
        elif obs is None:
            self.obs = ServingObs(trace=trace)
        else:
            self.obs = obs
            if trace and self.obs.tracer is None:
                from ..obs.trace import TraceRecorder

                self.obs.tracer = TraceRecorder.process()
        # JAX's compile events, charged to the step span that caused
        # them (profiler.count_compile_events), on this registry too
        count_compile_events(self.obs.registry)
        self._now = self.obs.now
        self.stats = self.obs.legacy_stats_view()
        # static per-build collective profile -> registry gauges (zeros
        # suppressed; a tp=1 engine leaves the series empty)
        self.obs.set_quantum_collectives(self.quantum_collectives)
        self.obs.set_pool_bytes_per_token(self.pool.bytes_per_token())
        self.obs.set_state_bytes_per_slot(self.pool.state_bytes_per_slot())
        self.obs.set_window_bytes_per_slot(
            self.pool.window_bytes_per_slot())
        # cost-ledger MFU constants (obs/attribution.py): target-model
        # FLOPs per decoded token (2N weight-matmul floor, embedding
        # gathers excluded) and the chip peak (0.0 on the CPU backend —
        # the MFU gauge then reads 0 and raw FLOP/s is the number; an
        # accelerator the peak table does not know raises)
        from ..obs.attribution import decode_flops_per_token
        from ..profiler.mfu import peak_flops_per_chip

        # "params actually multiplied per token": of an expert layer's
        # routed experts a token multiplies its top k, not all of them
        blocks = _expert_blocks(model)
        self._moe_top_k = blocks[0].router.top_k if blocks else 0
        n_params = sum(int(v.size) for v in self._p_vals) - sum(
            b.inactive_params_per_token() for b in blocks)
        embed = (int(getattr(cfg, "vocab_size", 0))
                 * int(getattr(cfg, "hidden_size", 0)))
        # int8 flops model: a quantized stack feeds the MXU's int8 path,
        # whose peak is 2x the bf16 peak — the MFU denominator doubles
        # (flops per token is unchanged: same 2N contraction count)
        self.obs.ledger.configure(
            flops_per_token=decode_flops_per_token(
                n_params, n_embedding_params=embed),
            peak_flops=peak_flops_per_chip()
            * (2.0 if quantize is not None else 1.0))
        # SLO + flight recorder (the operability tier over the obs
        # boundaries): health feeds the front door's shedding policy
        # (serving/frontend.py), and the journal explains a slow tail
        # request after the fact
        if slo is True:
            self.slo = SLOSet()
        elif slo is None or isinstance(slo, SLOSet):
            self.slo = slo
        else:
            self.slo = SLOSet(slo)
        if flight is True:
            self.flight = FlightRecorder(slo=self.slo)
        elif flight is None or flight is False:
            self.flight = None
        else:
            self.flight = flight
        # resilience tier (serving/faults.py + serving/resilience.py):
        # a disarmed injector is a constant-time no-op at every hook,
        # so a default engine — and every golden — is untouched
        self.faults = faults if faults is not None else FaultInjector()
        self.pool.fault_hook = self.faults.on_alloc
        if self.d_pool is not None:
            self.d_pool.fault_hook = self.faults.on_alloc
        if resilience is True:
            resilience = ResiliencePolicy()
        self.resilience = resilience
        self.watchdog = (QuantumWatchdog(resilience)
                         if resilience is not None else None)
        if resilience is not None and self.prefix_cache:
            # arm the chain-hash content verify: publish records a
            # per-block checksum, attach re-verifies before aliasing
            self.pool.kv_checksums = True
            if self.d_pool is not None:
                self.d_pool.kv_checksums = True
        self._spec_disabled = False
        self._plain_quantum = None
        self._plain_audited = None
        self._spec_faults = 0
        self._isolating = False
        self._quarantined = []   # req_ids finished with reason "error"
        self._pool_rebuilds = 0
        self._step_skips = 0
        self._retries_total = 0
        self._fault_mark = 0     # injector-journal cursor -> obs/flight
        self._prefix_quarantine_mark = 0

    # -- public API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens=32, req_id=None, seed=0,
               arrival_time=None, priority=1, temperature=None,
               stop_token_ids=None, stop_sequences=None):
        """Queue one request; returns the :class:`Request` handle.

        Per-request knobs: ``priority`` (admission class, see
        serving/policy.py), ``temperature`` (a sampling engine without
        ``spec_draft``), ``stop_token_ids`` /
        ``stop_sequences`` (host-side stop rules; ``finish_reason``
        becomes ``"stop"``), plus the existing ``max_new_tokens`` /
        ``seed``."""
        if temperature is not None and self._temps is None:
            if self.decode_strategy != "sampling":
                raise ValueError(
                    "submit(temperature=) needs an engine built with "
                    "decode_strategy='sampling': a greedy engine's "
                    "programs take no temperature")
            raise NotImplementedError(
                "submit(temperature=) does not compose with spec_draft "
                "yet: the speculative round's acceptance math takes the "
                "engine-wide temperature")
        req = Request(prompt, max_new_tokens=max_new_tokens,
                      req_id=req_id, seed=seed, priority=priority,
                      temperature=temperature,
                      stop_token_ids=stop_token_ids,
                      stop_sequences=stop_sequences,
                      arrival_time=(self._now()
                                    if arrival_time is None
                                    else arrival_time))
        total = req.prompt_len + req.max_new_tokens
        if total > self.max_context:
            raise ValueError(
                f"request needs {total} tokens > max_context "
                f"{self.max_context}")
        self.scheduler.submit(req)
        self._on_submitted(req)
        return req

    def preempt(self, req):
        """Evict a live request under pool pressure: its blocks return
        to every pool (refcount-safe), its slot frees, and it re-enters
        the head of its priority class for recompute-on-resume — the
        next admission re-prefills ``prompt + tokens`` and the stream
        continues bit-exact vs an undisturbed run (tests/test_serving's
        preemption oracle). The evicted KV (``seq_lens[slot]`` cached
        tokens) is counted as recompute debt."""
        if req.slot is None or req.finished:
            raise ValueError(
                f"request {req.req_id} is not live — only an admitted, "
                f"unfinished request can be preempted")
        # a quantum step() left in flight holds this row: its tokens are
        # the request's (and may finish it: then there is nothing left
        # to evict, its blocks are free already)
        self._drain()
        if req.finished:
            return req
        slot = req.slot
        now = self._now()
        cached = int(self._seq_lens[slot])
        self._done[slot] = True
        self._max_new[slot] = 0
        self.scheduler.preempt(req)
        self.obs.on_preempt(req, now, cached_tokens=cached)
        if self.flight is not None:
            self.flight.on_preempt(req, now, cached_tokens=cached,
                                   tokens_emitted=len(req.tokens))
        return req

    def _on_submitted(self, req):
        """Observability fan-out for one queued request (req_id is
        assigned by the scheduler, so this runs after its submit)."""
        self.obs.on_submit(req)
        if self.flight is not None:
            self.flight.on_submit(req, req.arrival_time)

    @property
    def has_work(self):
        return self.scheduler.has_work

    def step(self):
        """One scheduler iteration: admit, then either a mixed
        prefill(+decode) step or a jitted decode quantum, then retire.

        In steady decode the iteration is PIPELINED one quantum deep:
        with quantum *n* dispatched, the step dispatches *n+1* on the
        device-resident carry of *n* BEFORE it collects *n*
        (``_runs_ahead`` says when: nothing the tokens of *n* could
        change decides what *n+1* is), and leaves *n+1* in flight for
        the next call, which collects it behind *n+2*. The host's half
        of a pump (admission, the pool walk, table growth, the uploads,
        the token loop) then runs beside the device and not between two
        programs. A call still delivers ONE quantum's tokens; streams,
        finish reasons and retire order are the serial pump's
        (``step_collect(step_dispatch())``, which the cluster pump
        drives and which keeps nothing in flight). Whatever changes slot
        state collects the quantum in flight first (``_drain``).

        With ``resilience=`` the step is also the FAULT BOUNDARY: pool
        accounting is audited first (drift rebuilds the allocator from
        the live block tables instead of killing the engine), and an
        :class:`~paddle_tpu.serving.faults.InjectedFault` that survives
        the retry budget is contained here — a poison request is
        isolated by batch bisect and finished with
        ``finish_reason="error"``; a transient fault skips the step
        (nothing was dispatched, so the next step simply retries). Such
        an engine never runs ahead."""
        pending, self._inflight = self._inflight, None
        if pending is None:
            pending = self._step_dispatch()
        if self._runs_ahead(pending):
            self._inflight = self._step_dispatch(ahead=True)
        self.step_collect(pending)
        self._drop_finished_ahead()
        return self.scheduler.has_work

    def _runs_ahead(self, pending):
        """May the quantum AFTER ``pending`` (dispatched, not collected)
        be dispatched before ``pending`` is read back? Only where the
        host can prove, from what it holds now, that the next step is a
        decode quantum of the same rows whatever tokens come back:
        nothing waits and no slot prefills (``steady_state``, the
        predicate ``_choose_k`` uses), a row cannot reach its
        ``max_new_tokens`` within ``pending`` (so a batch's last quantum
        is followed by none), the host has changed no carry since host
        and device last agreed (a stop rule or a closed stream that
        finished a row the device still runs must reach the device
        first), and no seam needs the host between two quanta: an armed
        fault injector, a watchdog, a bisect probe or its ``include=``,
        a speculative draft, a dispatch of several quanta."""
        if (pending is None or pending["k"] != 1 or pending["excluded"]
                or self._mq_quantum is not None
                or self.spec_draft is not None or self._isolating
                or self.watchdog is not None or self.faults.armed):
            return False
        return (self.scheduler.steady_state() and self._carry_in_step()
                and any(self._outlives(r)
                        for r in self.scheduler.decoding()))

    def _outlives(self, req):
        """Is ``req`` still decoding after the quantum that takes the
        mirrors as they are, whatever it emits short of a stop token?"""
        return (self._n_gen[req.slot] + self.config.decode_quantum
                < self._max_new[req.slot])

    def _drain(self):
        """Collect the quantum ``step()`` left in flight, if any: what
        touches a mirror or the pool's tables outside ``step()`` calls
        this first."""
        pending, self._inflight = self._inflight, None
        if pending is not None:
            self.step_collect(pending)

    def _drop_finished_ahead(self):
        """An ahead quantum whose rows ALL finished in the quantum
        before it (a stop token each) runs done-masked from end to end:
        its record is dropped here, in the same step and counted, so
        that none outlives ``has_work``. Nothing waits for it: the pools
        it hands on are adopted, whatever comes next queues behind it on
        the device, and the host goes on meanwhile. Its collect row of
        ``engine.decode`` is a mark (``dropped=1``); no histogram,
        ledger or token count sees it."""
        ahead = self._inflight
        if ahead is None or not all(r.finished for r in ahead["rows"]):
            return
        self._inflight = None
        with RecordEvent("engine.decode", step_kind="decode",
                         step=ahead["step"], half="collect", dropped=1):
            self.obs.on_quantum_ahead(dropped=True)

    def step_dispatch(self):
        """DISPATCH HALF of a step — admit, then enqueue the
        decode quantum WITHOUT forcing its results, returning an opaque
        pending record for :meth:`step_collect` (or ``None`` when the
        step completed synchronously: mixed prefill steps, speculative
        rounds, fault-contained steps, and idle engines). JAX dispatch
        is async, so between the two halves the device executes while
        the host is free to run OTHER work — the cluster front door
        dispatches every replica before collecting any. Driven as
        ``step_collect(step_dispatch())`` the halves are the SERIAL
        pump: one dispatch, one collect, nothing in flight across them
        (a quantum :meth:`step` left in flight is collected first);
        :meth:`step` runs the same two halves one quantum apart in
        steady decode (same fault boundaries, bit-identical streams).
        Each half is its own ``engine.step`` span (``half=dispatch`` |
        ``collect``), so none stays open across another engine's work."""
        self._drain()
        return self._step_dispatch()

    def _step_dispatch(self, ahead=False):
        """:meth:`step_dispatch`'s body. ``ahead``: a quantum is in
        flight and this is the step after it, dispatched early; the
        mirrors are one quantum behind, so what is counted live is what
        outlives that quantum."""
        with RecordEvent("engine.step", half="dispatch"):
            self.stats["steps"] += 1
            if self.resilience is not None:
                self._audit_pools()
            if self.faults.armed:
                self.faults.maybe_corrupt(self.pool)
            pending = None
            try:
                with RecordEvent("engine.admit"):
                    self._admit()
                live = self.scheduler.live()
                if ahead:
                    live = [r for r in live if self._outlives(r)]
                self.stats["occupancy_sum"] += (
                    len(live) / self.config.num_slots)
                self.obs.on_step(self._now(), len(live),
                                 self.config.num_slots, self.pool,
                                 self.d_pool)
                if self.scheduler.prefilling():
                    self._mixed_step()
                elif self.scheduler.decoding():
                    pending = self._decode_dispatch(ahead=ahead)
            except InjectedFault as e:
                self._contain_fault(e)
            finally:
                if pending is None:
                    # the step ran to completion (or contained a fault)
                    # inside this half — close the fault boundary here
                    self._sync_faults()
                    self._sync_prefix_quarantines()
            return pending

    def step_collect(self, pending):
        """COLLECT HALF of a step: force the pending dispatch's
        results, emit/account/retire, and close the step's fault
        boundary. ``pending=None`` (the step already completed in
        :meth:`step_dispatch`) just reports whether work remains."""
        if pending is None:
            return self.scheduler.has_work
        with RecordEvent("engine.step", half="collect"):
            try:
                self._decode_collect(pending)
            except InjectedFault as e:
                self._contain_fault(e)
            finally:
                self._sync_faults()
                self._sync_prefix_quarantines()
            return self.scheduler.has_work

    def run(self, requests=None):
        """Submit ``requests`` (if given) and drive until idle; returns
        the completed :class:`Request` list in submission order."""
        if requests is not None:
            for r in requests:
                if isinstance(r, Request):
                    self.scheduler.submit(r)
                    self._on_submitted(r)
                elif isinstance(r, dict):
                    self.submit(**r)
                else:
                    self.submit(r)
        while self.step():
            pass
        return self.completed

    def output_tokens(self, req):
        """prompt + generated ids as one int32 array (generate()-style
        row, truncated at retirement rather than pad-filled)."""
        return np.concatenate([req.prompt,
                               np.asarray(req.tokens, np.int32)])

    def engine_stats(self):
        out = dict(self.stats)
        out["pool"] = self.pool.fragmentation_stats()
        out["tp"] = self.tp
        out["quantum_collectives"] = {
            k: (dict(v) if isinstance(v, dict) else v)
            for k, v in self.quantum_collectives.items()}
        if self.tp > 1:
            out["pool_bytes_per_chip"] = \
                self.pool.per_chip_bytes_in_use()
        out["admitted"] = self.scheduler.admitted_total
        out["finished"] = self.scheduler.finished_total
        out["preempted"] = self.scheduler.preempted_total
        out["resumed"] = self.scheduler.resumed_total
        if self.stats["steps"]:
            out["mean_occupancy"] = (self.stats["occupancy_sum"]
                                     / self.stats["steps"])
        if self.d_pool is not None:
            out["draft_pool"] = self.d_pool.fragmentation_stats()
            out["spec_acceptance_rate"] = (
                self.stats["spec_accepted"]
                / max(self.stats["spec_proposed"], 1))
        if self.prefix_cache:
            out["prefix_cache"] = self.pool.prefix_cache_stats()
            if self.d_pool is not None:
                out["draft_prefix_cache"] = \
                    self.d_pool.prefix_cache_stats()
        out["resilience"] = self.resilience_report()
        return out

    def attribution(self):
        """The cost ledger's phase-attribution report
        (:meth:`~paddle_tpu.obs.attribution.CostLedger.report`) plus
        the raw counters its conservation invariants are checked
        against — emitted tokens by phase, wall seconds by phase
        (prefill / decode / spec_verify / preempt_recompute),
        novel/recompute/cached prefill work, rejected drafts, and the
        useful-fraction / prefix-savings / MFU gauges."""
        rep = self.obs.ledger.report()
        r = self.obs.registry
        rep["raw_counters"] = {
            "serving_tokens_emitted_total":
                int(r.get("serving_tokens_emitted_total").value()),
            "serving_prefill_tokens_total":
                int(self.stats["prefill_tokens"]),
            "serving_spec_proposed_total":
                int(self.stats["spec_proposed"]),
            "serving_spec_accepted_total":
                int(self.stats["spec_accepted"]),
            "serving_tokens_recomputed_total":
                int(r.get("serving_tokens_recomputed_total").value()),
        }
        return rep

    def decode_step_target(self):
        """(auditable step, example args) for ``analysis.check_budget``
        — the EXACT compiled object the serving hot loop dispatches,
        with the engine's live state as the example batch. A
        spec-disabled engine hands out the plain fallback quantum (the
        degraded-mode golden test fingerprints exactly this)."""
        self._drain()
        if self._spec_disabled:
            return self._plain_audited, self._quantum_args()
        return self._audited, self._quantum_args()

    def multiquantum_step_target(self):
        """(auditable step, example args) for the MULTI-QUANTUM
        while_loop variant — the exact object `_dispatch_quantum`
        routes K > 1 dispatches through, fed by the same live-state
        argument tuple as the plain quantum (identical signature). The
        ``serving_multiquantum_step`` recipe fingerprints this."""
        if self._mq_audited is None:
            raise ValueError(
                "engine built without multi_quantum>1 (or with "
                "spec_draft): no multi-quantum variant to audit")
        self._drain()
        return self._mq_audited, self._quantum_args()

    def health(self, now=None):
        """Evaluate the engine's SLOs over the obs sample series: the
        multi-window burn-rate report (state ``ok``/``warn``/
        ``critical`` + per-objective windows) the exporter's
        ``/healthz`` endpoint and the front door's shedding admission
        (serving/policy.py) consume. The engine must have been built
        with ``slo=``."""
        if self.slo is None:
            raise ValueError(
                "engine built without slo=: pass slo=True (stock "
                "objectives) or an SLOSet to evaluate health")
        report = self.slo.evaluate(self.obs, now=now)
        report["resilience"] = self.resilience_report()
        return report

    # -- resilience: containment, degradation ladders, recovery -----------
    def resilience_report(self):
        """Live view of the resilience tier: which degraded modes are
        active, what was quarantined/rebuilt, and the fault/retry/
        watchdog counters — carried by ``health()`` and
        ``engine_stats()``."""
        out = {
            "spec_disabled": self._spec_disabled,
            "spec_faults": self._spec_faults,
            "quarantined": list(self._quarantined),
            "pool_rebuilds": self._pool_rebuilds,
            "prefix_quarantines": self._prefix_quarantine_mark,
            "step_skips": self._step_skips,
            "retries_total": self._retries_total,
            "faults": self.faults.stats(),
        }
        if self.watchdog is not None:
            out["watchdog"] = self.watchdog.stats()
        return out

    def _audit_pools(self):
        """Ladder rung 3 — accounting drift: the pool's hard
        invariants (``_check_accounting``) normally fail-stop; under a
        resilience policy a drifted pool is REBUILT from the live block
        tables (the only ground truth tied to real sequence state) and
        serving continues. The prefix index is conservatively dropped
        with it — cached subtrees cannot be trusted after drift."""
        for pool in (self.pool, self.d_pool):
            if pool is None:
                continue
            try:
                pool._check_accounting()
            except RuntimeError:
                pool.rebuild_accounting()
                self._pool_rebuilds += 1
                now = self._now()
                self.obs.on_degrade("pool_rebuild", now)
                if self.flight is not None:
                    for r in self.scheduler.live():
                        self.flight.on_degrade(r, now,
                                               mode="pool_rebuild")

    def _sync_faults(self):
        """Fan the injector's journal delta out to obs counters (and
        the flight journal for poison-attributed entries)."""
        j = self.faults.journal
        if self._fault_mark >= len(j):
            return
        now = self._now()
        live = {str(r.req_id): r for r in self.scheduler.live()}
        for entry in j[self._fault_mark:]:
            self.obs.on_fault(entry["site"], entry["kind"])
            if self.flight is not None:
                req = live.get(str(entry.get("poison", "")))
                if req is not None:
                    self.flight.on_fault(req, now, site=entry["site"],
                                         kind=entry["kind"])
        self._fault_mark = len(j)

    def _sync_prefix_quarantines(self):
        """Ladder rung 2 — cached-KV corruption: the pools quarantine
        a corrupted cached subtree at verify time (paged_cache
        ``attach_prefix`` under ``kv_checksums``); the engine syncs the
        counter delta into obs here."""
        if not self.prefix_cache:
            return
        total = int(getattr(self.pool, "prefix_quarantines", 0))
        if self.d_pool is not None:
            total += int(getattr(self.d_pool, "prefix_quarantines", 0))
        if total > self._prefix_quarantine_mark:
            delta = total - self._prefix_quarantine_mark
            self._prefix_quarantine_mark = total
            self.obs.on_quarantine(self._now(), "prefix", count=delta)

    def _contain_fault(self, e):
        """Containment for an :class:`InjectedFault` that escaped the
        retry budget (``step()`` is the only caller). A poison fault is
        isolated — by batch bisect on the decode path, directly on the
        mixed path where the batch is host-built — and the culprit is
        finished with ``finish_reason="error"``; everyone else keeps
        serving. A transient fault (allocation failure, exhausted
        retries) drops the step on the floor: the injector fires BEFORE
        any device dispatch, allocation is idempotent, so the next step
        retries against intact state."""
        if e.poison is None:
            self._step_skips += 1
            return
        victim = None
        rows = self.scheduler.decoding()
        if e.site in ("decode", "spec_round") and len(rows) > 1:
            victim = self._isolate_poison()
        if victim is None:
            victim = next((r for r in self.scheduler.live()
                           if str(r.req_id) == str(e.poison)), None)
        if victim is not None:
            self._quarantine(victim)

    def _isolate_poison(self):
        """Batch-bisect quarantine: probe subsets of the decoding rows
        with REAL dispatches — a clean subset makes full progress (its
        tokens are emitted; the excluded rows ride along done-masked,
        completely inert through the dispatch) — until one row is
        isolated. Containment relies only on "a dispatch raises iff
        its active rows include a poison", never on the exception
        naming the culprit. Returns the isolated Request, or None if
        every probe ran clean."""
        suspects = list(self.scheduler.decoding())
        self._isolating = True
        try:
            while len(suspects) > 1:
                half = suspects[:len(suspects) // 2]
                rest = suspects[len(suspects) // 2:]
                if self._probe(half):
                    suspects = half
                else:
                    # half is clean and just made progress (some of it
                    # may even have finished) — the poison is in rest
                    suspects = [r for r in rest if not r.finished]
            if len(suspects) == 1 and self._probe(suspects):
                return suspects[0]
            return None
        finally:
            self._isolating = False

    def _probe(self, subset):
        """One real dispatch restricted to ``subset``; True if an
        injected fault fired (no progress), False after a clean
        dispatch whose tokens were emitted."""
        try:
            self._decode_quantum(include=subset)
        except InjectedFault:
            return True
        return False

    def _quarantine(self, req):
        """Finish one poison request with ``finish_reason="error"``
        and keep serving everyone else: its blocks return to every
        pool through the normal retire path, obs records the bad
        outcome (the error rate burns the SLO error budget), and the
        injector is cured so probes stop raising."""
        now = self._now()
        req.finished = True
        req.finish_reason = "error"
        if req.finish_time is None:
            req.finish_time = now
        self.faults.cure(req.req_id)
        self._quarantined.append(str(req.req_id))
        self.obs.on_quarantine(now, "poison")
        if self.flight is not None:
            self.flight.on_fault(req, now, site="quarantine",
                                 kind="poison")
        if req.slot is not None:
            self._retire_finished()

    def _note_spec_fault(self):
        """Ladder rung 1 — repeated spec-round faults (injected raises
        or watchdog trips) one-way degrade to the plain quantum."""
        if self.spec_draft is None or self._spec_disabled:
            return
        self._spec_faults += 1
        if (self.resilience is not None
                and self._spec_faults
                >= self.resilience.spec_fault_threshold):
            self._disable_spec()

    def _disable_spec(self):
        """Fall back from the speculative round to the PLAIN decode
        quantum — the same compiled family a ``spec_draft=None`` build
        jits, so no new golden. In-flight state carries over unchanged:
        the target pool holds every accepted token's KV, greedy streams
        continue bit-exact (the spec greedy arm already emits the
        target's own argmax stream), and the draft pool simply stops
        growing (its blocks free on retire/preempt as usual — ``free``
        is a no-op for sequences that never ensured draft blocks)."""
        if self._spec_disabled or self.spec_draft is None:
            return
        self._spec_disabled = True
        cfg = self.model.config
        self._plain_quantum = jax.jit(self._make_quantum(),
                                      donate_argnums=(0, 1, 2, 3, 4))
        self._plain_audited = _AuditedStep(
            self._plain_quantum,
            n_donatable=(4 if self.pool.quantized else 2)
            * cfg.num_hidden_layers,
            mesh=self.mesh)
        now = self._now()
        self.obs.on_degrade("spec_disabled", now)
        if self.flight is not None:
            for r in self.scheduler.live():
                self.flight.on_degrade(r, now, mode="spec_disabled")

    def _guarded_dispatch(self, kind, rows, quanta=1):
        """One quantum dispatch under the resilience envelope: the
        injector's pre-dispatch check (faults fire BEFORE any donated
        buffer is consumed, so a retry re-runs against intact state),
        exponential-backoff retries for transient injected faults, and
        the wall-clock watchdog. Real exceptions propagate untouched —
        fail-stop is preserved for anything the injector didn't
        cause. Isolation probes never retry (the raise IS the probe
        signal), and poison faults escalate immediately. ``quanta > 1``
        dispatches the multi-quantum variant and normalizes the
        watchdog wall by the quantum count, so a K-quantum dispatch is
        judged against the same per-quantum calibration as K singles."""
        rids = [r.req_id for r in rows]
        pol = self.resilience
        attempt = 0
        while True:
            t0 = self._now()
            try:
                self.faults.before_dispatch(kind, rids)
                out = self._dispatch_quantum(quanta)
            except InjectedFault as e:
                if kind == "spec_round" and e.poison is None:
                    self._note_spec_fault()
                    if self._spec_disabled:
                        # the fault just crossed the disable threshold:
                        # a retry here would dispatch the PLAIN quantum
                        # under the spec-round caller — skip the step
                        # instead; the next step takes the plain path
                        raise
                if (self._isolating or e.poison is not None
                        or pol is None or attempt >= pol.max_retries):
                    raise
                delay = pol.backoff_s(attempt)
                attempt += 1
                self._retries_total += 1
                self.obs.on_retry(kind, attempt)
                if self.flight is not None:
                    now = self._now()
                    for r in rows:
                        self.flight.on_retry(r, now, kind=kind,
                                             attempt=attempt,
                                             backoff_s=delay)
                pol.sleep(delay)
                continue
            if self.watchdog is not None:
                dt = (self._now() - t0) / quanta
                if self.watchdog.check(kind, dt):
                    self.obs.on_watchdog(kind, dt)
                    if kind == "spec_round":
                        self._note_spec_fault()
            return out

    # -- crash recovery: snapshot / restore --------------------------------
    def snapshot(self):
        """JSON-able crash-recovery image of the SCHEDULER tier: every
        in-flight request's identity, generation params, and
        emitted-so-far tokens (plus completed-request summaries for
        audit). Device state is deliberately NOT captured — a restored
        engine re-admits each in-flight request through the existing
        RECOMPUTE-ON-RESUME machinery (``Request.begin_resume``:
        re-prefill ``prompt + tokens``, continue via
        ``fold_in(key, n_emitted)``), so greedy output streams are
        bit-exact vs the uninterrupted run without serializing a single
        pool buffer."""
        def req_state(req):
            return {
                "req_id": str(req.req_id),
                "prompt": [int(t) for t in np.asarray(req.prompt)],
                "max_new_tokens": int(req.max_new_tokens),
                "seed": int(req.seed),
                "priority": int(req.priority),
                "temperature": (None if req.temperature is None
                                else float(req.temperature)),
                "stop_token_ids": (sorted(req.stop_token_ids)
                                   if req.stop_token_ids else None),
                "stop_sequences": ([list(s) for s in req.stop_sequences]
                                   if req.stop_sequences else None),
                "tokens": [int(t) for t in req.tokens],
                "preemptions": int(req.preemptions),
            }

        inflight = list(self.scheduler.live()) + list(
            self.scheduler.waiting)
        return {
            "version": 1,
            "kind": "serving_engine_snapshot",
            "num_slots": self.config.num_slots,
            "block_size": self.pool.block_size,
            "max_context": self.max_context,
            "prefill_chunk": self.config.prefill_chunk,
            "decode_quantum": self.config.decode_quantum,
            "decode_strategy": self.decode_strategy,
            "top_k": self.top_k, "top_p": self.top_p,
            "temperature": self.temperature,
            "eos_token_id": self.eos_token_id,
            "spec_gamma": self.spec_gamma,
            "prefix_cache": self.prefix_cache,
            "quantize": self.quantize,
            "kv_dtype": self.kv_dtype,
            "submitted_total": self.scheduler._submitted_total,
            "inflight": [req_state(r) for r in inflight],
            "completed": [{"req_id": str(r.req_id),
                           "tokens": [int(t) for t in r.tokens],
                           "finish_reason": r.finish_reason}
                          for r in self.completed],
        }

    @classmethod
    def restore(cls, snap, model, spec_draft=None, **overrides):
        """Build a FRESH engine from a :meth:`snapshot` and re-admit
        every in-flight request via recompute-on-resume. ``model`` (and
        ``spec_draft``) are re-supplied by the caller — params are not
        part of the snapshot; ``overrides`` adjust any constructor
        kwarg (e.g. ``resilience=True``, ``flight=True``). Completed
        summaries ride the snapshot for audit but are not
        re-materialized. A key that an older snapshot carries and the
        constructor no longer takes (``per_request_sampling``) is not
        read."""
        if snap.get("kind") != "serving_engine_snapshot":
            raise ValueError(
                "not a serving engine snapshot (kind="
                f"{snap.get('kind')!r})")
        kwargs = dict(
            num_slots=snap["num_slots"], block_size=snap["block_size"],
            max_context=snap["max_context"],
            prefill_chunk=snap["prefill_chunk"],
            decode_quantum=snap["decode_quantum"],
            decode_strategy=snap["decode_strategy"],
            top_k=snap["top_k"], top_p=snap["top_p"],
            temperature=snap["temperature"],
            eos_token_id=snap["eos_token_id"],
            spec_gamma=snap["spec_gamma"],
            prefix_cache=snap["prefix_cache"],
            quantize=snap.get("quantize"),
            kv_dtype=snap.get("kv_dtype"))
        kwargs.update(overrides)
        eng = cls(model, spec_draft=spec_draft, **kwargs)
        now = eng._now()
        for st in snap["inflight"]:
            req = Request(
                np.asarray(st["prompt"], np.int32),
                max_new_tokens=st["max_new_tokens"],
                req_id=st["req_id"], seed=st["seed"],
                priority=st["priority"],
                temperature=st["temperature"],
                stop_token_ids=st["stop_token_ids"],
                stop_sequences=st["stop_sequences"],
                arrival_time=now)
            req.tokens = list(st["tokens"])
            req.preemptions = int(st["preemptions"])
            if req.tokens or req.preemptions:
                # the restart IS a whole-engine preemption: re-admission
                # re-prefills prompt + tokens; the recomputed tokens are
                # NOT re-emitted and the continuation stays bit-exact
                req.begin_resume()
            eng.scheduler.submit(req)
            eng._on_submitted(req)
            if eng.flight is not None:
                eng.flight.on_restore(req, now,
                                      tokens_resumed=len(req.tokens))
        eng.scheduler._submitted_total = max(
            eng.scheduler._submitted_total,
            int(snap.get("submitted_total", 0)))
        eng.obs.on_restore(now, len(snap["inflight"]))
        return eng

    # -- admission + prefill ----------------------------------------------
    def _admit(self):
        now = self._now()
        for req in self.scheduler.try_admit():
            resumed = req.preemptions > 0
            req.admit_time = now
            if resumed:
                self.obs.on_resume(req, now)
                if self.flight is not None:
                    self.flight.on_resume(
                        req, now, slot=req.slot,
                        prefill_tokens=req.prefill_target)
            else:
                self.obs.on_admit(req, now)
                if self.flight is not None:
                    st = self.pool.fragmentation_stats()
                    reserved = self.scheduler._reservations.get(req)
                    cached_blk = self.pool.held_blocks(req.req_id)
                    self.flight.on_admit(
                        req, now, queue_wait=now - req.arrival_time,
                        blocks_reserved=reserved,
                        pool_free_blocks=st["free_blocks"],
                        pool_blocks_in_use=st["blocks_in_use"],
                        cached_blocks=cached_blk,
                        novel_blocks=(None if reserved is None
                                      else reserved - cached_blk))
            slot = req.slot
            cached = 0
            if self.prefix_cache and req.cached_prefix_tokens:
                # never skip the WHOLE prefill source: the final
                # position is re-prefilled (a one-token chunk) so
                # completion still emits a token — and that write is
                # the designed copy-on-write trigger for the tail
                # shared block when the entire prompt was cached
                cached = min(req.cached_prefix_tokens,
                             req.prefill_target - 1)
                req.prefill_pos = cached
                self.obs.on_cached_prefill(req, cached)
            self._seq_lens[slot] = cached
            # a resumed request goes on from the tokens it has: the mixed
            # step keys its continuation token by fold_in(key, n_emitted)
            self._n_gen[slot] = len(req.tokens)
            self._done[slot] = True  # not decodable until prefill ends
            self._max_new[slot] = req.max_new_tokens
            self._keys[slot] = np.asarray(jax.random.PRNGKey(req.seed))
            if self._temps is not None:
                self._temps[slot] = (self.temperature
                                     if req.temperature is None
                                     else req.temperature)

    def _make_mixed(self, model, scratch, select):
        """Build the mixed step's callable for ``model``: one chunk of
        ``paged_chunk_math`` over its pool (per-row counts, the head at
        each row's last valid position) ending, for the target, in the
        quantum's own ``_select_device`` — prefill and decode pick
        tokens with one definition. The speculative arm's DRAFT ingests
        the same rows through a program of its own that returns its
        pools only (``select=False``: the forward exists for its KV
        writes). Weights are arguments (``p_vals``), the pool's five
        sides (``PagedKVCachePool.arrays``) the leading, donated ones."""
        def mixed(kc, vc, ks, vs, st, p_vals, tables, ids, seq_lens,
                  counts, keys, n_gen, temps=None):
            with autograd.no_grad():
                def fwd(ids_t):
                    return paged_chunk_math(
                        model, scratch, ids_t, seq_lens, tables, kc, vc,
                        counts > 0, ks=ks, vs=vs, counts=counts,
                        st=st), moe_rows(model)

                ((logits, *pools), rows), _ = functional_call(
                    model, fwd, [Tensor(ids, stop_gradient=True)], {},
                    p_vals, [])
            if not select:
                return tuple(pools)
            # ``rows`` is () for a model without experts: no aval; the
            # tokens stay the last output
            return (*pools, rows,
                    self._select_device(logits, keys, n_gen, temps))

        return mixed

    def _mixed_args(self, pre, dec, spec):
        """Host half of one mixed step: grow (and copy-on-write) every
        row's blocks, lay the step's tokens out by SLOT, and build the
        argument tuples of the target's program and, in the speculative
        arm, the draft's. A prefilling row brings its next chunk, a
        decoding row its last token, every other slot nothing
        (``counts`` 0). The chunk length compiled for is the smallest
        power of two that holds the step's longest row, at most
        ``prefill_chunk``: a pure function of the step's rows. Returns
        ``(args, d_args, bucket, chunk_lens)``."""
        s = self.config.num_slots
        chunk_lens = [min(self.config.prefill_chunk,
                          r.prefill_target - r.prefill_pos) for r in pre]
        longest = max(chunk_lens + [1])
        bucket = min(1 << (longest - 1).bit_length(),
                     self.config.prefill_chunk)
        ids = np.zeros((s, bucket), np.int32)
        counts = np.zeros(s, np.int32)
        seq_ids = [None] * s     # an idle slot's table row stays zeros
        pools = (self.pool, self.d_pool) if spec else (self.pool,)
        for req, n in zip(pre + dec, chunk_lens + [1] * len(dec)):
            slot = req.slot
            seq = int(self._seq_lens[slot])   # a prefill row's position
            ids[slot, :n] = (req.prefill_src[seq:seq + n]
                             if req.prefilling else self._last_tok[slot])
            counts[slot] = n
            seq_ids[slot] = req.req_id
            if seq == 0 and self.pool.state:
                # the program starts this row's state from zeros
                self.obs.on_state_reset()
            for pool in pools:
                pool.ensure(req.req_id, seq + n)
                if self.prefix_cache:
                    # copy-on-write before the dispatch: the chunk's KV
                    # writes must never land in a block another holder
                    # (sequence or prefix index) still maps
                    pool.make_writable(req.req_id, seq, seq + n)
        small = [self._dev(a) for a in (
            ids, self._seq_lens, counts, self._keys, self._n_gen,
            *self._temps_arg())]

        def args_of(pool, p_vals):
            # the scale tuples are EMPTY on a float pool (no avals), the
            # slot side without state layers
            return (*pool.arrays(), p_vals,
                    self._dev(pool.block_table_array(
                        seq_ids, pad_to=self._table_width)), *small)

        return (args_of(self.pool, self._p_vals),
                args_of(self.d_pool, self._d_p_vals) if spec else None,
                bucket, chunk_lens)

    def mixed_step_target(self):
        """(auditable step, example args) for ``analysis.check_budget``:
        the EXACT jitted program ``_mixed_step`` dispatches, with the
        rows the scheduler holds now as the example batch (the
        ``serving_mixed_step`` recipe fingerprints this)."""
        self._drain()
        args, _, _, _ = self._mixed_args(
            self.scheduler.prefilling(), self.scheduler.decoding(), False)
        return self._mixed, args

    def _mixed_step(self):
        """One chunk of prefill for every prefilling slot, one decode
        token for every in-flight slot: a single MIXED batch (chunked
        prefill interleaved with decode, the reference's serving batch
        shape) through ONE jitted, pool-donating program that ends in
        the token selection — the host reads ``num_slots`` int32s. The
        speculative arm pushes the SAME rows through the draft's program
        into the draft pool (token selection stays the target's).

        On the host, in this order: the fault boundary (before anything
        is donated), block growth and copy-on-write, the dispatch, the
        one sync, then emission, prefix publication and accounting."""
        with RecordEvent("engine.mixed", step_kind="mixed",
                         step=self.stats["steps"]) as span:
            self.stats["mixed_steps"] += 1
            pre = self.scheduler.prefilling()
            dec = self.scheduler.decoding()
            rows = pre + dec
            spec = self.spec_draft is not None and not self._spec_disabled
            # the mixed step's fault boundary: BEFORE any pool mutation, so
            # a raised step retries cleanly from the next step()
            self.faults.before_dispatch("mixed", [r.req_id for r in rows])
            with RecordEvent("engine.mixed.prepare"):
                args, d_args, bucket, chunk_lens = self._mixed_args(
                    pre, dec, spec)
                # cost-ledger work split: a resumed row's chunk re-computes
                # KV a preemption dropped (recompute debt); a fresh row's
                # chunk is novel prefill work (obs/attribution.py)
                recompute_toks = sum(
                    n for r, n in zip(pre, chunk_lens) if r.preemptions > 0)
                prefill_toks = sum(chunk_lens)
                padded = (self.config.num_slots * bucket
                          - prefill_toks - len(dec))
                self.stats["prefill_tokens"] += prefill_toks
                # a bucket's first use builds its program (the draft's too)
                self.obs.on_mixed_dispatch(
                    bucket, padded, built=0 if bucket in self._mixed_buckets
                    else 1 + spec)
                self._mixed_buckets.add(bucket)
            span.args.update(rows=len(rows), prefill_tokens=prefill_toks,
                             bucket=bucket, padded_tokens=padded)
            # a forward span is its program's enqueue to its end; the
            # donated pools are adopted as soon as the call returns. The
            # draft is waited for too: the uploads both programs read may
            # alias the host mirrors (a CPU upload is zero-copy), which
            # the emission below writes
            with RecordEvent("engine.mixed.forward", model="target"):
                *pools, rows, toks = self._mixed(*args)
                self.pool.adopt(*pools)
                jax.block_until_ready(toks)
            if spec:
                with RecordEvent("engine.mixed.forward", model="draft"):
                    self.d_pool.adopt(*self._d_mixed(*d_args))
                    jax.block_until_ready(self.d_pool.k_pools[-1])
            with RecordEvent("engine.mixed.select"):
                nxt = np.asarray(toks)               # (S,) int32
                if not isinstance(rows, tuple):
                    # (expert layers, experts held): the step's routed
                    # rows, and the choices that fell on absent experts
                    got = np.asarray(rows)
                    span.args["moe_rows"] = int(got.sum())
                    span.args["moe_offshare_rows"] = (
                        got.shape[0] * self.config.num_slots * bucket
                        * self._moe_top_k - int(got.sum()))
                if self._window:
                    base = self._seq_lens[[r.slot for r in pre + dec]]
                    span.args.update(self.obs.on_keys_attended(
                        base, base + np.asarray(
                            chunk_lens + [1] * len(dec), base.dtype),
                        self._window))
            now = self._now()  # the stamp of every token of the step
            with RecordEvent("engine.mixed.emit"):
                prefill_emitted = 0
                for req, n in zip(pre, chunk_lens):
                    slot = req.slot
                    req.prefill_pos += n
                    self._seq_lens[slot] = req.prefill_pos
                    if self.flight is not None:
                        self.flight.on_prefill_chunk(
                            req, now, n, req.prefill_pos)
                    if req.prefill_pos < req.prefill_target:
                        continue
                    if self.prefix_cache:
                        # the whole prefill source is in the pool now:
                        # publish its full blocks into the prefix index
                        # (both pools — lockstep) so the next request
                        # with this prefix aliases instead of computing
                        self.pool.publish_prefix(req.req_id,
                                                 req.prefill_src)
                        if spec:
                            self.d_pool.publish_prefix(
                                req.req_id, req.prefill_src)
                        self.scheduler.clear_cow_debt(req)
                    if req.first_token_time is None:
                        # TTFT observes exactly ONCE per request — a
                        # resumed request's re-prefill completion emits
                        # a continuation token, not a first token
                        req.first_token_time = now
                        self.obs.on_first_token(req, now)
                        if self.flight is not None:
                            self.flight.on_first_token(
                                req, now, now - req.arrival_time)
                    self._emit(req, int(nxt[slot]))
                    prefill_emitted += 1
                    self._record_host(slot, req, int(nxt[slot]))
                for req in dec:
                    slot = req.slot
                    self._seq_lens[slot] += 1  # last_tok entered the cache
                    self._emit(req, int(nxt[slot]))
                    self._record_host(slot, req, int(nxt[slot]))
                breakdown = {"prefill_emitted": prefill_emitted,
                             "decode_emitted": len(dec),
                             "novel_tokens": prefill_toks - recompute_toks,
                             "recompute_tokens": recompute_toks,
                             "decode_rows": len(dec)}
                self.obs.on_quantum("mixed", span.t0, now,
                                    prefill_emitted + len(dec), len(rows),
                                    breakdown=breakdown)
            if self.watchdog is not None and self.watchdog.check(
                    "mixed", now - span.t0):
                self.obs.on_watchdog("mixed", now - span.t0)
            self._retire_finished()

    def _emit(self, req, tok):
        """Append ONE generated token to a request's stream (retirement
        rule included) and count it — the obs token counter matches the
        emitted streams exactly because every append goes through here.
        The front door's ``token_sink`` fires on the same boundary (the
        streaming API's per-token push)."""
        req.record(tok, self.eos_token_id)
        self.obs.on_token(req)
        if self.token_sink is not None:
            self.token_sink(req, int(tok))

    def _record_host(self, slot, req, tok):
        self._last_tok[slot] = tok
        self._n_gen[slot] = len(req.tokens)
        self._done[slot] = req.finished

    # -- the jitted decode quantum ----------------------------------------
    @jax.named_scope("sample")
    def _select_device(self, logits, keys, n_gen, temps=None):
        if self.decode_strategy == "greedy":
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if temps is not None:
            # per-slot temperature: same scale-then-filter order — and
            # the same f32 division — as the engine-wide path (the
            # speculative round's), so a uniform temps row replays it
            # bit-for-bit
            filt = _filter_logits(
                logits.astype(jnp.float32)
                / jnp.maximum(temps, 1e-6)[:, None],
                self.top_k, self.top_p, None)
        else:
            filt = _filter_logits(logits, self.top_k, self.top_p,
                                  self.temperature)
        step_keys = jax.vmap(jax.random.fold_in)(keys, n_gen)
        return jax.vmap(jax.random.categorical)(
            step_keys, filt).astype(jnp.int32)

    def _make_quantum(self, multi=None):
        """Build the decode-quantum callable. ``multi=None``: the plain
        single-quantum scan, exactly as ever. ``multi=K``: the
        MULTI-QUANTUM driver — the same scan wrapped in a
        ``lax.while_loop`` that runs up to K quanta per dispatch,
        short-circuiting on-device when every row's retirement mask
        sets; tokens land in a (K, T, S) buffer and the loop counter
        comes back so the host can account exactly the quanta that
        ran. The K=1 graph is untouched — both wrappers call the same
        ``scan_steps``."""
        model = self.model
        scratch = self._scratch_block
        t_steps = self.config.decode_quantum
        n_slots = self.config.num_slots
        has_eos = self.eos_token_id is not None
        eos = -1 if self.eos_token_id is None else int(self.eos_token_id)

        def scan_steps(kc, vc, ks, vs, st, p_vals, tables, seq_lens,
                       last_tok, n_gen, done, max_new, keys, temps):
            # ks/vs are the int8 pool's per-row scale pools and st the
            # slot side; on a float engine without state layers they are
            # EMPTY tuples — zero avals in the carry, so the compiled
            # graph (and golden) is byte-identical
            def body(carry, _):
                (kc, vc, ks, vs, st, seq_lens, last_tok, n_gen,
                 done) = carry
                live = ~done
                with autograd.no_grad():
                    def fwd(tok_t):
                        return paged_decode_math(
                            model, scratch, tok_t, seq_lens, tables,
                            kc, vc, live, ks=ks, vs=vs,
                            st=st), moe_rows(model)

                    tok_t = Tensor(last_tok[:, None], stop_gradient=True)
                    ((logits, kc2, vc2, ks2, vs2, st2), rows), _ = \
                        functional_call(model, fwd, [tok_t], {}, p_vals,
                                        [])
                nxt = self._select_device(logits, keys, n_gen, temps)
                with jax.named_scope("sample"):   # ... and who retires
                    nxt = jnp.where(done, last_tok, nxt).astype(jnp.int32)
                    n_gen2 = n_gen + live.astype(jnp.int32)
                    done2 = done | (n_gen2 >= max_new)
                    if has_eos:
                        done2 = done2 | (live & (nxt == eos))
                    seq_lens2 = seq_lens + live.astype(jnp.int32)
                return (kc2, vc2, ks2, vs2, st2, seq_lens2, nxt, n_gen2,
                        done2), (nxt, rows)

            # ``rows``: (T, expert layers, experts) int32, or () for a
            # model without experts (no aval: its graph is what it was)
            (kc, vc, ks, vs, st, seq_lens, last_tok, n_gen, done), \
                (toks, rows) = jax.lax.scan(
                    body,
                    (kc, vc, tuple(ks), tuple(vs), tuple(st), seq_lens,
                     last_tok, n_gen, done),
                    None, length=t_steps)
            return (kc, vc, ks, vs, st, seq_lens, last_tok, n_gen, done,
                    toks, rows)

        def multi_steps(kc, vc, ks, vs, st, p_vals, tables, seq_lens,
                        last_tok, n_gen, done, max_new, keys, temps):
            # K quanta per dispatch: the host round-trips device state
            # untouched between steady-state quanta, so folding the
            # round-trips into a while_loop changes no math — streams
            # stay bit-identical to K sequential dispatches. The
            # all-done cond is the on-device early exit; the returned
            # counter tells the host how many quanta to account.
            k_max = int(multi)
            buf0 = jnp.zeros((k_max, t_steps, n_slots), jnp.int32)
            rbuf0 = _moe_rows_buffer(model, k_max, t_steps)

            def cond(carry):
                qi, done = carry[0], carry[9]
                return (qi < k_max) & ~jnp.all(done)

            def body(carry):
                (qi, kc, vc, ks, vs, st, seq_lens, last_tok, n_gen, done,
                 buf, rbuf) = carry
                (kc, vc, ks, vs, st, seq_lens, last_tok, n_gen, done,
                 toks, rows) = scan_steps(kc, vc, ks, vs, st, p_vals,
                                          tables, seq_lens, last_tok,
                                          n_gen, done, max_new, keys,
                                          temps)
                buf = jax.lax.dynamic_update_slice(
                    buf, toks[None], (qi, 0, 0))
                rbuf = jax.tree_util.tree_map(
                    lambda b, r: jax.lax.dynamic_update_slice(
                        b, r[None], (qi, 0, 0, 0)), rbuf, rows)
                return (qi + 1, kc, vc, ks, vs, st, seq_lens, last_tok,
                        n_gen, done, buf, rbuf)

            (qi, kc, vc, ks, vs, st, seq_lens, last_tok, n_gen, done,
             buf, rbuf) = jax.lax.while_loop(
                cond, body,
                (jnp.int32(0), kc, vc, tuple(ks), tuple(vs), tuple(st),
                 seq_lens, last_tok, n_gen, done, buf0, rbuf0))
            return (kc, vc, ks, vs, st, seq_lens, last_tok, n_gen, done,
                    buf, rbuf, qi)

        inner = scan_steps if multi is None else multi_steps

        # ``temps`` is the per-slot temperature row of a sampling engine
        # (`_temps_arg`); every other engine calls with thirteen arguments
        def quantum(kc, vc, ks, vs, st, p_vals, tables, seq_lens,
                    last_tok, n_gen, done, max_new, keys, temps=None):
            return inner(kc, vc, ks, vs, st, p_vals, tables, seq_lens,
                         last_tok, n_gen, done, max_new, keys, temps)

        return quantum

    def _temps_arg(self):
        """The trailing argument of the quantum and of the mixed program:
        the per-slot temperatures where the engine has them, nothing
        otherwise."""
        return () if self._temps is None else (self._dev(self._temps),)

    def _dev(self, a):
        """Device view of one host mirror: plain uncommitted transfer on
        a single chip; committed REPLICATED under tp, so every dispatch
        hands the compiled quantum the exact input layouts it was built
        for."""
        v = jnp.asarray(a)
        if self._rep_sharding is None:
            return v
        return jax.device_put(v, self._rep_sharding)

    def _dev_kept(self, name, mirror):
        """Device view of a mirror the device never writes (the tables,
        ``max_new``, the keys, the temperatures): the last upload while
        the mirror still equals it, else a new one. What is uploaded is
        a COPY no one writes: a jitted dispatch returns before it has
        run, ``jnp.asarray`` aliases a numpy buffer on the CPU and an
        accelerator reads it until the transfer is done, and with a
        quantum in flight the host writes the mirrors (``_tables[slot]``
        for the next one) while a program handed the last upload may
        still run."""
        kept = self._kept.get(name)
        if kept is None or not np.array_equal(kept[0], mirror):
            host = mirror.copy()
            kept = self._kept[name] = (host, self._dev(host))
        return kept[1]

    def _carry_in_step(self):
        """Do the four carry mirrors read what they read when host and
        device last agreed? Then the last dispatched quantum's outputs
        are the next one's arguments; else the host has changed one
        (admission, a mixed step's token, retire, preempt, a bisect
        mask) and the mirrors go up again."""
        return self._carry is not None and all(
            np.array_equal(m, seen) for m, seen in zip(
                (self._seq_lens, self._last_tok, self._n_gen, self._done),
                self._carry_seen))

    def _carry_args(self):
        """``seq_lens, last_tok, n_gen, done`` as the next quantum takes
        them: the device-resident outputs of the last one where
        ``_carry_in_step``; else copies of the mirrors (see
        ``_dev_kept``), put where those outputs live so that the one
        executable takes either."""
        if not self._carry_in_step():
            self._carry_seen = tuple(m.copy() for m in (
                self._seq_lens, self._last_tok, self._n_gen, self._done))
            self._carry = tuple(map(self._put_carry, self._carry_seen))
        return self._carry

    def _put_carry(self, a):
        """``a`` where a quantum's small outputs live (see
        ``_carry_sharding``): a mirror's copy on its way up, or, under
        tp, an output put replicated as the executable takes it (no
        copy where it lies so already)."""
        if self._carry_sharding is None:
            return jnp.asarray(a)
        return jax.device_put(a, self._carry_sharding)

    def _quantum_args(self):
        """The quantum's argument tuple; its uploads (the ``_dev``
        calls: in steady decode none but a grown table) are the span
        ``engine.decode.args``."""
        with RecordEvent("engine.decode.args"):
            # the scale tuples ride right after their pool's v_pools and the
            # slot side after them (empty on a float engine without state
            # layers — no avals, goldens untouched); donation covers all
            # leading pool pytrees
            if self.spec_draft is not None and not self._spec_disabled:
                return (list(self.pool.k_pools), list(self.pool.v_pools),
                        tuple(self.pool.k_scales),
                        tuple(self.pool.v_scales),
                        list(self.d_pool.k_pools),
                        list(self.d_pool.v_pools),
                        tuple(self.d_pool.k_scales),
                        tuple(self.d_pool.v_scales),
                        self._p_vals, self._d_p_vals,
                        self._dev(self._tables),
                        self._dev(self._d_tables),
                        self._dev(self._seq_lens),
                        self._dev(self._last_tok),
                        self._dev(self._n_gen), self._dev(self._done),
                        self._dev(self._max_new),
                        self._dev(self._keys))
            return (*self.pool.arrays(), self._p_vals,
                    self._dev_kept("tables", self._tables),
                    *self._carry_args(),
                    self._dev_kept("max_new", self._max_new),
                    self._dev_kept("keys", self._keys),
                    *(() if self._temps is None
                      else (self._dev_kept("temps", self._temps),)))

    def _dispatch_quantum(self, quanta=1):
        """Run ONE quantum dispatch. Single chip: the jitted callable,
        exactly as before. Under tp: inside the engine's MeshScope
        (the first call's trace needs the mesh installed for the mp
        layers' constraints) and through the build-time compiled
        executable when present — the census compile doubles as the
        serving executable. After a spec-disable degrade the PLAIN
        fallback quantum dispatches instead (the tp census executable
        was compiled for the spec signature). ``quanta > 1`` routes to
        the multi-quantum while_loop variant (same argument tuple)."""
        quantum = (self._plain_quantum if self._spec_disabled
                   else self._quantum)
        if quanta > 1:
            quantum = self._mq_quantum
        if self.mesh is None:
            return quantum(*self._quantum_args())
        with MeshScope(self.mesh):
            if (self._quantum_compiled is not None
                    and not self._spec_disabled and quanta == 1):
                return self._quantum_compiled(*self._quantum_args())
            return quantum(*self._quantum_args())

    def _spec_round_step(self, include=None):
        """Dispatch ONE jitted speculative round (draft-γ scan + target
        verify + in-graph acceptance and cache roll forward/back); the
        host runs only here, at the admit/retire boundary — variable
        per-round token yield composes with the same retirement masks
        as the plain quantum. ``include`` restricts the round to a
        subset of the decoding rows (the bisect-quarantine probe path):
        excluded rows ride along done-masked — inert through the
        dispatch — and their host state is restored afterwards."""
        with RecordEvent("engine.spec_round", step_kind="spec_round",
                         step=self.stats["steps"]) as span:
            g = self.spec_gamma
            t0 = span.t0
            self.stats["spec_rounds"] += 1
            rows = self.scheduler.decoding()
            excluded = []
            if include is not None:
                keep = {id(r) for r in include}
                excluded = [r for r in rows if id(r) not in keep]
                rows = [r for r in rows if id(r) in keep]
                for r in excluded:
                    self._done[r.slot] = True
            try:
                for req in rows:
                    slot = req.slot
                    # cover the round's worst-case writes (γ proposals past
                    # the accepted history) in BOTH pools before entering
                    # the device loop — tables are static inside
                    need = int(self._seq_lens[slot]) + g + 1
                    for pool, tables in ((self.pool, self._tables),
                                         (self.d_pool, self._d_tables)):
                        if need > pool.seq_len(req.req_id):
                            pool.ensure(req.req_id, need)
                        if self.prefix_cache:
                            pool.make_writable(
                                req.req_id, int(self._seq_lens[slot]), need)
                        row = pool.block_table_array(
                            [req.req_id], pad_to=self._table_width)
                        tables[slot] = np.asarray(row)[0][
                            :self._table_width]
                (t_kc, t_vc, t_ks, t_vs, d_kc, d_vc, d_ks, d_vs, seq_lens,
                 last_tok, n_gen, done, stream, counts,
                 acc) = self._guarded_dispatch("spec_round", rows)
            except BaseException:
                for r in excluded:
                    self._done[r.slot] = r.finished
                raise
            self.pool.adopt(t_kc, t_vc, t_ks, t_vs)
            self.d_pool.adopt(d_kc, d_vc, d_ks, d_vs)
            stream = np.asarray(stream)                      # (S, γ+1) sync
            counts = np.asarray(counts)
            acc = np.asarray(acc)
            self._seq_lens = np.asarray(seq_lens).copy()
            self._last_tok = np.asarray(last_tok).copy()
            self._n_gen = np.asarray(n_gen).copy()
            self._done = np.asarray(done).copy()
            for r in excluded:
                # a masked row's device state carried through unchanged;
                # only its done flag was forced — restore the host truth
                self._done[r.slot] = r.finished
            span.args["rows"] = len(rows)
            self.stats["quantum_tokens"] += int(counts.sum())
            self.stats["spec_proposed"] += g * len(rows)
            self.stats["spec_accepted"] += int(acc.sum())
            now = self._now()
            emitted = 0
            for req in rows:
                slot = req.slot
                got = 0
                for k in range(int(counts[slot])):
                    if req.finished:
                        break
                    self._emit(req, int(stream[slot, k]))
                    emitted += 1
                    got += 1
                if self.flight is not None:
                    self.flight.on_spec_round(
                        req, now, proposed=g, accepted=int(acc[slot]),
                        emitted=got)
                if req.finished:
                    req.finish_time = now
            self.obs.on_quantum("spec_round", t0, now, emitted, len(rows))
            self.obs.on_spec_round(now, g * len(rows), int(acc.sum()))
            self._retire_finished()

    def _choose_k(self):
        """How many decode quanta the NEXT dispatch may run on-device.
        The multi-quantum cap applies only when the scheduler is in
        steady state (batch composition CANNOT change before the
        dispatch lands) and no host seam needs per-quantum visibility:
        an armed fault injector or an in-flight bisect probe forces
        per-quantum dispatch so fault attribution stays exact."""
        if self._mq_quantum is None or self._isolating:
            return 1
        if self.faults.armed:
            return 1
        if not self.scheduler.steady_state():
            return 1
        return self._mq_max

    def _decode_quantum(self, include=None):
        """Dispatch + collect one decode step SYNCHRONOUSLY: the bisect
        probe. ``step()`` runs the two halves one quantum apart in
        steady decode, and the overlap tier (cluster pump,
        `step_dispatch`/`step_collect`) drives them separately."""
        pending = self._decode_dispatch(include=include)
        if pending is not None:
            self._decode_collect(pending)

    def _grow_tables(self, rows, ahead, tokens):
        """The span ``engine.decode.prepare``: grow each row's block
        table to cover the dispatch (``tokens`` more than it holds)
        before the device loop is entered (tables are static inside);
        capped by the request's own prompt+max_new bound, which
        admission already reserved, so growth can never oversubscribe
        the pool. ``ahead``: a quantum is in flight, the length mirrors
        are its arguments and the rows hold ``decode_quantum`` more by
        the time this dispatch runs. The mirror's row is REPLACED, never
        written through: the program in flight was handed the last
        upload's copy (``_dev_kept``)."""
        with RecordEvent("engine.decode.prepare"):
            for req in rows:
                slot = req.slot
                have = int(self._seq_lens[slot])
                need = min(have + ahead * self.config.decode_quantum
                           + tokens,
                           req.prompt_len + req.max_new_tokens - 1)
                self._tables[slot] = self.pool.grow_decode_table(
                    req.req_id, need, have, pad_to=self._table_width,
                    cow=self.prefix_cache)[:self._table_width]

    def _decode_dispatch(self, include=None, ahead=False):
        """DISPATCH HALF of the decode step: grow block tables, enqueue
        the jitted quantum (K quanta when `_choose_k` allows), adopt
        the async donated pool outputs, and return a pending record for
        `_decode_collect` — WITHOUT forcing a host sync, so the device
        executes while the host moves on (the overlap ``step()`` and the
        cluster pump exploit). ``include`` restricts the quantum to a
        subset of the decoding rows (the bisect-quarantine probe path):
        excluded rows ride along done-masked — inert through the
        dispatch — and their host state is restored at collect.
        ``ahead`` (``step()`` alone, where ``_runs_ahead``): the quantum
        before this one is still in flight; this one takes that one's
        outputs where they lie on the device, its rows are those that
        outlive it by their lengths, and its row says ``ahead=1``. A
        speculative round (host needs its acceptance counts to proceed)
        runs to completion here and returns None.

        The frames from ``door.pump`` down to the jitted call take the
        words of data stack they took before the spans (the quantum's
        outputs are adopted through one starred name for that): the
        quantum's first trace, 11 s of a run's set-up, moves by seconds
        with where CPython's stack chunks end under it (``PERF.md``
        section 6, PR 26; ``tests/test_program_spans.py`` holds the
        sum)."""
        if self.spec_draft is not None and not self._spec_disabled:
            self._spec_round_step(include=include)
            return None
        step, t_steps = self.stats["steps"], self.config.decode_quantum
        with RecordEvent("engine.decode", step_kind="decode", step=step,
                         half="dispatch") as span:
            k = 1 if include is not None or ahead else self._choose_k()
            rows = self.scheduler.decoding()
            excluded = []
            if include is not None:
                keep = {id(r) for r in include}
                excluded = [r for r in rows if id(r) not in keep]
                rows = [r for r in rows if id(r) in keep]
                for r in excluded:
                    self._done[r.slot] = True
            if ahead:
                rows = [r for r in rows if self._outlives(r)]
                span.args["ahead"] = 1
                self.obs.on_quantum_ahead()
            span.args.update(rows=len(rows), k=k)
            try:
                self._grow_tables(rows, ahead, k * t_steps)
                # the jitted call until it returns (its uploads, the
                # span engine.decode.args, lie inside)
                with RecordEvent("engine.decode.enqueue") as enqueue:
                    kc, vc, ks, vs, *out = self._guarded_dispatch(
                        "decode", rows, quanta=k)
            except BaseException:
                for r in excluded:
                    self._done[r.slot] = r.finished
                raise
            # adopt the donated pool outputs NOW (async handles — no
            # sync): the pre-dispatch buffers were consumed by donation
            # (the slot side leads ``out``: no name of its own, see the
            # docstring's note on this frame's words); the four carries
            # stay where they are for the next quantum (under tp, put
            # replicated as the executable takes them)
            self.pool.adopt(kc, vc, ks, vs, out.pop(0))
            self._carry = tuple(
                out[:4] if self._rep_sharding is None
                else (self._put_carry(c) for c in out[:4]))
            # out: seq_lens, last_tok, n_gen, done, toks, the experts'
            # rows (() without experts), and the count of quanta that
            # ran where the dispatch was of several. The
            # device's share of the wall starts where the call returned:
            # the enqueue span's end
            return {"rows": rows, "excluded": excluded, "t0": span.t0,
                    "t_disp": enqueue.t1, "k": k, "ahead": ahead,
                    "step": step,
                    "out": (*out, None) if k == 1 else tuple(out)}

    def _decode_collect(self, pending):
        """COLLECT HALF of the decode step: force the device results
        (the ONE host sync per dispatch), refresh the host mirrors,
        emit every generated token, account the dispatch as the
        ``n_exec`` quanta that actually ran (obs histograms, cost
        ledger, host-gap gauge — each sub-quantum gets an equal slice
        of the wall, so the conservation invariants partition exactly),
        and retire finished rows. A quantum dispatched AHEAD ran behind
        the one before it: its wall, and the device's share of it,
        start where that one ended (its sync span's end), not at its
        own dispatch, so no second is counted twice; and the rows that
        one finished are not rows of this one (they rode done-masked)."""
        with RecordEvent("engine.decode", step_kind="decode",
                         step=pending["step"], half="collect") as step:
            rows, excluded = pending["rows"], pending["excluded"]
            if pending["ahead"]:
                rows = [r for r in rows if not r.finished]
            k = pending["k"]
            t0 = max(pending["t0"], self._decode_end)
            seq_lens, last_tok, n_gen, done, toks, moe, nq = \
                pending["out"]
            t_steps = self.config.decode_quantum
            with RecordEvent("engine.decode.sync") as sync:
                toks = np.asarray(toks)                      # sync
                before = self._seq_lens
                # host and device agree here: on these values the next
                # quantum ran (or will run) where it takes this one's
                # outputs as they lie; the mirrors are copies, and what
                # the host writes to them from here on (below: a slot
                # freed meanwhile) is its own change
                self._carry_seen = tuple(
                    np.asarray(a) for a in (seq_lens, last_tok, n_gen, done))
                (self._seq_lens, self._last_tok, self._n_gen,
                 self._done) = (a.copy() for a in self._carry_seen)
                if pending["ahead"]:
                    # a row the host finished while this quantum was in
                    # flight (a stop rule, a closed stream) ran on: its
                    # slot is free, the device does not know
                    for slot, req in enumerate(self.scheduler.slots):
                        if req is None or req.finished:
                            self._done[slot] = True
                if not isinstance(moe, tuple):
                    # (T, layers, experts), or (K, T, ...) of which the
                    # quanta that ran: per expert layer and decode step
                    moe = np.asarray(moe)
                    if k > 1:
                        moe = moe[:max(int(np.asarray(nq)), 1)]
                    step.args.update(self.obs.on_moe_rows(
                        moe.reshape(-1, *moe.shape[-2:]),
                        self.config.num_slots * self._moe_top_k))
                if self._window:
                    at = [r.slot for r in rows]
                    step.args.update(self.obs.on_keys_attended(
                        before[at], self._seq_lens[at], self._window))
            # the device's share of the wall: from the jitted call's return
            # (the enqueue span's end) to the sync span's end
            now = sync.t1
            device_s = max(
                now - max(pending["t_disp"], self._decode_end), 0.0)
            self._decode_end = now
            with RecordEvent("engine.decode.emit"):
                for r in excluded:
                    # a masked row's device state carried through unchanged;
                    # only its done flag was forced — restore the host truth
                    self._done[r.slot] = r.finished
                if k > 1:
                    # (K, T, S) buffer + on-device loop counter: keep only
                    # the quanta that ran before the all-done early exit
                    n_exec = int(np.asarray(nq))
                    toks = toks[:n_exec].reshape(-1, toks.shape[2])
                    n_exec = max(n_exec, 1)
                else:
                    n_exec = 1                               # (T, S)
                self.stats["decode_quanta"] += n_exec
                self.stats["quantum_tokens"] += int(toks.shape[0]) * int(
                    toks.shape[1])
                emitted_k = [0] * n_exec
                for req in rows:
                    slot = req.slot
                    got = 0
                    for j in range(toks.shape[0]):
                        if req.finished:
                            break
                        self._emit(req, int(toks[j, slot]))
                        emitted_k[j // t_steps] += 1
                        got += 1
                    if self.flight is not None and got:
                        self.flight.on_quantum_tokens(req, now, got)
                    if req.finished:
                        req.finish_time = now
                # a K-quantum dispatch is K quanta to every seam downstream:
                # the sub-intervals partition [t0, now] exactly (last edge
                # IS `now`), so Σ phase seconds == histogram sums stays exact
                dt = (now - t0) / n_exec
                dev_dt = device_s / n_exec
                prev = t0
                for j in range(n_exec):
                    edge = now if j == n_exec - 1 else t0 + (j + 1) * dt
                    self.obs.on_quantum("decode", prev, edge, emitted_k[j],
                                        len(rows), device_s=dev_dt)
                    prev = edge
            self._retire_finished()

    def _retire_finished(self):
        now = self._now()
        for req in list(self.scheduler.live()):
            if req.finished:
                slot = req.slot
                if req.finish_time is None:
                    req.finish_time = now
                self.stats["generated_tokens"] += len(req.tokens)
                self.obs.on_retire(req, req.finish_time)
                if self.flight is not None:
                    self.flight.on_retire(
                        req, req.finish_time,
                        ttft=(req.first_token_time - req.arrival_time
                              if req.first_token_time is not None
                              else None),
                        e2e=req.finish_time - req.arrival_time,
                        reason=req.finish_reason)
                self._done[slot] = True
                self._max_new[slot] = 0
                self.scheduler.retire(req)
                self.completed.append(req)
