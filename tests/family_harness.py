"""What the served families' tests share (not collected): the ONE place the
helpers live, and ``FAMILIES``, one row a family that serves through the
engine's layer protocol. ``tests/test_family_contract.py`` runs every row
through the same scenarios; a family's own module imports from here what it
needs for the mechanisms that are its own. A new family is a row.

The rule of this file: a test that drives a model by hand does it through a
jitted program, as the engine does (both of its steps are one jitted program
each). ``Paged`` runs ``engine.paged_chunk_math`` / ``paged_decode_math``
inside ONE ``jax.jit`` each, built the way ``ServingEngine._make_mixed`` /
``_make_quantum`` build theirs (``Tensor(ids, stop_gradient=True)``,
``functional_call`` with the weights as arguments); ``forward`` is the
whole-sequence pass under ``jit``. Driven eagerly, one primitive a dispatch,
the same tests compiled hundreds of one-primitive programs each: a way of
running the model that no user and no cell has. The benchmark's float32
reference is called as it is: its layers are jitted inside, once a set of
dims, and four of the five pick an expert's rows on the host, so they cannot
be traced from outside (the fifth, traced whole, compiled once a call and was
slower).

Toys and engines are built once a worker: ``toy(name)`` holds a family's
configuration, model and leaves, ``door(name, **keywords)`` one served door a
set of keywords for the tests that only drain it. A test that preempts, or
reads a counter of the engine's own registry, reads a difference or builds its
own (``serve``). No persistent compilation cache: the suite's time does not
depend on what an earlier run left on the machine.

Test weights. The families with a state-space mixer draw their own
(``slow_leaves``): the benchmark's seeded ones make ``A`` about -1 and ``dt``
about 0.69, so the state forgets within ~10 tokens and a wrong carry of the
state over a chunk boundary would hide. Here ``dt_bias`` is about -4 and
``A_log`` in 0..2.7: ``dt A`` runs from -0.02 to -0.3 a token and a state
still holds a tenth of what it held 8 to 130 tokens ago. The others take the
benchmark's seeded weights.

Tolerances (a row's two). Program and reference compute the same float32
numbers in another order (the program fuses gate|up, sorts rows by expert,
folds attention tiles, sums a chunk at a time through decay matrices, reads
keys out of a ring, absorbs the up-projection at decode), so they differ by
summation order only: logits of magnitude ~1 agree to ``logit_tol``. The
reference's int8-operand control moves the same logits by > 100 x that and a
served token's gap to ~1e-2, so each scenario asserts that its tolerance is
tight enough for the control to fail it.
"""
import contextlib
import dataclasses
import functools
import hashlib
import importlib
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import autograd
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.jit import functional_call
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM, PagedKVCachePool
from paddle_tpu.nn import initializer
from paddle_tpu.serving import ServingEngine, no_shed_policy
from paddle_tpu.serving import engine as engine_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

GOLDEN = os.path.join(ROOT, "tests", "goldens", "family_step_programs.json")


# ------------------------------------------------------------- the helpers
def host(x, dtype=None):
    """A device value on the host, said out loud (the repo's lint takes a
    bare ``np.asarray`` / ``float`` over a jax value for an accident)."""
    return np.asarray(jax.device_get(x), dtype)


def max_abs(a, b=0.0):
    return float(np.abs(host(a) - host(b)).max())


def prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg["vocab_size"], (n,), dtype=np.int32)
            for n in lengths]


SERVE = {"num_slots": 4, "block_size": 8, "num_blocks": 64,
         "max_context": 96, "prefill_chunk": 16, "decode_quantum": 4}


def serve(model, **kw):
    """A front door of its own over ``model`` (four slots, blocks of 8,
    chunks of 16, quanta of 4 unless ``kw`` says otherwise)."""
    return paddle.inference.serve(model, policy=no_shed_policy(),
                                  **{**SERVE, **kw})


def drain(door, prompts_, new_tokens):
    streams = [door.submit(p, max_new_tokens=new_tokens) for p in prompts_]
    while door.engine.has_work:
        door.pump()
    return [host(s.request.tokens, np.int32) for s in streams]


def stamp(pool, row, value):
    """Fill slot ``row``'s side of the pool (a state-space layer's state, a
    window layer's ring) with ``value`` in every such layer."""
    pool.state = tuple(tuple(a.at[row].set(value) for a in layer)
                       for layer in pool.state)


def products_counts():
    """``moe_products_programs_total{path}``, read as a dict."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
        moe_products_programs)

    counter = moe_products_programs()
    return {p: counter.value(path=p) for p in ("kernel", "ragged_dot")}


def cases(field, label):
    """``pytest.param(family, item)`` for every item of every row's
    ``field``, the family's name leading the case id."""
    return [pytest.param(name, item, id=f"{name}-{label(item)}")
            for name, row in FAMILIES.items()
            for item in getattr(row, field)]


@contextlib.contextmanager
def undrawn():
    """Parameters built inside are zeros: nothing is drawn for a model
    whose every leaf is replaced, or whose values no assertion reads (a
    refusal, a program's lowered text, its scopes, its avals). The eager
    initialisers compile a generator's program a shape."""
    initializer.set_global_initializer(initializer.Constant(0.0))
    try:
        yield
    finally:
        initializer.set_global_initializer(None)


def llama_tiny():
    """The dense decoder's tiny preset, built ``undrawn``, in eval mode."""
    with undrawn():
        model = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    model.eval()
    return model


def slow_leaves(reference, cfg, seed=0, adjust=None):
    """name -> float32 array for every leaf of the reference's table:
    matrices of standard deviation 1/sqrt(fan-in), norms near 1, and a
    state that decays SLOWLY (see the module docstring). ``adjust(cfg,
    short name, values)`` is a family's own sizing of a leaf."""
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
            v = rng.standard_normal(shape)
        else:
            v = rng.standard_normal(shape) / np.sqrt(shape[-2])
        if adjust is not None:
            v = adjust(cfg, short, v)
        out[name] = jnp.asarray(v, jnp.float32)
    return out


# ------------------------------------------------ programs, built as the
# engine builds its own: weights as arguments, the ids a plain Tensor
def _program(model, body):
    """``jit`` of ``body(ids_t, *args)`` with ``model``'s parameters
    rebound to the first argument."""
    def program(p_vals, ids, *args):
        with autograd.no_grad():
            out, _ = functional_call(
                model, lambda ids_t: body(ids_t, *args),
                [Tensor(ids, stop_gradient=True)], {}, p_vals, [])
        return out

    return jax.jit(program)


def weights_of(model):
    return [p._value for _, p in model.named_parameters()]


def forward(model):
    """ids (B, S) -> logits (B, S, V): the model's whole-sequence pass as
    one jitted program."""
    step = _program(model, lambda ids_t: model(ids_t)._value)
    return lambda ids: step(weights_of(model), jnp.asarray(ids))


class Paged:
    """The engine's two bodies driven by hand over a pool of three slots,
    so that a test reads LOGITS where the engine hands out tokens: row 0
    serves, row 1 is never live, row 2 rides along. Each body is one
    jitted program; the pool is the one ``ServingEngine`` would build for
    the model's layout (``engine.pool_geometry``)."""

    def __init__(self, model, slots=3, num_blocks=32, block_size=8,
                 chunk=16, table=12):
        self.model, self.slots, self.table = model, slots, table
        self.pool = PagedKVCachePool(
            num_blocks, block_size, dtype=jnp.float32,
            **engine_mod.pool_geometry(model.paged_cache_layout(), slots,
                                       block_size, chunk))
        scratch = self.pool.ensure("__scratch__", 1)[0]
        self.lens = np.zeros(slots, np.int32)
        self._chunk = _program(
            model, lambda ids_t, lens, tables, live, counts, kc, vc, st:
            engine_mod.paged_chunk_math(model, scratch, ids_t, lens, tables,
                                        kc, vc, live, counts=counts, st=st))
        self._decode = _program(
            model, lambda ids_t, lens, tables, live, kc, vc, st:
            engine_mod.paged_decode_math(model, scratch, ids_t, lens,
                                         tables, kc, vc, live, st=st))

    def _tables(self, grow):
        for r, n in enumerate(grow):
            self.pool.ensure(f"r{r}", int(self.lens[r]) + int(n))
        return self.pool.block_table_array(
            [f"r{r}" for r in range(self.slots)], pad_to=self.table)

    def _run(self, step, ids, grow, *masks):
        kc, vc, _, _, st = self.pool.arrays()
        logits, *pools = step(
            weights_of(self.model), jnp.asarray(ids, jnp.int32),
            # a COPY: on the CPU ``jnp.asarray`` aliases the numpy buffer,
            # and ``lens += grow`` below would reach a program that is
            # still queued (seen: Falcon-H1's keys rotated a chunk ahead)
            jnp.array(self.lens), self._tables(grow),
            *(jnp.asarray(m) for m in masks), kc, vc, st)
        self.pool.adopt(*pools)
        self.lens += grow
        return logits

    def chunk(self, ids, counts):
        """ids (S, C), counts (S,): one mixed step; logits (S, V)."""
        counts = np.asarray(counts, np.int32)
        return self._run(self._chunk, ids, counts, counts > 0, counts)

    def decode(self, toks, live):
        """toks (S,), live (S,): one decode step; logits (S, V)."""
        live = np.asarray(live, bool)
        return self._run(self._decode, np.asarray(toks, np.int32)[:, None],
                         live.astype(np.int32), live)


# ------------------------------------------------------------------ a row
@dataclasses.dataclass(frozen=True)
class Family:
    """One served family. ``config``: its toy configuration under
    ``benchmark/configs/``, with ``overrides`` where the test needs another
    value; the ``benchmark.families.<name>`` module has the model builder,
    the reference and the leaf table. ``weights``: the benchmark's seed, or
    ``slow_leaves``' ``adjust`` (None: as drawn) for a family whose state
    must decay slowly. ``engine``: what its toy door is built with beside
    ``serve``'s defaults; ``served``: the (prefill_chunk, decode_quantum)
    pairs it is served at; ``pool_shapes(pool, slots, chunk)``: what a toy
    engine's pool must show. ``paged``: the hand-driven scenario (``Paged``'s
    keywords, the two rows' lengths, and by name the per-step counts of rows
    0 and 2). ``model`` / ``tiny``: the program's class and its config's
    ``tiny``; ``refusals``: (id, engine keywords, words the message has);
    ``config_refusals``: (overrides, what the message names); ``presets``:
    (the published preset's keywords, its parameter count, a check of the
    config or None); ``counters``: the family's own part of the counters
    scenario, ``scopes`` the names both step programs must carry,
    ``expert_layers`` / ``offshare`` / ``inactive``: its routed-expert
    layers, whether a chip holds a share of them, and the parameters a
    token does not multiply."""
    name: str
    config: str
    logit_tol: float
    gap_tol: float
    magnitude: float
    weights: object
    overrides: dict
    engine: dict
    served: tuple
    pool_shapes: object
    paged: dict
    model: object
    tiny: object
    refusals: tuple
    config_refusals: tuple
    presets: tuple
    scopes: tuple
    expert_layers: int
    offshare: bool
    inactive: int
    counters: object

    @property
    def module(self):
        return importlib.import_module(f"benchmark.families.{self.name}")

    @property
    def reference(self):
        return self.module.reference

    def toy_cfg(self, **more):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               self.config)) as f:
            return {**json.load(f), **self.overrides, **more}

    def leaves(self, cfg, seed=0):
        """name -> array: the test's own draw (``slow_leaves``)."""
        return slow_leaves(self.reference, cfg, seed, self.weights)

    def build(self, cfg, leaves=None):
        """(model, get_leaf) for ``cfg``: the benchmark's seeded weights, or
        the test's own (``leaves``, drawn here when None)."""
        if isinstance(self.weights, int):
            get_leaf = self.module.leaf_reader(cfg, self.weights)
        else:
            get_leaf = (leaves or self.leaves(cfg)).__getitem__
        dtype_was = paddle.get_default_dtype()
        # every leaf is replaced, leaf by leaf (the benchmark's
        # ``install_weights`` compiles ONE program that draws them all:
        # 18 s for the window family's toy)
        try:
            with undrawn():
                model = self.module.build_model(cfg)
        finally:
            paddle.set_default_dtype(dtype_was)    # the builder sets it
        _, params = self.module.parameters(model, cfg)
        for p, (name, _, _) in zip(params, self.reference.leaf_table(cfg)):
            p._value = get_leaf(name)
        model.eval()
        return model, get_leaf

    def tiny_model(self, **overrides):
        """The program's tiny preset, built ``undrawn``, in eval mode."""
        with undrawn():
            model = self.model(self.tiny(**overrides))
        model.eval()
        return model

    def serve(self, **kw):
        """A door of its own over the toy (a test that counts what the
        engine's registry counts from zero)."""
        return serve(toy(self.name)[1], **{**self.engine, **kw})


@functools.lru_cache(maxsize=None)
def toy(name):
    """(configuration, model, get_leaf) of family ``name``'s toy, once a
    worker."""
    row = FAMILIES[name]
    cfg = row.toy_cfg()
    return (cfg, *row.build(cfg))


@functools.lru_cache(maxsize=None)
def _door(name, keywords):
    return serve(toy(name)[1], **dict(keywords))


def door(name, **kw):
    """The family's toy door for these keywords, built once a worker and
    shared by every test that only drains it (its two programs compile
    once). The key is the WHOLE set of keywords, defaults among them: a
    family's door at its own chunk and quantum is its plain door."""
    return _door(name, tuple(sorted(
        {**SERVE, **FAMILIES[name].engine, **kw}.items())))


@functools.lru_cache(maxsize=None)
def toy_forward(name):
    return forward(toy(name)[1])


# ----------------------------------- every family's tiny preset, by name
def tiny_model(name):
    """The program's tiny preset of family ``name`` (``llama`` among them),
    built and in eval mode."""
    if name == "llama":
        return llama_tiny()
    return FAMILIES[name].tiny_model(**PRESET_KEYWORDS.get(name, {}))


def step_program_hashes(name):
    """sha256 of the two step programs' StableHLO text (no locations) of
    family ``name`` at its tiny preset, as the engine lowers them."""
    eng = ServingEngine(tiny_model(name), num_slots=2, block_size=8,
                        max_context=64, prefill_chunk=16, decode_quantum=4)
    eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
    eng._admit()
    return {program: hashlib.sha256(
        step.lower(*args).as_text().encode()).hexdigest()
        for program, (step, args) in (
            ("mixed", eng.mixed_step_target()),
            ("quantum", eng.decode_step_target()))}


# ===================================================== the families' rows
SLOTS, TOP_K, VOCAB_X_HIDDEN = 4, 3, 2048 * 128    # every toy's
_STATE_SPACE_PLAN = {          # C = 16: counts 1, C - 1, C, then the rest
    "chunk": 16, "lengths": (47, 21),
    "plans": {"uneven": ((1, 1), (15, 15), (16, 0), (5, 0))}}
_SERVED = ((16, 4), (8, 1), (32, 8))
_SSM_SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out")
_MOE_SCOPES = ("moe.router", "moe.experts", "moe.shared")


def _refusals(word, drafts, more=()):
    """What every family with a slot side or a latent pool refuses of the
    engine's keywords, each message naming the keyword and ``word``.
    ``drafts``: case id -> who drafts for whom (``"llama"``: the dense
    decoder for this family; ``"family"``: this family for the dense
    decoder, refused whatever the target; ``"self"``: this family for
    itself)."""
    rows = [("kv_dtype_int8", {"kv_dtype": "int8"}, ("kv_dtype='int8'",)),
            ("tp_2", {"tp": 2}, ("tp > 1",)), *more,
            *((i, {"spec_draft": how}, ("spec_draft",))
              for i, how in drafts.items())]
    return tuple((i, kw, (*words, word)) for i, kw, words in rows)


_PREFIX = ("prefix_cache", {"prefix_cache": True}, ("prefix_cache=True",))
_MESH = ("mesh", {"mesh": True}, ("tp > 1",))


def _built_now(name):
    """An engine over family ``name``'s toy built NOW: a gauge of the pool's
    geometry is the process's, published at an engine's build, and the
    shared door's has been overwritten by every engine built since."""
    return FAMILIES[name].serve().engine


# ------------------------------------------------------------ deepseek_v3
def _latent_pool(pool, slots, chunk):
    assert pool.layout == "latent" and pool.v_pools == []
    assert pool.k_pools[0].shape == (pool.num_blocks, 8, 64 + 16)
    assert len(pool.k_pools) == 3 and pool.state == ()


def _deepseek_counters(eng, moved, collect, mixed, model):
    rows, steps = moved["routed_rows"], moved["layer_steps"]
    touched, fullest = moved["experts_touched"], moved["expert_rows_max"]
    assert steps <= touched <= steps * 8 and fullest * 8 >= rows
    assert sum(a["moe_experts_touched"] for a in collect) == touched
    assert sum(a["moe_rows_max"] for a in collect) == fullest
    assert sum(a["moe_layer_steps"] for a in collect) == steps
    eng = _built_now("deepseek_v3")
    stats = eng.engine_stats()["pool"]
    assert stats["bytes_per_token"] == 3 * (64 + 16) * 4
    assert eng.obs.registry.get("serving_pool_bytes_per_token").value(
        pool="target") == stats["bytes_per_token"]


def _deepseek():
    from paddle_tpu.nlp import DeepseekV3Config, DeepseekV3ForCausalLM

    return Family(
        name="deepseek_v3", config="toy-mla-moe.json", logit_tol=2e-5,
        gap_tol=1e-4, magnitude=0.5, weights=2147483659, overrides={},
        engine={}, served=_SERVED, pool_shapes=_latent_pool,
        paged=_STATE_SPACE_PLAN,
        model=DeepseekV3ForCausalLM, tiny=DeepseekV3Config.tiny,
        refusals=_refusals(
            "latent-attention", {"spec_draft_llama": "llama",
                                 "spec_draft_latent": "family"})
        # the model says so itself, in its layout
        + (("sliding_window", {"sliding_window": 16}, ("sliding_window",)),),
        config_refusals=(
            ({"sliding_window": 16}, "sliding_window"),
            ({"q_lora_rank": 16}, "q_lora_rank"),
            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
            ({"tie_word_embeddings": True}, "tie_word_embeddings"),
            ({"moe_layer_freq": 2}, "moe_layer_freq")),
        # kanana-2-30b-a3b whole, and the benchmark's cut (layer 0 + 7
        # expert layers, all 128 experts, whole vocabulary)
        presets=((DeepseekV3Config.kanana_2_30b_a3b, {}, 30_670_815_104,
                  None),
                 (DeepseekV3Config.kanana_2_30b_a3b,
                  {"num_hidden_layers": 8}, 5_069_642_624, None)),
        scopes=("mla", *_MOE_SCOPES), expert_layers=2, offshare=False,
        # a token's top 3 of 8 experts a layer: 2 layers x 5 experts x 3
        # matrices of 128 x 64
        inactive=2 * 5 * 3 * 128 * 64, counters=_deepseek_counters)


# ------------------------------------------------------- granitemoehybrid
def _granite_leaf(cfg, short, v):
    if short == "embed":
        return v / 12.0
    if short in ("out_w", "o_w", "e_out", "s_out"):
        # what enters the residual stream: x 16, so that the layers and
        # not the (tied) embedding of the last token decide the next one
        return v * 16.0
    return v


def _granite_pool(pool, slots, chunk):
    assert len(pool.k_pools) == 1 == len(pool.v_pools)   # one attention layer
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (slots, 16, 16, 32), (slots, 3, 16 * 16 + 2 * 32)]
    assert len(pool.state) == 3
    assert pool.state[0][0].dtype == jnp.float32


def _granite_counters(eng, moved, collect, mixed, model):
    eng = _built_now("granitemoehybrid")
    stats = eng.engine_stats()["pool"]
    per_slot = 3 * (16 * 16 * 32 * 4 + 3 * 320 * 4)
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["state_slots"] == SLOTS
    assert stats["bytes_per_token"] == 2 * 2 * 32 * 4   # one K/V layer
    assert eng.obs.registry.get("serving_state_bytes_per_slot").value(
        pool="target") == per_slot


def _granite_preset(cfg):
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    full = type(cfg).granite_4_0_h_small()
    assert [i for i, t in enumerate(full.layer_types)
            if t == "attention"] == [5, 15, 25, 35]
    assert cfg.mamba_d_inner == 8192 and cfg.mamba_conv_dim == 8448


def _granite():
    from paddle_tpu.nlp.granitemoehybrid import (
        GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)

    return Family(
        name="granitemoehybrid", config="toy-ssm-moe.json", logit_tol=2e-5,
        gap_tol=1e-4, magnitude=0.2, weights=_granite_leaf,
        # the toy file's head is scaled for the benchmark's seeded weights;
        # the test's own weights are sized for the source's logits / 16
        overrides={"logits_scaling": 16},
        engine={}, served=_SERVED, pool_shapes=_granite_pool,
        paged=_STATE_SPACE_PLAN,
        model=GraniteMoeHybridForCausalLM, tiny=GraniteMoeHybridConfig.tiny,
        refusals=_refusals("state-space", {"spec_draft_llama": "llama",
                                           "spec_draft_state": "family"},
                           more=(_PREFIX,)),
        config_refusals=(
            ({"mamba_n_groups": 3}, "mamba_n_groups"),
            ({"position_embedding_type": "rope"}, "position"),
            ({"tie_word_embeddings": False}, "untied"),
            ({"mamba_proj_bias": True}, "bias"),
            ({"layer_types": ("mamba", "window", "attention", "mamba")},
             "layer_types"),
            ({"sliding_window": 16}, "sliding_window")),
        # granite_4_0_h_small() is the source's config: one period with
        # experts 0-35 held counts the cut's parameters
        presets=((GraniteMoeHybridConfig.granite_4_0_h_small,
                  {"num_hidden_layers": 10, "held_experts": (0, 36)},
                  4_962_732_672, _granite_preset),),
        scopes=(*_SSM_SCOPES, *_MOE_SCOPES), expert_layers=4, offshare=True,
        # of a layer's 4 held experts, the 3 x 4 / 8 = 1 a token
        # multiplies on average
        inactive=4 * 3 * 3 * 128 * 32, counters=_granite_counters)


# ------------------------------------------------------------------ afmoe
WINDOW, RING = 8, 12        # window 8, chunk 4, blocks of 4: a ring of 12


def _afmoe_pool(pool, slots, chunk):
    assert len(pool.k_pools) == 1 == len(pool.v_pools)   # one full layer
    ring = -(-(WINDOW + chunk) // 4) * 4
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (slots, ring, 2 * 32)] * 2
    assert len(pool.state) == 4 and pool.ring_tokens == ring


def _keys(n, w):
    m = min(n, w)
    return m * (m + 1) // 2 + (n - m) * w


def _afmoe_counters(eng, moved, collect, mixed, model):
    from paddle_tpu.obs.registry import MetricsRegistry

    # every position of a request but its last served token was computed:
    # 28 and 17 positions, each attending min(p + 1, 8) / p + 1 keys
    window, full = moved["window_keys"], moved["full_keys"]
    assert full == _keys(28, 10 ** 9) + _keys(17, 10 ** 9)
    assert window == _keys(28, WINDOW) + _keys(17, WINDOW)
    assert sum(a["window_keys"] for a in collect + mixed) == window
    assert sum(a["full_keys"] for a in collect + mixed) == full
    eng = _built_now("afmoe")
    stats = eng.engine_stats()["pool"]
    per_slot = 4 * 2 * RING * 2 * 32 * 4
    assert stats["window_bytes_per_slot"] == per_slot \
        == stats["state_bytes_per_slot"]
    assert stats["window_ring_tokens"] == RING
    assert stats["bytes_per_token"] == 2 * 2 * 32 * 4   # the one full layer
    for registry in (eng.obs.registry, MetricsRegistry.process()):
        assert registry.get("serving_window_bytes_per_slot").value(
            pool="target") == per_slot


def _afmoe_preset(cfg):
    assert cfg.layer_types == (("sliding_attention",) * 3
                               + ("full_attention",)) * 8


def _afmoe():
    from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM

    full = AfmoeConfig.trinity_mini()
    riding = {name: ((0, 4),) * 3 + tuple((n, 1) for n in sizes)
              for name, sizes in (
                  ("divides", (4,) * 9),
                  ("uneven", (1, 3, 4, 2, 4, 4, 3, 4, 4, 4, 3)),
                  ("half_chunks", (2,) * 18))}
    return Family(
        name="afmoe", config="toy-window-moe.json", logit_tol=2e-5,
        gap_tol=1e-4, magnitude=0.5, weights=2147483777,
        overrides={"sliding_window": WINDOW},
        engine={"block_size": 4, "num_blocks": 96, "prefill_chunk": 4},
        served=((4, 4), (8, 1), (16, 8)), pool_shapes=_afmoe_pool,
        # row 2 is prefilled first, alone, so that it DECODES beside row 0
        # (one position a step); contexts reach 48, four times the ring
        paged={"chunk": 4, "lengths": (48, 30), "plans": riding,
               "num_blocks": 48, "block_size": 4, "table": 16},
        model=AfmoeForCausalLM, tiny=AfmoeConfig.tiny,
        refusals=_refusals("window-ring", {"spec_draft_llama": "llama",
                                           "spec_draft_ring": "family"},
                           more=(_MESH, _PREFIX)),
        config_refusals=(
            ({"score_func": "softmax"}, "score function"),
            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
            ({"tie_word_embeddings": True}, "tied"),
            ({"mup_enabled": False}, "mup_enabled"),
            ({"layer_types": ["sliding_attention"] * 5}, "full_attention"),
            ({"layer_types": ["full_attention"] * 4 + ["chunked"]},
             "layer_types"),
            ({"sliding_window": None}, "sliding_window")),
        # trinity_mini() is the source's config: 26.1 B parameters whole,
        # and the benchmark's cut (5 layers, one dense, the first five
        # layer types)
        presets=((AfmoeConfig.trinity_mini, {},
                  2 * 65_020_160 + 30 * 839_131_520 + 819_988_480,
                  _afmoe_preset),
                 (AfmoeConfig.trinity_mini,
                  {"num_hidden_layers": 5, "num_dense_layers": 1,
                   "layer_types": full.layer_types[:5]}, 4_241_534_720,
                  None)),
        scopes=("attn.window", "attn.full", "attn.gate", *_MOE_SCOPES),
        expert_layers=4, offshare=False,
        # a token's top 3 of a layer's 8 experts
        inactive=4 * 5 * 3 * 128 * 64, counters=_afmoe_counters)


# ------------------------------------------------------------- nemotron_h
def _nemotron_pool(pool, slots, chunk):
    assert len(pool.k_pools) == 1 == len(pool.v_pools)   # one * layer
    assert tuple(pool.k_pools[0].shape) == (pool.num_blocks, 8, 2, 48)
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (slots, 8, 16, 32), (slots, 3, 8 * 16 + 2 * 2 * 32)]
    assert len(pool.state) == 4                          # four M layers
    assert pool.state[0][0].dtype == jnp.float32


def _nemotron_preset(cfg):
    pattern = cfg.hybrid_override_pattern
    assert (len(pattern), pattern.count("M"), pattern.count("E"),
            pattern.count("*")) == (52, 23, 23, 6)


def _nemotron_cut(pattern):
    def check(cfg):
        assert cfg.hybrid_override_pattern == pattern
    return check


def _nemotron():
    from paddle_tpu.nlp.nemotron_h import (
        NemotronHConfig, NemotronHForCausalLM)

    preset = NemotronHConfig.nemotron_3_nano_30b_a3b
    return Family(
        name="nemotron_h", config="toy-ssm-relu2-moe.json", logit_tol=5e-5,
        gap_tol=2e-4, magnitude=0.5, weights=None, overrides={},
        engine={}, served=_SERVED, pool_shapes=_nemotron_pool,
        paged=_STATE_SPACE_PLAN,
        model=NemotronHForCausalLM, tiny=NemotronHConfig.tiny,
        # decided by what the layers cache (a slot's recurrent state),
        # never by the model's class or a config attribute
        refusals=_refusals("state-space", {"spec_draft_self": "self"},
                           more=(_PREFIX,)),
        config_refusals=(
            ({"hybrid_override_pattern": "MEM-EMEM"},
             "hybrid_override_pattern"),
            ({"hybrid_override_pattern": "MEM*"}, "hybrid_override_pattern"),
            ({"n_groups": 3}, "n_groups"),
            ({"tie_word_embeddings": True}, "tied"),
            ({"mamba_proj_bias": True}, "bias"),
            ({"use_conv_bias": False}, "convolution"),
            ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
            ({"residual_in_fp32": True}, "residual_in_fp32"),
            ({"sliding_window": 16}, "sliding_window"),
            ({"time_step_limit": (0.0, 1.0)}, "time_step_limit"),
            ({"model_type": "mamba2"}, "model_type"),
            ({"n_group": 2}, "group-limited")),
        # nemotron_3_nano_30b_a3b() is the source's config: whole it counts
        # the card's 31.6 B; the issue's cut (16 layers, experts 0-63 held)
        # and the cut the chip's memory allowed (14 layers: PERF.md
        # section 6, PR 39)
        presets=((preset, {}, 31_577_940_288, _nemotron_preset),
                 (preset, {"num_hidden_layers": 16, "held_experts": (0, 64)},
                  5_634_855_744, _nemotron_cut("MEMEM*EMEMEM*EME")),
                 (preset, {"num_hidden_layers": 14, "held_experts": (0, 64)},
                  4_937_225_472, _nemotron_cut("MEMEM*EMEMEM*E"))),
        scopes=(*_SSM_SCOPES, *_MOE_SCOPES, "attn.proj", "attn.full",
                "norm"),
        expert_layers=3, offshare=True,
        # of a layer's 4 held experts, the 3 x 4 / 8 = 1 a token multiplies
        # on average (untied head: the embedding alone is a lookup)
        inactive=3 * 3 * 2 * 128 * 40, counters=None)


# -------------------------------------------------------------- falcon_h1
# the leaf each scalar multiplier scales the product of
_SCALED = {"k_w": "key_multiplier", "o_w": "attention_out_multiplier",
           "out_w": "ssm_out_multiplier", "head": "lm_head_multiplier",
           "embed": "embedding_multiplier"}


def _falcon_leaf(cfg, short, v):
    """Every leaf a multiplier scales is drawn so that leaf x multiplier has
    the size it would have without one (the published 0.011 on the keys
    would make the softmax a plain mean, 0.0375 / 0.011 on the branches and
    the MLP would leave the stream to the embedding)."""
    if short in _SCALED:
        return v / cfg[_SCALED[short]]
    if short == "in_w":
        return v / cfg["ssm_in_multiplier"]
    if short in ("gate_w", "down_w"):
        return v / cfg["mlp_multipliers"][short == "down_w"]
    return v


def _falcon_pool(pool, slots, chunk):
    # three layers, each on BOTH sides
    assert len(pool.k_pools) == 3 == len(pool.v_pools) == len(pool.state)
    assert tuple(pool.k_pools[0].shape) == (pool.num_blocks, 8, 2, 32)
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (slots, 8, 16, 32), (slots, 3, 8 * 16 + 2 * 2 * 32)]
    assert pool.state[0][0].dtype == jnp.float32


def _falcon():
    from paddle_tpu.nlp.falcon_h1 import FalconH1Config, FalconH1ForCausalLM

    preset = FalconH1Config.falcon_h1_34b
    layer, top = 430_120_032, 2 * 261120 * 5120 + 5120
    return Family(
        name="falcon_h1", config="toy-parallel-ssm.json", logit_tol=5e-5,
        gap_tol=2e-4, magnitude=0.5, weights=_falcon_leaf, overrides={},
        engine={}, served=_SERVED, pool_shapes=_falcon_pool,
        paged=_STATE_SPACE_PLAN,
        model=FalconH1ForCausalLM, tiny=FalconH1Config.tiny,
        # decided by what the layers' PARTS cache (a slot's recurrent state
        # among them), never by the model's class or a config attribute
        refusals=_refusals("state-space", {"spec_draft_self": "self"},
                           more=(_PREFIX,)),
        config_refusals=(
            ({"attention_bias": True}, "bias"),
            ({"projectors_bias": True}, "bias"),
            ({"mamba_conv_bias": False}, "convolution"),
            ({"hidden_act": "gelu"}, "hidden_act"),
            ({"mamba_d_ssm": 64}, "mamba_d_ssm"),
            ({"mamba_n_groups": 3}, "mamba_n_groups"),
            ({"mamba_rms_norm": False}, "gated norm"),
            ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
            ({"mamba_use_mlp": False}, "MLP"),
            ({"attn_layer_indices": [0]}, "attn_layer_indices"),
            ({"rope_scaling": {"type": "linear"}}, "rope_scaling"),
            ({"tie_word_embeddings": True}, "tied"),
            ({"sliding_window": 16}, "sliding_window"),
            ({"ssm_multipliers": (1.0, 1.0)}, "ssm_multipliers"),
            ({"model_type": "mamba2"}, "model_type")),
        # falcon_h1_34b() is the source's config: a layer counts ISSUE 41's
        # 430,120,032 parameters, the cut of six 5,254,594,112 and the
        # whole model 33,642,516,224
        presets=((preset, {"num_hidden_layers": 1}, layer + top, None),
                 (preset, {"num_hidden_layers": 6}, 5_254_594_112, None),
                 (preset, {}, 72 * layer + top, None)),
        scopes=(*_SSM_SCOPES, "attn.proj", "attn.full", "cache.write",
                "mix.sum", "mlp", "norm", "embed", "head"),
        expert_layers=0, offshare=False, inactive=0, counters=None)


# ------------------------------------------------------------ solar_open2
def _solar_leaf(cfg, short, v):
    if short.endswith("_conv"):
        # taps of ~0.5, as ``conv_w`` is drawn (the default would divide
        # them by sqrt(channels))
        return v * np.sqrt(v.shape[-2]) * 0.5
    return v


def _solar_pool(pool, slots, chunk):
    assert len(pool.k_pools) == 1 == len(pool.v_pools)   # one GQA layer
    assert tuple(pool.k_pools[0].shape) == (pool.num_blocks, 8, 2, 48)
    # a KDA layer's slot row: the matrix state a head and three tails
    assert [tuple(a.shape) for a in pool.state[0]] == [
        (slots, 4, 32, 32)] + [(slots, 3, 4 * 32)] * 3
    assert len(pool.state) == 3                          # three KDA layers
    assert pool.state[0][0].dtype == jnp.float32


def _solar_counters(eng, moved, collect, mixed, model):
    eng = _built_now("solar_open2")
    stats = eng.engine_stats()["pool"]
    per_slot = 3 * (4 * 32 * 32 * 4 + 3 * 3 * 128 * 4)
    assert stats["state_bytes_per_slot"] == per_slot
    assert stats["state_slots"] == SLOTS
    assert stats["bytes_per_token"] == 2 * 2 * 48 * 4     # one K/V layer
    assert eng.obs.registry.get("serving_state_bytes_per_slot").value(
        pool="target") == per_slot


def _solar_preset(cfg):
    assert cfg.gqa_layers == tuple(range(0, 48, 4))
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv) == (64, 128, 4)


def _solar():
    from paddle_tpu.nlp.solar_open2 import (
        SolarOpen2Config, SolarOpen2ForCausalLM)

    preset = SolarOpen2Config.solar_open2_250b
    kda, gqa = 137_732_288, 109_051_904              # ISSUE 46's mixers
    # a layer outside its mixer: two norms, router and bias, shared expert
    fixed, expert = 2 * 4096 + 4096 * 320 + 320 + 15_728_640, 15_728_640
    return Family(
        name="solar_open2", config="toy-kda-moe.json", logit_tol=5e-5,
        gap_tol=2e-4, magnitude=0.5, weights=_solar_leaf, overrides={},
        engine={}, served=_SERVED, pool_shapes=_solar_pool,
        paged=_STATE_SPACE_PLAN,
        model=SolarOpen2ForCausalLM, tiny=SolarOpen2Config.tiny,
        # decided by what the layers cache (a slot's matrix state), never
        # by the model's class or a config attribute
        refusals=_refusals("state-space", {"spec_draft_self": "self"},
                           more=(_PREFIX,)),
        config_refusals=(
            ({"kda_use_full_proj": True}, "kda_use_full_proj"),
            ({"linear_attn_config": {"short_conv_kernel_size": 4,
                                     "head_dim": 8, "num_heads": 4,
                                     "num_kv_heads": 2}}, "num_kv_heads"),
            ({"use_rope": True}, "use_rope"),
            ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
            ({"tie_word_embeddings": True}, "tied"),
            ({"gqa_layers": [1]}, "gqa_layers"),
            ({"gqa_layers": [0, 2]}, "gqa_layers"),
            ({"use_gqa_gate": False}, "use_gqa_gate"),
            ({"sliding_window": 16}, "sliding_window"),
            ({"model_type": "kimi_linear"}, "model_type")),
        # solar_open2_250b() is the source's config: whole it counts 250.3 B
        # (36 KDA and 12 GQA layers, 320 experts each), and the cell's cut
        # (one period, experts 0-39 held, an eighth of the vocabulary)
        presets=((preset, {}, 36 * kda + 12 * gqa
                  + 48 * (fixed + 320 * expert) + 2 * 196608 * 4096 + 4096,
                  _solar_preset),
                 (preset, {"num_hidden_layers": 4, "held_experts": (0, 40),
                           "vocab_size": 24576},
                  3 * kda + gqa + 4 * (fixed + 40 * expert)
                  + 2 * 24576 * 4096 + 4096, None)),
        scopes=("kda.proj", "kda.conv", "kda.gates", "kda.scan", "kda.out",
                *_MOE_SCOPES, "attn.proj", "attn.full", "attn.gate",
                "cache.write", "norm"),
        expert_layers=4, offshare=True,
        # of a layer's 4 held experts, the 3 x 4 / 8 = 1 a token multiplies
        # on average (untied head: the embedding alone is a lookup)
        inactive=4 * 3 * 3 * 128 * 40, counters=_solar_counters)


FAMILIES = {row.name: row for row in (
    _deepseek(), _granite(), _afmoe(), _nemotron(), _falcon(), _solar())}
# served toy families whose parity suite is not the contract's, and where
# it is
ELSEWHERE = {"llama_decoder": "tests/test_mixed_step.py: the dense decoder "
                              "is what the engine's own tests serve"}
# the keywords of each family's tiny preset in the step-program golden and
# the scope audit
PRESET_KEYWORDS = {"nemotron_h": {"held_experts": (0, 4)},
                   "solar_open2": {"held_experts": (0, 4)}}
