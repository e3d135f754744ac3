"""The main path's Pallas kernels compiled, at Llama-2-7B widths, by the
installed TPU compiler for a DESCRIBED (not attached) v5e chip — what
interpret mode cannot show: tiling alignment, VMEM budgets, Mosaic
lowering. Nothing runs, so a pass here says nothing about results or
times and is never reported as a chip run (``chip_smoke.py`` is that).
Skipped where the topology cannot be described.
"""
import importlib
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

H, D, HIDDEN, SEQ = 32, 128, 4096, 4096   # Llama-2-7B attention widths
SLOTS, BLOCK, TABLE_W = 8, 32, 64         # serving engine defaults @ 2048
POOL_BLOCKS = SLOTS * TABLE_W + 1

_KERNEL_MODULES = ("flash_attention", "rms_norm", "decode_attention",
                   "paged_attention", "varlen_flash_attention",
                   "chunk_attention", "grouped_matmul")


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, kernels steered out of interpret mode
    (they ask ``jax.default_backend()``, which still says cpu here) and
    the persistent compile cache off: an entry compiled for a described
    device cannot be read back without the chip and would only warn."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    mp = pytest.MonkeyPatch()
    for name in _KERNEL_MODULES:
        mod = importlib.import_module(f"paddle_tpu.ops.pallas.{name}")
        mp.setattr(mod, "_interpret_mode", lambda: False)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()
    mp.undo()


def _compiled_kernels(chip, fn, *shapes):
    """Compile ``fn`` for the described chip from shapes alone; returns
    the names of the Pallas kernels in the compiled program."""
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names

    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    return compiled_kernel_names(
        jax.jit(fn).lower(*args).compile().as_text())


def test_flash_attention_fwd_bwd(chip):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    qkv = ((1, SEQ, H, D), jnp.bfloat16)
    names = _compiled_kernels(
        chip, jax.value_and_grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert names == {"flash_attention_fwd", "flash_attention_bwd_dq",
                     "flash_attention_bwd_dkv"}


def test_rms_norm_fwd_bwd(chip):
    from paddle_tpu.ops.pallas.rms_norm import rms_norm

    def loss(x, w):
        return rms_norm(x, w, 1e-6).astype(jnp.float32).sum()

    names = _compiled_kernels(
        chip, jax.value_and_grad(loss, argnums=(0, 1)),
        ((SEQ, HIDDEN), jnp.bfloat16), ((HIDDEN,), jnp.bfloat16))
    assert names == {"rms_norm_fwd", "rms_norm_bwd"}


def test_decode_attention(chip):
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    cache = ((SLOTS, SEQ, H, D), jnp.bfloat16)
    names = _compiled_kernels(
        chip, decode_attention, ((SLOTS, H, D), jnp.bfloat16), cache,
        cache, ((SLOTS,), jnp.int32))
    assert names == {"decode_attention"}


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_attention(chip, pool_dtype):
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )

    pool = ((POOL_BLOCKS, BLOCK, H, D), pool_dtype)
    shapes = [((SLOTS, H, D), jnp.bfloat16), pool, pool,
              ((SLOTS, TABLE_W), jnp.int32), ((SLOTS,), jnp.int32)]
    if pool_dtype == jnp.int8:
        # static per-kv-head dequant scales ride into the kernel
        shapes += [((H,), jnp.float32)] * 2

        def fn(q, kp, vp, tables, lens, ks, vs):
            return paged_decode_attention(q, kp, vp, tables, lens,
                                          k_scale=ks, v_scale=vs)
    else:
        fn = paged_decode_attention
    assert _compiled_kernels(chip, fn, *shapes) == {
        "paged_decode_attention"}


def _pool_sized(text, elements, skip=("get-tuple-element", "bitcast")):
    """``(opcode, line)`` of every instruction of a computation's text
    whose result has ``elements`` elements, pass-throughs left out."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]+)\]\S* ([\w\-]+)\(",
                     line)
        if m and math.prod(map(int, m.group(1).split(","))) == elements \
                and m.group(2) not in skip:
            found.append((m.group(2), line.strip()))
    return found


def _scattered_and_moved(text, body, elements):
    """Of a loop body's instructions with a pool-sized result: the
    scatter fusions (the in-place writes), and every other one, which
    moves a whole pool a step."""
    scatters = {c.split(" ", 1)[0] for c in text.split("\n\n")
                if " scatter(" in c}
    written, moved = [], []
    for op, line in _pool_sized(body, elements):
        if op == "fusion" and re.search(
                r"calls=(%\S+?),", line).group(1) in scatters:
            written.append(line)
        else:
            moved.append(line.split(", metadata")[0][:200])
    return written, moved


def test_paged_decode_reads_the_pool_as_stored(chip):
    """The decode cell's shapes (Qwen2-7B: 28/4 heads x 128, 16 slots,
    1,153 blocks of 32, table width 16): `_paged_write`'s scatter + the
    kernel inside a scan with the pools donated, as `jit_quantum` runs
    them. No instruction of the loop body but the scatter itself may
    have a pool-sized result — no copy, transpose or prefetch of a whole
    layer pool a step, in whatever layout."""
    from paddle_tpu.nlp.llama import _paged_write
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )

    blocks, bs, hk, h, slots, width, steps = 1153, 32, 4, 28, 16, 16, 8

    def quantum(kp, vp, q, kv_new, tables, lens, blk, off):
        def body(carry, x):
            q_t, lens_t = x
            kp, vp, _, _ = _paged_write(kv_new, kv_new, blk, off,
                                        (*carry, None, None))
            return (kp, vp), paged_decode_attention(q_t, kp, vp, tables,
                                                    lens_t)

        (kp, vp), out = jax.lax.scan(body, (kp, vp), (q, lens))
        return kp, vp, out

    def shape(s, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dt, sharding=chip)

    pool = shape((blocks, bs, hk, D))
    text = jax.jit(quantum, donate_argnums=(0, 1)).lower(
        pool, pool, shape((steps, slots, h, D)), shape((slots, hk, D)),
        shape((slots, width), jnp.int32), shape((steps, slots), jnp.int32),
        shape((slots,), jnp.int32), shape((slots,), jnp.int32),
    ).compile().as_text()
    assert compiled_kernel_names(text) == {"paged_decode_attention"}
    body = next(c for c in text.split("\n\n") if "tpu_custom_call" in c)
    written, moved = _scattered_and_moved(text, body, blocks * bs * hk * D)
    assert len(written) == 2 and not moved, (written, moved)


def test_varlen_flash_attention_prefill(chip):
    """The serving engine's chunked-prefill attention: 5 rows of 64 new
    tokens each, attending over their cached context + the chunk."""
    from paddle_tpu.ops.pallas.varlen_flash_attention import (
        varlen_flash_attention,
    )

    def fn(q, k, v, cu_q, cu_k):
        return varlen_flash_attention(q, k, v, cu_q, cu_k, causal=True)

    names = _compiled_kernels(
        chip, fn, ((320, H, D), jnp.bfloat16), ((960, H, D), jnp.bfloat16),
        ((960, H, D), jnp.bfloat16), ((6,), jnp.int32), ((6,), jnp.int32))
    assert names == {"varlen_flash_attention_fwd"}


def _kanana_attention():
    """One latent attention layer at kanana-2-30b-a3b's widths (32 heads
    of 128 + 64 / 128 over a 512-wide latent), bf16: the layer, its
    configuration and its parameter values."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp.deepseek_v3 import (
        DeepseekV3Attention, DeepseekV3Config)

    paddle.set_default_dtype("bfloat16")
    try:
        cfg = DeepseekV3Config.kanana_2_30b_a3b(dtype="bfloat16")
        attn = DeepseekV3Attention(cfg)
    finally:
        paddle.set_default_dtype("float32")
    return attn, cfg, [p._value for _, p in attn.named_parameters()]


def test_latent_paged_decode_reads_the_pool_as_stored(chip):
    """The latent cell's shapes (kanana-2-30b-a3b: 5,120 blocks x 32 x
    576 bf16, 32 slots, table 136): the layer's ``paged_decode`` (write,
    then the kernel) scanned over 8 steps with the pool donated, as
    `jit_quantum` runs it. The kernel is in the program by name and
    reads the pool the scatter wrote: no instruction of the loop body
    but the scatter has a pool-sized result — no copy, transpose or
    gather of a layer's pool a step. ROUND the scan the compiler still
    re-lays the pool out, once in and once out: 576 lanes are not whole
    128-lane tiles, so the device stores the array block-index-minor
    ({0,2,1}) whatever the number of its axes (PERF.md section 6, PR
    33)."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names

    attn, cfg, p_vals = _kanana_attention()
    blocks, slots, width, steps = 5120, 32, 136, 8
    half = cfg.qk_rope_head_dim // 2

    def quantum(pool, p_vals, x, cos, sin, tables, lens, blk, off):
        def layer(pool, x_t, cos_t, sin_t, lens_t):
            def fwd(x_in):
                att, new = attn.paged_decode(
                    x_in, (cos_t, sin_t), tables, lens_t, blk, off,
                    (pool, None, None, None))
                return new[0], att._value

            return functional_call(
                attn, fwd, [Tensor(x_t, stop_gradient=True)], {}, p_vals,
                [])[0]

        return jax.lax.scan(lambda pool, xs: layer(pool, *xs), pool,
                            (x, cos, sin, lens))

    def shape(s, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(s), dt, sharding=chip)

    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        text = jax.jit(quantum, donate_argnums=(0,)).lower(
            shape((blocks, BLOCK, cfg.latent_dim)),
            [shape(v.shape, v.dtype) for v in p_vals],
            shape((steps, slots, 1, cfg.hidden_size)),
            shape((steps, slots, 1, half), jnp.float32),
            shape((steps, slots, 1, half), jnp.float32),
            shape((slots, width), jnp.int32),
            shape((steps, slots), jnp.int32),
            shape((slots,), jnp.int32), shape((slots,), jnp.int32),
        ).compile().as_text()
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    assert "latent_decode_attention" in compiled_kernel_names(text)
    pool_size = blocks * BLOCK * cfg.latent_dim
    computations = text.split("\n\n")
    body = next(c for c in computations
                if "tpu_custom_call" in c and "latent_decode_attention" in c)
    written, moved = _scattered_and_moved(text, body, pool_size)
    assert len(written) == 1 and not moved, (written, moved)
    entry = next(c for c in computations if c.startswith("ENTRY"))
    round_it = [op for op, _ in _pool_sized(
        entry, pool_size, skip=("get-tuple-element", "bitcast", "parameter",
                                "while", "tuple"))]
    assert round_it in (["copy"], ["copy", "copy"]), round_it


@pytest.mark.parametrize("route", ["kernel", "xla"])
def test_latent_paged_chunk_leaves_no_score_in_memory(chip, route):
    """The latent cell's ``paged_chunk`` (kanana-2-30b-a3b widths: 32
    heads of 128 + 64 / 128 over a 512-wide latent; 32 slots x 512
    tokens, table 136 x 32, bf16) for the described v5e. Through the
    kernel the compiled layer holds the kernel by name (so it fits
    VMEM) and no float32 array as large as ONE tile of the XLA loop's
    (S, H, C, 128 keys) scores, which is also its (S, H, C, Dv) carry;
    the same reading of the XLA route finds them, so the check can
    fail. The layer's pool is re-laid out once on the way in and once on
    the way out (the device stores it block-index-minor: see the decode
    test above) and nowhere else: no copy of it a key tile."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names

    slots, chunk, width, blocks = 32, 512, 136, 5120
    attn, cfg, p_vals = _kanana_attention()

    def layer(p_vals, x, cos, sin, tables, base_lens, blk, off, pool):
        def fwd(x_t):
            att, new = attn.paged_chunk(x_t, (cos, sin), tables, base_lens,
                                        blk, off, (pool, None, None, None))
            return att._value, new[0]

        return functional_call(attn, fwd, [Tensor(x, stop_gradient=True)],
                               {}, p_vals, [])[0]

    def shape(s, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(s), dt, sharding=chip)

    half = cfg.qk_rope_head_dim // 2
    args = ([shape(v.shape, v.dtype) for v in p_vals],
            shape((slots, chunk, cfg.hidden_size)),
            shape((slots, chunk, half), jnp.float32),
            shape((slots, chunk, half), jnp.float32),
            shape((slots, width), jnp.int32), shape((slots,), jnp.int32),
            shape((slots, chunk), jnp.int32),
            shape((slots, chunk), jnp.int32),
            shape((blocks, BLOCK, cfg.latent_dim)))
    paddle.set_flags({"FLAGS_pallas_force": route == "kernel"})
    try:
        compiled = jax.jit(layer, donate_argnums=(8,)).lower(
            *args).compile()
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    text = compiled.as_text()
    tile = slots * cfg.num_attention_heads * chunk * 128
    # (slot, head, query, key | value lane) in whatever order; the
    # projections' own float32 results have no head axis
    big = [m.group(0)
           for m in re.finditer(r"f32\[(\d+,\d+,\d+,\d+)\]", text)
           if math.prod(map(int, m.group(1).split(","))) >= tile]
    temp = compiled.memory_analysis().temp_size_in_bytes
    if route == "kernel":
        assert "chunk_attention" in compiled_kernel_names(text)
        assert not big, sorted(set(big))
        assert temp < 3 * tile * 4, temp
        copies = [line for op, line in _pool_sized(
            text, blocks * BLOCK * cfg.latent_dim) if op == "copy"]
        assert len(copies) <= 2, copies
    else:
        assert "chunk_attention" not in compiled_kernel_names(text)
        assert big and temp > 3 * tile * 4, temp


# (slots, chunk, heads, KV heads, table width, pool blocks, ring rows)
_GQA_CHUNK_SHAPES = {
    # the dense decode cell's mixed step: Qwen2-7B's SEVEN heads a group
    "qwen2_7b_table": (16, 128, 28, 4, 16, 1153, None),
    # the state-space cell's one attention layer in ten
    "granite_table": (64, 128, 32, 8, 40, 2560, None),
    # a speculative verify pass: draft + 1 queries, padded to 16
    "verify_table": (8, 5, 32, 8, 64, 513, None),
    # a window layer's ring at the window cell's 8 slots and at 16
    "trinity_ring": (8, 1024, 32, 4, None, None, 3072),
    "trinity_ring_16_slots": (16, 1024, 32, 4, None, None, 3072),
}


@pytest.mark.parametrize("shapes", sorted(_GQA_CHUNK_SHAPES))
def test_gqa_chunk_attention(chip, shapes, pallas_forced):
    """The dense routes of the mixed step compile to the kernel at the
    shapes the cells (and the verify pass) bring, groups of 4, 7 and 8
    heads; a ring is read as it is stored, through a bitcast: no
    instruction has a ring-sized result (but the compiler's own prefetch
    of a ring, in the stored order, at 16 slots)."""
    from paddle_tpu.nlp import paged_attention as PA
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names

    s, c, h, hk, w, nb, r = _GQA_CHUNK_SHAPES[shapes]
    bf16, i32 = jnp.bfloat16, jnp.int32
    if r is None:
        fn = PA._paged_chunk_attn
        args = [((s, c, h, D), bf16), ((nb, BLOCK, hk, D), bf16),
                ((nb, BLOCK, hk, D), bf16), ((s, w), i32), ((s,), i32)]
    else:
        def fn(q, ring_k, ring_v, base, counts):
            return PA.ring_chunk_attn(q, ring_k, ring_v, base, counts, 2048)

        args = [((s, c, h, D), bf16), ((s, r, hk * D), bf16),
                ((s, r, hk * D), bf16), ((s,), i32), ((s,), i32)]
    text = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(shape, dt, sharding=chip)
        for shape, dt in args]).compile().as_text()
    assert compiled_kernel_names(text) == {"gqa_chunk_attention"}
    if r is not None:
        assert not _pool_sized(text, s * r * hk * D,
                               skip=("get-tuple-element", "bitcast",
                                     "parameter", "copy-start",
                                     "copy-done"))


# a mixed step's routed rows (a tile's, in the state-space cell) or a
# decode step's, experts held, and the two products' (K, N)
_EXPERT_PRODUCTS = {
    "trinity_p1": (65536, 128, 2048, 2048),
    "trinity_p2": (65536, 128, 1024, 2048),
    "kanana_p1": (98304, 128, 2048, 1536),
    "kanana_p2": (98304, 128, 768, 2048),
    "granite_p1": (40960, 36, 4096, 1536),
    "granite_p2": (40960, 36, 768, 4096),
    # ungated, 1856 = 14.5 lanes: p1 reads the stack as stored (N on
    # sublanes), p2 contracts over a K padded with zeros; the mixed step's
    # rows at 512-row tiles and a decode step's at the 16-row tile
    "nemotron_prefill_p1": (49152, 64, 2688, 1856),
    "nemotron_prefill_p2": (49152, 64, 1856, 2688),
    "nemotron_decode_p1": (384, 64, 2688, 1856),
    "nemotron_decode_p2": (384, 64, 1856, 2688),
    # the four other cells' decode steps at the 16-row tile (ISSUE 47), and
    # the points between at the state-space widths (64-, 128-, 256-row
    # tiles); Solar-Open2's first block is over 16 MiB: two N tiles, no
    # epilogue
    "trinity_decode_p1": (64, 128, 2048, 2048),
    "trinity_decode_p2": (64, 128, 1024, 2048),
    "kanana_decode_p1": (192, 128, 2048, 1536),
    "kanana_decode_p2": (192, 128, 768, 2048),
    "granite_decode_p1": (640, 36, 4096, 1536),
    "granite_decode_p2": (640, 36, 768, 4096),
    "granite_64_rows_a_group_p1": (2310, 36, 4096, 1536),
    "granite_128_rows_a_group_p1": (4610, 36, 4096, 1536),
    "granite_256_rows_a_group_p1": (9220, 36, 4096, 1536),
    "solar_prefill_p1": (98304, 40, 4096, 2560),
    "solar_prefill_p2": (98304, 40, 1280, 4096),
    "solar_decode_p1": (768, 40, 4096, 2560),
    "solar_decode_p2": (768, 40, 1280, 4096),
}


@pytest.mark.parametrize("shapes", sorted(_EXPERT_PRODUCTS))
def test_grouped_matmul(chip, shapes):
    """The routed experts' products of the five expert cells compile to
    the kernel at the tiles it reads from the shapes (a gated cell's
    first with the SwiGLU epilogue where gate and up share a block, as
    the cells run it): two (K, block_n) weight blocks, a row tile in and
    one out fit the VMEM limit it asks for."""
    import functools

    from paddle_tpu.ops.pallas.grouped_matmul import (fuses_swiglu,
                                                      grouped_matmul)

    rows, e, k, n = _EXPERT_PRODUCTS[shapes]
    bf16 = jnp.bfloat16
    gated = shapes.endswith("p1") and not shapes.startswith("nemotron")
    assert _compiled_kernels(
        chip, functools.partial(
            grouped_matmul, swiglu=gated and fuses_swiglu(
                jax.ShapeDtypeStruct((e, k, n), bf16))),
        ((rows, k), bf16), ((e, k, n), bf16),
        ((e,), jnp.int32)) == {"grouped_matmul"}


def _layouts(text, elements, rows, but_leading=None):
    """``(opcode, minor-to-major order)`` of every instruction of a compiled
    program whose result has ``elements`` elements in rows of one of the
    widths ``rows`` (a weight may have as many elements as a ring: the
    dense feed-forward's 2048 x 6144 has), pass-throughs left out, and
    results whose leading dimension is ``but_leading`` (rows gathered a
    slot, which are as many as the pool's where the tables name every
    block)."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \(?\w+\[([\d,]+)\]\{([\d,]+)\S* "
                     r"([\w\-]+)\(", line)
        if not m or m.group(3) in ("get-tuple-element", "bitcast",
                                   "parameter"):
            continue
        dims = list(map(int, m.group(1).split(",")))
        if math.prod(dims) == elements and dims[-1] in rows \
                and dims[0] != but_leading:
            found.append((m.group(3), m.group(2)))
    return found


def test_the_window_cells_programs_move_no_ring_and_no_pool(chip,
                                                            monkeypatch):
    """``trinity-mini.doc16k-o128``'s REAL ``jit_quantum`` and ``jit_mixed``
    (the family's model from the cell's configuration, 4.24 B parameters
    as zeros; the engine with the cell's options; a batch of 8 x 16,384
    admitted) compiled for the described v5e. A window layer's ring
    ``(8, 3072, 512)`` and the full layer's block arrays ``(4224, 32, 4,
    128)`` are written by scatters in place and read as they are stored:
    no instruction of either program, in any loop body or outside, has a
    ring-sized or pool-sized result in another order than the stored one
    (no ``copy`` or ``transpose`` of a whole ring or pool a layer and
    step; stored with a head axis of its own on either side of the rows a
    ring was copied whole 8 to 24 times a decode step: PERF.md section 6,
    PR 35); what the compiler itself moves is a prefetch in the stored
    order. The programs fit the chip beside their arguments, and the full
    layer's decode attention is the dense cell's kernel."""
    import json

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.nn import initializer as I
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names
    from paddle_tpu.serving import ServingEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmark.families import afmoe as family
    from benchmark.harness import counts_afmoe as counts

    class Zeros(I.Constant):        # 8.5 GB of weights nobody reads
        def __init__(self, *a, **k):
            super().__init__(0.0)

    for name in ("XavierNormal", "XavierUniform", "Normal"):
        monkeypatch.setattr(I, name, Zeros)
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-mini-l5.json")) as f:
        cfg = json.load(f)
    dtype_was = paddle.get_default_dtype()
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        model = family.build_model(cfg)
        model.eval()
        eng = ServingEngine(model, **cfg["engine"])
        for _ in range(8):
            eng.submit(np.ones(16384, np.int32), max_new_tokens=128)
        eng._admit()

        def shapes(args):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                               sharding=chip), args)

        compiled = {}
        for name, (step, args) in (("quantum", eng.decode_step_target()),
                                   ("mixed", eng.mixed_step_target())):
            compiled[name] = step.lower(*shapes(args)).compile()
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
        paddle.set_default_dtype(dtype_was)
    n_params = sum(int(p._value.size) for _, p in model.named_parameters())
    assert n_params == counts.total_params(cfg) == 4_241_534_720
    ring = 8 * 3072 * 512
    pool = 4224 * 32 * 4 * 128
    resident = 2 * n_params + 2 * 2 * pool + 8 * 25_165_824
    for name, program in compiled.items():
        text = program.as_text()
        mem = program.memory_analysis()
        assert 0 <= mem.argument_size_in_bytes - resident < 1 << 20, name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < 12e9, (name, mem.temp_size_in_bytes)
        rings = _layouts(text, ring, (512,))
        # the chunk kernel reads the table's rows gathered a slot, (8, 528
        # x 32, 4 x 128): 8 x 528 is every block here, but no pool
        pools = _layouts(text, pool, (128, 512), but_leading=8)
        # every window layer's K and V ring and the full layer's K and V
        # arrays are scattered into, in the stored (row-major) order
        assert [op for op, _ in rings].count("scatter") == 8, rings
        assert [op for op, _ in pools].count("scatter") == 2, pools
        # row-major as stored (a scatter may see its array with the
        # leading axes folded, (slots x rows, 512): the same bytes)
        for _, order in rings + pools:
            n = order.count(",") + 1
            assert order == ",".join(map(str, reversed(range(n)))), (
                name, rings, pools)
        moved = {op for op, _ in rings + pools} & {"copy", "transpose"}
        assert not moved, (name, moved)
    assert "paged_decode_attention" in compiled_kernel_names(
        compiled["quantum"].as_text())
    assert compiled["quantum"].memory_analysis().temp_size_in_bytes < 1 << 28
    # both chunk attentions of the mixed step are the kernel (five calls:
    # four rings and the table), and nothing the size of a fold's (S, H,
    # C, 256 keys) float32 score tile is left in the program
    mixed = compiled["mixed"].as_text()
    assert "gqa_chunk_attention" in compiled_kernel_names(mixed)
    assert len(re.findall(r" custom-call\(.*gqa_chunk_attention/pallas_call",
                          mixed)) == 5
    assert not re.search(r"f32\[8,4,8,1024,\d+\]", mixed)
    # the four expert layers' two products are the grouped-matmul kernel
    # in the mixed step (65,536 routed rows over 128 experts, 512-row
    # tiles) AND in the quantum (64 rows, the 16-row tile: ISSUE 47)
    for text in (mixed, compiled["quantum"].as_text()):
        assert len(re.findall(
            r" custom-call\(.*grouped_matmul/pallas_call", text)) == 2 * 4
        assert "ragged-dot" not in text


def test_the_nemotron_cells_programs_fit_and_take_both_kernels(chip,
                                                               monkeypatch):
    """``nemotron-3-nano-30b-a3b.gen512-o256``'s REAL ``jit_quantum`` and
    ``jit_mixed`` (the family's model from the cell's configuration, 4.94 B
    parameters as zeros; the engine with the cell's options; a batch of 64
    x 512 admitted) compiled for the described v5e. Both kernels take G 16
    / HK 2 (``paged_decode_attention`` in the quantum,
    ``gqa_chunk_attention`` in the mixed step); the experts' width 1856 is
    14.5 lanes and 116 sublane tiles, so by ``grouped_matmul.supports``
    both products are the ``grouped_matmul`` kernel in BOTH programs (ISSUE
    40), two custom calls an expert layer, and no ``ragged-dot`` is left.
    The chip stores ``up_proj`` (64, 2688, 1856) with the 2688 minor; the
    kernel reads that through a bitcast and contracts on the block's
    minor axis, so NO expert stack is copied in either program (the
    ``ragged-dot`` custom call wanted 1856 minor padded to 1920: six
    copies alive together in the quantum, 3.99 GB of temporaries, which
    cut the configuration from 16 layers to 14: PERF.md section 6, PR
    39)."""
    import json

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.nn import initializer as I
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names
    from paddle_tpu.serving import ServingEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmark.families import nemotron_h as family
    from benchmark.harness import counts_nemotron_h as counts

    class Zeros(I.Constant):        # 9.9 GB of weights nobody reads
        def __init__(self, *a, **k):
            super().__init__(0.0)

    for name in ("XavierNormal", "XavierUniform", "Normal"):
        monkeypatch.setattr(I, name, Zeros)
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b-l14-ep2.json")) as f:
        cfg = json.load(f)
    dtype_was = paddle.get_default_dtype()
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        model = family.build_model(cfg)
        model.eval()
        eng = ServingEngine(model, **cfg["engine"])
        for _ in range(64):
            eng.submit(np.ones(512, np.int32), max_new_tokens=128)
        eng._admit()

        def shapes(args):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                               sharding=chip), args)

        compiled = {}
        for name, (step, args) in (("quantum", eng.decode_step_target()),
                                   ("mixed", eng.mixed_step_target())):
            compiled[name] = step.lower(*shapes(args)).compile()
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
        paddle.set_default_dtype(dtype_was)
    n_params = sum(int(p._value.size) for _, p in model.named_parameters())
    assert n_params == counts.total_params(cfg) == 4_937_225_472
    resident = (2 * n_params + 2 * 2 * 1664 * 32 * 2 * 128 * 2
                + 64 * counts.state_bytes_per_slot(cfg))
    hbm = 15.75 * 2 ** 30
    for name, program in compiled.items():
        mem = program.memory_analysis()
        assert 0 <= mem.argument_size_in_bytes - resident < 1 << 20, name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < hbm - (1 << 30), (name, mem.temp_size_in_bytes)
    quantum, mixed = (compiled[k].as_text() for k in ("quantum", "mixed"))
    # no expert stack is re-laid out: the quantum's temporaries are under
    # ONE up_proj (they were six, 3.99 GB)
    up = 64 * 2688 * 1856 * 2
    assert compiled["quantum"].memory_analysis().temp_size_in_bytes < up
    assert "paged_decode_attention" in compiled_kernel_names(quantum)
    assert "gqa_chunk_attention" in compiled_kernel_names(mixed)
    assert len(re.findall(r" custom-call\(.*gqa_chunk_attention/pallas_call",
                          mixed)) == 2
    for text in (quantum, mixed):
        assert "ragged-dot" not in text
        assert len(re.findall(
            r" custom-call\(.*grouped_matmul/pallas_call", text)) == 2 * 6
        assert not [line for line in text.splitlines()
                    if re.search(r"= bf16\[64,(2688,1856|1856,2688)\]\S* "
                                 r"(copy|transpose|fusion)\(", line)]


def test_the_falcon_h1_cells_programs_fit_and_take_both_kernels(chip,
                                                                monkeypatch):
    """``falcon-h1-34b.chat1k-o256``'s REAL ``jit_quantum`` and
    ``jit_mixed`` (the family's model from the cell's configuration, 5.25 B
    parameters as zeros; the engine with the cell's options; a batch of 64
    x 1,024 admitted) compiled for the described v5e. Every one of the six
    layers is on BOTH sides of the pool: six K and six V arrays and six
    slot rows of a float32 state (32 x 128 x 256) and a convolution tail.
    Both attention kernels take G 5 / HK 4 (``paged_decode_attention`` in
    the quantum, ``gqa_chunk_attention`` in the mixed step, one call a
    layer); arguments and temporaries fit the chip's 15.75 GiB with 1 GiB
    to spare, and no weight is copied into another layout."""
    import json

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names
    from paddle_tpu.serving import ServingEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmark.families import falcon_h1 as family
    from benchmark.harness import counts_falcon_h1 as counts

    with open(os.path.join(root, "benchmark", "configs",
                           "falcon-h1-34b-l6.json")) as f:
        cfg = json.load(f)
    dtype_was = paddle.get_default_dtype()
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        model = family.build_model(cfg)     # every matrix zeros
        model.eval()
        eng = ServingEngine(model, **cfg["engine"])
        for _ in range(64):
            eng.submit(np.ones(1024, np.int32), max_new_tokens=256)
        eng._admit()

        def shapes(args):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                               sharding=chip), args)

        compiled = {}
        for name, (step, args) in (("quantum", eng.decode_step_target()),
                                   ("mixed", eng.mixed_step_target())):
            compiled[name] = step.lower(*shapes(args)).compile()
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
        paddle.set_default_dtype(dtype_was)
    layers = cfg["num_hidden_layers"]
    n_params = sum(int(p._value.size) for _, p in model.named_parameters())
    assert n_params == counts.total_params(cfg) \
        == layers * 430_120_032 + 2 * 261120 * 5120 + 5120
    blocks = cfg["engine"]["num_blocks"]
    resident = (2 * n_params + blocks * 32 * counts.cache_bytes_per_token(cfg)
                + cfg["engine"]["num_slots"]
                * counts.state_bytes_per_slot(cfg))
    hbm = 15.75 * 2 ** 30
    for name, program in compiled.items():
        mem = program.memory_analysis()
        assert 0 <= mem.argument_size_in_bytes - resident < 1 << 20, name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < hbm - (1 << 30), (name, mem.temp_size_in_bytes / 2 ** 30)
    quantum, mixed = (compiled[k].as_text() for k in ("quantum", "mixed"))
    assert "paged_decode_attention" in compiled_kernel_names(quantum)
    assert "gqa_chunk_attention" in compiled_kernel_names(mixed)
    assert len(re.findall(r" custom-call\(.*gqa_chunk_attention/pallas_call",
                          mixed)) == layers
    # no weight is re-laid out: no copy, transpose or fusion whose result
    # has a matrix's shape (either order) in bf16
    widths = r"(5120|21504|9248|4096|2560|512|261120)"
    for text in (quantum, mixed):
        assert not [line for line in text.splitlines()
                    if re.search(r"= bf16\[" + widths + "," + widths
                                 + r"\]\S* (copy|transpose)\(", line)]


def test_the_solar_open2_cells_programs_fit_and_take_their_kernels(
        chip, monkeypatch):
    """``solar-open2-250b.chat1k-o256``'s REAL ``jit_quantum`` and
    ``jit_mixed`` (the family's model from the cell's configuration, 3.31 B
    parameters as zeros; the engine with the cell's options; one batch of
    the cell's traffic file admitted, 96 x 1,024 as the cell is committed:
    96 slots, 12,288 positions a mixed step; the issue's 128 made a batch
    12.1 s, PERF.md section 6) compiled for the described v5e. Arguments
    (the weights, one K and one V array, three slot rows of a float32 state
    (64 x 128 x 128) and three tails) and temporaries fit the chip's
    15.75 GiB; the quantum's delta rule is the
    ``kda_decode_update`` kernel, one call a KDA layer, its state aliased
    in place; no weight and no matrix state is copied whole in either
    program. The experts' products, at width 1280 (ten whole lane tiles):
    ``grouped_matmul`` in BOTH programs, two calls a layer (ISSUE 47: the
    quantum's 2.4 rows a held expert at the 16-row tile, the first
    product's 21 MB block as two N tiles), and no ``ragged-dot`` is left."""
    import json

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names
    from paddle_tpu.serving import ServingEngine

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from benchmark.families import solar_open2 as family
    from benchmark.harness import counts_solar_open2 as counts
    from paddle_tpu.ops.pallas import kda_decode

    monkeypatch.setattr(kda_decode, "_interpret_mode", lambda: False)
    with open(os.path.join(root, "benchmark", "configs",
                           "solar-open2-250b-l4-ep8.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == "solar-open2-250b.chat1k-o256")
    with open(os.path.join(root, "benchmark", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    slots = cfg["engine"]["num_slots"]
    assert slots == traffic["batch"]
    dtype_was = paddle.get_default_dtype()
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        model = family.build_model(cfg)     # every leaf zeros
        model.eval()
        eng = ServingEngine(model, **cfg["engine"])
        for _ in range(slots):
            eng.submit(np.ones(traffic["prompt_len"], np.int32),
                       max_new_tokens=traffic["new_tokens"])
        eng._admit()

        def shapes(args):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                               sharding=chip), args)

        compiled = {}
        for name, (step, args) in (("quantum", eng.decode_step_target()),
                                   ("mixed", eng.mixed_step_target())):
            compiled[name] = step.lower(*shapes(args)).compile()
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
        paddle.set_default_dtype(dtype_was)
    n_params = sum(int(p._value.size) for _, p in model.named_parameters())
    assert n_params == counts.total_params(cfg) == 3_308_353_344
    blocks = cfg["engine"]["num_blocks"]
    resident = (2 * n_params + blocks * 32 * counts.cache_bytes_per_token(cfg)
                + slots * counts.state_bytes_per_slot(cfg))
    hbm = 15.75 * 2 ** 30
    for name, program in compiled.items():
        mem = program.memory_analysis()
        assert 0 <= mem.argument_size_in_bytes - resident < 1 << 20, name
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            < hbm - (1 << 30), (name, mem.temp_size_in_bytes / 2 ** 30)
    assert compiled["quantum"].memory_analysis().temp_size_in_bytes < 1 << 28
    quantum, mixed = (compiled[k].as_text() for k in ("quantum", "mixed"))
    assert {"paged_decode_attention", "kda_decode_update"} \
        <= compiled_kernel_names(quantum)
    assert len(re.findall(r" custom-call\(.*kda_decode_update/pallas_call",
                          quantum)) == 3
    assert "output_to_operand_aliasing={{1}: (1, {})}" in quantum
    assert "gqa_chunk_attention" in compiled_kernel_names(mixed)
    for text in (quantum, mixed):
        assert len(re.findall(
            r" custom-call\(.*grouped_matmul/pallas_call", text)) == 2 * 4
        assert "ragged-dot" not in text
    # nothing the size of a weight or of a layer's matrix state is copied
    # or transposed whole (the tails, 6 MB a layer, the compiler may move)
    weights = "|".join((
        "4096,8192", "8192,4096", "4096,1024", "4096,24576", "24576,4096",
        "40,4096,2560", "40,1280,4096", "4096,1280", "1280,4096",
        "4096,128", "128,8192", "4096,320", "4096,64"))
    state = rf"f32\[{slots},64,128,128\]"
    for text in (quantum, mixed):
        # (rows gathered for the experts have a weight's shape, 8,192 x
        # 4,096: `moe.dispatch` / `moe.combine` move those, not weights)
        assert not [line for line in text.splitlines() if re.search(
            r"= bf16\[(" + weights + r")\]\S* (copy|transpose)\(", line)
            and "/moe." not in line]
        assert not [line for line in text.splitlines() if re.search(
            "= " + state + r"\S* (copy|transpose)\(", line)]
