"""Attention over the paged pool in plain XLA (and the route to the
Pallas paged kernel): what a model's attention module calls from its
``paged_decode`` / ``paged_chunk`` forms, the serving engine's attention
protocol (``serving/engine.py``: ``paged_decode_math`` /
``paged_chunk_math``). Kept beside ``paged_cache.py``: the pool's arrays
are these functions' operands, and a model file must not import the
serving tier to attend over them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..parallel import mesh as mesh_state


def _rope_rows(x, cos, sin):
    """Rotate (..., H, D) by per-row angles (..., D/2) — the model's
    default (neox) rotary layout at each row's own cache position.
    Broadcasts over any leading dims: (S, H, D) with (S, D/2) for the
    decode quantum, (S, C, H, D) with (S, C, D/2) for the speculative
    verify chunk."""
    xf = x.astype(jnp.float32)
    c = cos[..., None, :]
    s = sin[..., None, :]
    d = x.shape[-1]
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _xla_paged_decode_attn(q, kp, vp, tables, lens, ks=None, vs=None,
                           scale=None):
    """Off-TPU decode attention over the paged pool: gather the table's
    blocks and run the same f32 masked softmax as the contiguous-cache
    fallback (`_masked_decode_attn`). ``ks``/``vs`` are the optional
    per-row scale pools of an int8 pool ((NB, BS, HK) f32): the gathered
    rows dequantize in f32 before the softmax, so the math matches the
    float path up to the quantization rounding itself. ``scale``
    multiplies the scores (default ``1 / sqrt(D)``)."""
    s_, h, d = q.shape
    w = tables.shape[1]
    bs, hk = kp.shape[1], kp.shape[2]
    k = kp[tables].reshape(s_, w * bs, hk, d)
    v = vp[tables].reshape(s_, w * bs, hk, d)
    if ks is not None:
        k = k.astype(jnp.float32) * ks[tables].reshape(
            s_, w * bs, hk)[..., None]
        v = v.astype(jnp.float32) * vs[tables].reshape(
            s_, w * bs, hk)[..., None]
    rep = h // hk
    kr = jnp.repeat(k, rep, axis=2) if rep > 1 else k
    vr = jnp.repeat(v, rep, axis=2) if rep > 1 else v
    sc = 1.0 / math.sqrt(d) if scale is None else scale
    logits = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                        kr.astype(jnp.float32)) * sc
    mask = jnp.arange(w * bs)[None, :] < lens[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype)


# the f32 score tile of `_paged_chunk_attn` may take this many bytes; a
# chunk whose scores over its whole block table would take more streams
# over the table in tiles of key blocks (a shape rule: no knob)
_CHUNK_SCORE_BYTES = 256 << 20


def _paged_chunk_attn(q, kp, vp, tables, base_lens, ks=None, vs=None,
                      scale=None):
    """Chunk attention over the paged pool, shared by the speculative
    VERIFY pass and the mixed prefill step: query position j of each
    slot attends pool positions < base+j+1 (the row's cached context
    and the chunk's own positions up to j, which the caller has already
    written). q is (S, C, H, D).

    The query heads are grouped over their KV head (K and V are never
    repeated), operands keep the pool's dtype with f32 accumulation,
    and the softmax is f32 with the same -1e30 mask as the decode
    paths. The (S, H, C, keys) f32 scores are built for
    ``_CHUNK_SCORE_BYTES`` worth of key blocks at a time and folded
    into running (m, l, acc) statistics (an online softmax with a chunk
    dimension); a table whose scores fit that size is one tile and no
    loop. ``ks``/``vs`` are the
    int8 pool's per-row scale pools: a tile dequantizes in f32 as it
    streams through. ``scale`` multiplies the scores (default
    ``1 / sqrt(D)``). No Pallas analog yet: this runs on every
    backend."""
    s_, c, h, d = q.shape
    w = tables.shape[1]
    bs, hk = kp.shape[1], kp.shape[2]
    g = h // hk
    sc = 1.0 / math.sqrt(d) if scale is None else scale
    tile = max(1, min(w, _CHUNK_SCORE_BYTES // (s_ * h * c * bs * 4)))
    n_tiles = -(-w // tile)
    # whole tiles: the padding columns point at pool block 0 and lie
    # past every row's length, so the mask below hides them
    tiled = jnp.pad(tables, ((0, 0), (0, n_tiles * tile - w))).reshape(
        s_, n_tiles, tile).transpose(1, 0, 2)           # (N, S, tile)
    lens = base_lens[:, None] + jnp.arange(c)[None, :] + 1   # (S, C)
    neg = jnp.float32(-1e30)
    qg = q.reshape(s_, c, hk, g, d)

    def fold(carry, ti):
        m, l, acc = carry
        blk = tiled[ti]                                 # (S, tile)
        k = kp[blk].reshape(s_, tile * bs, hk, d)
        v = vp[blk].reshape(s_, tile * bs, hk, d)
        if ks is not None:
            k = k.astype(jnp.float32) * ks[blk].reshape(
                s_, tile * bs, hk)[..., None]
            v = v.astype(jnp.float32) * vs[blk].reshape(
                s_, tile * bs, hk)[..., None]
        ct = jnp.promote_types(q.dtype, k.dtype)
        logits = jnp.einsum(
            "bchgd,bkhd->bhgck", qg.astype(ct), k.astype(ct),
            preferred_element_type=jnp.float32) * sc    # (S,HK,G,C,K)
        kpos = ti * (tile * bs) + jnp.arange(tile * bs)
        mask = kpos[None, None, :] < lens[:, :, None]   # (S, C, K)
        logits = jnp.where(mask[:, None, None], logits, neg)
        m2 = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m2)                         # (S, HK, G, C)
        p = jnp.exp(logits - m2[..., None])
        l2 = l * alpha + jnp.sum(p, axis=-1)
        acc2 = acc * alpha[..., None] + jnp.einsum(
            "bhgck,bkhd->bhgcd", p.astype(ct), v.astype(ct),
            preferred_element_type=jnp.float32)
        return (m2, l2, acc2), None

    # every query sees pool position 0 (base >= 0), so the first tile
    # lifts m above the -1e30 init before any masked tile's exp(neg - m)
    # underflows to an exact 0
    carry = (jnp.full((s_, hk, g, c), neg, jnp.float32),
             jnp.zeros((s_, hk, g, c), jnp.float32),
             jnp.zeros((s_, hk, g, c, d), jnp.float32))
    if n_tiles == 1:
        carry, _ = fold(carry, 0)
    else:
        carry, _ = jax.lax.scan(fold, carry, jnp.arange(n_tiles))
    _, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # (S,HK,G,C,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(s_, c, h, d).astype(
        q.dtype)


class PagedResidualLayer:
    """The serving engine's LAYER protocol (``serving/engine.py``) for a
    pre-norm residual decoder layer with ``input_layernorm``,
    ``self_attn``, ``post_attention_layernorm`` and ``mlp`` (a mix-in):
    ``x += Attn(norm(x)); x += MLP(norm(x))``, the attention asked of
    ``self_attn.paged_decode`` / ``paged_chunk``. ``step`` is the step's
    addressing (``rope``, ``tables``, ``lens``, ``write_blk``,
    ``write_off``, ...), ``cache`` this layer's pool arrays ``(k, v,
    k_scale, v_scale)``, a side the pool lacks None. Each returns the new
    hidden stream and the layer's new pool arrays."""

    def _paged(self, attend, hidden, step, cache):
        att, new = attend(
            self.input_layernorm(hidden), step["rope"], step["tables"],
            step["lens"], step["write_blk"], step["write_off"], cache)
        hidden = hidden + att
        hidden = hidden + self.mlp(self.post_attention_layernorm(hidden))
        return hidden, new

    def paged_decode(self, hidden, step, cache):
        return self._paged(self.self_attn.paged_decode, hidden, step, cache)

    def paged_chunk(self, hidden, step, cache):
        return self._paged(self.self_attn.paged_chunk, hidden, step, cache)


def use_pallas_kernels():
    """The rule every attention route over the pool follows: the Pallas
    kernels where the backend is a TPU (or ``FLAGS_pallas_force`` sends
    a CPU test through the interpreter), unless
    ``FLAGS_use_pallas_kernels`` is off."""
    from ..core.flags import get_flags

    flags = get_flags(["FLAGS_use_pallas_kernels", "FLAGS_pallas_force"])
    return flags["FLAGS_use_pallas_kernels"] and (
        jax.default_backend() == "tpu" or flags["FLAGS_pallas_force"])


def _paged_attn(q, kp, vp, tables, lens, ks=None, vs=None, scale=None):
    """Route decode attention: Pallas paged kernel on TPU (it takes the
    pool arrays as they are stored — no relayout on the way in — and
    DMAs only the live blocks of each row's table, every kv head of a
    block at once), the XLA gather (`_xla_paged_decode_attn`, the
    reference the parity tests compare with) elsewhere. Per-row scale
    pools (int8 engine) always take the XLA path: the Pallas kernel only
    supports STATIC per-head scales, not per-(block, position, head)
    pools."""
    if ks is None and use_pallas_kernels():
        from ..ops.pallas.paged_attention import paged_decode_attention

        return paged_decode_attention(q, kp, vp, tables, lens,
                                      sm_scale=scale)
    return _xla_paged_decode_attn(q, kp, vp, tables, lens, ks=ks, vs=vs,
                                  scale=scale)


_programs_counted = {}   # counter name -> the trace it last counted


def _count_traced_program(counter, path):
    """Raise ``counter{path}`` ONCE for the program being traced, however
    many layers ask: a route is static per compiled program."""
    trace = jax.core.get_opaque_trace_state()
    if _programs_counted.get(counter.name) != trace:
        _programs_counted[counter.name] = trace
        counter.inc(path=path)


def count_chunk_attention_program(path):
    """Raise ``serving_chunk_attention_programs_total{path}`` (``kernel``
    | ``xla``) on the process's registry once for the mixed program being
    traced, so a scrape says which path the mixed programs of this
    process were built with."""
    _count_traced_program(chunk_attention_programs(), path)


def count_latent_decode_program(path):
    """The same for the decode quantum of a latent model:
    ``serving_latent_decode_programs_total{path}``."""
    _count_traced_program(latent_decode_programs(), path)


def chunk_attention_programs():
    """The counter itself (an engine's registry shares it)."""
    from ..obs.registry import MetricsRegistry

    return MetricsRegistry.process().counter(
        "serving_chunk_attention_programs_total",
        "mixed-step programs traced, by the path their latent chunk "
        "attention takes (kernel | xla)")


def latent_decode_programs():
    """The counter itself (an engine's registry shares it)."""
    from ..obs.registry import MetricsRegistry

    return MetricsRegistry.process().counter(
        "serving_latent_decode_programs_total",
        "decode programs traced, by the path their attention over the "
        "latent pool takes (kernel | xla)")


def _pin_kv(arr):
    """Constrain one per-layer pool array to the head-sharded mesh
    layout (``P(None, None, 'mp', None)``) so GSPMD keeps the donated
    pool outputs on exactly the layout they arrived in — the in-place
    block write must never force a gather/reshard of the whole pool.
    Identity when no mesh is installed, ``mp == 1``, or the KV-head dim
    doesn't divide: the single-chip quantum graphs (and their golden
    fingerprints) are untouched byte-for-byte."""
    mp = mesh_state.mesh_axis_size("mp")
    if mp > 1 and arr.shape[2] % mp == 0:
        return mesh_state.constraint(arr, None, None, "mp", None)
    return arr


def _pin_kv_scale(arr):
    """`_pin_kv` for the (NB, BS, HK) scale pools of an int8 pool: the
    kv-head axis is the last one, so the constraint drops the trailing
    head-dim entry. Same identity conditions as `_pin_kv`."""
    mp = mesh_state.mesh_axis_size("mp")
    if mp > 1 and arr.shape[2] % mp == 0:
        return mesh_state.constraint(arr, None, None, "mp")
    return arr
