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

from ..ops.pallas._utils import count_traced_program, use_pallas_kernels
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


# the f32 score tile of `_xla_paged_chunk_attn` may take this many bytes; a
# chunk whose scores over its whole block table would take more streams
# over the table in tiles of key blocks (a shape rule: no knob)
_CHUNK_SCORE_BYTES = 256 << 20


def _paged_chunk_attn(q, kp, vp, tables, base_lens, ks=None, vs=None,
                      scale=None):
    """Chunk attention over the paged pool, shared by the speculative
    VERIFY pass and the mixed prefill step: query position j of each
    slot attends pool positions < base+j+1 (the row's cached context
    and the chunk's own positions up to j, which the caller has already
    written). q is (S, C, H, D); ``scale`` multiplies the scores
    (default ``1 / sqrt(D)``).

    The route: the Pallas kernel (``ops/pallas/chunk_attention.py``:
    the score tile and the running statistics stay in VMEM) where
    :func:`use_pallas_kernels` holds, the pool is a float pool and the
    head width is whole lanes; the XLA fold
    (:func:`_xla_paged_chunk_attn`, the reference the parity tests
    compare with) elsewhere — the CPU, the int8 engine's per-row scale
    pools (``ks`` / ``vs``). Which one a traced program took is
    ``serving_chunk_attention_programs_total{path}``."""
    from ..ops.pallas import chunk_attention as kernel

    if ks is None and use_pallas_kernels() and kernel.supports_gqa(q, kp):
        count_chunk_attention_program("kernel")
        return kernel.paged_chunk_attention(q, kp, vp, tables, base_lens,
                                            scale)
    count_chunk_attention_program("xla")
    return _xla_paged_chunk_attn(q, kp, vp, tables, base_lens, ks=ks,
                                 vs=vs, scale=scale)


def _xla_paged_chunk_attn(q, kp, vp, tables, base_lens, ks=None, vs=None,
                          scale=None):
    """:func:`_paged_chunk_attn` in plain XLA, on every backend.

    The query heads are grouped over their KV head (K and V are never
    repeated), operands keep the pool's dtype with f32 accumulation,
    and the softmax is f32 with the same -1e30 mask as the decode
    paths. The (S, H, C, keys) f32 scores are built for
    ``_CHUNK_SCORE_BYTES`` worth of key blocks at a time and folded
    into running (m, l, acc) statistics (an online softmax with a chunk
    dimension); a table whose scores fit that size is one tile and no
    loop. ``ks``/``vs`` are the
    int8 pool's per-row scale pools: a tile dequantizes in f32 as it
    streams through."""
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


# -- a window layer's keys: a per-slot RING on the pool's slot side ----------
# A layer that attends only the last W positions keeps, per slot, a K and a V
# array (slots, R, HK x D): position p of the request in slot s lives at row
# p mod R, its KV heads side by side. R >= W + the longest chunk
# (`ring_tokens`), so a chunk may write all its positions before it attends
# and no key a valid query needs is overwritten. What a row may see is
# decided by positions alone, so a reused slot needs no zeroing and nothing
# is reset from the host.
#
# Why the heads lie side by side in ONE minor axis: a scatter wants the row
# it writes minor, a product batched over KV heads wants the head axis
# outside the rows, and with a head axis of its own, on either side of the
# rows, the TPU compiler copied every ring whole into the other order and
# back, each layer and decode step (PERF.md section 6, PR 35). The decode
# product therefore contracts over ALL of a row (`_per_kv_head`): a plain
# batched matrix product over the ring as it is stored.


def ring_tokens(window, prefill_chunk, block_size):
    """Rows of a window layer's ring: the ``window - 1`` keys before a
    chunk and the chunk's own ``prefill_chunk``, in whole blocks."""
    return -(-(int(window) + int(prefill_chunk)) // int(block_size)) \
        * int(block_size)


def ring_write(ring_k, ring_v, k, v, pos, ok):
    """Write rows ``k`` / ``v`` (S, C, HK, D) of positions ``pos`` (S, C)
    into each slot's ring at ``pos mod R``; a position whose ``ok`` is
    false (past a row's count, a row that is not live, an idle slot)
    writes nothing: its index lies outside the ring and the scatter drops
    it."""
    r = ring_k.shape[1]
    row = jnp.where(ok, pos % r, r)
    slot = jnp.arange(ring_k.shape[0])[:, None]
    flat = (*k.shape[:2], ring_k.shape[2])
    return (ring_k.at[slot, row].set(
                k.reshape(flat).astype(ring_k.dtype), mode="drop"),
            ring_v.at[slot, row].set(
                v.reshape(flat).astype(ring_v.dtype), mode="drop"))


def _ring_positions(top, r, lo=0, n=None):
    """The position each ring row ``lo .. lo + n - 1`` holds when the last
    position written is ``top`` (S,): the largest ``p <= top`` with ``p mod
    R == row``; negative where the request has not come that far (the row
    then holds what an earlier request left, which no one may see)."""
    rows = lo + jnp.arange(r if n is None else n)
    return top[:, None] - (top[:, None] - rows[None, :]) % r


def _per_kv_head(q, hk):
    """q (S, H, D), query heads grouped over ``hk`` KV heads -> (S, H, HK x
    D): each query head's values in its own KV head's place of a ring row
    and zeros in the others', so that a product over the whole row is the
    head's own score."""
    s_, h, d = q.shape
    own = jnp.eye(hk, dtype=q.dtype)[:, None, :, None]       # (HK,1,HK,1)
    return (q.reshape(s_, hk, h // hk, 1, d) * own).reshape(s_, h, hk * d)


def ring_decode_attn(q, ring_k, ring_v, lens, window, scale=None):
    """One query a slot over its ring: the row of length ``lens`` (with
    this token, already written) attends positions ``lens - window <= s <
    lens``. q (S, H, D). Plain XLA over the whole ring as it is stored,
    with a mask by position; the (S, H, R) float32 scores are small. The
    products run over whole ring rows (HK x the operations a head needs,
    which one query a slot makes cheap) so that no head axis has to be
    brought outside the rows."""
    s_, h, d = q.shape
    r, hk = ring_k.shape[1], ring_k.shape[2] // d
    sc = 1.0 / math.sqrt(d) if scale is None else scale
    pos = _ring_positions(lens - 1, r)                       # (S, R)
    seen = (pos >= 0) & (pos > lens[:, None] - 1 - window)
    ct = jnp.promote_types(q.dtype, ring_k.dtype)
    logits = jnp.einsum("bhc,bkc->bhk", _per_kv_head(q, hk).astype(ct),
                        ring_k.astype(ct),
                        preferred_element_type=jnp.float32) * sc
    logits = jnp.where(seen[:, None], logits, jnp.float32(-1e30))
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkc->bhc", p.astype(ct), ring_v.astype(ct),
                     preferred_element_type=jnp.float32)     # (S, H, HK x D)
    # each query head keeps its own KV head's D values
    out = out.reshape(s_, hk, h // hk, hk, d)
    own = jnp.arange(hk)[None, :, None, None, None]
    out = jnp.take_along_axis(out, own, axis=3)[:, :, :, 0]
    return out.reshape(s_, h, d).astype(q.dtype)


def ring_chunk_attn(q, ring_k, ring_v, base_lens, counts, window,
                    scale=None):
    """C queries a slot over its ring, the chunk's own positions already
    written (`ring_write`): query j of a row with base ``b`` attends
    positions ``b + j - window < s <= b + j``. q (S, C, H, D); ``counts``
    (S,) each row's valid positions (the ring holds positions up to ``b +
    counts - 1``; a query past the count reads nothing anyone keeps).

    The route is :func:`_paged_chunk_attn`'s: the same Pallas kernel with
    ``window`` as its lower bound, over the ring as it is stored, or the
    XLA fold :func:`_xla_ring_chunk_attn`."""
    from ..ops.pallas import chunk_attention as kernel

    if use_pallas_kernels() and kernel.supports_gqa(q, ring_k):
        count_chunk_attention_program("kernel")
        return kernel.ring_chunk_attention(q, ring_k, ring_v, base_lens,
                                           counts, window, scale)
    count_chunk_attention_program("xla")
    return _xla_ring_chunk_attn(q, ring_k, ring_v, base_lens, counts,
                                window, scale=scale)


def _xla_ring_chunk_attn(q, ring_k, ring_v, base_lens, counts, window,
                         scale=None):
    """:func:`ring_chunk_attn` in plain XLA, on every backend.

    The same fold as :func:`_xla_paged_chunk_attn`: the (S, H, C, keys) float32
    scores are built for ``_CHUNK_SCORE_BYTES`` worth of ring rows at a
    time and folded into running (m, l, acc); query heads are grouped over
    their KV head and operands keep the ring's dtype. A tile of rows is
    given its head axis as it is sliced out (a tile-sized copy)."""
    s_, c, h, d = q.shape
    r, hk = ring_k.shape[1], ring_k.shape[2] // d
    g = h // hk
    sc = 1.0 / math.sqrt(d) if scale is None else scale
    tile = max(1, min(r, _CHUNK_SCORE_BYTES // (s_ * h * c * 4)))
    while r % tile:          # whole tiles of ring rows
        tile -= 1
    n_tiles = r // tile
    qpos = base_lens[:, None] + jnp.arange(c)[None, :]       # (S, C)
    top = base_lens + counts - 1
    neg = jnp.float32(-1e30)
    qg = q.reshape(s_, c, hk, g, d)
    ct = jnp.promote_types(q.dtype, ring_k.dtype)

    def fold(carry, ti):
        m, l, acc = carry
        k = jax.lax.dynamic_slice_in_dim(ring_k, ti * tile, tile, 1)
        v = jax.lax.dynamic_slice_in_dim(ring_v, ti * tile, tile, 1)
        logits = jnp.einsum(
            "bchgd,bkhd->bhgck", qg.astype(ct),
            k.reshape(s_, tile, hk, d).astype(ct),
            preferred_element_type=jnp.float32) * sc         # (S,HK,G,C,K)
        kpos = _ring_positions(top, r, ti * tile, tile)[:, None, :]
        seen = ((kpos >= 0) & (kpos <= qpos[:, :, None])
                & (kpos > qpos[:, :, None] - window))        # (S, C, K)
        logits = jnp.where(seen[:, None, None], logits, neg)
        m2 = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m2)                              # (S, HK, G, C)
        # a tile may hold no key a query sees (m still at its init): its
        # exp(neg - neg) must not count
        p = jnp.where(seen[:, None, None], jnp.exp(logits - m2[..., None]),
                      0.0)
        l2 = l * alpha + jnp.sum(p, axis=-1)
        acc2 = acc * alpha[..., None] + jnp.einsum(
            "bhgck,bkhd->bhgcd", p.astype(ct),
            v.reshape(s_, tile, hk, d).astype(ct),
            preferred_element_type=jnp.float32)
        return (m2, l2, acc2), None

    carry = (jnp.full((s_, hk, g, c), neg, jnp.float32),
             jnp.zeros((s_, hk, g, c), jnp.float32),
             jnp.zeros((s_, hk, g, c, d), jnp.float32))
    if n_tiles == 1:
        carry, _ = fold(carry, 0)
    else:
        carry, _ = jax.lax.scan(fold, carry, jnp.arange(n_tiles))
    _, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]             # (S,HK,G,C,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(s_, c, h, d).astype(
        q.dtype)


def normed(norm, hidden, row_axis=None):
    """A layer-level norm under the profiler scope every family gives
    it (``norm``); ``row_axis``: the mesh axis ``hidden``'s (B, S, E)
    sequence is split over (``RMSNorm.forward``)."""
    with jax.named_scope("norm"):
        return norm(hidden) if row_axis is None else norm(
            hidden, row_axis=row_axis)


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
            normed(self.input_layernorm, hidden), step["rope"],
            step["tables"], step["lens"], step["write_blk"],
            step["write_off"], cache)
        hidden = hidden + att
        hidden = hidden + self.mlp(
            normed(self.post_attention_layernorm, hidden))
        return hidden, new

    def paged_decode(self, hidden, step, cache):
        return self._paged(self.self_attn.paged_decode, hidden, step, cache)

    def paged_chunk(self, hidden, step, cache):
        return self._paged(self.self_attn.paged_chunk, hidden, step, cache)


def sigmoid_gated_out(o_proj, att, gate):
    """``(concat_heads(att) * sigmoid(gate)) W_o``: the gate on an
    attention's output (``nlp/afmoe.py``, ``nlp/solar_open2.py``); ``att``
    and ``gate`` raw arrays, the gate's pre-activation (..., H x D)."""
    from ..core.tensor import Tensor

    with jax.named_scope("attn.gate"):
        att = att.reshape(gate.shape)
        att = (att.astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(gate.dtype)
    with jax.named_scope("attn.proj"):
        return o_proj(Tensor(att, stop_gradient=True))


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


def count_chunk_attention_program(path):
    """Raise ``serving_chunk_attention_programs_total{path}`` (``kernel``
    | ``xla``) on the process's registry once for the mixed program being
    traced, so a scrape says which path the mixed programs of this
    process were built with."""
    count_traced_program(chunk_attention_programs(), path)


def count_latent_decode_program(path):
    """The same for the decode quantum of a latent model:
    ``serving_latent_decode_programs_total{path}``."""
    count_traced_program(latent_decode_programs(), path)


def chunk_attention_programs():
    """The counter itself (an engine's registry shares it)."""
    from ..obs.registry import MetricsRegistry

    return MetricsRegistry.process().counter(
        "serving_chunk_attention_programs_total",
        "mixed-step programs traced, by the path their chunk attention "
        "(over the latent pool, a K/V table or a window ring) takes "
        "(kernel | xla)")


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
