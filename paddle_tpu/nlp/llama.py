"""Llama-family model — the north-star workload (BASELINE.md config #3:
Llama-2-7B, Fleet hybrid TP×PP×sharding-3, ≥45% MFU target).

TPU-first design notes:
- Attention routes through F.scaled_dot_product_attention → the Pallas
  flash kernel on TPU (GQA consumed natively via the kernel's KV-head
  index map, no repeat materialisation).
- RMSNorm routes to the Pallas rms_norm kernel; rotary embedding is the
  fused_rope functional (pure-XLA elementwise, fused by the compiler).
- Tensor parallelism is the fleet mp-layer tier: Column/RowParallelLinear
  and VocabParallelEmbedding place weights with NamedShardings over the
  ``mp`` mesh axis and GSPMD inserts the collectives — no explicit
  all-reduce calls anywhere in the model.
- Sequence parallelism marks hidden states sharded over ``sep`` between
  the attention blocks; activations inside attention gather via the same
  GSPMD propagation.
- Training's forward on a mesh with ``mp`` > 1 keeps the hidden stream, its
  residual adds and its norms split over ``mp`` along the sequence
  (``_stream_split``): a tensor-parallel half-layer all-gathers it into its
  column-parallel group and reduce-scatters it out of its row-parallel
  product (``mp_layers.column_parallel_group`` / ``row_parallel_scatter``).
- With no mesh installed every class degrades to plain serial layers, so
  the same model file serves the single-chip and multi-chip paths.
"""
from __future__ import annotations

import contextlib
import math

from jax import named_scope

from ..nn.layer.layers import Layer
from ..nn.layer.common import Linear, Embedding
from ..nn.layer.norm import RMSNorm
from ..nn import functional as F
from ..nn.functional.rope import build_rope_cache, apply_rotary_emb
from ..tensor._helpers import apply, ensure_tensor
from ..parallel import mesh as mesh_state
from ..distributed.fleet.layers.mpu.mp_layers import (
    column_parallel_group, hidden_stream_axis, row_parallel_scatter,
)
from .paged_attention import PagedResidualLayer, normed

__all__ = [
    "LlamaConfig", "LlamaAttention", "LlamaMLP", "LlamaDecoderLayer",
    "LlamaModel", "LlamaForCausalLM", "LlamaPretrainingCriterion",
]


class LlamaConfig:
    """Configuration (mirrors the HF/PaddleNLP llama config fields that
    matter for pretraining)."""

    def __init__(self, vocab_size=32000, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=None,
                 max_position_embeddings=4096, rms_norm_eps=1e-6,
                 rope_theta=10000.0, tie_word_embeddings=False,
                 tensor_parallel=True, sequence_parallel=False,
                 context_parallel=None, use_recompute=False,
                 recompute_granularity="full", dtype="float32",
                 fuse_linear_cross_entropy=False, lce_chunk_rows=1024,
                 sliding_window=None, attention_bias=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.tie_word_embeddings = tie_word_embeddings
        self.tensor_parallel = tensor_parallel
        self.sequence_parallel = sequence_parallel
        # context parallelism over the sep axis: None | "ring" | "ulysses"
        self.context_parallel = context_parallel
        self.use_recompute = use_recompute
        self.recompute_granularity = recompute_granularity
        self.dtype = dtype
        # training-loss fusion: forward() returns the final hidden states
        # (no lm_head matmul) and LlamaPretrainingCriterion applies the
        # chunked fused lm-head+CE — full (N, V) logits never exist;
        # lce_chunk_rows is its scan-chunk size (peak logits bytes =
        # chunk_rows * vocab * 4)
        self.fuse_linear_cross_entropy = fuse_linear_cross_entropy
        self.lce_chunk_rows = lce_chunk_rows
        # causal sliding-window attention (Mistral semantics): each
        # query attends to the last `sliding_window` tokens. Training
        # and prefill use the banded flash kernel; decode runs against
        # a ROLLING KV buffer of window length (init_caches clamps).
        # Packed cu_seqlens applies the band per segment; chunked
        # prefill (cache, offset>0, s>1) and context_parallel raise.
        self.sliding_window = sliding_window
        # Qwen2-style: q/k/v projections carry biases (o_proj does not)
        self.attention_bias = attention_bias

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def llama2_7b(**overrides):
        cfg = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                   num_hidden_layers=32, num_attention_heads=32,
                   max_position_embeddings=4096)
        cfg.update(overrides)
        return LlamaConfig(**cfg)

    @staticmethod
    def tiny(**overrides):
        """Test-scale config used by the CI suite and the multichip dryrun."""
        cfg = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                   num_hidden_layers=2, num_attention_heads=4,
                   num_key_value_heads=2, max_position_embeddings=256)
        cfg.update(overrides)
        return LlamaConfig(**cfg)

    @staticmethod
    def mistral_7b(**overrides):
        """Mistral-7B shape: GQA 32/8 + sliding-window 4096 on the
        same decoder stack (the architectures differ only in config)."""
        cfg = dict(vocab_size=32000, hidden_size=4096,
                   intermediate_size=14336, num_hidden_layers=32,
                   num_attention_heads=32, num_key_value_heads=8,
                   max_position_embeddings=32768, rope_theta=10000.0,
                   sliding_window=4096)
        cfg.update(overrides)
        return LlamaConfig(**cfg)

    @staticmethod
    def qwen2_7b(**overrides):
        """Qwen2-7B shape: GQA 28/4 with q/k/v biases
        (attention_bias) on the same decoder stack."""
        cfg = dict(vocab_size=152064, hidden_size=3584,
                   intermediate_size=18944, num_hidden_layers=28,
                   num_attention_heads=28, num_key_value_heads=4,
                   max_position_embeddings=32768, rope_theta=1000000.0,
                   attention_bias=True)
        cfg.update(overrides)
        return LlamaConfig(**cfg)


def _use_mp(config):
    # The fleet mp layers degrade to plain serial layers when no mesh is
    # installed, so gating on the config alone keeps initialization (and
    # the parallel==serial oracle) identical across runs.
    return config.tensor_parallel


def _sep_axis(config):
    return "sep" if (
        (config.sequence_parallel or config.context_parallel)
        and mesh_state.mesh_axis_size("sep") > 1) else None


# The mesh axis the forward being traced splits its hidden stream's
# sequence over, as a 1-tuple (None inside: the stream whole on every
# member); None while no forward runs. Set by ``_stream_split`` alone.
_STREAM = None


def _stream_axis():
    return _STREAM[0] if _STREAM else None


@contextlib.contextmanager
def _stream_split(model, seq_len, caches, head=None):
    """The scope of ONE forward of ``model`` (a ``LlamaModel``, and the
    ``head`` after it) over ``seq_len`` positions: its hidden stream split
    over ``mp`` along the sequence where ``hidden_stream_axis`` allows,
    else as it was. The outermost forward decides, once: inside
    ``LlamaForCausalLM``'s scope ``LlamaModel``'s own is a no-op."""
    global _STREAM
    if _STREAM is not None:
        yield
        return
    tp = [m for layer in model.layers for m in (
        layer.self_attn.q_proj, layer.self_attn.k_proj,
        layer.self_attn.v_proj, layer.self_attn.o_proj,
        layer.mlp.gate_proj, layer.mlp.up_proj, layer.mlp.down_proj)]
    if head is not None:
        tp.append(head)
    _STREAM = (hidden_stream_axis(
        seq_len, tp, cached=caches is not None,
        seq_taken=_sep_axis(model.config) is not None),)
    try:
        yield
    finally:
        _STREAM = None


def _normed(norm, hidden):
    """``normed`` on each member's own rows of the stream."""
    return normed(norm, hidden, row_axis=_stream_axis())


def _mark_hidden(t, config):
    """Constrain hidden states (B, S, E): batch over the data axes (dp,
    and sharding as fsdp data axis), seq over sep when sequence-parallel,
    or over mp where the forward keeps the stream split (_stream_split)."""
    if not mesh_state.has_mesh():
        return t
    seq_axis = _sep_axis(config) or _stream_axis()

    def fn(v):
        return mesh_state.constraint(
            v, mesh_state.data_axes(v.shape[0]), seq_axis, None)

    return apply(fn, ensure_tensor(t), op_name="hidden_constraint")


def _paged_write(k, v, write_blk, write_off, cache):
    """Write a step's rotated K rows and V rows (..., HK, D) into a
    layer's pool arrays ``cache`` = ``(k, v, k_scale, v_scale)`` at
    ``(write_blk, write_off)``; on an int8 pool (scales not None) each
    row is quantized at its write site. Returns the new four."""
    from ..nn.quant import quantize_kv_rows
    from .paged_attention import _pin_kv, _pin_kv_scale

    kc, vc, ks, vs = cache
    ksi = vsi = None
    if ks is not None:
        k, k_sc = quantize_kv_rows(k)            # (..., HK, D)/(..., HK)
        v, v_sc = quantize_kv_rows(v)
        ksi = _pin_kv_scale(ks.at[write_blk, write_off].set(k_sc))
        vsi = _pin_kv_scale(vs.at[write_blk, write_off].set(v_sc))
    kci = _pin_kv(kc.at[write_blk, write_off].set(k.astype(kc.dtype)))
    vci = _pin_kv(vc.at[write_blk, write_off].set(v.astype(vc.dtype)))
    return kci, vci, ksi, vsi


class LlamaAttention(Layer):
    """Self-attention with rotary embedding, GQA, and optional KV cache.

    Reference shape: PaddleNLP LlamaAttention; the fused inference analog
    is fused_multi_transformer (SURVEY.md §2.5) — here the train path uses
    the Pallas flash kernel and the decode path the Pallas decode kernel.
    """

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, hk, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
        self.num_heads, self.num_kv_heads, self.head_dim = h, hk, d
        qkv_bias = bool(getattr(config, "attention_bias", False))
        if _use_mp(config):
            from ..distributed.fleet.layers.mpu.mp_layers import (
                ColumnParallelLinear, RowParallelLinear,
            )

            self.q_proj = ColumnParallelLinear(
                config.hidden_size, h * d, has_bias=qkv_bias,
                gather_output=False)
            self.k_proj = ColumnParallelLinear(
                config.hidden_size, hk * d, has_bias=qkv_bias,
                gather_output=False)
            self.v_proj = ColumnParallelLinear(
                config.hidden_size, hk * d, has_bias=qkv_bias,
                gather_output=False)
            self.o_proj = RowParallelLinear(
                h * d, config.hidden_size, has_bias=False,
                input_is_parallel=True)
        else:
            self.q_proj = Linear(config.hidden_size, h * d,
                                 bias_attr=qkv_bias or False)
            self.k_proj = Linear(config.hidden_size, hk * d,
                                 bias_attr=qkv_bias or False)
            self.v_proj = Linear(config.hidden_size, hk * d,
                                 bias_attr=qkv_bias or False)
            self.o_proj = Linear(h * d, config.hidden_size, bias_attr=False)

    def forward(self, hidden, position_offset=0, cache=None,
                cu_seqlens=None, position_ids=None):
        b, s, _ = hidden.shape
        with named_scope("attn.proj"):
            q, k, v = self._rotated_qkv(hidden, position_offset,
                                           position_ids)
        with named_scope("attn.window" if self.config.sliding_window
                         else "attn.full"):
            out, cache = self._attend(q, k, v, position_offset, cache,
                                      cu_seqlens)
        out = out.reshape([b, s, self.num_heads * self.head_dim])
        axis = _stream_axis()
        with named_scope("attn.proj"):
            return (self.o_proj(out) if axis is None else
                    row_parallel_scatter(out, self.o_proj, axis)), cache

    def _rotated_qkv(self, hidden, position_offset, position_ids):
        """q, k, v (B, S, heads, D), q and k rotated at their positions."""
        b, s, _ = hidden.shape

        def heads(t, n):
            return t.reshape([b, s, n, self.head_dim])

        h, hk = self.num_heads, self.num_kv_heads
        axis = _stream_axis()
        if axis is None:
            q = heads(self.q_proj(hidden), h)
            k = heads(self.k_proj(hidden), hk)
            v = heads(self.v_proj(hidden), hk)
        else:
            q, k, v = map(heads, column_parallel_group(
                hidden, (self.q_proj, self.k_proj, self.v_proj), axis),
                (h, hk, hk))

        cos, sin = build_rope_cache(
            s, self.head_dim, base=self.config.rope_theta,
            position_offset=position_offset,
        )
        if position_ids is not None:
            # packed-varlen training: rotary positions restart at every
            # segment boundary (position_ids precomputed from cu_seqlens)
            q = apply(lambda t, pid: apply_rotary_emb(
                t, cos, sin, position_ids=pid), q, position_ids,
                op_name="rope_q")
            k = apply(lambda t, pid: apply_rotary_emb(
                t, cos, sin, position_ids=pid), k, position_ids,
                op_name="rope_k")
        else:
            q = apply(lambda t: apply_rotary_emb(t, cos, sin), q,
                      op_name="rope_q")
            k = apply(lambda t: apply_rotary_emb(t, cos, sin), k,
                      op_name="rope_k")
        return q, k, v

    def _attend(self, q, k, v, position_offset, cache, cu_seqlens):
        """The attention itself, by what the call brings: packed ragged
        sequences, a dense cache, a window, context parallelism, or
        plain causal attention. Returns (B, S, H, D) and the cache."""
        b, s = q.shape[0], q.shape[1]
        if cu_seqlens is not None:
            # packed ragged sequences, (B=1, T) layout: the Pallas varlen
            # kernel skips dead cross-segment tiles AND their KV DMA
            # (ops/pallas/varlen_flash_attention.py); sliding-window
            # models apply the band PER SEGMENT (round 5)
            t = b * s
            out, _ = F.flash_attn_unpadded(
                q.reshape([t, self.num_heads, self.head_dim]),
                k.reshape([t, self.num_kv_heads, self.head_dim]),
                v.reshape([t, self.num_kv_heads, self.head_dim]),
                cu_seqlens, cu_seqlens, s, s,
                scale=1.0 / math.sqrt(self.head_dim), causal=True,
                window_size=self.config.sliding_window or None)
            out = out.reshape([b, s, self.num_heads, self.head_dim])
        elif cache is not None:
            # incremental decode: cache is (k_cache, v_cache) Tensors laid
            # out (B, S_max, HK, D) with valid length = position_offset + s.
            # Sliding-window models use the cache as a ROLLING buffer of
            # length min(S_max, window): writes wrap (position % len) and
            # attention covers the live slots — softmax is permutation-
            # invariant over keys, so the wrapped order needs no
            # unwrapping (allocate via init_caches, which clamps).
            if self.config.sliding_window and s > 1:
                # windowed prefill: attend the CALL'S OWN keys with the
                # dense banded kernel (every query's band lies inside
                # this chunk when offset==0); the rolling buffer is
                # storage for the subsequent decode steps. Chunked
                # prefill (offset>0) would need evicted keys back.
                if position_offset != 0:
                    raise NotImplementedError(
                        "sliding_window + chunked prefill (cache with "
                        "position_offset>0 and s>1) is not supported; "
                        "prefill in one chunk, then decode token by "
                        "token")
                _, _, cache = self._update_cache(k, v, cache,
                                                 position_offset)
                out = F.sliding_window_attention(
                    q, k, v, self.config.sliding_window)
            else:
                k, v, cache = self._update_cache(k, v, cache,
                                                 position_offset)
                out = self._decode_attend(q, k, v, position_offset + s)
        elif self.config.sliding_window:
            if (self.config.context_parallel
                    and mesh_state.mesh_axis_size("sep") > 1):
                raise NotImplementedError(
                    "sliding_window + context_parallel is not composed "
                    "yet (shard-local bands would drop cross-shard "
                    "in-window keys); disable one of the two")
            out = F.sliding_window_attention(
                q, k, v, self.config.sliding_window)
        elif (self.config.context_parallel
              and mesh_state.mesh_axis_size("sep") > 1):
            from ..distributed.fleet.meta_parallel.context_parallel import (
                sep_attention,
            )

            out = sep_attention(
                q, k, v, is_causal=True,
                schedule=self.config.context_parallel,
            )
        else:
            out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return out, cache

    # -- the attention half of the serving engine's layer protocol --------
    # (serving/engine.py: paged_decode_math / paged_chunk_math keep the
    # layer loop and ask each LAYER for the rest; a decoder layer with
    # K/V attention hands its normed input on to these)
    # what multiplies the scores over the pool; None is 1 / sqrt(D)
    softmax_scale = None

    def _rotate(self, x, rope):
        """Rotary embedding of (..., H, D) rows at their own positions
        (a model without positions overrides this with the identity)."""
        from .paged_attention import _rope_rows

        return _rope_rows(x, *rope)

    def _project_out(self, att, x):
        """The attention output (..., H x D) -> the layer's output; ``x``
        is the normed input the heads were projected from (a model that
        gates the output by it overrides this: ``nlp/solar_open2.py``)."""
        return self.o_proj(att)

    def paged_rope(self, positions):
        """What the rotary embedding needs at ``positions`` (float32, any
        shape): ``(cos, sin)`` with a trailing D/2. The engine's bodies
        ask the first layer once and hand the result to every layer."""
        import jax.numpy as jnp

        d = self.head_dim
        inv_freq = 1.0 / (self.config.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = positions[..., None] * inv_freq
        return jnp.cos(freqs), jnp.sin(freqs)

    def paged_decode(self, x, rope, tables, lens, write_blk, write_off,
                     cache):
        """One token a slot over the paged pool. ``x`` (S, 1, E) is the
        normed input; ``cache`` this layer's pool arrays ``(k, v,
        k_scale, v_scale)`` (the scales None on a float pool). Writes
        the step's K/V row at ``(write_blk, write_off)``, attends the
        ``lens`` live positions of each row's ``tables`` and returns the
        attention output (S, 1, E) and the layer's new pool arrays."""
        from ..core.tensor import Tensor
        from .paged_attention import _paged_attn

        s = x.shape[0]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        with named_scope("attn.proj"):
            q = self.q_proj(x).reshape([s, 1, h, d])
            k = self.k_proj(x).reshape([s, 1, hk, d])
            v = self.v_proj(x).reshape([s, 1, hk, d])
            qv = self._rotate(q._value[:, 0], rope)      # (S, H, D)
            kv = self._rotate(k._value[:, 0], rope)
            vv = v._value[:, 0]
        with named_scope("cache.write"):
            kci, vci, ksi, vsi = new = _paged_write(kv, vv, write_blk,
                                                    write_off, cache)
        with named_scope("attn.full"):
            att = _paged_attn(qv, kci, vci, tables, lens, ks=ksi, vs=vsi,
                              scale=self.softmax_scale)
        att_t = Tensor(att.reshape(s, 1, h * d), stop_gradient=True)
        with named_scope("attn.proj"):
            return self._project_out(att_t, x), new

    def paged_chunk(self, x, rope, tables, base_lens, write_blk,
                    write_off, cache):
        """C tokens a slot over the paged pool (the mixed prefill step
        and the speculative verify): position j of a row writes at
        ``(write_blk, write_off)[:, j]`` and attends the row's
        ``base_lens`` cached positions and the chunk's own up to j.
        Same contract as :meth:`paged_decode` with a chunk axis."""
        from ..core.tensor import Tensor
        from .paged_attention import _paged_chunk_attn

        s, c = x.shape[0], x.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        with named_scope("attn.proj"):
            q = self.q_proj(x).reshape([s, c, h, d])
            k = self.k_proj(x).reshape([s, c, hk, d])
            v = self.v_proj(x).reshape([s, c, hk, d])
            qv = self._rotate(q._value, rope)            # (S, C, H, D)
            kv = self._rotate(k._value, rope)
            vv = v._value
        with named_scope("cache.write"):
            kci, vci, ksi, vsi = new = _paged_write(kv, vv, write_blk,
                                                    write_off, cache)
        with named_scope("attn.full"):
            att = _paged_chunk_attn(qv, kci, vci, tables, base_lens,
                                    ks=ksi, vs=vsi,
                                    scale=self.softmax_scale)
        att_t = Tensor(att.reshape(s, c, h * d), stop_gradient=True)
        with named_scope("attn.proj"):
            return self._project_out(att_t, x), new

    def forward_no_cache(self, hidden, position_offset=0,
                         cu_seqlens=None, position_ids=None):
        """Single-output variant for the remat wrapper (core_attn)."""
        out, _ = self.forward(hidden, position_offset, None,
                              cu_seqlens, position_ids)
        return out

    def _update_cache(self, k, v, cache, position_offset):
        import jax
        import jax.numpy as jnp

        kc = ensure_tensor(cache[0])
        vc = ensure_tensor(cache[1])
        cache_len = int(kc.shape[1])
        s = int(k.shape[1])
        if s > cache_len and not self.config.sliding_window:
            # a non-windowed model overflowing its cache has no valid
            # semantics — wrap-writes would permute slots the slot-index
            # causal mask then misreads (silent causality violation)
            raise ValueError(
                f"KV cache length {cache_len} < {s} tokens written; "
                f"allocate init_caches(max_len >= prompt + new tokens)")
        if self.config.sliding_window:
            # rolling buffer: wrap writes; if this call alone overflows
            # the buffer only its LAST cache_len tokens matter (scatter
            # with duplicate slots has no write order to rely on)
            if s > cache_len:
                k = k[:, s - cache_len:]
                v = v[:, s - cache_len:]
                position_offset = position_offset + (s - cache_len)
                s = cache_len

            def upd(c, n):
                idx = (position_offset + jnp.arange(s)) % cache_len
                return c.at[:, idx].set(n.astype(c.dtype))

            new_kc = apply(upd, kc, k, op_name="kv_cache_update")
            new_vc = apply(upd, vc, v, op_name="kv_cache_update")
            return new_kc, new_vc, (new_kc, new_vc)
        new_kc = apply(lambda c, n: jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), position_offset, axis=1), kc, k,
            op_name="kv_cache_update")
        new_vc = apply(lambda c, n: jax.lax.dynamic_update_slice_in_dim(
            c, n.astype(c.dtype), position_offset, axis=1), vc, v,
            op_name="kv_cache_update")
        return new_kc, new_vc, (new_kc, new_vc)

    def _decode_attend(self, q, k_cache, v_cache, valid_len):
        """Single-step (or short-suffix) attention over the cache.
        ``valid_len`` counts ABSOLUTE tokens so far; with a rolling
        (sliding-window) buffer only ``min(valid_len, cache_len)`` slots
        are live, and multi-token suffixes mask by each slot's
        reconstructed absolute position."""
        import jax
        import jax.numpy as jnp

        windowed = bool(self.config.sliding_window)

        def fn(qv, kc, vc):
            b = qv.shape[0]
            cache_len = kc.shape[1]
            live = min(valid_len, cache_len) if windowed else valid_len
            pallas_ok = (not windowed
                         or cache_len <= int(self.config.sliding_window))
            if qv.shape[1] == 1 and jax.default_backend() == "tpu" \
                    and pallas_ok:
                from ..ops.pallas.decode_attention import decode_attention

                # single query: it attends every live slot (the window
                # IS the buffer — cache_len <= window checked above),
                # wrapped order irrelevant to softmax
                lens = jnp.full((b,), live, jnp.int32)
                return decode_attention(qv, kc, vc, lens)
            rep = qv.shape[2] // kc.shape[2]
            kr = jnp.repeat(kc, rep, axis=2) if rep > 1 else kc
            vr = jnp.repeat(vc, rep, axis=2) if rep > 1 else vc
            sq, sk = qv.shape[1], kr.shape[1]
            sc = 1.0 / math.sqrt(qv.shape[-1])
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", qv.astype(jnp.float32),
                kr.astype(jnp.float32)) * sc
            q_pos = valid_len - sq + jnp.arange(sq)  # absolute
            k_slot = jnp.arange(sk)
            if windowed:
                # slot j holds absolute position a(j) = the largest
                # p < valid_len with p % cache_len == j
                a = valid_len - 1 - ((valid_len - 1 - k_slot) % sk)
                w = int(self.config.sliding_window)
                mask = (a[None, :] <= q_pos[:, None]) \
                    & (a[None, :] > q_pos[:, None] - w) \
                    & (a[None, :] >= 0)
            else:
                mask = k_slot[None, :] <= q_pos[:, None]
            logits = jnp.where(mask[None, None], logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p, vr.astype(jnp.float32))
            return out.astype(qv.dtype)

        return apply(fn, q, k_cache, v_cache, op_name="decode_attention")


class LlamaMLP(Layer):
    """SwiGLU MLP: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        if _use_mp(config):
            from ..distributed.fleet.layers.mpu.mp_layers import (
                ColumnParallelLinear, RowParallelLinear,
            )

            self.gate_proj = ColumnParallelLinear(
                config.hidden_size, config.intermediate_size, has_bias=False,
                gather_output=False)
            self.up_proj = ColumnParallelLinear(
                config.hidden_size, config.intermediate_size, has_bias=False,
                gather_output=False)
            self.down_proj = RowParallelLinear(
                config.intermediate_size, config.hidden_size, has_bias=False,
                input_is_parallel=True)
        else:
            self.gate_proj = Linear(
                config.hidden_size, config.intermediate_size, bias_attr=False)
            self.up_proj = Linear(
                config.hidden_size, config.intermediate_size, bias_attr=False)
            self.down_proj = Linear(
                config.intermediate_size, config.hidden_size, bias_attr=False)

    def forward(self, x):
        with named_scope("mlp"):
            axis = _stream_axis()
            if axis is None:
                return self.down_proj(
                    F.silu(self.gate_proj(x)) * self.up_proj(x))
            gate, up = column_parallel_group(
                x, (self.gate_proj, self.up_proj), axis)
            return row_parallel_scatter(
                F.silu(gate) * up, self.down_proj, axis)


class LlamaDecoderLayer(PagedResidualLayer, Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, hidden, position_offset=0, cache=None,
                cu_seqlens=None, position_ids=None):
        residual = hidden
        # PaddleNLP-parity granularities: full_attn/core_attn remat only
        # the attention sublayer (its softmax/score intermediates), which
        # keeps the MLP activations resident
        attn_remat = (self.config.use_recompute and cache is None
                      and self.config.recompute_granularity
                      in ("full_attn", "core_attn"))
        if attn_remat:
            from ..distributed.fleet.utils.recompute import recompute

            # bound method of the attention Layer: recompute() registers
            # its params as differentiable inputs (a bare closure would
            # silently freeze q/k/v/o in eager training)
            attn_out = recompute(
                self.self_attn.forward_no_cache,
                _normed(self.input_layernorm, hidden),
                position_offset, cu_seqlens, position_ids,
            )
        else:
            attn_out, cache = self.self_attn(
                _normed(self.input_layernorm, hidden),
                position_offset, cache, cu_seqlens, position_ids)
        hidden = residual + attn_out
        hidden = _mark_hidden(hidden, self.config)
        hidden = hidden + self.mlp(
            _normed(self.post_attention_layernorm, hidden))
        hidden = _mark_hidden(hidden, self.config)
        return hidden, cache

    def forward_no_cache(self, hidden, position_offset=0,
                         cu_seqlens=None, position_ids=None):
        """Single-output variant for the recompute (remat) wrapper."""
        out, _ = self.forward(hidden, position_offset, None,
                              cu_seqlens, position_ids)
        return out


def packed_position_ids(cu_seqlens, total_tokens):
    """Per-token rotary positions for a packed (1, T) batch: positions
    restart at every ``cu_seqlens`` boundary. Returns a (1, T) Tensor."""
    import jax.numpy as jnp

    def fn(cu):
        t = jnp.arange(total_tokens, dtype=jnp.int32)
        seg = jnp.searchsorted(cu, t, side="right") - 1
        return (t - cu[seg])[None, :]

    return apply(fn, ensure_tensor(cu_seqlens), op_name="packed_position_ids")


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if _use_mp(config):
            from ..distributed.fleet.layers.mpu.mp_layers import (
                VocabParallelEmbedding,
            )

            self.embed_tokens = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size)
        else:
            self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = LlamaDecoderLayer(config)
            self.add_sublayer(f"layers.{i}", layer)
            self.layers.append(layer)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_offset=0, caches=None,
                cu_seqlens=None):
        with _stream_split(self, int(input_ids.shape[1]), caches):
            return self._forward(input_ids, position_offset, caches,
                                 cu_seqlens)

    def _forward(self, input_ids, position_offset, caches, cu_seqlens):
        with named_scope("embed"):
            hidden = self.embed_tokens(input_ids)
        hidden = _mark_hidden(hidden, self.config)
        position_ids = None
        if cu_seqlens is not None:
            if caches is not None:
                raise ValueError(
                    "packed cu_seqlens training and KV caches are "
                    "mutually exclusive (serving uses the paged path)")
            if int(input_ids.shape[0]) != 1:
                raise ValueError(
                    f"packed cu_seqlens training expects the (1, T) "
                    f"packed layout, got batch {input_ids.shape[0]}")
            cu_seqlens = ensure_tensor(cu_seqlens)
            position_ids = packed_position_ids(
                cu_seqlens, int(input_ids.shape[1]))
        new_caches = [] if caches is not None else None
        from ..distributed.fleet.utils.recompute import should_remat_layer

        for i, layer in enumerate(self.layers):
            cache_i = caches[i] if caches is not None else None
            # full_attn/core_attn remat happens inside the decoder layer;
            # block-level remat (full/selective) only without caches
            do_remat = caches is None and should_remat_layer(
                self.config, i,
                allowed=("full", "full_attn", "core_attn", "selective"))
            if do_remat:
                from ..distributed.fleet.utils.recompute import recompute

                hidden = recompute(layer.forward_no_cache, hidden,
                                   position_offset, cu_seqlens, position_ids)
            else:
                hidden, cache_i = layer(hidden, position_offset, cache_i,
                                        cu_seqlens, position_ids)
            if new_caches is not None:
                new_caches.append(cache_i)
        axis = _stream_axis()
        with named_scope("head"):
            return (self.norm(hidden) if axis is None else
                    self.norm(hidden, row_axis=axis)), new_caches

    def paged_rope(self, positions):
        """What every layer's rotary embedding needs at ``positions``
        (the serving engine's bodies ask once a step)."""
        return self.layers[0].self_attn.paged_rope(positions)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if _use_mp(config):
            from ..distributed.fleet.layers.mpu.mp_layers import (
                ColumnParallelLinear,
            )

            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True)
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def forward(self, input_ids, position_offset=0, caches=None,
                cu_seqlens=None):
        # training-loss fusion: the lm_head matmul happens inside
        # LlamaPretrainingCriterion's chunked fused op — returning
        # logits here would defeat the point (full (N, V) buffers)
        fused = self.config.fuse_linear_cross_entropy and caches is None
        with _stream_split(self.llama, int(input_ids.shape[1]), caches,
                           None if fused else self.lm_head):
            hidden, new_caches = self.llama(input_ids, position_offset,
                                            caches, cu_seqlens)
            axis = _stream_axis()
            if not fused:
                with named_scope("head"):
                    # the group gathers the final norm's split output
                    logits = self.lm_head(hidden) if axis is None else (
                        column_parallel_group(hidden, (self.lm_head,),
                                              axis)[0])
        if fused:
            # the criterion's row chunks take a split stream whole again
            return hidden if axis is None else _mark_hidden(
                hidden, self.config)
        if caches is not None:
            return logits, new_caches
        return logits

    # -- what the serving engine asks of a model ---------------------------
    @property
    def decoder(self):
        """The stack the engine's bodies loop over: ``embed_tokens``,
        ``paged_rope``, ``layers`` (each with ``paged_decode`` /
        ``paged_chunk``) and ``norm``."""
        return self.llama

    def paged_cache_layout(self):
        """The pool geometry this model's attention caches: a K and a V
        array a layer, each row ``num_key_value_heads x head_dim``;
        ``layers`` says it of every layer (``"kv"``: block arrays). A
        ``sliding_window`` is refused here: what a model's layers cannot
        serve, the model says."""
        cfg = self.config
        if cfg.sliding_window:
            raise NotImplementedError(
                "LlamaForCausalLM cannot be served with sliding_window: "
                "one uniform window over the K/V block path is not built "
                "(its paged attention has no lower bound, and blocks that "
                "left the window are never released)")
        return {"layout": "kv", "num_kv_heads": cfg.num_key_value_heads,
                "head_dim": cfg.head_dim,
                "layers": ("kv",) * cfg.num_hidden_layers}

    def generate(self, input_ids, max_new_tokens=32,
                 decode_strategy="greedy_search", **kwargs):
        """paddle-style generation entry (greedy / sampling / beam —
        see nlp.generation.generate)."""
        from .generation import generate

        return generate(self, input_ids, max_new_tokens,
                        decode_strategy=decode_strategy, **kwargs)

    def init_caches(self, batch_size, max_len, dtype=None):
        """Allocate empty KV caches: list of (k, v) per layer,
        (B, max_len, HK, D)."""
        import paddle_tpu as paddle

        cfg = self.config
        if cfg.sliding_window:
            # rolling buffer: the cache never needs more than the window
            max_len = min(max_len, cfg.sliding_window)
        caches = []
        for _ in range(cfg.num_hidden_layers):
            shape = [batch_size, max_len, cfg.num_key_value_heads, cfg.head_dim]
            k = paddle.zeros(shape, dtype or cfg.dtype)
            v = paddle.zeros(shape, dtype or cfg.dtype)
            caches.append((k, v))
        return caches


class LlamaPretrainingCriterion(Layer):
    """Shifted next-token cross entropy (PaddleNLP parity).

    With ``config.fuse_linear_cross_entropy`` the model's forward returns
    the final HIDDEN states instead of logits and this criterion applies
    the chunked fused lm-head+CE (``lm_head`` must be passed — kept as a
    plain attribute, NOT a sublayer, so its params register only on the
    model). The full (N, V) logits never exist in HBM."""

    def __init__(self, config: LlamaConfig = None, lm_head=None):
        super().__init__()
        self._fuse = bool(config is not None
                          and config.fuse_linear_cross_entropy)
        self._lce_chunk_rows = int(
            getattr(config, "lce_chunk_rows", 0) or 1024)
        self.__dict__["_lm_head"] = lm_head

    def forward(self, logits, labels, cu_seqlens=None):
        if self._fuse:
            return self._fused_forward(logits, labels, cu_seqlens)
        shifted = logits[:, :-1, :]
        targets = labels[:, 1:]
        if cu_seqlens is None:
            return F.cross_entropy(
                shifted.reshape([-1, shifted.shape[-1]]),
                targets.reshape([-1]),
            )
        # packed batch: a segment's last token must not predict the next
        # segment's first token — mask the cross-boundary positions.
        # Packed layout is (1, T): with B>1 the per-row shift would break
        # the flat position <-> cu_seqlens correspondence below.
        if int(logits.shape[0]) != 1:
            raise ValueError(
                f"packed cu_seqlens criterion expects batch 1 (packed "
                f"(1, T) layout), got batch {logits.shape[0]}")
        import jax.numpy as jnp

        per_tok = F.cross_entropy(
            shifted.reshape([-1, shifted.shape[-1]]),
            targets.reshape([-1]), reduction="none",
        )

        def masked_mean(losses, cu):
            t = losses.shape[0]  # = T - 1
            pos = jnp.arange(t, dtype=jnp.int32)
            seg_here = jnp.searchsorted(cu, pos, side="right")
            seg_next = jnp.searchsorted(cu, pos + 1, side="right")
            mask = (seg_here == seg_next).astype(losses.dtype)
            return (losses * mask).sum() / jnp.maximum(mask.sum(), 1.0)

        return apply(masked_mean, per_tok, ensure_tensor(cu_seqlens),
                     op_name="packed_criterion")

    def _fused_forward(self, hidden, labels, cu_seqlens=None):
        if self._lm_head is None:
            raise ValueError(
                "fuse_linear_cross_entropy needs the lm_head: construct "
                "LlamaPretrainingCriterion(config, lm_head=model.lm_head)")
        from ..incubate.nn.functional import fused_linear_cross_entropy

        shifted = hidden[:, :-1, :]
        targets = labels[:, 1:]
        if cu_seqlens is not None:
            # packed batch: a segment's last token must not predict the
            # next segment's first token — those targets become
            # ignore_index (same positions the unfused packed branch
            # masks out of its mean)
            if int(hidden.shape[0]) != 1:
                raise ValueError(
                    f"packed cu_seqlens criterion expects batch 1, got "
                    f"batch {hidden.shape[0]}")
            import jax.numpy as jnp

            def mask_boundaries(tgt, cu):
                t = tgt.shape[-1]
                pos = jnp.arange(t, dtype=jnp.int32)
                seg_here = jnp.searchsorted(cu, pos, side="right")
                seg_next = jnp.searchsorted(cu, pos + 1, side="right")
                return jnp.where(seg_here == seg_next, tgt, -100)

            targets = apply(mask_boundaries, targets,
                            ensure_tensor(cu_seqlens),
                            op_name="packed_fused_targets")
        return fused_linear_cross_entropy(
            shifted, self._lm_head.weight, targets,
            bias=getattr(self._lm_head, "bias", None),
            chunk_rows=self._lce_chunk_rows)
