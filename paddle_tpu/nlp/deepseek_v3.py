"""DeepSeek-V3-shaped decoder (``model_type`` ``deepseek_v3``: DeepSeek-V3,
Kanana-2-30B-A3B, ...): multi-head LATENT attention, a few leading dense
layers, then expert layers whose routed experts sit beside shared ones.

Layer equations (``x`` the layer's input, pre-norm residual as in Llama:
``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``):

- Attention (``q_lora_rank`` None: the query is not compressed):
  ``q = x W_q``, H heads of ``qk_nope_head_dim + qk_rope_head_dim``;
  ``[c_raw | k_rope] = x W_kva`` (``kv_lora_rank`` | rope dim);
  ``c = RMSNorm(c_raw)``; ``k_rope`` is ONE head shared by all H. Rotary
  embedding on ``q_rope`` and ``k_rope`` only, interleaved: the rotated
  pairs are ``(x[2i], x[2i+1])`` (the output holds the first members,
  then the second: q and k share the layout, so scores do not see it).
  ``[k_nope_h | v_h] = c W_kvb`` per head; ``score_h = (q_nope_h .
  k_nope_h + q_rope_h . k_rope) / sqrt(qk_head_dim)``, causal softmax in
  float32, ``y = concat_h(sum p v_h) W_o``.
- What serving caches is the token's ``[c | rope(k_rope)]`` row, written
  once (``paged_cache_layout``: the latent pool). Decode attends in
  latent space with the up-projection ABSORBED: ``q~_h = W_uk_h
  q_nope_h``, ``score_h = (q~_h . c + q_rope_h . k_rope) / sqrt(...)``,
  ``out_h = (sum p c) W_uv_h``; per-head keys and values of the context
  are never built. The chunk (prefill) step up-projects one tile of
  cached rows at a time instead: fewer operations at long chunks; on a
  TPU inside a Pallas kernel whose scores never leave VMEM.
- Dense layers: SwiGLU of ``intermediate_size``. Expert layers:
  ``s = sigmoid(x W_r)`` in float32, selection by the top k of ``s + b``
  (``e_score_correction_bias``), weights ``s[sel] / sum s[sel]`` times
  ``routed_scaling_factor``; ``FFN(x) = sum_j w_j E_sel_j(x) + S(x)``
  with ``S`` one SwiGLU of ``n_shared_experts x moe_intermediate_size``.
  No capacity, no dropped token.

Serving only (``paddle.inference.serve``); ``forward`` is the plain
whole-sequence pass the tests compare with. Not done here: training, a
dense KV cache for ``generate``, tensor parallelism, ``q_lora_rank``,
group-limited routing, rope scaling.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from . import paged_attention as PA
from .routed_experts import GateLeaves, SigmoidRoutedExperts, SwiGLUMLP

__all__ = ["DeepseekV3Config", "DeepseekV3Attention", "DeepseekV3MLP",
           "DeepseekV3MoE", "DeepseekV3DecoderLayer", "DeepseekV3Model",
           "DeepseekV3ForCausalLM"]


class DeepseekV3Config:
    """The published ``config.json`` keys the layer equations read."""

    def __init__(self, vocab_size=129280, hidden_size=7168,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 num_hidden_layers=61, num_attention_heads=128,
                 kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=256,
                 n_shared_experts=1, num_experts_per_tok=8,
                 first_k_dense_replace=3, moe_layer_freq=1,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 n_group=1, topk_group=1, max_position_embeddings=4096,
                 rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 sliding_window=None, tie_word_embeddings=False,
                 dtype="float32"):
        if q_lora_rank is not None:
            raise NotImplementedError(
                "DeepseekV3: q_lora_rank (a compressed query) is not "
                "implemented")
        if rope_scaling is not None:
            raise NotImplementedError(
                "DeepseekV3: rope_scaling is not implemented")
        if tie_word_embeddings:
            raise NotImplementedError(
                "DeepseekV3: tie_word_embeddings is not implemented")
        if moe_layer_freq != 1:
            raise NotImplementedError(
                "DeepseekV3: moe_layer_freq other than 1 is not "
                "implemented")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.first_k_dense_replace = first_k_dense_replace
        self.routed_scaling_factor = routed_scaling_factor
        self.norm_topk_prob = norm_topk_prob
        self.n_group = n_group
        self.topk_group = topk_group
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        # no layer of this family has a window; the key exists so that
        # the engine's refusal reads it like any other model's
        self.sliding_window = sliding_window
        self.dtype = dtype

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self):
        """Values a token and layer caches: ``[c | rope(k_rope)]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @staticmethod
    def tiny(**overrides):
        """Test-scale config: every mechanism at toy widths (one dense
        layer, two expert layers of 8 experts, top 3, two shared)."""
        cfg = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                   moe_intermediate_size=32, num_hidden_layers=3,
                   num_attention_heads=4, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   n_routed_experts=8, n_shared_experts=2,
                   num_experts_per_tok=3, first_k_dense_replace=1,
                   routed_scaling_factor=2.448, max_position_embeddings=256,
                   rope_theta=1e6)
        cfg.update(overrides)
        return DeepseekV3Config(**cfg)

    @staticmethod
    def kanana_2_30b_a3b(**overrides):
        """kakaocorp/kanana-2-30b-a3b-instruct-2601 as published."""
        cfg = dict(vocab_size=128256, hidden_size=2048,
                   intermediate_size=6144, moe_intermediate_size=768,
                   num_hidden_layers=48, num_attention_heads=32,
                   kv_lora_rank=512, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128,
                   n_routed_experts=128, n_shared_experts=2,
                   num_experts_per_tok=6, first_k_dense_replace=1,
                   routed_scaling_factor=2.448,
                   max_position_embeddings=32768, rope_theta=1e6)
        cfg.update(overrides)
        return DeepseekV3Config(**cfg)


def _rope_interleaved(x, cos, sin):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by the
    angles ``cos`` / ``sin`` (..., D/2), broadcast over the axes of
    ``x`` between them and the last. The output holds the rotated first
    members, then the second."""
    xf = x.astype(jnp.float32)
    while cos.ndim < xf.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _latent_decode_attn(q_lat, q_rope, pool, tables, lens, scale):
    """Absorbed decode attention over the latent pool, in plain XLA: the
    table's blocks are gathered ONCE and serve as keys (all lanes) and
    as values (the first ``R`` lanes, the compressed part). ``q_lat``
    (S, H, R) is the query carried into latent space, ``q_rope``
    (S, H, Dr); ``pool`` (NB, BS, R + Dr). Operands stay in the
    pool's dtype with float32 accumulation; softmax in float32 with the
    -1e30 mask of the other decode paths. Returns the context in latent
    space, (S, H, R) float32. The CPU's path, and the reference the
    kernel's parity tests compare with."""
    s_, _, r = q_lat.shape
    w, bs = tables.shape[1], pool.shape[1]
    rows = pool[tables].reshape(s_, w * bs, pool.shape[-1])
    ct = rows.dtype
    logits = (jnp.einsum("shr,skr->shk", q_lat.astype(ct), rows[..., :r],
                         preferred_element_type=jnp.float32)
              + jnp.einsum("shd,skd->shk", q_rope.astype(ct), rows[..., r:],
                           preferred_element_type=jnp.float32)) * scale
    mask = jnp.arange(w * bs)[None, :] < lens[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("shk,skr->shr", p.astype(ct), rows[..., :r],
                      preferred_element_type=jnp.float32)


def _latent_chunk_attn(q_nope, q_rope, pool, tables, base_lens, w_uk, w_uv,
                       scale):
    """Chunk attention over the latent pool (the mixed prefill step):
    query j of a slot attends pool positions < base + j + 1. The cached
    rows are gathered a tile of key blocks at a time (the rule of
    ``paged_attention._paged_chunk_attn``: ``_CHUNK_SCORE_BYTES`` of
    float32 scores), UP-PROJECTED to that tile's per-head keys and
    values, and folded into running (m, l, acc) statistics; tiles past
    the longest row's last position are not visited (a dynamic trip
    count). ``q_nope`` (S, C, H, Dn), ``q_rope`` (S, C, H, Dr); ``w_uk``
    (R, H, Dn), ``w_uv`` (R, H, Dv). Returns (S, C, H, Dv) in the
    queries' dtype."""
    s_, c, h, _ = q_nope.shape
    r, dv = w_uk.shape[0], w_uv.shape[-1]
    w, bs = tables.shape[1], pool.shape[1]
    tile = max(1, min(w, PA._CHUNK_SCORE_BYTES // (s_ * h * c * bs * 4)))
    n_tiles = -(-w // tile)
    tiled = jnp.pad(tables, ((0, 0), (0, n_tiles * tile - w))).reshape(
        s_, n_tiles, tile).transpose(1, 0, 2)           # (N, S, tile)
    lens = base_lens[:, None] + jnp.arange(c)[None, :] + 1   # (S, C)
    neg = jnp.float32(-1e30)
    ct = pool.dtype

    def fold(ti, carry):
        m, l, acc = carry
        rows = pool[tiled[ti]].reshape(s_, tile * bs, pool.shape[-1])
        lat, k_rope = rows[..., :r], rows[..., r:]
        k_nope = jnp.einsum("skr,rhd->skhd", lat, w_uk.astype(ct),
                            preferred_element_type=jnp.float32).astype(ct)
        v = jnp.einsum("skr,rhd->skhd", lat, w_uv.astype(ct),
                       preferred_element_type=jnp.float32).astype(ct)
        logits = (jnp.einsum("schd,skhd->shck", q_nope.astype(ct), k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("schd,skd->shck", q_rope.astype(ct), k_rope,
                               preferred_element_type=jnp.float32)) * scale
        kpos = ti * (tile * bs) + jnp.arange(tile * bs)
        mask = kpos[None, None, :] < lens[:, :, None]   # (S, C, K)
        logits = jnp.where(mask[:, None], logits, neg)
        m2 = jnp.maximum(m, jnp.max(logits, axis=-1))
        alpha = jnp.exp(m - m2)                         # (S, H, C)
        p = jnp.exp(logits - m2[..., None])
        l2 = l * alpha + jnp.sum(p, axis=-1)
        acc2 = acc * alpha[..., None] + jnp.einsum(
            "shck,skhd->shcd", p.astype(ct), v,
            preferred_element_type=jnp.float32)
        return m2, l2, acc2

    # every query sees pool position 0, so the first tile lifts m above
    # the -1e30 init before a masked tile's exp(neg - m) underflows to 0
    carry = (jnp.full((s_, h, c), neg, jnp.float32),
             jnp.zeros((s_, h, c), jnp.float32),
             jnp.zeros((s_, h, c, dv), jnp.float32))
    if n_tiles == 1:
        carry = fold(0, carry)
    else:
        live_tiles = jnp.clip(
            -(-(jnp.max(base_lens) + c) // (tile * bs)), 1, n_tiles)
        carry = jax.lax.fori_loop(0, live_tiles, fold, carry)
    _, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # (S, H, C, Dv)
    return out.transpose(0, 2, 1, 3).astype(q_nope.dtype)


class DeepseekV3Attention(Layer):
    """Multi-head latent attention; see the module's equations."""

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        h = config.num_attention_heads
        self.num_heads = h
        self.q_proj = Linear(config.hidden_size, h * config.qk_head_dim,
                             bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(
            config.hidden_size, config.latent_dim, bias_attr=False)
        self.kv_a_layernorm = RMSNorm(config.kv_lora_rank,
                                      epsilon=config.rms_norm_eps)
        self.kv_b_proj = Linear(
            config.kv_lora_rank,
            h * (config.qk_nope_head_dim + config.v_head_dim),
            bias_attr=False)
        self.o_proj = Linear(h * config.v_head_dim, config.hidden_size,
                             bias_attr=False)
        self.scale = 1.0 / math.sqrt(config.qk_head_dim)

    # -- shared pieces ------------------------------------------------------
    def paged_rope(self, positions):
        """``(cos, sin)`` at ``positions`` (float32, any shape), trailing
        axis ``qk_rope_head_dim / 2``."""
        d = self.config.qk_rope_head_dim
        inv_freq = 1.0 / (self.config.rope_theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = positions[..., None] * inv_freq
        return jnp.cos(freqs), jnp.sin(freqs)

    @jax.named_scope("attn.proj")
    def _queries(self, x, rope):
        """x (..., E) -> q_nope (..., H, Dn), rotated q_rope (..., H, Dr)
        as raw arrays."""
        cfg = self.config
        lead = tuple(x.shape[:-1])
        q = self.q_proj(x)._value.reshape(*lead, self.num_heads,
                                          cfg.qk_head_dim)
        q_nope = q[..., :cfg.qk_nope_head_dim]
        q_rope = _rope_interleaved(q[..., cfg.qk_nope_head_dim:], *rope)
        return q_nope, q_rope

    @jax.named_scope("attn.proj")
    def _latent_rows(self, x, rope):
        """x (..., E) -> the rows the cache holds, (..., R + Dr):
        ``[RMSNorm(c_raw) | rope(k_rope)]``."""
        r = self.config.kv_lora_rank
        kva = self.kv_a_proj_with_mqa(x)
        c = self.kv_a_layernorm(kva[..., :r])._value
        k_rope = _rope_interleaved(kva._value[..., r:], *rope)
        return jnp.concatenate([c, k_rope.astype(c.dtype)], axis=-1)

    def _up_weights(self):
        """``W_kvb`` split per head: W_uk (R, H, Dn), W_uv (R, H, Dv)."""
        cfg = self.config
        w = self.kv_b_proj.weight._value.reshape(
            cfg.kv_lora_rank, self.num_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def _chunk_attn(self, q_nope, q_rope, pool, tables, base_lens):
        """Route the chunk attention as ``paged_attention._paged_attn``
        routes decode: the Pallas kernel on a TPU for a float pool and
        widths it takes (``ops/pallas/chunk_attention.py``: keys and
        values up-projected in VMEM, no score ever stored; it takes
        ``kv_b_proj``'s weight as stored), ``_latent_chunk_attn`` (the
        reference the parity tests compare with) elsewhere. The path is
        counted once a traced program."""
        from ..ops.pallas import chunk_attention as kernel

        w_kvb = self.kv_b_proj.weight._value
        if PA.use_pallas_kernels() and kernel.supports(
                q_nope, q_rope, pool, w_kvb):
            PA.count_chunk_attention_program("kernel")
            return kernel.latent_chunk_attention(
                q_nope, q_rope, pool, tables, base_lens, w_kvb, self.scale)
        PA.count_chunk_attention_program("xla")
        return _latent_chunk_attn(q_nope, q_rope, pool, tables, base_lens,
                                  *self._up_weights(), self.scale)

    def _decode_attn(self, q_lat, q_rope, pool, tables, lens):
        """Route the absorbed decode attention by the rule of
        ``_chunk_attn``: on a TPU, for a float pool of whole-lane widths,
        the kernel (``ops/pallas/paged_attention.py``: each live block
        read once from the pool as it is stored, a row ``[c | k_rope]``
        the key of ONE product with ``[q_lat | q_rope]`` and its first R
        lanes the value), ``_latent_decode_attn`` elsewhere. The path is
        counted once a traced program."""
        from ..ops.pallas import paged_attention as kernel

        r = q_lat.shape[-1]
        if PA.use_pallas_kernels() and kernel.supports_latent(pool, r):
            PA.count_latent_decode_program("kernel")
            return kernel.latent_decode_attention(
                jnp.concatenate([q_lat, q_rope.astype(q_lat.dtype)], -1),
                pool, tables, lens, self.scale, r)
        PA.count_latent_decode_program("xla")
        return _latent_decode_attn(q_lat, q_rope, pool, tables, lens,
                                   self.scale)

    @jax.named_scope("cache.write")
    def _write(self, pool, rows, write_blk, write_off):
        return pool.at[write_blk, write_off].set(rows.astype(pool.dtype))

    @jax.named_scope("attn.proj")
    def _project_out(self, out, lead):
        return self.o_proj(Tensor(
            out.reshape(*lead, self.num_heads * self.config.v_head_dim),
            stop_gradient=True))

    # -- the whole-sequence pass --------------------------------------------
    def forward(self, x):
        """Causal self-attention over x (B, S, E), nothing cached, keys
        and values up-projected (the un-absorbed form)."""
        with jax.named_scope("mla"):
            s = x.shape[1]
            rope = self.paged_rope(jnp.arange(s, dtype=jnp.float32)[None])
            q_nope, q_rope = self._queries(x, rope)
            rows = self._latent_rows(x, rope)
            r = self.config.kv_lora_rank
            w_uk, w_uv = self._up_weights()
            k_nope = jnp.einsum("bkr,rhd->bkhd", rows[..., :r], w_uk)
            v = jnp.einsum("bkr,rhd->bkhd", rows[..., :r], w_uv)
            logits = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bqhd,bkd->bhqk", q_rope, rows[..., r:],
                                   preferred_element_type=jnp.float32)
                      ) * self.scale
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
            p = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)
            return self._project_out(out.astype(x._value.dtype),
                                     x.shape[:2])

    # -- the attention half of the serving engine's layer protocol ----------
    def paged_decode(self, x, rope, tables, lens, write_blk, write_off,
                     cache):
        """One token a slot over the latent pool, absorbed: the step's
        ``[c | k_rope]`` row is written at ``(write_blk, write_off)``,
        the pool is read once for scores and values, and no per-head key
        or value of the context is built. ``cache`` is ``(latent pool,
        None, None, None)``."""
        with jax.named_scope("mla"):
            pool = self._write(cache[0], self._latent_rows(x, rope)[:, 0],
                               write_blk, write_off)
            q_nope, q_rope = self._queries(x, rope)      # (S, 1, H, .)
            w_uk, w_uv = self._up_weights()
            q_lat = jnp.einsum("shd,rhd->shr", q_nope[:, 0], w_uk,
                               preferred_element_type=jnp.float32)
            ctx = self._decode_attn(q_lat, q_rope[:, 0], pool, tables,
                                    lens)                # (S, H, R) f32
            out = jnp.einsum("shr,rhd->shd", ctx.astype(w_uv.dtype), w_uv,
                             preferred_element_type=jnp.float32)
            att = self._project_out(out.astype(x._value.dtype)[:, None],
                                    (x.shape[0], 1))
        return att, (pool, None, None, None)

    def paged_chunk(self, x, rope, tables, base_lens, write_blk, write_off,
                    cache):
        """C tokens a slot over the latent pool: the chunk's rows are
        written, then each query attends its slot's cached rows and the
        chunk's own up to itself, up-projected a tile at a time."""
        with jax.named_scope("mla"):
            pool = self._write(cache[0], self._latent_rows(x, rope),
                               write_blk, write_off)
            q_nope, q_rope = self._queries(x, rope)      # (S, C, H, .)
            out = self._chunk_attn(q_nope, q_rope, pool, tables, base_lens)
            att = self._project_out(out, x.shape[:2])
        return att, (pool, None, None, None)


# SwiGLU: down(silu(gate(x)) * up(x)); the dense layers' feed-forward and
# the shared experts'
DeepseekV3MLP = SwiGLUMLP


# the router's parameters: ``weight`` (E_model, experts) and the selection
# bias ``e_score_correction_bias`` (experts,)
DeepseekV3Gate = GateLeaves


class DeepseekV3MoE(SigmoidRoutedExperts):
    """An expert layer's feed-forward (``nlp/routed_experts.py``: routed
    experts beside shared ones, no capacity) under this source's names:
    ``gate.weight``, ``gate.e_score_correction_bias``,
    ``experts.{gate_up_proj,down_proj}``, ``shared_experts``; ``router``
    is the decision (:class:`SigmoidTopKGate`)."""

    op_name = "deepseek_v3_routed_experts"

    def __init__(self, config: DeepseekV3Config):
        super().__init__(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            config.n_shared_experts * config.moe_intermediate_size,
            config.norm_topk_prob, config.routed_scaling_factor,
            config.n_group, config.topk_group)
        self.router = self.decision

    def _build_router(self, hidden_size, num_experts):
        self.gate = DeepseekV3Gate(hidden_size, num_experts)

    def _router_leaves(self):
        return self.gate.weight, self.gate.e_score_correction_bias


class DeepseekV3DecoderLayer(PA.PagedResidualLayer, Layer):
    def __init__(self, config: DeepseekV3Config, layer_idx):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.mlp = (DeepseekV3MoE(config)
                    if layer_idx >= config.first_k_dense_replace
                    else DeepseekV3MLP(config.hidden_size,
                                       config.intermediate_size))

    def forward(self, hidden):
        hidden = hidden + self.self_attn(self.input_layernorm(hidden))
        return hidden + self.mlp(self.post_attention_layernorm(hidden))


class DeepseekV3Model(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = DeepseekV3DecoderLayer(config, i)
            self.add_sublayer(f"layers.{i}", layer)
            self.layers.append(layer)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        hidden = self.embed_tokens(input_ids)
        for layer in self.layers:
            hidden = layer(hidden)
        return self.norm(hidden)

    def paged_rope(self, positions):
        return self.layers[0].self_attn.paged_rope(positions)


class DeepseekV3ForCausalLM(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        if config.sliding_window:
            raise NotImplementedError(
                "DeepseekV3: sliding_window with latent attention is not "
                "implemented")
        self.config = config
        self.model = DeepseekV3Model(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V): the whole sequence,
        nothing cached."""
        return self.lm_head(self.model(input_ids))

    # -- what the serving engine asks of a model ---------------------------
    @property
    def decoder(self):
        return self.model

    def paged_cache_layout(self):
        """One array a layer, one row a token: ``[c | rope(k_rope)]``,
        shared by every head."""
        if self.config.sliding_window:
            raise NotImplementedError(
                "DeepseekV3: sliding_window over the latent pool is not "
                "implemented")
        return {"layout": "latent", "num_kv_heads": 1,
                "head_dim": self.config.latent_dim,
                "layers": ("latent",) * self.config.num_hidden_layers}
