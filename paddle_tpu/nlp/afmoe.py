"""AFMoE-shaped decoder (``model_type`` ``afmoe``: arcee-ai/Trinity-Mini,
Trinity-Nano, ...): WINDOW attention layers with rotary positions and FULL
attention layers without, mixed by ``layer_types`` (three to one); every
attention output gated by a sigmoid of the layer's input; queries and keys
RMS-normed per head; each block normed before AND after; a few leading
dense layers, then routed experts beside a shared one.

Equations (``h`` = ``hidden_size``, ``W`` = ``sliding_window``, eps
``rms_norm_eps``; the gate, the per-head norms, which layers rotate and the
four norms are the source's ``modeling_afmoe.py``, no config key names them):

- ``x_0 = sqrt(h) * Embed(ids)`` (``mup_enabled``). Every layer: ``x +=
  N2(Attn(N1(x))); x += N4(FF(N3(x)))``, four RMSNorms with weights
  (``input_layernorm``, ``post_attention_layernorm``, ``pre_mlp_layernorm``,
  ``post_mlp_layernorm``). ``logits = RMSNorm(x) W_head`` (untied).
- Attention, for the normed input ``u``: ``q = u W_q`` as H heads, ``k = u
  W_k``, ``v = u W_v`` as HK heads, ``g = u W_g`` (H x D wide; no bias
  anywhere); ``q`` and ``k`` RMS-normed per head (``q_norm`` / ``k_norm``).
  ``layer_types[i] == "sliding_attention"``: rotate ``q`` and ``k``
  (``rope_theta``, the whole head, half-split layout, no scaling) and let
  query ``t`` see keys ``t - W < s <= t``. ``"full_attention"``: no
  rotation, keys ``s <= t``. Scores x ``1 / sqrt(D)``, softmax in float32,
  each query head over its KV head. ``Attn(u) = (concat_heads(out) *
  sigmoid(g)) W_o``.
- Dense layer (``i < num_dense_layers``): SwiGLU of ``intermediate_size``.
  Expert layer: ``nlp/routed_experts.py`` (``s = sigmoid(v W_r)`` in
  float32; the ``num_experts_per_tok`` largest of ``s + expert_bias``;
  weights ``s_e / (sum of the chosen + 1e-20)`` (``route_norm``) x
  ``route_scale``; experts SwiGLUs of ``moe_intermediate_size``; beside
  them one shared SwiGLU of ``num_shared_experts`` x that width). No
  capacity, no dropped row.

What serving caches: a FULL layer K and V rows in the pool's blocks, for
ever (``"kv"``: the K/V block path of ``llama.py`` with the rotation an
identity); a WINDOW layer the last positions only, as a per-slot RING on
the pool's slot side (``"state"``; ``nlp/paged_attention.py``: ``ring_write``
/ ``ring_decode_attn`` / ``ring_chunk_attn``), ``W + prefill_chunk`` rows
whatever the context.

Serving only (``paddle.inference.serve``); ``forward`` is the plain
whole-sequence pass the tests compare with. Not done here: training,
``generate`` over a dense cache, tensor parallelism, group-limited routing,
rope scaling, a tied head, ``mup_enabled`` false.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from . import paged_attention as PA
from .llama import LlamaAttention, _paged_write
from .routed_experts import SigmoidRoutedExperts, SwiGLUMLP

__all__ = ["AfmoeConfig", "AfmoeAttention", "AfmoeMoE", "AfmoeDecoderLayer",
           "AfmoeModel", "AfmoeForCausalLM"]

F32 = jnp.float32
_KINDS = ("sliding_attention", "full_attention")


class AfmoeConfig:
    """The published ``config.json`` keys the layer equations read (the
    defaults are arcee-ai/Trinity-Mini's)."""

    def __init__(self, vocab_size=200192, hidden_size=2048,
                 intermediate_size=6144, moe_intermediate_size=1024,
                 num_hidden_layers=32, num_dense_layers=2, layer_types=None,
                 global_attn_every_n_layers=4, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, num_experts=128,
                 num_experts_per_tok=8, num_shared_experts=1,
                 route_norm=True, route_scale=2.826, score_func="sigmoid",
                 n_group=1, topk_group=1, sliding_window=2048,
                 rope_theta=10000.0, rope_scaling=None, mup_enabled=True,
                 max_position_embeddings=131072, rms_norm_eps=1e-5,
                 tie_word_embeddings=False, dtype="float32"):
        if layer_types is None:
            n = int(global_attn_every_n_layers)
            layer_types = [_KINDS[(i + 1) % n == 0]
                           for i in range(num_hidden_layers)]
        for what, bad in (
                ("a score function other than sigmoid",
                 score_func != "sigmoid"),
                ("rope_scaling", rope_scaling is not None),
                ("a tied output head", tie_word_embeddings),
                ("mup_enabled false", not mup_enabled),
                ("layer_types of another length than the depth, or of "
                 "other kinds than sliding_attention | full_attention",
                 len(layer_types) != num_hidden_layers
                 or set(layer_types) - set(_KINDS)),
                ("a model without a full_attention layer (the block pool "
                 "would hold no layer)", "full_attention" not in layer_types),
                ("a sliding_attention layer without a sliding_window",
                 "sliding_attention" in layer_types
                 and not sliding_window)):
            if bad:
                raise NotImplementedError(
                    f"Afmoe: {what} is not implemented")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_dense_layers = num_dense_layers
        self.layer_types = tuple(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.route_norm = route_norm
        self.route_scale = route_scale
        self.n_group = n_group
        self.topk_group = topk_group
        self.sliding_window = int(sliding_window or 0)
        self.rope_theta = rope_theta
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        # what ``LlamaAttention`` reads of any config
        self.attention_bias = False
        self.tensor_parallel = False
        self.dtype = dtype

    @staticmethod
    def tiny(**overrides):
        """Test-scale config: every mechanism at toy widths (one dense
        layer, then four expert layers ``sliding, sliding, full, sliding``:
        the benchmark cut's pattern; window 8; 8 experts, top 3)."""
        cfg = dict(vocab_size=128, hidden_size=64, intermediate_size=96,
                   moe_intermediate_size=32, num_hidden_layers=5,
                   num_dense_layers=1,
                   layer_types=["sliding_attention"] * 3
                   + ["full_attention", "sliding_attention"],
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   num_experts=8, num_experts_per_tok=3, sliding_window=8,
                   max_position_embeddings=256)
        cfg.update(overrides)
        return AfmoeConfig(**cfg)

    @staticmethod
    def trinity_mini(**overrides):
        """arcee-ai/Trinity-Mini as published (the defaults)."""
        return AfmoeConfig(**overrides)


def _head_norm(x, norm):
    """RMSNorm over the last axis of (..., heads, D) in float32."""
    xf = x.astype(F32)
    xf = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                            + norm._epsilon)
    return (xf * norm.weight._value.astype(F32)).astype(x.dtype)


class AfmoeAttention(LlamaAttention):
    """Gated GQA with per-head q/k norms; a window layer rotates and keeps
    its keys in a ring, a full layer does neither (``LlamaAttention``'s
    projections, and for a full layer its paged K/V forms)."""

    def __init__(self, config: AfmoeConfig, layer_idx):
        super().__init__(config)
        h, d = self.num_heads, self.head_dim
        self.gate_proj = Linear(config.hidden_size, h * d, bias_attr=False)
        self.q_norm = RMSNorm(d, epsilon=config.rms_norm_eps)
        self.k_norm = RMSNorm(d, epsilon=config.rms_norm_eps)
        # 0: a full layer
        self.window = config.sliding_window if config.layer_types[
            layer_idx] == "sliding_attention" else 0

    def state_arrays(self):
        """What a slot keeps for a window layer, as ``(shape, dtype)``:
        the K and the V ring in the pool's dtype (None), ``(ring, HK x
        D)``; the None is the ring's length, which the engine sizes
        (``window + prefill_chunk`` in whole blocks)."""
        row = (None, self.num_kv_heads * self.head_dim)
        return [(row, None), (row, None)]

    @jax.named_scope("attn.proj")
    def _project(self, x, rope):
        """The normed input (S, C, E) -> q (S, C, H, D) and k (S, C, HK,
        D), normed per head and, in a window layer, rotated; v; and the
        gate's pre-activation (S, C, H x D). Raw arrays."""
        lead = tuple(x.shape[:-1])
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = _head_norm(self.q_proj(x)._value.reshape(*lead, h, d),
                       self.q_norm)
        k = _head_norm(self.k_proj(x)._value.reshape(*lead, hk, d),
                       self.k_norm)
        v = self.v_proj(x)._value.reshape(*lead, hk, d)
        if self.window:
            q, k = PA._rope_rows(q, *rope), PA._rope_rows(k, *rope)
        return q, k, v, self.gate_proj(x)._value

    def _gated_out(self, att, gate):
        """``(concat_heads(att) * sigmoid(g)) W_o``."""
        return PA.sigmoid_gated_out(self.o_proj, att, gate)

    def forward(self, x):
        """Causal (and, in a window layer, windowed) self-attention over
        x (B, S, E) by a dense mask over positions, nothing cached."""
        s = x.shape[1]
        hk, g = self.num_kv_heads, self.num_heads // self.num_kv_heads
        rope = self.paged_rope(jnp.arange(s, dtype=F32)[None, :])
        q, k, v, gate = self._project(x, rope)
        logits = jnp.einsum(
            "bqhgd,bkhd->bhgqk", q.reshape(*q.shape[:2], hk, g, -1), k,
            preferred_element_type=F32) / math.sqrt(self.head_dim)
        t, u = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        seen = u <= t
        if self.window:
            seen &= u > t - self.window
        p = jax.nn.softmax(jnp.where(seen, logits, -1e30), axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                         preferred_element_type=F32)
        return self._gated_out(out.astype(x._value.dtype), gate)

    # -- the serving engine's layer protocol, the attention's half ----------
    def paged_decode(self, x, step, cache):
        """One token a slot. A full layer: ``cache`` is its block arrays
        ``(k, v, k_scale, v_scale)``, written at ``(write_blk, write_off)``
        and attended over the ``lens`` live positions of ``tables``. A
        window layer: ``cache`` is its ``(K ring, V ring)``, written at
        ``(lens - 1) mod R`` and attended over the last ``window``
        positions. Returns the attention output (S, 1, E) and the new
        cache arrays."""
        cos, sin = step["rope"]               # (S, D/2): one position a row
        q, k, v, gate = self._project(x, (cos[:, None], sin[:, None]))
        lens = step["lens"]
        if self.window:
            with jax.named_scope("attn.window"):
                with jax.named_scope("cache.write"):
                    new = PA.ring_write(*cache, k, v, lens[:, None] - 1,
                                        step["live"][:, None])
                att = PA.ring_decode_attn(q[:, 0], *new, lens, self.window)
        else:
            with jax.named_scope("attn.full"):
                with jax.named_scope("cache.write"):
                    kci, vci, ksi, vsi = new = _paged_write(
                        k[:, 0], v[:, 0], step["write_blk"],
                        step["write_off"], cache)
                att = PA._paged_attn(q[:, 0], kci, vci, step["tables"],
                                     lens, ks=ksi, vs=vsi)
        return self._gated_out(att, gate), new

    def paged_chunk(self, x, step, cache):
        """C tokens a slot (the mixed prefill step): position j of a row
        is written, then attends the row's ``lens`` cached positions and
        the chunk's own up to j, in a window layer the last ``window`` of
        them. Same contract as :meth:`paged_decode` with a chunk axis."""
        q, k, v, gate = self._project(x, step["rope"])
        base, valid = step["lens"], step["valid"]
        if self.window:
            with jax.named_scope("attn.window"):
                pos = base[:, None] + jnp.arange(x.shape[1])[None, :]
                with jax.named_scope("cache.write"):
                    new = PA.ring_write(*cache, k, v, pos, valid)
                att = PA.ring_chunk_attn(
                    q, *new, base, jnp.sum(valid, axis=1).astype(base.dtype),
                    self.window)
        else:
            with jax.named_scope("attn.full"):
                with jax.named_scope("cache.write"):
                    kci, vci, ksi, vsi = new = _paged_write(
                        k, v, step["write_blk"], step["write_off"], cache)
                att = PA._paged_chunk_attn(q, kci, vci, step["tables"],
                                           base, ks=ksi, vs=vsi)
        return self._gated_out(att, gate), new


class AfmoeRouter(Layer):
    """``gate``: hidden -> ``num_experts`` scores' pre-activations; the
    decision is the block's (:class:`SigmoidTopKGate`)."""

    def __init__(self, hidden_size, num_experts, top_k):
        super().__init__()
        self.gate = Linear(hidden_size, num_experts, bias_attr=False)
        self.top_k = int(top_k)


class AfmoeMoE(SigmoidRoutedExperts):
    """An expert layer's feed-forward (``nlp/routed_experts.py``) under
    this source's names: ``router.gate.weight``, ``expert_bias``,
    ``experts.{gate_up_proj,down_proj}`` (the source's per-expert
    matrices, stacked), ``shared_experts``."""

    op_name = "afmoe_routed_experts"

    def __init__(self, config: AfmoeConfig):
        super().__init__(
            config.hidden_size, config.moe_intermediate_size,
            config.num_experts, config.num_experts_per_tok,
            config.num_shared_experts * config.moe_intermediate_size,
            config.route_norm, config.route_scale, config.n_group,
            config.topk_group)

    def _build_router(self, hidden_size, num_experts):
        self.router = AfmoeRouter(hidden_size, num_experts,
                                  self.decision.top_k)
        self.expert_bias = self.create_parameter((num_experts,),
                                                 is_bias=True)

    def _router_leaves(self):
        return self.router.gate.weight, self.expert_bias


class AfmoeDecoderLayer(Layer):
    """``x += N2(Attn(N1(x))); x += N4(FF(N3(x)))``."""

    def __init__(self, config: AfmoeConfig, layer_idx):
        super().__init__()
        def norm():
            return RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

        self.self_attn = AfmoeAttention(config, layer_idx)
        self.input_layernorm = norm()
        self.post_attention_layernorm = norm()
        self.pre_mlp_layernorm = norm()
        self.post_mlp_layernorm = norm()
        self.mlp = (AfmoeMoE(config)
                    if layer_idx >= config.num_dense_layers
                    else SwiGLUMLP(config.hidden_size,
                                   config.intermediate_size))

    def _feed_forward(self, hidden, att):
        hidden = hidden + PA.normed(self.post_attention_layernorm, att)
        return hidden + PA.normed(
            self.post_mlp_layernorm,
            self.mlp(PA.normed(self.pre_mlp_layernorm, hidden)))

    def forward(self, hidden):
        return self._feed_forward(hidden, self.self_attn(
            PA.normed(self.input_layernorm, hidden)))

    # -- the serving engine's layer protocol --------------------------------
    def paged_decode(self, hidden, step, cache):
        att, new = self.self_attn.paged_decode(
            PA.normed(self.input_layernorm, hidden), step, cache)
        return self._feed_forward(hidden, att), new

    def paged_chunk(self, hidden, step, cache):
        att, new = self.self_attn.paged_chunk(
            PA.normed(self.input_layernorm, hidden), step, cache)
        return self._feed_forward(hidden, att), new


class _MupEmbedding(Embedding):
    """``sqrt(hidden_size) * Embed(ids)``."""

    def __init__(self, config: AfmoeConfig):
        super().__init__(config.vocab_size, config.hidden_size)
        self.multiplier = math.sqrt(config.hidden_size)

    def forward(self, x):
        return super().forward(x) * self.multiplier


class AfmoeModel(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _MupEmbedding(config)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = AfmoeDecoderLayer(config, i)
            self.add_sublayer(f"layers.{i}", layer)
            self.layers.append(layer)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        hidden = self.embed_tokens(input_ids)
        for layer in self.layers:
            hidden = layer(hidden)
        return self.norm(hidden)

    def paged_rope(self, positions):
        """What the window layers' rotary embedding needs at ``positions``
        (the full layers rotate nothing)."""
        return self.layers[0].self_attn.paged_rope(positions)


class AfmoeForCausalLM(Layer):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.model = AfmoeModel(config)
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
        """Per layer what it caches: a full layer K and V blocks
        (``"kv"``), a window layer a ring on the pool's slot side
        (``"state"``: the arrays of ``state``, per slot, whose length the
        engine works out from ``window``)."""
        cfg = self.config
        ring = next((layer.self_attn for layer in self.model.layers
                     if layer.self_attn.window), None)
        return {"layout": "kv", "num_kv_heads": cfg.num_key_value_heads,
                "head_dim": cfg.head_dim,
                "layers": tuple("state" if t == "sliding_attention" else "kv"
                                for t in cfg.layer_types),
                "state": ring.state_arrays() if ring else [],
                "window": ring.window if ring else 0}
