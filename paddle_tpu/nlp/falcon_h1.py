"""Falcon-H1-shaped decoder (``model_type`` ``falcon_h1``:
tiiuae/Falcon-H1-34B-Instruct, ...): EVERY layer runs a Mamba-2 state-space
mixer AND grouped-query attention with rotary positions side by side on one
normed input and adds both to the stream, then a dense SwiGLU MLP; muP
multipliers scale fourteen places of the network (seven scalars, the five
sections of the mixer's input product, the MLP's gate and output).

Equations (eps ``rms_norm_eps``, ``x`` the stream):

- ``x_0 = Embed(ids) * embedding_multiplier``.
- Layer: ``u = RMSNorm_in(x)``; ``a = Attn(u * attention_in_multiplier) *
  attention_out_multiplier``; ``m = Mixer(u * ssm_in_multiplier) *
  ssm_out_multiplier``; ``x += a + m``; ``x += MLP(RMSNorm_ff(x))``.
- ``Attn`` (:class:`FalconH1Attention`: ``LlamaAttention``'s projections and
  paged K/V forms): ``q = v W_q``, ``k = (v W_k) * key_multiplier``, ``v' =
  v W_v``, ``num_attention_heads`` / ``num_key_value_heads`` heads of
  ``head_dim`` (a key of its own), no bias; the rotary embedding (halves
  rotated, base ``rope_theta``, no scaling) on q and k over the whole head
  width; causal softmax at ``head_dim ** -0.5``; ``W_o``.
- ``Mixer`` (:class:`~paddle_tpu.nlp.granitemoehybrid.Mamba2Mixer`, whose
  module has the equations): ``p = (v W_in) * mup`` with ``mup`` repeating
  ``ssm_multipliers[0..4]`` over the sections ``[z | xs | B | C | dt_raw]``;
  ``mamba_n_heads`` x ``mamba_d_head`` = ``mamba_d_ssm`` inner channels (NOT
  ``mamba_expand`` x hidden), ``mamba_n_groups`` of B and C of width
  ``mamba_d_state``; a convolution of width ``mamba_d_conv`` with bias; ``dt
  = softplus(dt_raw + dt_bias)`` unclamped; the gate BEFORE the norm
  (``mamba_norm_before_gate`` false), the norm per group.
- ``MLP``: ``(up(v) * silu(gate(v) * mlp_multipliers[0])) W_down *
  mlp_multipliers[1]``.
- ``logits = (RMSNorm_f(x) W_head) * lm_head_multiplier``; the head untied.

What a layer caches (``paged_cache_layout``): BOTH K and V blocks and a row
of the pool's slot side, ``("kv", "state")``: it is handed ``(block arrays,
slot arrays)`` and hands both back.

Parameter names follow the source's model code (``model.embed_tokens``,
``model.layers.N.{input_layernorm, mamba.{in_proj, conv1d, dt_bias, A_log,
D, norm, out_proj}, self_attn.{q,k,v,o}_proj, pre_ff_layernorm,
feed_forward.{gate,up,down}_proj}``, ``model.final_layernorm``,
``lm_head``).

Serving only (``paddle.inference.serve``); ``forward`` is the plain
whole-sequence pass the tests compare with. Not done here: training,
``generate`` over a dense cache, tensor parallelism, biases on the
projections, ``rope_scaling``, a norm before the gate, a tied head.
"""
from __future__ import annotations

import jax

from ..core.tensor import Tensor
from ..nn.layer.common import Linear
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from .granitemoehybrid import Mamba2Mixer, PlainAttention, _ScaledEmbedding
from .paged_attention import normed

__all__ = ["FalconH1Config", "FalconH1Attention", "FalconH1MLP",
           "FalconH1DecoderLayer", "FalconH1Model", "FalconH1ForCausalLM"]


class FalconH1Config:
    """The published ``config.json`` keys: those the layer equations read,
    and every other one taken and refused by name where it asks for
    something that is not computed."""

    def __init__(self, vocab_size=261120, hidden_size=5120,
                 intermediate_size=21504, num_hidden_layers=72,
                 num_attention_heads=20, num_key_value_heads=4, head_dim=128,
                 attention_bias=False, mlp_bias=False, projectors_bias=False,
                 hidden_act="silu", mamba_d_ssm=4096, mamba_n_heads=32,
                 mamba_d_head=128, mamba_d_state=256, mamba_d_conv=4,
                 mamba_n_groups=2, mamba_chunk_size=128, mamba_expand=2,
                 mamba_conv_bias=True, mamba_proj_bias=False,
                 mamba_rms_norm=True, mamba_norm_before_gate=False,
                 mamba_use_mlp=True, attn_layer_indices=None,
                 embedding_multiplier=5.656854249492381,
                 lm_head_multiplier=0.0078125, attention_in_multiplier=1.0,
                 attention_out_multiplier=0.0375,
                 key_multiplier=0.011048543456039804, ssm_in_multiplier=0.25,
                 ssm_out_multiplier=0.08838834764831845,
                 ssm_multipliers=(0.3535533905932738, 0.25,
                                  0.1767766952966369, 0.5,
                                  0.3535533905932738),
                 mlp_multipliers=(0.1767766952966369, 0.011160714285714284),
                 mlp_expansion_factor=8, rope_theta=100000000000.0,
                 rope_scaling=None, max_position_embeddings=262144,
                 rms_norm_eps=1e-5, tie_word_embeddings=False,
                 num_logits_to_keep=1, sliding_window=None,
                 model_type="falcon_h1", dtype="float32"):
        for what, bad in (
                ("a model_type other than falcon_h1",
                 model_type != "falcon_h1"),
                ("a bias on a projection",
                 attention_bias or mlp_bias or projectors_bias
                 or mamba_proj_bias),
                ("a convolution without bias", not mamba_conv_bias),
                ("hidden_act other than silu", hidden_act != "silu"),
                ("mamba_n_heads x mamba_d_head != mamba_d_ssm",
                 mamba_n_heads * mamba_d_head != mamba_d_ssm),
                ("mamba_n_groups that does not divide mamba_n_heads",
                 mamba_n_groups < 1 or mamba_n_heads % mamba_n_groups),
                ("a mixer without its gated norm (mamba_rms_norm false)",
                 not mamba_rms_norm),
                ("mamba_norm_before_gate", mamba_norm_before_gate),
                ("a layer without its MLP (mamba_use_mlp false)",
                 not mamba_use_mlp),
                ("attn_layer_indices (attention in some layers only)",
                 attn_layer_indices is not None),
                ("rope_scaling", rope_scaling is not None),
                ("a tied output head", tie_word_embeddings),
                ("sliding_window", sliding_window),
                ("ssm_multipliers of another length than the five "
                 "sections, or mlp_multipliers than two",
                 len(ssm_multipliers) != 5 or len(mlp_multipliers) != 2)):
            if bad:
                raise NotImplementedError(
                    f"FalconH1: {what} is not implemented")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_n_groups = mamba_n_groups
        self.mamba_chunk_size = mamba_chunk_size
        self.embedding_multiplier = float(embedding_multiplier)
        self.lm_head_multiplier = float(lm_head_multiplier)
        self.attention_in_multiplier = float(attention_in_multiplier)
        self.attention_out_multiplier = float(attention_out_multiplier)
        self.key_multiplier = float(key_multiplier)
        self.ssm_in_multiplier = float(ssm_in_multiplier)
        self.ssm_out_multiplier = float(ssm_out_multiplier)
        self.ssm_multipliers = tuple(float(m) for m in ssm_multipliers)
        self.mlp_multipliers = tuple(float(m) for m in mlp_multipliers)
        self.rope_theta = float(rope_theta)
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        # taken and read by nothing: ``mamba_expand`` and
        # ``mlp_expansion_factor`` (the widths are keys of their own),
        # ``num_logits_to_keep`` (it picks the source's implementation)
        # what ``LlamaAttention`` and the engine read of any config
        self.attention_bias = False
        self.tensor_parallel = False
        self.sliding_window = None
        self.dtype = dtype

    @staticmethod
    def tiny(**overrides):
        """Test-scale config: every mechanism at toy widths (three layers;
        5 query heads a KV head as published; 4 mixer heads in 2 groups;
        ``head_dim`` 16 where hidden / heads is 6.4)."""
        cfg = dict(vocab_size=128, hidden_size=32, intermediate_size=48,
                   num_hidden_layers=3, num_attention_heads=5,
                   num_key_value_heads=1, head_dim=16, mamba_d_ssm=32,
                   mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
                   mamba_n_groups=2, mamba_chunk_size=8, rope_theta=10000.0,
                   max_position_embeddings=256)
        cfg.update(overrides)
        return FalconH1Config(**cfg)

    @staticmethod
    def falcon_h1_34b(**overrides):
        """tiiuae/Falcon-H1-34B-Instruct as published (the defaults)."""
        return FalconH1Config(**overrides)


class _ScaledLinear(Linear):
    """A product without bias whose result is multiplied by ``multiplier``
    (a muP factor the source applies to this product's output)."""

    def __init__(self, in_features, out_features, multiplier):
        super().__init__(in_features, out_features, bias_attr=False)
        self.multiplier = float(multiplier)

    def forward(self, x):
        return super().forward(x) * self.multiplier


class FalconH1Attention(PlainAttention):
    """Rotary GQA at a ``head_dim`` of its own whose keys are scaled by
    ``key_multiplier`` as they leave ``k_proj`` (before the rotation, as in
    the source): ``LlamaAttention``'s paged K/V forms as they are."""

    def __init__(self, config: FalconH1Config):
        super().__init__(config)
        self.k_proj = _ScaledLinear(
            config.hidden_size, self.num_kv_heads * self.head_dim,
            config.key_multiplier)


class FalconH1MLP(Layer):
    """``(up(x) * silu(gate(x) * m_gate)) W_down * m_down``."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        h, f = config.hidden_size, config.intermediate_size
        self.gate_multiplier, self.down_multiplier = config.mlp_multipliers
        self.gate_proj = Linear(h, f, bias_attr=False)
        self.up_proj = Linear(h, f, bias_attr=False)
        self.down_proj = Linear(f, h, bias_attr=False)

    def forward(self, x):
        with jax.named_scope("mlp"):
            gate = jax.nn.silu(self.gate_proj(x)._value
                               * self.gate_multiplier)
            return self.down_proj(Tensor(
                self.up_proj(x)._value * gate, stop_gradient=True)
            ) * self.down_multiplier


class FalconH1DecoderLayer(Layer):
    """``u = norm(x); x += Attn(u a_in) a_out + Mixer(u s_in) s_out; x +=
    MLP(norm(x))``: the two branches side by side on one normed input."""

    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.attention_in = config.attention_in_multiplier
        self.attention_out = config.attention_out_multiplier
        self.ssm_in = config.ssm_in_multiplier
        self.ssm_out = config.ssm_out_multiplier
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.mamba = Mamba2Mixer(
            config.hidden_size, config.mamba_n_heads, config.mamba_d_head,
            config.mamba_d_state, config.mamba_d_conv,
            config.mamba_n_groups, config.mamba_chunk_size,
            config.rms_norm_eps, multipliers=config.ssm_multipliers)
        self.self_attn = FalconH1Attention(config)
        self.pre_ff_layernorm = RMSNorm(config.hidden_size,
                                        epsilon=config.rms_norm_eps)
        self.feed_forward = FalconH1MLP(config)

    def _branches(self, hidden, attend, mix):
        """The layer, given how each branch is asked (whole sequence or
        over the cache): the new stream and what the branches hand back
        beside their outputs."""
        u = normed(self.input_layernorm, hidden)
        with jax.named_scope("attn.proj"):
            u_att = u * self.attention_in
        att, kept_att = attend(u_att)
        with jax.named_scope("ssm.in_proj"):
            u_ssm = u * self.ssm_in
        mixed, kept_ssm = mix(u_ssm)
        with jax.named_scope("mix.sum"):
            hidden = hidden + (att * self.attention_out
                               + mixed * self.ssm_out)
        hidden = hidden + self.feed_forward(
            normed(self.pre_ff_layernorm, hidden))
        return hidden, (kept_att, kept_ssm)

    def forward(self, hidden):
        return self._branches(hidden, lambda u: (self.self_attn(u), None),
                              lambda u: (self.mamba(u), None))[0]

    # -- the serving engine's layer protocol --------------------------------
    def _paged(self, form, hidden, step, cache):
        """``cache`` is ``(block arrays, slot arrays)``, the order
        ``paged_cache_layout`` names the parts in."""
        blocks, slot = cache
        return self._branches(
            hidden,
            lambda u: getattr(self.self_attn, form)(
                u, step["rope"], step["tables"], step["lens"],
                step["write_blk"], step["write_off"], blocks),
            lambda u: getattr(self.mamba, form)(u, step, slot))

    def paged_decode(self, hidden, step, cache):
        return self._paged("paged_decode", hidden, step, cache)

    def paged_chunk(self, hidden, step, cache):
        return self._paged("paged_chunk", hidden, step, cache)


class FalconH1Model(Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.embed_tokens = _ScaledEmbedding(config)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = FalconH1DecoderLayer(config)
            self.add_sublayer(f"layers.{i}", layer)
            self.layers.append(layer)
        self.final_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        hidden = self.embed_tokens(input_ids)
        for layer in self.layers:
            hidden = layer(hidden)
        return self.final_layernorm(hidden)

    # -- what the engine's bodies ask of a decoder ---------------------------
    @property
    def norm(self):
        return self.final_layernorm

    def paged_rope(self, positions):
        """What every layer's rotary embedding needs at ``positions``."""
        return self.layers[0].self_attn.paged_rope(positions)


class FalconH1ForCausalLM(Layer):
    def __init__(self, config: FalconH1Config):
        super().__init__()
        self.config = config
        self.model = FalconH1Model(config)
        self.lm_head = _ScaledLinear(config.hidden_size, config.vocab_size,
                                     config.lm_head_multiplier)

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V): the whole sequence,
        nothing cached."""
        return self.lm_head(self.model(input_ids))

    # -- what the serving engine asks of a model ---------------------------
    @property
    def decoder(self):
        return self.model

    def paged_cache_layout(self):
        """Every layer caches K and V blocks AND a row of the pool's slot
        side (the arrays of ``state``, per slot): ``("kv", "state")``."""
        cfg = self.config
        return {"layout": "kv", "num_kv_heads": cfg.num_key_value_heads,
                "head_dim": cfg.head_dim,
                "layers": (("kv", "state"),) * cfg.num_hidden_layers,
                "state": self.model.layers[0].mamba.state_arrays()}
