"""Nemotron-H-shaped decoder (``model_type`` ``nemotron_h``:
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ...): every layer is ONE part
under one norm, a Mamba-2 state-space mixer, grouped-query attention WITHOUT
positions, or routed experts beside one shared expert; which, the string
``hybrid_override_pattern`` says, a character a layer (``M`` | ``*`` |
``E``).

Equations (eps ``layer_norm_epsilon``): ``x_0 = Embed(ids)``; layer ``i``
``x += Part_i(RMSNorm_i(x))``; ``logits = RMSNorm_f(x) W_head`` (an untied
head; no residual, embedding or logit multiplier).

- ``M`` (:class:`~paddle_tpu.nlp.granitemoehybrid.Mamba2Mixer`, whose
  module has the equations): ``mamba_num_heads`` x ``mamba_head_dim`` inner
  channels (NOT ``expand x hidden``: the source's ``expand`` is read by
  nothing), ``n_groups`` of B and C of width ``ssm_state_size``, the gated
  norm per group, a convolution of width ``conv_kernel`` with bias; ``dt =
  softplus(dt_raw + dt_bias)`` unclamped (``time_step_limit`` (0, inf);
  ``time_step_min`` / ``max`` / ``floor`` only shape the source's
  initialisation). ``chunk_size`` is the blocking of ``forward``'s sum.
- ``*``: q (hidden -> heads x ``head_dim``), k, v (hidden -> KV heads x
  ``head_dim``), o, no bias, causal, scale ``1 / sqrt(head_dim)``, NO rotary
  embedding (the family's attention layers carry no positions: the
  state-space layers do; ``rope_theta`` and ``partial_rotary_factor`` are in
  the published config and its attention reads neither); ``head_dim`` is a
  key of its own, not hidden / heads.
- ``E`` (``nlp/routed_experts.py``, the block the deepseek_v3 and afmoe
  families use, with ``act="relu2"``): ``s = sigmoid(v W_r)`` in float32;
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s_chosen / (sum s_chosen + 1e-20) x routed_scaling_factor``; an
  expert is UNGATED, ``relu(v W_up)^2 W_down`` of width
  ``moe_intermediate_size``; ``+ relu(v S_up)^2 S_down`` of width
  ``moe_shared_expert_intermediate_size`` (x ``n_shared_experts``).
  ``held_experts = (lo, n)``: the router keeps its ``n_routed_experts``
  outputs and this chip holds, and computes, experts ``lo .. lo + n - 1``;
  an absent choice adds exactly zero.

What a layer caches (``paged_cache_layout``): ``M`` a row of the pool's slot
side, ``*`` K and V blocks, ``E`` NOTHING (``"none"``).

Parameter names follow the source's model code (``backbone.embeddings``,
``backbone.layers.N.norm``, ``.mixer.*``, ``backbone.norm_f``, ``lm_head``);
the routed experts are stacked (``mixer.experts.{up_proj,down_proj}``), as
the other families stack theirs.

Serving only (``paddle.inference.serve``); ``forward`` is the plain
whole-sequence pass the tests compare with. Not done here: training,
``generate`` over a dense cache, tensor parallelism, dense ``-`` (MLP)
layers of the pattern, biases on the projections, group-limited routing.
"""
from __future__ import annotations

from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from .granitemoehybrid import Mamba2Mixer, NoPositionAttention
from .paged_attention import normed
from .routed_experts import GateLeaves, SigmoidRoutedExperts

__all__ = ["NemotronHConfig", "NemotronHMoE", "NemotronHBlock",
           "NemotronHModel", "NemotronHForCausalLM"]

_PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_CACHES = {"M": "state", "*": "kv", "E": "none"}


class NemotronHConfig:
    """The published ``config.json`` keys the layer equations read (every
    other published key is taken and refused by name where it asks for
    something that is not computed), and ``held_experts``: ``(lo, n)``,
    the routed experts this chip holds (default: all)."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 num_hidden_layers=52, hybrid_override_pattern=None,
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                 attention_bias=False, mamba_num_heads=64, mamba_head_dim=64,
                 ssm_state_size=128, conv_kernel=4, n_groups=8, chunk_size=128,
                 expand=2, use_conv_bias=True, mamba_proj_bias=False,
                 use_bias=False, mamba_hidden_act="silu",
                 n_routed_experts=128, num_experts_per_tok=6,
                 held_experts=None, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_shared_experts=1, intermediate_size=1856,
                 mlp_hidden_act="relu2", mlp_bias=False, norm_topk_prob=True,
                 routed_scaling_factor=2.5, n_group=1, topk_group=1,
                 layer_norm_epsilon=1e-5, norm_eps=1e-5,
                 residual_in_fp32=False, tie_word_embeddings=False,
                 max_position_embeddings=262144, sliding_window=None,
                 rope_theta=10000, partial_rotary_factor=1,
                 time_step_limit=(0.0, float("inf")), time_step_min=0.001,
                 time_step_max=0.1, time_step_floor=0.0001,
                 rescale_prenorm_residual=True, use_mamba_kernels=True,
                 num_logits_to_keep=1, model_type="nemotron_h",
                 dtype="float32"):
        if hybrid_override_pattern is None:
            hybrid_override_pattern = _PUBLISHED[:num_hidden_layers]
        for what, bad in (
                ("a model_type other than nemotron_h",
                 model_type != "nemotron_h"),
                ("a hybrid_override_pattern of another length than the "
                 "depth, or of other parts than M | * | E",
                 len(hybrid_override_pattern) != num_hidden_layers
                 or set(hybrid_override_pattern) - set(_CACHES)),
                ("a bias on a projection",
                 attention_bias or mamba_proj_bias or use_bias or mlp_bias),
                ("a convolution without bias", not use_conv_bias),
                ("mamba_hidden_act other than silu",
                 mamba_hidden_act != "silu"),
                ("mlp_hidden_act other than relu2",
                 mlp_hidden_act != "relu2"),
                ("n_groups that does not divide mamba_num_heads",
                 n_groups < 1 or mamba_num_heads % n_groups),
                ("a tied output head", tie_word_embeddings),
                ("residual_in_fp32", residual_in_fp32),
                ("norm_eps other than layer_norm_epsilon",
                 norm_eps != layer_norm_epsilon),
                ("sliding_window", sliding_window),
                ("time_step_limit other than (0, inf)",
                 tuple(time_step_limit) != (0.0, float("inf")))):
            if bad:
                raise NotImplementedError(
                    f"NemotronH: {what} is not implemented")
        lo, n = held_experts or (0, n_routed_experts)
        if not 0 <= lo < lo + n <= n_routed_experts:
            raise ValueError(
                f"held_experts {held_experts} is no range of the "
                f"{n_routed_experts} experts")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.hybrid_override_pattern = str(hybrid_override_pattern)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.conv_kernel = conv_kernel
        self.n_groups = n_groups
        self.chunk_size = chunk_size
        self.n_routed_experts = n_routed_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.held_experts = (int(lo), int(n))
        self.moe_intermediate_size = moe_intermediate_size
        self.moe_shared_expert_intermediate_size = \
            moe_shared_expert_intermediate_size
        self.n_shared_experts = n_shared_experts
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.n_group, self.topk_group = n_group, topk_group
        self.layer_norm_epsilon = layer_norm_epsilon
        self.max_position_embeddings = max_position_embeddings
        # taken and read by nothing: ``expand`` (d_inner is heads x head
        # width), ``intermediate_size`` (a dense ``-`` layer's width: none
        # is taken), ``rope_theta`` / ``partial_rotary_factor`` (attention
        # carries no positions), what shapes the source's initialisation
        # (``time_step_min`` / ``max`` / ``floor``,
        # ``rescale_prenorm_residual``) and what picks its implementation
        # (``use_mamba_kernels``, ``num_logits_to_keep``)
        # what ``LlamaAttention`` and the engine read of any config
        self.attention_bias = False
        self.tensor_parallel = False
        self.sliding_window = None
        self.dtype = dtype

    @staticmethod
    def tiny(**overrides):
        """Test-scale config: every mechanism at toy widths (eight layers
        ``MEM*EMEM``; 4 heads in 2 groups; 8 experts, top 3; ``head_dim``
        16 where hidden / heads is 8)."""
        cfg = dict(vocab_size=128, hidden_size=32, num_hidden_layers=8,
                   hybrid_override_pattern="MEM*EMEM",
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
                   n_groups=2, chunk_size=8, n_routed_experts=8,
                   num_experts_per_tok=3, moe_intermediate_size=24,
                   moe_shared_expert_intermediate_size=40,
                   max_position_embeddings=256)
        cfg.update(overrides)
        return NemotronHConfig(**cfg)

    @staticmethod
    def nemotron_3_nano_30b_a3b(**overrides):
        """nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as published (the
        defaults)."""
        return NemotronHConfig(**overrides)


class NemotronHMoE(SigmoidRoutedExperts):
    """An ``E`` layer's part (``nlp/routed_experts.py``: ungated
    ``relu^2`` experts, a held share) under this source's names:
    ``gate.weight``, ``gate.e_score_correction_bias``,
    ``experts.{up_proj,down_proj}``, ``shared_experts.{up_proj,down_proj}``;
    ``router`` is the decision (:class:`SigmoidTopKGate`)."""

    op_name = "nemotron_h_routed_experts"

    def __init__(self, config: NemotronHConfig):
        super().__init__(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            config.n_shared_experts
            * config.moe_shared_expert_intermediate_size,
            config.norm_topk_prob, config.routed_scaling_factor,
            config.n_group, config.topk_group, act="relu2",
            held=config.held_experts)
        self.router = self.decision

    def _build_router(self, hidden_size, num_experts):
        self.gate = GateLeaves(hidden_size, num_experts)

    def _router_leaves(self):
        return self.gate.weight, self.gate.e_score_correction_bias


class NemotronHBlock(Layer):
    """``x += mixer(norm(x))``: ``mixer`` is the layer's ONE part."""

    def __init__(self, config: NemotronHConfig, layer_idx):
        super().__init__()
        self.kind = config.hybrid_override_pattern[layer_idx]
        self.norm = RMSNorm(config.hidden_size,
                            epsilon=config.layer_norm_epsilon)
        if self.kind == "M":
            self.mixer = Mamba2Mixer(
                config.hidden_size, config.mamba_num_heads,
                config.mamba_head_dim, config.ssm_state_size,
                config.conv_kernel, config.n_groups, config.chunk_size,
                config.layer_norm_epsilon)
        elif self.kind == "*":
            self.mixer = NoPositionAttention(config)
        else:
            self.mixer = NemotronHMoE(config)

    @property
    def mlp(self):
        """The block that routes rows to experts (what the engine's
        ``moe_rows`` reads), None in a layer without one."""
        return self.mixer if self.kind == "E" else None

    def forward(self, hidden):
        return hidden + self.mixer(normed(self.norm, hidden))

    # -- the serving engine's layer protocol --------------------------------
    def _paged(self, form, hidden, step, cache):
        x = normed(self.norm, hidden)
        if self.kind == "E":         # caches nothing: () in, () out
            return hidden + self.mixer(x), ()
        if self.kind == "M":
            mixed, new = getattr(self.mixer, form)(x, step, cache)
        else:
            mixed, new = getattr(self.mixer, form)(
                x, None, step["tables"], step["lens"], step["write_blk"],
                step["write_off"], cache)
        return hidden + mixed, new

    def paged_decode(self, hidden, step, cache):
        return self._paged("paged_decode", hidden, step, cache)

    def paged_chunk(self, hidden, step, cache):
        return self._paged("paged_chunk", hidden, step, cache)


class NemotronHModel(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embeddings = Embedding(config.vocab_size, config.hidden_size)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = NemotronHBlock(config, i)
            self.add_sublayer(f"layers.{i}", layer)
            self.layers.append(layer)
        self.norm_f = RMSNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids):
        hidden = self.embeddings(input_ids)
        for layer in self.layers:
            hidden = layer(hidden)
        return self.norm_f(hidden)

    # -- what the engine's bodies ask of a decoder ---------------------------
    @property
    def embed_tokens(self):
        return self.embeddings

    @property
    def norm(self):
        return self.norm_f

    def paged_rope(self, positions):
        """No layer of this family rotates anything."""
        return None


class NemotronHForCausalLM(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V): the whole sequence,
        nothing cached."""
        return self.lm_head(self.backbone(input_ids))

    # -- what the serving engine asks of a model ---------------------------
    @property
    def decoder(self):
        return self.backbone

    def paged_cache_layout(self):
        """Per layer what it caches: ``M`` a row of the pool's slot side
        (``"state"``: the arrays of ``state``, per slot), ``*`` K and V
        blocks (``"kv"``), ``E`` nothing (``"none"``)."""
        cfg = self.config
        mamba = next((layer.mixer for layer in self.backbone.layers
                      if layer.kind == "M"), None)
        return {"layout": "kv", "num_kv_heads": cfg.num_key_value_heads,
                "head_dim": cfg.head_dim,
                "layers": tuple(_CACHES[c]
                                for c in cfg.hybrid_override_pattern),
                "state": mamba.state_arrays() if mamba else []}
