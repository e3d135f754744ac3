"""The AFMoE-shaped decoder family (window and full attention layers mixed,
gated attention, routed experts beside a shared one; Trinity-Mini): how a
configuration file becomes the PROGRAM's model, and where its plain reference
is. The only file of the benchmark that knows this family's model class and
parameter names. Serving only: the program does not train through this model."""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.harness import weights
from benchmark.reference import afmoe as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

_LAYER = {"ln1": "input_layernorm.weight",
          "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
          "v_w": "self_attn.v_proj.weight", "o_w": "self_attn.o_proj.weight",
          "g_w": "self_attn.gate_proj.weight",
          "q_ln": "self_attn.q_norm.weight", "k_ln": "self_attn.k_norm.weight",
          "ln2": "post_attention_layernorm.weight",
          "ln3": "pre_mlp_layernorm.weight",
          "ln4": "post_mlp_layernorm.weight",
          "gate_w": "mlp.gate_proj.weight", "up_w": "mlp.up_proj.weight",
          "down_w": "mlp.down_proj.weight",
          "router_w": "mlp.router.gate.weight",
          "router_b": "mlp.expert_bias",
          "e_gate_up": "mlp.experts.gate_up_proj",
          "e_down": "mlp.experts.down_proj",
          "s_gate_w": "mlp.shared_experts.gate_proj.weight",
          "s_up_w": "mlp.shared_experts.up_proj.weight",
          "s_down_w": "mlp.shared_experts.down_proj.weight"}
_TOP = {"top.embed": "model.embed_tokens.weight",
        "top.norm": "model.norm.weight", "top.head": "lm_head.weight"}


def program_path(leaf):
    """The reference's leaf name -> the program's parameter path."""
    if leaf in _TOP:
        return _TOP[leaf]
    layer, short = leaf.split(".", 1)
    return f"model.layers.{int(layer[1:])}.{_LAYER[short]}"


def build_model(cfg, tensor_parallel=False):
    """The program's model for ``cfg`` in the configuration's dtype, with
    whatever its own initializers gave (``install_weights`` replaces it).
    A checkout whose program lacks the model fails here, at once."""
    from paddle_tpu.nlp.afmoe import AfmoeConfig, AfmoeForCausalLM

    import paddle_tpu as paddle

    if tensor_parallel:
        raise NotImplementedError("the program has no tensor-parallel "
                                  "form of this family")
    paddle.set_default_dtype(cfg["torch_dtype"])
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "num_dense_layers",
            "layer_types", "global_attn_every_n_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts", "num_experts_per_tok", "num_shared_experts",
            "route_norm", "route_scale", "score_func", "n_group",
            "topk_group", "sliding_window", "rope_theta", "rope_scaling",
            "mup_enabled", "max_position_embeddings", "rms_norm_eps",
            "tie_word_embeddings")
    if cfg["hidden_act"] != "silu" or int(cfg["num_expert_groups"]) != 1 \
            or int(cfg["num_limited_groups"]) != 1:
        raise ValueError("the program's model computes silu and routes "
                         "within one group of experts; the configuration "
                         "states otherwise")
    return AfmoeForCausalLM(AfmoeConfig(**{k: cfg[k] for k in keys},
                                        dtype=cfg["torch_dtype"]))


def parameters(model, cfg):
    """The program's parameters in the order of the reference's leaf
    table; every leaf must be there with the table's shape, and no other."""
    table = reference.leaf_table(cfg)
    named = dict(model.named_parameters())
    want = {program_path(n): tuple(s) for n, s, _ in table}
    have = {k: tuple(p._value.shape) for k, p in named.items()}
    if want != have:
        odd = sorted(set(want.items()) ^ set(have.items()))[:6]
        raise ValueError(f"the program's parameters differ from the "
                         f"reference's leaf table, e.g. {odd}")
    return table, [named[program_path(n)] for n, _, _ in table]


def install_weights(model, cfg, seed):
    """Replace the model's parameters by the seed's weights (one jitted
    call; the old buffers are freed first)."""
    table, params = parameters(model, cfg)
    new = weights.fill(table, seed, DTYPES[cfg["torch_dtype"]],
                       [p._value for p in params])
    for p, v in zip(params, new):
        p._value = v
    return table, params


def leaf_reader(cfg, seed):
    return weights.leaf_reader(reference.leaf_table(cfg), seed,
                               DTYPES[cfg["torch_dtype"]])
