"""The DeepSeek-V3-shaped decoder family (latent attention, routed experts
beside shared ones; kanana-2-30b-a3b): how a configuration file becomes the
PROGRAM's model, and where its plain reference is. The only file of the
benchmark that knows this family's model class and parameter names.
Serving only: the program does not train through this model."""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.harness import weights
from benchmark.reference import deepseek_v3 as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

_LAYER = {"ln1": "input_layernorm.weight",
          "q_w": "self_attn.q_proj.weight",
          "kva_w": "self_attn.kv_a_proj_with_mqa.weight",
          "kva_ln": "self_attn.kv_a_layernorm.weight",
          "kvb_w": "self_attn.kv_b_proj.weight",
          "o_w": "self_attn.o_proj.weight",
          "ln2": "post_attention_layernorm.weight",
          "gate_w": "mlp.gate_proj.weight", "up_w": "mlp.up_proj.weight",
          "down_w": "mlp.down_proj.weight",
          "router_w": "mlp.gate.weight",
          "router_b": "mlp.gate.e_score_correction_bias",
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
    from paddle_tpu.nlp.deepseek_v3 import (
        DeepseekV3Config, DeepseekV3ForCausalLM)

    import paddle_tpu as paddle

    if tensor_parallel:
        raise NotImplementedError("the program has no tensor-parallel "
                                  "form of this family")
    paddle.set_default_dtype(cfg["torch_dtype"])
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "kv_lora_rank", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "moe_layer_freq",
            "routed_scaling_factor", "norm_topk_prob", "n_group",
            "topk_group", "max_position_embeddings", "rms_norm_eps",
            "rope_theta", "rope_scaling", "tie_word_embeddings")
    if cfg["scoring_func"] != "sigmoid" or cfg["topk_method"] != "noaux_tc" \
            or not cfg["rope_interleave"] or cfg["attention_bias"] \
            or cfg["hidden_act"] != "silu":
        raise ValueError("the program's model computes sigmoid scores with "
                         "a selection bias, interleaved rotary pairs, silu "
                         "and no attention bias; the configuration states "
                         "otherwise")
    pcfg = DeepseekV3Config(**{k: cfg[k] for k in keys},
                            dtype=cfg["torch_dtype"])
    if pcfg.qk_head_dim != cfg["qk_head_dim"]:
        raise ValueError("the program derives another query head size "
                         "than the configuration states")
    return DeepseekV3ForCausalLM(pcfg)


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
