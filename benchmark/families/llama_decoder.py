"""The Llama-style decoder family (Mistral-7B, Qwen2-7B): how a
configuration file becomes the PROGRAM's model, and where its plain
reference is. The only file of the benchmark that knows the program's model
class and parameter names; a family the program gains later adds its own
file here and its own reference beside ``reference/llama_decoder.py``."""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.harness import weights
from benchmark.reference import llama_decoder as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

_LAYER = {"ln1": "input_layernorm.weight",
          "q_w": "self_attn.q_proj.weight", "q_b": "self_attn.q_proj.bias",
          "k_w": "self_attn.k_proj.weight", "k_b": "self_attn.k_proj.bias",
          "v_w": "self_attn.v_proj.weight", "v_b": "self_attn.v_proj.bias",
          "o_w": "self_attn.o_proj.weight",
          "ln2": "post_attention_layernorm.weight",
          "gate_w": "mlp.gate_proj.weight", "up_w": "mlp.up_proj.weight",
          "down_w": "mlp.down_proj.weight"}
_TOP = {"top.embed": "llama.embed_tokens.weight",
        "top.norm": "llama.norm.weight", "top.head": "lm_head.weight"}


def program_path(leaf):
    """The reference's leaf name -> the program's parameter path."""
    if leaf in _TOP:
        return _TOP[leaf]
    layer, short = leaf.split(".", 1)
    return f"llama.layers.{int(layer[1:])}.{_LAYER[short]}"


def build_model(cfg, tensor_parallel=False):
    """The program's model for ``cfg`` in the configuration's dtype, with
    whatever its own initializers gave (``install_weights`` replaces it)."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    paddle.set_default_dtype(cfg["torch_dtype"])
    pcfg = LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        sliding_window=cfg.get("sliding_window"),
        attention_bias=bool(cfg.get("attention_bias", False)),
        tensor_parallel=tensor_parallel, use_recompute=False,
        dtype=cfg["torch_dtype"])
    if pcfg.head_dim != reference.dims(cfg)["d"]:
        raise ValueError("the program derives another head size than the "
                         "configuration states")
    return LlamaForCausalLM(pcfg)


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
    call; the old buffers are freed first, their shardings kept)."""
    table, params = parameters(model, cfg)
    new = weights.fill(table, seed, DTYPES[cfg["torch_dtype"]],
                       [p._value for p in params])
    for p, v in zip(params, new):
        p._value = v
    return table, params


def leaf_reader(cfg, seed):
    return weights.leaf_reader(reference.leaf_table(cfg), seed,
                               DTYPES[cfg["torch_dtype"]])


def criterion(cfg, model):
    """Shifted next-token cross entropy in float32, as the program's
    trainers use it (bench.build_step)."""
    from paddle_tpu.nlp import LlamaPretrainingCriterion

    crit = LlamaPretrainingCriterion(model.config)
    return lambda out, labels: crit(out.astype("float32"), labels)
