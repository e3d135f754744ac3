"""The Granite-4.0-H-shaped decoder family (Mamba-2 state-space layers beside
attention without positions, routed experts of which a chip holds its share
beside a shared MLP; granite-4.0-h-small): how a configuration file becomes
the PROGRAM's model, and where its plain reference is. The only file of the
benchmark that knows this family's model class and parameter names.
Serving only: the program does not train through this model."""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.harness import weights
from benchmark.reference import granitemoehybrid as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

_LAYER = {"ln1": "input_layernorm.weight",
          "in_w": "mamba.in_proj.weight",
          "conv_w": "mamba.conv1d.weight", "conv_b": "mamba.conv1d.bias",
          "dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log", "D": "mamba.D",
          "ssm_ln": "mamba.norm.weight", "out_w": "mamba.out_proj.weight",
          "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
          "v_w": "self_attn.v_proj.weight", "o_w": "self_attn.o_proj.weight",
          "ln2": "post_attention_layernorm.weight",
          "router_w": "block_sparse_moe.router.layer.weight",
          "e_in": "block_sparse_moe.input_linear.weight",
          "e_out": "block_sparse_moe.output_linear.weight",
          "s_in": "shared_mlp.input_linear.weight",
          "s_out": "shared_mlp.output_linear.weight"}
_TOP = {"top.embed": "model.embed_tokens.weight",
        "top.norm": "model.norm.weight"}



def leaf_scale(cfg):
    """name -> the power of two (exact in bf16) by which this family scales
    a leaf after the harness has drawn it like every other (a matrix is k /
    8192, standard deviation 0.018), for the program and the reference
    alike: 2 to the configuration's ``seeded_leaf_scale_log2[short name]``
    (``embed``, ``conv_w``, ``D``; absent: 1).

    Why: the source's embedding is small and ``embedding_multiplier`` 12
    lifts it; one of the other matrices' size, times 12, read back through
    the TIED head, puts the last token's own logit far above the spread of
    all others: every served token repeats the one before it by a margin
    no precision moves, and the int8 control cannot be told from the
    program. The convolution's four taps are the opposite case: at 0.018
    they leave ``xs``, ``B`` and ``C`` at ~0.02 and the recurrence's share
    of ``y`` far below ``D xs``, so a wrong state would move no logit.
    With the taps lifted, ``D`` (drawn near 1) is the third: the
    recurrence's ``H C`` is then ~16 x ``D xs``, a sum over ``C_t . B_s``
    that now and then cancels; ``y`` is small there, the gated norm scales
    it back up and the layer passes a rounding on twenty times larger, in
    any precision. ``D`` at ``H C``'s size gives ``y`` a floor."""
    exps = cfg.get("seeded_leaf_scale_log2", {})
    return lambda name: 2.0 ** int(exps.get(name.split(".")[-1], 0))


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
    from paddle_tpu.nlp.granitemoehybrid import (
        GraniteMoeHybridConfig, GraniteMoeHybridForCausalLM)

    import paddle_tpu as paddle

    if tensor_parallel:
        raise NotImplementedError("the program has no tensor-parallel "
                                  "form of this family")
    paddle.set_default_dtype(cfg["torch_dtype"])
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "shared_intermediate_size", "num_hidden_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads",
            "num_experts_per_tok", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_d_conv", "mamba_expand",
            "mamba_n_groups", "mamba_chunk_size", "mamba_conv_bias",
            "mamba_proj_bias", "attention_bias", "attention_multiplier",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "position_embedding_type", "max_position_embeddings",
            "rms_norm_eps", "tie_word_embeddings")
    if cfg["hidden_act"] != "silu" \
            or cfg["normalization_function"] != "rmsnorm" \
            or cfg["rope_scaling"] is not None:
        raise ValueError("the program's model computes silu, RMSNorm and no "
                         "rotary embedding; the configuration states "
                         "otherwise")
    # the file's num_local_experts is what this chip HOLDS; the router
    # keeps the published width
    held = int(cfg["num_local_experts"])
    lo, hi = cfg.get("held_experts", [0, held])
    if hi - lo != held:
        raise ValueError("held_experts is not num_local_experts wide")
    pcfg = GraniteMoeHybridConfig(
        **{k: cfg[k] for k in keys},
        num_local_experts=int(cfg.get("published_experts", held)),
        held_experts=(int(lo), held), dtype=cfg["torch_dtype"])
    return GraniteMoeHybridForCausalLM(pcfg)


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
    scale = leaf_scale(cfg)
    for p, v, (name, _, _) in zip(params, new, table):
        p._value = v if scale(name) == 1.0 else v * scale(name)
    return table, params


def leaf_reader(cfg, seed):
    draw = weights.leaf_reader(reference.leaf_table(cfg), seed,
                               DTYPES[cfg["torch_dtype"]])
    scale = leaf_scale(cfg)
    return lambda name: draw(name) * scale(name)
