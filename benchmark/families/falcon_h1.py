"""The Falcon-H1-shaped decoder family (every layer a Mamba-2 state-space
mixer AND rotary grouped-query attention side by side on one normed input,
muP multipliers, a dense SwiGLU MLP; Falcon-H1-34B-Instruct): how a
configuration file becomes the PROGRAM's model, and where its plain
reference is. The only file of the benchmark that knows this family's model
class and parameter names. Serving only: the program does not train through
this model."""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from benchmark.harness import weights
from benchmark.reference import falcon_h1 as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

_LAYER = {"ln": "input_layernorm.weight",
          "in_w": "mamba.in_proj.weight",
          "conv_w": "mamba.conv1d.weight", "conv_b": "mamba.conv1d.bias",
          "dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log", "D": "mamba.D",
          "ssm_ln": "mamba.norm.weight", "out_w": "mamba.out_proj.weight",
          "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
          "v_w": "self_attn.v_proj.weight", "o_w": "self_attn.o_proj.weight",
          "ff_ln": "pre_ff_layernorm.weight",
          "gate_w": "feed_forward.gate_proj.weight",
          "up_w": "feed_forward.up_proj.weight",
          "down_w": "feed_forward.down_proj.weight"}
_TOP = {"top.embed": "model.embed_tokens.weight",
        "top.norm": "model.final_layernorm.weight",
        "top.head": "lm_head.weight"}
# published keys the program's config takes as they are
_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "head_dim", "attention_bias", "mlp_bias", "projectors_bias",
         "hidden_act", "mamba_d_ssm", "mamba_n_heads", "mamba_d_head",
         "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
         "mamba_chunk_size", "mamba_expand", "mamba_conv_bias",
         "mamba_proj_bias", "mamba_rms_norm", "mamba_norm_before_gate",
         "mamba_use_mlp", "attn_layer_indices", "embedding_multiplier",
         "lm_head_multiplier", "attention_in_multiplier",
         "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
         "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
         "mlp_expansion_factor", "rope_theta", "rope_scaling",
         "max_position_embeddings", "rms_norm_eps", "tie_word_embeddings",
         "num_logits_to_keep", "model_type")
# installed first, while the device holds nothing else: the two leaves of
# vocabulary x hidden (2.67 GB each at the cell's size)
_FIRST = ("top.embed", "top.head")


def leaf_scale(cfg):
    """name -> the power of two (exact in bf16) by which this family scales
    a leaf after the harness has drawn it like every other (a matrix is k /
    8192, standard deviation 0.018), for the program and the reference
    alike: 2 to the configuration's ``seeded_leaf_scale_log2[short name]``
    (absent: 1). The configuration's ``assumed`` gives each leaf's reason;
    the published multipliers are never changed."""
    exps = cfg.get("seeded_leaf_scale_log2", {})
    return lambda name: 2.0 ** int(exps.get(name.split(".")[-1], 0))


def program_path(leaf):
    """The reference's leaf name -> the program's parameter path."""
    if leaf in _TOP:
        return _TOP[leaf]
    layer, short = leaf.split(".", 1)
    return f"model.layers.{int(layer[1:])}.{_LAYER[short]}"


@contextlib.contextmanager
def _nothing_drawn():
    """While the model is built its weights are zeros
    (``set_global_initializer``): the seed's weights replace every leaf,
    and the program's own eager draw of a 261,120 x 5,120 leaf (float32,
    then scaled, then cast: 10.7 GB of temporaries beside 7.8 GB of layers)
    does not fit the chip."""
    from paddle_tpu.nn import initializer as init

    init.set_global_initializer(init.Constant(0.0))
    try:
        yield
    finally:
        init.set_global_initializer(None)


def build_model(cfg, tensor_parallel=False):
    """The program's model for ``cfg`` in the configuration's dtype, every
    matrix zeros (``install_weights`` replaces them all). A checkout whose
    program lacks the model fails here, at once."""
    from paddle_tpu.nlp.falcon_h1 import FalconH1Config, FalconH1ForCausalLM

    import paddle_tpu as paddle

    if tensor_parallel:
        raise NotImplementedError("the program has no tensor-parallel "
                                  "form of this family")
    paddle.set_default_dtype(cfg["torch_dtype"])
    with _nothing_drawn():
        return FalconH1ForCausalLM(FalconH1Config(
            **{k: cfg[k] for k in _KEYS}, dtype=cfg["torch_dtype"]))


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
    """Replace the model's parameters by the seed's weights, LEAF BY LEAF
    through ``weights.leaf_reader`` (the same ``(seed, index)`` as
    ``weights.fill``, so the same values), the two vocabulary leaves first:
    one call for all leaves would hold every leaf's draw at once. The old
    buffers are freed first; each new leaf takes its old one's placement."""
    table, params = parameters(model, cfg)
    places = [p._value.sharding for p in params]
    for p in params:
        p._value.delete()
    draw = leaf_reader(cfg, seed)
    order = sorted(range(len(table)),
                   key=lambda i: (table[i][0] not in _FIRST, i))
    for i in order:
        params[i]._value = jax.device_put(draw(table[i][0]), places[i])
    return table, params


def leaf_reader(cfg, seed):
    draw = weights.leaf_reader(reference.leaf_table(cfg), seed,
                               DTYPES[cfg["torch_dtype"]])
    scale = leaf_scale(cfg)

    def get_leaf(name):
        leaf, by = draw(name), scale(name)
        return leaf if by == 1.0 else leaf * by

    return get_leaf
