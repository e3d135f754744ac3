"""The Solar-Open2-shaped decoder family (three layers in four a Kimi Delta
Attention mixer whose heads each keep a matrix state under a delta rule and
a per-channel decay, the fourth a gated GQA without positions; routed
experts of which a chip holds its share beside one shared expert;
Solar-Open2-250B): how a configuration file becomes the PROGRAM's model, and
where its plain reference is. The only file of the benchmark that knows this
family's model class and parameter names. Serving only: the program does not
train through this model."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the scales of a configuration's ``seeded_leaf_scale_log2`` and the
# building with every leaf zeros are the Falcon-H1 family file's
from benchmark.families.falcon_h1 import _nothing_drawn, leaf_scale
from benchmark.harness import weights
from benchmark.reference import solar_open2 as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

_LAYER = {"ln1": "input_layernorm.weight",
          "q_w": "self_attn.q_proj.weight", "k_w": "self_attn.k_proj.weight",
          "v_w": "self_attn.v_proj.weight",
          "q_conv": "self_attn.q_conv1d.weight",
          "k_conv": "self_attn.k_conv1d.weight",
          "v_conv": "self_attn.v_conv1d.weight",
          "A_log": "self_attn.A_log", "fa_w": "self_attn.f_a_proj.weight",
          "fb_w": "self_attn.f_b_proj.weight", "dt_bias": "self_attn.dt_bias",
          "beta_w": "self_attn.b_proj.weight",
          "ga_w": "self_attn.g_a_proj.weight",
          "gb_w": "self_attn.g_b_proj.weight",
          "o_ln": "self_attn.o_norm.weight",
          "out_w": "self_attn.o_proj.weight",     # a KDA layer's W_o
          "o_w": "self_attn.o_proj.weight",       # a GQA layer's
          "g_w": "self_attn.gate_proj.weight",
          "ln2": "post_attention_layernorm.weight",
          "router_w": "mlp.gate.weight",
          "router_b": "mlp.gate.e_score_correction_bias",
          "e_gate_up": "mlp.experts.gate_up_proj",
          "e_down": "mlp.experts.down_proj",
          "s_gate": "mlp.shared_experts.gate_proj.weight",
          "s_up": "mlp.shared_experts.up_proj.weight",
          "s_down": "mlp.shared_experts.down_proj.weight"}
_TOP = {"top.embed": "model.embed_tokens.weight",
        "top.norm": "model.norm.weight", "top.head": "lm_head.weight"}
# published keys the program's config takes as they are
_KEYS = ("model_type", "partial_rotary_factor", "linear_attn_config",
         "hidden_size", "num_hidden_layers", "num_attention_heads",
         "head_dim", "num_key_value_heads", "vocab_size",
         "intermediate_size", "moe_intermediate_size", "rms_norm_eps",
         "rope_theta", "tie_word_embeddings", "max_position_embeddings",
         "first_k_dense_replace", "use_rope", "gqa_interval", "gqa_layers",
         "use_gqa_gate", "kda_use_full_proj", "kda_allow_neg_eigval",
         "n_shared_experts", "norm_topk_prob", "routed_scaling_factor",
         "num_experts_per_tok")
# installed first, while the device holds nothing else: the two leaves of
# vocabulary x hidden
_FIRST = ("top.embed", "top.head")


def program_path(leaf):
    """The reference's leaf name -> the program's parameter path."""
    if leaf in _TOP:
        return _TOP[leaf]
    layer, short = leaf.split(".", 1)
    return f"model.layers.{int(layer[1:])}.{_LAYER[short]}"


def build_model(cfg, tensor_parallel=False):
    """The program's model for ``cfg`` in the configuration's dtype, every
    leaf zeros (``install_weights`` replaces them all: the program's own
    eager draw of a stack of 40 experts is memory and time nobody reads). A
    checkout whose program lacks the model fails here, at once."""
    from paddle_tpu.nlp.solar_open2 import (
        SolarOpen2Config, SolarOpen2ForCausalLM)

    import paddle_tpu as paddle

    if tensor_parallel:
        raise NotImplementedError("the program has no tensor-parallel "
                                  "form of this family")
    paddle.set_default_dtype(cfg["torch_dtype"])
    # the file's n_routed_experts is what this chip HOLDS; the router
    # keeps the published width
    held = int(cfg["n_routed_experts"])
    lo, hi = cfg.get("held_experts", [0, held])
    if hi - lo != held:
        raise ValueError("held_experts is not n_routed_experts wide")
    with _nothing_drawn():
        return SolarOpen2ForCausalLM(SolarOpen2Config(
            **{k: cfg[k] for k in _KEYS},
            n_routed_experts=int(cfg.get("published_experts", held)),
            held_experts=(int(lo), held), dtype=cfg["torch_dtype"]))


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


def leaf_offset(cfg):
    """name -> what this family ADDS to a leaf after its scale, for the
    program and the reference alike: the configuration's
    ``seeded_leaf_offset[short name]`` (absent: 0). A power of two cannot
    move a leaf drawn round 0 away from it, and a KDA state that is to
    remember needs ``dt_bias`` round -3: the configuration's ``assumed``
    gives the reason and the reading."""
    by = cfg.get("seeded_leaf_offset", {})
    return lambda name: float(by.get(name.split(".")[-1], 0.0))


def leaf_reader(cfg, seed):
    draw = weights.leaf_reader(reference.leaf_table(cfg), seed,
                               DTYPES[cfg["torch_dtype"]])
    scale, offset = leaf_scale(cfg), leaf_offset(cfg)

    def get_leaf(name):
        leaf, by, plus = draw(name), scale(name), offset(name)
        if by != 1.0:
            leaf = leaf * by
        return leaf + jnp.asarray(plus, leaf.dtype) if plus else leaf

    return get_leaf
