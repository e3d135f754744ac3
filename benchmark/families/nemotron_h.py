"""The Nemotron-H-shaped decoder family (layers of ONE part each: a Mamba-2
state-space mixer with groups, attention without positions, or ungated
relu^2 routed experts of which a chip holds its share beside one shared
expert; NVIDIA-Nemotron-3-Nano-30B-A3B): how a configuration file becomes
the PROGRAM's model, and where its plain reference is. The only file of the
benchmark that knows this family's model class and parameter names.
Serving only: the program does not train through this model."""
from __future__ import annotations

import jax.numpy as jnp

from benchmark.harness import weights
from benchmark.reference import nemotron_h as reference

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}

_LAYER = {"ln": "norm.weight",
          "in_w": "mixer.in_proj.weight",
          "conv_w": "mixer.conv1d.weight", "conv_b": "mixer.conv1d.bias",
          "dt_bias": "mixer.dt_bias", "A_log": "mixer.A_log", "D": "mixer.D",
          "ssm_ln": "mixer.norm.weight", "out_w": "mixer.out_proj.weight",
          "q_w": "mixer.q_proj.weight", "k_w": "mixer.k_proj.weight",
          "v_w": "mixer.v_proj.weight", "o_w": "mixer.o_proj.weight",
          "router_w": "mixer.gate.weight",
          "router_b": "mixer.gate.e_score_correction_bias",
          "e_up": "mixer.experts.up_proj",
          "e_down": "mixer.experts.down_proj",
          "s_up": "mixer.shared_experts.up_proj.weight",
          "s_down": "mixer.shared_experts.down_proj.weight"}
_TOP = {"top.embed": "backbone.embeddings.weight",
        "top.norm": "backbone.norm_f.weight", "top.head": "lm_head.weight"}
# published keys the program's config takes as they are
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "hybrid_override_pattern", "num_attention_heads",
         "num_key_value_heads", "head_dim", "attention_bias",
         "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "conv_kernel",
         "n_groups", "chunk_size", "expand", "use_conv_bias",
         "mamba_proj_bias", "use_bias", "mamba_hidden_act",
         "num_experts_per_tok", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "n_shared_experts",
         "intermediate_size", "mlp_hidden_act", "mlp_bias", "norm_topk_prob",
         "routed_scaling_factor", "n_group", "topk_group",
         "layer_norm_epsilon", "norm_eps", "residual_in_fp32",
         "tie_word_embeddings", "max_position_embeddings", "sliding_window",
         "rope_theta", "partial_rotary_factor", "time_step_min",
         "time_step_max", "time_step_floor", "rescale_prenorm_residual",
         "use_mamba_kernels", "num_logits_to_keep", "model_type")


def leaf_scale(cfg):
    """name -> the power of two (exact in bf16) by which this family scales
    a leaf after the harness has drawn it like every other (a matrix is k /
    8192, standard deviation 0.018), for the program and the reference
    alike: 2 to the configuration's ``seeded_leaf_scale_log2[short name]``
    (``conv_w``, ``D``, ``s_down``, ``e_down``, ``router_b``; absent: 1).

    Why ``conv_w`` and ``D`` (the Granite-4.0-H cell's lessons, PERF.md
    section 6, PR 32; the mixer is the same): the convolution's four taps
    at 0.018 leave ``xs``, ``B`` and ``C`` at ~0.02 and the recurrence's
    share of ``y`` far below ``D xs``, so a wrong state would move no
    logit. With the taps lifted, ``D`` (drawn near 1) is the second: the
    recurrence's ``H C`` is then ~16 x ``D xs``, a sum over ``C_t . B_s``
    that now and then cancels; ``y`` is small there, the gated norm scales
    it back up and the layer passes a rounding on twenty times larger, in
    any precision. ``D`` at ``H C``'s size gives ``y`` a floor. The Granite
    cell's third scale (a small embedding) has no reason here: the head is
    untied and nothing multiplies the embedding.

    Why the experts' down projections and the selection bias (PERF.md
    section 6, PR 39): a trained router's load is kept even (that is what
    ``e_score_correction_bias`` is trained for); the seeded one's is not.
    ``relu(h)^2`` is positive in every feature, so ``S_down`` / ``W_down``
    carry the features' MEAN into one fixed direction of the stream, the
    same for every token, and each later router reads it as an offset an
    expert (12 % of a router input's power by the last layer); the drawn
    bias (+-0.03 beside scores that spread 0.2) is a second offset. With
    both, a decode step's 384 choices touch 53.7-54.7 of the 64 held
    experts, BY SEED, and the quantum's time follows the count (~10 ms an
    expert: 601-613 ms over seven seeds, a spread of 1.19 % in a set of
    six where a new cell's ``gap_p95_ms`` may spread 1 %). With the down
    projections at 2^-2 and the bias at 2^-3 a step touches ~59 of 64
    whatever the seed (a uniform router would touch 60.8); the bias alone
    at 2^-3, the experts at unit scale, leaves 55.1-55.4: the down
    projections carry the larger offset. What the two down scales cost:
    a fault INSIDE an expert moves a logit a quarter as far as at the
    drawn size (PERF.md section 7 has what ``correct`` then refuses)."""
    exps = cfg.get("seeded_leaf_scale_log2", {})
    return lambda name: 2.0 ** int(exps.get(name.split(".")[-1], 0))


def program_path(leaf):
    """The reference's leaf name -> the program's parameter path."""
    if leaf in _TOP:
        return _TOP[leaf]
    layer, short = leaf.split(".", 1)
    return f"backbone.layers.{int(layer[1:])}.{_LAYER[short]}"


def build_model(cfg, tensor_parallel=False):
    """The program's model for ``cfg`` in the configuration's dtype, with
    whatever its own initializers gave (``install_weights`` replaces it).
    A checkout whose program lacks the model fails here, at once."""
    from paddle_tpu.nlp.nemotron_h import (
        NemotronHConfig, NemotronHForCausalLM)

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
    pcfg = NemotronHConfig(
        **{k: cfg[k] for k in _KEYS},
        n_routed_experts=int(cfg.get("published_experts", held)),
        held_experts=(int(lo), held), dtype=cfg["torch_dtype"])
    return NemotronHForCausalLM(pcfg)


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
