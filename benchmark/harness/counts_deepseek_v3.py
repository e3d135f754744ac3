"""Operations and bytes that the DeepSeek-V3-shaped decoder needs (latent
attention, routed experts beside shared ones), computed from shapes; the
family's ``counts.py``. Hand counts for ``kanana-2-30b-a3b-l8`` are in
PERF.md section 3 and are asserted by ``tests/test_deepseek_v3_benchmark.py``.

A matrix product of (m, k) by (k, n) is 2 m k n operations.
"""
from __future__ import annotations

from benchmark.harness.counts import decode_context_tokens


def _d(cfg):
    return dict(
        h=int(cfg["hidden_size"]), nh=int(cfg["num_attention_heads"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]), r=int(cfg["kv_lora_rank"]),
        f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
        experts=int(cfg["n_routed_experts"]),
        shared=int(cfg["n_shared_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        dense=int(cfg["first_k_dense_replace"]), v=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]))


def is_family(cfg):
    return cfg.get("family") == "deepseek_v3"


def attention_matmul_params(cfg):
    """q, the compressing kv_a, the up-projecting kv_b and o."""
    m = _d(cfg)
    return (m["h"] * m["nh"] * (m["nope"] + m["rope"])
            + m["h"] * (m["r"] + m["rope"])
            + m["r"] * m["nh"] * (m["nope"] + m["dv"])
            + m["nh"] * m["dv"] * m["h"])


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    m = _d(cfg)
    return 3 * m["h"] * m["fe"]


def expert_layer_fixed_params(cfg):
    """What every token multiplies in an expert layer's feed-forward
    whatever it is routed to: the router and the shared experts."""
    m = _d(cfg)
    return m["h"] * m["experts"] + 3 * m["h"] * m["shared"] * m["fe"]


def active_matmul_params_per_token(cfg):
    """Weights one token multiplies on its way through the layers: every
    layer's attention, the dense layers' feed-forward, and in an expert
    layer the router, the shared experts and ``num_experts_per_tok``
    routed experts. The output head is left out (a mixed step runs it at
    one position a row) and so is the embedding (a lookup)."""
    m = _d(cfg)
    return (m["layers"] * attention_matmul_params(cfg)
            + m["dense"] * 3 * m["h"] * m["f"]
            + (m["layers"] - m["dense"])
            * (expert_layer_fixed_params(cfg)
               + m["top_k"] * expert_params(cfg)))


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def prefill_flops(cfg, tokens, requests, prompt_len):
    """Operations the prefill of ``requests`` prompts needs: 2 x the active
    weights for each of the ``tokens`` valid tokens, and for every causal
    (query, key) pair, head and layer QK^T over the 192-wide key and PV
    over the 128-wide value, un-absorbed (the cheapest form)."""
    m = _d(cfg)
    attn = (2 * m["nh"] * (m["nope"] + m["rope"] + m["dv"])
            * causal_pairs(prompt_len) * requests * m["layers"])
    return 2 * active_matmul_params_per_token(cfg) * tokens + attn


def fixed_weight_bytes_per_step(cfg, bytes_per_weight=2):
    """Bytes of weights one decode step reads whatever the routing: every
    layer's attention matrices and three norms (two of the layer, the
    latent's), the dense feed-forward, each expert layer's router, its
    selection bias and its shared experts, the final norm and the head; of
    the embedding only the rows looked up (left out)."""
    m = _d(cfg)
    per_layer = attention_matmul_params(cfg) + 2 * m["h"] + m["r"]
    n = (m["layers"] * per_layer + m["dense"] * 3 * m["h"] * m["f"]
         + (m["layers"] - m["dense"])
         * (expert_layer_fixed_params(cfg) + m["experts"])
         + m["h"] + m["h"] * m["v"])
    return n * bytes_per_weight


def latent_cache_bytes(cfg, streams, prompt_len, new_tokens,
                       bytes_per_value=2):
    """Bytes of the latent cache the decode steps of one closed batch
    read: per stream, step and layer the live rows once, each
    ``kv_lora_rank + qk_rope_head_dim`` values."""
    m = _d(cfg)
    return (decode_context_tokens(prompt_len, new_tokens) * streams
            * m["layers"] * (m["r"] + m["rope"]) * bytes_per_value)


def cache_bytes_per_token(cfg, bytes_per_value=2):
    """What a latent pool takes a token over all layers."""
    m = _d(cfg)
    return m["layers"] * (m["r"] + m["rope"]) * bytes_per_value


def decode_bytes_needed(cfg, steps, experts_touched, batches, streams,
                        prompt_len, new_tokens, bytes_per_weight=2):
    """Bytes ``steps`` decode steps over ``batches`` closed batches must
    read: the fixed weights once a step, each expert that got a row once
    (``experts_touched``: the program's count over all expert layers and
    steps, never all of them by assumption), and the live latent cache."""
    return (steps * fixed_weight_bytes_per_step(cfg, bytes_per_weight)
            + experts_touched * expert_params(cfg) * bytes_per_weight
            + batches * latent_cache_bytes(cfg, streams, prompt_len,
                                           new_tokens))
