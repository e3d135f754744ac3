"""Operations and bytes that the Granite-4.0-H-shaped decoder needs (Mamba-2
state-space layers beside attention, routed experts of which this chip holds
its share), computed from shapes; the family's ``counts.py``. Hand counts for
``granite-4.0-h-small-l10-ep2`` are in PERF.md section 3 and are asserted by
``tests/test_granitemoehybrid_benchmark.py``.

A matrix product of (m, k) by (k, n) is 2 m k n operations. A count is of
what must be read or multiplied, never of what the program happens to do.
"""
from __future__ import annotations

from benchmark.harness.counts import decode_context_tokens

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _d(cfg):
    nh, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    kinds = list(cfg["layer_types"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        layers=len(kinds), state_layers=kinds.count("mamba"),
        attn_layers=kinds.count("attention"),
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]),
        hd=int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]),
        fe=int(cfg["intermediate_size"]),
        fs=int(cfg["shared_intermediate_size"]),
        held=int(cfg["num_local_experts"]),
        experts=int(cfg.get("published_experts", cfg["num_local_experts"])),
        top_k=int(cfg["num_experts_per_tok"]),
        mh=nh, mp=hd, d_in=nh * hd, n=int(cfg["mamba_d_state"]),
        conv=int(cfg["mamba_d_conv"]))


def is_family(cfg):
    return cfg.get("family") == "granitemoehybrid"


def mamba_matmul_params(cfg):
    """What a token multiplies in a state-space mixer: in_proj and out_proj."""
    m = _d(cfg)
    return (m["h"] * (2 * m["d_in"] + 2 * m["n"] + m["mh"])
            + m["d_in"] * m["h"])


def mamba_params(cfg):
    """A state-space mixer whole: the two projections, the convolution's
    taps and bias, dt_bias, A_log, D and the gated norm."""
    m = _d(cfg)
    conv_dim = m["d_in"] + 2 * m["n"]
    return (mamba_matmul_params(cfg) + conv_dim * (m["conv"] + 1)
            + 3 * m["mh"] + m["d_in"])


def attention_params(cfg):
    """q and o (hidden x hidden), k and v (hidden x kv heads x head)."""
    m = _d(cfg)
    return 2 * m["h"] * m["nq"] * m["hd"] + 2 * m["h"] * m["nkv"] * m["hd"]


def beside_mixer_matmul_params(cfg):
    """What every token multiplies in a layer's feed-forward whatever it is
    routed to: the router (all published outputs) and the shared MLP."""
    m = _d(cfg)
    return m["h"] * m["experts"] + 3 * m["h"] * m["fs"]


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    m = _d(cfg)
    return 3 * m["h"] * m["fe"]


def total_params(cfg):
    """Every parameter this chip holds (the tied embedding once)."""
    m = _d(cfg)
    return (m["state_layers"] * mamba_params(cfg)
            + m["attn_layers"] * attention_params(cfg)
            + m["layers"] * (beside_mixer_matmul_params(cfg) + 2 * m["h"]
                             + m["held"] * expert_params(cfg))
            + m["v"] * m["h"] + m["h"])


def fixed_matmul_params_per_token(cfg):
    """Weights a token multiplies on its way through the layers whatever
    its routing: every mixer's projections, every router and shared MLP.
    The tied head is left out (a mixed step runs it at one position a row),
    as is the embedding (a lookup)."""
    m = _d(cfg)
    return (m["state_layers"] * mamba_matmul_params(cfg)
            + m["attn_layers"] * attention_params(cfg)
            + m["layers"] * beside_mixer_matmul_params(cfg))


def recurrence_flops_per_token(cfg):
    """The state-space sum in its cheapest form, the recurrence: per
    token, state layer, head and state element the decay (a multiply), the
    input's outer product added in (a multiply and an add) and ``y = H C``
    (a multiply and an add)."""
    m = _d(cfg)
    return 5 * m["state_layers"] * m["mh"] * m["mp"] * m["n"]


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def prefill_flops(cfg, tokens, routed_rows, requests, prompt_len):
    """Operations the prefill of ``requests`` prompts needs: 2 x the fixed
    weights for each of the ``tokens`` valid tokens, 2 x one expert for
    each of the ``routed_rows`` the held experts were handed (the program's
    count, not half of top-k by assumption), the recurrence, and for every
    causal (query, key) pair, query head and attention layer QK^T and PV."""
    m = _d(cfg)
    attn = (4 * m["nq"] * m["hd"] * causal_pairs(prompt_len) * requests
            * m["attn_layers"])
    return (2 * fixed_matmul_params_per_token(cfg) * tokens
            + 2 * expert_params(cfg) * routed_rows
            + recurrence_flops_per_token(cfg) * tokens + attn)


def fixed_weight_bytes_per_step(cfg):
    """Bytes of weights one decode step reads whatever the routing: every
    mixer whole, each layer's two norms, router and shared MLP, the final
    norm and the tied head (the embedding, read as the head)."""
    m = _d(cfg)
    n = (m["state_layers"] * mamba_params(cfg)
         + m["attn_layers"] * attention_params(cfg)
         + m["layers"] * (beside_mixer_matmul_params(cfg) + 2 * m["h"])
         + m["h"] + m["v"] * m["h"])
    return n * ITEMSIZE[cfg["torch_dtype"]]


def state_bytes_per_slot(cfg):
    """What one request keeps over all state layers whatever its context:
    the recurrence's state in float32 and the convolution's last
    ``mamba_d_conv - 1`` inputs in the model's dtype."""
    m = _d(cfg)
    conv_dim = m["d_in"] + 2 * m["n"]
    return m["state_layers"] * (
        m["mh"] * m["mp"] * m["n"] * 4
        + (m["conv"] - 1) * conv_dim * ITEMSIZE[cfg["torch_dtype"]])


def cache_bytes_per_token(cfg):
    """What the K/V pool takes a token: the attention layers only."""
    m = _d(cfg)
    return (m["attn_layers"] * 2 * m["nkv"] * m["hd"]
            * ITEMSIZE[cfg["torch_dtype"]])


def decode_bytes_needed(cfg, experts_touched, batches, streams, prompt_len,
                        new_tokens):
    """Bytes the decode steps of ``batches`` closed batches must move: the
    fixed weights once a step (``new_tokens - 1`` steps a batch: the first
    token comes from prefill), each HELD expert that got a row once
    (``experts_touched``: the program's count over all layers and steps),
    each live slot's state read and written once a step, and the live
    keys and values read once a step."""
    steps = batches * (new_tokens - 1)
    keys = (decode_context_tokens(prompt_len, new_tokens) * streams
            * batches * cache_bytes_per_token(cfg))
    return (steps * fixed_weight_bytes_per_step(cfg)
            + experts_touched * expert_params(cfg)
            * ITEMSIZE[cfg["torch_dtype"]]
            + steps * streams * 2 * state_bytes_per_slot(cfg) + keys)
