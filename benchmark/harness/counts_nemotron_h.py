"""Operations and bytes that the Nemotron-H-shaped decoder needs (layers of
ONE part each: a Mamba-2 mixer with groups, attention without positions, or
ungated relu^2 routed experts of which this chip holds its share beside one
shared expert), computed from shapes; the family's ``counts.py``. Hand counts
for ``nemotron-3-nano-30b-a3b-l14-ep2`` are in PERF.md section 3 and are
asserted by ``tests/test_nemotron_h_benchmark.py``.

A matrix product of (m, k) by (k, n) is 2 m k n operations. A count is of
what must be read or multiplied, never of what the program happens to do.
"""
from __future__ import annotations

from benchmark.harness.counts import decode_context_tokens
from benchmark.harness.counts_granitemoehybrid import ITEMSIZE, causal_pairs


def _d(cfg):
    nh, hp = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    kinds = str(cfg["hybrid_override_pattern"])
    held = int(cfg["n_routed_experts"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        layers=len(kinds), state_layers=kinds.count("M"),
        attn_layers=kinds.count("*"), expert_layers=kinds.count("E"),
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        fe=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["n_shared_experts"])
        * int(cfg["moe_shared_expert_intermediate_size"]),
        held=held, experts=int(cfg.get("published_experts", held)),
        top_k=int(cfg["num_experts_per_tok"]),
        mh=nh, mp=hp, d_in=nh * hp, groups=int(cfg["n_groups"]),
        n=int(cfg["ssm_state_size"]), conv=int(cfg["conv_kernel"]))


def is_family(cfg):
    return cfg.get("family") == "nemotron_h"


def conv_dim(cfg):
    """What the convolution runs over: ``[xs | B | C]``, B and C a group."""
    m = _d(cfg)
    return m["d_in"] + 2 * m["groups"] * m["n"]


def mamba_matmul_params(cfg):
    """What a token multiplies in a state-space mixer: in_proj and out_proj."""
    m = _d(cfg)
    return (m["h"] * (m["d_in"] + conv_dim(cfg) + m["mh"])
            + m["d_in"] * m["h"])


def mamba_params(cfg):
    """A state-space mixer whole: the two projections, the convolution's
    taps and bias, dt_bias, A_log, D and the gated norm."""
    m = _d(cfg)
    return (mamba_matmul_params(cfg) + conv_dim(cfg) * (m["conv"] + 1)
            + 3 * m["mh"] + m["d_in"])


def attention_params(cfg):
    """q and o (hidden x heads x head), k and v (hidden x kv heads x head)."""
    m = _d(cfg)
    return 2 * m["h"] * m["nq"] * m["hd"] + 2 * m["h"] * m["nkv"] * m["hd"]


def expert_layer_matmul_params(cfg):
    """What every token multiplies in an expert layer whatever it is routed
    to: the router (all published outputs) and the shared expert's two
    matrices."""
    m = _d(cfg)
    return m["h"] * m["experts"] + 2 * m["h"] * m["fs"]


def expert_layer_fixed_params(cfg):
    """An expert layer outside its routed experts: those and the router's
    selection bias."""
    return expert_layer_matmul_params(cfg) + _d(cfg)["experts"]


def expert_params(cfg):
    """One routed expert: up and down (no gate matrix)."""
    m = _d(cfg)
    return 2 * m["h"] * m["fe"]


def total_params(cfg):
    """Every parameter this chip holds: each layer's one part and its
    norm, the embedding, the final norm and the untied head."""
    m = _d(cfg)
    return (m["state_layers"] * mamba_params(cfg)
            + m["attn_layers"] * attention_params(cfg)
            + m["expert_layers"] * (expert_layer_fixed_params(cfg)
                                    + m["held"] * expert_params(cfg))
            + m["layers"] * m["h"] + 2 * m["v"] * m["h"] + m["h"])


def fixed_matmul_params_per_token(cfg):
    """Weights a token multiplies on its way through the layers whatever
    its routing: every mixer's and attention's projections, every router
    and shared expert. The head is left out (a mixed step runs it at one
    position a row), as is the embedding (a lookup)."""
    m = _d(cfg)
    return (m["state_layers"] * mamba_matmul_params(cfg)
            + m["attn_layers"] * attention_params(cfg)
            + m["expert_layers"] * expert_layer_matmul_params(cfg))


def recurrence_flops_per_token(cfg):
    """The state-space sum in its cheapest form, the recurrence: per
    token, state layer, head and state element the decay (a multiply), the
    input's outer product added in (a multiply and an add) and ``y = H C``
    (a multiply and an add)."""
    m = _d(cfg)
    return 5 * m["state_layers"] * m["mh"] * m["mp"] * m["n"]


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
    mixer and attention whole, each expert layer outside its routed
    experts, every layer's norm, the final norm and the untied head (the
    embedding is a lookup of a row a stream)."""
    m = _d(cfg)
    n = (m["state_layers"] * mamba_params(cfg)
         + m["attn_layers"] * attention_params(cfg)
         + m["expert_layers"] * expert_layer_fixed_params(cfg)
         + m["layers"] * m["h"] + m["h"] + m["v"] * m["h"])
    return n * ITEMSIZE[cfg["torch_dtype"]]


def state_bytes_per_slot(cfg):
    """What one request keeps over all state layers whatever its context:
    the recurrence's state in float32 and the convolution's last
    ``conv_kernel - 1`` inputs in the model's dtype."""
    m = _d(cfg)
    return m["state_layers"] * (
        m["mh"] * m["mp"] * m["n"] * 4
        + (m["conv"] - 1) * conv_dim(cfg) * ITEMSIZE[cfg["torch_dtype"]])


def cache_bytes_per_token(cfg):
    """What the K/V pool takes a token: the attention layers only."""
    m = _d(cfg)
    return (m["attn_layers"] * 2 * m["nkv"] * m["hd"]
            * ITEMSIZE[cfg["torch_dtype"]])


def paged_attention_needs(cfg, streams, prompt_len, new_tokens):
    """(operations, bytes) that paged decode attention needs over one
    closed batch: per stream, decode step and ATTENTION layer (the ``*`` of
    the pattern alone) the live keys and values read once
    (``cache_bytes_per_token`` has both, for all such layers) and QK^T and
    PV over them for every query head (4 x ctx x heads x head_dim)."""
    m = _d(cfg)
    ctx = decode_context_tokens(prompt_len, new_tokens) * streams
    return (4 * m["nq"] * m["hd"] * ctx * m["attn_layers"],
            ctx * cache_bytes_per_token(cfg))


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
