"""Operations and bytes that the Falcon-H1-shaped decoder needs (every layer
a Mamba-2 mixer AND rotary grouped-query attention on one normed input, then
a dense SwiGLU MLP), computed from shapes; the family's ``counts.py``. Hand
counts for ``falcon-h1-34b-l6`` are ISSUE 41's and PERF.md section 3's and
are asserted by ``tests/test_falcon_h1_benchmark.py``.

A matrix product of (m, k) by (k, n) is 2 m k n operations. A count is of
what must be read or multiplied, never of what the program happens to do.
"""
from __future__ import annotations

from benchmark.harness.counts import decode_context_tokens
from benchmark.harness.counts_granitemoehybrid import ITEMSIZE, causal_pairs


def _d(cfg):
    nh, hp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        f=int(cfg["intermediate_size"]),
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        mh=nh, mp=hp, d_in=nh * hp, groups=int(cfg["mamba_n_groups"]),
        n=int(cfg["mamba_d_state"]), conv=int(cfg["mamba_d_conv"]))


def is_family(cfg):
    return cfg.get("family") == "falcon_h1"


def conv_dim(cfg):
    """What the convolution runs over: ``[xs | B | C]``, B and C a group."""
    m = _d(cfg)
    return m["d_in"] + 2 * m["groups"] * m["n"]


def mamba_matmul_params(cfg):
    """What a token multiplies in the mixer: in_proj and out_proj."""
    m = _d(cfg)
    return (m["h"] * (m["d_in"] + conv_dim(cfg) + m["mh"])
            + m["d_in"] * m["h"])


def mamba_params(cfg):
    """The mixer whole: the two projections, the convolution's taps and
    bias, dt_bias, A_log, D and the gated norm."""
    m = _d(cfg)
    return (mamba_matmul_params(cfg) + conv_dim(cfg) * (m["conv"] + 1)
            + 3 * m["mh"] + m["d_in"])


def attention_params(cfg):
    """q and o (hidden x heads x head), k and v (hidden x kv heads x head)."""
    m = _d(cfg)
    return 2 * m["h"] * m["nq"] * m["hd"] + 2 * m["h"] * m["nkv"] * m["hd"]


def mlp_params(cfg):
    """gate, up and down."""
    m = _d(cfg)
    return 3 * m["h"] * m["f"]


def layer_params(cfg):
    """One layer: both branches, the MLP and the two norms."""
    return (attention_params(cfg) + mamba_params(cfg) + mlp_params(cfg)
            + 2 * _d(cfg)["h"])


def total_params(cfg):
    """Every parameter: the layers, the embedding, the untied head and the
    final norm."""
    m = _d(cfg)
    return m["layers"] * layer_params(cfg) + 2 * m["v"] * m["h"] + m["h"]


def matmul_params_per_token(cfg):
    """Weights a token multiplies on its way through the layers. The head
    is left out (a mixed step runs it at one position a row), as is the
    embedding (a lookup)."""
    return _d(cfg)["layers"] * (attention_params(cfg)
                                + mamba_matmul_params(cfg) + mlp_params(cfg))


def recurrence_flops_per_token(cfg):
    """The state-space sum in its cheapest form, the recurrence: per
    token, layer, head and state element the decay (a multiply), the
    input's outer product added in (a multiply and an add) and ``y = H C``
    (a multiply and an add)."""
    m = _d(cfg)
    return 5 * m["layers"] * m["mh"] * m["mp"] * m["n"]


def prefill_flops(cfg, tokens, requests, prompt_len):
    """Operations the prefill of ``requests`` prompts needs: 2 x the
    weights for each of the ``tokens`` valid tokens, the recurrence, and
    for every causal (query, key) pair, query head and layer QK^T and PV."""
    m = _d(cfg)
    attn = (4 * m["nq"] * m["hd"] * causal_pairs(prompt_len) * requests
            * m["layers"])
    return ((2 * matmul_params_per_token(cfg)
             + recurrence_flops_per_token(cfg)) * tokens + attn)


def weight_bytes_per_step(cfg):
    """Bytes of weights one decode step reads: every layer whole, the
    final norm and the untied head (the embedding is a lookup of a row a
    stream)."""
    m = _d(cfg)
    return ((m["layers"] * layer_params(cfg) + m["h"] + m["v"] * m["h"])
            * ITEMSIZE[cfg["torch_dtype"]])


def state_bytes_per_slot(cfg):
    """What one request keeps over all layers whatever its context: the
    recurrence's state in float32 and the convolution's last
    ``mamba_d_conv - 1`` inputs in the model's dtype."""
    m = _d(cfg)
    return m["layers"] * (
        m["mh"] * m["mp"] * m["n"] * 4
        + (m["conv"] - 1) * conv_dim(cfg) * ITEMSIZE[cfg["torch_dtype"]])


def cache_bytes_per_token(cfg):
    """What the K/V pool takes a token: K and V in every layer."""
    m = _d(cfg)
    return (m["layers"] * 2 * m["nkv"] * m["hd"]
            * ITEMSIZE[cfg["torch_dtype"]])


def paged_attention_needs(cfg, streams, prompt_len, new_tokens):
    """(operations, bytes) that paged decode attention needs over one
    closed batch: per stream, decode step and layer the live keys and
    values read once (``cache_bytes_per_token`` has both, for all layers)
    and QK^T and PV over them for every query head (4 x ctx x heads x
    head_dim)."""
    m = _d(cfg)
    ctx = decode_context_tokens(prompt_len, new_tokens) * streams
    return (4 * m["nq"] * m["hd"] * ctx * m["layers"],
            ctx * cache_bytes_per_token(cfg))


def decode_bytes_needed(cfg, batches, streams, prompt_len, new_tokens):
    """Bytes the decode steps of ``batches`` closed batches must move: the
    weights once a step (``new_tokens - 1`` steps a batch: the first token
    comes from prefill), each live slot's state read and written once a
    step, and the live keys and values read once a step."""
    steps = batches * (new_tokens - 1)
    keys = (decode_context_tokens(prompt_len, new_tokens) * streams
            * batches * cache_bytes_per_token(cfg))
    return (steps * weight_bytes_per_step(cfg)
            + steps * streams * 2 * state_bytes_per_slot(cfg) + keys)
