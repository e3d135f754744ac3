"""Operations and bytes that the algorithm needs, computed from shapes.
Kept with the benchmark so that no PR that claims a gain can change what a
share of a peak means. Hand counts for both configurations are in PERF.md
section 3 and are asserted by ``selfcheck.py``.

A matrix product of (m, k) by (k, n) is 2 m k n operations.
"""
from __future__ import annotations


def _d(cfg):
    h = int(cfg["hidden_size"])
    nh = int(cfg["num_attention_heads"])
    nkv = int(cfg.get("num_key_value_heads") or nh)
    d = int(cfg.get("head_dim") or h // nh)
    return h, nh, nkv, d, int(cfg["intermediate_size"]), \
        int(cfg["vocab_size"]), int(cfg["num_hidden_layers"])


def layer_matmul_params(cfg):
    """Weights of one layer that every token multiplies."""
    h, nh, nkv, d, f, _, _ = _d(cfg)
    return h * nh * d + 2 * h * nkv * d + nh * d * h + 3 * h * f


def attended_pairs(seq, window=None):
    """(query, key) pairs of one causal sequence: query i sees keys
    max(0, i - window + 1) .. i."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def train_flops_per_token(cfg, seq):
    """Forward plus backward operations per trained token, with no
    recomputation: 3 x (2 x weights multiplied + attention's two products
    over the pairs the mask lets through). The embedding lookup multiplies
    nothing; the output head does."""
    h, nh, _, d, _, v, layers = _d(cfg)
    pairs = attended_pairs(seq, cfg.get("sliding_window"))
    attn = 4 * nh * d * pairs / seq          # QK^T and PV, per token
    fwd = layers * (2 * layer_matmul_params(cfg) + attn) + 2 * h * v
    return 3 * fwd


def flash_kernel_flops(cfg, batch, seq, layers=None):
    """Operations the three flash-attention kernels of one training step
    need, given what each is handed: forward QK^T and PV (4 per pair, head
    and lane); dq recomputes the scores, then dP and dQ (6); dkv recomputes
    the scores, then dV, dP and dK (8). Over the pairs inside the causal
    band only, so tiles the kernels compute and mask do not count."""
    _, nh, _, d, _, _, n_layers = _d(cfg)
    pairs = attended_pairs(seq, cfg.get("sliding_window")) * batch
    per = nh * d * pairs * (layers if layers is not None else n_layers)
    return {"flash_attention_fwd": 4 * per, "flash_attention_bwd_dq": 6 * per,
            "flash_attention_bwd_dkv": 8 * per}


def weight_bytes_per_token_step(cfg, bytes_per_weight=2):
    """Bytes of weights one decode step must read: every layer's matrices,
    norms and biases and the output head, once; of the embedding only the
    rows looked up (left out)."""
    h, nh, nkv, d, _, v, layers = _d(cfg)
    per_layer = layer_matmul_params(cfg) + 2 * h
    if cfg.get("attention_bias"):
        per_layer += nh * d + 2 * nkv * d
    return (layers * per_layer + h + h * v) * bytes_per_weight


def decode_context_tokens(prompt_len, new_tokens):
    """Keys one stream attends over its decode steps: the first new token
    comes from prefill; decode step i (0-based) sees prompt + i + 1 keys."""
    steps = new_tokens - 1
    return steps * (prompt_len + 1) + steps * (steps - 1) // 2


def paged_attention_needs(cfg, streams, prompt_len, new_tokens,
                          bytes_per_value=2):
    """(operations, bytes) that paged decode attention needs over one
    closed batch: per stream, step and layer it reads the live keys and
    values once (2 x ctx x kv heads x d values) and does QK^T and PV over
    them for every query head (4 x ctx x heads x d)."""
    _, nh, nkv, d, _, _, layers = _d(cfg)
    ctx = decode_context_tokens(prompt_len, new_tokens) * streams * layers
    return 4 * nh * d * ctx, 2 * nkv * d * ctx * bytes_per_value


def decode_bytes_needed(cfg, streams, prompt_len, new_tokens):
    """Bytes the decode steps of one closed batch must read: the weights
    once per token step (all streams share a step) and the live cache."""
    _, cache = paged_attention_needs(cfg, streams, prompt_len, new_tokens)
    return (new_tokens - 1) * weight_bytes_per_token_step(cfg) + cache


def roofline_seconds(flops, nbytes, peaks):
    """The least time the chip could take and which bound sets it."""
    t_f = flops / peaks["bf16_flops"]
    t_b = nbytes / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
