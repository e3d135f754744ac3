"""Operations and bytes that the AFMoE-shaped decoder needs (window and full
attention layers mixed, gated attention, routed experts beside a shared
one), computed from shapes; the family's ``counts.py``. Hand counts for
``trinity-mini-l5`` are in PERF.md section 3 and are asserted by
``tests/test_afmoe_benchmark.py``.

A matrix product of (m, k) by (k, n) is 2 m k n operations. A window layer's
query attends ``min(t + 1, sliding_window)`` keys, a full layer's ``t + 1``.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _d(cfg):
    kinds = tuple(cfg["layer_types"])
    return dict(
        h=int(cfg["hidden_size"]), nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
        experts=int(cfg["num_experts"]),
        shared=int(cfg["num_shared_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        dense=int(cfg["num_dense_layers"]), v=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        window=int(cfg["sliding_window"]),
        window_layers=kinds.count("sliding_attention"),
        full_layers=kinds.count("full_attention"))


def is_family(cfg):
    return cfg.get("family") == "afmoe"


def attention_matmul_params(cfg):
    """q, k, v, o and the gate."""
    m = _d(cfg)
    return (3 * m["h"] * m["nq"] * m["hd"] + 2 * m["h"] * m["nkv"] * m["hd"])


def attention_params(cfg):
    """The matrices and the two per-head norms."""
    return attention_matmul_params(cfg) + 2 * _d(cfg)["hd"]


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    m = _d(cfg)
    return 3 * m["h"] * m["fe"]


def expert_layer_fixed_params(cfg):
    """What every token multiplies in an expert layer's feed-forward
    whatever it is routed to: the router and the shared expert."""
    m = _d(cfg)
    return m["h"] * m["experts"] + 3 * m["h"] * m["shared"] * m["fe"]


def dense_layer_params(cfg):
    """Attention, four norms and the dense SwiGLU."""
    m = _d(cfg)
    return attention_params(cfg) + 4 * m["h"] + 3 * m["h"] * m["f"]


def expert_layer_params(cfg):
    """Attention, four norms, router, selection bias, shared expert and
    every routed expert."""
    m = _d(cfg)
    return (attention_params(cfg) + 4 * m["h"]
            + expert_layer_fixed_params(cfg) + m["experts"]
            + m["experts"] * expert_params(cfg))


def top_params(cfg):
    """Embedding, untied head and the final norm."""
    m = _d(cfg)
    return 2 * m["v"] * m["h"] + m["h"]


def total_params(cfg):
    m = _d(cfg)
    return (m["dense"] * dense_layer_params(cfg)
            + (m["layers"] - m["dense"]) * expert_layer_params(cfg)
            + top_params(cfg))


def active_matmul_params_per_token(cfg):
    """Weights one token multiplies on its way through the layers: every
    layer's attention, the dense layers' feed-forward, and in an expert
    layer the router, the shared expert and ``num_experts_per_tok`` routed
    experts. The output head is left out (a mixed step runs it at one
    position a row) and so is the embedding (a lookup)."""
    m = _d(cfg)
    return (m["layers"] * attention_matmul_params(cfg)
            + m["dense"] * 3 * m["h"] * m["f"]
            + (m["layers"] - m["dense"])
            * (expert_layer_fixed_params(cfg)
               + m["top_k"] * expert_params(cfg)))


def attended_pairs(seq, window=0):
    """(query, key) pairs of one sequence and layer: ``sum over t of t +
    1``, clamped to ``window`` keys a query in a window layer."""
    if not window or seq <= window:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def prefill_flops(cfg, tokens, requests, prompt_len):
    """Operations the prefill of ``requests`` prompts needs: 2 x the active
    weights for each of the ``tokens`` valid tokens, and for every attended
    (query, key) pair and query head QK^T and PV over ``head_dim``, the
    pairs clamped by layer kind."""
    m = _d(cfg)
    pairs = (m["full_layers"] * attended_pairs(prompt_len)
             + m["window_layers"] * attended_pairs(prompt_len, m["window"]))
    return (2 * active_matmul_params_per_token(cfg) * tokens
            + 4 * m["nq"] * m["hd"] * pairs * requests)


def fixed_weight_bytes_per_step(cfg):
    """Bytes of weights one decode step reads whatever the routing: every
    layer's attention and four norms, the dense feed-forward, each expert
    layer's router, selection bias and shared expert, the final norm and
    the head; of the embedding only the rows looked up (left out)."""
    m = _d(cfg)
    n = (m["layers"] * (attention_params(cfg) + 4 * m["h"])
         + m["dense"] * 3 * m["h"] * m["f"]
         + (m["layers"] - m["dense"])
         * (expert_layer_fixed_params(cfg) + m["experts"])
         + m["h"] + m["h"] * m["v"])
    return n * ITEMSIZE[cfg["torch_dtype"]]


def key_bytes(cfg):
    """One cached position of one layer: K and V rows."""
    m = _d(cfg)
    return 2 * m["nkv"] * m["hd"] * ITEMSIZE[cfg["torch_dtype"]]


def cache_bytes_per_token(cfg):
    """What the K/V block pool takes a token: the full layers only."""
    return _d(cfg)["full_layers"] * key_bytes(cfg)


def ring_tokens(cfg):
    """Rows of a window layer's ring: ``sliding_window`` + the engine's
    ``prefill_chunk``, in whole blocks of ``block_size``."""
    eng = cfg["engine"]
    bs = int(eng["block_size"])
    return -(-(int(cfg["sliding_window"]) + int(eng["prefill_chunk"]))
             // bs) * bs


def window_bytes_per_slot(cfg):
    """What one request keeps over all window layers whatever its context:
    a K and a V ring a layer."""
    return _d(cfg)["window_layers"] * ring_tokens(cfg) * key_bytes(cfg)


def decode_bytes_needed(cfg, steps, experts_touched, full_keys, window_keys):
    """Bytes ``steps`` decode steps must read: the fixed weights once a
    step, each expert that got a row once (``experts_touched``: the
    program's count over all expert layers and steps, never all of them by
    assumption), and the keys attended: ``full_keys`` / ``window_keys`` a
    layer of the kind (the program's spans: over live rows and steps ``len``
    / ``min(len, sliding_window)``), K and V. A ring is read whole
    (``ring_tokens`` rows); what is NEEDED is the window."""
    m = _d(cfg)
    return (steps * fixed_weight_bytes_per_step(cfg)
            + experts_touched * expert_params(cfg)
            * ITEMSIZE[cfg["torch_dtype"]]
            + (m["full_layers"] * full_keys
               + m["window_layers"] * window_keys) * key_bytes(cfg))
