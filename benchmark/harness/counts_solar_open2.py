"""Operations and bytes that the Solar-Open2-shaped decoder needs (three
layers in four a Kimi Delta Attention mixer whose heads each keep a ``d x d``
float32 state, the fourth a gated GQA without positions; every layer routed
experts of which this chip holds its share beside one shared expert),
computed from shapes; the family's ``counts.py``. Hand counts for
``solar-open2-250b-l4-ep8`` are in PERF.md section 3 and are asserted by
``tests/test_solar_open2_benchmark.py``.

A matrix product of (m, k) by (k, n) is 2 m k n operations. A count is of
what must be read or multiplied, never of what the program happens to do:
the delta rule is counted AS WRITTEN (the recurrence), not as the chunked
algorithm's triangular solve, so a share reads the same work whatever
implements it.
"""
from __future__ import annotations

from benchmark.harness.counts import decode_context_tokens
from benchmark.harness.counts_granitemoehybrid import ITEMSIZE, causal_pairs


def _d(cfg):
    lin = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    gqa = len(cfg["gqa_layers"])
    held = int(cfg["n_routed_experts"])
    kh, kd = int(lin["num_heads"]), int(lin["head_dim"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]), layers=layers,
        kda_layers=layers - gqa, gqa_layers=gqa,
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        kh=kh, kd=kd, d_in=kh * kd, conv=int(lin["short_conv_kernel_size"]),
        fe=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        held=held, experts=int(cfg.get("published_experts", held)),
        top_k=int(cfg["num_experts_per_tok"]))


def is_family(cfg):
    return cfg.get("family") == "solar_open2"


def kda_matmul_params(cfg):
    """What a token multiplies in a KDA mixer: q, k, v and o (hidden x H d
    each), the two rank-d pairs (hidden x d, d x H d) and beta (hidden x
    H)."""
    m = _d(cfg)
    pair = m["h"] * m["kd"] + m["kd"] * m["d_in"]
    return 4 * m["h"] * m["d_in"] + 2 * pair + m["h"] * m["kh"]


def kda_params(cfg):
    """A KDA mixer whole: those, the three convolutions' taps, ``A_log``,
    ``dt_bias`` and the output norm."""
    m = _d(cfg)
    return (kda_matmul_params(cfg) + 3 * m["d_in"] * m["conv"] + m["kh"]
            + m["d_in"] + m["kd"])


def attention_params(cfg):
    """q, o and the gate (hidden x heads x head), k and v (hidden x kv
    heads x head)."""
    m = _d(cfg)
    return 3 * m["h"] * m["nq"] * m["hd"] + 2 * m["h"] * m["nkv"] * m["hd"]


def expert_layer_matmul_params(cfg):
    """What every token multiplies in a layer's feed-forward whatever it is
    routed to: the router (all published outputs) and the shared expert's
    three matrices."""
    m = _d(cfg)
    return m["h"] * m["experts"] + 3 * m["h"] * m["fs"]


def expert_layer_fixed_params(cfg):
    """A layer's feed-forward outside its routed experts: those and the
    router's selection bias."""
    return expert_layer_matmul_params(cfg) + _d(cfg)["experts"]


def expert_params(cfg):
    """One routed expert: gate, up and down."""
    m = _d(cfg)
    return 3 * m["h"] * m["fe"]


def total_params(cfg):
    """Every parameter this chip holds: each layer's mixer, feed-forward
    and two norms, the embedding, the final norm and the untied head."""
    m = _d(cfg)
    return (m["kda_layers"] * kda_params(cfg)
            + m["gqa_layers"] * attention_params(cfg)
            + m["layers"] * (expert_layer_fixed_params(cfg)
                             + m["held"] * expert_params(cfg) + 2 * m["h"])
            + 2 * m["v"] * m["h"] + m["h"])


def fixed_matmul_params_per_token(cfg):
    """Weights a token multiplies on its way through the layers whatever
    its routing: every mixer's products, every router and shared expert.
    The head is left out (a mixed step runs it at one position a row), as
    is the embedding (a lookup)."""
    m = _d(cfg)
    return (m["kda_layers"] * kda_matmul_params(cfg)
            + m["gqa_layers"] * attention_params(cfg)
            + m["layers"] * expert_layer_matmul_params(cfg))


def recurrence_flops_per_token(cfg):
    """The delta rule as written: per token, KDA layer, head and state
    element the decay (a multiply), ``S'^T k`` (a multiply and an add), the
    rank-one correction added in (a multiply and an add) and ``o = S^T q``
    (a multiply and an add): 7 x d x d a head."""
    m = _d(cfg)
    return 7 * m["kda_layers"] * m["kh"] * m["kd"] * m["kd"]


def prefill_flops(cfg, tokens, routed_rows, requests, prompt_len):
    """Operations the prefill of ``requests`` prompts needs: 2 x the fixed
    weights for each of the ``tokens`` valid tokens, 2 x one expert for
    each of the ``routed_rows`` the held experts were handed (the program's
    count), the recurrence, and for every causal (query, key) pair, query
    head and GQA layer QK^T and PV."""
    m = _d(cfg)
    attn = (4 * m["nq"] * m["hd"] * causal_pairs(prompt_len) * requests
            * m["gqa_layers"])
    return (2 * fixed_matmul_params_per_token(cfg) * tokens
            + 2 * expert_params(cfg) * routed_rows
            + recurrence_flops_per_token(cfg) * tokens + attn)


def fixed_weight_bytes_per_step(cfg):
    """Bytes of weights one decode step reads whatever the routing: every
    mixer whole, each layer's feed-forward outside its routed experts and
    its two norms, the final norm and the untied head (the embedding is a
    lookup of a row a stream)."""
    m = _d(cfg)
    n = (m["kda_layers"] * kda_params(cfg)
         + m["gqa_layers"] * attention_params(cfg)
         + m["layers"] * (expert_layer_fixed_params(cfg) + 2 * m["h"])
         + m["h"] + m["v"] * m["h"])
    return n * ITEMSIZE[cfg["torch_dtype"]]


def matrix_state_bytes(cfg):
    """One KDA layer's float32 state a slot: H heads of d x d."""
    m = _d(cfg)
    return m["kh"] * m["kd"] * m["kd"] * 4


def state_bytes_per_slot(cfg):
    """What one request keeps over all KDA layers whatever its context:
    the matrix state in float32 and the three convolutions' last
    ``short_conv_kernel_size - 1`` inputs in the model's dtype."""
    m = _d(cfg)
    return m["kda_layers"] * (
        matrix_state_bytes(cfg)
        + 3 * (m["conv"] - 1) * m["d_in"] * ITEMSIZE[cfg["torch_dtype"]])


def cache_bytes_per_token(cfg):
    """What the K/V pool takes a token: the GQA layers only."""
    m = _d(cfg)
    return (m["gqa_layers"] * 2 * m["nkv"] * m["hd"]
            * ITEMSIZE[cfg["torch_dtype"]])


def paged_attention_needs(cfg, streams, prompt_len, new_tokens):
    """(operations, bytes) that paged decode attention needs over one
    closed batch: per stream, decode step and GQA layer the live keys and
    values read once and QK^T and PV over them for every query head."""
    m = _d(cfg)
    ctx = decode_context_tokens(prompt_len, new_tokens) * streams
    return (4 * m["nq"] * m["hd"] * ctx * m["gqa_layers"],
            ctx * cache_bytes_per_token(cfg))


def kda_update_needs(cfg, streams, new_tokens):
    """(operations, bytes) the one-step delta rule needs over one closed
    batch's decode steps: per stream, step and KDA layer the matrix state
    read once and written once, the head's q, k, v, log decay and beta in
    and o out (float32), and 7 operations a state element. NEEDED steps:
    ``new_tokens - 1`` a stream (the first token comes from prefill). The
    engine runs whole quanta, 32 x 8 = 256 kernel steps for 255 needed,
    and the last one's token is read by nobody: it is in the kernel's time
    and not in these counts, so ``kda_decode_update_roofline`` reads 1 /
    256 (0.4 %) under what the kernel does a step it runs."""
    m = _d(cfg)
    each = streams * (new_tokens - 1) * m["kda_layers"]
    vectors = m["kh"] * (5 * m["kd"] + 1) * 4
    return (each * 7 * m["kh"] * m["kd"] * m["kd"],
            each * (2 * matrix_state_bytes(cfg) + vectors))


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
