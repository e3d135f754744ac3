"""Plain reference of the DeepSeek-V3-shaped decoder (``model_type``
``deepseek_v3``; here kakaocorp/kanana-2-30b-a3b-instruct-2601): RMSNorm ->
multi-head latent attention -> residual -> RMSNorm -> feed-forward ->
residual; final RMSNorm, untied output head. As published in
``modeling_deepseek_v3.py``:

* attention (``q_lora_rank`` null): ``q = x W_q``, heads of ``q_nope | q_rope``;
  ``[c_raw | k_rope] = x W_kva``; ``c = RMSNorm(c_raw)``; ``k_rope`` is one
  head shared by all; rotary embedding on ``q_rope`` and ``k_rope`` only;
  ``[k_nope_h | v_h] = c W_kvb`` per head; scores ``(q_nope_h . k_nope_h +
  q_rope_h . k_rope) / sqrt(qk_head_dim)``, causal softmax, ``sum p v_h``,
  ``W_o``. The UN-ABSORBED form: per-head keys and values are built from
  ``c`` for every position; nothing is cached;
* the first ``first_k_dense_replace`` layers: SwiGLU of ``intermediate_size``;
* the other layers: ``s = sigmoid(x W_r)``; the top ``num_experts_per_tok``
  of ``s + b`` (``e_score_correction_bias``) are selected (``n_group`` =
  ``topk_group`` = 1: the group limit is the identity); weights ``s[sel] /
  sum s[sel]`` (``norm_topk_prob``) times ``routed_scaling_factor``; each
  expert a SwiGLU of ``moe_intermediate_size``, applied to the tokens routed
  to it and to no other; beside them one SwiGLU of ``n_shared_experts x
  moe_intermediate_size`` applied to every token. No capacity.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no cache, no batching tricks, no sorting
of tokens by expert. It imports nothing of the program under test.
Departures from the source, each for a stated reason:

* float32 throughout (the source runs bfloat16 with a float32 router and
  softmax): the reference is what the bf16 program is measured against;
* ``rope_interleave``: the source de-interleaves q and k (first members of
  the pairs, then second) and rotates halves; here the pairs ``(x[2i],
  x[2i+1])`` are rotated where they stand, at frequency ``theta^(-2i/d)``.
  q and k share the layout, so every score is the same number;
* the routed experts' gate and up projections are one leaf ``e_gate_up``
  (experts, hidden, 2 x width), gate first, because the program stores them
  so; they are split here before use;
* an expert's tokens are picked out on the host and padded to a multiple of
  ``EXPERT_ROW_BUCKET`` rows that point at a zero row, so that a few
  compiled shapes serve every count; attention scores are formed for a
  block of queries at a time; rows go through in blocks of ``block_rows``
  so that a 4,352-token request fits beside nothing else.

``control=True`` computes the CONTROL as well: the same code with both
operands of every matrix product (the router's too) rounded to int8 (rows
of the activation, output channels of the weight, by their largest
magnitude), the precision below the bf16 that the configuration states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
EXPERT_ROW_BUCKET = 256


# ------------------------------------------------------------------ shapes
def dims(cfg):
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    return dict(
        h=int(cfg["hidden_size"]), nh=int(cfg["num_attention_heads"]),
        nope=nope, rope=rope, qk=nope + rope, dv=int(cfg["v_head_dim"]),
        r=int(cfg["kv_lora_rank"]), f=int(cfg["intermediate_size"]),
        fe=int(cfg["moe_intermediate_size"]),
        experts=int(cfg["n_routed_experts"]),
        shared=int(cfg["n_shared_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        dense=int(cfg["first_k_dense_replace"]),
        scaling=float(cfg["routed_scaling_factor"]),
        norm_topk=bool(cfg["norm_topk_prob"]),
        v=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def leaf_table(cfg):
    """Every parameter of the model as (name, shape, kind), in a fixed
    order. ``kind`` is ``matrix`` | ``norm`` | ``bias``: how the benchmark
    draws it from the seed (the selection bias is a ``bias``). Matrices are
    stored (in, out); the experts' are stacked (experts, in, out)."""
    m = dims(cfg)
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise NotImplementedError("group-limited routing (n_group > 1)")
    if cfg.get("q_lora_rank") is not None:
        raise NotImplementedError("a compressed query (q_lora_rank)")
    h, nh, r, e, fe = m["h"], m["nh"], m["r"], m["experts"], m["fe"]
    out = [("top.embed", (m["v"], h), "matrix")]
    for i in range(m["layers"]):
        p = f"L{i}."
        out += [(p + "ln1", (h,), "norm"),
                (p + "q_w", (h, nh * m["qk"]), "matrix"),
                (p + "kva_w", (h, r + m["rope"]), "matrix"),
                (p + "kva_ln", (r,), "norm"),
                (p + "kvb_w", (r, nh * (m["nope"] + m["dv"])), "matrix"),
                (p + "o_w", (nh * m["dv"], h), "matrix"),
                (p + "ln2", (h,), "norm")]
        if i < m["dense"]:
            out += [(p + "gate_w", (h, m["f"]), "matrix"),
                    (p + "up_w", (h, m["f"]), "matrix"),
                    (p + "down_w", (m["f"], h), "matrix")]
        else:
            fs = m["shared"] * fe
            out += [(p + "router_w", (h, e), "matrix"),
                    (p + "router_b", (e,), "bias"),
                    (p + "e_gate_up", (e, h, 2 * fe), "matrix"),
                    (p + "e_down", (e, fe, h), "matrix"),
                    (p + "s_gate_w", (h, fs), "matrix"),
                    (p + "s_up_w", (h, fs), "matrix"),
                    (p + "s_down_w", (fs, h), "matrix")]
    out += [("top.norm", (h,), "norm"), ("top.head", (h, m["v"]), "matrix")]
    return out


def layer_leaves(cfg, i):
    return [n for n, _, _ in leaf_table(cfg) if n.startswith(f"L{i}.")]


# ------------------------------------------------------------- arithmetic
def _fake_int8(x, axis):
    """Round to 255 levels of the largest magnitude along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.round(x / s) * s


def matmul(x, w, control):
    """x (..., in) @ w (in, out) in float32; for the control both operands
    are first rounded to int8: rows of x, output channels of w."""
    x = x.astype(F32)
    w = w.astype(F32)
    if control:
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def swiglu(x, gate_w, up_w, down_w, control):
    act = jax.nn.silu(matmul(x, gate_w, control)) * matmul(x, up_w, control)
    return matmul(act, down_w, control)


def rotate_pairs(x, positions, theta):
    """Rotary embedding on the pairs (x[2i], x[2i+1]) of the last axis,
    where they stand. x (B, S, ..., D), positions (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]        # (S, D/2)
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v, q_block=256):
    """Causal attention, a head of k and v for every head of q.
    q, k (B, S, H, Dqk); v (B, S, H, Dv)."""
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    outs = []
    for start in range(0, s, q_block):
        stop = min(start + q_block, s)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:stop], k[:, :stop],
                        precision=HI) * scale
        ok = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :stop],
                               precision=HI))
    return jnp.concatenate(outs, axis=1).reshape(b, s, h * v.shape[-1])


def attention_block(x, lp, m, control=False):
    """x + Attn(RMSNorm(x)). x (B, S, H) float32."""
    b, s, _ = x.shape
    pos = jnp.arange(s)
    y = rms_norm(x, lp["ln1"], m["eps"])
    q = matmul(y, lp["q_w"], control).reshape(b, s, m["nh"], m["qk"])
    q_nope, q_rope = q[..., :m["nope"]], q[..., m["nope"]:]
    kva = matmul(y, lp["kva_w"], control)
    c = rms_norm(kva[..., :m["r"]], lp["kva_ln"], m["eps"])
    k_rope = rotate_pairs(kva[..., m["r"]:], pos, m["theta"])   # (B, S, Dr)
    q_rope = rotate_pairs(q_rope, pos, m["theta"])
    kv = matmul(c, lp["kvb_w"], control).reshape(
        b, s, m["nh"], m["nope"] + m["dv"])
    k_nope, v = kv[..., :m["nope"]], kv[..., m["nope"]:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None, :], (b, s, m["nh"], m["rope"]))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    return x + matmul(attention(qf, k, v), lp["o_w"], control)


def route(y, lp, m, control=False):
    """The gate: y (T, H) -> (selected experts (T, k), weights (T, k))."""
    s = jax.nn.sigmoid(matmul(y, lp["router_w"], control))
    _, sel = jax.lax.top_k(s + lp["router_b"].astype(F32)[None, :],
                           m["top_k"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if m["norm_topk"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * m["scaling"]


@functools.partial(jax.jit, static_argnums=(7,), donate_argnums=(0,))
def _apply_expert(out, ypad, wdense, idx, e, e_gate_up, e_down, control):
    """Add expert ``e``'s weighted output for the rows ``idx`` (padding
    points at the zero row at the end) into ``out``."""
    x = ypad[idx]
    gu = matmul(x, e_gate_up[e], control)
    f = gu.shape[-1] // 2
    act = jax.nn.silu(gu[..., :f]) * gu[..., f:]
    o = matmul(act, e_down[e], control)
    return out.at[idx].add(o * wdense[idx, e][:, None])


@functools.lru_cache(maxsize=None)
def _jitted(m_items):
    """The pieces of a layer as jitted functions of (arrays..., control),
    built once per set of dims so that every layer and block of rows
    reuses what was compiled."""
    m = dict(m_items)

    def jit(fn):
        return jax.jit(fn, static_argnums=(2,))

    return {
        "attention": jit(lambda x, lp, c: attention_block(x, lp, m, c)),
        "norm2": jax.jit(lambda x, w: rms_norm(x, w, m["eps"])),
        "dense": jit(lambda y, lp, c: swiglu(
            y, lp["gate_w"], lp["up_w"], lp["down_w"], c)),
        "shared": jit(lambda y, lp, c: swiglu(
            y, lp["s_gate_w"], lp["s_up_w"], lp["s_down_w"], c)),
        "route": jit(lambda y, lp, c: route(y, lp, m, c)),
    }


def _pick(lp, *names):
    return {k: lp[k] for k in names}


def routed_experts(y, lp, m, control=False):
    """sum_j w_j E_sel_j(y) for y (T, H): each expert applied to the tokens
    routed to it, picked out on the host."""
    t = y.shape[0]
    sel, w = _jitted(tuple(sorted(m.items())))["route"](
        y, _pick(lp, "router_w", "router_b"), control)
    wdense = jnp.zeros((t + 1, m["experts"]), F32).at[
        jnp.arange(t)[:, None], sel].set(w)
    ypad = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), F32)])
    out = jnp.zeros_like(ypad)
    sel_host = np.asarray(sel)
    for e in range(m["experts"]):
        rows = np.nonzero((sel_host == e).any(axis=1))[0]
        if not len(rows):
            continue
        n = -(-len(rows) // EXPERT_ROW_BUCKET) * EXPERT_ROW_BUCKET
        idx = np.full(n, t, np.int32)
        idx[:len(rows)] = rows
        out = _apply_expert(out, ypad, wdense, jnp.asarray(idx),
                            jnp.int32(e), lp["e_gate_up"], lp["e_down"],
                            control)
    return out[:t]


def layer_forward(x, lp, m, i, control=False):
    """One decoder layer. x (B, S, H) float32; lp: this layer's leaves by
    their short names."""
    fn = _jitted(tuple(sorted(m.items())))
    x = fn["attention"](x, _pick(lp, "ln1", "q_w", "kva_w", "kva_ln",
                                 "kvb_w", "o_w"), control)
    y = fn["norm2"](x, lp["ln2"])
    if i < m["dense"]:
        return x + fn["dense"](y, _pick(lp, "gate_w", "up_w", "down_w"),
                               control)
    b, s, h = y.shape
    shared = fn["shared"](y, _pick(lp, "s_gate_w", "s_up_w", "s_down_w"),
                          control)
    routed = routed_experts(y.reshape(b * s, h), lp, m, control)
    return x + shared + routed.reshape(b, s, h)


def forward_hidden(cfg, get_leaf, ids, control=False):
    """Token ids (B, S) -> the last layer's output (B, S, H), before the
    final norm."""
    m = dims(cfg)
    x = get_leaf("top.embed")[jnp.asarray(ids, jnp.int32)].astype(F32)
    for i in range(m["layers"]):
        lp = {n.split(".", 1)[1]: get_leaf(n) for n in layer_leaves(cfg, i)}
        x = layer_forward(x, lp, m, i, control)
    return x


def head_logits(x, tp, m, control=False):
    return matmul(rms_norm(x, tp["norm"], m["eps"]), tp["head"], control)


def logits(cfg, get_leaf, ids, control=False):
    """Token ids (B, S) -> logits (B, S, V): the whole forward."""
    tp = {"norm": get_leaf("top.norm"), "head": get_leaf("top.head")}
    return head_logits(forward_hidden(cfg, get_leaf, ids, control), tp,
                       dims(cfg), control)


# ---------------------------------------------------------------- serving
def gap_below_best(cfg, get_leaf, rows, control=False, block_rows=4):
    """For each row (prompt tokens, served tokens): one full forward over
    prompt + served[:-1], then at every served position the gap by which
    the served token's logit lies below the best logit.

    With ``control`` the forward is ALSO run as the control, and the gap
    read is that of the token the control puts first (the control stands in
    the program's place; it need not decode).

    Returns (gaps, control_gaps): float32 arrays over all served positions
    (``control_gaps`` None without a control). Layer by layer over blocks of
    at most ``block_rows`` rows of one shape (no padding), so one layer's
    weights and one block's activations are alive at a time.
    """
    m = dims(cfg)
    by_shape = {}
    for r, (p, t) in enumerate(rows):
        by_shape.setdefault((len(p), len(t)), []).append(r)
    blocks = [idx[i:i + block_rows] for idx in by_shape.values()
              for i in range(0, len(idx), block_rows)]
    ids = [jnp.asarray(np.stack([np.concatenate([rows[r][0], rows[r][1][:-1]])
                                 for r in blk]), jnp.int32) for blk in blocks]
    served = [jnp.asarray(np.stack([rows[r][1] for r in blk]), jnp.int32)
              for blk in blocks]

    @jax.jit
    def head_gaps(x, xc, tp, tokens):
        ref = head_logits(x, tp, m)
        best = ref.max(axis=-1)
        pick = jnp.take_along_axis(ref, tokens[..., None], axis=-1)[..., 0]
        if xc is None:
            return best - pick, None
        first = jnp.argmax(head_logits(xc, tp, m, True), axis=-1)
        cpick = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
        return best - pick, best - cpick

    embed = get_leaf("top.embed")
    x0 = [embed[i].astype(F32) for i in ids]
    del embed
    last = {}  # per arithmetic and block: the positions that predict the served
    for c in (False, True) if control else (False,):
        xs = list(x0)
        for i in range(m["layers"]):
            lp = {n.split(".", 1)[1]: get_leaf(n)
                  for n in layer_leaves(cfg, i)}
            xs = [layer_forward(x, lp, m, i, c) for x in xs]
            del lp
        # positions prompt-1 .. end predict the served tokens
        last[c] = [x[:, len(rows[blk[0]][0]) - 1:]
                   for x, blk in zip(xs, blocks)]
    tp = {"norm": get_leaf("top.norm"), "head": get_leaf("top.head")}
    gaps, cgaps = [], []
    for b, tokens in enumerate(served):
        g, cg = head_gaps(last[False][b], last[True][b] if control else None,
                          tp, tokens)
        gaps.append(g.reshape(-1))
        if control:
            cgaps.append(cg.reshape(-1))
    return (jnp.concatenate(gaps),
            jnp.concatenate(cgaps) if control else None)
