"""Plain reference of the AFMoE-shaped decoder (``model_type`` ``afmoe``; here
arcee-ai/Trinity-Mini). As published in ``modeling_afmoe.py``
(``AfmoeAttention``, ``AfmoeDecoderLayer``, ``AfmoeTokenChoiceRouter``); the
configuration carries no key for the attention gate, the per-head norms,
which layers rotate, or the four norms, so these follow the modeling code:

* ``x_0 = sqrt(hidden_size) * Embed(ids)`` (``mup_enabled``); every layer ``x
  += N2(Attn(N1(x))); x += N4(FF(N3(x)))`` with four weighted RMSNorms
  (``input_layernorm``, ``post_attention_layernorm``, ``pre_mlp_layernorm``,
  ``post_mlp_layernorm``); ``logits = RMSNorm(x) W_head`` (untied);
* attention, for the normed input ``u``: ``q = u W_q`` as
  ``num_attention_heads`` heads of ``head_dim``, ``k = u W_k`` and ``v = u
  W_v`` as ``num_key_value_heads`` heads, ``g = u W_g`` (heads x head_dim
  wide); no bias anywhere; ``q`` and ``k`` RMS-normed per head with weights
  ``q_norm`` / ``k_norm``. A ``sliding_attention`` layer rotates ``q`` and
  ``k`` (``rope_theta``, the whole head, the half-split layout, no scaling)
  and lets query ``t`` see keys ``t - sliding_window < s <= t`` (itself and
  the ``sliding_window - 1`` before it); a ``full_attention`` layer rotates
  nothing and sees ``s <= t``. Scores x ``1 / sqrt(head_dim)``, softmax,
  each query head over its KV head; ``Attn(u) = (concat_heads(out) *
  sigmoid(g)) W_o``;
* the first ``num_dense_layers`` layers: ``FF`` a SwiGLU of
  ``intermediate_size``;
* the other layers: ``s = sigmoid(v W_r)`` over ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + expert_bias`` are selected
  (``n_group`` = ``topk_group`` = 1: one group; any other value is refused);
  weights ``s[sel] / (sum s[sel] + 1e-20)`` (``route_norm``) times
  ``route_scale``; each expert a SwiGLU of ``moe_intermediate_size``, applied
  to the tokens routed to it and to no other; beside them one SwiGLU of
  ``num_shared_experts x moe_intermediate_size`` for every token. No capacity.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no cache, no ring, no batching tricks, no
sorting of tokens by expert: both layer kinds are a dense mask over
positions. It imports nothing of the program under test. Departures from the
source, each for a stated reason:

* float32 throughout (the source runs bfloat16 with a float32 router and
  softmax): the reference is what the bf16 program is measured against;
* the routed experts' gate and up projections are one leaf ``e_gate_up``
  (experts, hidden, 2 x width), gate first, and the experts are stacked,
  because the program stores them so (the source keeps a module an expert);
  ``expert_bias`` is a leaf like any other (the source trains it by the load);
* an expert's tokens are picked out on the host and padded to a multiple of
  ``EXPERT_ROW_BUCKET`` rows that point at a zero row, so that a few compiled
  shapes serve every count; attention scores are formed for ``Q_BLOCK``
  queries at a time against a fixed span of keys (a full layer: all of them;
  a window layer: the block's own and the ``sliding_window - 1`` before it)
  with the mask by position deciding what is seen, so that one compiled
  body serves every block; rows go through a few at a time so that a
  16,512-token request fits.

``control=True`` computes the CONTROL as well: the same code with both
operands of every matrix product (the router's and the head's too) rounded
to int8 (rows of the activation, output channels of the weight, by their
largest magnitude), the precision below the bf16 that the configuration
states.
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
Q_BLOCK = 512
BLOCK_TOKENS = 17408      # tokens that go through a layer together


# ------------------------------------------------------------------ shapes
def dims(cfg):
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise NotImplementedError("group-limited routing (n_group > 1)")
    if cfg.get("score_func", "sigmoid") != "sigmoid":
        raise NotImplementedError("a score function other than sigmoid")
    if cfg.get("rope_scaling") is not None:
        raise NotImplementedError("rope_scaling")
    kinds = tuple(cfg["layer_types"])
    if len(kinds) != int(cfg["num_hidden_layers"]) \
            or set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError("layer_types does not name every layer's kind")
    return dict(
        h=int(cfg["hidden_size"]), nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
        experts=int(cfg["num_experts"]),
        shared=int(cfg["num_shared_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        dense=int(cfg["num_dense_layers"]),
        scaling=float(cfg["route_scale"]), norm_topk=bool(cfg["route_norm"]),
        window=int(cfg["sliding_window"]), kinds=kinds,
        v=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
        theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def leaf_table(cfg):
    """Every parameter of the model as (name, shape, kind), in a fixed
    order. ``kind`` is ``matrix`` | ``norm`` | ``bias``: how the benchmark
    draws it from the seed (the selection bias is a ``bias``). Matrices are
    stored (in, out); the experts' are stacked (experts, in, out)."""
    m = dims(cfg)
    h, e, fe = m["h"], m["experts"], m["fe"]
    out = [("top.embed", (m["v"], h), "matrix")]
    for i in range(m["layers"]):
        p = f"L{i}."
        out += [(p + "ln1", (h,), "norm"),
                (p + "q_w", (h, m["nq"] * m["hd"]), "matrix"),
                (p + "k_w", (h, m["nkv"] * m["hd"]), "matrix"),
                (p + "v_w", (h, m["nkv"] * m["hd"]), "matrix"),
                (p + "o_w", (m["nq"] * m["hd"], h), "matrix"),
                (p + "g_w", (h, m["nq"] * m["hd"]), "matrix"),
                (p + "q_ln", (m["hd"],), "norm"),
                (p + "k_ln", (m["hd"],), "norm"),
                (p + "ln2", (h,), "norm"), (p + "ln3", (h,), "norm"),
                (p + "ln4", (h,), "norm")]
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


def rotate_halves(x, theta):
    """Rotary embedding of x (B, S, heads, D) at positions 0 .. S - 1, the
    half-split layout: the pairs are ``(x[i], x[i + D/2])``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, window, q_block=Q_BLOCK):
    """Attention by a dense mask over positions: query ``t`` sees keys ``s
    <= t`` and, with a ``window``, ``s > t - window``; each query head over
    its KV head. q (B, S, H, D); k, v (B, S, HK, D). ``q_block`` queries at
    a time against a fixed span of keys: every key (no window), or the
    block's own and the ``window - 1`` before it."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    q_block = min(q_block, s)
    n_blocks = -(-s // q_block)
    pad = n_blocks * q_block - s
    back = window - 1 if window else 0          # keys kept before a block
    qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, n_blocks, q_block, hk, h // hk, d)
    kp = jnp.pad(k, ((0, 0), (back, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (back, pad), (0, 0), (0, 0)))
    span = q_block + back if window else s + pad
    scale = 1.0 / math.sqrt(d)

    def block(i):
        start = i * q_block
        lo = start if window else 0             # into the padded keys
        kb = jax.lax.dynamic_slice_in_dim(kp, lo, span, 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, lo, span, 1)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qg[:, i], kb,
                        precision=HI) * scale
        t = (start + jnp.arange(q_block))[:, None]
        u = (lo - back + jnp.arange(span))[None, :]     # a key's position
        ok = (u >= 0) & (u <= t)
        if window:
            ok &= u > t - window
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, vb, precision=HI)

    out = jax.lax.map(block, jnp.arange(n_blocks))      # (N, B, Q, HK, G, D)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(
        b, n_blocks * q_block, h * d)[:, :s]


def attention_block(x, lp, m, kind, control=False):
    """x + N2(Attn(N1(x))). x (B, S, H) float32."""
    b, s, _ = x.shape
    u = rms_norm(x, lp["ln1"], m["eps"])
    q = matmul(u, lp["q_w"], control).reshape(b, s, m["nq"], m["hd"])
    k = matmul(u, lp["k_w"], control).reshape(b, s, m["nkv"], m["hd"])
    v = matmul(u, lp["v_w"], control).reshape(b, s, m["nkv"], m["hd"])
    q = rms_norm(q, lp["q_ln"], m["eps"])
    k = rms_norm(k, lp["k_ln"], m["eps"])
    window = 0
    if kind == "sliding_attention":
        q, k = rotate_halves(q, m["theta"]), rotate_halves(k, m["theta"])
        window = m["window"]
    out = attention(q, k, v, window)
    out = out * jax.nn.sigmoid(matmul(u, lp["g_w"], control))
    return x + rms_norm(matmul(out, lp["o_w"], control), lp["ln2"], m["eps"])


def route(y, lp, m, control=False):
    """The router: y (T, H) -> (selected experts (T, k), weights (T, k))."""
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

    out = {
        "norm": jax.jit(lambda x, w: rms_norm(x, w, m["eps"])),
        "add_normed": jax.jit(lambda x, y, w: x + rms_norm(y, w, m["eps"])),
        "dense": jit(lambda y, lp, c: swiglu(
            y, lp["gate_w"], lp["up_w"], lp["down_w"], c)),
        "shared": jit(lambda y, lp, c: swiglu(
            y, lp["s_gate_w"], lp["s_up_w"], lp["s_down_w"], c)),
        "route": jit(lambda y, lp, c: route(y, lp, m, c)),
    }
    for kind in set(m["kinds"]):
        out[kind] = jit(functools.partial(
            lambda x, lp, c, kind: attention_block(x, lp, m, kind, c),
            kind=kind))
    return out


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
    x = fn[m["kinds"][i]](x, _pick(
        lp, "ln1", "q_w", "k_w", "v_w", "o_w", "g_w", "q_ln", "k_ln",
        "ln2"), control)
    y = fn["norm"](x, lp["ln3"])
    if i < m["dense"]:
        ff = fn["dense"](y, _pick(lp, "gate_w", "up_w", "down_w"), control)
    else:
        b, s, h = y.shape
        ff = fn["shared"](y, _pick(lp, "s_gate_w", "s_up_w", "s_down_w"),
                          control)
        ff = ff + routed_experts(y.reshape(b * s, h), lp, m,
                                 control).reshape(b, s, h)
    return fn["add_normed"](x, ff, lp["ln4"])


def embed(cfg, get_leaf, ids):
    """``sqrt(hidden_size) * Embed(ids)`` in float32."""
    return get_leaf("top.embed")[jnp.asarray(ids, jnp.int32)].astype(
        F32) * math.sqrt(int(cfg["hidden_size"]))


def forward_hidden(cfg, get_leaf, ids, control=False):
    """Token ids (B, S) -> the last layer's output (B, S, H), before the
    final norm."""
    m = dims(cfg)
    x = embed(cfg, get_leaf, ids)
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
def gap_below_best(cfg, get_leaf, rows, control=False, block_rows=None):
    """For each row (prompt tokens, served tokens): one full forward over
    prompt + served[:-1], then at every served position the gap by which
    the served token's logit lies below the best logit.

    With ``control`` the forward is ALSO run as the control, and the gap
    read is that of the token the control puts first (the control stands in
    the program's place; it need not decode).

    Returns (gaps, control_gaps): float32 arrays over all served positions
    (``control_gaps`` None without a control). Layer by layer over blocks of
    rows of one shape (no padding; ``block_rows`` a block, default as many
    as hold ``BLOCK_TOKENS`` tokens), so one layer's weights and one block's
    activations are alive at a time.
    """
    m = dims(cfg)
    by_shape = {}
    for r, (p, t) in enumerate(rows):
        by_shape.setdefault((len(p), len(t)), []).append(r)
    blocks = []
    for (p_len, t_len), idx in by_shape.items():
        n = block_rows or max(1, BLOCK_TOKENS // (p_len + t_len - 1))
        blocks += [idx[i:i + n] for i in range(0, len(idx), n)]
    ids = [np.stack([np.concatenate([rows[r][0], rows[r][1][:-1]])
                     for r in blk]) for blk in blocks]
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

    x0 = [embed(cfg, get_leaf, i) for i in ids]
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
