"""Plain reference of the Solar-Open2-shaped decoder (``model_type``
``solar_open2``; here upstage/Solar-Open2-250B), written from the published
config's keys and, for the linear-attention layers, from Kimi Linear
(arXiv:2510.26692) and its public ``KimiDeltaAttention`` (eps
``rms_norm_eps``, ``x`` a layer's RMS-normed input):

* ``h_0 = Embed(ids)``; layer ``i``: ``h += mixer_i(norm1(h)); h +=
  experts(norm2(h))``; the mixer is GQA where ``i in gqa_layers``, else KDA;
  ``logits = RMSNorm(h) W_head`` (an untied head; no multiplier anywhere);
* KDA (``linear_attn_config``: H ``num_heads`` of ``head_dim`` d,
  ``short_conv_kernel_size``): ``q~, k~, v~ = x W_q, x W_k, x W_v`` (hidden
  -> H d each); ``q, k, v = silu(causal depthwise conv(.))``, no bias, a
  convolution each, zeros before the first token; per head ``q^ = q /
  sqrt(|q|^2 + 1e-6) * d^-1/2``, ``k^ = k / sqrt(|k|^2 + 1e-6)``; ``a = (x
  W_fa) W_fb`` (hidden -> d -> H d), ``g_t = -exp(A_log_h) softplus(a_t +
  dt_bias)``, ``alpha_t = exp(g_t)`` per head AND channel; ``beta_t =
  sigmoid(x W_beta)``, times 2 (``kda_allow_neg_eigval``); the state S (d
  x d a head) from zeros, POSITION BY POSITION (a ``lax.scan``): ``S' =
  Diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k^_t (v_t - S'^T k^_t)^T``;
  ``o_t = S_t^T q^_t``; ``y = RMSNorm_d(o_t) * sigmoid((x W_ga) W_gb)``;
  ``W_o``. The program's chunked form (a triangular solve a chunk) is
  checked against different arithmetic;
* GQA: q (``num_attention_heads`` x ``head_dim``), k, v
  (``num_key_value_heads`` x ``head_dim``), no bias, NO positions
  (``use_rope`` false), scale ``head_dim^-1/2``, causal softmax over the
  whole sequence; ``(concat(att) * sigmoid(x W_gate)) W_o``
  (``use_gqa_gate``);
* experts: ``s = sigmoid(v W_r)`` over all ``published_experts``; the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s_chosen / (sum s_chosen + 1e-20) x routed_scaling_factor``; a
  SwiGLU expert of ``moe_intermediate_size`` applied to the tokens routed
  to it and to no other, ONE HELD EXPERT AT A TIME. ``held_experts`` ``[lo,
  hi)``: only those experts exist here (one chip's share under expert
  parallelism); what the others would add is LEFT OUT, as in the program,
  and the partial result goes on to the next layer. Beside them the shared
  SwiGLU expert (``n_shared_experts`` x the same width) for every token.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST`` (what ``jax.default_matmul_precision("highest")``
sets); no kernels, no cache, no chunks, no sorting of tokens by expert. It
imports nothing of the program under test; ``matmul`` (with its
int8-operand control), ``rms_norm``, ``swiglu`` and the one-expert-at-a-time
application are the Granite-4.0-H reference's, the sigmoid router the
Nemotron-H one's and the head in slabs the Falcon-H1 one's (the same
equations, not copied again). Departures from the published description,
each for a stated reason:

* float32 throughout (the source runs bfloat16 with float32 inside the
  recurrence, the router and the softmax): the reference is what the bf16
  program is measured against;
* the source's kernels run the delta rule in chunks of 64 with a
  triangular solve; the recurrence is the same and the plain form is the
  scan;
* a convolution's weight is one leaf ``(channels, width)`` (the source
  stores ``(channels, 1, width)``), tap ``j`` multiplying the input ``width
  - 1 - j`` positions back, applied as a sum of ``width`` shifted products;
  ``A_log`` is ``(H,)`` (the source stores ``(1, 1, H, 1)``); the held
  experts' matrices are stacked, gate and up in one slab (``e_gate_up``
  (held, in, 2 x width), ``e_down``), where the source keeps a module an
  expert;
* an expert's tokens are picked out on the host and padded to a multiple of
  ``EXPERT_ROW_BUCKET`` rows that point at a zero row; attention scores are
  formed for a block of queries at a time; the head is taken ``HEAD_BLOCK``
  columns at a time; rows go through in blocks of ``block_rows``, so that
  one layer's weights and one block's activations are alive at a time.

``control=True`` computes the CONTROL as well: the same code with both
operands of every matrix product (the router's and the head's too) rounded
to int8 (rows of the activation, output channels of the weight, by their
largest magnitude), the precision below the bf16 that the configuration
states. The recurrence, the convolutions and the norms multiply no matrix
and stay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.falcon_h1 import _head_gaps
from benchmark.reference.granitemoehybrid import (
    EXPERT_ROW_BUCKET, F32, HI, _apply_expert, matmul, rms_norm, swiglu)
from benchmark.reference.nemotron_h import route

Q_BLOCK = 256


# ------------------------------------------------------------------ shapes
def dims(cfg):
    held = int(cfg["n_routed_experts"])
    lo, hi = cfg.get("held_experts", [0, held])
    lin = cfg["linear_attn_config"]
    layers = int(cfg["num_hidden_layers"])
    gqa = tuple(int(i) for i in cfg["gqa_layers"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]), layers=layers,
        kinds="".join("*" if i in gqa else "K" for i in range(layers)),
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        kh=int(lin["num_heads"]), kd=int(lin["head_dim"]),
        conv=int(lin["short_conv_kernel_size"]),
        beta_scale=2.0 if cfg["kda_allow_neg_eigval"] else 1.0,
        fe=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        held=held, lo=int(lo), hi=int(hi),
        experts=int(cfg.get("published_experts", held)),
        top_k=int(cfg["num_experts_per_tok"]),
        route_norm=bool(cfg["norm_topk_prob"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        eps=float(cfg["rms_norm_eps"]), head_mult=1.0)


def leaf_table(cfg):
    """Every parameter of the model as (name, shape, kind), in a fixed
    order. ``kind`` is ``matrix`` | ``norm`` | ``bias``: how the benchmark
    draws it from the seed (the norms ``norm``; ``A_log``, ``dt_bias`` and
    the router's selection bias ``bias``). Matrices are stored (in, out);
    the held experts' are stacked (held, in, out)."""
    m = dims(cfg)
    for what, bad in (
            ("a tied head", cfg["tie_word_embeddings"]),
            ("rotary positions (use_rope)", cfg["use_rope"]),
            ("an ungated GQA (use_gqa_gate false)", not cfg["use_gqa_gate"]),
            ("kda_use_full_proj", cfg["kda_use_full_proj"]),
            ("KDA heads that share keys (num_kv_heads)",
             cfg["linear_attn_config"].get("num_kv_heads") is not None),
            ("leading dense layers (first_k_dense_replace)",
             int(cfg["first_k_dense_replace"])),
            ("held_experts of another size than n_routed_experts",
             m["hi"] - m["lo"] != m["held"] or m["hi"] > m["experts"]),
            ("gqa_layers other than every (gqa_interval + 1)-th from 0",
             [i for i, c in enumerate(m["kinds"]) if c == "*"]
             != list(range(0, m["layers"], int(cfg["gqa_interval"]) + 1)))):
        if bad:
            raise NotImplementedError(what)
    h, d = m["h"], m["kh"] * m["kd"]
    out = [("top.embed", (m["v"], h), "matrix")]
    for i, kind in enumerate(m["kinds"]):
        p = f"L{i}."
        out.append((p + "ln1", (h,), "norm"))
        if kind == "K":
            out += [(p + "q_w", (h, d), "matrix"),
                    (p + "k_w", (h, d), "matrix"),
                    (p + "v_w", (h, d), "matrix"),
                    (p + "q_conv", (d, m["conv"]), "matrix"),
                    (p + "k_conv", (d, m["conv"]), "matrix"),
                    (p + "v_conv", (d, m["conv"]), "matrix"),
                    (p + "A_log", (m["kh"],), "bias"),
                    (p + "fa_w", (h, m["kd"]), "matrix"),
                    (p + "fb_w", (m["kd"], d), "matrix"),
                    (p + "dt_bias", (d,), "bias"),
                    (p + "beta_w", (h, m["kh"]), "matrix"),
                    (p + "ga_w", (h, m["kd"]), "matrix"),
                    (p + "gb_w", (m["kd"], d), "matrix"),
                    (p + "o_ln", (m["kd"],), "norm"),
                    (p + "out_w", (d, h), "matrix")]
        else:
            out += [(p + "q_w", (h, m["nq"] * m["hd"]), "matrix"),
                    (p + "k_w", (h, m["nkv"] * m["hd"]), "matrix"),
                    (p + "v_w", (h, m["nkv"] * m["hd"]), "matrix"),
                    (p + "o_w", (m["nq"] * m["hd"], h), "matrix"),
                    (p + "g_w", (h, m["nq"] * m["hd"]), "matrix")]
        out += [(p + "ln2", (h,), "norm"),
                (p + "router_w", (h, m["experts"]), "matrix"),
                (p + "router_b", (m["experts"],), "bias"),
                (p + "e_gate_up", (m["held"], h, 2 * m["fe"]), "matrix"),
                (p + "e_down", (m["held"], m["fe"], h), "matrix"),
                (p + "s_gate", (h, m["fs"]), "matrix"),
                (p + "s_up", (h, m["fs"]), "matrix"),
                (p + "s_down", (m["fs"], h), "matrix")]
    out += [("top.norm", (h,), "norm"), ("top.head", (h, m["v"]), "matrix")]
    return out


def layer_leaves(cfg, i):
    return [n for n, _, _ in leaf_table(cfg) if n.startswith(f"L{i}.")]


# ------------------------------------------------------------- arithmetic
def short_conv(x, w):
    """silu of the causal depthwise convolution of x (B, S, D), zeros
    before token 0: a sum of ``width`` shifted products."""
    k, s = w.shape[1], x.shape[1]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(F32)
    return jax.nn.silu(sum(pad[:, j:j + s] * w[:, j] for j in range(k)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def delta_rule(q, k, v, alpha, beta):
    """The three lines, position by position: q, k, v, alpha (B, S, H, d),
    beta (B, S, H) -> o (B, S, H, d), from a zero state."""
    b, _, h, d = q.shape

    def step(state, inp):
        q_t, k_t, v_t, a_t, b_t = inp
        decayed = state * a_t[..., None]                  # Diag(alpha) S
        answered = jnp.einsum("bhkv,bhk->bhv", decayed, k_t, precision=HI)
        state = decayed + k_t[..., None] * (
            b_t[..., None] * (v_t - answered))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), F32),
                        tuple(jnp.moveaxis(t, 1, 0)
                              for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda_mixer(x, lp, m, control=False):
    """Kimi Delta Attention of x (B, S, H), as the recurrence."""
    b, s, _ = x.shape
    heads = (b, s, m["kh"], m["kd"])
    q, k, v = (short_conv(matmul(x, lp[n + "_w"], control),
                          lp[n + "_conv"]).reshape(heads) for n in "qkv")
    a = matmul(matmul(x, lp["fa_w"], control), lp["fb_w"], control)
    g = -jnp.exp(lp["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        a.reshape(heads) + lp["dt_bias"].astype(F32).reshape(heads[2:]))
    beta = jax.nn.sigmoid(matmul(x, lp["beta_w"], control)) * m["beta_scale"]
    o = delta_rule(l2norm(q) * m["kd"] ** -0.5, l2norm(k), v, jnp.exp(g),
                   beta)
    gate = matmul(matmul(x, lp["ga_w"], control), lp["gb_w"], control)
    y = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                           + m["eps"]) * lp["o_ln"].astype(F32)
         * jax.nn.sigmoid(gate.reshape(heads)))
    return matmul(y.reshape(b, s, -1), lp["out_w"], control)


def gated_attention(x, lp, m, control=False, q_block=Q_BLOCK):
    """Causal grouped-query attention of x (B, S, H) without positions,
    the heads' outputs gated by a sigmoid of x before ``W_o``."""
    b, s, _ = x.shape
    nq, nkv, hd = m["nq"], m["nkv"], m["hd"]
    q = matmul(x, lp["q_w"], control).reshape(b, s, nq, hd)
    k, v = (jnp.repeat(matmul(x, lp[n], control).reshape(b, s, nkv, hd),
                       nq // nkv, axis=2) for n in ("k_w", "v_w"))
    outs = []
    for start in range(0, s, q_block):
        stop = min(start + q_block, s)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:stop], k[:, :stop],
                        precision=HI) * hd ** -0.5
        ok = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :stop],
                               precision=HI))
    att = jnp.concatenate(outs, axis=1).reshape(b, s, nq * hd)
    return matmul(att * jax.nn.sigmoid(matmul(x, lp["g_w"], control)),
                  lp["o_w"], control)


def shared_expert(y, lp, control):
    return swiglu(y, jnp.concatenate([lp["s_gate"], lp["s_up"]], axis=1),
                  lp["s_down"], control)


@functools.lru_cache(maxsize=None)
def _jitted(m_items):
    """The pieces of a layer as jitted functions of (arrays..., control),
    built once per set of dims so that every layer and block of rows
    reuses what was compiled."""
    m = dict(m_items)

    def jit(fn):
        return jax.jit(fn, static_argnums=(2,))

    def mixer(fn):
        return jit(lambda x, lp, c: x + fn(
            rms_norm(x, lp["ln1"], m["eps"]), lp, m, c))

    return {
        "K": mixer(kda_mixer), "*": mixer(gated_attention),
        "norm": jax.jit(lambda x, w: rms_norm(x, w, m["eps"])),
        "shared": jit(shared_expert),
        "route": jit(lambda y, lp, c: route(y, lp, m, c)),
    }


def _pick(lp, *names):
    return {k: lp[k] for k in names}


def _fn(m):
    return _jitted(tuple(sorted(m.items())))


def routed_experts(y, lp, m, control=False, held=None):
    """sum over the chosen experts that are HELD of w_e E_e(y), for y
    (T, H): a loop over the held experts, each applied to the tokens routed
    to it, picked out on the host. ``held`` = (lo, hi) overrides the
    configuration's range (the test that adds the shares up); slab 0 of
    ``e_gate_up`` is expert ``lo``."""
    lo, hi = (m["lo"], m["hi"]) if held is None else held
    t = y.shape[0]
    sel, w = _fn(m)["route"](y, _pick(lp, "router_w", "router_b"), control)
    wdense = jnp.zeros((t + 1, m["experts"]), F32).at[
        jnp.arange(t)[:, None], sel].set(w)
    ypad = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), F32)])
    out = jnp.zeros_like(ypad)
    sel_host = np.asarray(sel)
    for e in range(lo, hi):
        rows = np.nonzero((sel_host == e).any(axis=1))[0]
        if not len(rows):
            continue
        n = -(-len(rows) // EXPERT_ROW_BUCKET) * EXPERT_ROW_BUCKET
        idx = np.full(n, t, np.int32)
        idx[:len(rows)] = rows
        out = _apply_expert(out, ypad, wdense, jnp.asarray(idx),
                            jnp.int32(e), jnp.int32(e - lo),
                            lp["e_gate_up"], lp["e_down"], control)
    return out[:t]


def layer_forward(x, lp, m, i, control=False):
    """One layer: the mixer, then the experts. x (B, S, H) float32; lp:
    this layer's leaves by their short names."""
    fn = _fn(m)
    x = fn[m["kinds"][i]](x, {k: v for k, v in lp.items()
                              if not k.startswith(("e_", "s_", "router_"))
                              and k != "ln2"}, control)
    y = fn["norm"](x, lp["ln2"])
    b, s, h = y.shape
    shared = fn["shared"](y, _pick(lp, "s_gate", "s_up", "s_down"), control)
    routed = routed_experts(y.reshape(b * s, h), lp, m, control)
    return x + shared + routed.reshape(b, s, h)


def embed(cfg, get_leaf, ids):
    return get_leaf("top.embed")[jnp.asarray(ids, jnp.int32)].astype(F32)


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
    """The untied head: RMSNorm(x) W_head."""
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
    weights and one block's activations are alive at a time; the head in
    slabs (``reference/falcon_h1.py::_head_gaps``).
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
    head_gaps = jax.jit(functools.partial(_head_gaps, m=m))

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
    del x0, xs
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
