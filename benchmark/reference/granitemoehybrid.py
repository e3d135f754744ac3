"""Plain reference of the Granite-4.0-H-shaped decoder (``model_type``
``granitemoehybrid``; here ibm-granite/granite-4.0-h-small). As published in
``modeling_granitemoehybrid.py`` (``r`` = ``residual_multiplier``):

* ``x_0 = embedding_multiplier * Embed(ids)``; every layer ``x += r *
  Mixer(RMSNorm(x)); v = RMSNorm(x); x += r * (Routed(v) + Shared(v))``;
  ``logits = RMSNorm(x) Embed^T / logits_scaling`` (the head is the embedding);
* a ``mamba`` layer's mixer: ``[z | xBC | dt_raw] = u W_in``; a depthwise
  causal convolution of width ``mamba_d_conv`` with bias over ``xBC``, zeros
  before the first token, then silu; ``[xs | B | C] = xBC`` with one group
  of B and C for all ``mamba_n_heads`` heads of ``mamba_d_head``; per head
  ``dt = softplus(dt_raw + dt_bias)`` (``time_step_limit`` (0, inf): no
  clamp), ``A = -exp(A_log)``, ``H_t = exp(dt_t A) H_{t-1} + dt_t xs_t (x)
  B_t`` from ``H_0 = 0``, ``y_t = H_t C_t + D xs_t``; then ``g = y *
  silu(z)``, ``g * rsqrt(mean(g^2) + eps) * w_norm`` over all of ``d_in``,
  ``W_out``. Here the RECURRENCE itself, a ``lax.scan`` over positions: the
  program's chunked sum is checked against different arithmetic;
* an ``attention`` layer's mixer: q/k/v/o without bias, grouped queries, NO
  rotary embedding, scores times ``attention_multiplier``, causal softmax
  over the whole sequence;
* routed experts: ``l = v W_r`` over all ``published_experts``; the
  ``num_experts_per_tok`` largest; weights the softmax over those values
  alone; each expert a SwiGLU of ``intermediate_size``, applied to the tokens
  routed to it and to no other. ``held_experts`` ``[lo, hi)``: only those
  experts exist here (one chip's share under expert parallelism); what the
  others would add is LEFT OUT, as in the program, and the partial result
  goes on to the next layer. ``Shared(v)``: one SwiGLU of
  ``shared_intermediate_size`` for every token.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no cache, no batching tricks, no sorting
of tokens by expert. It imports nothing of the program under test.
Departures from the source, each for a stated reason:

* float32 throughout (the source runs bfloat16 with float32 inside the
  recurrence and the softmaxes): the reference is what the bf16 program is
  measured against;
* the source's fused kernels run the recurrence in chunks of
  ``mamba_chunk_size``; the sum is the same and the plain form is the scan;
* the convolution's weight is one leaf ``(channels, width)`` (the source
  stores ``(channels, 1, width)``), tap ``j`` multiplying the input ``width
  - 1 - j`` positions back; gate and up projections are one leaf (``e_in``,
  ``s_in``), gate first, as the source stores them;
* an expert's tokens are picked out on the host and padded to a multiple of
  ``EXPERT_ROW_BUCKET`` rows that point at a zero row; attention scores are
  formed for a block of queries at a time; rows go through in blocks of
  ``block_rows``.

``control=True`` computes the CONTROL as well: the same code with both
operands of every matrix product (the router's and the head's too) rounded
to int8 (rows of the activation, output channels of the weight, by their
largest magnitude), the precision below the bf16 that the configuration
states. The recurrence and the convolution multiply no matrix and stay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
EXPERT_ROW_BUCKET = 256


# ------------------------------------------------------------------ shapes
def dims(cfg):
    held = int(cfg["num_local_experts"])
    lo, hi = cfg.get("held_experts", [0, held])
    nh, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        kinds=tuple(cfg["layer_types"]),
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]),
        hd=int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]),
        fe=int(cfg["intermediate_size"]),
        fs=int(cfg["shared_intermediate_size"]),
        held=held, lo=int(lo), hi=int(hi),
        experts=int(cfg.get("published_experts", held)),
        top_k=int(cfg["num_experts_per_tok"]),
        mh=nh, mp=hd, d_in=nh * hd, n=int(cfg["mamba_d_state"]),
        conv=int(cfg["mamba_d_conv"]),
        att_mult=float(cfg["attention_multiplier"]),
        emb_mult=float(cfg["embedding_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        eps=float(cfg["rms_norm_eps"]))


def leaf_table(cfg):
    """Every parameter of the model as (name, shape, kind), in a fixed
    order. ``kind`` is ``matrix`` | ``norm`` | ``bias``: how the benchmark
    draws it from the seed (``D`` and the norms ``norm``; ``A_log``,
    ``dt_bias`` and the convolution's bias ``bias``). Matrices are stored
    (in, out); the held experts' are stacked (held, in, out)."""
    m = dims(cfg)
    for what, bad in (
            ("mamba_n_groups other than 1", int(cfg["mamba_n_groups"]) != 1),
            ("an untied head", not cfg["tie_word_embeddings"]),
            ("a bias on a projection",
             cfg["attention_bias"] or cfg["mamba_proj_bias"]),
            ("positions", cfg["position_embedding_type"] != "nope"),
            ("mamba_expand x hidden != heads x head size",
             m["d_in"] != int(cfg["mamba_expand"]) * m["h"]),
            ("held_experts of another size than num_local_experts",
             m["hi"] - m["lo"] != m["held"] or m["hi"] > m["experts"]),
            ("layer_types of another length than the depth",
             len(m["kinds"]) != m["layers"])):
        if bad:
            raise NotImplementedError(what)
    h, cd = m["h"], m["d_in"] + 2 * m["n"]
    out = [("top.embed", (m["v"], h), "matrix")]
    for i, kind in enumerate(m["kinds"]):
        p = f"L{i}."
        out.append((p + "ln1", (h,), "norm"))
        if kind == "mamba":
            out += [(p + "in_w", (h, m["d_in"] + cd + m["mh"]), "matrix"),
                    (p + "conv_w", (cd, m["conv"]), "matrix"),
                    (p + "conv_b", (cd,), "bias"),
                    (p + "dt_bias", (m["mh"],), "bias"),
                    (p + "A_log", (m["mh"],), "bias"),
                    (p + "D", (m["mh"],), "norm"),
                    (p + "ssm_ln", (m["d_in"],), "norm"),
                    (p + "out_w", (m["d_in"], h), "matrix")]
        else:
            out += [(p + "q_w", (h, m["nq"] * m["hd"]), "matrix"),
                    (p + "k_w", (h, m["nkv"] * m["hd"]), "matrix"),
                    (p + "v_w", (h, m["nkv"] * m["hd"]), "matrix"),
                    (p + "o_w", (m["nq"] * m["hd"], h), "matrix")]
        out += [(p + "ln2", (h,), "norm"),
                (p + "router_w", (h, m["experts"]), "matrix"),
                (p + "e_in", (m["held"], h, 2 * m["fe"]), "matrix"),
                (p + "e_out", (m["held"], m["fe"], h), "matrix"),
                (p + "s_in", (h, 2 * m["fs"]), "matrix"),
                (p + "s_out", (m["fs"], h), "matrix")]
    out.append(("top.norm", (h,), "norm"))
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


def swiglu(x, in_w, out_w, control):
    gu = matmul(x, in_w, control)
    f = gu.shape[-1] // 2
    return matmul(jax.nn.silu(gu[..., :f]) * gu[..., f:], out_w, control)


def mamba_mixer(u, lp, m, control=False):
    """The state-space mixer of u (B, S, H), as the recurrence."""
    b, s, _ = u.shape
    d_in, n, k = m["d_in"], m["n"], m["conv"]
    zxd = matmul(u, lp["in_w"], control)
    z, xbc = zxd[..., :d_in], zxd[..., d_in:2 * d_in + 2 * n]
    dt = jax.nn.softplus(zxd[..., 2 * d_in + 2 * n:]
                         + lp["dt_bias"].astype(F32))          # (B, S, Hm)
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))   # zeros before token 0
    w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(pad[:, j:j + s] * w[:, j] for j in range(k))
                      + lp["conv_b"].astype(F32))
    xs = xbc[..., :d_in].reshape(b, s, m["mh"], m["mp"])
    bm, cm = xbc[..., d_in:d_in + n], xbc[..., d_in + n:]
    a = -jnp.exp(lp["A_log"].astype(F32))
    d = lp["D"].astype(F32)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp      # (B, Hm, P), (B, N), (B, N), (B, Hm)
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t, precision=HI) \
            + d[:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(
        step, jnp.zeros((b, m["mh"], m["mp"], n), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, bm, cm, dt)))
    g = jnp.moveaxis(y, 0, 1).reshape(b, s, d_in) * jax.nn.silu(z)
    return matmul(rms_norm(g, lp["ssm_ln"], m["eps"]), lp["out_w"], control)


def attention_mixer(u, lp, m, control=False, q_block=256):
    """Causal grouped-query attention of u (B, S, H): no positions, scores
    times ``attention_multiplier``."""
    b, s, _ = u.shape
    nq, nkv, hd = m["nq"], m["nkv"], m["hd"]
    q = matmul(u, lp["q_w"], control).reshape(b, s, nq, hd)
    k = jnp.repeat(matmul(u, lp["k_w"], control).reshape(b, s, nkv, hd),
                   nq // nkv, axis=2)
    v = jnp.repeat(matmul(u, lp["v_w"], control).reshape(b, s, nkv, hd),
                   nq // nkv, axis=2)
    outs = []
    for start in range(0, s, q_block):
        stop = min(start + q_block, s)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:stop], k[:, :stop],
                        precision=HI) * m["att_mult"]
        ok = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :stop],
                               precision=HI))
    return matmul(jnp.concatenate(outs, axis=1).reshape(b, s, nq * hd),
                  lp["o_w"], control)


def route(y, lp, m, control=False):
    """The gate: y (T, H) -> (chosen experts (T, k) among ALL published
    ones, weights (T, k): the softmax over the chosen logits)."""
    top, sel = jax.lax.top_k(matmul(y, lp["router_w"], control), m["top_k"])
    return sel, jax.nn.softmax(top, axis=-1)


@functools.partial(jax.jit, static_argnums=(8,), donate_argnums=(0,))
def _apply_expert(out, ypad, wdense, idx, e, slab, e_in, e_out, control):
    """Add published expert ``e``'s (held slab ``slab``) weighted output
    for the rows ``idx`` (padding points at the zero row at the end)."""
    o = swiglu(ypad[idx], e_in[slab], e_out[slab], control)
    return out.at[idx].add(o * wdense[idx, e][:, None])


@functools.lru_cache(maxsize=None)
def _jitted(m_items):
    """The pieces of a layer as jitted functions of (arrays..., control),
    built once per set of dims so that every layer and block of rows
    reuses what was compiled."""
    m = dict(m_items)

    def jit(fn):
        return jax.jit(fn, static_argnums=(2,))

    def mix(fn):
        return jit(lambda x, lp, c: x + m["res_mult"] * fn(
            rms_norm(x, lp["ln1"], m["eps"]), lp, m, c))

    return {
        "mamba": mix(mamba_mixer), "attention": mix(attention_mixer),
        "norm2": jax.jit(lambda x, w: rms_norm(x, w, m["eps"])),
        "shared": jit(lambda y, lp, c: swiglu(y, lp["s_in"], lp["s_out"], c)),
        "route": jit(lambda y, lp, c: route(y, lp, m, c)),
    }


def _pick(lp, *names):
    return {k: lp[k] for k in names}


def _fn(m):
    return _jitted(tuple(sorted(m.items())))


def routed_experts(y, lp, m, control=False, held=None):
    """sum over the chosen experts that are HELD of w_e E_e(y), for y
    (T, H): each expert applied to the tokens routed to it, picked out on
    the host. ``held`` = (lo, hi) overrides the configuration's range (the
    test that adds the shares up); slab 0 of ``e_in`` is expert ``lo``."""
    lo, hi = (m["lo"], m["hi"]) if held is None else held
    t = y.shape[0]
    sel, w = _fn(m)["route"](y, _pick(lp, "router_w"), control)
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
                            jnp.int32(e), jnp.int32(e - lo), lp["e_in"],
                            lp["e_out"], control)
    return out[:t]


def layer_forward(x, lp, m, i, control=False):
    """One decoder layer. x (B, S, H) float32; lp: this layer's leaves by
    their short names."""
    fn = _fn(m)
    if m["kinds"][i] == "mamba":
        x = fn["mamba"](x, _pick(lp, "ln1", "in_w", "conv_w", "conv_b",
                                 "dt_bias", "A_log", "D", "ssm_ln",
                                 "out_w"), control)
    else:
        x = fn["attention"](x, _pick(lp, "ln1", "q_w", "k_w", "v_w", "o_w"),
                            control)
    y = fn["norm2"](x, lp["ln2"])
    b, s, h = y.shape
    shared = fn["shared"](y, _pick(lp, "s_in", "s_out"), control)
    routed = routed_experts(y.reshape(b * s, h), lp, m, control)
    return x + m["res_mult"] * (shared + routed.reshape(b, s, h))


def forward_hidden(cfg, get_leaf, ids, control=False):
    """Token ids (B, S) -> the last layer's output (B, S, H), before the
    final norm."""
    m = dims(cfg)
    x = m["emb_mult"] * get_leaf("top.embed")[
        jnp.asarray(ids, jnp.int32)].astype(F32)
    for i in range(m["layers"]):
        lp = {n.split(".", 1)[1]: get_leaf(n) for n in layer_leaves(cfg, i)}
        x = layer_forward(x, lp, m, i, control)
    return x


def head_logits(x, tp, m, control=False):
    """The tied head: RMSNorm(x) Embed^T / logits_scaling."""
    return matmul(rms_norm(x, tp["norm"], m["eps"]), tp["embed"].T,
                  control) / m["logits_scaling"]


def logits(cfg, get_leaf, ids, control=False):
    """Token ids (B, S) -> logits (B, S, V): the whole forward."""
    tp = {"norm": get_leaf("top.norm"), "embed": get_leaf("top.embed")}
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
    x0 = [m["emb_mult"] * embed[i].astype(F32) for i in ids]
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
    tp = {"norm": get_leaf("top.norm"), "embed": embed}
    gaps, cgaps = [], []
    for b, tokens in enumerate(served):
        g, cg = head_gaps(last[False][b], last[True][b] if control else None,
                          tp, tokens)
        gaps.append(g.reshape(-1))
        if control:
            cgaps.append(cg.reshape(-1))
    return (jnp.concatenate(gaps),
            jnp.concatenate(cgaps) if control else None)
