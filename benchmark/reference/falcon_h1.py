"""Plain reference of the Falcon-H1-shaped decoder (``model_type``
``falcon_h1``; here tiiuae/Falcon-H1-34B-Instruct), written from the
published config's keys and the source's model code (``modeling_falcon_h1``;
eps ``rms_norm_eps``, ``x`` the stream):

* ``x_0 = Embed(ids) * embedding_multiplier``;
* every layer: ``u = RMSNorm_in(x)``; ``a = Attn(u *
  attention_in_multiplier) * attention_out_multiplier``; ``m = Mixer(u *
  ssm_in_multiplier) * ssm_out_multiplier``; ``x += a + m``; ``x +=
  MLP(RMSNorm_ff(x))``: the two branches read ONE normed input and are
  summed;
* ``Attn``: ``q = v W_q``, ``k = (v W_k) * key_multiplier``, ``v' = v W_v``,
  ``num_attention_heads`` / ``num_key_value_heads`` heads of ``head_dim``,
  no bias; the rotary embedding over the whole head width, halves rotated
  (pairs ``(x[i], x[i + D/2])``), base ``rope_theta``, no scaling; causal
  softmax at ``head_dim ** -0.5`` over the whole sequence; ``W_o``;
* ``Mixer``, Mamba-2: ``H = mamba_n_heads``, ``P = mamba_d_head``, ``d_in =
  mamba_d_ssm = H x P`` (not ``mamba_expand x hidden``), ``G =
  mamba_n_groups``, ``N = mamba_d_state``; ``p = (v W_in) * mup`` where
  ``mup`` repeats ``ssm_multipliers[0..4]`` over the sections ``[z d_in | xs
  d_in | B GN | C GN | dt_raw H]``; a depthwise causal convolution of width
  ``mamba_d_conv`` with bias over ``[xs | B | C]``, zeros before the first
  token, then silu; head ``h`` reads group ``h // (H / G)``; per head ``dt =
  softplus(dt_raw + dt_bias)`` (no clamp), ``A = -exp(A_log)``, ``H_t =
  exp(dt_t A) H_{t-1} + dt_t xs_t (x) B_t`` from ``H_0 = 0``, ``y_t = H_t
  C_t + D xs_t``; ``g = y * silu(z)`` FIRST (``mamba_norm_before_gate``
  false), then the norm PER GROUP of ``d_in / G`` channels, ``g *
  rsqrt(mean_group(g^2) + eps) * w_norm``; ``W_out``. Here the RECURRENCE
  itself, position by position (a ``lax.scan``): the program's chunked sum
  is checked against different arithmetic;
* ``MLP``: ``(up(v) * silu(gate(v) * mlp_multipliers[0])) W_down *
  mlp_multipliers[1]``;
* ``logits = (RMSNorm_f(x) W_head) * lm_head_multiplier``, an untied head.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST`` (what ``jax.default_matmul_precision("highest")``
sets); no kernels, no cache, no batching tricks. It imports nothing of the
program under test; ``matmul`` (with its int8-operand control) and
``rms_norm`` are the Granite-4.0-H reference's
(``reference/granitemoehybrid.py``) and ``rotate_halves`` the AFMoE
reference's (``reference/afmoe.py``): the same functions, not copied a
sixth time. Departures from the source, each for a stated reason:

* float32 throughout (the source runs bfloat16 with float32 inside the
  recurrence and the softmax): the reference is what the bf16 program is
  measured against;
* the source's fused kernels run the recurrence in chunks of
  ``mamba_chunk_size``; the sum is the same and the plain form is the scan;
* the convolution's weight is one leaf ``(channels, width)`` (the source
  stores ``(channels, 1, width)``), tap ``j`` multiplying the input ``width
  - 1 - j`` positions back;
* attention scores are formed for a block of queries at a time, rows go
  through in blocks of ``block_rows`` and the head's 261,120 outputs in
  blocks of ``HEAD_BLOCK`` columns, so that one layer's weights, one block's
  activations and one slab of logits are alive at a time.

``control=True`` computes the CONTROL as well: the same code with both
operands of every matrix product (the head's too) rounded to int8 (rows of
the activation, output channels of the weight, by their largest magnitude),
the precision below the bf16 that the configuration states. The recurrence,
the convolution and the rotation multiply no matrix and stay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe import rotate_halves
from benchmark.reference.granitemoehybrid import F32, HI, matmul, rms_norm

Q_BLOCK = 256
HEAD_BLOCK = 32768        # columns of the head a slab of logits holds


# ------------------------------------------------------------------ shapes
def dims(cfg):
    nh, hp = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        f=int(cfg["intermediate_size"]),
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        theta=float(cfg["rope_theta"]),
        mh=nh, mp=hp, d_in=nh * hp, groups=int(cfg["mamba_n_groups"]),
        n=int(cfg["mamba_d_state"]), conv=int(cfg["mamba_d_conv"]),
        eps=float(cfg["rms_norm_eps"]),
        emb_mult=float(cfg["embedding_multiplier"]),
        head_mult=float(cfg["lm_head_multiplier"]),
        att_in=float(cfg["attention_in_multiplier"]),
        att_out=float(cfg["attention_out_multiplier"]),
        key_mult=float(cfg["key_multiplier"]),
        ssm_in=float(cfg["ssm_in_multiplier"]),
        ssm_out=float(cfg["ssm_out_multiplier"]),
        ssm_mults=tuple(float(x) for x in cfg["ssm_multipliers"]),
        mlp_mults=tuple(float(x) for x in cfg["mlp_multipliers"]))


def leaf_table(cfg):
    """Every parameter of the model as (name, shape, kind), in a fixed
    order. ``kind`` is ``matrix`` | ``norm`` | ``bias``: how the benchmark
    draws it from the seed (``D`` and the norms ``norm``; ``A_log``,
    ``dt_bias`` and the convolution's bias ``bias``). Matrices are stored
    (in, out)."""
    m = dims(cfg)
    for what, bad in (
            ("a tied head", cfg["tie_word_embeddings"]),
            ("a bias on a projection",
             cfg["attention_bias"] or cfg["mamba_proj_bias"]
             or cfg["mlp_bias"] or cfg["projectors_bias"]),
            ("a convolution without bias", not cfg["mamba_conv_bias"]),
            ("another activation than silu", cfg["hidden_act"] != "silu"),
            ("mamba_n_heads x mamba_d_head != mamba_d_ssm",
             m["d_in"] != int(cfg["mamba_d_ssm"])),
            ("mamba_n_groups that does not divide mamba_n_heads",
             m["mh"] % m["groups"]),
            ("a mixer without its gated norm, or the norm before the gate",
             not cfg["mamba_rms_norm"] or cfg["mamba_norm_before_gate"]),
            ("attention in some layers only, or a layer without its MLP",
             cfg["attn_layer_indices"] is not None
             or not cfg["mamba_use_mlp"]),
            ("rope_scaling", cfg["rope_scaling"] is not None),
            ("five ssm_multipliers and two mlp_multipliers",
             len(m["ssm_mults"]) != 5 or len(m["mlp_mults"]) != 2)):
        if bad:
            raise NotImplementedError(what)
    h, f, d_in = m["h"], m["f"], m["d_in"]
    cd = d_in + 2 * m["groups"] * m["n"]
    out = [("top.embed", (m["v"], h), "matrix")]
    for i in range(m["layers"]):
        p = f"L{i}."
        out += [(p + "ln", (h,), "norm"),
                (p + "in_w", (h, d_in + cd + m["mh"]), "matrix"),
                (p + "conv_w", (cd, m["conv"]), "matrix"),
                (p + "conv_b", (cd,), "bias"),
                (p + "dt_bias", (m["mh"],), "bias"),
                (p + "A_log", (m["mh"],), "bias"),
                (p + "D", (m["mh"],), "norm"),
                (p + "ssm_ln", (d_in,), "norm"),
                (p + "out_w", (d_in, h), "matrix"),
                (p + "q_w", (h, m["nq"] * m["hd"]), "matrix"),
                (p + "k_w", (h, m["nkv"] * m["hd"]), "matrix"),
                (p + "v_w", (h, m["nkv"] * m["hd"]), "matrix"),
                (p + "o_w", (m["nq"] * m["hd"], h), "matrix"),
                (p + "ff_ln", (h,), "norm"),
                (p + "gate_w", (h, f), "matrix"),
                (p + "up_w", (h, f), "matrix"),
                (p + "down_w", (f, h), "matrix")]
    out += [("top.norm", (h,), "norm"), ("top.head", (h, m["v"]), "matrix")]
    return out


def layer_leaves(cfg, i):
    return [n for n, _, _ in leaf_table(cfg) if n.startswith(f"L{i}.")]


# ------------------------------------------------------------- arithmetic
def attention_branch(u, lp, m, control=False, q_block=Q_BLOCK):
    """Causal grouped-query attention of u (B, S, H) with rotated q and k,
    the keys times ``key_multiplier`` as they leave their product."""
    b, s, _ = u.shape
    nq, nkv, hd = m["nq"], m["nkv"], m["hd"]
    q = matmul(u, lp["q_w"], control).reshape(b, s, nq, hd)
    k = matmul(u, lp["k_w"], control).reshape(b, s, nkv, hd) * m["key_mult"]
    v = matmul(u, lp["v_w"], control).reshape(b, s, nkv, hd)
    q, k = rotate_halves(q, m["theta"]), rotate_halves(k, m["theta"])
    k, v = (jnp.repeat(t, nq // nkv, axis=2) for t in (k, v))
    outs = []
    for start in range(0, s, q_block):
        stop = min(start + q_block, s)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:stop], k[:, :stop],
                        precision=HI) * hd ** -0.5
        ok = jnp.arange(stop)[None, :] <= jnp.arange(start, stop)[:, None]
        p = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :stop],
                               precision=HI))
    return matmul(jnp.concatenate(outs, axis=1).reshape(b, s, nq * hd),
                  lp["o_w"], control)


def mixer_branch(u, lp, m, control=False):
    """The state-space mixer of u (B, S, H), as the recurrence; B and C a
    group of ``mh / groups`` heads, the gate before the per-group norm."""
    b, s, _ = u.shape
    d_in, n, k, g = m["d_in"], m["n"], m["conv"], m["groups"]
    gn = g * n
    cd = d_in + 2 * gn
    mz, mx, mb, mc, mdt = m["ssm_mults"]
    zxd = matmul(u, lp["in_w"], control)
    z = zxd[..., :d_in] * mz
    xbc = jnp.concatenate([zxd[..., d_in:2 * d_in] * mx,
                           zxd[..., 2 * d_in:2 * d_in + gn] * mb,
                           zxd[..., 2 * d_in + gn:d_in + cd] * mc], axis=-1)
    dt = jax.nn.softplus(zxd[..., d_in + cd:] * mdt
                         + lp["dt_bias"].astype(F32))          # (B, S, Hm)
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))   # zeros before token 0
    w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(pad[:, j:j + s] * w[:, j] for j in range(k))
                      + lp["conv_b"].astype(F32))
    xs = xbc[..., :d_in].reshape(b, s, m["mh"], m["mp"])
    per = m["mh"] // g                       # each head's own B and C row
    bm = jnp.repeat(xbc[..., d_in:d_in + gn].reshape(b, s, g, n), per, 2)
    cm = jnp.repeat(xbc[..., d_in + gn:].reshape(b, s, g, n), per, 2)
    a = -jnp.exp(lp["A_log"].astype(F32))
    d = lp["D"].astype(F32)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp  # (B, Hm, P), (B, Hm, N) x 2, (B, Hm)
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HI) \
            + d[:, None] * x_t
        return state, y_t

    _, y = jax.lax.scan(
        step, jnp.zeros((b, m["mh"], m["mp"], n), F32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, bm, cm, dt)))
    gated = (jnp.moveaxis(y, 0, 1).reshape(b, s, d_in) * jax.nn.silu(z)
             ).reshape(b, s, g, d_in // g)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + m["eps"])
    return matmul(normed.reshape(b, s, d_in) * lp["ssm_ln"].astype(F32),
                  lp["out_w"], control)


def mlp(y, lp, m, control=False):
    gate = jax.nn.silu(matmul(y, lp["gate_w"], control) * m["mlp_mults"][0])
    return matmul(matmul(y, lp["up_w"], control) * gate, lp["down_w"],
                  control) * m["mlp_mults"][1]


def _layer(x, lp, m, control):
    u = rms_norm(x, lp["ln"], m["eps"])
    x = (x + attention_branch(u * m["att_in"], lp, m, control) * m["att_out"]
         + mixer_branch(u * m["ssm_in"], lp, m, control) * m["ssm_out"])
    return x + mlp(rms_norm(x, lp["ff_ln"], m["eps"]), lp, m, control)


@functools.lru_cache(maxsize=None)
def _jitted(m_items):
    """One layer as a jitted function of (x, leaves, control), built once
    per set of dims so that every layer and block of rows reuses what was
    compiled."""
    m = dict(m_items)
    return jax.jit(lambda x, lp, c: _layer(x, lp, m, c), static_argnums=(2,))


def layer_forward(x, lp, m, control=False):
    """One layer. x (B, S, H) float32; lp: this layer's leaves by their
    short names."""
    return _jitted(tuple(sorted(m.items())))(x, lp, control)


def embed(cfg, get_leaf, ids):
    return (get_leaf("top.embed")[jnp.asarray(ids, jnp.int32)].astype(F32)
            * dims(cfg)["emb_mult"])


def forward_hidden(cfg, get_leaf, ids, control=False):
    """Token ids (B, S) -> the last layer's output (B, S, H), before the
    final norm."""
    m = dims(cfg)
    x = embed(cfg, get_leaf, ids)
    for i in range(m["layers"]):
        lp = {n.split(".", 1)[1]: get_leaf(n) for n in layer_leaves(cfg, i)}
        x = layer_forward(x, lp, m, control)
    return x


def head_logits(x, tp, m, control=False):
    """The untied head: (RMSNorm_f(x) W_head) * lm_head_multiplier."""
    return matmul(rms_norm(x, tp["norm"], m["eps"]), tp["head"],
                  control) * m["head_mult"]


def logits(cfg, get_leaf, ids, control=False):
    """Token ids (B, S) -> logits (B, S, V): the whole forward."""
    tp = {"norm": get_leaf("top.norm"), "head": get_leaf("top.head")}
    return head_logits(forward_hidden(cfg, get_leaf, ids, control), tp,
                       dims(cfg), control)


# ---------------------------------------------------------------- serving
def _head_gaps(x, xc, tp, tokens, m):
    """The gaps at the positions of x (B, T, H) for the served ``tokens``
    (B, T), the head taken ``HEAD_BLOCK`` columns at a time: the reference's
    best logit, the served token's and, with the control's stream ``xc``,
    the reference's logit at the control's own first choice."""
    y = rms_norm(x, tp["norm"], m["eps"])
    yc = None if xc is None else rms_norm(xc, tp["norm"], m["eps"])
    best = jnp.full(tokens.shape, -jnp.inf, F32)
    pick = jnp.zeros(tokens.shape, F32)
    cbest = jnp.full(tokens.shape, -jnp.inf, F32)
    cpick = jnp.zeros(tokens.shape, F32)
    for lo in range(0, m["v"], HEAD_BLOCK):
        w = tp["head"][:, lo:lo + HEAD_BLOCK]
        ref = matmul(y, w, False) * m["head_mult"]
        best = jnp.maximum(best, ref.max(axis=-1))
        inside = (tokens >= lo) & (tokens < lo + w.shape[1])
        at = jnp.clip(tokens - lo, 0, w.shape[1] - 1)
        pick = jnp.where(inside, jnp.take_along_axis(
            ref, at[..., None], axis=-1)[..., 0], pick)
        if yc is not None:
            ctl = matmul(yc, w, True) * m["head_mult"]
            first = jnp.argmax(ctl, axis=-1)
            top = jnp.take_along_axis(ctl, first[..., None], axis=-1)[..., 0]
            here = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
            cpick = jnp.where(top > cbest, here, cpick)
            cbest = jnp.maximum(cbest, top)
    return best - pick, None if yc is None else best - cpick


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
    head_gaps = jax.jit(functools.partial(_head_gaps, m=m))

    x0 = [embed(cfg, get_leaf, i) for i in ids]
    last = {}  # per arithmetic and block: the positions that predict the served
    for c in (False, True) if control else (False,):
        xs = list(x0)
        for i in range(m["layers"]):
            lp = {n.split(".", 1)[1]: get_leaf(n)
                  for n in layer_leaves(cfg, i)}
            xs = [layer_forward(x, lp, m, c) for x in xs]
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
