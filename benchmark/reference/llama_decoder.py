"""Plain reference of the Llama-style decoder stack (Mistral-7B, Qwen2-7B):
RMSNorm -> GQA attention with rotate-half rotary embedding, optional q/k/v
biases, optional causal sliding window (a query sees itself and the
``window - 1`` keys before it) -> residual -> RMSNorm -> SwiGLU -> residual;
final RMSNorm, untied output head, shifted next-token cross entropy, AdamW
with decoupled weight decay. As published in the models' ``modeling_*.py``.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, no cache, no batching tricks. It imports
nothing of the program under test. Departures from a textbook forward, all
to make 8k-token sequences at 7B widths fit one 16 GB chip:

* attention scores are formed for a block of queries at a time, and the
  block is recomputed in the backward pass (``jax.checkpoint``);
* training back-propagates layer by layer by hand (``jax.vjp`` of one
  layer at a time) and applies AdamW to a layer's leaves as soon as their
  gradients exist, so one layer's gradients are alive at a time;
* the output head's loss is taken in blocks of rows;
* leaves live on ``devices[i % n]`` by group (embedding, each layer, top),
  and plain ops follow their operands, so on a four-chip host the state is
  spread over the chips. No collective, no sharding rule.

``control=True`` computes the CONTROL instead: the same code with both
operands of every matrix product rounded to int8 (rows of the activation,
output channels of the weight, by their largest magnitude), the precision
below the bf16 that the configurations state. It stands in the program's
place and has to come out not correct.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ------------------------------------------------------------------ shapes
def dims(cfg):
    h = int(cfg["hidden_size"])
    nh = int(cfg["num_attention_heads"])
    nkv = int(cfg.get("num_key_value_heads") or nh)
    d = int(cfg.get("head_dim") or h // nh)
    return dict(h=h, nh=nh, nkv=nkv, d=d, f=int(cfg["intermediate_size"]),
                v=int(cfg["vocab_size"]), layers=int(cfg["num_hidden_layers"]),
                bias=bool(cfg.get("attention_bias", False)),
                window=cfg.get("sliding_window") or None,
                theta=float(cfg.get("rope_theta", 10000.0)),
                eps=float(cfg.get("rms_norm_eps", 1e-6)))


def leaf_table(cfg):
    """Every parameter of the model as (name, shape, kind), in a fixed
    order. ``kind`` is ``matrix`` | ``norm`` | ``bias``: how the benchmark
    draws it from the seed. Matrices are stored (in, out)."""
    m = dims(cfg)
    h, nh, nkv, d, f, v = m["h"], m["nh"], m["nkv"], m["d"], m["f"], m["v"]
    out = [("top.embed", (v, h), "matrix")]
    for i in range(m["layers"]):
        p = f"L{i}."
        out += [(p + "ln1", (h,), "norm"),
                (p + "q_w", (h, nh * d), "matrix"),
                (p + "k_w", (h, nkv * d), "matrix"),
                (p + "v_w", (h, nkv * d), "matrix")]
        if m["bias"]:
            out += [(p + "q_b", (nh * d,), "bias"),
                    (p + "k_b", (nkv * d,), "bias"),
                    (p + "v_b", (nkv * d,), "bias")]
        out += [(p + "o_w", (nh * d, h), "matrix"),
                (p + "ln2", (h,), "norm"),
                (p + "gate_w", (h, f), "matrix"),
                (p + "up_w", (h, f), "matrix"),
                (p + "down_w", (f, h), "matrix")]
    out += [("top.norm", (h,), "norm"), ("top.head", (h, v), "matrix")]
    return out


def groups(cfg):
    """Leaf names by group: the embedding, each layer, the top."""
    by = {}
    for name, _, _ in leaf_table(cfg):
        g = name.split(".")[0]
        if name == "top.embed":
            g = "embed"
        by.setdefault(g, []).append(name)
    order = ["embed"] + [f"L{i}" for i in range(dims(cfg)["layers"])] + ["top"]
    return [(g, by[g]) for g in order]


# ------------------------------------------------------------- arithmetic
def _fake_int8(x, axis):
    """Round to 255 levels of the largest magnitude along ``axis``; the
    gradient passes straight through."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return x + jax.lax.stop_gradient(jnp.round(x / s) * s - x)


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


def rotate(x, positions, theta):
    """Rotate-half rotary embedding. x (B, S, H, D), positions (S,)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, q_block=512):
    """Causal grouped-query attention. q (B, S, NH, D); k, v (B, S, NKV, D).
    With ``window`` query i sees keys i-window+1 .. i."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    q = q.reshape(b, s, nkv, nh // nkv, d)
    scale = 1.0 / math.sqrt(d)

    @jax.checkpoint
    def block(qs, ks, vs, qpos, kpos):
        sc = jnp.einsum("bqkgd,bjkd->bkgqj", qs, ks, precision=HI) * scale
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        sc = jnp.where(ok[None, None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bkgqj,bjkd->bqkgd", p, vs, precision=HI)

    outs = []
    for start in range(0, s, q_block):
        stop = min(start + q_block, s)
        lo = max(0, start - window + 1) if window else 0
        outs.append(block(q[:, start:stop], k[:, lo:stop], v[:, lo:stop],
                          jnp.arange(start, stop), jnp.arange(lo, stop)))
    return jnp.concatenate(outs, axis=1).reshape(b, s, nh * d)


def layer_forward(x, lp, m, control=False):
    """One decoder layer. x (B, S, H) float32; lp: this layer's leaves by
    their short names (``ln1``, ``q_w``, ...)."""
    b, s, _ = x.shape
    pos = jnp.arange(s)
    y = rms_norm(x, lp["ln1"], m["eps"])
    q = matmul(y, lp["q_w"], control)
    k = matmul(y, lp["k_w"], control)
    v = matmul(y, lp["v_w"], control)
    if m["bias"]:
        q = q + lp["q_b"].astype(F32)
        k = k + lp["k_b"].astype(F32)
        v = v + lp["v_b"].astype(F32)
    q = rotate(q.reshape(b, s, m["nh"], m["d"]), pos, m["theta"])
    k = rotate(k.reshape(b, s, m["nkv"], m["d"]), pos, m["theta"])
    v = v.reshape(b, s, m["nkv"], m["d"])
    x = x + matmul(attention(q, k, v, m["window"]), lp["o_w"], control)
    y = rms_norm(x, lp["ln2"], m["eps"])
    act = jax.nn.silu(matmul(y, lp["gate_w"], control)) * matmul(
        y, lp["up_w"], control)
    return x + matmul(act, lp["down_w"], control)


def head_logits(x, tp, m, control=False):
    return matmul(rms_norm(x, tp["norm"], m["eps"]), tp["head"], control)


# ---------------------------------------------------------------- serving
def gap_below_best(cfg, get_leaf, rows, control=False, block_rows=16):
    """For each row (prompt tokens, served tokens): one full forward over
    prompt + served[:-1], then at every served position the gap by which
    the served token's logit lies below the best logit.

    With ``control`` the forward is ALSO run as the control, and the gap
    read is that of the token the control puts first (the control stands in
    the program's place; it need not decode).

    Returns (gaps, control_gaps): float32 arrays over all served positions
    (``control_gaps`` None without a control). Layer by layer over blocks of
    at most ``block_rows`` rows of one shape (no padding), so one layer's
    float32 weights and one block's logits are alive at a time.
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

    layer = jax.jit(lambda x, lp, c: layer_forward(x, lp, m, c),
                    static_argnums=(2,))

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
                  for g, names in groups(cfg) if g == f"L{i}" for n in names}
            xs = [layer(x, lp, c) for x in xs]
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


# --------------------------------------------------------------- training
def _loss_rows(x, tp, labels, m, control):
    """Sum over rows of the next-token cross entropy. x (N, H), labels (N,)."""
    logits = head_logits(x, tp, m, control)
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - pick)


def _adamw(p, g, mo, ve, t, hp):
    mo = hp["beta1"] * mo + (1 - hp["beta1"]) * g
    ve = hp["beta2"] * ve + (1 - hp["beta2"]) * jnp.square(g)
    m_hat = mo / (1 - hp["beta1"] ** t)
    v_hat = ve / (1 - hp["beta2"] ** t)
    new = p - hp["lr"] * m_hat / (jnp.sqrt(v_hat) + hp["eps"])
    new = new - hp["lr"] * hp["weight_decay"] * p
    return new, mo, ve


def train_steps(cfg, get_leaf, batches, hp, devices, control=False,
                head_rows=2048):
    """Follow ``len(batches)`` AdamW steps from the seed's weights.
    ``batches``: int32 arrays (B, S); labels are the inputs (the loss
    shifts). ``hp``: lr, beta1, beta2, eps, weight_decay.

    Returns {"losses": [...], "grad_norm": {leaf: norm of the first
    gradient}, "delta_norm": {leaf: norm of the change after all steps}}.
    """
    m = dims(cfg)
    grp = groups(cfg)
    place = {g: devices[i % len(devices)] for i, (g, _) in enumerate(grp)}
    names_of = dict(grp)

    # state: float32 weights and both moments, by group, on its device
    P, M, V = {}, {}, {}
    for g, names in grp:
        for n in names:
            w = jax.device_put(get_leaf(n), place[g]).astype(F32)
            P[n] = w
            M[n] = jnp.zeros_like(w)
            V[n] = jnp.zeros_like(w)

    update = jax.jit(lambda p, g, mo, ve, t: _adamw(p, g, mo, ve, t, hp),
                     donate_argnums=(0, 2, 3))
    sq = jax.jit(lambda a: jnp.sum(jnp.square(a)))
    sqdiff = jax.jit(lambda a, b: jnp.sum(jnp.square(a - b.astype(F32))))

    layer_fwd = jax.jit(lambda x, lp: layer_forward(x, lp, m, control))

    @jax.jit
    def layer_bwd(x, lp, dy):
        _, vjp = jax.vjp(lambda a, b: layer_forward(a, b, m, control), x, lp)
        return vjp(dy)

    @jax.jit
    def head_bwd(x, tp, labels, scale):
        loss, (dx, dtp) = jax.value_and_grad(
            lambda a, b: _loss_rows(a, b, labels, m, control) * scale,
            argnums=(0, 1))(x, tp)
        return loss, dx, dtp

    @jax.jit
    def embed_grad(ids, dx, like):
        return jnp.zeros_like(like).at[ids.reshape(-1)].add(
            dx.reshape(-1, dx.shape[-1]))

    losses, grad_norm = [], {}

    def apply(n, g, t):
        if t == 1:
            grad_norm[n] = sq(g)
        P[n], M[n], V[n] = update(P[n], g, M[n], V[n], jnp.float32(t))

    for t, ids in enumerate(batches, start=1):
        ids = jnp.asarray(ids, jnp.int32)
        b, s = ids.shape
        xs = [jax.device_put(P["top.embed"][jax.device_put(
            ids, place["embed"])], place["L0"])]
        for i in range(m["layers"]):
            g = f"L{i}"
            lp = {n.split(".", 1)[1]: P[n] for n in names_of[g]}
            nxt = place[f"L{i + 1}"] if i + 1 < m["layers"] else place["top"]
            xs.append(jax.device_put(layer_fwd(xs[-1], lp), nxt))
        # head: rows 0..S-2 of each sequence predict tokens 1..S-1
        x = xs.pop()[:, :-1].reshape(b * (s - 1), -1)
        labels = jax.device_put(ids[:, 1:].reshape(-1), place["top"])
        tp = {"norm": P["top.norm"], "head": P["top.head"]}
        scale = jnp.float32(1.0 / (b * (s - 1)))
        loss = jnp.float32(0)
        dxs, dtp = [], None
        for r in range(0, x.shape[0], head_rows):
            l_r, dx_r, dtp_r = head_bwd(x[r:r + head_rows], tp,
                                        labels[r:r + head_rows], scale)
            loss = loss + l_r
            dxs.append(dx_r)
            dtp = dtp_r if dtp is None else jax.tree_util.tree_map(
                jnp.add, dtp, dtp_r)
        losses.append(loss)
        del tp
        apply("top.norm", dtp["norm"], t)
        apply("top.head", dtp["head"], t)
        del dtp
        dy = jnp.concatenate(dxs).reshape(b, s - 1, -1)
        dy = jnp.pad(dy, ((0, 0), (0, 1), (0, 0)))
        del dxs, x
        for i in reversed(range(m["layers"])):
            g = f"L{i}"
            lp = {n.split(".", 1)[1]: P[n] for n in names_of[g]}
            dy, dlp = layer_bwd(xs.pop(), lp, jax.device_put(dy, place[g]))
            del lp
            for n in names_of[g]:
                apply(n, dlp.pop(n.split(".", 1)[1]), t)
        ids_e = jax.device_put(ids, place["embed"])
        apply("top.embed", embed_grad(
            ids_e, jax.device_put(dy, place["embed"]), P["top.embed"]), t)
        del dy

    delta = {n: sqdiff(P[n], jax.device_put(get_leaf(n), P[n].devices().pop()))
             for n in P}
    return {"losses": [float(x) for x in losses],
            "grad_norm": {n: math.sqrt(float(v)) for n, v in grad_norm.items()},
            "delta_norm": {n: math.sqrt(float(v)) for n, v in delta.items()}}
