"""Plain reference of the Nemotron-H-shaped decoder (``model_type``
``nemotron_h``; here nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), written
from the published config's keys and the family's description (eps
``layer_norm_epsilon``):

* ``x_0 = Embed(ids)``; layer ``i`` is ONE part under one norm, ``x +=
  Part_i(RMSNorm_i(x))``, the part named by ``hybrid_override_pattern[i]``;
  ``logits = RMSNorm_f(x) W_head`` (an untied head; no multiplier anywhere);
* ``M``, Mamba-2: ``H = mamba_num_heads``, ``P = mamba_head_dim``, ``d_in =
  H x P`` (not ``expand x hidden``), ``G = n_groups``, ``N =
  ssm_state_size``; ``[z | xBC | dt_raw] = u W_in`` (``d_in | d_in + 2GN |
  H``); a depthwise causal convolution of width ``conv_kernel`` with bias
  over ``xBC``, zeros before the first token, then silu; ``[xs | B | C] =
  xBC`` with B and C of shape (G, N), head ``h`` using group ``h // (H /
  G)``; per head ``dt = softplus(dt_raw + dt_bias)`` (no clamp), ``A =
  -exp(A_log)``, ``H_t = exp(dt_t A) H_{t-1} + dt_t xs_t (x) B_t`` from
  ``H_0 = 0``, ``y_t = H_t C_t + D xs_t``; ``g = y * silu(z)``; the norm PER
  GROUP of ``d_in / G`` channels, ``g * rsqrt(mean_group(g^2) + eps) *
  w_norm``; ``W_out``. Here the RECURRENCE itself, position by position (a
  ``lax.scan``): the program's chunked sum is checked against different
  arithmetic;
* ``*``, attention: q (``num_attention_heads`` x ``head_dim``), k, v
  (``num_key_value_heads`` x ``head_dim``), o, no bias, grouped queries, NO
  rotary embedding, scale ``1 / sqrt(head_dim)``, causal softmax over the
  whole sequence;
* ``E``, experts: ``s = sigmoid(v W_r)`` over all ``published_experts``;
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s_chosen / (sum s_chosen + 1e-20) x routed_scaling_factor``; an
  expert is ungated, ``relu(v W_up)^2 W_down``, applied to the tokens routed
  to it and to no other, ONE HELD EXPERT AT A TIME. ``held_experts`` ``[lo,
  hi)``: only those experts exist here (one chip's share under expert
  parallelism); what the others would add is LEFT OUT, as in the program,
  and the partial result goes on to the next layer. Beside them the shared
  expert ``relu(v S_up)^2 S_down`` for every token.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST`` (what ``jax.default_matmul_precision("highest")``
sets); no kernels, no cache, no batching tricks, no sorting of tokens by
expert. It imports nothing of the program under test; ``matmul`` (with its
int8-operand control), ``rms_norm`` and the attention without positions are
the Granite-4.0-H reference's (``reference/granitemoehybrid.py``: the same
equations, not copied a fifth time). Departures from the source, each for
a stated reason:

* float32 throughout (the source runs bfloat16 with float32 inside the
  recurrence, the router and the softmaxes): the reference is what the bf16
  program is measured against;
* the source's fused kernels run the recurrence in chunks of ``chunk_size``;
  the sum is the same and the plain form is the scan;
* the convolution's weight is one leaf ``(channels, width)`` (the source
  stores ``(channels, 1, width)``), tap ``j`` multiplying the input ``width
  - 1 - j`` positions back; the held experts' matrices are stacked (``e_up``
  (held, in, out), ``e_down``), where the source keeps a module an expert;
* an expert's tokens are picked out on the host and padded to a multiple of
  ``EXPERT_ROW_BUCKET`` rows that point at a zero row; attention scores are
  formed for a block of queries at a time; rows go through in blocks of
  ``block_rows``, so that one layer's weights and one block's activations
  are alive at a time beside nothing else.

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

from benchmark.reference.granitemoehybrid import (
    EXPERT_ROW_BUCKET, F32, HI, attention_mixer, matmul, rms_norm)

PARTS = {"M": "mamba", "*": "attention", "E": "experts"}


# ------------------------------------------------------------------ shapes
def dims(cfg):
    held = int(cfg["n_routed_experts"])
    lo, hi = cfg.get("held_experts", [0, held])
    nh, hp = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    hd = int(cfg["head_dim"])
    return dict(
        h=int(cfg["hidden_size"]), v=int(cfg["vocab_size"]),
        layers=int(cfg["num_hidden_layers"]),
        kinds=str(cfg["hybrid_override_pattern"]),
        nq=int(cfg["num_attention_heads"]),
        nkv=int(cfg["num_key_value_heads"]), hd=hd, att_mult=hd ** -0.5,
        fe=int(cfg["moe_intermediate_size"]),
        fs=int(cfg["n_shared_experts"])
        * int(cfg["moe_shared_expert_intermediate_size"]),
        held=held, lo=int(lo), hi=int(hi),
        experts=int(cfg.get("published_experts", held)),
        top_k=int(cfg["num_experts_per_tok"]),
        route_norm=bool(cfg["norm_topk_prob"]),
        route_scale=float(cfg["routed_scaling_factor"]),
        mh=nh, mp=hp, d_in=nh * hp, groups=int(cfg["n_groups"]),
        n=int(cfg["ssm_state_size"]), conv=int(cfg["conv_kernel"]),
        eps=float(cfg["layer_norm_epsilon"]))


def leaf_table(cfg):
    """Every parameter of the model as (name, shape, kind), in a fixed
    order. ``kind`` is ``matrix`` | ``norm`` | ``bias``: how the benchmark
    draws it from the seed (``D`` and the norms ``norm``; ``A_log``,
    ``dt_bias``, the convolution's bias and the router's selection bias
    ``bias``). Matrices are stored (in, out); the held experts' are stacked
    (held, in, out)."""
    m = dims(cfg)
    for what, bad in (
            ("a tied head", cfg["tie_word_embeddings"]),
            ("a bias on a projection",
             cfg["attention_bias"] or cfg["mamba_proj_bias"]
             or cfg["use_bias"] or cfg["mlp_bias"]),
            ("a convolution without bias", not cfg["use_conv_bias"]),
            ("another activation than silu (mixer) and relu2 (experts)",
             cfg["mamba_hidden_act"] != "silu"
             or cfg["mlp_hidden_act"] != "relu2"),
            ("group-limited routing",
             int(cfg["n_group"]) != 1 or int(cfg["topk_group"]) != 1),
            ("n_groups that does not divide mamba_num_heads",
             m["mh"] % m["groups"]),
            ("residual_in_fp32", cfg["residual_in_fp32"]),
            ("held_experts of another size than n_routed_experts",
             m["hi"] - m["lo"] != m["held"] or m["hi"] > m["experts"]),
            ("a hybrid_override_pattern of another length than the depth, "
             "or of other parts than M | * | E",
             len(m["kinds"]) != m["layers"] or set(m["kinds"]) - set(PARTS))):
        if bad:
            raise NotImplementedError(what)
    h, cd = m["h"], m["d_in"] + 2 * m["groups"] * m["n"]
    out = [("top.embed", (m["v"], h), "matrix")]
    for i, kind in enumerate(m["kinds"]):
        p = f"L{i}."
        out.append((p + "ln", (h,), "norm"))
        if kind == "M":
            out += [(p + "in_w", (h, m["d_in"] + cd + m["mh"]), "matrix"),
                    (p + "conv_w", (cd, m["conv"]), "matrix"),
                    (p + "conv_b", (cd,), "bias"),
                    (p + "dt_bias", (m["mh"],), "bias"),
                    (p + "A_log", (m["mh"],), "bias"),
                    (p + "D", (m["mh"],), "norm"),
                    (p + "ssm_ln", (m["d_in"],), "norm"),
                    (p + "out_w", (m["d_in"], h), "matrix")]
        elif kind == "*":
            out += [(p + "q_w", (h, m["nq"] * m["hd"]), "matrix"),
                    (p + "k_w", (h, m["nkv"] * m["hd"]), "matrix"),
                    (p + "v_w", (h, m["nkv"] * m["hd"]), "matrix"),
                    (p + "o_w", (m["nq"] * m["hd"], h), "matrix")]
        else:
            out += [(p + "router_w", (h, m["experts"]), "matrix"),
                    (p + "router_b", (m["experts"],), "bias"),
                    (p + "e_up", (m["held"], h, m["fe"]), "matrix"),
                    (p + "e_down", (m["held"], m["fe"], h), "matrix"),
                    (p + "s_up", (h, m["fs"]), "matrix"),
                    (p + "s_down", (m["fs"], h), "matrix")]
    out += [("top.norm", (h,), "norm"), ("top.head", (h, m["v"]), "matrix")]
    return out


def layer_leaves(cfg, i):
    return [n for n, _, _ in leaf_table(cfg) if n.startswith(f"L{i}.")]


# ------------------------------------------------------------- arithmetic
def relu2_mlp(x, up_w, down_w, control):
    """The ungated expert: ``relu(x W_up)^2 W_down``."""
    return matmul(jnp.square(jax.nn.relu(matmul(x, up_w, control))), down_w,
                  control)


def mamba_mixer(u, lp, m, control=False):
    """The state-space mixer of u (B, S, H), as the recurrence; B and C a
    group of ``mh / groups`` heads, the gated norm a group of channels."""
    b, s, _ = u.shape
    d_in, n, k, g = m["d_in"], m["n"], m["conv"], m["groups"]
    cd = d_in + 2 * g * n
    zxd = matmul(u, lp["in_w"], control)
    z, xbc = zxd[..., :d_in], zxd[..., d_in:d_in + cd]
    dt = jax.nn.softplus(zxd[..., d_in + cd:]
                         + lp["dt_bias"].astype(F32))          # (B, S, Hm)
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))   # zeros before token 0
    w = lp["conv_w"].astype(F32)
    xbc = jax.nn.silu(sum(pad[:, j:j + s] * w[:, j] for j in range(k))
                      + lp["conv_b"].astype(F32))
    xs = xbc[..., :d_in].reshape(b, s, m["mh"], m["mp"])
    # each head's own B and C row: its group's
    per = m["mh"] // g
    bm = jnp.repeat(xbc[..., d_in:d_in + g * n].reshape(b, s, g, n), per, 2)
    cm = jnp.repeat(xbc[..., d_in + g * n:].reshape(b, s, g, n), per, 2)
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


def route(y, lp, m, control=False):
    """The gate: y (T, H) -> (chosen experts (T, k) among ALL published
    ones, weights (T, k)): sigmoid scores, the selection bias moving the
    choice and never the weight, the chosen scores normalised and scaled."""
    s = jax.nn.sigmoid(matmul(y, lp["router_w"], control))
    _, sel = jax.lax.top_k(s + lp["router_b"].astype(F32), m["top_k"])
    w = jnp.take_along_axis(s, sel, axis=-1)
    if m["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * m["route_scale"]


@functools.partial(jax.jit, static_argnums=(8,), donate_argnums=(0,))
def _apply_expert(out, ypad, wdense, idx, e, slab, e_up, e_down, control):
    """Add published expert ``e``'s (held slab ``slab``) weighted output
    for the rows ``idx`` (padding points at the zero row at the end)."""
    o = relu2_mlp(ypad[idx], e_up[slab], e_down[slab], control)
    return out.at[idx].add(o * wdense[idx, e][:, None])


@functools.lru_cache(maxsize=None)
def _jitted(m_items):
    """The pieces of a layer as jitted functions of (arrays..., control),
    built once per set of dims so that every layer and block of rows
    reuses what was compiled."""
    m = dict(m_items)

    def jit(fn):
        return jax.jit(fn, static_argnums=(2,))

    def part(fn):
        return jit(lambda x, lp, c: x + fn(
            rms_norm(x, lp["ln"], m["eps"]), lp, m, c))

    return {
        "M": part(mamba_mixer), "*": part(attention_mixer),
        "norm": jax.jit(lambda x, w: rms_norm(x, w, m["eps"])),
        "shared": jit(lambda y, lp, c: relu2_mlp(y, lp["s_up"],
                                                 lp["s_down"], c)),
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
    ``e_up`` is expert ``lo``."""
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
                            jnp.int32(e), jnp.int32(e - lo), lp["e_up"],
                            lp["e_down"], control)
    return out[:t]


def layer_forward(x, lp, m, i, control=False):
    """One layer, ONE part. x (B, S, H) float32; lp: this layer's leaves by
    their short names."""
    fn, kind = _fn(m), m["kinds"][i]
    if kind != "E":
        return fn[kind](x, lp, control)
    y = fn["norm"](x, lp["ln"])
    b, s, h = y.shape
    shared = fn["shared"](y, _pick(lp, "s_up", "s_down"), control)
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
    """The untied head: RMSNorm_f(x) W_head."""
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
