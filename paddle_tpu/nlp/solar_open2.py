"""Solar-Open2-shaped decoder (``model_type`` ``solar_open2``:
upstage/Solar-Open2-250B): three layers in four mix tokens by Kimi Delta
Attention (KDA, arXiv:2510.26692), a linear attention whose heads each keep a
``d x d`` MATRIX state and correct it with a delta rule under a per-channel
decay; the fourth by gated grouped-query attention WITHOUT positions; every
layer's feed-forward is routed experts beside one shared expert.

Equations (``x`` the layer's RMS-normed input, eps ``rms_norm_eps``): layer
``i`` is GQA if ``i in gqa_layers`` (every ``gqa_interval + 1``-th from 0),
else KDA; ``h += mixer(norm1(h)); h += moe(norm2(h))``; ``logits =
RMSNorm(h) W_head`` (untied).

- KDA (:class:`KimiDeltaAttention`; ``linear_attn_config``: H ``num_heads``
  of ``head_dim`` d, ``short_conv_kernel_size``): ``q~ = x W_q``, ``k~ = x
  W_k``, ``v~ = x W_v`` (hidden -> H d each); ``q, k, v = silu(causal
  depthwise conv(.))``, no bias, a convolution each, zeros before a
  sequence's first token; per head ``q^ = q / sqrt(|q|^2 + 1e-6) *
  d^-1/2``, ``k^ = k / sqrt(|k|^2 + 1e-6)``; the decay, per head AND
  channel: ``a = (x W_fa) W_fb`` (hidden -> d -> H d), ``g_t = -exp(A_log_h)
  * softplus(a_t + dt_bias)`` (<= 0), ``alpha_t = exp(g_t)``; ``beta_t =
  sigmoid(x W_beta)`` (hidden -> H), times 2 with ``kda_allow_neg_eigval``;
  the state S (d x d a head, float32) from zeros:

      ``S' = Diag(alpha_t) S_{t-1}``
      ``S_t = S' + beta_t k^_t (v_t - S'^T k^_t)^T``
      ``o_t = S_t^T q^_t``

  (:func:`kda_step`); ``y = RMSNorm_d(o_t) * sigmoid((x W_ga) W_gb)``,
  ``out = concat(y) W_o``. ``kda_use_full_proj`` false: both gates go
  through the rank-d pair.
- The same over a chunk of C positions from ``S_0`` (:func:`kda_chunk`),
  ``Gamma_t`` the running sum of g inside the chunk: ``A[t, s] = beta_t
  sum_c k^_t[c] k^_s[c] exp(Gamma_t[c] - Gamma_s[c])`` for s < t; ``U = (I
  + A)^-1 Diag(beta) (V - (K^ * exp(Gamma)) S_0)``; ``O = (Q^ * exp(Gamma))
  S_0 + lower_incl(q^_t . k^_s exp(Gamma_t - Gamma_s)) U``; ``S_C =
  Diag(exp(Gamma_C)) S_0 + (K^ * exp(Gamma_C - Gamma))^T U``. Only RATIOS
  ``exp(Gamma_t - Gamma_s)``, s <= t, are ever formed (``exp(-Gamma)``
  overflows under a strong decay), and ``(I + A)^-1`` is the product
  ``prod_j (I + (-A)^(2^j))`` (A is nilpotent): matrix products, no loop
  over the chunk's rows. A position with ``g = 0`` and ``beta = 0`` is the
  identity.
- GQA (:class:`SolarOpen2Attention`): ``NoPositionAttention`` (``use_rope``
  false: ``rope_theta`` and ``partial_rotary_factor`` are read by nothing)
  at scale ``head_dim^-1/2`` with ``use_gqa_gate``: ``out = (concat(att) *
  sigmoid(x W_gate)) W_o``; no q/k norm.
- Experts (``nlp/routed_experts.py``, the block the deepseek_v3, afmoe and
  nemotron_h families use): sigmoid scores in float32, a selection bias for
  the choice only, the ``num_experts_per_tok`` chosen scores over their sum
  (``norm_topk_prob``) times ``routed_scaling_factor``; SwiGLU experts of
  ``moe_intermediate_size`` beside ``n_shared_experts`` shared ones of the
  same width. ``held_experts = (lo, n)``: the router keeps its
  ``n_routed_experts`` outputs and this chip holds, and computes, experts
  ``lo .. lo + n - 1``; an absent choice adds exactly zero.

What a layer caches (``paged_cache_layout``): a GQA layer K and V blocks
(``"kv"``); a KDA layer a row of the pool's slot side (``"state"``): the
matrix state ``(H, d, d)`` in float32 and the three convolutions' last
``short_conv_kernel_size - 1`` inputs, whatever the context.

Parameter names follow the public ``KimiDeltaAttention`` (``q_proj``,
``q_conv1d``, ``A_log``, ``f_a_proj``, ``f_b_proj``, ``dt_bias``,
``b_proj``, ``g_a_proj``, ``g_b_proj``, ``o_norm``, ``o_proj``) and the
DeepSeek-V3-shaped sources' expert block (``mlp.gate.weight``,
``mlp.gate.e_score_correction_bias``, ``mlp.experts``,
``mlp.shared_experts``); the GQA gate is ``gate_proj``.

Serving only (``paddle.inference.serve``); ``forward`` is the plain
whole-sequence pass the tests compare with. Not done here: training,
``generate`` over a dense cache, tensor parallelism, ``kda_use_full_proj``,
KDA heads that share keys (``num_kv_heads``), rotary positions, leading
dense layers, a tied head, an ungated GQA.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..ops.pallas._utils import use_pallas_kernels
from .granitemoehybrid import NoPositionAttention, conv_silu
from .paged_attention import normed, sigmoid_gated_out
from .routed_experts import GateLeaves, SigmoidRoutedExperts

__all__ = ["SolarOpen2Config", "KimiDeltaAttention", "SolarOpen2Attention",
           "SolarOpen2MoE", "SolarOpen2DecoderLayer", "SolarOpen2Model",
           "SolarOpen2ForCausalLM", "kda_chunk", "kda_step"]

F32 = jnp.float32
# the chunk's triangular system is solved by products of C x C matrices
# whose powers cancel against one another: three bf16 passes an operand
# (float32 to ~2^-16), where the chunk's other products take the default
SOLVE = jax.lax.Precision.HIGH
# the float32 temporaries of `kda_chunk` a row and chunk (a dozen arrays of
# (H, C, d)) may take this many bytes in all; more rows than that stream
# through in equal groups (a shape rule as `granitemoehybrid._DECAY_BYTES`:
# no knob)
_CHUNK_BYTES = 256 << 20
# the positions `kda_chunk` takes at a time: the blocking of a sum, which no
# published key names (a power of two; a shorter step is one chunk)
KDA_CHUNK = 64


class SolarOpen2Config:
    """The published ``config.json`` keys: those the layer equations read,
    every other one taken and refused by name where it asks for something
    that is not computed; ``held_experts``: ``(lo, n)``, the routed experts
    this chip holds (default: all)."""

    def __init__(self, vocab_size=196608, hidden_size=4096,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128, linear_attn_config=None,
                 gqa_interval=3, gqa_layers=None, use_rope=False,
                 use_gqa_gate=True, kda_use_full_proj=False,
                 kda_allow_neg_eigval=True, intermediate_size=10240,
                 moe_intermediate_size=1280, n_routed_experts=320,
                 n_shared_experts=1, num_experts_per_tok=8,
                 norm_topk_prob=True, routed_scaling_factor=1,
                 first_k_dense_replace=0, held_experts=None,
                 rms_norm_eps=1e-5, rope_theta=10000, partial_rotary_factor=1,
                 tie_word_embeddings=False, max_position_embeddings=1048576,
                 sliding_window=None,
                 model_type="solar_open2", dtype="float32"):
        lin = dict(linear_attn_config or {
            "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
            "num_kv_heads": None})
        every = list(range(0, num_hidden_layers, int(gqa_interval) + 1))
        if gqa_layers is None:
            gqa_layers = every
        for what, bad in (
                ("a model_type other than solar_open2",
                 model_type != "solar_open2"),
                ("kda_use_full_proj (a full matrix for each gate)",
                 kda_use_full_proj),
                ("linear_attn_config.num_kv_heads (KDA heads that share "
                 "keys)", lin.get("num_kv_heads") is not None),
                ("use_rope (rotary positions in the GQA layers)", use_rope),
                ("first_k_dense_replace (leading dense layers)",
                 first_k_dense_replace),
                ("a tied output head (tie_word_embeddings)",
                 tie_word_embeddings),
                ("gqa_layers other than every (gqa_interval + 1)-th layer "
                 "from 0", list(gqa_layers) != every),
                ("use_gqa_gate false (an ungated GQA)", not use_gqa_gate),
                ("sliding_window", sliding_window)):
            if bad:
                raise NotImplementedError(
                    f"SolarOpen2: {what} is not implemented")
        lo, n = held_experts or (0, n_routed_experts)
        if not 0 <= lo < lo + n <= n_routed_experts:
            raise ValueError(
                f"held_experts {held_experts} is no range of the "
                f"{n_routed_experts} experts")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.kda_heads = int(lin["num_heads"])
        self.kda_head_dim = int(lin["head_dim"])
        self.kda_conv = int(lin["short_conv_kernel_size"])
        self.kda_allow_neg_eigval = bool(kda_allow_neg_eigval)
        self.gqa_layers = tuple(gqa_layers)
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.held_experts = (int(lo), int(n))
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        # taken and read by nothing: ``rope_theta`` and
        # ``partial_rotary_factor`` (``use_rope`` is false),
        # ``intermediate_size`` (a dense layer's width: there is none)
        # what ``LlamaAttention`` and the engine read of any config
        self.attention_bias = False
        self.tensor_parallel = False
        self.sliding_window = None
        self.dtype = dtype

    @staticmethod
    def tiny(**overrides):
        """Test-scale config: every mechanism at toy widths (one period:
        GQA then three KDA layers; 4 KDA heads of 8; 8 experts, top 3)."""
        cfg = dict(vocab_size=128, hidden_size=32, num_hidden_layers=4,
                   num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                   linear_attn_config={"short_conv_kernel_size": 4,
                                       "head_dim": 8, "num_heads": 4,
                                       "num_kv_heads": None},
                   moe_intermediate_size=24, n_routed_experts=8,
                   num_experts_per_tok=3,
                   max_position_embeddings=256)
        cfg.update(overrides)
        return SolarOpen2Config(**cfg)

    @staticmethod
    def solar_open2_250b(**overrides):
        """upstage/Solar-Open2-250B as published (the defaults)."""
        return SolarOpen2Config(**overrides)


# ------------------------------------------------------------ the recurrence
def kda_step(q, k, v, g, beta, s0):
    """One position of the delta rule (the module's three lines), in
    float32: ``q``, ``k`` (S, H, d) normalised, ``v`` (S, H, dv), ``g``
    (S, H, d) <= 0 the log decay, ``beta`` (S, H), ``s0`` (S, H, d, dv).
    Returns ``o`` (S, H, dv) and the new state. ``S'`` is never asked for
    twice: ``S'^T k = S^T (alpha k)``."""
    q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
    alpha = jnp.exp(g.astype(F32))
    answered = jnp.sum(s0 * (alpha * k)[..., None], axis=-2)   # S'^T k
    u = beta.astype(F32)[..., None] * (v - answered)
    s1 = s0 * alpha[..., None] + k[..., None] * u[..., None, :]
    return jnp.sum(s1 * q[..., None], axis=-2), s1


def kda_chunk(q, k, v, g, beta, s0, keep=None):
    """The delta rule over one chunk (the module's second equation block),
    in float32. ``q``, ``k`` (S, C, H, d) normalised, ``v`` (S, C, H, dv),
    ``g`` (S, C, H, d) <= 0, ``beta`` (S, C, H) (a position with ``g = 0``
    and ``beta = 0`` is the identity), ``s0`` (S, H, d, dv) the incoming
    state; C a power of two; ``keep`` (S,) 0 | 1 multiplies what ``s0``
    adds (0: the row starts from a zero state; the factor rides the decays,
    so no zeroed copy of the state is made). Returns ``o`` (S, C, H, dv)
    and the outgoing state.

    Every decay is a ratio ``exp(Gamma_t - Gamma_s)``, s <= t, formed by
    HALVING: a pair s < t lies in the two halves of exactly one aligned
    block of 2m positions (m = C/2 .. 1), and there the ratio is
    ``exp(Gamma_t - r) exp(r - Gamma_s)`` with ``r`` the running sum at the
    lower half's last position, both factors <= 1 (for either half
    ``exp(-|Gamma - r|)``). A level is one product over the whole chunk
    under a constant mask of the level's (upper row, lower key) pairs: log2
    C products the matrix unit does, no tensor a pair and channel."""
    s_, c, h, dk = k.shape
    if c & (c - 1):
        raise ValueError(f"kda_chunk takes a power of two positions, not {c}")
    q, k, v, g = (t.astype(F32).transpose(0, 2, 1, 3) for t in (q, k, v, g))
    beta = beta.astype(F32).transpose(0, 2, 1)                 # (S, H, C)
    gam = jnp.cumsum(g, axis=2)                                # (S, H, C, d)
    x = jnp.stack([k, q])           # the keys and the queries at t, at once
    at = jnp.arange(c)
    # x_t . k_s exp(Gamma_t - Gamma_s) for s < t: (2, S, H, C, C)
    pairs = jnp.zeros((2, s_, h, c, c), F32)
    m = c // 2
    while m:
        halves = gam.reshape(s_, h, c // (2 * m), 2, m, dk)
        ref = halves[:, :, :, :1, -1:]          # at the lower half's end
        # an upper half's exp(Gamma_t - r), a lower half's exp(r - Gamma_s)
        scaled = x * jnp.exp(-jnp.abs(halves - ref)).reshape(gam.shape)
        upper = (at // m) % 2 == 1
        level = ((at // (2 * m))[:, None] == (at // (2 * m))[None, :]) \
            & upper[:, None] & ~upper[None, :]
        pairs = pairs + jnp.einsum("xshtd,shud->xshtu", scaled,
                                   scaled[0]) * level.astype(F32)
        m //= 2
    a = pairs[0] * beta[..., None]
    # (I + A)^-1 = prod_j (I + (-A)^(2^j)): A is strictly lower
    inv, power = jnp.eye(c, dtype=F32) - a, -a
    for _ in range((c - 1).bit_length() - 1):
        power = jnp.matmul(power, power, precision=SOLVE)
        inv = inv + jnp.matmul(inv, power, precision=SOLVE)
    decay = jnp.exp(gam)
    carried, asked, left = k * decay, q * decay, decay[:, :, -1]
    if keep is not None:
        kept = keep.astype(F32)[:, None, None, None]
        carried, asked, left = carried * kept, asked * kept, left * kept[..., 0]
    u = jnp.matmul(inv, beta[..., None] * (v - jnp.matmul(carried, s0)))
    # position t sees its own correction whole: q^_t . k^_t on the diagonal
    o = (jnp.matmul(asked, s0) + jnp.matmul(pairs[1], u)
         + jnp.sum(q * k, axis=-1, keepdims=True) * u)
    s1 = s0 * left[..., None] + jnp.einsum(
        "shcd,shcv->shdv", k * jnp.exp(gam[:, :, -1:] - gam), u)
    return o.transpose(0, 2, 1, 3), s1


class _ShortConv(Layer):
    """A depthwise convolution's taps, no bias: ``weight`` (channels,
    width), tap ``j`` multiplying the input ``width - 1 - j`` positions
    back."""

    def __init__(self, channels, width):
        super().__init__()
        self.weight = self.create_parameter(
            (channels, width), default_initializer=I.XavierNormal())


def _l2norm(x):
    """(..., d) over the last axis in float32: ``x / sqrt(|x|^2 + 1e-6)``."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


class KimiDeltaAttention(Layer):
    """The KDA mixer of the module's equations, by its sizes: ``heads`` of
    ``head_dim`` (keys and values alike), convolutions of width ``conv``;
    ``forward`` and ``paged_chunk`` take ``chunk_size`` positions at a time
    through :func:`kda_chunk`, ``paged_decode`` one through
    :func:`kda_step`."""

    def __init__(self, hidden_size, heads, head_dim, conv=4, eps=1e-5,
                 neg_eigval=True, chunk_size=KDA_CHUNK):
        super().__init__()
        if chunk_size & (chunk_size - 1):
            raise ValueError(f"chunk_size {chunk_size} is no power of two")
        self.heads, self.head_dim, self.conv = heads, head_dim, conv
        self.chunk_size, self.eps = int(chunk_size), float(eps)
        self.beta_scale = 2.0 if neg_eigval else 1.0
        d = heads * head_dim
        for name in "qkv":
            setattr(self, f"{name}_proj", Linear(hidden_size, d,
                                                 bias_attr=False))
            setattr(self, f"{name}_conv1d", _ShortConv(d, conv))
        self.A_log = self.create_parameter((heads,), is_bias=True)
        self.f_a_proj = Linear(hidden_size, head_dim, bias_attr=False)
        self.f_b_proj = Linear(head_dim, d, bias_attr=False)
        self.dt_bias = self.create_parameter((d,), is_bias=True)
        self.b_proj = Linear(hidden_size, heads, bias_attr=False)
        self.g_a_proj = Linear(hidden_size, head_dim, bias_attr=False)
        self.g_b_proj = Linear(head_dim, d, bias_attr=False)
        self.o_norm = RMSNorm(head_dim, epsilon=eps)
        self.o_proj = Linear(d, hidden_size, bias_attr=False)

    def state_arrays(self):
        """What a slot keeps for this layer, as ``(shape, dtype)``: the
        matrix state in float32 and the three convolutions' last inputs
        in the model's dtype (None)."""
        tail = ((self.conv - 1, self.heads * self.head_dim), None)
        return [((self.heads, self.head_dim, self.head_dim), "float32"),
                tail, tail, tail]

    # -- shared pieces ------------------------------------------------------
    def _heads(self, x):
        return x.reshape(*x.shape[:-1], self.heads, self.head_dim)

    def _stream(self, name, x, tail):
        """One of q / k / v of the normed input (S, C, E): its product, the
        window behind ``tail`` (S, width - 1, D), the convolution -> the
        stream as heads (S, C, H, d) and the window."""
        with jax.named_scope("kda.proj"):
            raw = getattr(self, f"{name}_proj")(x)._value
        with jax.named_scope("kda.conv"):
            window = jnp.concatenate([tail.astype(raw.dtype), raw], axis=1)
            return self._heads(conv_silu(
                window, getattr(self, f"{name}_conv1d").weight._value)
            ), window

    def _gates(self, x):
        """The log decay (S, C, H, d) <= 0 and beta (S, C, H), float32."""
        with jax.named_scope("kda.gates"):
            a = self._heads(self.f_b_proj(self.f_a_proj(x))._value)
            g = -jnp.exp(self.A_log._value.astype(F32))[:, None] \
                * jax.nn.softplus(a.astype(F32) + self._heads(
                    self.dt_bias._value.astype(F32)))
            beta = jax.nn.sigmoid(self.b_proj(x)._value.astype(F32)) \
                * self.beta_scale
            return g, beta

    def _normalised(self, q, k):
        with jax.named_scope("kda.gates"):
            return _l2norm(q) * self.head_dim ** -0.5, _l2norm(k)

    def _out(self, o, x):
        """The norm per head, the low-rank gate, the output product: ``o``
        (S, C, H, d) float32, ``x`` the normed input."""
        with jax.named_scope("kda.out"):
            gate = self._heads(self.g_b_proj(self.g_a_proj(x))._value)
            y = o * jax.lax.rsqrt(
                jnp.mean(jnp.square(o), axis=-1, keepdims=True) + self.eps)
            y = (y * self.o_norm.weight._value.astype(F32)
                 * jax.nn.sigmoid(gate.astype(F32))).astype(gate.dtype)
            return self.o_proj(Tensor(y.reshape(*y.shape[:2], -1),
                                      stop_gradient=True))

    def _rows(self, q, k, v, g, beta, state, keep):
        """Some rows' C positions, ``chunk_size`` at a time from ``state``
        (``keep`` is the first chunk's). A length that is no whole number
        of chunks (``forward`` alone: the engine's are powers of two) is
        padded with positions that bring nothing."""
        q, k = self._normalised(q, k)
        c = q.shape[1]
        size = min(self.chunk_size, 1 << (c - 1).bit_length())
        pad = -c % size
        if pad:
            q, k, v, g, beta = (
                jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                for t in (q, k, v, g, beta))
        outs = []
        for lo in range(0, c + pad, size):
            cut = slice(lo, lo + size)
            o, state = kda_chunk(q[:, cut], k[:, cut], v[:, cut], g[:, cut],
                                 beta[:, cut], state,
                                 keep if lo == 0 else None)
            outs.append(o)
        o = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        return (o[:, :c] if pad else o), state

    def _recur(self, q, k, v, g, beta, state0, keep=None, live=None):
        """The delta rule over (S, C) positions from ``state0``; rows that
        are not ``live`` keep their state. Rows stream through in equal
        groups under ``_CHUNK_BYTES``, each group's state rows read from and
        written to the one carried array, so a donated state is updated in
        place."""
        s_, c = q.shape[:2]
        per_row = 12 * self.heads * min(c, self.chunk_size) \
            * self.head_dim * 4
        group = max(1, min(s_, _CHUNK_BYTES // per_row))
        while s_ % group:
            group -= 1

        def rows(state_all, lo, n):
            def cut(t):
                return None if t is None else \
                    jax.lax.dynamic_slice_in_dim(t, lo, n, 0)

            old = cut(state_all)
            if n < s_:
                # ONE read of the carried array a group: its products each
                # slicing the whole array for themselves made the compiler
                # copy all of it, twice a group (seen in the third layer's
                # loop of the cell's compiled mixed step)
                old = jax.lax.optimization_barrier(old)
            o, new = self._rows(*map(cut, (q, k, v, g, beta)), old,
                                cut(keep))
            if live is not None:
                new = jnp.where(cut(live)[:, None, None, None], new, old)
            return o, new

        if group == s_:
            return rows(state0, 0, s_)

        def fold(carry, lo):
            o, new = rows(carry[1], lo, group)
            return (jax.lax.dynamic_update_slice_in_dim(carry[0], o, lo, 0),
                    jax.lax.dynamic_update_slice_in_dim(carry[1], new, lo,
                                                        0)), None

        out, _ = jax.lax.scan(
            fold, (jnp.zeros((*q.shape[:3], self.head_dim), F32), state0),
            jnp.arange(0, s_, group))
        return out

    def _mix(self, x, valid, cache, keep=None, live=None):
        """C positions a row from ``cache = (state, q tail, k tail, v
        tail)``: ``x`` (S, C, E) the normed input, ``valid`` (S, C) 0 where
        a position brings no token (its g and beta are 0: the identity).
        Returns the mixer's output and the new cache arrays (the state
        already kept where ``live`` is false)."""
        state0, *tails = cache
        counts = jnp.sum(valid, axis=1).astype(jnp.int32)
        last = (counts[:, None] + jnp.arange(self.conv - 1))[..., None]
        streams, new_tails = [], []
        for name, tail in zip("qkv", tails):
            if keep is not None:
                with jax.named_scope("kda.conv"):
                    tail = tail * keep[:, None, None].astype(tail.dtype)
            stream, window = self._stream(name, x, tail)
            streams.append(stream)
            with jax.named_scope("kda.conv"):
                # the inputs that END at each row's last valid position
                new_tails.append(jnp.take_along_axis(window, last, axis=1))
        q, k, v = streams
        g, beta = self._gates(x)
        with jax.named_scope("kda.scan"):
            mask = valid.astype(F32)
            o, state = self._recur(q, k, v, g * mask[..., None, None],
                                   beta * mask[..., None], state0, keep,
                                   live)
            # the new tails are a few rows of the windows: have them taken
            # before the stream goes on (as ``Mamba2Mixer._chunk``)
            o, new_tails = jax.lax.optimization_barrier((o, new_tails))
        return self._out(o, x), (state, *new_tails)

    # -- the whole-sequence pass --------------------------------------------
    def forward(self, x):
        """x (B, S, E) from zero state; nothing is kept."""
        b, s = x.shape[0], x.shape[1]
        cache = [jnp.zeros((b, *shape), dtype or x._value.dtype)
                 for shape, dtype in self.state_arrays()]
        return self._mix(x, jnp.ones((b, s), F32), cache)[0]

    # -- the serving engine's layer protocol, the mixer's half --------------
    def paged_chunk(self, x, step, cache):
        """C positions a slot (the mixed step): a row whose base length is
        0 starts from zeros, a row that is not live keeps its state and its
        tails. ``cache`` is ``(state (S, H, d, d) float32, three tails (S,
        width - 1, H d))``, row ``s`` slot ``s``'s."""
        live = step["live"]
        out, (state, *tails) = self._mix(
            x, step["valid"], cache, keep=~(live & (step["lens"] == 0)),
            live=live)
        with jax.named_scope("cache.write"):
            return out, (state, *(
                jnp.where(live[:, None, None], new.astype(old.dtype), old)
                for new, old in zip(tails, cache[1:])))

    def paged_decode(self, x, step, cache):
        """One position a slot: the delta rule itself
        (:func:`kda_step`, or the ``kda_decode_update`` kernel where
        :func:`use_pallas_kernels` holds and the head is whole tiles: the
        state read once and written once)."""
        from ..ops.pallas import kda_decode as kernel

        state0, *tails = cache
        live = step["live"]
        (q, k, v), windows = zip(*(self._stream(name, x, tail)
                                   for name, tail in zip("qkv", tails)))
        g, beta = self._gates(x)
        q, k = self._normalised(q, k)
        with jax.named_scope("kda.scan"):
            q, k, v, g, beta = (t[:, 0] for t in (q, k, v, g, beta))
            # a row that is not live: alpha 1 and beta 0, the identity
            g = jnp.where(live[:, None, None], g, 0.0)
            beta = jnp.where(live[:, None], beta, 0.0)
            update = kernel.kda_decode_update if use_pallas_kernels() \
                and kernel.supports(state0) else kda_step
            o, state = update(q, k, v, g, beta, state0)
        out = self._out(o[:, None], x)
        with jax.named_scope("cache.write"):
            return out, (state, *(
                jnp.where(live[:, None, None], w[:, 1:].astype(old.dtype),
                          old) for w, old in zip(windows, tails)))


class SolarOpen2Attention(NoPositionAttention):
    """GQA without positions whose output is gated by a sigmoid of the
    layer's input before ``o_proj`` (``use_gqa_gate``;
    ``paged_attention.sigmoid_gated_out``, the gate ``nlp/afmoe.py`` has):
    ``LlamaAttention``'s paged K/V forms as they are, asked with the
    mixers' ``(x, step, cache)``."""

    def __init__(self, config: SolarOpen2Config):
        super().__init__(config)
        self.gate_proj = Linear(config.hidden_size,
                                self.num_heads * self.head_dim,
                                bias_attr=False)

    def _project_out(self, att, x):
        return sigmoid_gated_out(self.o_proj, att._value,
                                 self.gate_proj(x)._value)

    def _paged(self, form, x, step, cache):
        return form(x, None, step["tables"], step["lens"],
                    step["write_blk"], step["write_off"], cache)

    def paged_decode(self, x, step, cache):
        return self._paged(super().paged_decode, x, step, cache)

    def paged_chunk(self, x, step, cache):
        return self._paged(super().paged_chunk, x, step, cache)


class SolarOpen2MoE(SigmoidRoutedExperts):
    """A layer's feed-forward (``nlp/routed_experts.py``: SwiGLU experts,
    a held share, one shared expert of the experts' width) under the
    DeepSeek-V3-shaped sources' names: ``gate.weight``,
    ``gate.e_score_correction_bias``, ``experts.{gate_up_proj,down_proj}``,
    ``shared_experts``; ``router`` is the decision
    (:class:`SigmoidTopKGate`)."""

    op_name = "solar_open2_routed_experts"

    def __init__(self, config: SolarOpen2Config):
        super().__init__(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            config.n_shared_experts * config.moe_intermediate_size,
            config.norm_topk_prob, config.routed_scaling_factor,
            held=config.held_experts)
        self.router = self.decision

    def _build_router(self, hidden_size, num_experts):
        self.gate = GateLeaves(hidden_size, num_experts)

    def _router_leaves(self):
        return self.gate.weight, self.gate.e_score_correction_bias


class SolarOpen2DecoderLayer(Layer):
    """``h += mixer(norm(h)); h += moe(norm(h))``
    (``paged_attention.PagedResidualLayer``'s shape; ``self_attn`` is the
    KDA mixer or the gated GQA, both asked with ``(x, step, cache)``)."""

    def __init__(self, config: SolarOpen2Config, layer_idx):
        super().__init__()
        self.kind = "gqa" if layer_idx in config.gqa_layers else "kda"
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = (
            SolarOpen2Attention(config) if self.kind == "gqa"
            else KimiDeltaAttention(
                config.hidden_size, config.kda_heads, config.kda_head_dim,
                config.kda_conv, config.rms_norm_eps,
                config.kda_allow_neg_eigval))
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = SolarOpen2MoE(config)

    def _feed_forward(self, hidden, mixed):
        hidden = hidden + mixed
        return hidden + self.mlp(
            normed(self.post_attention_layernorm, hidden))

    def forward(self, hidden):
        return self._feed_forward(hidden, self.self_attn(
            normed(self.input_layernorm, hidden)))

    # -- the serving engine's layer protocol --------------------------------
    def paged_decode(self, hidden, step, cache):
        mixed, new = self.self_attn.paged_decode(
            normed(self.input_layernorm, hidden), step, cache)
        return self._feed_forward(hidden, mixed), new

    def paged_chunk(self, hidden, step, cache):
        mixed, new = self.self_attn.paged_chunk(
            normed(self.input_layernorm, hidden), step, cache)
        return self._feed_forward(hidden, mixed), new


class SolarOpen2Model(Layer):
    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = SolarOpen2DecoderLayer(config, i)
            self.add_sublayer(f"layers.{i}", layer)
            self.layers.append(layer)
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        hidden = self.embed_tokens(input_ids)
        for layer in self.layers:
            hidden = layer(hidden)
        return self.norm(hidden)

    def paged_rope(self, positions):
        """No layer of this family rotates anything."""
        return None


class SolarOpen2ForCausalLM(Layer):
    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        self.config = config
        self.model = SolarOpen2Model(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              bias_attr=False)

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V): the whole sequence,
        nothing cached."""
        return self.lm_head(self.model(input_ids))

    # -- what the serving engine asks of a model ---------------------------
    @property
    def decoder(self):
        return self.model

    def paged_cache_layout(self):
        """Per layer what it caches: a GQA layer K and V blocks
        (``"kv"``), a KDA layer a row of the pool's slot side
        (``"state"``: the arrays of ``state``, per slot)."""
        cfg = self.config
        kda = next((layer.self_attn for layer in self.model.layers
                    if layer.kind == "kda"), None)
        return {"layout": "kv", "num_kv_heads": cfg.num_key_value_heads,
                "head_dim": cfg.head_dim,
                "layers": tuple("kv" if layer.kind == "gqa" else "state"
                                for layer in self.model.layers),
                "state": kda.state_arrays() if kda else []}
