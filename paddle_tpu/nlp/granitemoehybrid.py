"""Granite-4.0-H-shaped decoder (``model_type`` ``granitemoehybrid``:
ibm-granite/granite-4.0-h-small, ...): most layers mix tokens by a Mamba-2
state-space recurrence, a few by grouped-query attention WITHOUT positions;
every layer's feed-forward is routed experts beside one shared MLP.

Equations (``r`` = ``residual_multiplier``, eps ``rms_norm_eps``):
``x_0 = embedding_multiplier * Embed(ids)``; every layer ``x += r *
Mixer(RMSNorm(x)); v = RMSNorm(x); x += r * (Routed(v) + Shared(v))``;
``logits = RMSNorm(x) Embed^T / logits_scaling`` (a tied head).

- State-space mixer (``layer_types[i] == "mamba"``; :class:`Mamba2Mixer`,
  which ``nlp/nemotron_h.py`` shares: ``d_in = heads x head width`` (here
  also ``mamba_expand x hidden``), ``N`` the state width, ``G`` groups: B
  and C are shared by the ``heads / G`` heads of a group, head ``h`` uses
  group ``h // (heads / G)``; this family has one group): ``[z | xBC |
  dt_raw] = u W_in`` (``d_in | d_in + 2GN | heads``); a depthwise causal
  convolution of width ``mamba_d_conv`` with bias over ``xBC``, zeros before
  a sequence's first token, then silu; ``[xs | B | C] = xBC`` with B, C of
  shape (G, N). Per head, in float32: ``dt = softplus(dt_raw + dt_bias)``,
  ``A = -exp(A_log)``, ``H_t = exp(dt_t A) H_{t-1} + dt_t xs_t (x) B_t`` (a
  ``d_head x N`` state, ``H_0 = 0``), ``y_t = H_t C_t + D xs_t``. Gate, then
  norm PER GROUP of ``d_in / G`` channels: ``g = y * silu(z)``; ``g *
  rsqrt(mean_group(g^2) + eps) * w_norm``; ``out = g W_out``.
- The same sum over a chunk of C positions with incoming state ``H_0``
  (:func:`ssd_chunk`): ``c_t = sum_{s<=t} dt_s A``; ``y_t = sum_{s<=t}
  exp(c_t - c_s) dt_s (C_t . B_s) xs_s + exp(c_t) H_0 C_t + D xs_t``
  (``C_t . B_s`` taken within the head's group);
  ``H_C = exp(c_C) H_0 + sum_s exp(c_C - c_s) dt_s xs_s (x) B_s``. A
  position with ``dt = 0`` is the identity. What serving keeps per request
  and state layer is ``H`` (float32) and the convolution's last
  ``mamba_d_conv - 1`` inputs, whatever the context: a row of the pool's
  SLOT side (``paged_cache_layout``), not blocks.
- Attention (``"attention"``): q/k/v/o without bias, GQA, no rotary
  embedding (``position_embedding_type`` ``nope``), scores times
  ``attention_multiplier``; the paged K/V attention of ``llama.py`` with
  the rotation an identity and the scale handed in.
- Routed experts: ``l = v W_r`` in float32; the ``num_experts_per_tok``
  largest ``l``; weights the softmax over those values; an expert is
  ``(silu(v W1[:, :f]) * (v W1[:, f:])) W2``. ``held_experts = (lo, n)``:
  the router keeps its ``num_local_experts`` outputs and this chip holds,
  and computes, experts ``lo .. lo + n - 1`` only; what the absent experts
  would add is left out (one chip's share under expert parallelism, without
  the exchange). ``Shared(v)``: the same form, ``shared_intermediate_size``.

Serving only (``paddle.inference.serve``); ``forward`` is the plain
whole-sequence pass in chunks of ``mamba_chunk_size`` the tests compare
with. Not done here: training, ``generate`` over a dense cache, tensor
parallelism, biases on the projections, ``time_step_limit`` other than
(0, inf). The mixer and :func:`ssd_chunk` take any number of groups; this
family's published configs have one, and ``mamba_expand x hidden`` has to
be ``heads x head width`` (a config that states otherwise is refused).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..incubate.distributed.models.moe.gate import SoftmaxTopKGate
from ..incubate.distributed.models.moe.moe_layer import (
    grouped_expert_ffn, swiglu)
from ..nn import initializer as I
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..tensor._helpers import apply
from .llama import LlamaAttention
from .paged_attention import normed

__all__ = ["GraniteMoeHybridConfig", "Mamba2Mixer", "GraniteMoeHybridMamba",
           "PlainAttention", "NoPositionAttention", "GraniteMoeHybridAttention",
           "GraniteMoeHybridMoE",
           "GraniteMoeHybridDecoderLayer", "GraniteMoeHybridModel",
           "GraniteMoeHybridForCausalLM", "ssd_chunk", "conv_silu"]

F32 = jnp.float32
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


class GraniteMoeHybridConfig:
    """The published ``config.json`` keys the layer equations read, and
    ``held_experts``: ``(lo, n)``, the routed experts this chip holds
    (default: all ``num_local_experts``)."""

    def __init__(self, vocab_size=100352, hidden_size=4096,
                 intermediate_size=768, shared_intermediate_size=1536,
                 num_hidden_layers=40, layer_types=None,
                 num_attention_heads=32, num_key_value_heads=8,
                 num_local_experts=72, num_experts_per_tok=10,
                 held_experts=None, mamba_n_heads=128, mamba_d_head=64,
                 mamba_d_state=128, mamba_d_conv=4, mamba_expand=2,
                 mamba_n_groups=1, mamba_chunk_size=256,
                 mamba_conv_bias=True, mamba_proj_bias=False,
                 attention_bias=False, attention_multiplier=0.0078125,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 logits_scaling=16.0, position_embedding_type="nope",
                 max_position_embeddings=131072, rms_norm_eps=1e-5,
                 tie_word_embeddings=True, sliding_window=None,
                 dtype="float32"):
        if layer_types is None:
            layer_types = (_PERIOD * (-(-num_hidden_layers // 10))
                           )[:num_hidden_layers]
        for what, bad in (
                ("mamba_n_groups that does not divide mamba_n_heads",
                 mamba_n_groups < 1 or mamba_n_heads % mamba_n_groups),
                ("a bias on the projections",
                 mamba_proj_bias or attention_bias),
                ("a convolution without bias", not mamba_conv_bias),
                ("position embeddings (only 'nope')",
                 position_embedding_type != "nope"),
                ("an untied output head", not tie_word_embeddings),
                ("layer_types of another length than the depth, or of "
                 "other kinds than mamba | attention",
                 len(layer_types) != num_hidden_layers
                 or set(layer_types) - {"mamba", "attention"}),
                ("mamba_n_heads x mamba_d_head != mamba_expand x hidden",
                 mamba_n_heads * mamba_d_head
                 != mamba_expand * hidden_size)):
            if bad:
                raise NotImplementedError(
                    f"GraniteMoeHybrid: {what} is not implemented")
        lo, n = held_experts or (0, num_local_experts)
        if not 0 <= lo < lo + n <= num_local_experts:
            raise ValueError(
                f"held_experts {held_experts} is no range of the "
                f"{num_local_experts} experts")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.shared_intermediate_size = shared_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = tuple(layer_types)
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.num_local_experts = num_local_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.held_experts = (int(lo), int(n))
        self.mamba_n_heads = mamba_n_heads
        self.mamba_d_head = mamba_d_head
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_n_groups = mamba_n_groups
        self.mamba_chunk_size = mamba_chunk_size
        self.attention_multiplier = attention_multiplier
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        # what ``LlamaAttention`` and the engine read of any config
        self.attention_bias = False
        self.tensor_parallel = False
        self.sliding_window = sliding_window
        self.dtype = dtype

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self):
        """What the convolution runs over: ``[xs | B | C]``."""
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    @staticmethod
    def tiny(**overrides):
        """Test-scale config: every mechanism at toy widths (four layers,
        the third attention; 8 experts, top 3)."""
        cfg = dict(vocab_size=128, hidden_size=32, intermediate_size=16,
                   shared_intermediate_size=24, num_hidden_layers=4,
                   layer_types=("mamba", "mamba", "attention", "mamba"),
                   num_attention_heads=4, num_key_value_heads=2,
                   num_local_experts=8, num_experts_per_tok=3,
                   mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
                   mamba_chunk_size=8, attention_multiplier=0.25,
                   max_position_embeddings=256)
        cfg.update(overrides)
        return GraniteMoeHybridConfig(**cfg)

    @staticmethod
    def granite_4_0_h_small(**overrides):
        """ibm-granite/granite-4.0-h-small as published (the defaults)."""
        return GraniteMoeHybridConfig(**overrides)


# the float32 decay matrix of `ssd_chunk`, (rows, heads, C, C), may take
# this many bytes; more heads than that stream through in equal groups
# (a shape rule as `paged_attention._CHUNK_SCORE_BYTES`: no knob)
_DECAY_BYTES = 128 << 20


def ssd_chunk(xs, b, c, dt, a, d, h0, keep=None):
    """The state-space sum over one chunk (the module's second equation
    block), in float32. ``xs`` (S, C, H, P) inputs as heads, ``b`` / ``c``
    (S, C, G, N), each group shared by ``H / G`` heads in order, ``dt``
    (S, C, H) >= 0 (0: that position is the identity), ``a`` (H,) < 0,
    ``d`` (H,), ``h0`` (S, H, P, N) the incoming state; ``keep`` (S,) 0 | 1
    multiplies what ``h0`` adds (0: the row starts from a zero state; the
    factor rides the decays, so no zeroed copy of the state is ever made).
    Returns ``y`` (S, C, H, P) and the outgoing state.

    The (S, g, C, C) decay matrix is built for ``g`` heads at a time under
    ``_DECAY_BYTES``, whole groups or an equal part of one; every product
    contracts over positions or the state width on the matrix unit."""
    s_, cl, h, p = xs.shape
    per = h // b.shape[2]                       # heads a group
    xs, b, c = xs.astype(F32), b.astype(F32), c.astype(F32)
    cum = jnp.cumsum(dt * a, axis=1).transpose(0, 2, 1)     # (S, H, C)
    carried = jnp.exp(cum)                    # what of h0 each position sees
    if keep is not None:
        carried = carried * keep.astype(F32)[:, None, None]
    x_dt = xs * dt[..., None]                               # dt_s xs_s
    cb = jnp.einsum("stgn,sugn->sgtu", c, b)                # C_t . B_u
    causal = jnp.arange(cl)[:, None] >= jnp.arange(cl)[None, :]
    group = max(1, min(h, _DECAY_BYTES // (s_ * cl * cl * 4)))
    while h % group or (group % per and per % group):
        group -= 1
    ng = max(1, group // per)       # B / C groups the streamed heads span
    k = group // ng                 # heads of each

    def heads(lo):
        def cut(v, axis):
            return jax.lax.dynamic_slice_in_dim(v, lo, group, axis)

        def of_group(v, axis):      # the streamed heads' B / C groups
            return jax.lax.dynamic_slice_in_dim(v, lo // per, ng, axis)

        def split(v, axis):         # a heads axis as (groups, heads of each)
            return v.reshape(*v.shape[:axis], ng, k, *v.shape[axis + 1:])

        cg, xg, kg = cut(cum, 1), cut(x_dt, 2), cut(carried, 1)
        bg, cgr = of_group(b, 2), of_group(c, 2)            # (S, C, ng, N)
        diff = cg[..., :, None] - cg[..., None, :]          # c_t - c_u
        m = (of_group(cb, 1)[:, :, None] * split(
            jnp.exp(jnp.where(causal, diff, -jnp.inf)), 1)
             ).reshape(s_, group, cl, cl)
        y = (jnp.einsum("shtu,suhp->sthp", m, xg)
             + jnp.einsum("sgkpn,stgn->stgkp", split(cut(h0, 1), 1), cgr
                          ).reshape(s_, cl, group, p)
             * kg.transpose(0, 2, 1)[..., None]
             + cut(d, 0)[:, None] * cut(xs, 2))
        h1 = (cut(h0, 1) * kg[..., -1][..., None, None]
              + jnp.einsum("sgku,sugkp,sugn->sgkpn",
                           split(jnp.exp(cg[..., -1:] - cg), 1),
                           split(xg, 2), bg).reshape(s_, group, p, -1))
        return y, h1

    if group == h:
        return heads(0)

    def fold(carry, lo):
        y, h1 = heads(lo)
        return (jax.lax.dynamic_update_slice_in_dim(carry[0], y, lo, 2),
                jax.lax.dynamic_update_slice_in_dim(carry[1], h1, lo, 1)
                ), None

    out, _ = jax.lax.scan(
        fold, (jnp.zeros(xs.shape, F32), jnp.zeros(h0.shape, F32)),
        jnp.arange(0, h, group))
    return out


def conv_silu(window, weight, bias=None):
    """The depthwise causal convolution: ``window`` (S, C + width - 1, D),
    each position's input behind the ``width - 1`` before it, ``weight``
    (D, width) with tap ``j`` multiplying the input ``width - 1 - j``
    positions back -> silu(conv + bias), (S, C, D), in float32 and rounded
    once (``nlp/solar_open2.py``'s three convolutions have no bias)."""
    w = weight.astype(F32)
    k = w.shape[1]
    n = window.shape[1] - k + 1
    acc = sum(window[:, j:j + n].astype(F32) * w[:, j] for j in range(k))
    if bias is not None:
        acc = acc + bias.astype(F32)
    return jax.nn.silu(acc).astype(window.dtype)


class _Conv1d(Layer):
    """The depthwise convolution's parameters: ``weight`` (channels,
    width), tap ``j`` multiplying the input ``width - 1 - j`` positions
    back, and ``bias`` (channels,)."""

    def __init__(self, channels, width):
        super().__init__()
        self.weight = self.create_parameter(
            (channels, width), default_initializer=I.XavierNormal())
        self.bias = self.create_parameter((channels,), is_bias=True)


class Mamba2Mixer(Layer):
    """The Mamba-2 mixer of the module's equations, by its sizes: ``heads``
    x ``d_head`` inner channels, ``groups`` of B and C of width ``d_state``,
    a convolution of width ``d_conv``; ``forward`` sums ``chunk_size``
    positions at a time. Parameter names are the source's (``in_proj``,
    ``conv1d``, ``dt_bias``, ``A_log``, ``D``, ``norm``, ``out_proj``).
    ``multipliers`` (``nlp/falcon_h1.py``'s ``ssm_multipliers``): five
    factors on ``in_proj``'s output, one a section ``[z | xs | B | C |
    dt_raw]``, applied in float32 and rounded once; None adds no
    operation."""

    def __init__(self, hidden_size, heads, d_head, d_state, d_conv=4,
                 groups=1, chunk_size=256, eps=1e-5, multipliers=None):
        super().__init__()
        if heads % groups:
            raise ValueError(f"{groups} groups do not divide {heads} heads")
        self.heads, self.d_head, self.d_state = heads, d_head, d_state
        self.d_conv, self.groups, self.chunk_size = d_conv, groups, chunk_size
        self.eps = float(eps)
        self.d_inner = heads * d_head
        self.conv_dim = self.d_inner + 2 * groups * d_state
        self._section_scale = None
        if multipliers is not None:
            z, x, b, c, dt = (float(m) for m in multipliers)
            gn = groups * d_state
            self._section_scale = np.repeat(
                np.asarray([z, x, b, c, dt], np.float32),
                [self.d_inner, self.d_inner, gn, gn, heads])
        self.in_proj = Linear(hidden_size,
                              self.d_inner + self.conv_dim + heads,
                              bias_attr=False)
        self.conv1d = _Conv1d(self.conv_dim, d_conv)
        self.dt_bias = self.create_parameter((heads,), is_bias=True)
        self.A_log = self.create_parameter((heads,), is_bias=True)
        self.D = self.create_parameter(
            (heads,), default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(self.d_inner, epsilon=eps)
        self.out_proj = Linear(self.d_inner, hidden_size, bias_attr=False)

    def state_arrays(self):
        """What a slot keeps for this layer, as ``(shape, dtype)``: the
        recurrence's state in float32 and the convolution's last inputs
        in the model's dtype (None)."""
        return [((self.heads, self.d_head, self.d_state), "float32"),
                ((self.d_conv - 1, self.conv_dim), None)]

    # -- shared pieces ------------------------------------------------------
    def _project(self, u):
        """u (..., E) -> z (..., d_in), xBC (..., conv_dim) and
        ``softplus(dt_raw + dt_bias)`` (..., H) float32, raw arrays."""
        with jax.named_scope("ssm.in_proj"):
            zxd = self.in_proj(u)._value
            if self._section_scale is not None:
                zxd = (zxd.astype(F32) * self._section_scale
                       ).astype(zxd.dtype)
            d_in, cd = self.d_inner, self.conv_dim
            dt = jax.nn.softplus(zxd[..., d_in + cd:].astype(F32)
                                 + self.dt_bias._value.astype(F32))
            return zxd[..., :d_in], zxd[..., d_in:d_in + cd], dt

    def _conv(self, window):
        return conv_silu(window, self.conv1d.weight._value,
                         self.conv1d.bias._value)

    def _split(self, xbc):
        """[xs | B | C] -> xs as heads (..., H, P), B, C (..., G, N)."""
        d_in, gn = self.d_inner, self.groups * self.d_state
        lead = xbc.shape[:-1]
        return (xbc[..., :d_in].reshape(*lead, self.heads, self.d_head),
                xbc[..., d_in:d_in + gn].reshape(*lead, self.groups, -1),
                xbc[..., d_in + gn:].reshape(*lead, self.groups, -1))

    def _out(self, y, z):
        """Gate by silu(z), norm over each group's ``d_in / G`` channels,
        project out. ``y`` (..., H, P) float32, ``z`` (..., d_in)."""
        with jax.named_scope("ssm.out"):
            g = y.reshape(z.shape) * jax.nn.silu(z.astype(F32))
            g = g.reshape(*z.shape[:-1], self.groups, -1)
            g = (g * jax.lax.rsqrt(
                jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                + self.eps)).reshape(z.shape)
            g = (g * self.norm.weight._value.astype(F32)).astype(z.dtype)
            return self.out_proj(Tensor(g, stop_gradient=True))

    def _chunk(self, u, dt_mask, tail, state, counts, keep=None):
        """C positions a row from ``(tail, state)``: ``u`` (S, C, E) the
        normed input, ``dt_mask`` (S, C) 0 where a position brings no
        token, ``counts`` (S,) each row's valid positions (the new tail
        ends at the last of them), ``keep`` (S,) 0 for a row that starts
        from zeros. Returns the mixer's output (S, C, E) and the new
        ``(state, tail)``."""
        z, xbc, dt = self._project(u)
        k1 = tail.shape[1]
        with jax.named_scope("ssm.conv"):
            if keep is not None:
                tail = tail * keep[:, None, None].astype(tail.dtype)
            window = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
            # the inputs that END at each row's last valid position
            new_tail = jnp.take_along_axis(
                window, (counts[:, None] + jnp.arange(k1))[..., None],
                axis=1)
            xs, b, c = self._split(self._conv(window))
        with jax.named_scope("ssm.scan"):
            y, state = ssd_chunk(
                xs, b, c, dt * dt_mask[..., None],
                -jnp.exp(self.A_log._value.astype(F32)),
                self.D._value.astype(F32), state, keep)
            # the new tail is a few rows of `window`: have it taken
            # before the stream goes on, or the scheduler keeps every
            # layer's window (132 MB at the cell's size) alive to the
            # program's end
            y, new_tail = jax.lax.optimization_barrier((y, new_tail))
        return self._out(y, z), (state, new_tail)

    # -- the whole-sequence pass --------------------------------------------
    def forward(self, u):
        """u (B, S, E) from zero state, ``chunk_size`` positions at a
        time; nothing is kept."""
        bsz, s = u.shape[0], u.shape[1]
        (shape, _), (tshape, _) = self.state_arrays()
        state = jnp.zeros((bsz, *shape), F32)
        tail = jnp.zeros((bsz, *tshape), u._value.dtype)
        outs = []
        for lo in range(0, s, self.chunk_size):
            part = u[:, lo:lo + self.chunk_size]
            n = part.shape[1]
            out, (state, tail) = self._chunk(
                part, jnp.ones((bsz, n), F32), tail, state,
                jnp.full((bsz,), n, jnp.int32))
            outs.append(out._value)
        return Tensor(jnp.concatenate(outs, axis=1), stop_gradient=True)

    # -- the serving engine's layer protocol, the mixer's half --------------
    def paged_chunk(self, u, step, cache):
        """C positions a slot (the mixed step; see ``_chunk``): a row
        whose base length is 0 starts from zeros, a row that is not live
        keeps its state. ``cache`` is ``(state (S, H, P, N) float32, tail
        (S, width - 1, D))``, row ``s`` slot ``s``'s."""
        state0, tail0 = cache
        valid, live = step["valid"], step["live"]
        out, (state, tail) = self._chunk(
            u, valid.astype(F32), tail0, state0,
            jnp.sum(valid, axis=1).astype(jnp.int32),
            keep=~(live & (step["lens"] == 0)))
        with jax.named_scope("cache.write"):
            return out, (
                jnp.where(live[:, None, None, None], state, state0),
                jnp.where(live[:, None, None], tail.astype(tail0.dtype),
                          tail0))

    def paged_decode(self, u, step, cache):
        """One position a slot: the recurrence itself, the state read and
        written once."""
        state0, tail0 = cache
        live = step["live"]
        z, xbc, dt = self._project(u)                  # (S, 1, .)
        with jax.named_scope("ssm.conv"):
            window = jnp.concatenate([tail0.astype(xbc.dtype), xbc], axis=1)
            xs, b, c = self._split(self._conv(window)[:, 0])
        with jax.named_scope("ssm.scan"):
            xs, b, c, dt = xs.astype(F32), b.astype(F32), c.astype(F32), \
                dt[:, 0]
            a = -jnp.exp(self.A_log._value.astype(F32))
            # heads as (groups, heads of each): a group's B and C row is
            # broadcast over its heads, never repeated in memory
            by_group = (-1, self.groups, self.heads // self.groups,
                        self.d_head, self.d_state)
            state = (state0 * jnp.exp(dt * a)[..., None, None]
                     + ((xs * dt[..., None])[..., None].reshape(
                         *by_group[:4], 1)
                        * b[:, :, None, None, :]).reshape(state0.shape))
            y = (jnp.sum(state.reshape(by_group) * c[:, :, None, None, :],
                         axis=-1).reshape(xs.shape)
                 + self.D._value.astype(F32)[:, None] * xs)
        out = self._out(y[:, None], z)
        with jax.named_scope("cache.write"):
            return out, (
                jnp.where(live[:, None, None, None], state, state0),
                jnp.where(live[:, None, None], window[:, 1:].astype(
                    tail0.dtype), tail0))


class GraniteMoeHybridMamba(Mamba2Mixer):
    """:class:`Mamba2Mixer` at this family's keys."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__(
            config.hidden_size, config.mamba_n_heads, config.mamba_d_head,
            config.mamba_d_state, config.mamba_d_conv,
            config.mamba_n_groups, config.mamba_chunk_size,
            config.rms_norm_eps)


class PlainAttention(LlamaAttention):
    """``LlamaAttention``'s projections and its paged K/V forms with the
    scale of the scores handed in (None: ``1 / sqrt(head_dim)``) and a
    whole-sequence ``forward`` in plain ``jax.numpy``; q and k are rotated
    by ``_rotate`` at ``paged_rope``'s angles (``nlp/falcon_h1.py``: the
    rotary embedding)."""

    def __init__(self, config, softmax_scale=None):
        super().__init__(config)
        self.softmax_scale = (float(softmax_scale) if softmax_scale
                              else self.head_dim ** -0.5)

    def forward(self, x):
        """Causal self-attention over x (B, S, E), nothing cached."""
        b, s = x.shape[0], x.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        rope = self.paged_rope(jnp.arange(s, dtype=F32)[None])
        q = self._rotate(self.q_proj(x)._value.reshape(b, s, h, d), rope
                         ).reshape(b, s, hk, h // hk, d)
        k = self._rotate(self.k_proj(x)._value.reshape(b, s, hk, d), rope)
        v = self.v_proj(x)._value.reshape(b, s, hk, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                            preferred_element_type=F32) * self.softmax_scale
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        p = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                         preferred_element_type=F32)
        return self._project_out(Tensor(
            out.astype(x._value.dtype).reshape(b, s, h * d),
            stop_gradient=True), x)


class NoPositionAttention(PlainAttention):
    """GQA without positions, scores times ``softmax_scale``: the rotation
    an identity."""

    def _rotate(self, x, rope):
        return x

    def paged_rope(self, positions):
        return None


class GraniteMoeHybridAttention(NoPositionAttention):
    """:class:`NoPositionAttention` with ``attention_multiplier`` as the
    scale."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__(config, config.attention_multiplier)


class _Stacked(Layer):
    """A stacked weight ``(experts, in, out)`` under the name ``weight``."""

    def __init__(self, *shape):
        super().__init__()
        self.weight = self.create_parameter(
            shape, default_initializer=I.XavierNormal())


class GraniteMoeHybridRouter(Layer):
    """``layer``: hidden -> ``num_local_experts`` logits; the decision is
    :class:`SoftmaxTopKGate`'s."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.layer = Linear(config.hidden_size, config.num_local_experts,
                            bias_attr=False)
        self.gate = SoftmaxTopKGate(config.num_experts_per_tok)
        self.top_k = self.gate.top_k


class GraniteMoeHybridMoE(Layer):
    """The routed experts this chip holds (``held_experts``), each a
    SwiGLU whose gate and up projections are one ``input_linear`` slab.
    After a forward ``rows_per_expert`` holds the rows each HELD expert
    was handed, (``num_experts``,) int32, a value of the same trace."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.held = config.held_experts
        self.num_experts = self.held[1]
        self.published_experts = config.num_local_experts
        f = config.intermediate_size
        self.router = GraniteMoeHybridRouter(config)
        self.input_linear = _Stacked(self.num_experts, config.hidden_size,
                                     2 * f)
        self.output_linear = _Stacked(self.num_experts, f,
                                      config.hidden_size)
        self.rows_per_expert = None

    def inactive_params_per_token(self):
        """Held routed-expert weights a token does NOT multiply: all but
        the share of its top k that falls on held experts, on average."""
        per_expert = (self.input_linear.weight._value.size
                      + self.output_linear.weight._value.size
                      ) // self.num_experts
        active = self.router.top_k * self.num_experts \
            // self.published_experts
        return (self.num_experts - active) * per_expert

    def _routed(self, xv, gw, w1, w2):
        xt = xv.reshape(-1, xv.shape[-1])
        with jax.named_scope("moe.router"):
            logits = jnp.matmul(xt.astype(F32), gw.astype(F32))
            topi, weights, _ = self.router.gate.topk_assignments(logits)
        with jax.named_scope("moe.experts"):
            held = None if self.num_experts == self.published_experts \
                else self.held
            y, rows = grouped_expert_ffn(xt, topi, weights, w1, w2, swiglu,
                                         held=held)
        return y.reshape(xv.shape), rows

    def forward(self, x):
        routed, rows = apply(
            self._routed, x, self.router.layer.weight,
            self.input_linear.weight, self.output_linear.weight,
            op_name="granitemoehybrid_routed_experts")
        self.rows_per_expert = rows._value
        return routed


class GraniteMoeHybridSharedMLP(Layer):
    """SwiGLU with the gate and up projections in one ``input_linear``."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        f = config.shared_intermediate_size
        self.input_linear = Linear(config.hidden_size, 2 * f,
                                   bias_attr=False)
        self.output_linear = Linear(f, config.hidden_size, bias_attr=False)

    def forward(self, x):
        h = self.input_linear(x)
        return self.output_linear(Tensor(swiglu(h._value),
                                         stop_gradient=True))


class GraniteMoeHybridDecoderLayer(Layer):
    def __init__(self, config: GraniteMoeHybridConfig, layer_idx):
        super().__init__()
        self.residual_multiplier = float(config.residual_multiplier)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.kind = config.layer_types[layer_idx]
        if self.kind == "mamba":
            self.mamba = GraniteMoeHybridMamba(config)
        else:
            self.self_attn = GraniteMoeHybridAttention(config)
        self.post_attention_layernorm = RMSNorm(
            config.hidden_size, epsilon=config.rms_norm_eps)
        self.block_sparse_moe = GraniteMoeHybridMoE(config)
        self.shared_mlp = GraniteMoeHybridSharedMLP(config)

    @property
    def mlp(self):
        """The block that routes rows to experts (what the engine's
        ``moe_rows`` reads)."""
        return self.block_sparse_moe

    def _feed_forward(self, hidden, mixed):
        r = self.residual_multiplier
        hidden = hidden + mixed * r
        v = normed(self.post_attention_layernorm, hidden)
        routed = self.block_sparse_moe(v)
        with jax.named_scope("moe.shared"):
            return hidden + (routed + self.shared_mlp(v)) * r

    def forward(self, hidden):
        mixer = self.mamba if self.kind == "mamba" else self.self_attn
        x = normed(self.input_layernorm, hidden)
        return self._feed_forward(hidden, mixer(x))

    # -- the serving engine's layer protocol --------------------------------
    def _paged(self, form, hidden, step, cache):
        x = normed(self.input_layernorm, hidden)
        if self.kind == "mamba":
            mixed, new = getattr(self.mamba, form)(x, step, cache)
        else:
            mixed, new = getattr(self.self_attn, form)(
                x, None, step["tables"], step["lens"], step["write_blk"],
                step["write_off"], cache)
        return self._feed_forward(hidden, mixed), new

    def paged_decode(self, hidden, step, cache):
        return self._paged("paged_decode", hidden, step, cache)

    def paged_chunk(self, hidden, step, cache):
        return self._paged("paged_chunk", hidden, step, cache)


class _ScaledEmbedding(Embedding):
    """``embedding_multiplier * Embed(ids)``."""

    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__(config.vocab_size, config.hidden_size)
        self.multiplier = float(config.embedding_multiplier)

    def forward(self, x):
        return super().forward(x) * self.multiplier


class GraniteMoeHybridModel(Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _ScaledEmbedding(config)
        self.layers = []
        for i in range(config.num_hidden_layers):
            layer = GraniteMoeHybridDecoderLayer(config, i)
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


class GraniteMoeHybridForCausalLM(Layer):
    def __init__(self, config: GraniteMoeHybridConfig):
        super().__init__()
        if config.sliding_window:
            raise NotImplementedError(
                "GraniteMoeHybrid: sliding_window is not implemented")
        self.config = config
        self.model = GraniteMoeHybridModel(config)

    def lm_head(self, hidden):
        """The tied head: ``hidden Embed^T / logits_scaling``."""
        scale = 1.0 / float(self.config.logits_scaling)
        return apply(
            lambda h, w: jnp.einsum("...e,ve->...v", h, w) * scale,
            hidden, self.model.embed_tokens.weight,
            op_name="granitemoehybrid_tied_head")

    def forward(self, input_ids):
        """input_ids (B, S) -> logits (B, S, V): the whole sequence,
        nothing cached."""
        return self.lm_head(self.model(input_ids))

    # -- what the serving engine asks of a model ---------------------------
    @property
    def decoder(self):
        return self.model

    def paged_cache_layout(self):
        """Per layer what it caches: an attention layer K and V blocks
        (``"kv"``), a state-space layer a row of the pool's slot side
        (``"state"``: the arrays of ``state``, per slot)."""
        cfg = self.config
        if cfg.sliding_window:
            raise NotImplementedError(
                "GraniteMoeHybrid: sliding_window is not implemented")
        mamba = next((layer.mamba for layer in self.model.layers
                      if layer.kind == "mamba"), None)
        return {"layout": "kv", "num_kv_heads": cfg.num_key_value_heads,
                "head_dim": cfg.head_dim,
                "layers": tuple("state" if t == "mamba" else "kv"
                                for t in cfg.layer_types),
                "state": mamba.state_arrays() if mamba else []}
