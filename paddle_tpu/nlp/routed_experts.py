"""The expert layer's feed-forward that the DeepSeek-V3-shaped, the
AFMoE-shaped and the Nemotron-H-shaped decoders share: a sigmoid router with
a selection bias (:class:`SigmoidTopKGate`), routed experts stacked for the
grouped matrix products, and a shared expert beside them. What differs
between the sources is an ARGUMENT of the one block, set by a model file
from its source's keys:

- ``act``: ``"swiglu"``, a gated expert ``down(silu(gate(x)) * up(x))``
  whose gate and up projections are one slab ``gate_up_proj`` (deepseek_v3,
  afmoe), or ``"relu2"``, an UNGATED one ``down(relu(up(x))^2)`` of two
  matrices, ``up_proj`` and ``down_proj`` (nemotron_h's ``mlp_hidden_act``);
  the shared expert has the routed experts' form;
- ``held=(lo, n)``: this chip holds, and computes, experts ``lo .. lo + n -
  1`` of the ``num_experts`` the router ranges over (one chip's share under
  expert parallelism, without the exchange: what the absent experts would
  add is left out; ``grouped_expert_ffn(held=)``). Default: all of them.

A model file gives the router's two leaves the names its source has
(``_build_router`` / ``_router_leaves``); everything that is computed is
here, once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..incubate.distributed.models.moe.gate import SigmoidTopKGate
from ..incubate.distributed.models.moe.moe_layer import (
    grouped_expert_ffn, swiglu)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.common import Linear
from ..nn.layer.layers import Layer
from ..tensor._helpers import apply

__all__ = ["SwiGLUMLP", "Relu2MLP", "StackedExperts", "GateLeaves",
           "SigmoidRoutedExperts", "relu2"]


def relu2(h):
    """``relu(h)^2``, the ungated experts' activation."""
    return jnp.square(jax.nn.relu(h))


class SwiGLUMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)). ``scope`` is the profiler
    scope its operations carry (a dense layer's ``mlp``; an expert
    block's shared experts say ``moe.shared``)."""

    scope = "mlp"

    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.gate_proj = Linear(hidden_size, intermediate_size,
                                bias_attr=False)
        self.up_proj = Linear(hidden_size, intermediate_size,
                              bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                bias_attr=False)

    def forward(self, x):
        with jax.named_scope(self.scope):
            return self.down_proj(
                F.silu(self.gate_proj(x)) * self.up_proj(x))


class Relu2MLP(Layer):
    """The ungated MLP: down(relu(up(x))^2); ``scope`` as
    :class:`SwiGLUMLP`'s."""

    scope = "mlp"

    def __init__(self, hidden_size, intermediate_size):
        super().__init__()
        self.up_proj = Linear(hidden_size, intermediate_size,
                              bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                bias_attr=False)

    def forward(self, x):
        with jax.named_scope(self.scope):
            h = F.relu(self.up_proj(x))
            return self.down_proj(h * h)


# an expert's form by name: (activation between the two grouped products,
# first slab's leaf name, its width in expert widths, the shared expert)
_FORMS = {"swiglu": (swiglu, "gate_up_proj", 2, SwiGLUMLP),
          "relu2": (relu2, "up_proj", 1, Relu2MLP)}


class StackedExperts(Layer):
    """The routed experts held here, stacked. Gated (``act`` ``swiglu``):
    ``gate_up_proj`` (experts, E_model, 2 x width) holds each expert's gate
    then up projection; ungated (``relu2``): ``up_proj`` (experts, E_model,
    width). ``down_proj`` (experts, width, E_model) either way."""

    def __init__(self, num_experts, hidden_size, width, act="swiglu"):
        super().__init__()
        _, self.first, wide, _ = _FORMS[act]
        setattr(self, self.first, self.create_parameter(
            (num_experts, hidden_size, wide * width),
            default_initializer=I.XavierNormal()))
        self.down_proj = self.create_parameter(
            (num_experts, width, hidden_size),
            default_initializer=I.XavierNormal())

    @property
    def first_proj(self):
        """The first product's slab, whatever its source calls it."""
        return getattr(self, self.first)


class GateLeaves(Layer):
    """A router's two parameters under the names the DeepSeek-V3 and
    Nemotron-H sources give them: ``weight`` (E_model, experts) and the
    selection bias ``e_score_correction_bias`` (experts,)."""

    def __init__(self, hidden_size, num_experts):
        super().__init__()
        self.weight = self.create_parameter(
            (hidden_size, num_experts),
            default_initializer=I.XavierNormal())
        self.e_score_correction_bias = self.create_parameter(
            (num_experts,), is_bias=True)


class SigmoidRoutedExperts(Layer):
    """An expert layer's feed-forward: routed experts (the sort + two
    grouped products core ``MoELayer`` uses, no capacity: nothing is ever
    dropped) beside the shared expert. After a forward,
    ``rows_per_expert`` holds the rows each expert HELD here was handed,
    (``num_experts``,) int32, a value of the same trace (as
    ``MoELayer.l_aux`` is).

    ``num_experts`` is what the router ranges over; with ``held=(lo, n)``
    the block keeps ``published_experts`` for that, holds ``n`` slabs and
    ``num_experts`` reads ``n`` (what the engine's accounting counts over).
    ``act`` is the experts' form (the module's docstring).

    A subclass names the router's leaves as its source does:
    ``_build_router(hidden_size, num_experts)`` creates them (before the
    experts, so the parameter order is the source's) and
    ``_router_leaves()`` hands back ``(weight (E_model, experts),
    selection bias (experts,))``. ``router`` is whatever the subclass
    puts there with a ``top_k`` (what the engine's accounting reads)."""

    op_name = "routed_experts"

    def __init__(self, hidden_size, width, num_experts, top_k, shared_width,
                 route_norm=True, route_scale=1.0, n_group=1, topk_group=1,
                 act="swiglu", held=None):
        super().__init__()
        self.published_experts = int(num_experts)
        lo, n = held or (0, self.published_experts)
        if not 0 <= lo < lo + n <= self.published_experts:
            raise ValueError(f"held {held} is no range of the "
                             f"{num_experts} experts")
        self.held = (int(lo), int(n))
        self.num_experts = int(n)
        self.act, _, _, shared = _FORMS[act]
        self.decision = SigmoidTopKGate(top_k, route_norm, route_scale,
                                        n_group, topk_group)
        self._build_router(hidden_size, self.published_experts)
        self.experts = StackedExperts(self.num_experts, hidden_size, width,
                                      act)
        self.shared_experts = shared(hidden_size, shared_width)
        self.shared_experts.scope = "moe.shared"
        self.rows_per_expert = None

    def _build_router(self, hidden_size, num_experts):
        raise NotImplementedError

    def _router_leaves(self):
        raise NotImplementedError

    def inactive_params_per_token(self):
        """Held routed-expert weights a token does NOT multiply: all but
        the share of its top k that falls on held experts, on average
        (what a 2N operations count must leave out)."""
        per_expert = (self.experts.first_proj._value.size
                      + self.experts.down_proj._value.size
                      ) // self.num_experts
        active = self.decision.top_k * self.num_experts \
            // self.published_experts
        return (self.num_experts - active) * per_expert

    def _routed(self, xv, gw, gb, w1, w2):
        xt = xv.reshape(-1, xv.shape[-1])
        with jax.named_scope("moe.router"):
            logits = jnp.matmul(xt.astype(jnp.float32),
                                gw.astype(jnp.float32))
            topi, weights, _ = self.decision.topk_assignments(logits, gb)
        with jax.named_scope("moe.experts"):
            held = None if self.num_experts == self.published_experts \
                else self.held
            y, rows = grouped_expert_ffn(xt, topi, weights, w1, w2,
                                         self.act, held=held)
        return y.reshape(xv.shape), rows

    def forward(self, x):
        routed, rows = apply(
            self._routed, x, *self._router_leaves(),
            self.experts.first_proj, self.experts.down_proj,
            op_name=self.op_name)
        self.rows_per_expert = rows._value
        with jax.named_scope("moe.shared"):
            return routed + self.shared_experts(x)
