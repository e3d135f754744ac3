"""The expert layer's feed-forward that the DeepSeek-V3-shaped and the
AFMoE-shaped decoders share: a sigmoid router with a selection bias
(:class:`SigmoidTopKGate`), routed experts stacked for the grouped matrix
products, and shared experts beside them. A model file gives the router's
two leaves the names its source has (``_build_router`` /
``_router_leaves``); everything that is computed is here, once.
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

__all__ = ["SwiGLUMLP", "StackedExperts", "SigmoidRoutedExperts"]


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


class StackedExperts(Layer):
    """The routed experts, stacked: ``gate_up_proj`` (experts, E_model,
    2 x width) holds each expert's gate then up projection, ``down_proj``
    (experts, width, E_model)."""

    def __init__(self, num_experts, hidden_size, width):
        super().__init__()
        self.gate_up_proj = self.create_parameter(
            (num_experts, hidden_size, 2 * width),
            default_initializer=I.XavierNormal())
        self.down_proj = self.create_parameter(
            (num_experts, width, hidden_size),
            default_initializer=I.XavierNormal())


class SigmoidRoutedExperts(Layer):
    """An expert layer's feed-forward: routed experts (the sort +
    ``ragged_dot`` core ``MoELayer`` uses, no capacity: nothing is ever
    dropped) beside the shared experts. After a forward,
    ``rows_per_expert`` holds the rows each expert was handed, (experts,)
    int32, a value of the same trace (as ``MoELayer.l_aux`` is).

    A subclass names the router's leaves as its source does:
    ``_build_router(hidden_size, num_experts)`` creates them (before the
    experts, so the parameter order is the source's) and
    ``_router_leaves()`` hands back ``(weight (E_model, experts),
    selection bias (experts,))``. ``router`` is whatever the subclass
    puts there with a ``top_k`` (what the engine's accounting reads)."""

    op_name = "routed_experts"

    def __init__(self, hidden_size, width, num_experts, top_k, shared_width,
                 route_norm=True, route_scale=1.0, n_group=1, topk_group=1):
        super().__init__()
        self.num_experts = int(num_experts)
        self.decision = SigmoidTopKGate(top_k, route_norm, route_scale,
                                        n_group, topk_group)
        self._build_router(hidden_size, self.num_experts)
        self.experts = StackedExperts(self.num_experts, hidden_size, width)
        self.shared_experts = SwiGLUMLP(hidden_size, shared_width)
        self.shared_experts.scope = "moe.shared"
        self.rows_per_expert = None

    def _build_router(self, hidden_size, num_experts):
        raise NotImplementedError

    def _router_leaves(self):
        raise NotImplementedError

    def inactive_params_per_token(self):
        """Routed-expert weights a token does NOT multiply: all but its
        top k experts' (what a 2N operations count must leave out)."""
        per_expert = (self.experts.gate_up_proj._value.size
                      + self.experts.down_proj._value.size
                      ) // self.num_experts
        return (self.num_experts - self.decision.top_k) * per_expert

    def _routed(self, xv, gw, gb, w1, w2):
        xt = xv.reshape(-1, xv.shape[-1])
        with jax.named_scope("moe.router"):
            logits = jnp.matmul(xt.astype(jnp.float32),
                                gw.astype(jnp.float32))
            topi, weights, _ = self.decision.topk_assignments(logits, gb)
        with jax.named_scope("moe.experts"):
            y, rows = grouped_expert_ffn(xt, topi, weights, w1, w2, swiglu)
        return y.reshape(xv.shape), rows

    def forward(self, x):
        routed, rows = apply(
            self._routed, x, *self._router_leaves(),
            self.experts.gate_up_proj, self.experts.down_proj,
            op_name=self.op_name)
        self.rows_per_expert = rows._value
        with jax.named_scope("moe.shared"):
            return routed + self.shared_experts(x)
