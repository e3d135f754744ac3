"""MoE gates (reference: .../moe/gate/{gshard,switch,naive}_gate.py —
unverified, SURVEY.md §0).

A gate maps token activations (T, E_model) → routing decisions. The
capacity-based formulation returns dense one-hot dispatch/combine masks
(T, num_experts, capacity) that downstream einsums consume; the
load-balancing auxiliary loss (GShard eq. 4) is stored on the gate.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["TopKGate", "GShardGate", "SwitchGate", "SigmoidTopKGate",
           "SoftmaxTopKGate"]


def _capacity(num_tokens, num_experts, capacity_factor, top_k):
    cap = int(num_tokens * top_k * capacity_factor / num_experts)
    return max(cap, top_k)


def _one_hot_dispatch(gates, top_k, capacity):
    """gates (T, E) softmax probs → (dispatch (T,E,C) bool, combine
    (T,E,C) float, aux_loss scalar)."""
    t, e = gates.shape
    # straight GShard: iterate the k choices, masking prior picks
    dispatch = jnp.zeros((t, e, capacity), jnp.bool_)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    masked = gates
    me = jnp.mean(gates, axis=0)          # mean prob per expert
    ce_counts = jnp.zeros((e,), jnp.float32)
    # position counters per expert, threaded across the k rounds
    pos_base = jnp.zeros((e,), jnp.int32)
    for _ in range(top_k):
        idx = jnp.argmax(masked, axis=1)                       # (T,)
        sel = jax.nn.one_hot(idx, e, dtype=jnp.float32)        # (T, E)
        ce_counts = ce_counts + jnp.sum(sel, axis=0)
        # position of each token within its expert's queue this round
        pos_in = jnp.cumsum(sel, axis=0) - sel                 # (T, E)
        pos = (pos_in + pos_base[None, :]).astype(jnp.int32)
        within = pos < capacity
        keep = (sel > 0) & within                              # (T, E)
        posc = jax.nn.one_hot(
            jnp.sum(pos * sel.astype(jnp.int32), axis=1), capacity,
            dtype=jnp.float32)                                 # (T, C)
        disp_k = keep[:, :, None] & (posc[:, None, :] > 0)
        dispatch = dispatch | disp_k
        gate_val = jnp.sum(gates * sel, axis=1)                # (T,)
        combine = combine + disp_k.astype(jnp.float32) * gate_val[:, None, None]
        pos_base = pos_base + jnp.sum(keep, axis=0).astype(jnp.int32)
        masked = jnp.where(sel > 0, -jnp.inf, masked)
    # GShard aux loss: E * mean(fraction_routed * mean_prob)
    fraction = ce_counts / jnp.maximum(jnp.sum(ce_counts), 1.0)
    aux = jnp.sum(fraction * me) * e
    return dispatch, combine, aux


class TopKGate:
    """Dense top-k capacity gate over a learned projection."""

    def __init__(self, top_k=2, capacity_factor=1.25):
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.l_aux = None

    def __call__(self, logits):
        """logits (T, E) → (dispatch, combine, capacity)."""
        t, e = logits.shape
        cap = _capacity(t, e, self.capacity_factor, self.top_k)
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        dispatch, combine, aux = _one_hot_dispatch(gates, self.top_k, cap)
        self.l_aux = aux
        return dispatch, combine, cap

    def topk_assignments(self, logits):
        """Sparse form of the SAME routing decision (grouped-matmul
        dispatch tier): logits (T, E) → (expert_ids (T, k), gate_vals
        (T, k) with capacity-dropped slots zeroed, aux). Capacity
        semantics match __call__: round-major queueing — every token's
        r-th choice is queued before any token's (r+1)-th choice."""
        t, e = logits.shape
        cap = _capacity(t, e, self.capacity_factor, self.top_k)
        gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        topv, topi = jax.lax.top_k(gates, self.top_k)   # (T, k) desc
        ce_counts = jnp.zeros((e,), jnp.float32)
        pos_base = jnp.zeros((e,), jnp.int32)
        kept = []
        for r in range(self.top_k):
            sel = jax.nn.one_hot(topi[:, r], e, dtype=jnp.float32)
            ce_counts = ce_counts + jnp.sum(sel, axis=0)
            pos_in = jnp.cumsum(sel, axis=0) - sel
            pos = (pos_in + pos_base[None, :]).astype(jnp.int32)
            keep = (sel > 0) & (pos < cap)              # (T, E)
            pos_base = pos_base + jnp.sum(keep, axis=0).astype(jnp.int32)
            kept.append(jnp.any(keep, axis=1))
        keep_mask = jnp.stack(kept, axis=1)             # (T, k)
        gate_vals = topv * keep_mask.astype(topv.dtype)
        me = jnp.mean(gates, axis=0)
        fraction = ce_counts / jnp.maximum(jnp.sum(ce_counts), 1.0)
        aux = jnp.sum(fraction * me) * e
        self.l_aux = aux
        return topi, gate_vals, aux


class GShardGate(TopKGate):
    def __init__(self, capacity_factor=2.0):
        super().__init__(top_k=2, capacity_factor=capacity_factor)


class SwitchGate(TopKGate):
    def __init__(self, capacity_factor=1.25):
        super().__init__(top_k=1, capacity_factor=capacity_factor)


class SigmoidTopKGate:
    """The DeepSeek-V3 router's decision (``topk_method`` ``noaux_tc``):
    sigmoid scores, a per-expert selection bias that moves the CHOICE
    and never the weight, the chosen scores normalised to sum to one and
    scaled. No capacity: every token keeps all ``top_k`` experts, so
    nothing is dropped at any imbalance, and there is no auxiliary loss
    (the bias is what balances the load).

    ``n_group`` / ``topk_group``: the published group-limited selection
    first keeps the ``topk_group`` best of ``n_group`` expert groups;
    with one group it is the identity, the only case taken here."""

    def __init__(self, top_k, norm_topk_prob=True,
                 routed_scaling_factor=1.0, n_group=1, topk_group=1):
        if n_group != 1 or topk_group != 1:
            raise NotImplementedError(
                f"SigmoidTopKGate: group-limited routing (n_group="
                f"{n_group}, topk_group={topk_group}) is not implemented;"
                f" only one group")
        self.top_k = int(top_k)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.routed_scaling_factor = float(routed_scaling_factor)

    def topk_assignments(self, logits, bias=None):
        """logits (T, E) -> (expert_ids (T, k), weights (T, k) float32,
        None). ``bias`` (E,) is added to the scores for the selection
        only."""
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        choice = scores if bias is None else \
            scores + bias.astype(jnp.float32)
        _, topi = jax.lax.top_k(choice, self.top_k)
        w = jnp.take_along_axis(scores, topi, axis=-1)
        if self.norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return topi, w * self.routed_scaling_factor, None


class SoftmaxTopKGate:
    """The Granite / Mixtral-style router's decision: the ``top_k``
    largest LOGITS are chosen and the weights are the softmax over those
    chosen values alone (not over all experts). No capacity, no bias, no
    auxiliary loss: every token keeps all ``top_k`` experts."""

    def __init__(self, top_k):
        self.top_k = int(top_k)

    def topk_assignments(self, logits, bias=None):
        """logits (T, E) -> (expert_ids (T, k), weights (T, k) float32,
        None)."""
        if bias is not None:
            raise NotImplementedError(
                "SoftmaxTopKGate takes no selection bias")
        topv, topi = jax.lax.top_k(logits.astype(jnp.float32), self.top_k)
        return topi, jax.nn.softmax(topv, axis=-1), None
