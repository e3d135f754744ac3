"""MoELayer — expert-parallel FFN mixture (reference:
python/paddle/incubate/distributed/models/moe/moe_layer.py — unverified,
SURVEY.md §0/§2.3 EP row).

Experts are a stacked SwiGLU/GELU FFN: weights (num_experts, ...) sharded
over an ``expert`` mesh axis. Dispatch/combine are the GShard einsums —
under a mesh, constraining the dispatched tensor's expert dim makes GSPMD
emit the all-to-all over ICI (the reference's GlobalScatter/GlobalGather
NCCL ops)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .....nn.layer.layers import Layer
from .....nn import initializer as I
from .....ops.pallas import grouped_matmul as _kernel
from .....ops.pallas.grouped_matmul import swiglu
from .....ops.pallas._utils import count_traced_program, use_pallas_kernels
from .....tensor._helpers import apply, ensure_tensor
from .....parallel import mesh as mesh_state
from .gate import TopKGate, SwitchGate

__all__ = ["MoELayer", "grouped_expert_ffn", "moe_products_programs",
           "swiglu"]


# the sorted-rows buffer of `grouped_expert_ffn` (T*k rows of the model
# width) may take this many bytes; more tokens than that go through in
# equal tiles of positions, one after another (a shape rule: no knob, and
# no row is ever capped)
_SORTED_ROWS_BYTES = 512 << 20


def moe_products_programs():
    """``moe_products_programs_total{path}`` (``kernel`` | ``ragged_dot``)
    on the process's registry (an engine's registry shares it): programs
    traced, by the form their routed experts' two products take."""
    from .....obs.registry import MetricsRegistry

    return MetricsRegistry.process().counter(
        "moe_products_programs_total",
        "programs traced, by the form the routed experts' two products "
        "take (kernel | ragged_dot)")


def grouped_expert_ffn(xt, expert_ids, gate_vals, w1, w2, act, b1=None,
                       b2=None, held=None):
    """The routed experts' FFN as two grouped matrix products
    (megablocks-style): the T*k routed rows are sorted by expert and
    multiplied with the per-expert group sizes, so the work is O(T*k)
    rows whatever the imbalance and no row is ever dropped by this
    function (a gate that drops hands in weight zero).

    ``xt`` (T, M) tokens; ``expert_ids`` / ``gate_vals`` (T, k) each
    token's experts and combine weights; ``w1`` (E, M, F1), ``w2``
    (E, F2, M) the stacked expert weights with ``act`` between them
    (F1 -> F2); ``b1`` / ``b2`` optional stacked biases. Returns the
    combined output (T, M) in ``xt``'s dtype and the rows each expert
    got, (E,) int32. The k expert outputs of a token are gathered back
    and summed in float32 in the order of ``expert_ids`` (a gather, not
    a scatter-add: TPUs serialise scatters).

    The two products are ONE algorithm in two forms, chosen by a static
    rule that reads the inputs (``ops/pallas/grouped_matmul.supports``
    beside ``use_pallas_kernels``): the Pallas kernel ``grouped_matmul``
    on a TPU where both products' widths are whole sublane tiles of the
    dtype (at most one of a product's two not whole lanes: the stack is
    then read as the device stores it), in prefill and in decode alike,
    its row tile read from the mean rows a group; ``jax.lax.ragged_dot``
    elsewhere (the CPU, a mesh, a width the kernel cannot take). Same
    precision either way, and ``ragged_dot``'s backward.
    Where ``act`` is :func:`swiglu` itself (no ``b1``) the kernel applies
    it as the first product's epilogue; any other callable runs after the
    plain kernel, as it does after ``ragged_dot``.
    Which form a traced program took is counted once a program:
    :func:`moe_products_programs`.

    ``held=(lo, n)``: this chip HOLDS experts ``lo .. lo + n - 1`` of
    the ones the ids range over (expert parallelism: the router keeps
    its published width); ``w1`` / ``w2`` are those ``n`` slabs. A choice
    that fell on an absent expert sorts past every group, takes part in
    no product and adds exactly zero; the returned rows are the held
    experts', (n,). The sorted-rows buffer is sized for the worst case
    (every choice held). Default: every expert is held."""
    t, k = expert_ids.shape
    n_tiles = -(-(t * k * xt.shape[-1] * xt.dtype.itemsize)
                // _SORTED_ROWS_BYTES)
    while t % n_tiles:      # whole tiles of positions
        n_tiles += 1
    kernel = use_pallas_kernels() and _kernel.supports(w1, w2)
    count_traced_program(moe_products_programs(),
                         "kernel" if kernel else "ragged_dot")
    if n_tiles > 1:
        ys, rows = jax.lax.map(
            lambda a: _expert_ffn_tile(*a, w1, w2, act, b1, b2, held,
                                       kernel),
            tuple(a.reshape(n_tiles, t // n_tiles, a.shape[-1])
                  for a in (xt, expert_ids, gate_vals)))
        return ys.reshape(t, -1), jnp.sum(rows, axis=0)
    return _expert_ffn_tile(xt, expert_ids, gate_vals, w1, w2, act, b1, b2,
                            held, kernel)


def _expert_ffn_tile(xt, expert_ids, gate_vals, w1, w2, act, b1, b2, held,
                     kernel):
    """One tile of positions of :func:`grouped_expert_ffn`; ``kernel``:
    its two products take ``grouped_matmul``, not ``ragged_dot``."""
    product = _kernel.grouped_matmul if kernel else jax.lax.ragged_dot
    t, k = expert_ids.shape
    e = w1.shape[0]
    with jax.named_scope("moe.dispatch"):
        expert_flat = expert_ids.reshape(-1)                  # (T*k,)
        if held is not None:
            # absent experts share the id ``e``: last in the sort, in no
            # group
            lo, n = held
            if n != e:
                raise ValueError(f"held={held} but {e} weight slabs")
            local = expert_flat - lo
            present = (local >= 0) & (local < e)
            expert_flat = jnp.where(present, local, e)
        order = jnp.argsort(expert_flat)                      # stable
        sorted_exp = expert_flat[order]
        group_sizes = jnp.bincount(expert_flat, length=e).astype(jnp.int32)
        xs = xt[order // k]                                   # (T*k, M)

    with jax.named_scope("moe.products"):
        w1 = w1.astype(xt.dtype)
        if kernel and act is swiglu and b1 is None \
                and _kernel.fuses_swiglu(w1):
            # the activation as the first product's epilogue: h is never
            # stored (the same roundings; within one bf16 step of XLA's
            # fusion on the chip)
            h = product(xs, w1, group_sizes, swiglu=True)
        else:
            h = product(xs, w1, group_sizes)
            if b1 is not None:
                h = h + b1[sorted_exp].astype(xt.dtype)
            h = act(h)
        out = product(h, w2.astype(xt.dtype), group_sizes)
        if b2 is not None:
            out = out + b2[sorted_exp].astype(xt.dtype)
    with jax.named_scope("moe.combine"):
        if held is not None:
            # rows past the last group belong to no product: exactly
            # zero, whatever the backend leaves there. The k outputs of a
            # token are gathered back one choice at a time into a float32
            # sum: no (T, k, M) float32 buffer beside the worst-case
            # sorted rows
            out = jnp.where((sorted_exp < e)[:, None], out, 0)
            gate_vals = jnp.where(present.reshape(t, k), gate_vals, 0)
            back = jnp.argsort(order).reshape(t, k)
            y = sum(out[back[:, j]].astype(jnp.float32)
                    * gate_vals.astype(jnp.float32)[:, j, None]
                    for j in range(k))
            return y.astype(xt.dtype), group_sizes
        back = out[jnp.argsort(order)].reshape(t, k, -1)      # token-major
        y = jnp.sum(back.astype(jnp.float32)
                    * gate_vals.astype(jnp.float32)[..., None], axis=1)
        return y.astype(xt.dtype), group_sizes


class MoELayer(Layer):
    """MoE FFN block.

    Args:
        d_model: token dim.
        d_hidden: expert FFN hidden dim.
        num_experts: global expert count.
        gate: "gshard" | "switch" | a gate object (default top-2).
        activation: "gelu" | "swiglu".
        expert_axis: mesh axis experts shard over (default: "dp" when its
            size divides num_experts, else "mp"; no mesh → serial).
    """

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard",
                 activation="gelu", capacity_factor=2.0, expert_axis=None,
                 dispatch_mode="auto", name=None):
        super().__init__()
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        self.activation = activation
        if isinstance(gate, str):
            gate = {"gshard": TopKGate(2, capacity_factor),
                    "switch": SwitchGate(capacity_factor),
                    "top2": TopKGate(2, capacity_factor)}[gate]
        self.gate = gate
        self.l_aux = None

        ffn1_out = 2 * d_hidden if activation == "swiglu" else d_hidden
        self.gate_weight = self.create_parameter(
            (d_model, num_experts), default_initializer=I.XavierNormal())
        self.w1 = self.create_parameter(
            (num_experts, d_model, ffn1_out),
            default_initializer=I.XavierNormal())
        self.b1 = self.create_parameter((num_experts, ffn1_out), is_bias=True)
        self.w2 = self.create_parameter(
            (num_experts, d_hidden, d_model),
            default_initializer=I.XavierNormal())
        self.b2 = self.create_parameter((num_experts, d_model), is_bias=True)

        axis = expert_axis
        if axis is None and mesh_state.has_mesh():
            for cand in ("dp", "mp"):
                if (mesh_state.mesh_axis_size(cand) > 1
                        and num_experts % mesh_state.mesh_axis_size(cand) == 0):
                    axis = cand
                    break
        self.expert_axis = axis
        if axis is not None:
            for p in (self.w1, self.b1, self.w2, self.b2):
                p.is_distributed = True
                spec = [axis] + [None] * (p._value.ndim - 1)
                p._value = mesh_state.shard_value(p._value, *spec)
        if dispatch_mode not in ("auto", "einsum", "grouped"):
            raise ValueError(
                f"dispatch_mode must be auto|einsum|grouped, got "
                f"{dispatch_mode!r}")
        # grouped (sort + lax.ragged_dot) is the perf tier: O(T*k) rows
        # of matmul instead of the dense (T, E, C) einsums. With
        # expert_axis set it runs the shard_map EP schedule (global gate
        # + per-shard ragged_dot, see _grouped_ep_fn); einsum remains the
        # GSPMD fallback for custom gates / non-divisible shapes.
        if dispatch_mode == "auto":
            # custom gate objects only promise the __call__ → (dispatch,
            # combine, cap) contract; grouped needs the sparse
            # topk_assignments form
            dispatch_mode = (
                "grouped" if hasattr(self.gate, "topk_assignments")
                and (axis is None
                     or num_experts % mesh_state.mesh_axis_size(axis) == 0)
                else "einsum")
        if (dispatch_mode == "grouped" and axis is not None
                and num_experts % max(
                    mesh_state.mesh_axis_size(axis), 1) != 0):
            raise ValueError(
                f"grouped EP dispatch needs num_experts ({num_experts}) "
                f"divisible by the {axis!r} axis size "
                f"({mesh_state.mesh_axis_size(axis)})")
        self.dispatch_mode = dispatch_mode

    def _act(self, h):
        if self.activation == "swiglu":
            return swiglu(h)
        return jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)

    def _grouped_fn(self, xv, gw, w1, b1, w2, b2):
        """Sort/segment grouped-matmul dispatch (megablocks-style): the
        T*k routed rows are sorted by expert and fed to
        ``jax.lax.ragged_dot`` with per-expert group sizes — O(T*k)
        matmul rows and O(T*k*M) memory, vs the dense einsum tier's
        (T, E, C) dispatch tensor. Same gate, same capacity-drop
        semantics (dropped rows keep their slot but combine with weight
        zero), same aux loss."""
        lead = xv.shape[:-1]
        xt = xv.reshape(-1, self.d_model)
        logits = xt.astype(jnp.float32) @ gw.astype(jnp.float32)
        topi, gate_vals, aux = self.gate.topk_assignments(logits)
        y, _ = grouped_expert_ffn(xt, topi, gate_vals, w1, w2, self._act,
                                  b1=b1, b2=b2)
        return y.reshape(*lead, self.d_model), aux

    def _grouped_ep_fn(self, xv, gw, w1, b1, w2, b2):
        """Expert-parallel grouped dispatch: a ``shard_map`` schedule over
        ``expert_axis`` with the same gate/capacity semantics as serial.

        Per device: (1) all-gather the token shard and run the GATE
        GLOBALLY (capacity queueing depends on global token order — a
        per-shard gate would diverge from the serial oracle); (2) sort
        the kept routed rows by expert (identical order on every device)
        and take this shard's expert segment via a dynamic slice whose
        STATIC size is the gate-capacity bound ``(E/P) * cap`` — the gate
        guarantees kept rows per expert ≤ cap, so the slice never
        truncates; (3) ``lax.ragged_dot`` with the local expert weights;
        (4) scatter-add into a (T, M) partial and ``psum_scatter`` back
        to the token owners. Per-device matmul rows scale as T*k*cf/P —
        the EP compute win the dense (T, E, C) einsum tier lacks at long
        T (its cost ∝ T²). Wire is one all-gather
        + one reduce-scatter of (T, M); swapping the gather/scatter pair
        for ``lax.ragged_all_to_all`` (row exchange ∝ routed tokens) is
        the upgrade path once XLA:CPU implements the op — today it would
        make every CPU-mesh test and the driver dryrun unrunnable."""
        from .gate import _capacity

        cfg = self
        mesh = mesh_state.get_mesh()
        ax = cfg.expert_axis
        pn = int(mesh.shape[ax])
        e = cfg.num_experts
        epp = e // pn
        lead = xv.shape[:-1]
        t = 1
        for s in lead:
            t *= s
        k = cfg.gate.top_k
        cap = _capacity(t, e, cfg.gate.capacity_factor, k)
        slice_rows = min(epp * cap, t * k)
        from jax.sharding import PartitionSpec as P

        def body(xt_loc, gw_, w1_, b1_, w2_, b2_):
            p = jax.lax.axis_index(ax)
            xt_all = jax.lax.all_gather(xt_loc, ax, axis=0, tiled=True)
            logits = xt_all.astype(jnp.float32) @ gw_.astype(jnp.float32)
            topi, gate_vals, aux = cfg.gate.topk_assignments(logits)
            expert_flat = topi.reshape(-1)
            gv_flat = gate_vals.reshape(-1).astype(xt_all.dtype)
            tok_flat = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
            kept = gv_flat > 0
            # dropped rows sort to the sentinel tail: the slice bound
            # below holds for KEPT rows only
            key = jnp.where(kept, expert_flat, e).astype(jnp.int32)
            order = jnp.argsort(key)
            pad_tail = jnp.full((slice_rows,), e, jnp.int32)
            sorted_tok = jnp.concatenate(
                [tok_flat[order], jnp.zeros((slice_rows,), jnp.int32)])
            sorted_exp = jnp.concatenate([key[order], pad_tail])
            sorted_gv = jnp.concatenate(
                [gv_flat[order], jnp.zeros((slice_rows,), gv_flat.dtype)])
            kept_counts = jnp.bincount(key, length=e + 1)[:e]
            start = jnp.sum(
                jnp.where(jnp.arange(e) < p * epp, kept_counts, 0)
            ).astype(jnp.int32)
            rows_tok = jax.lax.dynamic_slice(sorted_tok, (start,),
                                             (slice_rows,))
            rows_exp = jax.lax.dynamic_slice(sorted_exp, (start,),
                                             (slice_rows,))
            rows_gv = jax.lax.dynamic_slice(sorted_gv, (start,),
                                            (slice_rows,))
            xs = xt_all[rows_tok]
            mine = (rows_exp >= p * epp) & (rows_exp < (p + 1) * epp)
            local_exp = jnp.clip(rows_exp - p * epp, 0, epp - 1)
            gs = jax.lax.dynamic_slice(
                kept_counts, (p * epp,), (epp,)).astype(jnp.int32)
            # trailing non-mine rows feed the last group; masked below
            gs = gs.at[-1].add(slice_rows - jnp.sum(gs))
            h = jax.lax.ragged_dot(xs, w1_.astype(xs.dtype), gs)
            h = h + b1_[local_exp].astype(xs.dtype)
            h = cfg._act(h)
            out = jax.lax.ragged_dot(h, w2_.astype(xs.dtype), gs)
            out = out + b2_[local_exp].astype(xs.dtype)
            weight = jnp.where(mine, rows_gv, 0.0)
            y = jnp.zeros((t, cfg.d_model), xs.dtype).at[rows_tok].add(
                out * weight[:, None])
            y_loc = jax.lax.psum_scatter(y, ax, scatter_dimension=0,
                                         tiled=True)
            return y_loc, jax.lax.pmean(aux, ax)

        xt = xv.reshape(t, cfg.d_model)
        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(ax), P(), P(ax), P(ax), P(ax), P(ax)),
            out_specs=(P(ax), P()),
            check_vma=False,
        )(xt, gw, w1, b1, w2, b2)
        return y.reshape(*lead, cfg.d_model), aux

    def forward(self, x):
        """x: (..., d_model) → same shape; self.l_aux holds the aux loss."""
        x = ensure_tensor(x)
        gate = self.gate
        cfg = self

        if self.dispatch_mode == "grouped":
            ep = self.expert_axis is not None and mesh_state.has_mesh() \
                and mesh_state.mesh_axis_size(self.expert_axis) > 1
            if ep:
                t = 1
                for s in x.shape[:-1]:
                    t *= s
                pn = mesh_state.mesh_axis_size(self.expert_axis)
                # the mesh may be installed AFTER construction, so the
                # num_experts divisibility must be re-checked here too —
                # inside shard_map it would fail as an opaque in_specs
                # error on the expert weights
                if t % pn != 0 or self.num_experts % pn != 0:
                    import warnings

                    warnings.warn(
                        f"grouped EP dispatch needs token count {t} and "
                        f"num_experts {self.num_experts} divisible by "
                        f"{self.expert_axis}={pn}; falling back to the "
                        f"einsum tier", RuntimeWarning)
                else:
                    out, self.l_aux = apply(
                        self._grouped_ep_fn, x, self.gate_weight, self.w1,
                        self.b1, self.w2, self.b2,
                        op_name="moe_layer_grouped_ep")
                    return out
            else:
                out, self.l_aux = apply(
                    self._grouped_fn, x, self.gate_weight, self.w1, self.b1,
                    self.w2, self.b2, op_name="moe_layer_grouped")
                return out

        def fn(xv, gw, w1, b1, w2, b2):
            lead = xv.shape[:-1]
            t = 1
            for s in lead:
                t *= s
            xt = xv.reshape(t, cfg.d_model)
            logits = xt.astype(jnp.float32) @ gw.astype(jnp.float32)
            dispatch, combine, cap = gate(logits)
            aux = gate.l_aux
            # dispatch: (T, E, C) → expert inputs (E, C, M)
            disp = jnp.einsum(
                "tec,tm->ecm", dispatch.astype(xv.dtype), xt)
            if cfg.expert_axis is not None:
                disp = mesh_state.constraint(disp, cfg.expert_axis, None, None)
            h = jnp.einsum("ecm,emh->ech", disp, w1.astype(xv.dtype))
            h = h + b1[:, None, :].astype(xv.dtype)
            h = cfg._act(h)
            out = jnp.einsum("ech,ehm->ecm", h, w2.astype(xv.dtype))
            out = out + b2[:, None, :].astype(xv.dtype)
            if cfg.expert_axis is not None:
                out = mesh_state.constraint(out, cfg.expert_axis, None, None)
            y = jnp.einsum("tec,ecm->tm", combine.astype(xv.dtype), out)
            # aux returned through the op so the load-balancing loss stays
            # on the tape (differentiable into gate_weight)
            return y.reshape(*lead, cfg.d_model), aux

        out, self.l_aux = apply(
            fn, x, self.gate_weight, self.w1, self.b1, self.w2,
            self.b2, op_name="moe_layer")
        return out
