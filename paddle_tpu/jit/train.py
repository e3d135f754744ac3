"""Fully-jitted training step — the perf path of the framework.

The reference reaches peak throughput through static-graph execution with
fused ops (SURVEY.md §3.2/§3.3); the TPU-native equivalent is ONE
``jax.jit``-compiled function per training step: forward (via
``functional_call`` on the live Layer), loss, backward (``jax.grad``),
and the optimizer's functional multi-tensor update — all fused by XLA,
with parameter/state buffers donated so updates are in-place in HBM.

Under a ``jax.sharding.Mesh`` the params/opt-states are already placed
with NamedShardings (fleet TP layers / ZeRO state sharding); jit infers
in-shardings from placement and GSPMD inserts the ICI collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import autograd
from . import functional_call
from ..parallel import mesh as mesh_state
from ..profiler import RecordEvent, count_compile_events

__all__ = ["JittedTrainStep"]


class JittedTrainStep:
    """Compile the whole (forward, loss, backward, update) into one XLA
    program.

    Args:
        model: nn.Layer (params may carry NamedShardings from TP layers).
        criterion: callable(model_output, *labels) -> scalar loss Tensor.
        optimizer: paddle_tpu Optimizer (its functional bridge is used;
            the live optimizer object's state is NOT consumed).
        state_sharding_axis: optional mesh axis name — optimizer states
            are sharded over it along dim 0 when divisible (ZeRO-1/2: the
            reference's GroupShardedOptimizerStage2 semantics).
        input_batch_axes: mesh axes for the leading (batch) dim of every
            input (default ``("dp",)`` when a mesh is installed).
        donate: donate param/state buffers (in-place HBM update).
    """

    def __init__(self, model, criterion, optimizer,
                 state_sharding_axis=None, input_batch_axes=None,
                 donate=True):
        self._model = model
        self._criterion = criterion
        self._optimizer = optimizer
        self._params = [p for _, p in model.named_parameters()]
        self._buffers = [b for _, b in model.named_buffers()]
        self._p_vals = [p._value for p in self._params]
        self._b_vals = [b._value for b in self._buffers]
        if mesh_state.has_mesh():
            # commit EVERY param/buffer to the mesh (replicated when not
            # already placed): an uncommitted array leaves
            # allow_spmd_sharding_propagation_to_parameters open, and the
            # partitioner then back-propagates optimizer-state shardings
            # into e.g. layernorm weights, poisoning the whole forward
            # with involuntary-remat reshards
            self._p_vals = [_commit_to_mesh(v) for v in self._p_vals]
            self._b_vals = [_commit_to_mesh(v) for v in self._b_vals]
            for p, v in zip(self._params, self._p_vals):
                p._value = v
            for b, v in zip(self._buffers, self._b_vals):
                b._value = v
        self._s_vals = optimizer.functional_state_init(self._p_vals)
        self._decay_flags = [optimizer._decay_enabled(p) for p in self._params]
        self._step_no = 0
        self._input_batch_axes = input_batch_axes
        if state_sharding_axis and mesh_state.has_mesh():
            self._s_vals = _shard_states(
                self._s_vals, state_sharding_axis, self._p_vals)

        model_ref = model
        criterion_ref = criterion
        opt_ref = optimizer
        decay_flags = self._decay_flags
        # Pin grads of TENSOR-PARALLEL params to the param's own layout:
        # without it, 'sharding'-sharded moments leak their axis backward
        # through the bwd matmuls and GSPMD full-remats params whose
        # device order differs. Replicated params stay unpinned so their
        # partial-sum grads can reduce-scatter straight into ZeRO-sharded
        # moments (pinning those would force an early all-reduce).
        def _pin_sharding(v):
            sh = _named_sharding_of(v)
            if sh is not None and any(s is not None for s in sh.spec):
                return sh
            return None

        grad_pins = (
            [_pin_sharding(v) for v in self._p_vals]
            if mesh_state.has_mesh() else [None] * len(self._p_vals)
        )

        def one_step(p_vals, s_vals, b_vals, rng, lr, step_no, inputs, labels):
            from ..core.random import traced_key_scope

            def loss_of(pv):
                in_t = [Tensor(x, stop_gradient=True) for x in inputs]
                lb_t = [Tensor(x, stop_gradient=True) for x in labels]
                with autograd.no_grad(), traced_key_scope(rng):
                    def fwd_and_loss(*args):
                        n_in = len(in_t)
                        out = model_ref(*args[:n_in])
                        with jax.named_scope("loss"):
                            return criterion_ref(out, *args[n_in:])

                    loss_t, new_b = functional_call(
                        model_ref, fwd_and_loss, in_t + lb_t, {}, pv, b_vals
                    )
                return loss_t._value, new_b

            (loss, new_b), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p_vals)
            grads = [
                jax.lax.with_sharding_constraint(g, sh)
                if g is not None and sh is not None else g
                for g, sh in zip(grads, grad_pins)
            ]
            with jax.named_scope("optimizer"):
                new_p, new_s = opt_ref.functional_apply(
                    p_vals, grads, s_vals, lr, step_no, decay_flags)
            return loss, new_p, new_s, new_b

        def step_fn(p_vals, s_vals, b_vals, rng, lr, step_no, inputs, labels):
            return one_step(p_vals, s_vals, b_vals, rng, lr, step_no,
                            inputs, labels)

        def multi_step_fn(p_vals, s_vals, b_vals, rng, lr, step0,
                          inputs_stacked, labels_stacked):
            # K train steps in ONE XLA program (lax.scan over the batch
            # stack): amortizes host dispatch — the TPU-native analog of
            # the reference Executor running a multi-iteration program
            def body(carry, xs):
                p, s, b, step_no = carry
                in_i, lb_i = xs
                rng_i = jax.random.fold_in(rng, step_no)
                loss, p, s, b = one_step(p, s, b, rng_i, lr, step_no,
                                         in_i, lb_i)
                return (p, s, b, step_no + 1), loss

            (p, s, b, _), losses = jax.lax.scan(
                body, (p_vals, s_vals, b_vals, step0),
                (inputs_stacked, labels_stacked))
            return losses, p, s, b

        self._donate = bool(donate)
        self._step_fn = step_fn  # analysis hook: the pure step function
        donate_args = (0, 1, 2) if donate else ()
        jit_kw = {}
        if mesh_state.has_mesh():
            # pin state outputs to their input placements: donation stays
            # buffer-exact and the partitioner never "improves" the
            # round-trip sharding (a source of involuntary remat reshards);
            # only mesh placements are pinnable — uncommitted arrays
            # (SingleDeviceSharding) stay unconstrained
            p_sh = [_named_sharding_of(v) for v in self._p_vals]
            s_sh = jax.tree_util.tree_map(_named_sharding_of, self._s_vals)
            b_sh = [_named_sharding_of(v) for v in self._b_vals]
            jit_kw = {"out_shardings": (None, p_sh, s_sh, b_sh)}
        self._jitted = jax.jit(step_fn, donate_argnums=donate_args, **jit_kw)
        self._jitted_multi = jax.jit(
            multi_step_fn, donate_argnums=donate_args, **jit_kw)
        # JAX's compile events, charged to the step span that caused them
        count_compile_events()

    def _batch_args(self, inputs, labels):
        """Normalize/place one example batch: (in_vals, lb_vals, lr,
        step_no) exactly as __call__ would feed the jitted program."""
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        in_vals = [self._place_input(t) for t in inputs]
        lb_vals = [self._place_input(t) for t in labels]
        lr = jnp.asarray(self._optimizer.get_lr(), jnp.float32)
        step_no = jnp.asarray(self._step_no + 1, jnp.int32)
        return in_vals, lb_vals, lr, step_no

    def __call__(self, inputs, labels):
        """inputs/labels: Tensor or list of Tensors. Returns loss Tensor.
        One dispatch, spanned like :meth:`run_steps`."""
        from ..core.random import next_key

        with RecordEvent("train.run_steps", step_kind="train",
                         step=self._step_no):
            with RecordEvent("train.args"):
                in_vals, lb_vals, lr, step_no = self._batch_args(
                    inputs, labels)
                key = next_key()
            with RecordEvent("train.enqueue"):
                loss, self._p_vals, self._s_vals, self._b_vals = \
                    self._jitted(
                        self._p_vals, self._s_vals, self._b_vals, key, lr,
                        step_no, in_vals, lb_vals)
            self._step_no += 1
        return Tensor(loss)

    # -- lowered-IR hooks (paddle_tpu.analysis audits compile THESE) -------
    def lower(self, inputs, labels):
        """Lower (do not run) the single-step program for the CURRENT
        param/state values and the given example batch; returns the
        ``jax.stages.Lowered`` whose StableHLO / compiled HLO the
        analysis passes walk."""
        in_vals, lb_vals, lr, step_no = self._batch_args(inputs, labels)
        from ..core.random import next_key

        return self._jitted.lower(
            self._p_vals, self._s_vals, self._b_vals, next_key(), lr,
            step_no, in_vals, lb_vals,
        )

    def step_jaxpr(self, inputs, labels):
        """The step's ClosedJaxpr (pre-partitioning IR) for the current
        state — the dtype-promotion auditor walks this."""
        in_vals, lb_vals, lr, step_no = self._batch_args(inputs, labels)
        from ..core.random import next_key

        return jax.make_jaxpr(self._step_fn)(
            self._p_vals, self._s_vals, self._b_vals, next_key(), lr,
            step_no, in_vals, lb_vals,
        )

    def donatable_leaf_count(self):
        """How many leading jit arguments are param/state/buffer leaves
        (the ones ``donate=True`` hands back to XLA): the donation audit
        checks exactly these are aliased in the lowered program."""
        flat, _ = jax.tree_util.tree_flatten(
            (self._p_vals, self._s_vals, self._b_vals))
        return len(flat)

    @property
    def donate(self):
        return self._donate

    def _steps_args(self, inputs_stacked, labels_stacked):
        """The K-step program's arguments for the current state, exactly
        as run_steps feeds them."""
        if not isinstance(inputs_stacked, (list, tuple)):
            inputs_stacked = [inputs_stacked]
        if not isinstance(labels_stacked, (list, tuple)):
            labels_stacked = [labels_stacked]
        in_vals = [self._place_input(t, stacked=True) for t in inputs_stacked]
        lb_vals = [self._place_input(t, stacked=True) for t in labels_stacked]
        from ..core.random import next_key

        lr = jnp.asarray(self._optimizer.get_lr(), jnp.float32)
        step0 = jnp.asarray(self._step_no + 1, jnp.int32)
        return (self._p_vals, self._s_vals, self._b_vals, next_key(), lr,
                step0, in_vals, lb_vals)

    def run_steps(self, inputs_stacked, labels_stacked):
        """Run K train steps in ONE dispatch. inputs/labels carry a leading
        step dim (K, batch, ...); returns the (K,) per-step losses. The
        host's part is the span ``train.run_steps``: ``train.args``
        (placing the batch, the key, the scalars) and ``train.enqueue``
        (the jitted call until it returns; the device runs on)."""
        with RecordEvent("train.run_steps", step_kind="train",
                         step=self._step_no):
            with RecordEvent("train.args"):
                args = self._steps_args(inputs_stacked, labels_stacked)
            with RecordEvent("train.enqueue"):
                losses, self._p_vals, self._s_vals, self._b_vals = \
                    self._jitted_multi(*args)
            self._step_no += losses.shape[0]
        return Tensor(losses)

    def lower_steps(self, inputs_stacked, labels_stacked):
        """Lower (do not run) the K-step program run_steps dispatches —
        :meth:`lower`'s twin for the scan-fused program."""
        return self._jitted_multi.lower(
            *self._steps_args(inputs_stacked, labels_stacked))

    def _place_input(self, t, stacked=False):
        v = t._value if isinstance(t, Tensor) else jnp.asarray(t)
        if mesh_state.has_mesh():
            axes = self._input_batch_axes
            if axes is None:
                axes = ("dp",) if mesh_state.mesh_axis_size("dp") > 1 else ()
            if axes:
                from jax.sharding import NamedSharding, PartitionSpec

                lead = [None] if stacked else []
                spec = PartitionSpec(
                    *lead, axes, *([None] * (v.ndim - len(lead) - 1)))
                v = jax.device_put(
                    v, NamedSharding(mesh_state.get_mesh(), spec))
        return v

    def sync_to_model(self):
        """Write the jitted state back to the live Layer/Optimizer (for
        save/load or switching to eager)."""
        for p, v in zip(self._params, self._p_vals):
            p._value = v
        for b, v in zip(self._buffers, self._b_vals):
            b._value = v
        for p, s in zip(self._params, self._s_vals):
            self._optimizer._states[id(p)] = s
        self._optimizer._step_count = self._step_no

    @property
    def params(self):
        return self._p_vals


def _named_sharding_of(v):
    """The array's NamedSharding, or None when uncommitted/off-mesh."""
    from jax.sharding import NamedSharding

    sh = getattr(v, "sharding", None)
    return sh if isinstance(sh, NamedSharding) else None


def _commit_to_mesh(v):
    """Give an uncommitted array a replicated NamedSharding on the mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    if not isinstance(v, jax.Array):
        return v
    if _named_sharding_of(v) is not None:
        return v
    mesh = mesh_state.get_mesh()
    spec = PartitionSpec(*([None] * v.ndim))
    return jax.device_put(v, NamedSharding(mesh, spec))


def _shard_states(states, axis, p_vals):
    """Place optimizer state arrays sharded over ``axis`` (dim 0 when
    divisible) — ZeRO-1/2 optimizer-state partitioning on the mesh.

    Param-shaped states (moments, master weights) MERGE the param's own
    sharding (e.g. TP's mp axis) with the ZeRO axis instead of replacing
    it: a dim-1-mp-sharded param whose moments were dim-0-sharding-only
    would otherwise force the partitioner into replicate-then-repartition
    ("involuntary full rematerialization") at every optimizer update."""
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = mesh_state.get_mesh()
    size = mesh_state.mesh_axis_size(axis)
    if size <= 1:
        return states

    def _merged_spec(p, v):
        pspec = ()
        psh = _named_sharding_of(p)
        if psh is not None:
            pspec = tuple(psh.spec)
        # ZeRO axis goes MINOR on dim 0 (shared rule, see
        # mesh.merged_dim0_spec): each device's moment shard is a
        # sub-slice of its own param/grad shard.
        return mesh_state.merged_dim0_spec(v.shape, pspec, mesh, axis)

    out = []
    for p, st in zip(p_vals, states):
        def place(v, p=p):
            # 1-D params (norm scales, biases) keep replicated moments:
            # sharding them saves ~hidden_size bytes but their unpinnable
            # grads let the 'sharding' axis propagate backward into the
            # activation grads (involuntary full remats). 2-D+ params
            # carry the actual ZeRO memory win. Replicated still means
            # COMMITTED to the mesh — an uncommitted state input would
            # reopen the propagation hole.
            if not isinstance(v, jax.Array) or v.ndim == 0:
                return v
            if v.ndim < 2:
                return _commit_to_mesh(v)
            if v.shape == p.shape:
                spec = _merged_spec(p, v)
            elif v.shape[0] % size == 0:
                spec = PartitionSpec(axis, *([None] * (v.ndim - 1)))
            else:
                spec = PartitionSpec(*([None] * v.ndim))
            return jax.device_put(v, NamedSharding(mesh, spec))

        out.append(jax.tree_util.tree_map(place, st))
    return out
