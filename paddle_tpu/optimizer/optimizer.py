"""Optimizers (reference surface: python/paddle/optimizer/ — unverified,
SURVEY.md §0).

Design: each optimizer defines a pure per-tensor ``_update(p, g, state,
lr)`` rule; ``step()`` runs ONE jitted multi-tensor update over all
params/grads/accumulators — the TPU-native analog of the reference's
``fused_adam`` multi-tensor kernels (paddle/phi/kernels/fused_adam_kernel
— a single compiled XLA program updates every parameter). The same pure
rule is reused by the distributed trainer through
``functional_state_init`` / ``functional_apply``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, Parameter
from ..core import autograd
from .lr import LRScheduler
from .clip import ClipGradBase

__all__ = [
    "Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax", "Adagrad",
    "Adadelta", "RMSProp", "Lamb", "LarsMomentum", "Rprop", "NAdam",
    "RAdam", "ASGD", "LBFGS",
]


class L2Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


class L1Decay:
    def __init__(self, coeff=0.0):
        self.coeff = float(coeff)


def _wd_coeff(weight_decay):
    if weight_decay is None:
        return 0.0, "l2"
    if isinstance(weight_decay, L2Decay):
        return weight_decay.coeff, "l2"
    if isinstance(weight_decay, L1Decay):
        return weight_decay.coeff, "l1"
    return float(weight_decay), "l2"


class Optimizer:
    _decoupled_wd = False  # AdamW-style

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None, **kwargs):
        self._lr = learning_rate
        self._parameter_list = list(parameters) if parameters is not None else None
        self._wd, self._wd_kind = _wd_coeff(weight_decay)
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._states: dict[int, dict] = {}
        self._step_count = 0
        self._jit_cache: dict = {}

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def set_lr(self, value):
        if isinstance(self._lr, LRScheduler):
            raise RuntimeError("cannot set_lr when using an LRScheduler")
        self._lr = float(value)

    def set_lr_scheduler(self, scheduler):
        self._lr = scheduler

    # -- state ---------------------------------------------------------------
    def _init_state(self, p_value):
        """Return dict of accumulator arrays for one param (pure)."""
        return {}

    def _update(self, p, g, state, lr, step, decay=True):
        """Pure per-tensor update: returns (new_p, new_state)."""
        raise NotImplementedError

    def _decay_enabled(self, param) -> bool:
        """Per-param weight-decay gate (AdamW apply_decay_param_fun etc.)."""
        return True

    def _state_for(self, param):
        key = id(param)
        if key not in self._states:
            st = self._init_state(param._value)
            if self._multi_precision and param._value.dtype in (
                jnp.float16, jnp.bfloat16
            ):
                st["master"] = param._value.astype(jnp.float32)
            self._states[key] = st
        return self._states[key]

    # -- functional bridge (used by fleet/hapi jitted train steps) ----------
    def functional_state_init(self, params_tree):
        """Pytree of param arrays → pytree of state dicts (incl. master
        weights for low-precision params when multi_precision)."""

        def init(p):
            st = self._init_state(p)
            if self._multi_precision and p.dtype in (jnp.float16, jnp.bfloat16):
                st["master"] = p.astype(jnp.float32)
            return st

        return jax.tree_util.tree_map(
            init, params_tree,
            is_leaf=lambda x: isinstance(x, jax.Array),
        )

    def functional_apply(self, params_tree, grads_tree, states_tree, lr, step,
                         decay_flags=None):
        """Pure pytree update (no Tensor objects) for jitted trainers.

        ``decay_flags``: optional pytree of bools (same structure) marking
        which params receive weight decay (the eager path derives this
        from ``param.no_weight_decay``/bias detection via _decay_enabled).
        """
        flat_p, tdef = jax.tree_util.tree_flatten(
            params_tree, is_leaf=lambda x: isinstance(x, jax.Array)
        )
        flat_g = tdef.flatten_up_to(grads_tree)
        flat_s = tdef.flatten_up_to(states_tree)
        if decay_flags is None:
            flat_d = [True] * len(flat_p)
        else:
            flat_d = tdef.flatten_up_to(decay_flags)

        def upd(p, g, st, d=True):
            return self._apply_one(p, g, st, lr, step, decay=d)
        if self._grad_clip is not None:
            with jax.named_scope("grad.clip"):
                flat_g = self._grad_clip.clip_values(flat_g)
        new = [upd(p, g, st, d)
               for p, g, st, d in zip(flat_p, flat_g, flat_s, flat_d)]
        new_p = jax.tree_util.tree_unflatten(tdef, [x[0] for x in new])
        new_s = jax.tree_util.tree_unflatten(tdef, [x[1] for x in new])
        return new_p, new_s

    def _apply_one(self, p, g, state, lr, step, decay=True):
        """Full per-tensor update incl. weight decay + master weights."""
        work = state.get("master", p)
        g = g.astype(work.dtype)
        if self._wd and not self._decoupled_wd and decay:
            if self._wd_kind == "l2":
                g = g + self._wd * work
            else:
                g = g + self._wd * jnp.sign(work)
        new_work, new_state = self._update(
            work, g, {k: v for k, v in state.items() if k != "master"},
            lr, step, decay=decay,
        )
        if self._wd and self._decoupled_wd and decay:
            new_work = new_work - lr * self._wd * work
        if "master" in state:
            new_state["master"] = new_work
            return new_work.astype(p.dtype), new_state
        return new_work, new_state

    # -- eager step ----------------------------------------------------------
    @autograd.no_grad()
    def step(self):
        params = [
            p
            for p in (self._parameter_list or [])
            if p.trainable and p.grad is not None
        ]
        if not params:
            return
        lr = jnp.asarray(self.get_lr(), jnp.float32)
        step_no = jnp.asarray(self._step_count + 1, jnp.int32)
        p_vals = [p._value for p in params]
        g_vals = [p.grad._value for p in params]
        s_vals = [self._state_for(p) for p in params]
        decay_flags = [self._decay_enabled(p) for p in params]

        # Params may live on disjoint device sets (pipeline stages); a
        # single XLA program cannot span them, so fuse per device set.
        # Grad clipping with a GLOBAL norm must still see every grad, so
        # the squared-norm is reduced across groups first.
        def _devset(v):
            try:
                return tuple(sorted(d.id for d in v.sharding.device_set))
            except Exception:
                return ("default",)

        groups: dict = {}
        for i, v in enumerate(p_vals):
            groups.setdefault(_devset(v), []).append(i)

        # Global-norm clipping across multiple device sets: reduce the
        # squared norms per group, combine on host, feed the scale in as a
        # traced scalar so in-group clipping is skipped.
        from .clip import ClipGradByGlobalNorm

        gscale = None
        if isinstance(self._grad_clip, ClipGradByGlobalNorm) and len(groups) > 1:
            import numpy as _np

            # eager reductions (no jit: would retrace every step via the
            # fresh closure; a handful of per-group reductions is cheap)
            sq = 0.0
            for devset, idxs in groups.items():
                sq += float(
                    sum(
                        jnp.sum(jnp.square(g_vals[i].astype(jnp.float32)))
                        for i in idxs
                    )
                )
            global_norm = float(_np.sqrt(sq))
            clip_norm = self._grad_clip.clip_norm
            gscale = jnp.asarray(
                clip_norm / max(global_norm, clip_norm), jnp.float32
            )

        for devset, idxs in groups.items():
            sub_decay = tuple(decay_flags[i] for i in idxs)
            shapes = tuple((p_vals[i].shape, str(p_vals[i].dtype)) for i in idxs)
            cache_key = (devset, shapes, sub_decay, gscale is not None)
            if cache_key not in self._jit_cache:
                def fused(ps, gs, ss, lr_, st_, gscale_, _decay=sub_decay):
                    if gscale_ is not None:
                        gs = [
                            (g.astype(jnp.float32) * gscale_).astype(g.dtype)
                            for g in gs
                        ]
                    elif self._grad_clip is not None:
                        gs = self._grad_clip.clip_values(gs)
                    outs = [
                        self._apply_one(p, g, s, lr_, st_, decay=d)
                        for p, g, s, d in zip(ps, gs, ss, _decay)
                    ]
                    return [o[0] for o in outs], [o[1] for o in outs]

                self._jit_cache[cache_key] = jax.jit(
                    fused, static_argnames=()
                )
            jitted = self._jit_cache[cache_key]
            new_p, new_s = jitted(
                [p_vals[i] for i in idxs],
                [g_vals[i] for i in idxs],
                [s_vals[i] for i in idxs],
                lr, step_no, gscale,
            )
            for j, i in enumerate(idxs):
                params[i]._value = new_p[j]
                self._states[id(params[i])] = new_s[j]
        self._step_count += 1

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        loss.backward()
        self.step()
        return None, None

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list or []:
            p.clear_grad()

    clear_gradients = clear_grad

    # -- serialization -------------------------------------------------------
    def state_dict(self):
        out = {"step_count": self._step_count}
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        for i, p in enumerate(self._parameter_list or []):
            st = self._states.get(id(p))
            if st:
                for k, v in st.items():
                    out[f"{p.name}_{k}"] = Tensor(v)
        return out

    def set_state_dict(self, state_dict):
        self._step_count = state_dict.get("step_count", 0)
        if isinstance(self._lr, LRScheduler) and "LR_Scheduler" in state_dict:
            self._lr.set_state_dict(dict(state_dict["LR_Scheduler"]))
        missing = []
        for p in self._parameter_list or []:
            st = self._state_for(p)
            for k in list(st):
                key = f"{p.name}_{k}"
                if key in state_dict:
                    v = state_dict[key]
                    st[k] = v._value if isinstance(v, Tensor) else jnp.asarray(v)
                else:
                    missing.append(key)
        if missing:
            # id-based fallback names differ across processes — a silent
            # skip would reset accumulators to zero on resume
            import warnings

            warnings.warn(
                f"optimizer.set_state_dict: {len(missing)} accumulator keys "
                f"not found in the checkpoint (e.g. {missing[0]!r}); those "
                "accumulators keep their current values. Name parameters via "
                "Layer.create_parameter for stable keys.",
                RuntimeWarning,
            )


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _update(self, p, g, state, lr, step, decay=True):
        return p - lr.astype(p.dtype) * g, state


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, rescale_grad=1.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _init_state(self, p_value):
        return {"velocity": jnp.zeros(p_value.shape, jnp.float32)}

    def _update(self, p, g, state, lr, step, decay=True):
        v = self._momentum * state["velocity"] + g.astype(jnp.float32)
        if self._nesterov:
            upd = g.astype(jnp.float32) + self._momentum * v
        else:
            upd = v
        return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), {"velocity": v}


class Adam(Optimizer):
    """``moment_dtype="bfloat16"`` stores both moments in bf16 (HBM halved
    for optimizer state — on one 16G v5e chip this is what lets a ~1B
    model train WITHOUT activation recompute; the update math stays f32)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, moment_dtype="float32", name=None,
                 **kwargs):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        try:
            md = jnp.dtype(
                jnp.bfloat16 if moment_dtype in ("bf16",) else moment_dtype
            )
        except TypeError:
            md = None
        if md not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
            raise ValueError(
                f"moment_dtype must be float32 or bfloat16, got {moment_dtype!r}"
            )
        self._moment_dtype = md

    def _init_state(self, p_value):
        return {
            "moment1": jnp.zeros(p_value.shape, self._moment_dtype),
            "moment2": jnp.zeros(p_value.shape, self._moment_dtype),
        }

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        m = (self._beta1 * state["moment1"].astype(jnp.float32)
             + (1 - self._beta1) * g32)
        v = (self._beta2 * state["moment2"].astype(jnp.float32)
             + (1 - self._beta2) * jnp.square(g32))
        t = step.astype(jnp.float32)
        m_hat = m / (1 - self._beta1**t)
        v_hat = v / (1 - self._beta2**t)
        new_p = p.astype(jnp.float32) - lr * m_hat / (jnp.sqrt(v_hat) + self._eps)
        md = self._moment_dtype
        return new_p.astype(p.dtype), {
            "moment1": m.astype(md), "moment2": v.astype(md),
        }


class AdamW(Adam):
    _decoupled_wd = True

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False,
                 moment_dtype="float32", name=None, **kwargs):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         moment_dtype=moment_dtype)
        self._apply_decay_param_fun = apply_decay_param_fun

    def _decay_enabled(self, param) -> bool:
        if self._apply_decay_param_fun is None:
            return True
        return bool(self._apply_decay_param_fun(param.name))


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _init_state(self, p_value):
        return {
            "moment": jnp.zeros(p_value.shape, jnp.float32),
            "inf_norm": jnp.zeros(p_value.shape, jnp.float32),
        }

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        m = self._beta1 * state["moment"] + (1 - self._beta1) * g32
        u = jnp.maximum(self._beta2 * state["inf_norm"], jnp.abs(g32))
        t = step.astype(jnp.float32)
        new_p = p.astype(jnp.float32) - (lr / (1 - self._beta1**t)) * m / (u + self._eps)
        return new_p.astype(p.dtype), {"moment": m, "inf_norm": u}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _init_state(self, p_value):
        return {"moment": jnp.full(p_value.shape, self._init_acc, jnp.float32)}

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        acc = state["moment"] + jnp.square(g32)
        new_p = p.astype(jnp.float32) - lr * g32 / (jnp.sqrt(acc) + self._eps)
        return new_p.astype(p.dtype), {"moment": acc}


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._eps, self._rho = epsilon, rho

    def _init_state(self, p_value):
        return {
            "avg_squared_grad": jnp.zeros(p_value.shape, jnp.float32),
            "avg_squared_update": jnp.zeros(p_value.shape, jnp.float32),
        }

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        asg = self._rho * state["avg_squared_grad"] + (1 - self._rho) * jnp.square(g32)
        upd = (
            jnp.sqrt(state["avg_squared_update"] + self._eps)
            / jnp.sqrt(asg + self._eps)
        ) * g32
        asu = self._rho * state["avg_squared_update"] + (1 - self._rho) * jnp.square(upd)
        new_p = p.astype(jnp.float32) - lr * upd
        return new_p.astype(p.dtype), {
            "avg_squared_grad": asg,
            "avg_squared_update": asu,
        }


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, False, name)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_state(self, p_value):
        st = {
            "mean_square": jnp.zeros(p_value.shape, jnp.float32),
            "momentum_acc": jnp.zeros(p_value.shape, jnp.float32),
        }
        if self._centered:
            st["mean_grad"] = jnp.zeros(p_value.shape, jnp.float32)
        return st

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        ms = self._rho * state["mean_square"] + (1 - self._rho) * jnp.square(g32)
        new_state = {"mean_square": ms}
        if self._centered:
            mg = self._rho * state["mean_grad"] + (1 - self._rho) * g32
            denom = jnp.sqrt(ms - jnp.square(mg) + self._eps)
            new_state["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._eps)
        mom = self._momentum * state["momentum_acc"] + lr * g32 / denom
        new_state["momentum_acc"] = mom
        new_p = p.astype(jnp.float32) - mom
        return new_p.astype(p.dtype), new_state


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._lamb_wd = lamb_weight_decay
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_state(self, p_value):
        return {
            "moment1": jnp.zeros(p_value.shape, jnp.float32),
            "moment2": jnp.zeros(p_value.shape, jnp.float32),
        }

    def _decay_enabled(self, param) -> bool:
        if self._exclude_fn is None:
            return True
        return not bool(self._exclude_fn(param))

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        m = self._beta1 * state["moment1"] + (1 - self._beta1) * g32
        v = self._beta2 * state["moment2"] + (1 - self._beta2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        m_hat = m / (1 - self._beta1**t)
        v_hat = v / (1 - self._beta2**t)
        wd = self._lamb_wd if decay else 0.0
        r = m_hat / (jnp.sqrt(v_hat) + self._eps) + wd * p32
        w_norm = jnp.linalg.norm(p32)
        r_norm = jnp.linalg.norm(r)
        trust = jnp.where(
            (w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0
        )
        new_p = p32 - lr * trust * r
        return new_p.astype(p.dtype), {"moment1": m, "moment2": v}


class LarsMomentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, parameters=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip, False, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay

    def _init_state(self, p_value):
        return {"velocity": jnp.zeros(p_value.shape, jnp.float32)}

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        p32 = p.astype(jnp.float32)
        w_norm = jnp.linalg.norm(p32)
        g_norm = jnp.linalg.norm(g32)
        local_lr = jnp.where(
            (w_norm > 0) & (g_norm > 0),
            self._lars_coeff * w_norm / (g_norm + self._lars_wd * w_norm + 1e-12),
            1.0,
        )
        v = self._momentum * state["velocity"] + local_lr * lr * (
            g32 + self._lars_wd * p32
        )
        return (p32 - v).astype(p.dtype), {"velocity": v}


class Rprop(Optimizer):
    """Resilient backprop (reference paddle.optimizer.Rprop): per-weight
    step sizes grown/shrunk by the sign agreement of successive grads."""

    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50.0),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._eta_minus, self._eta_plus = etas
        self._lr_min, self._lr_max = learning_rate_range

    def _init_state(self, p_value):
        return {
            "prev_grad": jnp.zeros(p_value.shape, jnp.float32),
            "step_size": jnp.full(p_value.shape, self.get_lr(),
                                  jnp.float32),
        }

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        sign = jnp.sign(g32 * state["prev_grad"])
        grow = jnp.where(sign > 0, self._eta_plus,
                         jnp.where(sign < 0, self._eta_minus, 1.0))
        step_size = jnp.clip(state["step_size"] * grow,
                             self._lr_min, self._lr_max)
        # on sign flip: revert grad (classic Rprop-): no step this round
        g_eff = jnp.where(sign < 0, 0.0, g32)
        new_p = p.astype(jnp.float32) - jnp.sign(g_eff) * step_size
        return new_p.astype(p.dtype), {
            "prev_grad": g_eff, "step_size": step_size,
        }


class NAdam(Adam):
    """Adam with Nesterov momentum and the reference's mu_t momentum-decay
    schedule (paddle.optimizer.NAdam, momentum_decay=0.004)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, name=None, **kwargs):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, **kwargs)
        self._psi = float(momentum_decay)

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        t = step.astype(jnp.float32)
        mu_t = self._beta1 * (1 - 0.5 * 0.96 ** (t * self._psi))
        mu_next = self._beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self._psi))
        # running products of mu (closed form since mu depends on t only)
        # approximate prod via stored scalar is avoided: use the paddle
        # recurrences with mu products tracked in state
        mu_prod = state.get(
            "mu_prod", jnp.ones((), jnp.float32)) * mu_t
        m = self._beta1 * state["moment1"].astype(jnp.float32) \
            + (1 - self._beta1) * g32
        v = self._beta2 * state["moment2"].astype(jnp.float32) \
            + (1 - self._beta2) * jnp.square(g32)
        m_hat = (mu_next * m / (1 - mu_prod * mu_next)
                 + (1 - mu_t) * g32 / (1 - mu_prod))
        v_hat = v / (1 - self._beta2 ** t)
        new_p = p.astype(jnp.float32) - lr * m_hat / (
            jnp.sqrt(v_hat) + self._eps)
        md = self._moment_dtype
        return new_p.astype(p.dtype), {
            "moment1": m.astype(md), "moment2": v.astype(md),
            "mu_prod": mu_prod,
        }

    def _init_state(self, p_value):
        st = super()._init_state(p_value)
        st["mu_prod"] = jnp.ones((), jnp.float32)
        return st


class RAdam(Adam):
    """Rectified Adam (reference paddle.optimizer.RAdam): warms up the
    adaptive term by the variance-rectification factor."""

    def _update(self, p, g, state, lr, step, decay=True):
        g32 = g.astype(jnp.float32)
        b1, b2 = self._beta1, self._beta2
        m = b1 * state["moment1"].astype(jnp.float32) + (1 - b1) * g32
        v = b2 * state["moment2"].astype(jnp.float32) + (1 - b2) * jnp.square(g32)
        t = step.astype(jnp.float32)
        m_hat = m / (1 - b1 ** t)
        rho_inf = 2.0 / (1 - b2) - 1.0
        rho_t = rho_inf - 2.0 * t * b2 ** t / (1 - b2 ** t)
        r = jnp.sqrt(((rho_t - 4) * (rho_t - 2) * rho_inf)
                     / jnp.maximum((rho_inf - 4) * (rho_inf - 2) * rho_t,
                                   1e-12))
        v_hat = jnp.sqrt(v / (1 - b2 ** t))
        adaptive = lr * r * m_hat / (v_hat + self._eps)
        plain = lr * m_hat
        new_p = p.astype(jnp.float32) - jnp.where(rho_t > 4.0, adaptive,
                                                  plain)
        md = self._moment_dtype
        return new_p.astype(p.dtype), {
            "moment1": m.astype(md), "moment2": v.astype(md),
        }


class ASGD(Optimizer):
    """Averaged SGD (reference paddle.optimizer.ASGD): SGD steps plus a
    running parameter average stored alongside the state."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._batch_num = max(int(batch_num), 1)

    def _init_state(self, p_value):
        return {
            "d": jnp.zeros(p_value.shape, jnp.float32),  # rolling grad sum
            "y": jnp.zeros(p_value.shape, jnp.float32),  # grad replaced
        }

    def _update(self, p, g, state, lr, step, decay=True):
        # reference recurrence: d <- d - y + g; y <- g; p -= lr * d / n
        g32 = g.astype(jnp.float32)
        d = state["d"] - state["y"] + g32
        n = jnp.minimum(step.astype(jnp.float32), float(self._batch_num))
        new_p = p.astype(jnp.float32) - lr * d / jnp.maximum(n, 1.0)
        return new_p.astype(p.dtype), {"d": d, "y": g32}


class LBFGS(Optimizer):
    """L-BFGS with closure-based step (reference paddle.optimizer.LBFGS).

    ``step(closure)`` re-evaluates loss+grads; the two-loop recursion
    over the last ``history_size`` (s, y) pairs runs as fused jnp ops on
    flattened parameters."""

    def __init__(self, learning_rate=1.0, max_iter=1, history_size=10,
                 tolerance_grad=1e-7, tolerance_change=1e-9,
                 parameters=None, line_search_fn=None, name=None):
        super().__init__(learning_rate, parameters, None, None, False, name)
        self.max_iter = max_iter
        self.history_size = history_size
        self.tol_grad = tolerance_grad
        self.tol_change = tolerance_change
        self._hist = []  # list of (s, y, rho) flattened
        self._prev = None  # (flat_params, flat_grad)

    def _flat(self, vals):
        return jnp.concatenate([v.astype(jnp.float32).reshape(-1)
                                for v in vals])

    def _unflat(self, flat, params):
        # must walk the SAME param subset the flat vector was built from
        # (frozen/no-grad params are excluded by step)
        out, off = [], 0
        for p in params:
            n = int(np.prod(p._value.shape)) if p._value.ndim else 1
            out.append(flat[off: off + n].reshape(p._value.shape))
            off += n
        return out

    def _direction(self, q):
        alphas = []
        for s, y, rho in reversed(self._hist):
            a = rho * jnp.dot(s, q)
            q = q - a * y
            alphas.append(a)
        if self._hist:
            s, y, _ = self._hist[-1]
            q = q * (jnp.dot(s, y) / jnp.maximum(jnp.dot(y, y), 1e-12))
        for (s, y, rho), a in zip(self._hist, reversed(alphas)):
            b = rho * jnp.dot(y, q)
            q = q + (a - b) * s
        return q

    def step(self, closure=None):
        if closure is None:
            raise ValueError("LBFGS.step requires a closure")
        loss = None
        for _ in range(self.max_iter):
            loss = closure()
            params = [p for p in (self._parameter_list or [])
                      if p.grad is not None]
            if not params:
                return loss
            flat_g = self._flat([p.grad._value for p in params])
            flat_p = self._flat([p._value for p in params])
            if float(jnp.max(jnp.abs(flat_g))) <= self.tol_grad:
                break
            if self._prev is not None:
                # curvature pair from the PREVIOUS accepted step
                s = flat_p - self._prev[0]
                y = flat_g - self._prev[1]
                sy = float(jnp.dot(s, y))
                if sy > 1e-10:
                    self._hist.append((s, y, 1.0 / sy))
                    if len(self._hist) > self.history_size:
                        self._hist.pop(0)
            # record the CURRENT point before stepping away from it
            self._prev = (flat_p, flat_g)
            d = -self._direction(flat_g)
            lr = self.get_lr()
            step_vec = lr * d
            if float(jnp.max(jnp.abs(step_vec))) <= self.tol_change:
                break
            new_flat = flat_p + step_vec
            for p, v in zip(params, self._unflat(new_flat, params)):
                p._value = v.astype(p._value.dtype)
        return loss

    def state_dict(self):
        out = super().state_dict()
        out["lbfgs_hist"] = [
            (np.asarray(s), np.asarray(y), r) for s, y, r in self._hist
        ]
        if self._prev is not None:
            out["lbfgs_prev"] = tuple(np.asarray(v) for v in self._prev)
        return out

    def set_state_dict(self, state):
        super().set_state_dict(state)
        self._hist = [
            (jnp.asarray(s), jnp.asarray(y), r)
            for s, y, r in state.get("lbfgs_hist", [])
        ]
        prev = state.get("lbfgs_prev")
        self._prev = tuple(jnp.asarray(v) for v in prev) if prev else None
