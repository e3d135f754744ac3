"""AMP: auto_cast + GradScaler (reference: python/paddle/amp/ — unverified,
SURVEY.md §0).

``auto_cast`` flips a global mode consulted by the dispatch seam: O1 casts
white-listed ops (matmul/conv — the MXU ops) to the amp dtype and keeps
black-listed ops in fp32; O2 casts everything but the black list. On TPU
the natural amp dtype is bfloat16 (no loss scaling needed); fp16 +
GradScaler is kept for API parity.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.dtype import to_jax_dtype
from ..core.tensor import Tensor
from ..core import autograd

__all__ = ["auto_cast", "amp_guard", "GradScaler", "decorate", "amp_state"]

WHITE_LIST = {
    "matmul", "mm", "bmm", "mv", "linear", "conv1d", "conv2d", "conv3d",
    "conv1d_transpose", "conv2d_transpose", "conv3d_transpose", "einsum",
    "addmm", "flash_attention", "scaled_dot_product_attention",
    # mp_layers' sequence-split half-layers: linear's products
    "column_parallel_group", "row_parallel_scatter",
}
BLACK_LIST = {
    "exp", "log", "log2", "log10", "log1p", "expm1", "pow", "square",
    "softmax", "log_softmax", "cross_entropy", "softmax_with_cross_entropy",
    "layer_norm", "batch_norm", "instance_norm", "group_norm", "rms_norm",
    "mean", "sum", "cumsum", "logsumexp", "norm", "dist", "cosine_similarity",
    "sigmoid_focal_loss", "bce", "bce_with_logits", "kl_div", "nll_loss",
    "mse_loss", "l1_loss", "smooth_l1",
}


class _AmpState:
    enabled = False
    dtype = jnp.float16
    level = "O1"
    custom_white: set = set()
    custom_black: set = set()


amp_state = _AmpState()


def _known_op_names():
    """Registry names plus bare seam aliases (`functional.relu` → also
    `relu`): AMP lists traditionally use the bare op name."""
    from ..core.dispatch import OP_REGISTRY, SEAM_OPS

    names = set(OP_REGISTRY) | set(SEAM_OPS)
    names.update(n.rsplit(".", 1)[-1] for n in OP_REGISTRY)
    # built-in list entries are valid by definition (some are seam names
    # only recorded at first execution)
    names.update(WHITE_LIST)
    names.update(BLACK_LIST)
    return names


def cast_inputs_for_op(op_name, vals):
    """Called from dispatch.apply when amp is on; casts float arrays."""
    st = amp_state
    white = (op_name in WHITE_LIST or op_name in st.custom_white)
    black = (op_name in BLACK_LIST or op_name in st.custom_black) and not (
        op_name in st.custom_white
    )

    def cast_to(v, dt):
        if hasattr(v, "dtype") and jnp.issubdtype(
            jnp.asarray(v).dtype, jnp.floating
        ):
            if jnp.asarray(v).dtype != dt:
                return jnp.asarray(v).astype(dt)
        return v

    if st.level == "O2":
        if black:
            return [cast_to(v, jnp.float32) for v in vals]
        return [cast_to(v, st.dtype) for v in vals]
    # O1
    if white:
        return [cast_to(v, st.dtype) for v in vals]
    if black:
        return [cast_to(v, jnp.float32) for v in vals]
    return vals


class auto_cast:
    """paddle.amp.auto_cast context manager."""

    def __init__(self, enable=True, custom_white_list=None,
                 custom_black_list=None, level="O1", dtype="float16",
                 use_promote=True):
        self._enable = enable
        self._white = set(custom_white_list or ())
        self._black = set(custom_black_list or ())
        self._level = level
        self._dtype = to_jax_dtype(dtype)
        # custom lists key on registered op names (the kernel-registry
        # analog); an unknown name would silently never match — warn.
        # Skip entirely for the plain (no custom lists) hot path.
        unknown = ((self._white | self._black) - _known_op_names()
                   if (self._white or self._black) else ())
        if unknown:
            import warnings

            warnings.warn(
                f"auto_cast: op names not (yet) in the op registry: "
                f"{sorted(unknown)}. A dispatch-seam op name will still "
                f"match once that op runs; check "
                f"paddle.utils.get_registered_ops() for known names.",
                RuntimeWarning,
            )

    def __enter__(self):
        self._saved = (
            amp_state.enabled, amp_state.dtype, amp_state.level,
            amp_state.custom_white, amp_state.custom_black,
        )
        amp_state.enabled = self._enable
        amp_state.dtype = self._dtype
        amp_state.level = self._level
        amp_state.custom_white = self._white
        amp_state.custom_black = self._black
        return self

    def __exit__(self, *exc):
        (
            amp_state.enabled, amp_state.dtype, amp_state.level,
            amp_state.custom_white, amp_state.custom_black,
        ) = self._saved
        return False


amp_guard = auto_cast


def decorate(models, optimizers=None, level="O1", dtype="float16",
             master_weight=None, save_dtype=None):
    """paddle.amp.decorate: O2 casts model params to the amp dtype."""
    single_model = not isinstance(models, (list, tuple))
    model_list = [models] if single_model else list(models)
    if level == "O2":
        for m in model_list:
            m.astype(dtype)
    if optimizers is None:
        return models
    return models, optimizers


class GradScaler:
    """Dynamic loss scaling (reference: python/paddle/amp/grad_scaler.py).

    On TPU-with-bf16 the scale stays 1.0 and this is a pass-through; full
    dynamic scaling is implemented for fp16 parity.
    """

    def __init__(self, enable=True, init_loss_scaling=2.0**15, incr_ratio=2.0,
                 decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling) if enable else 1.0
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        self._unscaled = False  # set by unscale_, cleared by step/update

    def scale(self, loss):
        if not self._enable:
            return loss
        return loss * self._scale

    def unscale_(self, optimizer):
        if not self._enable or self._unscaled:
            return
        self._unscaled = True
        import numpy as np

        inv = 1.0 / self._scale
        found_inf = False
        for p in optimizer._parameter_list or []:
            if p.grad is not None:
                g = p.grad._value * inv
                p.grad._value = g
        # single fused finiteness check
        import jax

        vals = [
            p.grad._value
            for p in optimizer._parameter_list or []
            if p.grad is not None
        ]
        if vals:
            finite = all(bool(jnp.all(jnp.isfinite(v))) for v in vals)
            found_inf = not finite
        self._found_inf = found_inf

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)  # no-op if the user already unscaled
        self._unscaled = False
        if not self._found_inf:
            optimizer.step()

    def update(self):
        if not (self._enable and self._dynamic):
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        self.update()
        optimizer.clear_grad()

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_loss_scaling(self):
        return Tensor(jnp.asarray(self._scale))

    def set_init_loss_scaling(self, v):
        self._scale = float(v)

    def state_dict(self):
        return {
            "scale": self._scale,
            "incr_ratio": self._incr_ratio,
            "decr_ratio": self._decr_ratio,
            "incr_count": self._good_steps,
            "decr_count": self._bad_steps,
        }

    def load_state_dict(self, state):
        self._scale = state.get("scale", self._scale)
        self._good_steps = state.get("incr_count", 0)
        self._bad_steps = state.get("decr_count", 0)
