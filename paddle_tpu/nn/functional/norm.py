"""Normalization functionals (reference surface:
python/paddle/nn/functional/norm.py and the rms_norm fusion kernel
paddle/phi/kernels/fusion/gpu/rms_norm_kernel.cu — unverified, SURVEY.md §0).

``rms_norm`` routes to the Pallas kernel on TPU when
FLAGS_use_pallas_kernels is set; elsewhere the jnp path is used (XLA
fuses it fully).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...tensor._helpers import Tensor, apply, ensure_tensor
from ...ops.pallas.rms_norm import rms_norm as _pallas_rms_norm


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    x = ensure_tensor(x)
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(tuple(normalized_shape))
    axes = tuple(range(x.ndim - n_axes, x.ndim))

    def fn(v, *wb):
        mean = jnp.mean(v.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(v.astype(jnp.float32), axis=axes, keepdims=True)
        out = (v.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(v.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args, op_name="layer_norm")


def rms_norm(x, weight=None, bias=None, epsilon=1e-6, begin_norm_axis=-1,
             name=None, row_axis=None):
    """RMSNorm over dims [begin_norm_axis:]; the hot path of Llama-family
    models. Routes to the Pallas kernel (normalized dims flattened to one
    feature axis) on TPU; the XLA path serves other backends and the
    weightless / biased forms the kernel does not take. ``row_axis``: the
    mesh axis the caller keeps dim 1 of ``x`` split over, where it does
    (a sequence-split hidden stream): the kernel then runs on each
    member's own rows; the XLA path is partitioned from ``x``'s layout."""
    x = ensure_tensor(x)
    from ...core.flags import get_flags

    ndim = x.ndim
    axis0 = begin_norm_axis % ndim if begin_norm_axis is not None else ndim - 1
    norm_axes = tuple(range(axis0, ndim))

    flags = get_flags(["FLAGS_use_pallas_kernels", "FLAGS_pallas_force"])
    use_pallas = flags["FLAGS_use_pallas_kernels"] and (
        jax.default_backend() == "tpu" or flags["FLAGS_pallas_force"]
    )
    if use_pallas and weight is not None and bias is None:
        # no fallback: a kernel the compiler refuses fails the call
        def pk(v, w):
            # flatten the normalized dims into one feature axis
            lead = v.shape[:axis0]
            out = _pallas_rms_norm(
                v.reshape(*lead, -1), w.reshape(-1), epsilon,
                row_axis=row_axis)
            return out.reshape(v.shape)

        return apply(pk, x, ensure_tensor(weight), op_name="rms_norm")

    def fn(v, *wb):
        var = jnp.mean(
            jnp.square(v.astype(jnp.float32)), axis=norm_axes, keepdims=True)
        out = (v.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)).astype(v.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(v.shape[axis0:])
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(v.shape[axis0:])
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args, op_name="rms_norm")


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """BatchNorm. In training mode the running stats TENSORS are updated
    in-place (buffer rebind), matching paddle's mutable running stats."""
    x = ensure_tensor(x)
    running_mean = ensure_tensor(running_mean)
    running_var = ensure_tensor(running_var)
    ch_axis = 1 if data_format.startswith("NC") and x.ndim > 1 else x.ndim - 1
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    track = use_global_stats if use_global_stats is not None else not training

    def stats_fn(v):
        mean = jnp.mean(v.astype(jnp.float32), axis=reduce_axes)
        var = jnp.var(v.astype(jnp.float32), axis=reduce_axes)
        return mean, var

    if track:
        mean_t, var_t = running_mean, running_var
    else:
        with_stats = apply(stats_fn, x, op_name="batch_norm_stats")
        mean_t, var_t = with_stats
        # update running stats in place (paddle: r = m*r + (1-m)*batch)
        import jax as _jax

        n = 1
        for i in reduce_axes:
            n *= x.shape[i]
        unbiased = var_t * (n / max(n - 1, 1))
        running_mean._value = (
            momentum * running_mean._value
            + (1 - momentum) * mean_t._value.astype(running_mean._value.dtype)
        )
        running_var._value = (
            momentum * running_var._value
            + (1 - momentum) * unbiased._value.astype(running_var._value.dtype)
        )

    def norm_fn(v, m, var_, *wb):
        shape = [1] * v.ndim
        shape[ch_axis] = -1
        out = (v - m.reshape(shape)) * jax.lax.rsqrt(
            var_.reshape(shape) + epsilon
        )
        out = out.astype(v.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x, mean_t, var_t]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(norm_fn, *args, op_name="batch_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    x = ensure_tensor(x)
    ch_axis = 1 if data_format.startswith("NC") else x.ndim - 1
    reduce_axes = tuple(i for i in range(2, x.ndim)) if ch_axis == 1 else tuple(
        i for i in range(1, x.ndim - 1)
    )

    def fn(v, *wb):
        mean = jnp.mean(v, axis=reduce_axes, keepdims=True)
        var = jnp.var(v, axis=reduce_axes, keepdims=True)
        out = (v - mean) * jax.lax.rsqrt(var + eps)
        shape = [1] * v.ndim
        shape[ch_axis] = -1
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args, op_name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channels_last = not data_format.startswith("NC")

    def fn(v, *wb):
        if channels_last:
            v_ = jnp.moveaxis(v, -1, 1)
        else:
            v_ = v
        n, c = v_.shape[:2]
        spatial = v_.shape[2:]
        g = v_.reshape((n, num_groups, c // num_groups) + spatial)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(v_.shape)
        shape = [1, c] + [1] * len(spatial)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        if channels_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args, op_name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = ensure_tensor(x)

    def fn(v):
        ch_axis = 1 if data_format.startswith("NC") else v.ndim - 1
        sq = jnp.square(v)
        moved = jnp.moveaxis(sq, ch_axis, -1)
        pad_l = (size - 1) // 2
        pad_r = size - 1 - pad_l
        padded = jnp.pad(
            moved, [(0, 0)] * (moved.ndim - 1) + [(pad_l, pad_r)]
        )
        win = jnp.stack(
            [padded[..., i : i + moved.shape[-1]] for i in range(size)], axis=0
        ).sum(axis=0)
        div = jnp.power(k + alpha * win, beta)
        return v / jnp.moveaxis(div, -1, ch_axis)

    return apply(fn, x, op_name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return apply(
        lambda v: v
        / jnp.maximum(
            jnp.linalg.norm(v, ord=p, axis=axis, keepdims=True), epsilon
        ),
        ensure_tensor(x),
        op_name="normalize",
    )


__all__ = [
    "layer_norm", "rms_norm", "batch_norm", "instance_norm", "group_norm",
    "local_response_norm", "normalize",
]
