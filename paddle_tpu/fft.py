"""paddle.fft — discrete Fourier transforms (reference:
python/paddle/fft.py — unverified, SURVEY.md §0).

Thin dispatch-seam wrappers over ``jnp.fft``: XLA lowers FFTs natively
(TPU executes them on the VPU), and routing through ``apply`` gives the
tape autograd + AMP/nan-check for free. ``norm`` semantics follow the
reference ("backward" | "ortho" | "forward"), which match numpy's.
"""
from __future__ import annotations

import jax.numpy as jnp

from .tensor._helpers import apply, ensure_tensor, axes_arg

__all__ = [
    "fft", "ifft", "rfft", "irfft", "hfft", "ihfft",
    "fft2", "ifft2", "rfft2", "irfft2",
    "fftn", "ifftn", "rfftn", "irfftn",
    "fftfreq", "rfftfreq", "fftshift", "ifftshift",
]


def _norm(norm):
    if norm not in ("backward", "ortho", "forward"):
        raise ValueError(
            f"norm must be 'backward', 'ortho' or 'forward', got {norm!r}"
        )
    return norm


def _wrap1(jnp_fn, op_name):
    def op(x, n=None, axis=-1, norm="backward", name=None):
        x = ensure_tensor(x)
        nrm = _norm(norm)
        return apply(
            lambda v: jnp_fn(v, n=n, axis=axis, norm=nrm), x,
            op_name=op_name,
        )

    op.__name__ = op_name
    op.__doc__ = f"paddle.fft.{op_name}(x, n=None, axis=-1, norm='backward')"
    return op


def _wrap_nd(jnp_fn, op_name, default_axes):
    def op(x, s=None, axes=default_axes, norm="backward", name=None):
        x = ensure_tensor(x)
        ax = axes_arg(axes)
        nrm = _norm(norm)
        return apply(
            lambda v: jnp_fn(v, s=s, axes=ax, norm=nrm), x,
            op_name=op_name,
        )

    op.__name__ = op_name
    op.__doc__ = (
        f"paddle.fft.{op_name}(x, s=None, axes={default_axes}, "
        f"norm='backward')"
    )
    return op


fft = _wrap1(jnp.fft.fft, "fft")
ifft = _wrap1(jnp.fft.ifft, "ifft")
rfft = _wrap1(jnp.fft.rfft, "rfft")
irfft = _wrap1(jnp.fft.irfft, "irfft")
hfft = _wrap1(jnp.fft.hfft, "hfft")
ihfft = _wrap1(jnp.fft.ihfft, "ihfft")

fft2 = _wrap_nd(jnp.fft.fft2, "fft2", (-2, -1))
ifft2 = _wrap_nd(jnp.fft.ifft2, "ifft2", (-2, -1))
rfft2 = _wrap_nd(jnp.fft.rfft2, "rfft2", (-2, -1))
irfft2 = _wrap_nd(jnp.fft.irfft2, "irfft2", (-2, -1))
fftn = _wrap_nd(jnp.fft.fftn, "fftn", None)
ifftn = _wrap_nd(jnp.fft.ifftn, "ifftn", None)
rfftn = _wrap_nd(jnp.fft.rfftn, "rfftn", None)
irfftn = _wrap_nd(jnp.fft.irfftn, "irfftn", None)


def fftfreq(n, d=1.0, dtype=None, name=None):
    from .core.dtype import to_jax_dtype

    out = jnp.fft.fftfreq(int(n), d=float(d))
    if dtype is not None:
        out = out.astype(to_jax_dtype(dtype))
    return apply(lambda: out, op_name="fftfreq")


def rfftfreq(n, d=1.0, dtype=None, name=None):
    from .core.dtype import to_jax_dtype

    out = jnp.fft.rfftfreq(int(n), d=float(d))
    if dtype is not None:
        out = out.astype(to_jax_dtype(dtype))
    return apply(lambda: out, op_name="rfftfreq")


def fftshift(x, axes=None, name=None):
    x = ensure_tensor(x)
    ax = axes_arg(axes)
    return apply(lambda v: jnp.fft.fftshift(v, axes=ax), x, op_name="fftshift")


def ifftshift(x, axes=None, name=None):
    x = ensure_tensor(x)
    ax = axes_arg(axes)
    return apply(
        lambda v: jnp.fft.ifftshift(v, axes=ax), x, op_name="ifftshift"
    )
