"""paddle.signal — STFT / iSTFT (reference: python/paddle/signal.py —
unverified, SURVEY.md §0).

Framing/windowing/overlap-add are real-valued jnp ops on the tape; the
DFT itself routes through ``paddle.fft``."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .tensor._helpers import apply, ensure_tensor
from . import fft as _fft

__all__ = ["stft", "istft"]


def _frame(x, frame_length, hop_length):
    """(..., T) → (..., n_frames, frame_length)."""
    n = (x.shape[-1] - frame_length) // hop_length + 1
    idx = (jnp.arange(n)[:, None] * hop_length
           + jnp.arange(frame_length)[None, :])
    return x[..., idx]


def stft(x, n_fft, hop_length=None, win_length=None, window=None,
         center=True, pad_mode="reflect", normalized=False, onesided=True,
         name=None):
    """Returns (..., n_fft//2 + 1, n_frames) complex (onesided) like the
    reference."""
    x = ensure_tensor(x)
    hop_length = hop_length or n_fft // 4
    win_length = win_length or n_fft
    if window is not None:
        window = ensure_tensor(window)

    def padded_window(w, dtype):
        # reference: window=None is a RECTANGULAR window of win_length,
        # zero-padded and centered in the n_fft frame
        if w is None:
            w = jnp.ones((win_length,), dtype)
        if win_length < n_fft:
            lp = (n_fft - win_length) // 2
            w = jnp.pad(w, (lp, n_fft - win_length - lp))
        return w

    def prep(v, *maybe_w):
        if center:
            pad = n_fft // 2
            v = jnp.pad(
                v, [(0, 0)] * (v.ndim - 1) + [(pad, pad)], mode=pad_mode
            )
        frames = _frame(v, n_fft, hop_length)  # (..., n_frames, n_fft)
        frames = frames * padded_window(
            maybe_w[0] if maybe_w else None, frames.dtype
        )
        if normalized:
            frames = frames / jnp.sqrt(jnp.asarray(n_fft, frames.dtype))
        return frames

    args = [x] + ([window] if window is not None else [])
    frames = apply(prep, *args, op_name="stft_frames")
    spec = (_fft.rfft(frames, axis=-1) if onesided
            else _fft.fft(frames, axis=-1))
    # (..., n_frames, F) → (..., F, n_frames)
    perm = list(range(spec.ndim))
    perm[-1], perm[-2] = perm[-2], perm[-1]
    return spec.transpose(perm)


def istft(x, n_fft, hop_length=None, win_length=None, window=None,
          center=True, normalized=False, onesided=True, length=None,
          return_complex=False, name=None):
    x = ensure_tensor(x)
    hop_length = hop_length or n_fft // 4
    win_length = win_length or n_fft
    if return_complex and onesided:
        raise ValueError(
            "istft: return_complex=True requires onesided=False"
        )
    perm = list(range(x.ndim))
    perm[-1], perm[-2] = perm[-2], perm[-1]
    spec = x.transpose(perm)  # (..., n_frames, F)
    if onesided:
        frames = _fft.irfft(spec, n=n_fft, axis=-1)
    else:
        cframes = _fft.ifft(spec, axis=-1)
        frames = cframes if return_complex else cframes.real()
    if window is not None:
        window = ensure_tensor(window)

    def ola(fr, *maybe_w):
        if normalized:
            fr = fr * jnp.sqrt(jnp.asarray(n_fft, fr.dtype))
        w = maybe_w[0] if maybe_w else jnp.ones(
            (win_length,),
            fr.dtype if not jnp.iscomplexobj(fr) else jnp.float32,
        )
        if win_length < n_fft:
            lp = (n_fft - win_length) // 2
            w = jnp.pad(w, (lp, n_fft - win_length - lp))
        n_frames = fr.shape[-2]
        t_len = n_fft + hop_length * (n_frames - 1)
        out = jnp.zeros(fr.shape[:-2] + (t_len,), fr.dtype)
        norm = jnp.zeros((t_len,), fr.dtype)
        for i in range(n_frames):  # unrolled overlap-add (static frames)
            sl = slice(i * hop_length, i * hop_length + n_fft)
            out = out.at[..., sl].add(fr[..., i, :] * w)
            norm = norm.at[sl].add(w * w)
        out = out / jnp.maximum(norm, 1e-10)
        if center:
            out = out[..., n_fft // 2: t_len - n_fft // 2]
        return out

    args = [frames] + ([window] if window is not None else [])
    out = apply(ola, *args, op_name="istft_ola")
    if length is not None:
        out = out[..., :length]
    return out
