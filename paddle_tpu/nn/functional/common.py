"""Common functionals: linear/embedding/dropout/interpolate/... (reference
surface: python/paddle/nn/functional/common.py, input.py — unverified,
SURVEY.md §0)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...tensor._helpers import Tensor, apply, ensure_tensor
from ...tensor.manipulation import pad, unfold  # re-export paddle F.pad  # noqa: F401
from ...core.random import next_key


def linear_values(v, w, *maybe_b):
    """``linear`` on jax values: half-precision products accumulate in
    float32 and come back in ``v``'s dtype."""
    pet = jnp.float32 if v.dtype in (jnp.bfloat16, jnp.float16) else None
    out = jnp.matmul(v, w, preferred_element_type=pet)
    if pet is not None:
        out = out.astype(v.dtype)
    if maybe_b:
        out = out + maybe_b[0].astype(out.dtype)
    return out


def linear(x, weight, bias=None, name=None):
    """paddle weight layout: (in_features, out_features) — x @ W + b."""
    args = [ensure_tensor(x), ensure_tensor(weight)]
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(linear_values, *args, op_name="linear")


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    x, weight = ensure_tensor(x), ensure_tensor(weight)

    def fn(ids, w):
        out = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    return apply(fn, x, weight, op_name="embedding")


def one_hot(x, num_classes, name=None):
    from ...tensor.creation import one_hot as _oh

    return _oh(x, num_classes)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    x = ensure_tensor(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply(lambda v: v * (1.0 - p), x, op_name="dropout_infer")
        return x
    if p == 1.0:
        return apply(lambda v: jnp.zeros_like(v), x, op_name="dropout")
    key = next_key()

    def fn(v):
        shape = list(v.shape)
        if axis is not None:
            axes = (axis,) if isinstance(axis, int) else tuple(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, v / (1.0 - p), 0.0).astype(v.dtype)
        return jnp.where(keep, v, 0.0).astype(v.dtype)

    return apply(fn, x, op_name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ax = (2, 3) if data_format == "NCHW" else (1, 2)
    inv = tuple(i for i in range(4) if i not in ax)
    # drop whole channels: mask broadcast over spatial dims
    return dropout(x, p, axis=inv, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ax = (2, 3, 4) if data_format == "NCDHW" else (1, 2, 3)
    inv = tuple(i for i in range(5) if i not in ax)
    return dropout(x, p, axis=inv, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = ensure_tensor(x)
    if not training or p == 0.0:
        return x
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    key = next_key()

    def fn(v):
        keep = jax.random.bernoulli(key, 1.0 - p, v.shape)
        a = (1.0 / ((1 - p) * (1 + p * alpha_p**2)) ** 0.5)
        b = -a * alpha_p * p
        return (a * jnp.where(keep, v, alpha_p) + b).astype(v.dtype)

    return apply(fn, x, op_name="alpha_dropout")


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    return apply(
        lambda a, b: jnp.sum(a * b, axis=axis)
        / jnp.maximum(
            jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis), eps
        ),
        ensure_tensor(x1),
        ensure_tensor(x2),
        op_name="cosine_similarity",
    )


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    x = ensure_tensor(x)
    r = upscale_factor

    def fn(v):
        if data_format == "NCHW":
            n, c, h, w = v.shape
            v = v.reshape(n, c // (r * r), r, r, h, w)
            v = v.transpose(0, 1, 4, 2, 5, 3)
            return v.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = v.shape
        v = v.reshape(n, h, w, r, r, c // (r * r))
        v = v.transpose(0, 1, 3, 2, 4, 5)
        return v.reshape(n, h * r, w * r, c // (r * r))

    return apply(fn, x, op_name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    x = ensure_tensor(x)
    r = downscale_factor

    def fn(v):
        n, c, h, w = v.shape
        v = v.reshape(n, c, h // r, r, w // r, r)
        v = v.transpose(0, 1, 3, 5, 2, 4)
        return v.reshape(n, c * r * r, h // r, w // r)

    return apply(fn, x, op_name="pixel_unshuffle")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    x = ensure_tensor(x)

    def fn(v):
        n, c, h, w = v.shape
        v = v.reshape(n, groups, c // groups, h, w)
        return v.transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)

    return apply(fn, x, op_name="channel_shuffle")


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channels_last = not data_format.startswith("NC")
    n_spatial = x.ndim - 2
    in_spatial = (
        x.shape[1:-1] if channels_last else x.shape[2:]
    )
    if size is not None:
        if isinstance(size, Tensor):
            size = size.tolist()
        out_spatial = tuple(int(s) for s in (size if isinstance(size, (list, tuple)) else [size]))
    else:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else [scale_factor] * n_spatial
        out_spatial = tuple(int(d * f) for d, f in zip(in_spatial, sf))

    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def fn(v):
        if channels_last:
            out_shape = (v.shape[0],) + out_spatial + (v.shape[-1],)
        else:
            out_shape = v.shape[:2] + out_spatial
        if mode == "nearest":
            # paddle nearest uses floor(i * scale) source indexing
            idx = []
            for d in range(n_spatial):
                axis_len = in_spatial[d]
                out_len = out_spatial[d]
                scale = axis_len / out_len
                ii = jnp.floor(jnp.arange(out_len) * scale).astype(jnp.int32)
                idx.append(jnp.clip(ii, 0, axis_len - 1))
            out = v
            for d in range(n_spatial):
                ax = (1 if channels_last else 2) + d
                out = jnp.take(out, idx[d], axis=ax)
            return out
        if align_corners:
            # jax.image has no align_corners; do explicit linear gather
            out = v
            for d in range(n_spatial):
                ax = (1 if channels_last else 2) + d
                in_len, out_len = in_spatial[d], out_spatial[d]
                if out_len == 1 or in_len == 1:
                    pos = jnp.zeros((out_len,), jnp.float32)
                else:
                    pos = jnp.arange(out_len) * (in_len - 1) / (out_len - 1)
                lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, in_len - 1)
                hi = jnp.clip(lo + 1, 0, in_len - 1)
                t = (pos - lo).astype(v.dtype)
                shape = [1] * out.ndim
                shape[ax] = -1
                out = jnp.take(out, lo, axis=ax) * (1 - t.reshape(shape)) + jnp.take(
                    out, hi, axis=ax
                ) * t.reshape(shape)
            return out
        return jax.image.resize(v, out_shape, method=jmode).astype(v.dtype)

    return apply(fn, x, op_name="interpolate")


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    label = ensure_tensor(label)

    def fn(y, *maybe_p):
        k = y.shape[-1]
        if maybe_p:
            return (1 - epsilon) * y + epsilon * maybe_p[0]
        return (1 - epsilon) * y + epsilon / k

    args = [label]
    if prior_dist is not None:
        args.append(ensure_tensor(prior_dist))
    return apply(fn, *args, op_name="label_smooth")


def bilinear(x1, x2, weight, bias=None, name=None):
    def fn(a, b, w, *maybe_b):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if maybe_b:
            out = out + maybe_b[0]
        return out

    args = [ensure_tensor(x1), ensure_tensor(x2), ensure_tensor(weight)]
    if bias is not None:
        args.append(ensure_tensor(bias))
    return apply(fn, *args, op_name="bilinear")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """col2im — inverse of unfold."""
    x = ensure_tensor(x)

    def _pair(v):
        return (v, v) if isinstance(v, int) else tuple(v)

    oh, ow = _pair(output_sizes)
    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    dh, dw = _pair(dilations)
    if isinstance(paddings, int):
        pt = pb = pl_ = pr = paddings
    elif len(paddings) == 2:
        pt = pb = paddings[0]
        pl_ = pr = paddings[1]
    else:
        pt, pl_, pb, pr = paddings

    def fn(v):
        n, ckk, L = v.shape
        c = ckk // (kh * kw)
        out_h = (oh + pt + pb - (dh * (kh - 1) + 1)) // sh + 1
        out_w = (ow + pl_ + pr - (dw * (kw - 1) + 1)) // sw + 1
        cols = v.reshape(n, c, kh, kw, out_h, out_w)
        out = jnp.zeros((n, c, oh + pt + pb, ow + pl_ + pr), v.dtype)
        for i in range(kh):
            for j in range(kw):
                hi = i * dh
                wj = j * dw
                out = out.at[
                    :, :, hi : hi + out_h * sh : sh, wj : wj + out_w * sw : sw
                ].add(cols[:, :, i, j])
        return out[:, :, pt : pt + oh, pl_ : pl_ + ow]

    return apply(fn, x, op_name="fold")


def class_center_sample(label, num_classes, num_samples, group=None):
    """paddle.nn.functional.class_center_sample (PLSC margin-softmax
    helper): keep every positive class, top up with uniformly sampled
    negatives to ``num_samples``, and remap labels into the sampled
    index space. Returns (remapped_label, sampled_class_center).

    Output size is data-dependent (|positives| may exceed num_samples),
    so this is an EAGER op — the margin-softmax training loop calls it
    on host-side label batches, like the reference's GPU op driven from
    the python layer."""
    import numpy as np

    label = ensure_tensor(label)
    if isinstance(label._value, jax.core.Tracer):
        raise ValueError(
            "class_center_sample has data-dependent output shapes and "
            "cannot run under jit tracing; call it eagerly on the label "
            "batch")
    lab = np.asarray(label._value).reshape(-1)
    pos = np.unique(lab)
    if pos.size >= num_samples:
        sampled = pos
    else:
        from ...core.random import next_key

        neg_pool = np.setdiff1d(np.arange(num_classes), pos,
                                assume_unique=True)
        k = int(jax.random.key_data(next_key())[-1])
        perm = np.random.RandomState(k % (2 ** 31)).permutation(neg_pool)
        sampled = np.concatenate(
            [pos, perm[: num_samples - pos.size]])
    remap = np.full((num_classes,), -1, np.int64)
    remap[sampled] = np.arange(sampled.size)
    from ...core.tensor import Tensor

    return (Tensor(jnp.asarray(remap[lab].reshape(label.shape))),
            Tensor(jnp.asarray(sampled)))


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    x = ensure_tensor(x)

    def fn(v):
        nt, c, h, w = v.shape
        n = nt // seg_num
        v = v.reshape(n, seg_num, c, h, w)
        fold_c = int(c * shift_ratio)
        left = jnp.concatenate(
            [v[:, 1:, :fold_c], jnp.zeros_like(v[:, :1, :fold_c])], axis=1
        )
        right = jnp.concatenate(
            [jnp.zeros_like(v[:, :1, fold_c : 2 * fold_c]), v[:, :-1, fold_c : 2 * fold_c]],
            axis=1,
        )
        out = jnp.concatenate([left, right, v[:, :, 2 * fold_c :]], axis=2)
        return out.reshape(nt, c, h, w)

    return apply(fn, x, op_name="temporal_shift")


__all__ = [
    "linear", "embedding", "one_hot", "dropout", "dropout2d", "dropout3d",
    "alpha_dropout", "cosine_similarity", "pixel_shuffle", "pixel_unshuffle",
    "channel_shuffle", "interpolate", "upsample", "label_smooth", "bilinear",
    "pad", "unfold", "fold", "temporal_shift", "class_center_sample",
    "affine_grid", "grid_sample",
]


def affine_grid(theta, out_shape, align_corners=True, name=None):
    """2-D affine sampling grid (reference paddle.nn.functional.
    affine_grid): theta (N, 2, 3) → grid (N, H, W, 2) in [-1, 1]."""
    theta = ensure_tensor(theta)
    if isinstance(out_shape, Tensor):
        out_shape = out_shape.tolist()
    n, c, h, w = [int(s) for s in out_shape]

    def fn(th):
        def axis_coords(size):
            if align_corners:
                return jnp.linspace(-1.0, 1.0, size)
            step = 2.0 / size
            return jnp.linspace(-1.0 + step / 2, 1.0 - step / 2, size)

        ys = axis_coords(h)
        xs = axis_coords(w)
        gx, gy = jnp.meshgrid(xs, ys)  # (H, W)
        base = jnp.stack(
            [gx, gy, jnp.ones_like(gx)], axis=-1)  # (H, W, 3)
        # (N, 2, 3) @ (H*W, 3)^T → (N, H*W, 2)
        out = jnp.einsum("nij,hwj->nhwi", th.astype(jnp.float32), base)
        return out

    return apply(fn, theta, op_name="affine_grid")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    """Sample NCHW features at normalized grid locations (reference
    paddle.nn.functional.grid_sample); differentiable through the
    gathers."""
    x = ensure_tensor(x)
    grid = ensure_tensor(grid)
    if mode not in ("bilinear", "nearest"):
        raise ValueError(f"unsupported mode {mode!r}")
    if padding_mode not in ("zeros", "border", "reflection"):
        raise ValueError(f"unsupported padding_mode {padding_mode!r}")

    def fn(feat, g):
        n, c, h, w = feat.shape
        gx = g[..., 0].astype(jnp.float32)  # (N, Hg, Wg)
        gy = g[..., 1].astype(jnp.float32)
        if align_corners:
            fx = (gx + 1) * (w - 1) / 2
            fy = (gy + 1) * (h - 1) / 2
        else:
            fx = ((gx + 1) * w - 1) / 2
            fy = ((gy + 1) * h - 1) / 2

        def reflect(v, lo, hi):
            # triangular-wave reflection into [lo, hi]; in-range values
            # are fixed points
            rng = hi - lo
            if rng <= 0:
                return jnp.zeros_like(v)
            return rng - jnp.abs((v - lo) % (2 * rng) - rng) + lo

        if padding_mode == "reflection":
            if align_corners:  # reflect about pixel centers
                fx = reflect(fx, 0.0, float(w - 1))
                fy = reflect(fy, 0.0, float(h - 1))
            else:  # reference reflects about pixel boundaries
                fx = reflect(fx, -0.5, float(w) - 0.5)
                fy = reflect(fy, -0.5, float(h) - 0.5)

        def gather(iy, ix):
            iyc = jnp.clip(iy, 0, h - 1)
            ixc = jnp.clip(ix, 0, w - 1)
            out = jnp.take_along_axis(
                jnp.take_along_axis(
                    feat[:, :, :, None, :],  # (N,C,H,1,W)
                    iyc[:, None, None, :, :].astype(jnp.int32).reshape(
                        n, 1, 1, -1, 1), axis=2,  # size-1 C broadcasts
                ).squeeze(2),  # (N,C,Hg*Wg,W)
                ixc[:, None, :, :].astype(jnp.int32).reshape(
                    n, 1, -1, 1), axis=3,
            )[..., 0]  # (N, C, Hg*Wg)
            valid = ((iy >= 0) & (iy <= h - 1)
                     & (ix >= 0) & (ix <= w - 1))
            if padding_mode == "zeros":
                out = out * valid.reshape(n, 1, -1)
            return out

        hw = fx.shape[1] * fx.shape[2]
        if mode == "nearest":
            out = gather(jnp.round(fy), jnp.round(fx))
        else:
            x0 = jnp.floor(fx)
            y0 = jnp.floor(fy)
            wx = fx - x0
            wy = fy - y0
            v00 = gather(y0, x0)
            v01 = gather(y0, x0 + 1)
            v10 = gather(y0 + 1, x0)
            v11 = gather(y0 + 1, x0 + 1)
            wxf = wx.reshape(n, 1, hw)
            wyf = wy.reshape(n, 1, hw)
            out = ((1 - wyf) * ((1 - wxf) * v00 + wxf * v01)
                   + wyf * ((1 - wxf) * v10 + wxf * v11))
        return out.reshape(n, c, fx.shape[1], fx.shape[2]).astype(feat.dtype)

    return apply(fn, x, grid, op_name="grid_sample")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    """paddle.nn.functional.pairwise_distance: p-norm of (x - y + eps)."""
    def fn(a, b):
        d = jnp.abs(a - b + epsilon)
        if jnp.isinf(p):
            out = jnp.max(d, axis=-1, keepdims=keepdim)
        else:
            out = jnp.power(jnp.sum(jnp.power(d, p), axis=-1,
                                    keepdims=keepdim), 1.0 / p)
        return out

    return apply(fn, ensure_tensor(x), ensure_tensor(y),
                 op_name="pairwise_distance")


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    """paddle.nn.functional.sequence_mask: lengths → (…, maxlen) mask."""
    from ...core.dtype import to_jax_dtype

    x = ensure_tensor(x)
    if maxlen is None:
        if isinstance(x._value, jax.core.Tracer):
            raise ValueError(
                "sequence_mask(maxlen=None) needs the max length as a "
                "host value, which is unavailable while tracing "
                "(to_static/jit). Pass an explicit maxlen."
            )
        maxlen = int(jnp.max(x._value)) if x._value.size else 0
    jdt = to_jax_dtype(dtype)

    def fn(v):
        pos = jnp.arange(int(maxlen), dtype=v.dtype)
        return (pos < v[..., None]).astype(jdt)

    return apply(fn, x, op_name="sequence_mask")


def zeropad2d(x, padding, data_format="NCHW", name=None):
    """paddle.nn.functional.zeropad2d: [left, right, top, bottom]."""
    pl_, pr, pt, pb = (int(v) for v in padding)

    def fn(v):
        if data_format == "NCHW":
            cfg = ((0, 0), (0, 0), (pt, pb), (pl_, pr))
        else:  # NHWC
            cfg = ((0, 0), (pt, pb), (pl_, pr), (0, 0))
        return jnp.pad(v, cfg)

    return apply(fn, ensure_tensor(x), op_name="zeropad2d")


def gather_tree(ids, parents, name=None):
    """paddle.nn.functional.gather_tree: back-trace beam-search parent
    pointers. ids/parents: (T, B, W) → full sequences (T, B, W)."""
    def fn(idv, par):
        t = idv.shape[0]

        def body(carry, xs):
            beam = carry  # (B, W) beam index selected at step t+1
            ids_t, par_t = xs
            tok = jnp.take_along_axis(ids_t, beam, axis=1)
            prev = jnp.take_along_axis(par_t, beam, axis=1)
            return prev.astype(beam.dtype), tok

        init = jnp.broadcast_to(
            jnp.arange(idv.shape[2], dtype=idv.dtype), idv.shape[1:])
        _, toks = jax.lax.scan(
            body, init, (idv[::-1], par[::-1]))
        return toks[::-1]

    return apply(fn, ensure_tensor(ids), ensure_tensor(parents),
                 op_name="gather_tree")


__all__ += ["pairwise_distance", "sequence_mask", "zeropad2d", "gather_tree"]
