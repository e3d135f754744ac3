"""Norm + pooling layers (reference surface: python/paddle/nn/layer/norm.py,
pooling.py — unverified, SURVEY.md §0)."""
from __future__ import annotations

import jax.numpy as jnp

from .layers import Layer
from .. import functional as F
from .. import initializer as I
from ...core.tensor import Tensor

__all__ = [
    "BatchNorm", "BatchNorm1D", "BatchNorm2D", "BatchNorm3D", "SyncBatchNorm",
    "LayerNorm", "RMSNorm", "GroupNorm", "InstanceNorm1D", "InstanceNorm2D",
    "InstanceNorm3D", "LocalResponseNorm", "SpectralNorm",
    "MaxPool1D", "MaxPool2D", "MaxPool3D", "AvgPool1D", "AvgPool2D",
    "AvgPool3D", "AdaptiveAvgPool1D", "AdaptiveAvgPool2D", "AdaptiveAvgPool3D",
    "AdaptiveMaxPool1D", "AdaptiveMaxPool2D", "AdaptiveMaxPool3D",
]


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum, self._epsilon = momentum, epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = (
            self.create_parameter(
                (num_features,), attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
            if weight_attr is not False
            else None
        )
        self.bias = (
            self.create_parameter((num_features,), attr=bias_attr, is_bias=True)
            if bias_attr is not False
            else None
        )
        self.register_buffer("_mean", Tensor(jnp.zeros((num_features,), jnp.float32)))
        self.register_buffer("_variance", Tensor(jnp.ones((num_features,), jnp.float32)))

    def forward(self, x):
        return F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self._momentum,
            epsilon=self._epsilon, data_format=self._data_format,
            use_global_stats=self._use_global_stats,
        )

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}"


class BatchNorm(_BatchNormBase):
    """Legacy paddle.nn.BatchNorm(num_channels) API."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5,
                 param_attr=None, bias_attr=None, data_layout="NCHW",
                 use_global_stats=None, **kwargs):
        super().__init__(num_channels, momentum, epsilon, param_attr,
                         bias_attr, data_layout, use_global_stats)
        self._act = act

    def forward(self, x):
        out = super().forward(x)
        if self._act == "relu":
            out = F.relu(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCL",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format, use_global_stats)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCDHW",
                 use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr,
                         bias_attr, data_format, use_global_stats)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BN. Under GSPMD the batch axis is sharded and XLA
    computes global batch statistics automatically when the reduction spans
    the full array, so SyncBatchNorm == BatchNorm here (the reference needs
    explicit NCCL allreduce of stats; reference:
    python/paddle/nn/layer/norm.py SyncBatchNorm)."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self._normalized_shape = tuple(normalized_shape)
        self._epsilon = epsilon
        self.weight = (
            self.create_parameter(
                self._normalized_shape, attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
            if weight_attr is not False
            else None
        )
        self.bias = (
            self.create_parameter(
                self._normalized_shape, attr=bias_attr, is_bias=True
            )
            if bias_attr is not False
            else None
        )

    def forward(self, x):
        return F.layer_norm(
            x, self._normalized_shape, self.weight, self.bias, self._epsilon
        )

    def extra_repr(self):
        return f"normalized_shape={list(self._normalized_shape)}, epsilon={self._epsilon}"


class RMSNorm(Layer):
    """The Llama-family norm; routes to the Pallas kernel on TPU."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None, name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            (hidden_size,), attr=weight_attr,
            default_initializer=I.Constant(1.0),
        )

    def forward(self, x, row_axis=None):
        return F.rms_norm(x, self.weight, epsilon=self._epsilon,
                          row_axis=row_axis)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups, self._epsilon = num_groups, epsilon
        self._data_format = data_format
        self.weight = (
            self.create_parameter(
                (num_channels,), attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
            if weight_attr is not False
            else None
        )
        self.bias = (
            self.create_parameter((num_channels,), attr=bias_attr, is_bias=True)
            if bias_attr is not False
            else None
        )

    def forward(self, x):
        return F.group_norm(
            x, self._num_groups, self._epsilon, self.weight, self.bias,
            self._data_format,
        )


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._epsilon = epsilon
        self._data_format = data_format
        if weight_attr is not False:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr,
                default_initializer=I.Constant(1.0),
            )
            self.bias = self.create_parameter(
                (num_features,), attr=bias_attr, is_bias=True
            )
        else:
            self.weight = self.bias = None

    def forward(self, x):
        return F.instance_norm(
            x, weight=self.weight, bias=self.bias, eps=self._epsilon,
            data_format=self._data_format,
        )


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0,
                 data_format="NCHW", name=None):
        super().__init__()
        self._args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self._args)


class SpectralNorm(Layer):
    """Power-iteration spectral norm of a weight (reference:
    python/paddle/nn/layer/norm.py SpectralNorm)."""

    def __init__(self, weight_shape, dim=0, power_iters=1, epsilon=1e-12,
                 dtype="float32"):
        super().__init__()
        self._dim, self._power_iters, self._epsilon = dim, power_iters, epsilon
        h = weight_shape[dim]
        w = 1
        for i, s in enumerate(weight_shape):
            if i != dim:
                w *= s
        self.weight_u = self.create_parameter(
            (h,), default_initializer=I.Normal(0.0, 1.0)
        )
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter(
            (w,), default_initializer=I.Normal(0.0, 1.0)
        )
        self.weight_v.stop_gradient = True

    def forward(self, weight):
        from ...core.dispatch import apply
        import jax

        dim, eps, iters = self._dim, self._epsilon, self._power_iters
        u0, v0 = self.weight_u._value, self.weight_v._value

        def fn(w):
            mat = jnp.moveaxis(w, dim, 0).reshape(w.shape[dim], -1)
            u, v = u0, v0
            for _ in range(iters):
                v = mat.T @ u
                v = v / (jnp.linalg.norm(v) + eps)
                u = mat @ v
                u = u / (jnp.linalg.norm(u) + eps)
            sigma = u @ mat @ v
            return w / sigma

        return apply(fn, weight, op_name="spectral_norm")


# -- pooling layers ----------------------------------------------------------
def _pool_layer(fname, n, data_format_default):
    class _Pool(Layer):
        def __init__(self, kernel_size=None, stride=None, padding=0, **kwargs):
            super().__init__()
            self._kernel_size = kernel_size
            self._stride = stride
            self._padding = padding
            self._kwargs = {
                k: v for k, v in kwargs.items() if k not in ("name",)
            }

        def forward(self, x):
            return getattr(F, fname)(
                x, self._kernel_size, self._stride, self._padding, **self._kwargs
            )

    _Pool.__name__ = fname
    return _Pool


MaxPool1D = _pool_layer("max_pool1d", 1, "NCL")
MaxPool2D = _pool_layer("max_pool2d", 2, "NCHW")
MaxPool3D = _pool_layer("max_pool3d", 3, "NCDHW")
AvgPool1D = _pool_layer("avg_pool1d", 1, "NCL")
AvgPool2D = _pool_layer("avg_pool2d", 2, "NCHW")
AvgPool3D = _pool_layer("avg_pool3d", 3, "NCDHW")


def _adaptive_pool_layer(fname):
    class _Pool(Layer):
        def __init__(self, output_size, **kwargs):
            super().__init__()
            self._output_size = output_size
            self._kwargs = {k: v for k, v in kwargs.items() if k != "name"}

        def forward(self, x):
            return getattr(F, fname)(x, self._output_size, **self._kwargs)

    _Pool.__name__ = fname
    return _Pool


AdaptiveAvgPool1D = _adaptive_pool_layer("adaptive_avg_pool1d")
AdaptiveAvgPool2D = _adaptive_pool_layer("adaptive_avg_pool2d")
AdaptiveAvgPool3D = _adaptive_pool_layer("adaptive_avg_pool3d")
AdaptiveMaxPool1D = _adaptive_pool_layer("adaptive_max_pool1d")
AdaptiveMaxPool2D = _adaptive_pool_layer("adaptive_max_pool2d")
AdaptiveMaxPool3D = _adaptive_pool_layer("adaptive_max_pool3d")
