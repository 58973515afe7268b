"""Normalisation layers (reference: python/paddle/nn/layer/norm.py)."""
from __future__ import annotations

import numpy as np

from ...core.tensor import Tensor
from .. import functional as F
from .. import initializer as I
from ..layer_base import Layer
from ..layout import resolve_data_format as _resolve_data_format


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5,
                 weight_attr=None, bias_attr=None, data_format="NCHW",
                 use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = _resolve_data_format(data_format)
        self._use_global_stats = use_global_stats
        self.weight = self.create_parameter(
            (num_features,), attr=weight_attr,
            default_initializer=I.Constant(1.0))
        self.bias = self.create_parameter((num_features,), attr=bias_attr,
                                          is_bias=True)
        self.register_buffer("_mean", Tensor(np.zeros(num_features, np.float32)))
        self.register_buffer("_variance", Tensor(np.ones(num_features, np.float32)))

    def forward(self, x):
        return F.batch_norm(x, self._mean, self._variance, self.weight, self.bias,
                            training=self.training, momentum=self._momentum,
                            epsilon=self._epsilon, data_format=self._data_format,
                            use_global_stats=self._use_global_stats)

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}"


class BatchNorm(_BatchNormBase):
    """Legacy fluid BatchNorm (acts like BatchNorm1D/2D/3D by input rank)."""


class BatchNorm1D(_BatchNormBase):
    pass


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    pass


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica BN.  Under GSPMD data parallelism the batch statistics are
    computed over the global (sharded) batch automatically when the step is
    jitted over the mesh, so this shares the BatchNorm implementation
    (reference: python/paddle/nn/layer/norm.py SyncBatchNorm + c_sync_calc ops)."""

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        for l in layer.sublayers(include_self=True):
            for name, sub in list(l._sub_layers.items()):
                if isinstance(sub, _BatchNormBase) and not isinstance(sub, SyncBatchNorm):
                    new = SyncBatchNorm(sub._num_features, sub._momentum,
                                        sub._epsilon, data_format=sub._data_format)
                    new.weight = sub.weight
                    new.bias = sub.bias
                    new.register_buffer("_mean", sub._mean)
                    new.register_buffer("_variance", sub._variance)
                    l._sub_layers[name] = new
        return layer


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self._normalized_shape = tuple(normalized_shape)
        self._epsilon = epsilon
        self.weight = (None if weight_attr is False else self.create_parameter(
            self._normalized_shape, attr=weight_attr,
            default_initializer=I.Constant(1.0)))
        self.bias = (None if bias_attr is False else self.create_parameter(
            self._normalized_shape, attr=bias_attr, is_bias=True))

    def forward(self, x):
        return F.layer_norm(x, self._normalized_shape, self.weight, self.bias,
                            self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={list(self._normalized_shape)}"


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis (no mean, no bias)."""

    def __init__(self, hidden_size, epsilon=1e-6, weight_attr=None,
                 name=None):
        super().__init__()
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            (int(hidden_size),), attr=weight_attr,
            default_initializer=I.Constant(1.0))

    def forward(self, x):
        return F.rms_norm(x, self.weight, self._epsilon)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None,
                 bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self._data_format = _resolve_data_format(data_format)
        self.weight = (None if weight_attr is False else self.create_parameter(
            (num_channels,), attr=weight_attr,
            default_initializer=I.Constant(1.0)))
        self.bias = (None if bias_attr is False else self.create_parameter(
            (num_channels,), attr=bias_attr, is_bias=True))

    def forward(self, x):
        return F.group_norm(x, self._num_groups, self._epsilon, self.weight,
                            self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9,
                 weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._epsilon = epsilon
        if weight_attr is False or bias_attr is False:
            self.weight = None
            self.bias = None
        else:
            self.weight = self.create_parameter(
                (num_features,), attr=weight_attr,
                default_initializer=I.Constant(1.0))
            self.bias = self.create_parameter((num_features,), attr=bias_attr,
                                              is_bias=True)

    def forward(self, x):
        return F.instance_norm(x, weight=self.weight, bias=self.bias,
                               eps=self._epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW",
                 name=None):
        super().__init__()
        self._args = (size, alpha, beta, k, data_format)

    def forward(self, x):
        return F.local_response_norm(x, *self._args)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, epsilon=1e-12,
                 name=None):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._epsilon = epsilon
        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.register_buffer("weight_u", Tensor(np.random.randn(h).astype(np.float32)))
        self.register_buffer("weight_v", Tensor(np.random.randn(w).astype(np.float32)))

    def forward(self, weight):
        return F.spectral_norm(weight, self.weight_u, self.weight_v, self._dim,
                               self._power_iters, self._epsilon)
