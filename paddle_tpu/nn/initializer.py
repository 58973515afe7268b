"""Weight initializers (reference: python/paddle/fluid/initializer.py /
python/paddle/nn/initializer/).  Initializers are callables
``(shape, jnp_dtype) -> jnp array`` drawing from the global framework RNG."""
from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ..core import random as rnd


def _fan_in_out(shape):
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        # Linear weights are [in, out] in paddle
        return shape[0], shape[1]
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


class Initializer:
    def __call__(self, shape, dtype=jnp.float32):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, shape, dtype=jnp.float32):
        return jnp.full(shape, self.value, dtype=dtype)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    # a float32 draw keeps about twelve arrays of its size alive on a TPU:
    # a [151936, 2560] table would need 19 GB.  Above _WHOLE elements the
    # rows are drawn in blocks of at most _BLOCK elements, each from its own
    # fold of the key (smaller shapes keep the one draw and their values)
    _WHOLE, _BLOCK = 1 << 27, 1 << 25

    def __call__(self, shape, dtype=jnp.float32):
        key = rnd.next_key()
        n = int(np.prod(shape)) if len(shape) else 1
        if n <= self._WHOLE or len(shape) < 2:
            return (self.mean + self.std *
                    jax.random.normal(key, shape)).astype(dtype)
        rows = max(1, self._BLOCK // (n // shape[0]))
        return jnp.concatenate([
            (self.mean + self.std * jax.random.normal(
                jax.random.fold_in(key, r),
                (min(rows, shape[0] - r),) + tuple(shape[1:]))).astype(dtype)
            for r in range(0, shape[0], rows)])


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype=jnp.float32):
        return (self.mean + self.std *
                jax.random.truncated_normal(rnd.next_key(), -2.0, 2.0, shape)
                ).astype(dtype)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype=jnp.float32):
        return jax.random.uniform(rnd.next_key(), shape, jnp.float32,
                                  self.low, self.high).astype(dtype)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype=jnp.float32):
        fin, fout = _fan_in_out(shape)
        fin = self.fan_in or fin
        fout = self.fan_out or fout
        std = self.gain * math.sqrt(2.0 / (fin + fout))
        return (std * jax.random.normal(rnd.next_key(), shape)).astype(dtype)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype=jnp.float32):
        fin, fout = _fan_in_out(shape)
        fin = self.fan_in or fin
        fout = self.fan_out or fout
        limit = self.gain * math.sqrt(6.0 / (fin + fout))
        return jax.random.uniform(rnd.next_key(), shape, jnp.float32,
                                  -limit, limit).astype(dtype)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.negative_slope = fan_in, negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, shape, dtype=jnp.float32):
        fin, _ = _fan_in_out(shape)
        fin = self.fan_in or fin
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2)) \
            if self.nonlinearity in ("relu", "leaky_relu") else 1.0
        std = gain / math.sqrt(fin)
        return (std * jax.random.normal(rnd.next_key(), shape)).astype(dtype)


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.negative_slope = fan_in, negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, shape, dtype=jnp.float32):
        fin, _ = _fan_in_out(shape)
        fin = self.fan_in or fin
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2)) \
            if self.nonlinearity in ("relu", "leaky_relu") else 1.0
        limit = gain * math.sqrt(3.0 / fin)
        return jax.random.uniform(rnd.next_key(), shape, jnp.float32,
                                  -limit, limit).astype(dtype)


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype=jnp.float32):
        from ..core.tensor import Tensor
        v = self.value
        if isinstance(v, Tensor):
            v = v.numpy()
        arr = jnp.asarray(np.asarray(v), dtype=dtype)
        if tuple(arr.shape) != tuple(shape):
            arr = arr.reshape(shape)
        return arr


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype=jnp.float32):
        return (self.gain *
                jax.nn.initializers.orthogonal()(rnd.next_key(), shape,
                                                 jnp.float32)).astype(dtype)


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def __call__(self, shape, dtype=jnp.float32):
        out = np.zeros(shape, dtype=np.float32)
        oc, ic = shape[0], shape[1]
        centre = tuple(s // 2 for s in shape[2:])
        for i in range(min(oc, ic * self.groups)):
            out[(i, i % ic) + centre] = 1.0
        return jnp.asarray(out, dtype=dtype)


# paddle.nn.initializer re-export names
constant = Constant
normal = Normal
uniform = Uniform
