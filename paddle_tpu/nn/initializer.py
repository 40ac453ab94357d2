"""Weight initializers (ref python/paddle/fluid/initializer.py: Constant, Uniform,
Normal, TruncatedNormal, Xavier, MSRA/Kaiming, Bilinear, Assign).

Each initializer is a callable (shape, dtype) -> jnp.ndarray drawing from the
global Generator chain."""
import math

import numpy as np
import jax
import jax.numpy as jnp

from ..framework import state
from ..framework.dtype import convert_dtype


def _fan_in_out(shape):
    shape = tuple(shape)
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels [out_c, in_c, *spatial] (NCHW weights)
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


class Initializer:
    """Initialisation runs on host CPU: weight init is latency-bound
    bookkeeping, not MXU work, and each eager op on the accelerator is a
    dispatch of its own. The arrays migrate to the accelerator on first real use
    (jit input placement / device_put in the train-step compilers).

    Subclasses implement `_generate(shape, dtype)`; `__call__` is the template
    method that pins the computation to the host device."""

    def __call__(self, shape, dtype="float32"):
        from ..framework.state import host_device
        with jax.default_device(host_device()):
            return self._generate(shape, dtype)

    def _generate(self, shape, dtype):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def _generate(self, shape, dtype):
        return jnp.full(tuple(shape), self.value, convert_dtype(dtype))


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def _generate(self, shape, dtype):
        return jax.random.uniform(state.next_rng_key(), tuple(shape),
                                  convert_dtype(dtype), self.low, self.high)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def _generate(self, shape, dtype):
        return (jax.random.normal(state.next_rng_key(), tuple(shape),
                                  convert_dtype(dtype)) * self.std + self.mean)


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def _generate(self, shape, dtype):
        return (jax.random.truncated_normal(state.next_rng_key(), -2.0, 2.0,
                                            tuple(shape), convert_dtype(dtype))
                * self.std + self.mean)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self._fan_in, self._fan_out, self.gain = fan_in, fan_out, gain

    def _generate(self, shape, dtype):
        fi, fo = _fan_in_out(shape)
        fi = self._fan_in if self._fan_in is not None else fi
        fo = self._fan_out if self._fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(state.next_rng_key(), tuple(shape),
                                  convert_dtype(dtype), -limit, limit)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self._fan_in, self._fan_out, self.gain = fan_in, fan_out, gain

    def _generate(self, shape, dtype):
        fi, fo = _fan_in_out(shape)
        fi = self._fan_in if self._fan_in is not None else fi
        fo = self._fan_out if self._fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return jax.random.normal(state.next_rng_key(), tuple(shape),
                                 convert_dtype(dtype)) * std


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self._fan_in = fan_in

    def _generate(self, shape, dtype):
        fi, _ = _fan_in_out(shape)
        fi = self._fan_in if self._fan_in is not None else fi
        limit = math.sqrt(6.0 / fi)
        return jax.random.uniform(state.next_rng_key(), tuple(shape),
                                  convert_dtype(dtype), -limit, limit)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self._fan_in = fan_in

    def _generate(self, shape, dtype):
        fi, _ = _fan_in_out(shape)
        fi = self._fan_in if self._fan_in is not None else fi
        std = math.sqrt(2.0 / fi)
        return jax.random.normal(state.next_rng_key(), tuple(shape),
                                 convert_dtype(dtype)) * std


MSRAInitializer = KaimingNormal


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def _generate(self, shape, dtype):
        arr = np.asarray(self.value)
        return jnp.asarray(arr, convert_dtype(dtype)).reshape(tuple(shape))


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def _generate(self, shape, dtype):
        return jax.nn.initializers.orthogonal(scale=self.gain)(
            state.next_rng_key(), tuple(shape), convert_dtype(dtype))


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def _generate(self, shape, dtype):
        out = np.zeros(tuple(shape), dtype=np.float32)
        oc, ic = shape[0], shape[1]
        centers = [s // 2 for s in shape[2:]]
        for i in range(min(oc, ic)):
            out[(i, i) + tuple(centers)] = 1.0
        return jnp.asarray(out, convert_dtype(dtype))


# reference-compat aliases (fluid.initializer names)
ConstantInitializer = Constant
UniformInitializer = Uniform
NormalInitializer = Normal
TruncatedNormalInitializer = TruncatedNormal
XavierInitializer = XavierNormal
NumpyArrayInitializer = Assign


def calculate_gain(nonlinearity, param=None):
    gains = {"sigmoid": 1.0, "linear": 1.0, "conv1d": 1.0, "conv2d": 1.0,
             "conv3d": 1.0, "tanh": 5.0 / 3.0, "relu": math.sqrt(2.0),
             "leaky_relu": math.sqrt(2.0 / (1 + (param or 0.01) ** 2)),
             "selu": 0.75}
    return gains[nonlinearity]


class Bilinear(Initializer):
    """ref initializer.py BilinearInitializer — transposed-conv upsampling
    kernels: EVERY channel pair of the 4-D weight gets the separable
    bilinear interpolation filter (the reference fills all channels, so
    the canonical grouped layout [C, 1, kh, kw] upsamples every channel)."""

    def _generate(self, shape, dtype):
        if len(shape) != 4:
            raise ValueError("Bilinear expects a 4-D conv weight shape")
        kh, kw = shape[2], shape[3]

        def filt(k):
            f = (k + 1) // 2
            center = f - 1 if k % 2 == 1 else f - 0.5
            return (1 - np.abs(np.arange(k) - center) / f)

        kern = np.outer(filt(kh), filt(kw))
        w = np.broadcast_to(kern, shape)
        return jnp.asarray(w, convert_dtype(dtype))
