"""nn.functional — activations, conv/pool, norm, losses, embedding, dropout
(ref python/paddle/nn/functional/* and the kernels in paddle/fluid/operators/:
activation_op.cc, conv_cudnn_op.cu, pool_op, batch_norm_op, layer_norm_op,
softmax_with_cross_entropy_op, dropout_op, lookup_table_v2_op).

Convs ride lax.conv_general_dilated (MXU path); XLA picks TPU-optimal layouts so
both NCHW (paddle default) and NHWC are accepted.
"""
import functools
import math
import numbers
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..framework import state
from ..framework.dtype import convert_dtype
from ..framework.tensor import Tensor
from ..ops.dispatch import apply, as_array, register_op

# ----------------------------------------------------------------- activations


def _unary(fn, name):
    register_op(name, fn)

    def op(x, name=None, _opname=name):
        return apply(fn, (x,), name=_opname)
    op.__name__ = name
    return op


relu = _unary(jax.nn.relu, "relu")
relu6 = _unary(jax.nn.relu6, "relu6")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
tanh = _unary(jnp.tanh, "tanh")
silu = _unary(jax.nn.silu, "silu")
swish = silu
mish = _unary(lambda a: a * jnp.tanh(jax.nn.softplus(a)), "mish")
hardswish = _unary(jax.nn.hard_swish, "hardswish")
hardsigmoid = _unary(lambda a: jnp.clip(a / 6.0 + 0.5, 0.0, 1.0), "hardsigmoid")
tanhshrink = _unary(lambda a: a - jnp.tanh(a), "tanhshrink")


def _gelu_raw(a, approximate=False):
    return jax.nn.gelu(a, approximate=approximate)


register_op("gelu", _gelu_raw)


def gelu(x, approximate=False, name=None):
    return apply(_gelu_raw, (x,), {"approximate": bool(approximate)},
                 name="gelu")


def _leaky_relu_raw(a, negative_slope=0.01):
    return jax.nn.leaky_relu(a, negative_slope)


register_op("leaky_relu", _leaky_relu_raw)


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(_leaky_relu_raw, (x,),
                 {"negative_slope": float(negative_slope)}, name="leaky_relu")


def _elu_raw(a, alpha=1.0):
    return jax.nn.elu(a, alpha)


def _celu_raw(a, alpha=1.0):
    return jax.nn.celu(a, alpha)


def _selu_raw(a, scale=1.0507009873554805, alpha=1.6732632423543772):
    return scale * jnp.where(a > 0, a, alpha * jnp.expm1(a))


def _prelu_raw(a, w, data_format="NCHW"):
    if w.size == 1:
        return jnp.where(a > 0, a, w.reshape(()) * a)
    ch_axis = 1 if data_format == "NCHW" else a.ndim - 1
    shape = [1] * a.ndim
    shape[ch_axis] = w.size
    return jnp.where(a > 0, a, w.reshape(shape) * a)


def _hardtanh_raw(a, lo=-1.0, hi=1.0):
    return jnp.clip(a, lo, hi)


def _hardshrink_raw(a, threshold=0.5):
    return jnp.where(jnp.abs(a) > threshold, a, 0.0)


def _softshrink_raw(a, threshold=0.5):
    return jnp.where(a > threshold, a - threshold,
                     jnp.where(a < -threshold, a + threshold, 0.0))


def _softplus_raw(a, beta=1.0, threshold=20.0):
    return jnp.where(a * beta > threshold, a,
                     jax.nn.softplus(a * beta) / beta)


def _softsign_raw(a):
    return a / (1 + jnp.abs(a))


def _maxout_raw(a, groups=1, axis=1):
    c = a.shape[axis]
    new_shape = list(a.shape)
    new_shape[axis] = c // groups
    new_shape.insert(axis + 1, groups)
    return jnp.max(a.reshape(new_shape), axis=axis + 1)


register_op("elu", _elu_raw)
register_op("celu", _celu_raw)
register_op("selu", _selu_raw)
register_op("prelu", _prelu_raw)
register_op("hardtanh", _hardtanh_raw)
register_op("hardshrink", _hardshrink_raw)
register_op("softshrink", _softshrink_raw)
register_op("softplus", _softplus_raw)
register_op("softsign", _softsign_raw)
register_op("maxout", _maxout_raw)


def elu(x, alpha=1.0, name=None):
    return apply(_elu_raw, (x,), {"alpha": float(alpha)}, name="elu")


def celu(x, alpha=1.0, name=None):
    return apply(_celu_raw, (x,), {"alpha": float(alpha)}, name="celu")


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return apply(_selu_raw, (x,),
                 {"scale": float(scale), "alpha": float(alpha)}, name="selu")


def prelu(x, weight, data_format="NCHW", name=None):
    return apply(_prelu_raw, (x, weight), {"data_format": str(data_format)},
                 name="prelu")


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply(_hardtanh_raw, (x,), {"lo": float(min), "hi": float(max)},
                 name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    return apply(_hardshrink_raw, (x,), {"threshold": float(threshold)},
                 name="hardshrink")


def softshrink(x, threshold=0.5, name=None):
    return apply(_softshrink_raw, (x,), {"threshold": float(threshold)},
                 name="softshrink")


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(_softplus_raw, (x,),
                 {"beta": float(beta), "threshold": float(threshold)},
                 name="softplus")


def softsign(x, name=None):
    return apply(_softsign_raw, (x,), name="softsign")


def maxout(x, groups, axis=1, name=None):
    return apply(_maxout_raw, (x,),
                 {"groups": int(groups), "axis": int(axis)}, name="maxout")


def _softmax_raw(a, axis=-1, to_dtype=None):
    if to_dtype is not None:
        a = a.astype(convert_dtype(to_dtype))
    return jax.nn.softmax(a, axis=axis)


register_op("softmax", _softmax_raw)


def softmax(x, axis=-1, dtype=None, name=None):
    return apply(_softmax_raw, (x,),
                 {"axis": int(axis), "to_dtype": None if dtype is None else
                  str(np.dtype(convert_dtype(dtype)))}, name="softmax")


def _log_softmax_raw(a, axis=-1, to_dtype=None):
    if to_dtype is not None:
        a = a.astype(convert_dtype(to_dtype))
    return jax.nn.log_softmax(a, axis=axis)


register_op("log_softmax", _log_softmax_raw)


def log_softmax(x, axis=-1, dtype=None, name=None):
    return apply(_log_softmax_raw, (x,),
                 {"axis": int(axis), "to_dtype": None if dtype is None else
                  str(np.dtype(convert_dtype(dtype)))}, name="log_softmax")


def _gumbel_softmax_raw(a, key, temperature=1.0, hard=False, axis=-1):
    g = -jnp.log(-jnp.log(
        jax.random.uniform(key, tuple(a.shape)) + 1e-20))
    y = jax.nn.softmax((a + g) / temperature, axis=axis)
    if hard:
        # straight-through: one-hot forward, soft gradient
        idx = jnp.argmax(y, axis=axis)
        onehot = jax.nn.one_hot(idx, y.shape[axis], axis=axis, dtype=y.dtype)
        y = onehot + y - lax.stop_gradient(y)
    return y


register_op("gumbel_softmax", _gumbel_softmax_raw)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    # rng op: key is input #1 + "__rng__" salt, same replay contract as dropout
    return apply(_gumbel_softmax_raw, (x, Tensor(state.next_rng_key())),
                 {"temperature": float(temperature), "hard": bool(hard),
                  "axis": int(axis), "__rng__": True}, name="gumbel_softmax")


# ----------------------------------------------------------------- linear / emb

def _linear_raw(a, w, b=None):
    out = jnp.matmul(a, w)
    return out if b is None else out + b


register_op("linear", _linear_raw)


def linear(x, weight, bias=None, name=None):
    """paddle weight layout: [in_features, out_features] (ref nn/functional/common.py:1419)."""
    if bias is None:
        return apply(_linear_raw, (x, weight), name="linear")
    return apply(_linear_raw, (x, weight, bias), name="linear")


def _embedding_raw(idx, w, padding_idx=None):
    out = jnp.take(w, idx, axis=0)
    if padding_idx is not None:
        mask = (idx == padding_idx)[..., None]
        out = jnp.where(mask, 0.0, out)
    return out


register_op("embedding", _embedding_raw)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Device-side gather (TPU: embedding lookups stay on-chip; host-resident
    sparse tables are the PS path, see distributed/ps). sparse=True makes the
    EAGER backward produce a SelectedRows gradient on `weight` — O(batch*dim)
    instead of O(vocab*dim) (ref lookup_table_v2_op is_sparse grad; under
    jit, XLA's fused scatter-add already gives this, so the flag only
    changes the eager tape)."""
    if padding_idx is not None and padding_idx < 0:
        # paddle semantics: negative pad indexes from the end of the table
        padding_idx = int(as_array(weight).shape[0]) + int(padding_idx)
    if sparse and not state.is_functional_mode() and state.is_grad_enabled() \
            and isinstance(weight, Tensor) and not weight.stop_gradient \
            and weight._node is None:
        # leaf tables only: a non-leaf weight's producer holds a jax vjp
        # that cannot consume a SelectedRows cotangent
        return _sparse_embedding_eager(x, weight, padding_idx)
    return apply(_embedding_raw, (x, weight),
                 {"padding_idx": None if padding_idx is None
                  else int(padding_idx)}, name="embedding")


def _sparse_embedding_eager(x, weight, padding_idx):
    """Eager gather whose GradNode emits SelectedRows for the table."""
    from ..framework.tape import GradNode
    from ..framework.selected_rows import SelectedRows
    ids = as_array(x)
    w = as_array(weight)
    out = _embedding_raw(ids, w, padding_idx=padding_idx)
    height = int(w.shape[0])      # don't capture w: it pins a stale table

    def vjp(cot):
        flat_ids = ids.ravel()
        vals = cot.reshape((-1,) + cot.shape[ids.ndim:])
        if padding_idx is not None:
            vals = jnp.where((flat_ids == padding_idx)[..., None], 0.0, vals)
        return (jnp.zeros_like(ids),          # ids: int input, skipped
                SelectedRows(flat_ids, vals, height))

    res = Tensor(out, stop_gradient=False)
    node = GradNode(vjp=vjp,
                    inputs=[x if isinstance(x, Tensor) else None, weight],
                    n_outputs=1, out_shapes=(out.shape,),
                    out_dtypes=(out.dtype,), name="sparse_embedding")
    res._node = node
    res._slot = 0
    return res


def one_hot(x, num_classes, name=None):
    from ..ops.manipulation import _one_hot_raw
    return apply(_one_hot_raw, (x,), {"num_classes": int(num_classes)},
                 differentiable=False, name="one_hot")


# ----------------------------------------------------------------- dropout

def _dropout_raw(v, key, p=0.5, axis=None, mode="upscale_in_train",
                 training=True):
    """rng-explicit dropout (ref operators/dropout_op.cc: seed attr + mask
    output; here the mask is derived from a key input so the static desc
    replays with fresh randomness per run)."""
    if not training or p == 0.0:
        return v
    shape = tuple(v.shape)
    if axis is not None:
        axes = axis if isinstance(axis, (list, tuple)) else [axis]
        shape = tuple(s if i in axes else 1 for i, s in enumerate(v.shape))
    keep = jax.random.bernoulli(key, 1.0 - p, shape)
    if mode == "upscale_in_train":
        return jnp.where(keep, v / (1.0 - p), 0.0)
    return jnp.where(keep, v, 0.0)


register_op("dropout", _dropout_raw)


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    # same gating as apply(): in functional (jit-trace) mode the recorder is
    # inert and the eager fast path below is safe
    rec = None if state.is_functional_mode() else state.get_static_recorder()
    if (not training or p == 0.0) and rec is None:
        return x if isinstance(x, Tensor) else Tensor(x)
    key = state.next_rng_key()
    if isinstance(axis, (list, tuple)):
        axis = [int(a) for a in axis]
    elif axis is not None:
        axis = int(axis)
    # "__rng__": True asks the recorder to salt this op so the Executor
    # re-derives the key input per run (dispatch strips dunder attrs before
    # calling the impl)
    return apply(_dropout_raw, (x, Tensor(key)),
                 {"p": float(p), "axis": axis, "mode": mode,
                  "training": bool(training), "__rng__": True},
                 name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def _alpha_dropout_raw(v, key, p=0.5):
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale
    keep = jax.random.bernoulli(key, 1.0 - p, tuple(v.shape))
    q = 1.0 - p
    coef_a = (q + alpha_p ** 2 * q * p) ** -0.5
    coef_b = -coef_a * alpha_p * p
    return coef_a * jnp.where(keep, v, alpha_p) + coef_b


register_op("alpha_dropout", _alpha_dropout_raw)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    return apply(_alpha_dropout_raw, (x, Tensor(state.next_rng_key())),
                 {"p": float(p), "__rng__": True}, name="alpha_dropout")


# ----------------------------------------------------------------- conv / pool

def _norm_tuple(v, n):
    if isinstance(v, numbers.Number):
        return (int(v),) * n
    v = tuple(int(i) for i in v)
    if len(v) == 1:
        return v * n
    return v


def _conv_padding(padding, n, strides, dilations, ksize):
    """paddle padding spec -> lax padding list. Supports int, list, 'SAME','VALID'."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, numbers.Number):
        return [(int(padding), int(padding))] * n
    padding = list(padding)
    if len(padding) == n:
        if isinstance(padding[0], (list, tuple)):
            return [tuple(p) for p in padding]
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:  # [before0, after0, before1, after1...]
        return [(int(padding[2 * i]), int(padding[2 * i + 1])) for i in range(n)]
    raise ValueError(f"bad padding {padding}")


def _convnd_raw(a, w, *maybe_b, n=2, stride=1, padding=0, dilation=1,
                groups=1, channels_last=False):
    """Shared N-d conv impl (ref conv_op.cc): weight [out_c, in_c/g, *k]."""
    strides = _norm_tuple(stride, n)
    dilations = _norm_tuple(dilation, n)
    spatial = "DHW"[3 - n:]
    if channels_last:
        dn_str = ("N" + spatial + "C", "OI" + spatial, "N" + spatial + "C")
    else:
        dn_str = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    pad = _conv_padding(padding, n, strides, dilations, w.shape[2:])
    dn = lax.conv_dimension_numbers(a.shape, w.shape, dn_str)
    out = lax.conv_general_dilated(
        a, w, window_strides=strides, padding=pad,
        rhs_dilation=dilations, dimension_numbers=dn,
        feature_group_count=groups)
    if maybe_b:
        shape = ((1,) + (1,) * n + (-1,) if channels_last
                 else (1, -1) + (1,) * n)
        out = out + maybe_b[0].reshape(shape)
    return out


def _conv1d_raw(a, w, *maybe_b, stride=1, padding=0, dilation=1, groups=1,
                channels_last=False):
    return _convnd_raw(a, w, *maybe_b, n=1, stride=stride, padding=padding,
                       dilation=dilation, groups=groups,
                       channels_last=channels_last)


def _conv2d_raw(a, w, *maybe_b, stride=1, padding=0, dilation=1, groups=1,
                channels_last=False):
    return _convnd_raw(a, w, *maybe_b, n=2, stride=stride, padding=padding,
                       dilation=dilation, groups=groups,
                       channels_last=channels_last)


def _conv3d_raw(a, w, *maybe_b, stride=1, padding=0, dilation=1, groups=1,
                channels_last=False):
    return _convnd_raw(a, w, *maybe_b, n=3, stride=stride, padding=padding,
                       dilation=dilation, groups=groups,
                       channels_last=channels_last)


register_op("conv1d", _conv1d_raw)
register_op("conv2d", _conv2d_raw)
register_op("conv3d", _conv3d_raw)


def _pad_attr(padding):
    if isinstance(padding, str):
        return padding
    if isinstance(padding, numbers.Number):
        return int(padding)
    return [list(int(i) for i in p) if isinstance(p, (list, tuple))
            else int(p) for p in padding]


def _stride_attr(v):
    if isinstance(v, numbers.Number):
        return int(v)
    return [int(i) for i in v]


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    """weight layout: [out_c, in_c/groups, kh, kw] (paddle/ref conv_op.cc)."""
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(_conv2d_raw, args,
                 {"stride": _stride_attr(stride), "padding": _pad_attr(padding),
                  "dilation": _stride_attr(dilation), "groups": int(groups),
                  "channels_last": data_format != "NCHW"}, name="conv2d")


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(_conv1d_raw, args,
                 {"stride": _stride_attr(stride), "padding": _pad_attr(padding),
                  "dilation": _stride_attr(dilation), "groups": int(groups),
                  "channels_last": data_format != "NCL"}, name="conv1d")


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(_conv3d_raw, args,
                 {"stride": _stride_attr(stride), "padding": _pad_attr(padding),
                  "dilation": _stride_attr(dilation), "groups": int(groups),
                  "channels_last": data_format != "NCDHW"}, name="conv3d")


def _conv2d_transpose_raw(a, w, *maybe_b, stride=1, padding=0,
                          output_padding=0, dilation=1, groups=1,
                          channels_last=False):
    """weight layout: [in_c, out_c/groups, kh, kw] (ref conv_transpose_op.cc).
    Thin layout shim over the shared N-d impl (_convnd_transpose_raw)."""
    if channels_last:
        a = jnp.transpose(a, (0, 3, 1, 2))
    out = _convnd_transpose_raw(a, w, *maybe_b, n=2, stride=stride,
                                padding=padding,
                                output_padding=output_padding,
                                dilation=dilation, groups=groups)
    if channels_last:
        out = jnp.transpose(out, (0, 2, 3, 1))
    return out


register_op("conv2d_transpose", _conv2d_transpose_raw)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, dilation=1, groups=1, output_size=None,
                     data_format="NCHW", name=None):
    """weight layout: [in_c, out_c/groups, kh, kw] (ref conv_transpose_op.cc)."""
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(_conv2d_transpose_raw, args,
                 {"stride": _stride_attr(stride), "padding": _pad_attr(padding),
                  "output_padding": _stride_attr(output_padding),
                  "dilation": _stride_attr(dilation), "groups": int(groups),
                  "channels_last": data_format != "NCHW"},
                 name="conv2d_transpose")


def _poolnd_raw(a, n=2, ksize=1, strides=None, padding=0,
                channels_last=False, average=False, count_include_pad=True,
                ceil_mode=False):
    """Shared 1/2/3-d pooling over lax.reduce_window (NCX or NXC).
    ceil_mode=True rounds the output size UP (ref pooling.cc
    AdaptEndIndex/ceil branch) by extending the high-edge padding;
    the extra cells never count toward an exclusive average (the ones
    window sees them as padding)."""
    ksize = _norm_tuple(ksize, n)
    strides = _norm_tuple(strides or ksize, n)
    if not channels_last:
        dims = (1, 1) + ksize
        strd = (1, 1) + strides
    else:
        dims = (1,) + ksize + (1,)
        strd = (1,) + strides + (1,)
    pad = _conv_padding(padding, n, strides, (1,) * n, ksize)
    if ceil_mode and not isinstance(pad, str):
        spatial = a.shape[1:1 + n] if channels_last else a.shape[2:2 + n]
        pad = [list(p) for p in pad]
        for i in range(n):
            H, (pl, ph) = spatial[i], pad[i]
            total = H + pl + ph
            out = -(-(total - ksize[i]) // strides[i]) + 1   # ceil count
            # a window starting entirely in the high pad is not a window
            # (torch/caffe clamp rule); without it stride > kernel emits
            # all-padding cells (-inf / 0-count NaN)
            if (out - 1) * strides[i] >= H + pl:
                out -= 1
            needed = (out - 1) * strides[i] + ksize[i]
            if needed > total:
                pad[i][1] += needed - total
        pad = [tuple(p) for p in pad]
    if isinstance(pad, str):
        pad_cfg = pad
    else:
        if not channels_last:
            pad_cfg = [(0, 0), (0, 0)] + list(pad)
        else:
            pad_cfg = [(0, 0)] + list(pad) + [(0, 0)]
    if average:
        reducer, init = lax.add, 0.0
    else:
        reducer = lax.max
        init = (-jnp.inf if jnp.issubdtype(a.dtype, jnp.floating)
                else jnp.iinfo(a.dtype).min)
    out = lax.reduce_window(a, init, reducer, dims, strd, pad_cfg)
    if average:
        if count_include_pad or (isinstance(pad, str) and pad == "VALID"):
            out = out / np.prod(ksize)
        else:
            onesw = lax.reduce_window(jnp.ones_like(a), 0.0, lax.add, dims,
                                      strd, pad_cfg)
            out = out / onesw
    return out


register_op("max_pool2d", functools.partial(_poolnd_raw, n=2, average=False))
register_op("avg_pool2d", functools.partial(_poolnd_raw, n=2, average=True))


def _pool(x, ksize, strides, padding, data_format, name,
          ceil_mode=False, count_include_pad=True, average=False):
    from ..ops.dispatch import OP_REGISTRY
    attrs = {"ksize": _stride_attr(ksize),
             "strides": None if strides is None else _stride_attr(strides),
             "padding": _pad_attr(padding),
             "channels_last": data_format != "NCHW"}
    if ceil_mode:
        attrs["ceil_mode"] = True
    if average:
        attrs["count_include_pad"] = bool(count_include_pad)
    return apply(OP_REGISTRY[name], (x,), attrs, name=name)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCHW", name=None):
    return _pool(x, kernel_size, stride, padding, data_format,
                 "max_pool2d", ceil_mode=ceil_mode)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, divisor_override=None,
               data_format="NCHW", name=None):
    return _pool(x, kernel_size, stride, padding, data_format,
                 "avg_pool2d", ceil_mode=ceil_mode,
                 count_include_pad=count_include_pad, average=True)


def _adaptive_bins(in_n, out_n):
    """Reference adaptive bins (ref pooling.cc AdaptStartIndex/EndIndex):
    bin i covers [floor(i*I/O), ceil((i+1)*I/O))."""
    i = np.arange(out_n)
    start = (i * in_n) // out_n
    end = -((-(i + 1) * in_n) // out_n)      # ceil div
    return start, end


def _adaptive_avg_mat(in_n, out_n, dtype):
    """[out_n, in_n] averaging matrix: the general adaptive mean becomes
    a matmul over each spatial axis — static (trace-time) bin layout,
    MXU-friendly, no data-dependent shapes."""
    start, end = _adaptive_bins(in_n, out_n)
    j = np.arange(in_n)
    m = ((j[None, :] >= start[:, None])
         & (j[None, :] < end[:, None])).astype(np.float32)
    m /= m.sum(1, keepdims=True)
    return jnp.asarray(m, dtype)


def _adaptive_avg_pool2d_raw(a, output_size=1, channels_last=False):
    out_hw = _norm_tuple(output_size, 2)
    if not channels_last:
        h_axis, w_axis = 2, 3
    else:
        h_axis, w_axis = 1, 2
    ih, iw = a.shape[h_axis], a.shape[w_axis]
    oh, ow = out_hw
    if ih % oh == 0 and iw % ow == 0:
        # reshape-mean fast path
        if not channels_last:
            r = a.reshape(a.shape[0], a.shape[1], oh, ih // oh, ow, iw // ow)
            return r.mean(axis=(3, 5))
        r = a.reshape(a.shape[0], oh, ih // oh, ow, iw // ow, a.shape[-1])
        return r.mean(axis=(2, 4))
    # general (non-divisible) sizes: contract each spatial axis with its
    # averaging matrix — two matmuls instead of gathers
    acc = jnp.float32 if a.dtype != jnp.float64 else jnp.float64
    wh = _adaptive_avg_mat(ih, oh, acc)
    ww = _adaptive_avg_mat(iw, ow, acc)
    af = a.astype(acc)
    if not channels_last:
        out = jnp.einsum("nchw,oh,pw->ncop", af, wh, ww)
    else:
        out = jnp.einsum("nhwc,oh,pw->nopc", af, wh, ww)
    return out.astype(a.dtype)


def _adaptive_max_pool2d_raw(a, output_size=1):
    out_hw = _norm_tuple(output_size, 2)
    ih, iw = a.shape[2], a.shape[3]
    oh, ow = out_hw
    if ih % oh == 0 and iw % ow == 0:
        r = a.reshape(a.shape[0], a.shape[1], oh, ih // oh, ow, iw // ow)
        return r.max(axis=(3, 5))
    # general sizes: bins are static at trace time but ragged; reduce per
    # output row/col with dynamic slices (O static, so the loop unrolls)
    hs, he = _adaptive_bins(ih, oh)
    ws, we = _adaptive_bins(iw, ow)
    rows = jnp.stack([a[:, :, s:e, :].max(axis=2)
                      for s, e in zip(hs, he)], axis=2)      # [N,C,oh,iw]
    return jnp.stack([rows[:, :, :, s:e].max(axis=3)
                      for s, e in zip(ws, we)], axis=3)


register_op("adaptive_avg_pool2d", _adaptive_avg_pool2d_raw)
register_op("adaptive_max_pool2d", _adaptive_max_pool2d_raw)


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return apply(_adaptive_avg_pool2d_raw, (x,),
                 {"output_size": _stride_attr(output_size),
                  "channels_last": data_format != "NCHW"},
                 name="adaptive_avg_pool2d")


def adaptive_max_pool2d(x, output_size, return_mask=False, name=None):
    return apply(_adaptive_max_pool2d_raw, (x,),
                 {"output_size": _stride_attr(output_size)},
                 name="adaptive_max_pool2d")


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, name=None):
    t = x.unsqueeze(-1) if isinstance(x, Tensor) else Tensor(x)
    out = max_pool2d(t, (int(kernel_size) if isinstance(kernel_size, int)
                         else kernel_size[0], 1),
                     (int(stride) if isinstance(stride, (int, type(None)))
                      and stride else (stride[0] if stride else None), 1)
                     if stride else None,
                     padding=(padding if isinstance(padding, int) else padding[0],
                              0), ceil_mode=ceil_mode)
    return out.squeeze(-1)


def avg_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, name=None):
    t = x.unsqueeze(-1)
    out = avg_pool2d(t, (kernel_size if isinstance(kernel_size, int)
                         else kernel_size[0], 1),
                     (stride if isinstance(stride, int) else None, 1)
                     if stride else None,
                     padding=(padding if isinstance(padding, int) else padding[0],
                              0), ceil_mode=ceil_mode,
                     count_include_pad=count_include_pad)
    return out.squeeze(-1)


# ----------------------------------------------------------------- norm

def _batch_norm_raw(v, rm, rv, *wb, ch_axis=1, momentum=0.9, epsilon=1e-5,
                    training=False):
    """Single batch_norm op: y + updated running stats as explicit outputs
    (ref operators/batch_norm_op.cc MeanOut/VarianceOut in-place outputs).
    Eval mode passes the stats through unchanged."""
    ch = ch_axis % v.ndim
    shape = [1] * v.ndim
    shape[ch] = v.shape[ch]
    # stats in f32 (bf16 inputs must not accumulate in bf16), or in f64
    # when the caller is already double-precision (x64 mode). The f32
    # chain feeds ONLY the stats reductions: giving the apply its own
    # input-dtype chain keeps the convert fused inside the one stats
    # sweep — a shared f32 activation gets materialized by XLA as an
    # extra f32[N,C,H,W] output on the producing conv fusion (observed
    # on-chip: +10 ms/step on resnet50 b=128, ~410 MB per layer)
    stat_dt = v.dtype if v.dtype == jnp.float64 else jnp.float32
    if training:
        # Single-pass stats: the centered sum and sum-of-squares are
        # INDEPENDENT reductions over the same input, so XLA
        # sibling-fuses them into one HBM sweep; the mean-then-var form
        # chains two sweeps (var needs the mean first) and dominated the
        # resnet50 step on-chip (53 BN layers — see
        # docs/perf/traces/resnet). Stats in f32: bf16 activations would
        # otherwise accumulate in bf16. Centering the pass on a cheap
        # per-channel pivot (spatial mean of batch element 0, ~m within
        # a few std) keeps E[(x-p)^2] - (m-p)^2 far from the
        # catastrophic cancellation the naive E[x^2] - m^2 form hits
        # when |mean| >> std; the pivot slice is 1/N of the data so the
        # extra reduction is noise.
        reduce_axes = tuple(i for i in range(v.ndim) if i != ch)
        n = 1.0
        for i in reduce_axes:
            n *= v.shape[i]
        # the pivot averages two independently-sliced subsamples (all of
        # sample 0, and position 0 of every sample) so that no single
        # pathological slice — a blank first image, a letterboxed corner
        # — can leave the pivot far from the true mean on its own
        x0 = lax.index_in_dim(v, 0, axis=0, keepdims=True).astype(stat_dt)
        p_a = jnp.mean(x0, axis=reduce_axes)           # [C]
        xs = v
        for ax in reduce_axes:
            if ax != 0:
                xs = lax.index_in_dim(xs, 0, axis=ax, keepdims=True)
        p_b = jnp.mean(xs.astype(stat_dt), axis=reduce_axes)   # [C]
        pivot = lax.stop_gradient(0.5 * (p_a + p_b))
        xc = v.astype(stat_dt) - pivot.reshape(shape)
        s1 = jnp.sum(xc, axis=reduce_axes)
        s2 = jnp.sum(xc * xc, axis=reduce_axes)
        d = s1 / n                                     # m - pivot
        var = jnp.maximum(s2 / n - d * d, 0.0)
        m = d + pivot
        new_rm = momentum * rm + (1 - momentum) * m.astype(rm.dtype)
        new_rv = momentum * rv + (1 - momentum) * var.astype(rv.dtype)
        inv = lax.rsqrt(var + epsilon)
    else:
        new_rm, new_rv = rm, rv
        m = jnp.asarray(rm, stat_dt)
        inv = lax.rsqrt(jnp.asarray(rv, stat_dt) + epsilon)
    # explicit centering (x - m) * scale + bias: one fused elementwise
    # pass, and the subtraction happens at activation magnitude so a
    # large channel mean never rounds into the O(1) normalized output
    # (a folded x*scale+shift would put ~|mean|*inv-sized terms on both
    # sides of the add). The apply runs in the INPUT dtype with the [C]
    # vectors cast down — for bf16 activations the information below
    # bf16 resolution is already gone at the input, and an f32 apply
    # chain would force the shared f32 materialization described above.
    scale = inv
    bias = None
    if wb:
        scale = inv * jnp.asarray(wb[0], stat_dt)
        if len(wb) > 1:
            bias = jnp.asarray(wb[1], stat_dt)
    adt = v.dtype
    out = (v - m.astype(adt).reshape(shape)) * scale.astype(adt).reshape(shape)
    if bias is not None:
        out = out + bias.astype(adt).reshape(shape)
    return out, lax.stop_gradient(new_rm), lax.stop_gradient(new_rv)


register_op("batch_norm", _batch_norm_raw)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None):
    """ref operators/batch_norm_op.cc. Running stats are explicit op outputs;
    the wrapper writes them back onto the buffer Tensors (captured by
    functional_call and by the static recorder via alias_output)."""
    ch_axis = 1 if data_format in ("NCHW", "NCL", "NCDHW") else -1
    use_batch_stats = training and not use_global_stats
    args = [x, running_mean, running_var]
    if weight is None and bias is not None:
        weight = Tensor(jnp.ones_like(as_array(bias)))   # shift-only affine
    if weight is not None:
        args.append(weight)
        if bias is not None:
            args.append(bias)
    outs = apply(_batch_norm_raw, tuple(args),
                 {"ch_axis": int(ch_axis), "momentum": float(momentum),
                  "epsilon": float(epsilon),
                  "training": bool(use_batch_stats)}, name="batch_norm")
    y, new_rm, new_rv = outs
    if use_batch_stats:
        rec = state.get_static_recorder()
        if rec is not None:
            rec.alias_output(new_rm, running_mean)
            rec.alias_output(new_rv, running_var)
        running_mean._data = new_rm._data
        running_var._data = new_rv._data
    return y


def _layer_norm_raw(a, *wb, nd=1, epsilon=1e-5):
    axes = tuple(range(a.ndim - nd, a.ndim))
    if os.environ.get("PT_LN_SINGLE_PASS", "").lower() in ("1", "true",
                                                           "yes", "on"):
        # Experimental single-pass stats (same construction as
        # _batch_norm_raw: centered sum + sum-of-squares in one fused
        # sweep, f32 accumulation, first-element pivot, input-dtype
        # apply). OPT-IN until measured: the BN version won on-chip, but
        # the LN A/B got only degraded samples (68-70 ms vs the
        # 64-67 ms band), so the proven two-pass path stays the default
        # — perf defaults need an on-chip number.
        stat_dt = a.dtype if a.dtype == jnp.float64 else jnp.float32
        af = a.astype(stat_dt)
        n = 1.0
        for ax in axes:
            n *= a.shape[ax]
        # pivot = mean of a leading lane-aligned stripe of each row (up
        # to 128 elements per normalized axis), not a single element —
        # one outlier (padding zero, BOS spike) must not leave the
        # pivot |d| >> std and re-open the cancellation this construction
        # avoids (same safeguard idea as _batch_norm_raw's two-subsample
        # pivot)
        idx = tuple(slice(None) if i not in axes
                    else slice(0, min(128, a.shape[i]))
                    for i in range(a.ndim))
        pivot = lax.stop_gradient(
            jnp.mean(af[idx], axis=axes, keepdims=True))
        ac = af - pivot
        s1 = jnp.sum(ac, axis=axes, keepdims=True)
        s2 = jnp.sum(ac * ac, axis=axes, keepdims=True)
        d = s1 / n
        v = jnp.maximum(s2 / n - d * d, 0.0)
        m = (d + pivot).astype(a.dtype)
        rstd = lax.rsqrt(v + epsilon).astype(a.dtype)
        out = (a - m) * rstd
    else:
        m = jnp.mean(a, axis=axes, keepdims=True)
        v = jnp.var(a, axis=axes, keepdims=True)
        out = (a - m) * lax.rsqrt(v + epsilon)
    if wb:
        out = out * wb[0]
        if len(wb) > 1:
            out = out + wb[1]
    return out


register_op("layer_norm", _layer_norm_raw)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, numbers.Number):
        normalized_shape = (normalized_shape,)
    nd = len(tuple(normalized_shape))
    args = [x]
    if weight is not None:
        args.append(weight)
        if bias is not None:
            args.append(bias)
    return apply(_layer_norm_raw, tuple(args),
                 {"nd": nd, "epsilon": float(epsilon)}, name="layer_norm")


def _instance_norm_raw(a, *wb, eps=1e-5):
    axes = tuple(range(2, a.ndim))
    m = jnp.mean(a, axis=axes, keepdims=True)
    v = jnp.var(a, axis=axes, keepdims=True)
    out = (a - m) * lax.rsqrt(v + eps)
    if wb:
        shape = (1, -1) + (1,) * (a.ndim - 2)
        out = out * wb[0].reshape(shape)
        if len(wb) > 1:
            out = out + wb[1].reshape(shape)
    return out


register_op("instance_norm", _instance_norm_raw)


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    args = [x]
    if weight is not None:
        args.append(weight)
        if bias is not None:
            args.append(bias)
    return apply(_instance_norm_raw, tuple(args), {"eps": float(eps)},
                 name="instance_norm")


def _group_norm_raw(a, *wb, num_groups=1, epsilon=1e-5):
    n, c = a.shape[0], a.shape[1]
    g = num_groups
    r = a.reshape((n, g, c // g) + a.shape[2:])
    axes = tuple(range(2, r.ndim))
    m = jnp.mean(r, axis=axes, keepdims=True)
    v = jnp.var(r, axis=axes, keepdims=True)
    out = ((r - m) * lax.rsqrt(v + epsilon)).reshape(a.shape)
    if wb:
        shape = (1, c) + (1,) * (a.ndim - 2)
        out = out * wb[0].reshape(shape)
        if len(wb) > 1:
            out = out + wb[1].reshape(shape)
    return out


register_op("group_norm", _group_norm_raw)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    args = [x]
    if weight is not None:
        args.append(weight)
        if bias is not None:
            args.append(bias)
    return apply(_group_norm_raw, tuple(args),
                 {"num_groups": int(num_groups), "epsilon": float(epsilon)},
                 name="group_norm")


def _normalize_raw(a, p=2, axis=1, epsilon=1e-12):
    nrm = jnp.power(jnp.sum(jnp.power(jnp.abs(a), p), axis=axis,
                            keepdims=True), 1.0 / p)
    return a / jnp.maximum(nrm, epsilon)


register_op("normalize", _normalize_raw)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return apply(_normalize_raw, (x,),
                 {"p": float(p), "axis": int(axis), "epsilon": float(epsilon)},
                 name="normalize")


def _local_response_norm_raw(a, size=5, alpha=1e-4, beta=0.75, k=1.0):
    sq = jnp.square(a)
    half = size // 2
    pad_cfg = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (a.ndim - 2)
    padded = jnp.pad(sq, pad_cfg)
    window = sum(padded[:, i:i + a.shape[1]] for i in range(size))
    return a / jnp.power(k + alpha * window, beta)


register_op("local_response_norm", _local_response_norm_raw)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    return apply(_local_response_norm_raw, (x,),
                 {"size": int(size), "alpha": float(alpha),
                  "beta": float(beta), "k": float(k)},
                 name="local_response_norm")


# ----------------------------------------------------------------- losses

def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, name=None):
    """ref operators/softmax_with_cross_entropy_op.cc — fused log_softmax + NLL."""
    args = (input, label) if weight is None else (input, label, weight)
    return apply(_cross_entropy_raw, args,
                 {"ignore_index": int(ignore_index), "reduction": reduction,
                  "soft_label": bool(soft_label), "axis": int(axis),
                  "use_softmax": bool(use_softmax)}, name="cross_entropy")


def _cross_entropy_raw(logits, lab, *maybe_w, ignore_index=-100,
                       reduction="mean", soft_label=False, axis=-1,
                       use_softmax=True):
    if use_softmax:
        logp = jax.nn.log_softmax(logits, axis=axis)
    else:
        logp = jnp.log(jnp.maximum(logits, 1e-30))
    if soft_label:
        per = -jnp.sum(lab * logp, axis=axis)
    else:
        lab_i = lab.astype(jnp.int32)
        if lab_i.ndim == logp.ndim:  # [N,1] style labels
            lab_i = jnp.squeeze(lab_i, axis=axis)
        valid = lab_i != ignore_index
        safe = jnp.where(valid, lab_i, 0)
        per = -jnp.take_along_axis(logp, jnp.expand_dims(safe, axis),
                                   axis=axis)
        per = jnp.squeeze(per, axis=axis)
        if maybe_w:
            w = jnp.take(maybe_w[0], safe)
            per = per * w
        per = jnp.where(valid, per, 0.0)
        if reduction == "mean":
            if maybe_w:
                w = jnp.take(maybe_w[0], safe)
                denom = jnp.sum(jnp.where(valid, w, 0.0))
            else:
                denom = jnp.maximum(jnp.sum(valid.astype(per.dtype)), 1.0)
            return jnp.sum(per) / denom
    if reduction == "mean":
        return jnp.mean(per)
    if reduction == "sum":
        return jnp.sum(per)
    return per


register_op("cross_entropy", _cross_entropy_raw)


softmax_with_cross_entropy = cross_entropy


def _reduce_loss(per, reduction):
    if reduction == "mean":
        return jnp.mean(per)
    if reduction == "sum":
        return jnp.sum(per)
    return per


def _nll_loss_raw(logp, lab, *maybe_w, ignore_index=-100, reduction="mean"):
    lab_i = lab.astype(jnp.int32)
    valid = lab_i != ignore_index
    safe = jnp.where(valid, lab_i, 0)
    per = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    if maybe_w:
        per = per * jnp.take(maybe_w[0], safe)
    per = jnp.where(valid, per, 0.0)
    if reduction == "mean":
        denom = (jnp.sum(jnp.take(maybe_w[0], safe) * valid) if maybe_w
                 else jnp.maximum(jnp.sum(valid.astype(per.dtype)), 1.0))
        return jnp.sum(per) / denom
    return _reduce_loss(per, reduction)


register_op("nll_loss", _nll_loss_raw)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    args = (input, label) if weight is None else (input, label, weight)
    return apply(_nll_loss_raw, args,
                 {"ignore_index": int(ignore_index),
                  "reduction": str(reduction)}, name="nll_loss")


def _mse_loss_raw(a, b, reduction="mean"):
    return _reduce_loss(jnp.square(a - b), reduction)


def _l1_loss_raw(a, b, reduction="mean"):
    return _reduce_loss(jnp.abs(a - b), reduction)


def _smooth_l1_loss_raw(a, b, reduction="mean", delta=1.0):
    d = jnp.abs(a - b)
    l = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
    return _reduce_loss(l, reduction)


register_op("mse_loss", _mse_loss_raw)
register_op("l1_loss", _l1_loss_raw)
register_op("smooth_l1_loss", _smooth_l1_loss_raw)


def mse_loss(input, label, reduction="mean", name=None):
    return apply(_mse_loss_raw, (input, label),
                 {"reduction": str(reduction)}, name="mse_loss")


def l1_loss(input, label, reduction="mean", name=None):
    return apply(_l1_loss_raw, (input, label),
                 {"reduction": str(reduction)}, name="l1_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    return apply(_smooth_l1_loss_raw, (input, label),
                 {"reduction": str(reduction), "delta": float(delta)},
                 name="smooth_l1_loss")


def _binary_cross_entropy_raw(p, y, *maybe_w, reduction="mean"):
    per = -(y * jnp.log(jnp.maximum(p, 1e-12))
            + (1 - y) * jnp.log(jnp.maximum(1 - p, 1e-12)))
    if maybe_w:
        per = per * maybe_w[0]
    return _reduce_loss(per, reduction)


register_op("binary_cross_entropy", _binary_cross_entropy_raw)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    args = (input, label) if weight is None else (input, label, weight)
    return apply(_binary_cross_entropy_raw, args,
                 {"reduction": str(reduction)}, name="binary_cross_entropy")


def _bce_with_logits_raw(z, y, *rest, has_weight=False, has_pos_weight=False,
                         reduction="mean"):
    i = 0
    w = rest[i] if has_weight else None
    if has_weight:
        i += 1
    pw = rest[i] if has_pos_weight else None
    # numerically stable: max(z,0) - z*y + log(1+exp(-|z|))
    per = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    if pw is not None:
        log_w = (pw - 1) * y + 1
        per = per * log_w
    if w is not None:
        per = per * w
    return _reduce_loss(per, reduction)


register_op("bce_with_logits", _bce_with_logits_raw)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    args = [logit, label]
    if weight is not None:
        args.append(weight)
    if pos_weight is not None:
        args.append(pos_weight)
    return apply(_bce_with_logits_raw, tuple(args),
                 {"has_weight": weight is not None,
                  "has_pos_weight": pos_weight is not None,
                  "reduction": str(reduction)}, name="bce_with_logits")


def _kl_div_raw(logp, y, reduction="mean"):
    per = y * (jnp.log(jnp.maximum(y, 1e-12)) - logp)
    if reduction == "batchmean":
        return jnp.sum(per) / logp.shape[0]
    return _reduce_loss(per, reduction)


register_op("kl_div", _kl_div_raw)


def kl_div(input, label, reduction="mean", name=None):
    return apply(_kl_div_raw, (input, label),
                 {"reduction": str(reduction)}, name="kl_div")


def _margin_ranking_loss_raw(a, b, y, margin=0.0, reduction="mean"):
    per = jnp.maximum(-y * (a - b) + margin, 0.0)
    return _reduce_loss(per, reduction)


register_op("margin_ranking_loss", _margin_ranking_loss_raw)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return apply(_margin_ranking_loss_raw, (input, other, label),
                 {"margin": float(margin), "reduction": str(reduction)},
                 name="margin_ranking_loss")


def _hinge_embedding_loss_raw(a, y, margin=1.0, reduction="mean"):
    per = jnp.where(y == 1, a, jnp.maximum(margin - a, 0.0))
    return _reduce_loss(per, reduction)


register_op("hinge_embedding_loss", _hinge_embedding_loss_raw)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return apply(_hinge_embedding_loss_raw, (input, label),
                 {"margin": float(margin), "reduction": str(reduction)},
                 name="hinge_embedding_loss")


def _cosine_similarity_raw(a, b, axis=1, eps=1e-8):
    num = jnp.sum(a * b, axis=axis)
    den = jnp.maximum(jnp.linalg.norm(a, axis=axis)
                      * jnp.linalg.norm(b, axis=axis), eps)
    return num / den


register_op("cosine_similarity", _cosine_similarity_raw)


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None):
    return apply(_cosine_similarity_raw, (x1, x2),
                 {"axis": int(axis), "eps": float(eps)},
                 name="cosine_similarity")


def _square_error_cost_raw(a, b):
    return jnp.square(a - b)


register_op("square_error_cost", _square_error_cost_raw)


def square_error_cost(input, label):
    return apply(_square_error_cost_raw, (input, label),
                 name="square_error_cost")


def _sigmoid_focal_loss_raw(z, y, *maybe_n, alpha=0.25, gamma=2.0,
                            reduction="sum"):
    p = jax.nn.sigmoid(z)
    ce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    p_t = p * y + (1 - p) * (1 - y)
    a_t = alpha * y + (1 - alpha) * (1 - y)
    per = a_t * jnp.power(1 - p_t, gamma) * ce
    if maybe_n:
        per = per / maybe_n[0]
    return _reduce_loss(per, reduction)


register_op("sigmoid_focal_loss", _sigmoid_focal_loss_raw)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    args = (logit, label) if normalizer is None else (logit, label, normalizer)
    return apply(_sigmoid_focal_loss_raw, args,
                 {"alpha": float(alpha), "gamma": float(gamma),
                  "reduction": str(reduction)}, name="sigmoid_focal_loss")


# ----------------------------------------------------------------- padding etc.

def _pad_raw(a, pad=(), mode="constant", value=0.0, channels_first=True):
    p = [int(v) for v in pad]
    if len(p) == 2 * a.ndim:
        cfg = [(p[2 * i], p[2 * i + 1]) for i in range(a.ndim)]
    else:
        # paddle: pad applies to last len(p)//2 spatial dims
        # for NCHW 4-d input with 4 pads: [left,right,top,bottom] on W,H
        n_spatial = len(p) // 2
        cfg = [(0, 0)] * a.ndim
        if channels_first:
            dims = list(range(a.ndim - n_spatial, a.ndim))
        else:
            dims = list(range(1, 1 + n_spatial))
        # paddle order: innermost (last) dim first
        for i, d in enumerate(reversed(dims)):
            cfg[d] = (p[2 * i], p[2 * i + 1])
    jmode = {"constant": "constant", "reflect": "reflect",
             "replicate": "edge", "circular": "wrap"}[mode]
    if jmode == "constant":
        return jnp.pad(a, cfg, mode="constant", constant_values=value)
    return jnp.pad(a, cfg, mode=jmode)


register_op("pad", _pad_raw)


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    return apply(_pad_raw, (x,),
                 {"pad": [int(v) for v in pad], "mode": str(mode),
                  "value": float(value),
                  "channels_first": data_format.startswith("NC")}, name="pad")


def _unfold_raw(a, k=(1, 1), s=(1, 1), p=(0, 0), d=(1, 1)):
    k, s, p, d = (tuple(v) for v in (k, s, p, d))
    n, c, h, w = a.shape
    patches = lax.conv_general_dilated_patches(
        a, filter_shape=k, window_strides=s,
        padding=[(p[0], p[0]), (p[1], p[1])], rhs_dilation=d,
        dimension_numbers=lax.conv_dimension_numbers(
            a.shape, (c, c, k[0], k[1]), ("NCHW", "OIHW", "NCHW")))
    # -> [N, C*kh*kw, L]
    return patches.reshape(n, c * k[0] * k[1], -1)


register_op("unfold", _unfold_raw)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    return apply(_unfold_raw, (x,),
                 {"k": list(_norm_tuple(kernel_sizes, 2)),
                  "s": list(_norm_tuple(strides, 2)),
                  "p": list(_norm_tuple(paddings, 2)),
                  "d": list(_norm_tuple(dilations, 2))}, name="unfold")


def _interp_axis_coords(out_n, in_n, align_corners, align_mode=0):
    """Source coordinates for each output index along one axis.
    align_corners=True maps endpoints to endpoints (ref interpolate_op.h
    align_corners branch; ratio 0 when out_n <= 1, selecting pixel 0);
    False uses half-pixel centers when align_mode=0, or the fluid
    asymmetric rule src = i * in/out when align_mode=1 (the reference's
    `align_flag = align_mode == 0 && !align_corners` gate — the default
    for the 1.x resize_bilinear/resize_trilinear builders)."""
    if align_corners:
        ratio = (in_n - 1) / (out_n - 1) if out_n > 1 else 0.0
        return jnp.arange(out_n) * ratio
    scale = in_n / out_n
    if align_mode == 1:
        return jnp.arange(out_n) * scale
    return jnp.maximum((jnp.arange(out_n) + 0.5) * scale - 0.5, 0.0)


def _interp_linear_1axis(a, axis, out_n, align_corners, align_mode=0):
    """Linear resample of one axis by gather + lerp (any rank)."""
    in_n = a.shape[axis]
    c = _interp_axis_coords(out_n, in_n, align_corners, align_mode)
    lo = jnp.clip(jnp.floor(c).astype(jnp.int32), 0, in_n - 1)
    hi = jnp.clip(lo + 1, 0, in_n - 1)
    w = (c - lo).astype(a.dtype)
    lo_v = jnp.take(a, lo, axis=axis)
    hi_v = jnp.take(a, hi, axis=axis)
    shape = [1] * a.ndim
    shape[axis] = out_n
    return lo_v * (1.0 - w.reshape(shape)) + hi_v * w.reshape(shape)


def _interp_nearest_1axis(a, axis, out_n, align_corners):
    """Reference nearest_interp index rule (ref interpolate_op.h
    NearestNeighborInterpolate): floor(i*in/out) without align,
    floor(i*ratio + 0.5) with align_corners."""
    in_n = a.shape[axis]
    i = jnp.arange(out_n)
    if align_corners:
        ratio = (in_n - 1) / (out_n - 1) if out_n > 1 else 0.0
        idx = jnp.floor(i * ratio + 0.5)
    else:
        idx = jnp.floor(i * (in_n / out_n))
    return jnp.take(a, jnp.clip(idx.astype(jnp.int32), 0, in_n - 1),
                    axis=axis)


def _interp_cubic_1axis(a, axis, out_n, align_corners):
    """Cubic (Keys a=-0.75) resample of one axis with 4-tap gathers —
    honors align_corners, unlike jax.image.resize (ref bicubic_interp's
    cubic_interp1d)."""
    in_n = a.shape[axis]
    if align_corners:
        ratio = (in_n - 1) / (out_n - 1) if out_n > 1 else 0.0
        c = jnp.arange(out_n) * ratio
    else:
        # unclamped half-pixel coords: the reference (and torch) only clamp
        # for the linear family; cubic keeps negative fractions at borders
        c = (jnp.arange(out_n) + 0.5) * (in_n / out_n) - 0.5
    base = jnp.floor(c).astype(jnp.int32)
    t = (c - base).astype(a.dtype)
    A = -0.75

    def k1(x):      # |x| <= 1
        return ((A + 2.0) * x - (A + 3.0)) * x * x + 1.0

    def k2(x):      # 1 < |x| < 2
        return ((A * x - 5.0 * A) * x + 8.0 * A) * x - 4.0 * A

    ws = [k2(t + 1.0), k1(t), k1(1.0 - t), k2(2.0 - t)]
    shape = [1] * a.ndim
    shape[axis] = out_n
    out = None
    for tap, w in zip((-1, 0, 1, 2), ws):
        v = jnp.take(a, jnp.clip(base + tap, 0, in_n - 1), axis=axis)
        term = v * w.reshape(shape)
        out = term if out is None else out + term
    return out


def _interpolate_raw(a, size=None, scale_factor=None, mode="nearest",
                     channels_last=False, align_corners=False,
                     align_mode=0):
    """All reference interp op families on one raw (ref operators/
    interpolate_op.cc + interpolate_v2: linear [NCW], bilinear/nearest/
    bicubic/area [NCHW], trilinear [NCDHW]); align_corners honored for the
    nearest/linear family via explicit source-grid gathers."""
    n_spatial = a.ndim - 2
    sp_axes = tuple(range(1, 1 + n_spatial)) if channels_last \
        else tuple(range(2, 2 + n_spatial))
    spatial = tuple(a.shape[ax] for ax in sp_axes)
    if size is not None:
        out_sp = tuple(int(v) for v in (
            size if isinstance(size, (list, tuple)) else [size] * n_spatial))
    else:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) \
            else (scale_factor,) * n_spatial
        out_sp = tuple(int(s * f) for s, f in zip(spatial, sf))
    if mode in ("linear", "bilinear", "trilinear"):
        out = a
        for ax, o in zip(sp_axes, out_sp):
            out = _interp_linear_1axis(out, ax, o, align_corners,
                                       align_mode)
        return out
    if mode == "nearest":
        out = a
        for ax, o in zip(sp_axes, out_sp):
            out = _interp_nearest_1axis(out, ax, o, align_corners)
        return out
    if mode == "bicubic":
        out = a
        for ax, o in zip(sp_axes, out_sp):
            out = _interp_cubic_1axis(out, ax, o, align_corners)
        return out
    # area: jax.image.resize antialiased linear (half-pixel semantics)
    shape = list(a.shape)
    for ax, o in zip(sp_axes, out_sp):
        shape[ax] = o
    return jax.image.resize(a, tuple(shape), method="linear")


register_op("interpolate", _interpolate_raw)


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    nd = as_array(x).ndim - 2
    if size is not None:
        size = [int(v) for v in
                (size.tolist() if isinstance(size, Tensor) else size)] \
            if not isinstance(size, numbers.Number) else [int(size)] * nd
    if isinstance(scale_factor, (list, tuple)):
        scale_factor = [float(v) for v in scale_factor]
    elif scale_factor is not None:
        scale_factor = float(scale_factor)
    return apply(_interpolate_raw, (x,),
                 {"size": size, "scale_factor": scale_factor,
                  "mode": str(mode),
                  "channels_last": data_format in ("NHWC", "NWC", "NDHWC"),
                  "align_corners": bool(align_corners),
                  "align_mode": int(align_mode)},
                 name="interpolate")


upsample = interpolate


def _pixel_shuffle_raw(a, r=1):
    n, c, h, w = a.shape
    oc = c // (r * r)
    out = a.reshape(n, oc, r, r, h, w)
    out = out.transpose(0, 1, 4, 2, 5, 3)
    return out.reshape(n, oc, h * r, w * r)


register_op("pixel_shuffle", _pixel_shuffle_raw)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    return apply(_pixel_shuffle_raw, (x,), {"r": int(upscale_factor)},
                 name="pixel_shuffle")


def _temporal_shift_raw(a, seg_num=1, shift_ratio=0.25):
    nt, c, h, w = a.shape
    n = nt // seg_num
    r = a.reshape(n, seg_num, c, h, w)
    fold = int(c * shift_ratio)
    left = jnp.concatenate([r[:, 1:, :fold], jnp.zeros_like(r[:, -1:, :fold])],
                           axis=1)
    right = jnp.concatenate([jnp.zeros_like(r[:, :1, fold:2 * fold]),
                             r[:, :-1, fold:2 * fold]], axis=1)
    rest = r[:, :, 2 * fold:]
    return jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)


register_op("temporal_shift", _temporal_shift_raw)


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return apply(_temporal_shift_raw, (x,),
                 {"seg_num": int(seg_num), "shift_ratio": float(shift_ratio)},
                 name="temporal_shift")


def _grid_sample_raw(a, g, padding_mode="zeros", align_corners=True):
    n, c, h, w = a.shape
    gx = (g[..., 0] + 1) * (w - 1) / 2 if align_corners \
        else ((g[..., 0] + 1) * w - 1) / 2
    gy = (g[..., 1] + 1) * (h - 1) / 2 if align_corners \
        else ((g[..., 1] + 1) * h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1

    def sample(yy, xx):
        yy_c = jnp.clip(yy, 0, h - 1)
        xx_c = jnp.clip(xx, 0, w - 1)
        v = a[jnp.arange(n)[:, None, None], :, yy_c, xx_c]  # [N,Hg,Wg,C]
        if padding_mode == "zeros":
            inb = ((yy >= 0) & (yy < h) & (xx >= 0) & (xx < w))[..., None]
            v = jnp.where(inb, v, 0.0)
        return v

    wa = ((x1 - gx) * (y1 - gy))[..., None]
    wb = ((x1 - gx) * (gy - y0))[..., None]
    wc = ((gx - x0) * (y1 - gy))[..., None]
    wd = ((gx - x0) * (gy - y0))[..., None]
    out = (sample(y0, x0) * wa + sample(y1, x0) * wb
           + sample(y0, x1) * wc + sample(y1, x1) * wd)
    return out.transpose(0, 3, 1, 2)


register_op("grid_sample", _grid_sample_raw)


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    return apply(_grid_sample_raw, (x, grid),
                 {"padding_mode": str(padding_mode),
                  "align_corners": bool(align_corners)}, name="grid_sample")


def _affine_grid_raw(th, out_shape=(), align_corners=True):
    n, _, h, w = [int(v) for v in out_shape]
    if align_corners:
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
    else:
        ys = (jnp.arange(h) * 2 + 1) / h - 1
        xs = (jnp.arange(w) * 2 + 1) / w - 1
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # [H,W,3]
    return jnp.einsum("nij,hwj->nhwi", th, base)


register_op("affine_grid", _affine_grid_raw)


def affine_grid(theta, out_shape, align_corners=True, name=None):
    shape = [int(v) for v in (out_shape.tolist()
                              if isinstance(out_shape, Tensor) else out_shape)]
    return apply(_affine_grid_raw, (theta,),
                 {"out_shape": shape, "align_corners": bool(align_corners)},
                 name="affine_grid")


def _label_smooth_raw(y, *maybe_p, epsilon=0.1):
    k = y.shape[-1]
    if maybe_p:
        return (1 - epsilon) * y + epsilon * maybe_p[0]
    return (1 - epsilon) * y + epsilon / k


register_op("label_smooth", _label_smooth_raw)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    args = (label,) if prior_dist is None else (label, prior_dist)
    return apply(_label_smooth_raw, args, {"epsilon": float(epsilon)},
                 name="label_smooth")


def _npair_loss_raw(a, p, y, l2_reg=0.002):
    sim = jnp.matmul(a, p.T)
    same = (y[:, None] == y[None, :]).astype(a.dtype)
    same = same / jnp.sum(same, axis=1, keepdims=True)
    ce = jnp.mean(-jnp.sum(same * jax.nn.log_softmax(sim, axis=1), axis=1))
    reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(a), 1))
                    + jnp.mean(jnp.sum(jnp.square(p), 1))) * 0.25
    return ce + reg


register_op("npair_loss", _npair_loss_raw)


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    return apply(_npair_loss_raw, (anchor, positive, labels),
                 {"l2_reg": float(l2_reg)}, name="npair_loss")


def _diag_embed_raw(a):
    out = jnp.zeros(a.shape + (a.shape[-1],), a.dtype)
    idx = jnp.arange(a.shape[-1])
    return out.at[..., idx, idx].set(a)


register_op("diag_embed", _diag_embed_raw)


def diag_embed(x, offset=0, dim1=-2, dim2=-1, name=None):
    return apply(_diag_embed_raw, (x,), name="diag_embed")


def _sequence_mask_raw(l, maxlen=1, out_dtype="int64"):
    return (jnp.arange(maxlen)[None, :] < l[:, None]).astype(
        convert_dtype(out_dtype))


register_op("sequence_mask", _sequence_mask_raw)


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    ml = int(maxlen) if maxlen is not None else int(np.asarray(
        as_array(lengths)).max())
    return apply(_sequence_mask_raw, (lengths,),
                 {"maxlen": ml, "out_dtype": str(dtype)},
                 differentiable=False, name="sequence_mask")


def _pairwise_distance_raw(x_, y_, p=2.0, keepdim=False):
    return jnp.linalg.norm(x_ - y_, ord=p, axis=-1, keepdims=keepdim)


register_op("pairwise_distance", _pairwise_distance_raw)


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    """ref nn/functional/distance.py: p-norm of (x - y) over the last
    axis. The reference's p_norm kernel uses `epsilon` only in the
    GRADIENT denominator (p_norm_op.h PnormGradKernel), never the
    forward — kept in the signature for API parity; autodiff handles the
    norm-at-zero subgradient here."""
    return apply(_pairwise_distance_raw, (x, y),
                 {"p": float(p), "keepdim": bool(keepdim)},
                 name="pairwise_distance")


def _ctc_loss_raw(lp, lab, in_len, lab_len, blank=0, reduction="mean",
                  norm_by_times=False):
    T, B, C = lp.shape
    Lmax = lab.shape[1]
    S = 2 * Lmax + 1
    logp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
    neg_inf = jnp.float32(-1e30)

    # extended label sequence l' = [blank, l1, blank, l2, ..., blank]
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lab.astype(jnp.int32))
    # transition-2 allowed where l'_s != blank and l'_s != l'_{s-2}
    ext_m2 = jnp.concatenate(
        [jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
    can_skip = (ext != blank) & (ext != ext_m2)

    def emit(t_logp):
        # t_logp: [B, C] -> per-extended-position emission [B, S]
        return jnp.take_along_axis(t_logp, ext, axis=1)

    alpha0 = jnp.full((B, S), neg_inf)
    e0 = emit(logp[0])
    alpha0 = alpha0.at[:, 0].set(e0[:, 0])
    if S > 1:      # Lmax=0 (all-blank targets) has only position 0
        alpha0 = alpha0.at[:, 1].set(jnp.where(lab_len > 0, e0[:, 1],
                                               neg_inf))

    def step(alpha, t_logp_t):
        t_logp, t = t_logp_t
        if S > 1:
            prev1 = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            prev2 = jnp.concatenate(
                [jnp.full((B, 2), neg_inf),
                 alpha[:, :max(S - 2, 0)]], axis=1)[:, :S]
            prev2 = jnp.where(can_skip, prev2, neg_inf)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, prev1), prev2)
        else:      # Lmax=0: only the all-blank path exists
            merged = alpha
        new = merged + emit(t_logp)
        # freeze finished samples (t >= input_length)
        active = (t < in_len)[:, None]
        return jnp.where(active, new, alpha), None

    ts = jnp.arange(1, T)
    alpha, _ = jax.lax.scan(step, alpha0, (logp[1:], ts))

    # final: logsumexp of positions S-1 (last blank) and S-2 (last label)
    s_last = 2 * lab_len.astype(jnp.int32)        # index of last blank
    a_last = jnp.take_along_axis(alpha, s_last[:, None], axis=1)[:, 0]
    s_lab = jnp.maximum(s_last - 1, 0)
    a_lab = jnp.where(
        lab_len > 0,
        jnp.take_along_axis(alpha, s_lab[:, None], axis=1)[:, 0],
        neg_inf)
    nll = -jnp.logaddexp(a_last, a_lab)
    if norm_by_times:
        nll = nll / jnp.maximum(in_len.astype(jnp.float32), 1.0)
    if reduction == "mean":
        # paddle mean: divide per-sample loss by label_length first
        return jnp.mean(nll / jnp.maximum(
            lab_len.astype(jnp.float32), 1.0))
    if reduction == "sum":
        return jnp.sum(nll)
    return nll


register_op("ctc_loss", _ctc_loss_raw)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC loss (ref operators/warpctc_op.cc / paddle.nn.functional.ctc_loss).

    log_probs: [T, B, C] RAW logits (log_softmax applied internally, like
    the reference's warpctc). labels: [B, Lmax] padded int labels.
    input_lengths/label_lengths: [B] ints.

    TPU-native: the alpha recursion runs as one lax.scan over time in log
    space with static shapes ([B, 2*Lmax+1] state); per-sample lengths are
    handled by masking, so one compiled program serves the whole batch.
    Gradients come from autodiff through the scan (the reference ships a
    hand-written backward; XLA differentiates the recursion directly).
    """
    return apply(_ctc_loss_raw,
                 (log_probs, labels, input_lengths, label_lengths),
                 {"blank": int(blank), "reduction": str(reduction),
                  "norm_by_times": bool(norm_by_times)}, name="ctc_loss")


def _gather_tree_raw(ids_, par_):
    T, B, K = ids_.shape
    par_ = par_.astype(jnp.int32)

    def step(beams, xs):
        ids_t, par_t = xs
        out_t = jnp.take_along_axis(ids_t, beams, axis=-1)
        prev = jnp.take_along_axis(par_t, beams, axis=-1)
        return prev, out_t

    init = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32), (B, K))
    _, outs = jax.lax.scan(step, init, (ids_, par_), reverse=True)
    return outs


register_op("gather_tree", _gather_tree_raw)


def gather_tree(ids, parents):
    """Reconstruct full beam-search sequences from per-step token ids and
    parent beam indices (ref operators/gather_tree_op.cc; both [T, B, K]).
    TPU-native: one reverse lax.scan walking the parent chain — no
    per-(batch, beam) host loops."""
    return apply(_gather_tree_raw, (ids, parents), differentiable=False,
                 name="gather_tree")


# --------------------------------------------------------------- round-3 tail
# (last nn.functional gaps vs ref python/paddle/nn/functional: 1d/3d pools,
# 1d/3d transposed convs, log_sigmoid/thresholded_relu, hsigmoid_loss,
# inplace variants)

def _log_sigmoid_raw(a):
    return jax.nn.log_sigmoid(a)


def _thresholded_relu_raw(a, threshold=1.0):
    return jnp.where(a > threshold, a, 0.0)


register_op("log_sigmoid", _log_sigmoid_raw)
register_op("thresholded_relu", _thresholded_relu_raw)


def log_sigmoid(x, name=None):
    return apply(_log_sigmoid_raw, (x,), name="log_sigmoid")


def thresholded_relu(x, threshold=1.0, name=None):
    return apply(_thresholded_relu_raw, (x,),
                 {"threshold": float(threshold)}, name="thresholded_relu")


def _inplace(x, out):
    x._data = out._data
    x._node, x._slot = out._node, out._slot
    return x


def relu_(x, name=None):
    return _inplace(x, relu(x))


def elu_(x, alpha=1.0, name=None):
    return _inplace(x, elu(x, alpha=alpha))


def softmax_(x, axis=-1, dtype=None, name=None):
    return _inplace(x, softmax(x, axis=axis, dtype=dtype))


register_op("max_pool3d", functools.partial(_poolnd_raw, n=3, average=False))
register_op("avg_pool3d", functools.partial(_poolnd_raw, n=3, average=True))


def _reject_pool_extras(data_format, canonical):
    if data_format not in (None, canonical):
        raise NotImplementedError(
            f"pooling: only {canonical} layout supported, got {data_format}")


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    _reject_pool_extras(data_format, "NCDHW")
    # NCDHW validated above = channels-first; _pool owns the attr build
    return _pool(x, kernel_size, stride, padding, "NCHW", "max_pool3d",
                 ceil_mode=ceil_mode)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               count_include_pad=True, divisor_override=None,
               data_format="NCDHW", name=None):
    _reject_pool_extras(data_format, "NCDHW")
    if divisor_override is not None:
        raise NotImplementedError("avg_pool3d: divisor_override unsupported")
    return _pool(x, kernel_size, stride, padding, "NCHW", "avg_pool3d",
                 ceil_mode=ceil_mode, count_include_pad=count_include_pad,
                 average=True)


def _adaptive_poolnd_raw(a, output_size=1, n=2, average=True):
    """Divisible-size adaptive pool for any spatial rank (reshape-reduce)."""
    out_sz = _norm_tuple(output_size, n)
    lead = a.shape[:a.ndim - n]
    spatial = a.shape[a.ndim - n:]
    shape = list(lead)
    red_axes = []
    for i, (s, o) in enumerate(zip(spatial, out_sz)):
        if s % o:
            raise NotImplementedError(
                "adaptive pooling with non-divisible sizes not supported")
        shape += [o, s // o]
        red_axes.append(len(lead) + 2 * i + 1)
    r = a.reshape(shape)
    return (r.mean(axis=tuple(red_axes)) if average
            else r.max(axis=tuple(red_axes)))


register_op("adaptive_avg_pool1d",
            functools.partial(_adaptive_poolnd_raw, n=1, average=True))
register_op("adaptive_max_pool1d",
            functools.partial(_adaptive_poolnd_raw, n=1, average=False))
register_op("adaptive_avg_pool3d",
            functools.partial(_adaptive_poolnd_raw, n=3, average=True))
register_op("adaptive_max_pool3d",
            functools.partial(_adaptive_poolnd_raw, n=3, average=False))


def _adaptive_pool_fn(opname):
    from ..ops.dispatch import OP_REGISTRY

    def fn(x, output_size, name=None, return_mask=False,
           data_format=None):
        if data_format not in (None, "NCL", "NCHW", "NCDHW"):
            raise NotImplementedError(
                f"{opname}: only channels-first layouts supported, "
                f"got {data_format}")
        return apply(OP_REGISTRY[opname], (x,),
                     {"output_size": _stride_attr(output_size)},
                     name=opname)
    fn.__name__ = opname
    return fn


adaptive_avg_pool1d = _adaptive_pool_fn("adaptive_avg_pool1d")
adaptive_max_pool1d = _adaptive_pool_fn("adaptive_max_pool1d")
adaptive_avg_pool3d = _adaptive_pool_fn("adaptive_avg_pool3d")
adaptive_max_pool3d = _adaptive_pool_fn("adaptive_max_pool3d")


def _convnd_transpose_raw(a, w, *maybe_b, n=2, stride=1, padding=0,
                          output_padding=0, dilation=1, groups=1):
    """N-d transposed conv, NCX layout, weight [in_c, out_c/g, *k]
    (generalizes the 2-d path; ref conv_transpose_op.cc)."""
    strides = _norm_tuple(stride, n)
    dilations = _norm_tuple(dilation, n)
    out_pad = _norm_tuple(output_padding, n)
    pad = _conv_padding(padding, n, strides, dilations, w.shape[2:])
    if isinstance(pad, str):
        if pad != "VALID":
            raise ValueError("SAME padding unsupported for conv_transpose")
        pad = [(0, 0)] * n
    keff = [((w.shape[2 + i] - 1) * dilations[i] + 1) for i in range(n)]
    trans_pad = [(keff[i] - 1 - pad[i][0],
                  keff[i] - 1 - pad[i][1] + out_pad[i]) for i in range(n)]
    w_flip = jnp.flip(w, axis=tuple(range(2, 2 + n)))
    spatial = "DHW"[3 - n:]
    dn_str = ("NC" + spatial, "OI" + spatial, "NC" + spatial)
    perm = (1, 0) + tuple(range(2, 2 + n))

    def one(a_g, w_g):
        w_t = jnp.transpose(w_g, perm)       # -> [out_c/g, in_c/g, *k]
        dn = lax.conv_dimension_numbers(a_g.shape, w_t.shape, dn_str)
        return lax.conv_general_dilated(
            a_g, w_t, window_strides=(1,) * n, padding=trans_pad,
            lhs_dilation=strides, rhs_dilation=dilations,
            dimension_numbers=dn)

    if groups == 1:
        out = one(a, w_flip)
    else:
        icg = a.shape[1] // groups
        out = jnp.concatenate(
            [one(a[:, g * icg:(g + 1) * icg],
                 w_flip[g * icg:(g + 1) * icg]) for g in range(groups)],
            axis=1)
    if maybe_b:
        out = out + maybe_b[0].reshape((1, -1) + (1,) * n)
    return out


register_op("conv1d_transpose",
            functools.partial(_convnd_transpose_raw, n=1))
register_op("conv3d_transpose",
            functools.partial(_convnd_transpose_raw, n=3))


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    from ..ops.dispatch import OP_REGISTRY
    if data_format != "NCL":
        raise NotImplementedError(
            f"conv1d_transpose: only NCL supported, got {data_format}")
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(OP_REGISTRY["conv1d_transpose"], args,
                 {"stride": _stride_attr(stride), "padding": _pad_attr(padding),
                  "output_padding": _stride_attr(output_padding),
                  "dilation": _stride_attr(dilation), "groups": int(groups)},
                 name="conv1d_transpose")


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    from ..ops.dispatch import OP_REGISTRY
    if data_format != "NCDHW":
        raise NotImplementedError(
            f"conv3d_transpose: only NCDHW supported, got {data_format}")
    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(OP_REGISTRY["conv3d_transpose"], args,
                 {"stride": _stride_attr(stride), "padding": _pad_attr(padding),
                  "output_padding": _stride_attr(output_padding),
                  "dilation": _stride_attr(dilation), "groups": int(groups)},
                 name="conv3d_transpose")


def bilinear(x1, x2, weight, bias=None, name=None):
    """ref nn/functional/common.py bilinear: out[b,o] = x1 W_o x2 + b."""
    from ..nn.layers_common import _bilinear_raw
    args = (x1, x2, weight) if bias is None else (x1, x2, weight, bias)
    return apply(_bilinear_raw, args, name="bilinear")


def _hsigmoid_loss_raw(x, lab, w, *maybe_b, num_classes=2):
    """Hierarchical sigmoid over the default COMPLETE binary tree (ref
    hierarchical_sigmoid_op.cc without custom paths): internal nodes are
    1..C-1 heap-style; class c maps to leaf c + (C-1); the loss is the
    sum of binary CE along the root->leaf path. Static shapes: every path
    is padded to ceil(log2(C)) with zero-weight steps."""
    C = num_classes
    depth = max(int(np.ceil(np.log2(max(C, 2)))), 1)
    leaf = lab.reshape(-1).astype(jnp.int32) + (C - 1)   # accepts [N] or [N,1]
    losses = jnp.zeros(x.shape[0], jnp.float32)
    node = leaf
    for _ in range(depth):
        parent = (node - 1) // 2
        is_right = (node % 2 == 0) & (node > 0)
        valid = node > 0
        # internal-node weight row: parent index in [0, C-1)
        row = jnp.clip(parent, 0, C - 2)
        z = jnp.einsum("nd,nd->n", x, w[row])
        if maybe_b:
            z = z + maybe_b[0].reshape(-1)[row]
        t = is_right.astype(jnp.float32)
        bce = jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))
        losses = losses + jnp.where(valid, bce, 0.0)
        node = parent
    return losses[:, None]


register_op("hsigmoid_loss", _hsigmoid_loss_raw)


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    if path_table is not None or path_code is not None:
        raise NotImplementedError(
            "hsigmoid_loss: custom path tables not supported (default "
            "complete-binary-tree only)")
    args = (input, label, weight) if bias is None \
        else (input, label, weight, bias)
    return apply(_hsigmoid_loss_raw, args, {"num_classes": int(num_classes)},
                 name="hsigmoid_loss")


def _deform_conv2d_raw(x, offset, w, *rest, stride=1, padding=0, dilation=1,
                       has_mask=False, has_bias=False):
    """Deformable conv v1/v2 (ref operators/deformable_conv_op.h;
    static.nn.deform_conv2d). deformable_groups=1, groups=1.

    x [N,C,H,W]; offset [N, 2*kh*kw, H',W'] as (dy,dx) pairs; w
    [Co,C,kh,kw]; optional mask [N, kh*kw, H',W'] (v2 modulation) and
    bias [Co]. TPU-native: the kernel-offset sampling grid is built
    densely and gathered with ONE take_along_axis per corner — bilinear
    interpolation as four fused gathers, no per-position loops."""
    mask = rest[0] if has_mask else None
    b = rest[-1] if has_bias else None
    n_, c, h, w_in = x.shape
    co, _, kh, kw = w.shape
    sh, sw = _norm_tuple(stride, 2)
    ph, pw = _norm_tuple(padding, 2)
    dh, dw = _norm_tuple(dilation, 2)
    ho = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    wo = (w_in + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    K = kh * kw

    oi = jnp.arange(ho)[:, None]                  # output rows
    oj = jnp.arange(wo)[None, :]
    ku, kv = jnp.meshgrid(jnp.arange(kh), jnp.arange(kw), indexing="ij")
    base_y = (oi * sh - ph)[None, :, :] + (ku.reshape(-1) * dh)[:, None, None]
    base_x = (oj * sw - pw)[None, :, :] + (kv.reshape(-1) * dw)[:, None, None]
    off = offset.reshape(n_, K, 2, ho, wo)
    ys = base_y[None] + off[:, :, 0]              # [N,K,H',W']
    xs = base_x[None] + off[:, :, 1]

    y0 = jnp.floor(ys)
    x0 = jnp.floor(xs)
    wy = ys - y0
    wx = xs - x0

    def gather(yy, xx):
        inb = ((yy >= 0) & (yy < h) & (xx >= 0) & (xx < w_in))
        yc = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xc = jnp.clip(xx, 0, w_in - 1).astype(jnp.int32)
        flat = (yc * w_in + xc).reshape(n_, 1, -1)        # [N,1,K*H'*W']
        got = jnp.take_along_axis(x.reshape(n_, c, h * w_in), flat, axis=2)
        got = got.reshape(n_, c, K, ho, wo)
        return jnp.where(inb[:, None], got, 0.0)

    sampled = ((1 - wy) * (1 - wx))[:, None] * gather(y0, x0) \
        + ((1 - wy) * wx)[:, None] * gather(y0, x0 + 1) \
        + (wy * (1 - wx))[:, None] * gather(y0 + 1, x0) \
        + (wy * wx)[:, None] * gather(y0 + 1, x0 + 1)     # [N,C,K,H',W']
    if mask is not None:
        sampled = sampled * mask[:, None]
    out = jnp.einsum("nckij,ock->noij", sampled,
                     w.reshape(co, c, K),
                     preferred_element_type=jnp.float32).astype(x.dtype)
    if b is not None:
        out = out + b.reshape(1, -1, 1, 1)
    return out


register_op("deform_conv2d", _deform_conv2d_raw)


def deform_conv2d(x, offset, weight, bias=None, stride=1, padding=0,
                  dilation=1, deformable_groups=1, groups=1, mask=None,
                  name=None):
    if deformable_groups != 1 or groups != 1:
        raise NotImplementedError(
            "deform_conv2d: deformable_groups/groups > 1 unsupported")
    args = [x, offset, weight]
    if mask is not None:
        args.append(mask)
    if bias is not None:
        args.append(bias)
    return apply(_deform_conv2d_raw, tuple(args),
                 {"stride": _stride_attr(stride), "padding": _pad_attr(padding),
                  "dilation": _stride_attr(dilation),
                  "has_mask": mask is not None, "has_bias": bias is not None},
                 name="deform_conv2d")
