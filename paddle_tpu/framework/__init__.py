from .dtype import (float16, bfloat16, float32, float64, int8, int16, int32,
                    int64, uint8, bool_, complex64, complex128, convert_dtype,
                    dtype_name, is_floating_point, is_integer)
from .state import (Place, CPUPlace, TPUPlace, CUDAPlace, XPUPlace, set_device,
                    get_device, get_place, seed, default_generator, next_rng_key,
                    set_flags, get_flags, get_flag, no_grad, no_grad_ctx,
                    enable_grad_ctx, functional_mode_ctx, is_grad_enabled,
                    is_functional_mode, set_default_dtype, get_default_dtype)
from .tensor import Tensor, Parameter, to_tensor
from . import tape
from . import errors
from .errors import enforce, enforce_eq, enforce_shape


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Top-level paddle.create_parameter (ref python/paddle/__init__.py:237
    alias of fluid framework.create_parameter): a fresh trainable Parameter,
    Xavier-normal by default, zeros when is_bias."""
    from ..nn import initializer as I
    init = default_initializer
    if init is None and attr is not None and getattr(attr, "initializer",
                                                    None) is not None:
        init = attr.initializer
    if init is None:
        init = I.Constant(0.0) if is_bias else I.XavierNormal()
    p = Parameter(init(tuple(shape), dtype),
                  name=name or (getattr(attr, "name", None) if attr else None))
    if attr is not None and getattr(attr, "regularizer", None) is not None:
        p.regularizer = attr.regularizer
    return p
