"""Eager op dispatch.

TPU-native analog of the reference dygraph fast path
(ref paddle/fluid/imperative/tracer.cc:132 Tracer::TraceOp +
prepared_operator.cc kernel choice): an op is a pure-JAX function; dispatching it
eagerly means calling it on jax.Arrays (XLA compiles + caches per shape/dtype —
that cache replaces the reference's OpKernelType registry lookup). If any input
requires grad, the forward runs under jax.vjp and a GradNode is recorded
(ref tracer.cc:205 CreateGradOpNode).

Under functional mode (jax.jit / jax.grad tracing of a whole train step), the tape
is bypassed entirely and autodiff belongs to JAX — the performance path that turns
a dygraph model into one fused XLA program (the dy2static analog; ref
dygraph_to_static/program_translator.py:233).
"""
import functools

import jax
import jax.numpy as jnp

from ..framework import state
from ..framework.tensor import Tensor
from ..framework.tape import GradNode

# op-name -> python impl; consumed by the static-graph lowering (static/program.py)
OP_REGISTRY = {}


def register_op(name, fn):
    """Make `fn` the canonical raw impl for `name`, so desc ops recorded from
    apply(fn, ..., name=name) serialize (static/desc.py OpDesc.serializable:
    the recorded fn must BE the registered one and attrs must be JSON-able)."""
    OP_REGISTRY[name] = fn
    return fn


def axis_attr(axis):
    """Normalize an axis argument to its JSON-able desc-attr form (list or
    int) — the shared half of the desc serialization contract; raw impls
    convert back with axis_arg."""
    if isinstance(axis, (list, tuple)):
        return [int(a) for a in axis]
    return None if axis is None else int(axis)


def axis_arg(axis):
    """Inverse of axis_attr inside raw impls: JSON list -> tuple for jnp."""
    return tuple(axis) if isinstance(axis, list) else axis

# AMP op lists (ref python/paddle/fluid/contrib/mixed_precision/fp16_lists.py):
# white = compute-bound MXU ops run in low precision; black = numerically
# sensitive ops kept f32. Everything else follows its inputs.
AMP_WHITE_LIST = {
    "matmul", "mm", "bmm", "linear", "conv1d", "conv2d", "conv3d",
    "conv2d_transpose", "einsum", "addmm", "flash_attention",
}
AMP_BLACK_LIST = {
    "softmax", "log_softmax", "cross_entropy", "nll_loss", "exp", "log",
    "log2", "log10", "log1p", "mean", "sum", "logsumexp", "layer_norm",
    "batch_norm", "group_norm", "instance_norm", "norm", "cumsum", "prod",
    "sigmoid_focal_loss", "bce_with_logits", "binary_cross_entropy", "erf",
    "erfinv", "pow", "square", "std", "var", "kl_div",
}


def _amp_cast(arrays, name, amp):
    import jax.numpy as jnp
    low = amp["dtype"]
    if name in AMP_WHITE_LIST:
        return tuple(a.astype(low)
                     if hasattr(a, "dtype") and a.dtype == jnp.float32 else a
                     for a in arrays)
    if name in AMP_BLACK_LIST:
        return tuple(a.astype(jnp.float32)
                     if hasattr(a, "dtype") and a.dtype == low else a
                     for a in arrays)
    # gray ops: follow inputs (no cast)
    return arrays


def as_array(x):
    if isinstance(x, Tensor):
        return x._data
    return x


def _requires_grad(t):
    return isinstance(t, Tensor) and not t.stop_gradient


def _wrap_outputs(outs, multi, requires_grad):
    if multi:
        res = tuple(Tensor(o, stop_gradient=not requires_grad) for o in outs)
        return res
    return Tensor(outs, stop_gradient=not requires_grad)


def _check_nan_inf(name, outs):
    """Per-op non-finite scan, eager only (ref platform/flags.cc:44
    FLAGS_check_nan_inf + details/nan_inf_utils_detail.cu — the device-side
    reduction becomes one jnp.isfinite fused reduce per output)."""
    for i, o in enumerate(outs):
        if isinstance(o, jax.core.Tracer):
            return  # traced: use jax.debug/checkify instead
        if hasattr(o, "dtype") and jnp.issubdtype(o.dtype, jnp.inexact):
            if not bool(jnp.all(jnp.isfinite(o))):
                from ..framework.errors import PreconditionNotMetError
                raise PreconditionNotMetError(
                    f"Operator {name} output {i} contains NaN/Inf "
                    f"(FLAGS_check_nan_inf is on)")


# Ops with no TPU lowering (complex dtypes: the backend returns
# UNIMPLEMENTED — measured by the on-chip registry sweep,
# scripts/op_sweep_tpu.py). In eager mode these fall back to the host
# CPU, the analog of the reference's CPUPlace kernel fallback (ref
# paddle/fluid/framework/operator.cc ChooseKernel: when no kernel exists
# for the requested place, the op runs on CPUPlace). Complex outputs
# stay on host (accelerators cannot hold complex buffers); real-dtyped
# outputs transfer back to the default device so downstream device ops
# continue unchanged. Inside jit (functional mode) there is no fallback
# — a traced program is single-platform by construction.
HOST_FALLBACK_OPS = {
    # real -> complex producers (inputs are real, so the dtype check
    # below cannot catch them); consumers of complex inputs (real, imag,
    # conj, angle, abs, as_real, ...) are caught by iscomplexobj instead
    # — on real-dtyped inputs those ops lower fine on the TPU and must
    # NOT pay a host round-trip
    "complex", "polar", "as_complex",
}


def _default_backend():
    """Seam for tests: the live default jax backend name."""
    return jax.default_backend()


def _host_fallback(f):
    """Wrap a raw op impl to execute on the host CPU device."""
    @functools.wraps(f)
    def run(*xs):
        cpu = jax.devices("cpu")[0]
        xs = tuple(jax.device_put(x, cpu) if hasattr(x, "dtype") else x
                   for x in xs)
        with jax.default_device(cpu):
            out = f(*xs)

        def back(o):
            if hasattr(o, "dtype") and not jnp.iscomplexobj(o):
                return jax.device_put(o, jax.devices()[0])
            return o
        if isinstance(out, (tuple, list)):
            return type(out)(back(o) for o in out)
        return back(out)
    return run


def apply(fn, tensors, attrs=None, name=None, differentiable=True):
    """Run op `fn(*arrays, **attrs)` on tensor inputs; record GradNode if needed."""
    attrs = attrs or {}
    if name is None:
        name = getattr(fn, "__name__", "op")
    arrays = tuple(as_array(t) for t in tensors)
    amp = state.get_amp_state()
    if amp is not None:
        arrays = _amp_cast(arrays, name, amp)
    if attrs:
        # dunder attrs (e.g. "__rng__") are recorder directives, not impl
        # kwargs — static/desc.py resolve_impl strips them the same way
        call_attrs = {k: v for k, v in attrs.items()
                      if not k.startswith("__")}
        f = functools.partial(fn, **call_attrs) if call_attrs else fn
    else:
        f = fn

    # f_rec is what recorders capture (static desc -> jit-compiled
    # Executor programs): ALWAYS the unwrapped impl — the fallback's
    # device_put/default_device must never be traced into a compiled
    # program (a traced program is single-platform by construction)
    f_rec = f
    if (not state.is_functional_mode()
            and _default_backend() != "cpu"
            and (name in HOST_FALLBACK_OPS
                 or any(jnp.iscomplexobj(a) for a in arrays
                        if hasattr(a, "dtype")))):
        f = _host_fallback(f)

    check = state.get_flag("FLAGS_check_nan_inf")
    rec = None if state.is_functional_mode() else state.get_static_recorder()

    def call(g, *xs):
        """Run the impl; on failure attach op name/inputs/attrs to the
        exception IN PLACE (type preserved) — the eager analog of ref
        framework/op_call_stack.cc (python tracebacks already carry the
        call stack; this adds the operator-level summary)."""
        try:
            return g(*xs)
        except Exception as e:
            if not getattr(e, "_pt_op_ctx", False):
                from ..framework.errors import attach_op_context
                attach_op_context(e, name, xs, attrs)
                e._pt_op_ctx = True
            raise

    if state.is_functional_mode() or not state.is_grad_enabled():
        outs = call(f, *arrays)
        multi = isinstance(outs, (tuple, list))
        if check:
            _check_nan_inf(name, tuple(outs) if multi else (outs,))
        # in functional mode JAX owns autodiff; stop_gradient only tracks lineage
        rg = (state.is_functional_mode() and differentiable
              and any(_requires_grad(t) for t in tensors))
        wrapped = _wrap_outputs(tuple(outs) if multi else outs, multi, rg)
        if rec is not None:
            rec.record_op(name, fn, f_rec, tensors, attrs, wrapped, multi,
                          differentiable)
        return wrapped

    needs_grad = differentiable and any(_requires_grad(t) for t in tensors)
    if not needs_grad:
        outs = call(f, *arrays)
        multi = isinstance(outs, (tuple, list))
        if check:
            _check_nan_inf(name, tuple(outs) if multi else (outs,))
        wrapped = _wrap_outputs(tuple(outs) if multi else outs, multi, False)
        if rec is not None:
            rec.record_op(name, fn, f_rec, tensors, attrs, wrapped, multi,
                          differentiable)
        return wrapped

    outs, vjp_fn = call(lambda *xs: jax.vjp(f, *xs), *arrays)
    if check:
        _check_nan_inf(name, tuple(outs) if isinstance(outs, (tuple, list))
                       else (outs,))
    multi = isinstance(outs, (tuple, list))
    outs_t = tuple(outs) if multi else (outs,)

    # non-diff inputs recorded as None so backward skips them
    node_inputs = [t if isinstance(t, Tensor) else None for t in tensors]
    node = GradNode(
        vjp=vjp_fn,
        inputs=node_inputs,
        n_outputs=len(outs_t),
        out_shapes=tuple(o.shape for o in outs_t),
        out_dtypes=tuple(o.dtype for o in outs_t),
        name=name or getattr(fn, "__name__", "op"),
        fn=f,                 # replayable impl for create_graph double-grad
        primals=arrays,
    )
    wrapped = _wrap_outputs(outs_t if multi else outs_t[0], multi, True)
    ws = wrapped if multi else (wrapped,)
    for i, w in enumerate(ws):
        w._node = node
        w._slot = i
    if rec is not None:
        rec.record_op(name, fn, f_rec, tensors, attrs, wrapped, multi,
                      differentiable)
    return wrapped


def def_op(name=None, differentiable=True, n_tensor_args=None):
    """Register + wrap a pure-JAX impl as an eager op.

    The wrapped function accepts Tensors/arrays for its first `n_tensor_args`
    positional args (default: all positional) and keyword attrs after that.
    """

    def deco(fn):
        opname = name or fn.__name__
        OP_REGISTRY[opname] = fn

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if n_tensor_args is None:
                tensors = args
                attrs = kwargs
            else:
                tensors = args[:n_tensor_args]
                attrs = dict(kwargs)
                # extra positionals beyond tensor args are attrs by position — not
                # supported; keep the call sites keyword-only for attrs
                if len(args) > n_tensor_args:
                    raise TypeError(
                        f"{opname}: pass attrs as keywords (got extra positionals)")
            return apply(fn, tensors, attrs, name=opname,
                         differentiable=differentiable)

        wrapper.raw = fn
        return wrapper

    return deco
