"""paddle_tpu.jit — dygraph -> compiled execution.

TPU-native answer to the reference's two compilation paths:
  - dy2static AST transpiler (ref fluid/dygraph/dygraph_to_static/
    program_translator.py:233): here `to_static` needs no AST surgery — the
    layer's python forward IS the trace function; jax.jit traces it through
    functional_call and XLA owns fusion/scheduling.
  - ParallelExecutor/CompiledProgram (ref compiler.py:164): `TrainStep`
    compiles forward+backward+optimizer into ONE donated XLA executable —
    params/opt-state update in place on HBM, host does a single dispatch per
    step (vs. the reference's per-op C++ loop, executor.cc:414).
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp

from ..framework import state
from ..framework.tensor import Tensor
from ..nn.layer import Layer
from ..utils import chaos, telemetry
from ..utils.profiler import RecordEvent


def _unwrap(x):
    if isinstance(x, Tensor):
        return x._data
    if isinstance(x, (list, tuple)):
        return type(x)(_unwrap(v) for v in x)
    if isinstance(x, dict):
        return {k: _unwrap(v) for k, v in x.items()}
    return x


def _wrap(x):
    if isinstance(x, (jax.Array, jax.core.Tracer)):
        return Tensor(x)
    if isinstance(x, (list, tuple)):
        return type(x)(_wrap(v) for v in x)
    if isinstance(x, dict):
        return {k: _wrap(v) for k, v in x.items()}
    return x


def _split_static(args, kwargs):
    """Partition call arguments: array leaves become jit inputs, python
    scalars/strings stay COMPILE-TIME constants (the reference's
    dy2static contract — a python bool arg selects code paths and must
    not become a traced pred). Returns (dyn_leaves, hashable_meta)."""
    import numpy as np
    leaves, tree = jax.tree_util.tree_flatten((args, kwargs))
    dyn, static = [], []
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, (jax.Array, np.ndarray)):
            dyn.append(leaf)
        else:
            static.append((i, leaf))
    return tuple(dyn), (tree, len(leaves), tuple(static))


_MISSING = object()


def _merge_static(dyn, meta):
    tree, n, static = meta
    leaves = [_MISSING] * n
    for i, v in static:
        leaves[i] = v
    it = iter(dyn)
    leaves = [next(it) if v is _MISSING else v for v in leaves]
    return jax.tree_util.tree_unflatten(tree, leaves)


class StaticFunction:
    """Wraps a Layer (or plain function) into a jit-compiled callable keeping
    the dygraph Tensor interface."""

    def __init__(self, fn_or_layer, input_spec=None):
        import inspect
        self._method = None
        if inspect.ismethod(fn_or_layer) \
                and isinstance(fn_or_layer.__self__, Layer):
            # to_static(layer.forward): compile THROUGH the layer so its
            # Parameters join the autograd graph (a plain-function wrap
            # would see only the int input tensors and never train)
            self._target = fn_or_layer.__self__
            self._method = fn_or_layer.__func__
            self._is_layer = True
        else:
            self._target = fn_or_layer
            self._is_layer = isinstance(fn_or_layer, Layer)
        self._compiled = None
        self._input_spec = input_spec

    def _build(self):
        from . import dy2static
        convert = ProgramTranslator.get_instance().enable_to_static
        if self._is_layer:
            import types as _types
            layer = self._target
            base_fwd = self._method or type(layer).forward
            conv_fwd = dy2static.convert_function(base_fwd) if convert \
                else base_fwd
            conv_method = _types.MethodType(conv_fwd, layer)

            def pure(params, buffers, key, dyn, meta):
                args, kwargs = _merge_static(dyn, meta)
                # swap the converted forward in for the trace: the user
                # may have assigned THIS StaticFunction to layer.forward
                # (paddle idiom `model.forward = to_static(model.forward)`)
                # and dispatching through it again would recurse
                prev = layer.__dict__.get("forward", _MISSING)
                layer.__dict__["forward"] = conv_method
                try:
                    with state.functional_rng_ctx(key):
                        out, new_buf = layer.functional_call(
                            params, buffers, *_wrap(args), **_wrap(kwargs))
                finally:
                    if prev is _MISSING:
                        layer.__dict__.pop("forward", None)
                    else:
                        layer.__dict__["forward"] = prev
                return _unwrap(out), new_buf

            self._pure = pure
            self._compiled = jax.jit(pure, static_argnums=(4,))
        else:
            fn = dy2static.convert_function(self._target) if convert \
                else self._target

            def pure(key, dyn, meta):
                args, kwargs = _merge_static(dyn, meta)
                with state.functional_mode_ctx():
                    with state.functional_rng_ctx(key):
                        out = fn(*_wrap(args), **_wrap(kwargs))
                return _unwrap(out)

            self._pure = pure
            self._compiled = jax.jit(pure, static_argnums=(2,))

        # recompute-backward for eager training THROUGH the compiled
        # forward (the reference's ProgramTranslator captures backward in
        # the program, program_translator.py:233; here the whole jitted
        # forward is ONE tape op whose vjp re-derives the backward inside
        # jit — rematerialized, so nothing outlives the XLA program).
        # float_idx (static) selects the differentiable output slots.
        def bwd(p_leaves, dyn, buffers, key, cots, meta, names, float_idx):
            def f(*prims):
                p = dict(zip(names, prims[:len(names)]))
                d = tuple(prims[len(names):])
                if self._is_layer:
                    out, _ = self._pure(p, buffers, key, d, meta)
                else:
                    out = self._pure(key, d, meta)
                leaves = jax.tree_util.tree_flatten(out)[0]
                return tuple(leaves[i] for i in float_idx)

            _, vjp = jax.vjp(f, *(tuple(p_leaves) + tuple(dyn)))
            return vjp(tuple(cots))

        self._bwd = jax.jit(bwd, static_argnums=(5, 6, 7))

    def __call__(self, *args, **kwargs):
        if self._compiled is None:
            self._build()
        key = state.next_rng_key()
        dyn, meta = _split_static(_unwrap(args), _unwrap(kwargs))
        if self._is_layer:
            params, buffers = self._target.functional_state()
            out, new_buf = self._compiled(params, buffers, key, dyn, meta)
            # write back mutated buffers (BN running stats)
            named_b = dict(self._target.named_buffers())
            for n, arr in new_buf.items():
                named_b[n]._data = arr
        else:
            params, buffers = {}, {}
            out = self._compiled(key, dyn, meta)
        wrapped = _wrap(out)
        if state.is_functional_mode() or not state.is_grad_enabled():
            return wrapped
        self._record_grad(wrapped, args, kwargs, params, buffers, key,
                          dyn, meta)
        return wrapped

    def _record_grad(self, wrapped, args, kwargs, params, buffers, key,
                     dyn, meta):
        """Attach ONE GradNode covering the whole compiled forward, so
        eager `loss.backward()` flows into the layer's Parameters and
        any differentiable input Tensors. Double-grad (create_graph)
        through a to_static function is not supported (fn=None)."""
        from ..framework.tape import GradNode

        # original Tensor objects aligned with the dyn leaves: _unwrap is
        # structure-preserving, so wrapped and unwrapped trees flatten to
        # the same leaf positions
        w_leaves = jax.tree_util.tree_flatten((args, kwargs))[0]
        u_leaves = jax.tree_util.tree_flatten(
            (_unwrap(args), _unwrap(kwargs)))[0]
        dyn_tensors = [w if isinstance(w, Tensor) else None
                       for w, u in zip(w_leaves, u_leaves)
                       if isinstance(u, (jax.Array, np.ndarray))]

        names = tuple(params)
        named_p = dict(self._target.named_parameters()) \
            if self._is_layer else {}
        p_tensors = [named_p.get(n) for n in names]
        inputs = p_tensors + dyn_tensors
        if not any(t is not None and not t.stop_gradient for t in inputs):
            return

        # ONE flatten defines the slot numbering: every leaf is a slot;
        # only float Tensor slots are differentiable (float_idx), and the
        # same indexing selects the cotangents the tape hands back
        leaves_w = jax.tree_util.tree_flatten(
            wrapped, is_leaf=lambda x: isinstance(x, Tensor))[0]
        arrs = [w._data if isinstance(w, Tensor) else w for w in leaves_w]
        float_idx = tuple(
            i for i, (w, a) in enumerate(zip(leaves_w, arrs))
            if isinstance(w, Tensor)
            and jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating))
        if not float_idx:
            return
        p_leaves = tuple(params[n] for n in names)
        bwd = self._bwd

        def vjp_fn(cots):
            cots_t = cots if isinstance(cots, tuple) else (cots,)
            return bwd(p_leaves, dyn, buffers, key,
                       tuple(cots_t[i] for i in float_idx),
                       meta, names, float_idx)

        node = GradNode(
            vjp=vjp_fn,
            inputs=inputs,
            n_outputs=len(leaves_w),
            out_shapes=tuple(jnp.shape(a) for a in arrs),
            out_dtypes=tuple(jnp.asarray(a).dtype for a in arrs),
            name="to_static")
        for i in float_idx:
            leaves_w[i]._node = node
            leaves_w[i]._slot = i
            leaves_w[i].stop_gradient = False

    # paddle surface
    @property
    def forward(self):
        return self.__call__


def to_static(layer_or_fn=None, input_spec=None, **kwargs):
    """paddle.jit.to_static analog (decorator or call)."""
    if layer_or_fn is None:
        return functools.partial(to_static, input_spec=input_spec, **kwargs)
    return StaticFunction(layer_or_fn, input_spec=input_spec)


def grad_norm_sentinel(loss, grads):
    """(global_grad_norm, notfinite) fused into a compiled train step —
    ONE implementation for TrainStep and ShardedTrainStep: the
    (loss, grad_norm) pair is exactly what the kill/resume parity gate
    (scripts/chaos_train.py) compares across the two step flavours, so
    the reduction must never drift between them. A tiny fp32 reduction
    over the grads that XLA fuses into the backward — no extra host
    sync (the flag is only ever READ by an instrumented caller that is
    about to block anyway)."""
    gsq = sum((jnp.sum(jnp.square(g.astype(jnp.float32)))
               for g in jax.tree_util.tree_leaves(grads)),
              jnp.asarray(0.0, jnp.float32))
    notfinite = jnp.logical_not(
        jnp.all(jnp.isfinite(loss)) & jnp.isfinite(gsq))
    return jnp.sqrt(gsq), notfinite


class InstrumentedStepMixin:
    """Flight-recorder/watchdog instrumentation shared by the compiled
    train steps (`TrainStep` here, `distributed.sharded.ShardedTrainStep`).

    Hosts expectations: the step object carries `_compiled` (a jitted
    callable returning the canonical 8-tuple `(loss, params, buffers,
    opt_state, grad_acc, outs, grad_norm, notfinite)`), the state dicts
    those outputs rebind (`params`/`buffers`/`opt_state`/`grad_acc`),
    and `_step_i`. `_init_instrumentation()` must run in `__init__`."""

    def _init_instrumentation(self, label="train_step"):
        # the process's first program may be this step: its trace, lower
        # and compile (or load) are heard, under this label, with no
        # recorder attached; the collector's pauses are `train/gc` spans
        telemetry.install_compile_tracking()
        telemetry.install_gc_tracking("train")
        self._recorder = None
        self._label = label
        self._fail_fast = False
        self._cost_cache = {}
        self._pending_data_s = 0.0
        self._pending_batch = None
        self._watchdog = None
        self._last_grad_norm = None
        self._last_nonfinite = None

    # ------------------------------------------------------ flight recorder
    def attach_flight_recorder(self, recorder, label=None,
                               fail_fast=None, watchdog=None):
        """Instrument every subsequent step: journal `step` events with
        the data/host/device timing split, per-executable `compile`
        events with FLOPs/bytes from HLO cost analysis, MFU + non-finite
        telemetry. Adds ONE host sync per step (block_until_ready on the
        loss) — the same sync hapi's per-step float(loss) already pays.
        `fail_fast=True` (or recorder.fail_fast) raises NonFiniteError
        when loss/global-grad-norm go non-finite. `watchdog` (a started
        `utils.resume.TrainWatchdog`) is fed one `beat()` per completed
        step, so a step that never completes becomes a journaled `hang`
        event instead of a silent stall."""
        from ..utils import flight_recorder as fr
        self._recorder = recorder
        if label is not None:
            self._label = label
        self._watchdog = watchdog
        self._fail_fast = recorder.fail_fast if fail_fast is None \
            else bool(fail_fast)
        # constant per process; None off the peaks table (MFU then
        # reads "not measured")
        peaks = fr.device_peaks()
        self._peak_flops = peaks[0] if peaks else None
        self._m_mfu = telemetry.gauge(
            "train_mfu", "Model-FLOPs utilization of the latest step")
        self._m_flops = telemetry.gauge(
            "train_step_flops",
            "FLOPs per compiled train step (HLO cost analysis)")
        self._m_bytes = telemetry.gauge(
            "train_step_bytes",
            "Bytes accessed per compiled train step (HLO cost analysis)")
        self._m_nonfinite = telemetry.counter(
            "train_nonfinite_total",
            "Train steps with non-finite loss or global grad norm")
        self._m_data = telemetry.histogram(
            "train_data_wait_seconds", "Input-pipeline wait per step")
        self._m_host = telemetry.histogram(
            "train_host_dispatch_seconds",
            "Host time dispatching the compiled step")
        self._m_dev = telemetry.histogram(
            "train_device_step_seconds",
            "Device execution time per step (block_until_ready)")
        return self

    def detach_flight_recorder(self):
        self._recorder = None
        self._watchdog = None

    def set_data_wait(self, seconds, batch=None):
        """Data-pipeline wait (and optionally the epoch-relative batch
        index) attributed to the NEXT step event (Model.fit times the
        DataLoader and reports both here — the journal's `batch` field
        is the same index the resume cursor records, so data-wait
        attribution and fast-forward bookkeeping agree)."""
        self._pending_data_s = float(seconds)
        self._pending_batch = None if batch is None else int(batch)

    def last_nonfinite(self):
        """Sentinel of the latest step (host sync on first read)."""
        return None if self._last_nonfinite is None \
            else bool(self._last_nonfinite)

    def last_grad_norm(self):
        return None if self._last_grad_norm is None \
            else float(self._last_grad_norm)

    def _signature(self, args):
        # dtype via attribute, NOT jnp.asarray: these are the raw batch
        # leaves and asarray would device-transfer numpy batches once
        # more per step just to read their dtype
        leaves = jax.tree_util.tree_flatten((args[7], args[8]))[0]
        return tuple(
            (jnp.shape(a), str(getattr(a, "dtype", type(a).__name__)))
            for a in leaves)

    def _dispatch(self, args):
        """The compiled step, its state rebound from what it returns; what
        it traces, lowers, compiles or loads carries the step's label."""
        with telemetry.track_compiles(self._label):
            (loss, self.params, self.buffers, self.opt_state, self.grad_acc,
             outs, self._last_grad_norm, self._last_nonfinite) = \
                self._compiled(*args)
        return loss, outs

    def _instrumented_call(self, args):
        import time as _time
        from ..utils import flight_recorder as fr
        rec = self._recorder
        sig = self._signature(args)
        if sig not in self._cost_cache:
            # once per executable, BEFORE the call donates the buffers:
            # lowering-level HLO cost analysis, no second backend compile
            self._cost_cache[sig] = fr.cost_analysis(self._compiled, *args)
        cost = self._cost_cache[sig] or {}
        before = telemetry.compile_count(self._label)
        t0 = _time.perf_counter()
        loss, outs = self._dispatch(args)
        t1 = _time.perf_counter()
        loss.block_until_ready()
        t2 = _time.perf_counter()
        host_s, device_s = t1 - t0, t2 - t1
        compiled = telemetry.compile_count(self._label) - before
        flops = cost.get("flops")
        if compiled:
            rec.compile_event(self._label, count=compiled, compile_s=host_s,
                              flops=flops,
                              bytes_accessed=cost.get("bytes_accessed"))
        # gauges track the CURRENT executable's cost, not just freshly
        # compiled ones — a recorder attached after the compile (bench's
        # verification step) must still publish them
        if flops is not None:
            self._m_flops.set(flops)
        if cost.get("bytes_accessed") is not None:
            self._m_bytes.set(cost["bytes_accessed"])
        mfu = None
        if flops and self._peak_flops:
            mfu = flops / (max(device_s, 1e-9) * self._peak_flops)
            self._m_mfu.set(mfu)
        data_s, self._pending_data_s = self._pending_data_s, 0.0
        batch_idx, self._pending_batch = self._pending_batch, None
        nonfinite = bool(self._last_nonfinite)
        grad_norm = float(self._last_grad_norm)
        extra = {} if batch_idx is None else {"batch": batch_idx}
        rec.step(step=self._step_i, data_s=data_s, host_s=host_s,
                 device_s=device_s, loss=float(loss), grad_norm=grad_norm,
                 mfu=mfu, nonfinite=nonfinite, **extra)
        if self._watchdog is not None:
            self._watchdog.beat(step_s=host_s + device_s, step=self._step_i)
        self._m_data.observe(data_s)
        self._m_host.observe(host_s)
        self._m_dev.observe(device_s)
        if nonfinite:
            self._m_nonfinite.inc()
            rec.nonfinite(step=self._step_i, loss=float(loss),
                          grad_norm=grad_norm, source=self._label)
            if self._fail_fast:
                rec.flush()
                raise fr.NonFiniteError(
                    f"non-finite loss/grad at step {self._step_i}: "
                    f"loss={float(loss)!r} grad_norm={grad_norm!r}")
        return loss, outs


class TrainStep(InstrumentedStepMixin):
    """Whole-train-step compiler: loss + grads + optimizer in one XLA program.

    Usage:
        step = TrainStep(model, loss_fn, opt)
        loss = step(x, y)          # one device dispatch
        step.sync()                # write state back into model/opt
    """

    @telemetry.startup_span("step_build")
    def __init__(self, model, loss_fn, optimizer, donate=True,
                 return_outputs=False):
        from . import transforms as tfm
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.return_outputs = return_outputs
        params, buffers = model.functional_state()
        # copy: donated buffers are consumed by XLA, but the live Layer's
        # Parameters still reference the originals (callbacks/eager access
        # between steps must keep working — sync() writes back copies too)
        self.params = {n: jnp.copy(a) for n, a in params.items()}
        self.buffers = {n: jnp.copy(a) for n, a in buffers.items()}
        # parameters= threads the live Parameter objects through so an
        # optimizer carrying RESTORED accumulators (checkpoint resume,
        # or prior synced steps) seeds the functional state — a rebuilt
        # TrainStep must continue the trajectory, not zero the moments
        self.opt_state = optimizer.init_opt_state(
            params, parameters=dict(model.named_parameters()))
        self._step_i = optimizer._global_step
        apply_fn = optimizer.apply_gradients_fn()

        # strategy transforms recorded by the fleet meta-optimizer chain
        # (amp autocast, recompute, k-step gradient merge) — see
        # jit/transforms.py for the mapping
        self.transforms = tfm.resolve(optimizer)
        k_merge, merge_avg = tfm.merge_config(self.transforms)
        self.grad_acc = tfm.init_grad_acc(self.params, k_merge)
        update_fn = tfm.merged_update(apply_fn, k_merge, merge_avg)

        def _forward(p, bufs, key, inputs, labels):
            with state.functional_rng_ctx(key):
                # keep the param substitution alive THROUGH the loss call:
                # losses may read model parameters directly (CRF
                # transitions, tied heads) and must see the traced arrays,
                # not the pre-trace constants functional_call restores on
                # exit — otherwise those params silently train to nothing
                with model._use_state(p, bufs):
                    out, new_buf = model.functional_call(
                        p, bufs, *_wrap(inputs))
                    outs = out if isinstance(out, tuple) else (out,)
                    loss_t = loss_fn(*outs, *_wrap(labels))
            return _unwrap(loss_t), (new_buf, _unwrap(out))

        _forward = tfm.wrap_forward(_forward, self.transforms)

        ret_outs = return_outputs

        def _step(params, buffers, opt_state, acc, key, lr, step_i,
                  inputs, labels):
            (loss, (new_buf, outs)), grads = jax.value_and_grad(
                lambda p: _forward(p, buffers, key, inputs, labels),
                has_aux=True)(params)
            new_params, new_opt, new_acc = update_fn(
                params, grads, opt_state, acc, lr, step_i)
            grad_norm, notfinite = grad_norm_sentinel(loss, grads)
            # outs leave the jitted program ONLY when asked for: a returned
            # value can't be dead-code-eliminated, and fused-loss models
            # (e.g. GPT chunked head+CE) rely on XLA dropping the unused
            # wide logits entirely
            if not ret_outs:
                outs = ()
            return (loss, new_params, new_buf, new_opt, new_acc, outs,
                    grad_norm, notfinite)

        donate_args = (0, 1, 2, 3) if donate else ()
        # stashed for the program-level audit (tools/jxaudit): jax's
        # PjitFunction exposes no public donate introspection, so the
        # declaration of record rides on the TrainStep itself
        self._donate_argnums = donate_args
        self._compiled = jax.jit(_step, donate_argnums=donate_args)
        # flight-recorder instrumentation (attach_flight_recorder)
        self._init_instrumentation()

    def __call__(self, inputs, labels):
        if chaos.enabled():
            # the canonical "kill"/stall boundary for the exact-resume
            # parity harness: host-side, BEFORE the step counter, the
            # RNG draw, or the compiled dispatch — a raise here leaves
            # every piece of training state exactly at the last
            # completed step, like a SIGKILL between steps
            chaos.fire(chaos.TRAIN_STEP, step=self._step_i + 1)
        inputs = inputs if isinstance(inputs, (list, tuple)) else (inputs,)
        labels = labels if isinstance(labels, (list, tuple)) else (labels,)
        self._step_i += 1
        # a step annotation on the profiler's clock (its step view
        # groups by it), the host's two parts inside
        with RecordEvent("train", step_num=self._step_i):
            with RecordEvent("train/stage", step=self._step_i):
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                args = (self.params, self.buffers, self.opt_state,
                        self.grad_acc, state.next_rng_key(),
                        lr, jnp.asarray(self._step_i, jnp.int32),
                        _unwrap(tuple(inputs)), _unwrap(tuple(labels)))
            with RecordEvent("train/dispatch", step=self._step_i):
                loss, outs = (self._instrumented_call(args)
                              if self._recorder is not None
                              else self._dispatch(args))
        if self.return_outputs:
            return Tensor(loss), _wrap(outs)
        return Tensor(loss)

    def eval_fn(self, fn=None):
        """Compile an eval forward over the live functional state."""
        model = self.model

        def _eval(params, buffers, inputs):
            was_training = model.training
            model.eval()
            try:
                out, _ = model.functional_call(params, buffers, *_wrap(inputs))
            finally:
                if was_training:
                    model.train()
            return _unwrap(out)

        compiled = jax.jit(_eval)

        def run(*inputs):
            return _wrap(compiled(self.params, self.buffers,
                                  _unwrap(tuple(inputs))))
        return run

    def sync(self):
        """Write functional state back into the Layer/Optimizer objects.
        Copies are handed out so subsequent donated steps can't invalidate
        the Layer's view."""
        named_p = dict(self.model.named_parameters())
        for n, arr in self.params.items():
            named_p[n]._data = jnp.copy(arr)
        named_b = dict(self.model.named_buffers())
        for n, arr in self.buffers.items():
            named_b[n]._data = jnp.copy(arr)
        opt = self.optimizer
        opt._global_step = self._step_i
        for n, st in self.opt_state.items():
            p = named_p[n]
            opt._accumulators[id(p)] = {k: jnp.copy(v) for k, v in st.items()}


def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save analog (ref dygraph/jit.py:507): StableHLO export —
    see static/export.py for the on-disk format."""
    from ..static import export as _export
    if input_spec is None and isinstance(layer, StaticFunction):
        input_spec = layer._input_spec
        layer = layer._target
    return _export.save(layer, path, input_spec=input_spec, **configs)


def load(path, **configs):
    """paddle.jit.load analog (ref dygraph/jit.py:787) -> TranslatedLayer."""
    from ..static import export as _export
    return _export.load(path, **configs)


def not_to_static(fn):
    return fn


class ProgramTranslator:
    _instance = None

    @classmethod
    def get_instance(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        self.enable_to_static = True

    def enable(self, enable_to_static):
        self.enable_to_static = enable_to_static
