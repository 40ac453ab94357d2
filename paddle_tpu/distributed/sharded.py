"""ShardedTrainStep — the multi-chip compiled training step.

TPU-native replacement for the reference's multi-device executors
(ref framework/details/ SSA-graph ParallelExecutor + imperative Reducer +
fleet meta-optimizer program rewrites): ONE jit over a Mesh.
  - batch sharded on 'dp' (+ optionally 'sp' along sequence)
  - params/opt-state sharded per-tensor from Parameter.sharding
    PartitionSpec hints ('mp' Megatron layouts come from the model)
  - ZeRO: optimizer states (and optionally params) additionally sharded over
    'dp' (PAPERS.md arXiv:2004.13336 cross-replica weight-update sharding)
  - XLA SPMD partitioner inserts + schedules all collectives over ICI
    (gradient AllReduce, TP AllReduces, AllGathers) — bucketing/overlap is
    the compiler's latency-hiding scheduler.
  - exact-resume + elastic reshard (docs/robustness.md): the step fires
    the same `chaos.TRAIN_STEP` kill point, fuses the same grad-norm /
    non-finite sentinel, and carries the same flight-recorder
    instrumentation as the single-chip TrainStep, so
    `scripts/chaos_train.py --mesh dp=N --resume-mesh dp=M` can prove a
    killed sharded run resumes bitwise-identically onto a DIFFERENT
    replica count. `sync()` gathers the dp-sharded optimizer slots into
    host copies (the PR-7 optimizer-copy contract, per shard), and
    `sharding_state()` is what `Model.save` records in the `.pdtrain`
    payload so a resume can re-derive placements on the new mesh.

    The `exact_reshard` flag (opt-in: constructor kwarg or fleet
    `sharding_configs={"stage": 1, "exact_reshard": True}`) selects
    STORAGE-sharded, math-replicated execution: every dp-sharded state
    leaf is gathered to its full logical shape before arithmetic
    touches it (`with_sharding_constraint` to replicated), the whole
    forward/backward/update computes at dp-invariant tile shapes, and
    the out_shardings slice results back to their shards. The only
    dp-dependent collectives are all-gather (concatenation) and
    dynamic-slice — both bitwise-clean — so with a batch the mesh
    cannot dp-shard (leading dim not divisible), the per-step
    (loss, grad-norm, params, moments) are bit-identical across dp
    counts: a dp=2 checkpoint resumes on dp=4 bitwise. Measured on
    this XLA build, the default drifts by ~1 ulp per step across dp
    counts: per-shard tile geometry changes the compiler's fma/fusion
    choices even for the purely elementwise Adam update, and a
    dp-sharded batch's gradient psum tree reorders with dp. The
    default (False) keeps full ZeRO compute sharding —
    reduce-scattered grads, shard-local update math and transients —
    the right trade when throughput matters more than cross-mesh
    exactness; kill/resume onto the SAME mesh is bitwise in both
    modes, and storage stays sharded either way
    (`opt_specs`/`param_specs`), so the persistent opt-state residency
    win of arXiv:2004.13336 always holds.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework import state
from ..framework.tensor import Tensor
from ..jit import InstrumentedStepMixin, grad_norm_sentinel
from ..utils import chaos, telemetry
from ..utils.profiler import RecordEvent
from . import mesh as mesh_mod

#: per-device bytes of the dp-sharded optimizer state gathered at the
#: last checkpoint sync — the live measurement of ZeRO's memory win
#: (total state bytes / dp when sharding engaged; catalog:
#: docs/observability.md)
_SHARD_BYTES = telemetry.gauge(
    "checkpoint_shard_bytes",
    "Per-device bytes of dp-sharded optimizer state at the last "
    "checkpoint sync")


def _shard_nbytes(arr):
    """Per-device bytes of one (possibly sharded) array."""
    try:
        shape = arr.sharding.shard_shape(arr.shape)
    except Exception:
        shape = arr.shape
    return int(np.prod(shape, dtype=np.int64)) * arr.dtype.itemsize


def _spec_doc(spec):
    """PartitionSpec -> picklable list (axis name, None, or list of
    names per dim) for the `.pdtrain` sharding record."""
    return [list(e) if isinstance(e, (tuple, list)) else e for e in spec]


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
    return x


def _valid_spec(spec, mesh, shape):
    """Keep only axes present in the mesh and divisible dims; else replicate."""
    if spec is None:
        return P()
    parts = list(spec)
    out = []
    for i, p in enumerate(parts):
        if p is None or p not in mesh.axis_names:
            out.append(None)
            continue
        if i < len(shape) and shape[i] % mesh.shape[p] == 0:
            out.append(p)
        else:
            out.append(None)
    return P(*out) if any(o is not None for o in out) else P()


def _zero_spec(shape, mesh, dp_axis, base_spec):
    """Shard the largest unsharded dim over dp for opt-state (ZeRO-1)."""
    if dp_axis not in mesh.axis_names or not shape:
        return base_spec
    dp = mesh.shape[dp_axis]
    parts = list(base_spec) + [None] * (len(shape) - len(list(base_spec)))
    for i in np.argsort([-s for s in shape]):
        if parts[i] is None and shape[i] % dp == 0:
            parts[i] = dp_axis
            return P(*parts)
    return base_spec


class ShardedTrainStep(InstrumentedStepMixin):
    """Compiled SPMD train step over the current Mesh.

    Usage:
        make_mesh({'dp': 2, 'mp': 4})
        step = ShardedTrainStep(model, loss_fn, opt, zero_stage=1)
        loss = step(batch_inputs, batch_labels)   # global batch arrays
    """

    @telemetry.startup_span("step_build")
    def __init__(self, model, loss_fn, optimizer, mesh=None, dp_axis=None,
                 zero_stage=0, donate=True, remat=False, shard_seq=True,
                 return_outputs=False, exact_reshard=False):
        from ..jit import transforms as tfm
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.return_outputs = return_outputs
        self.mesh = mesh or mesh_mod.get_mesh() or mesh_mod.default_mesh()
        self.dp_axis = dp_axis or (
            mesh_mod.DP_AXIS if mesh_mod.DP_AXIS in self.mesh.axis_names
            else self.mesh.axis_names[0])
        # strategy transforms from the fleet meta-optimizer chain override
        # the constructor defaults (jit/transforms.py)
        self.transforms = tfm.resolve(optimizer)
        zero_stage = tfm.zero_stage_of(self.transforms, zero_stage)
        remat = remat or self.transforms.get("recompute") is not None
        self.zero_stage = zero_stage
        self.shard_seq = shard_seq
        # deterministic-elastic mode rides the sharding strategy too
        # (fleet sharding_configs={"stage": 1, "exact_reshard": True}),
        # so fit-built steps can opt in without new plumbing
        sh_cfg = self.transforms.get("sharding") or {}
        if "exact_reshard" in sh_cfg:
            exact_reshard = bool(sh_cfg["exact_reshard"])
        self.exact_reshard = bool(exact_reshard)

        params, buffers = model.functional_state()
        named_params = dict(model.named_parameters())

        # ---- param shardings from Parameter.sharding hints
        self.param_specs = {}
        for n, arr in params.items():
            hint = getattr(named_params[n], "sharding", None)
            self.param_specs[n] = _valid_spec(hint, self.mesh, arr.shape)
        self.buffer_specs = {n: P() for n in buffers}

        def shard(x, spec):
            # jnp.copy BEFORE the placement: a restored/set_value'd leaf
            # can be a ZERO-COPY view of host numpy memory (jax 0.4.37's
            # CPU client aliases aligned numpy buffers), and the
            # compiled step DONATES these — XLA freeing memory numpy
            # owns corrupts the heap ("double free"/"corrupted
            # double-linked list" on the first post-restore step). The
            # copy materializes an XLA-owned buffer first, exactly what
            # jit.TrainStep.__init__ does for the same reason;
            # construction-time-only cost.
            return jax.device_put(jnp.copy(x), NamedSharding(self.mesh, spec))

        # ---- optimizer state shardings (follow param; + dp for ZeRO>=1)
        # ZeRO stages under GSPMD (ref fleet sharding_optimizer.py stages;
        # PAPERS.md arXiv:2004.13336):
        #   1: optimizer state dp-sharded — the update math runs on 1/dp of
        #      each state tensor per device.
        #   2: gradient sharding. Grads are ephemeral inside the single
        #      compiled step and are consumed by the dp-sharded update, so
        #      the partitioner materialises them reduce-SCATTERED into the
        #      update — stage 2 is subsumed by stage 1 here (there is no
        #      standalone grad buffer to shard).
        #   3: parameters dp-sharded too. Gather-on-use is explicit in the
        #      partitioned HLO: every use site all-gathers the shard just
        #      before the matmul and the backward reduce-scatters dL/dW
        #      straight back to the shard (test_zero3.py asserts both
        #      collectives exist and per-device bytes are size/dp).
        # parameters= threads the live Parameter objects through so an
        # optimizer carrying RESTORED accumulators (checkpoint resume —
        # possibly written on a DIFFERENT mesh) seeds the functional
        # state; device_put below then reshards the restored host
        # copies onto THIS mesh's placements (the elastic-reshard load
        # path). Without it a rebuilt sharded step would zero the
        # moments on every resume, exactly the TrainStep bug PR 10
        # fixed on the single-chip path.
        # the moments made, then placed. Neither span waits for its
        # arrays: the first step's trace runs while they travel
        with telemetry.startup_span("opt_state", zero_stage=zero_stage):
            opt_state = optimizer.init_opt_state(
                params, parameters=named_params)
            self.opt_specs = {}
            for n, slots in opt_state.items():
                base = self.param_specs[n]
                spec = base
                if zero_stage >= 1:
                    spec = _zero_spec(params[n].shape, self.mesh,
                                      self.dp_axis, base)
                self.opt_specs[n] = {sn: spec for sn in slots}
            self.opt_state = jax.tree_util.tree_map_with_path(
                lambda kp, a: shard(a, self.opt_specs[kp[0].key][kp[1].key]),
                opt_state)
        if zero_stage >= 3:
            for n, arr in params.items():
                self.param_specs[n] = _zero_spec(arr.shape, self.mesh,
                                                 self.dp_axis,
                                                 self.param_specs[n])
        with telemetry.startup_span("shard"):
            self.params = {n: shard(a, self.param_specs[n])
                           for n, a in params.items()}
            self.buffers = {n: shard(a, P()) for n, a in buffers.items()}
        self._step_i = optimizer._global_step
        apply_fn = optimizer.apply_gradients_fn()
        dp_axis_name = self.dp_axis
        mesh = self.mesh

        def _forward(p, buffers, key, inputs, labels):
            with state.functional_rng_ctx(key):
                # loss may read model params directly (CRF transitions,
                # tied heads): keep the traced substitution alive through it
                # (same fix as jit.TrainStep._forward)
                with model._use_state(p, buffers):
                    out, new_buf = model.functional_call(
                        p, buffers, *_wrap(inputs))
                    outs = out if isinstance(out, tuple) else (out,)
                    loss_t = loss_fn(*outs, *_wrap(labels))
            return _unwrap(loss_t), (new_buf, _unwrap(out))

        # amp autocast (recompute is handled by the remat flag below so a
        # strategy-enabled recompute isn't checkpointed twice)
        amp_only = {k: v for k, v in self.transforms.items() if k == "amp"}
        _forward = tfm.wrap_forward(_forward, amp_only)
        if remat:
            from ..jit.transforms import _remat_policy
            _forward = jax.checkpoint(
                _forward, static_argnums=(),
                policy=_remat_policy(self.transforms.get("recompute")))

        # k-step gradient merge (strategy.gradient_merge): accumulator
        # sharded like the grads (= params)
        k_merge, merge_avg = tfm.merge_config(self.transforms)
        self.grad_acc = tfm.init_grad_acc(params, k_merge)
        if k_merge > 1:
            self.grad_acc = {n: shard(a, self.param_specs[n])
                             for n, a in self.grad_acc.items()}
        update_fn = tfm.merged_update(apply_fn, k_merge, merge_avg)

        # fp16_allreduce (strategy.fp16_allreduce, ref fleet
        # fp16_allreduce_optimizer.py): make the DP gradient reduction an
        # EXPLICIT cast -> psum('dp') -> upcast by computing grads inside a
        # shard_map that is manual over the dp axis only (mp/sp/ep stay
        # GSPMD-auto) — halves DP grad bytes over ICI. Incompatible with
        # ZeRO-3 (grads must reduce-scatter to the param shard, not
        # all-reduce) and with return_outputs (per-shard aux outputs).
        fp16_ar = self.transforms.get("fp16_allreduce")
        if fp16_ar and (zero_stage >= 3 or return_outputs
                        or self.mesh.shape[dp_axis_name] == 1):
            import warnings
            warnings.warn(
                "fp16_allreduce ignored: needs dp>1 and is incompatible "
                "with ZeRO-3 / return_outputs")
            fp16_ar = None
        self.fp16_allreduce = bool(fp16_ar)

        if fp16_ar:
            red_dt = tfm.reduced_dtype(fp16_ar.get("dtype"))
            dp_n = mesh.shape[dp_axis_name]

            def _grad_body(p, buffers, key, inputs, labels):
                # decorrelate per-shard randomness (dropout masks must
                # differ across dp shards like the GSPMD global draw)
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(dp_axis_name))

                def pure_loss(p_):
                    return _forward(p_, buffers, key, inputs, labels)

                (loss, (new_buf, _)), grads = jax.value_and_grad(
                    pure_loss, has_aux=True)(p)
                # the explicit reduced-precision DP reduction; dividing
                # BEFORE the cast keeps the fp16 sum in range (the mean
                # is identical; the sum of dp_n unscaled grads can
                # overflow fp16's 65504 max at large dp)
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(
                        (g / dp_n).astype(red_dt), dp_axis_name
                    ).astype(g.dtype)
                    if jnp.issubdtype(g.dtype, jnp.floating) else g, grads)
                loss = jax.lax.pmean(loss, dp_axis_name)
                # float buffers (e.g. BN running stats from local batch
                # stats) are averaged across dp shards; int counters are
                # dp-invariant already
                new_buf = jax.tree.map(
                    lambda b: jax.lax.pmean(b, dp_axis_name)
                    if jnp.issubdtype(b.dtype, jnp.floating) else b,
                    new_buf)
                return loss, new_buf, grads

            def _in_spec_tree(tree, spec):
                return jax.tree.map(lambda _: spec, tree)

        def _batch_dp_spec(a):
            # mirror _shard_batch: only leading dims divisible by dp are
            # dp-sharded; scalars / ragged batches stay replicated
            if (getattr(a, "ndim", 0) >= 1
                    and a.shape[0] % mesh.shape[dp_axis_name] == 0):
                return P(dp_axis_name)
            return P()

        exact = self.exact_reshard

        def _step(params, buffers, opt_state, acc, key, lr, step_i,
                  inputs, labels):
            if exact:
                # storage-sharded, math-replicated: gather every sharded
                # state leaf to its full logical shape BEFORE any
                # arithmetic touches it. Elementwise update math is then
                # compiled at dp-invariant tile shapes (XLA's fma/fusion
                # choices depend on the per-shard tile geometry — at
                # dp=2 vs dp=4 the same Adam update rounds differently
                # by 1 ulp otherwise), and the out_shardings slice the
                # results back to their shards. The collectives this
                # inserts (all-gather = concat in, dynamic-slice out)
                # are bitwise-clean, which is the whole point.
                rep = NamedSharding(mesh, P())

                def _gather(t):
                    return jax.tree.map(
                        lambda a: jax.lax.with_sharding_constraint(a, rep),
                        t)

                params = _gather(params)
                opt_state = _gather(opt_state)
                acc = _gather(acc)
            if fp16_ar:
                batch_spec = jax.tree.map(_batch_dp_spec, inputs)
                label_spec = jax.tree.map(_batch_dp_spec, labels)
                grad_fn = jax.shard_map(
                    _grad_body, mesh=mesh, axis_names={dp_axis_name},
                    in_specs=(_in_spec_tree(params, P()),
                              _in_spec_tree(buffers, P()), P(),
                              batch_spec, label_spec),
                    out_specs=(P(), _in_spec_tree(buffers, P()),
                               _in_spec_tree(params, P())),
                    check_vma=False)
                loss, new_buf, grads = grad_fn(params, buffers, key,
                                               inputs, labels)
                outs = ()
            else:
                def pure_loss(p):
                    return _forward(p, buffers, key, inputs, labels)

                (loss, (new_buf, outs)), grads = jax.value_and_grad(
                    pure_loss, has_aux=True)(params)
                if exact:
                    # pin the backward's results REPLICATED before the
                    # dp-sharded update reads them: sharding propagation
                    # then computes the whole backward at full logical
                    # shapes on every device (dp-count-invariant
                    # reduction trees — the bitwise elastic-reshard
                    # contract, see module docstring), instead of
                    # materializing reduce-scattered grads whose
                    # per-shard tile geometry varies with dp
                    rep = NamedSharding(mesh, P())
                    grads = jax.tree.map(
                        lambda g: jax.lax.with_sharding_constraint(g, rep),
                        grads)
            new_params, new_opt, new_acc = update_fn(
                params, grads, opt_state, acc, lr, step_i)
            # the SAME fused sentinel as jit.TrainStep (one shared
            # implementation — the (loss, grad_norm) pair IS what the
            # kill/resume parity gate compares across step flavours).
            # Under exact_reshard the grads are pinned replicated (the
            # fp16 path's psum out_specs already are), so the reduction
            # runs at full logical shape on every device —
            # dp-count-invariant.
            grad_norm, notfinite = grad_norm_sentinel(loss, grads)
            return (loss, new_params, new_buf, new_opt, new_acc, outs,
                    grad_norm, notfinite)

        # output shardings mirror inputs so state stays put across steps
        ns = lambda spec: NamedSharding(mesh, spec)
        param_sh = {n: ns(s) for n, s in self.param_specs.items()}
        buffer_sh = {n: ns(P()) for n in self.buffers}
        opt_sh = {n: {sn: ns(s) for sn, s in slots.items()}
                  for n, slots in self.opt_specs.items()}
        acc_sh = {n: param_sh[n] for n in self.grad_acc}
        donate_args = (0, 1, 2, 3) if donate else ()
        # the declaration of record for the program-level audit
        # (tools/jxaudit, xprof sharded_train_step_spec) — PjitFunction
        # exposes no public donate introspection
        self._donate_argnums = donate_args
        self._compiled = jax.jit(
            _step,
            in_shardings=(param_sh, buffer_sh, opt_sh, acc_sh, None, None,
                          None, None, None),
            out_shardings=(ns(P()), param_sh, buffer_sh, opt_sh, acc_sh,
                           None, ns(P()), ns(P())),
            donate_argnums=donate_args,
        )
        # flight-recorder instrumentation (attach_flight_recorder); the
        # label keys xla_compiles_total{function=} and matches the
        # xprof registry's tracked-program name
        self._init_instrumentation(label="sharded_train_step")

    # ------------------------------------------------------------------ step
    def _shard_batch(self, arrs):
        # dim 1 = sequence is a sequence-model convention; pass
        # shard_seq=False for models where dim 1 isn't a sequence axis
        sp = mesh_mod.SP_AXIS if (
            self.shard_seq
            and mesh_mod.SP_AXIS in self.mesh.axis_names) else None
        out = []
        for a in arrs:
            a = a._data if isinstance(a, Tensor) else jnp.asarray(a)
            parts = [None] * a.ndim
            if a.ndim >= 1 and a.shape[0] % self.mesh.shape[self.dp_axis] == 0:
                parts[0] = self.dp_axis
            # sequence dim rides 'sp' (ring attention shards activations too)
            if (sp and a.ndim >= 2
                    and a.shape[1] % self.mesh.shape[sp] == 0):
                parts[1] = sp
            spec = P(*parts) if any(parts) else P()
            out.append(jax.device_put(a, NamedSharding(self.mesh, spec)))
        return tuple(out)

    def __call__(self, inputs, labels):
        if chaos.enabled():
            # same kill/stall boundary as jit.TrainStep: host-side,
            # BEFORE the step counter, the RNG draw, or the compiled
            # dispatch — a raise here leaves every piece of (sharded)
            # training state exactly at the last completed step
            chaos.fire(chaos.TRAIN_STEP, step=self._step_i + 1)
        inputs = inputs if isinstance(inputs, (list, tuple)) else (inputs,)
        labels = labels if isinstance(labels, (list, tuple)) else (labels,)
        self._step_i += 1
        # the same spans as jit.TrainStep: a step annotation, with
        # staging (lr, key, batch placement) and the dispatch inside
        with RecordEvent("train", step_num=self._step_i):
            with RecordEvent("train/stage", step=self._step_i):
                lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
                args = (self.params, self.buffers, self.opt_state,
                        self.grad_acc, state.next_rng_key(), lr,
                        jnp.asarray(self._step_i, jnp.int32),
                        self._shard_batch(inputs),
                        self._shard_batch(labels))
            with RecordEvent("train/dispatch", step=self._step_i), \
                    self.mesh:
                loss, outs = (self._instrumented_call(args)
                              if self._recorder is not None
                              else self._dispatch(args))
        if self.return_outputs:
            return Tensor(loss), _wrap(outs)
        return Tensor(loss)

    def sync(self):
        """Write functional state back into the Layer/Optimizer objects.
        The dp-sharded optimizer slots are GATHERED into host copies
        (device_get on a sharded array assembles the full logical
        array), so the snapshot `optimizer.state_dict()` hands the
        checkpoint survives later donated steps — the PR-7 optimizer-
        copy contract, now per shard. `checkpoint_shard_bytes` records
        the per-device footprint of what was gathered (the live ZeRO
        memory-win measurement)."""
        named_p = dict(self.model.named_parameters())
        for n, arr in self.params.items():
            named_p[n]._data = jnp.copy(jax.device_get(arr))
        named_b = dict(self.model.named_buffers())
        for n, arr in self.buffers.items():
            named_b[n]._data = jnp.copy(jax.device_get(arr))
        opt = self.optimizer
        opt._global_step = self._step_i
        stale = None
        if chaos.enabled():
            # positive control for the reshard parity harness
            # (--inject stale-shard): a gather that silently loses the
            # dp shards' updates for one parameter's slots must make
            # the kill/resume parity check fail
            stale = chaos.value(chaos.SHARD_STATE, default=None)
        shard_bytes = 0
        stale_hit = False
        for n, slots in self.opt_state.items():
            host = {}
            for sn, arr in slots.items():
                shard_bytes += _shard_nbytes(arr)
                full = jnp.asarray(jax.device_get(arr))
                if stale is not None and not stale_hit and \
                        (stale is True or str(stale) in n):
                    full = jnp.zeros_like(full)
                host[sn] = full
            if stale is not None and not stale_hit and \
                    (stale is True or str(stale) in n):
                stale_hit = True
            opt._accumulators[id(named_p[n])] = host
        _SHARD_BYTES.set(shard_bytes)

    def sharding_state(self):
        """The placement record `Model.save` embeds in the `.pdtrain`
        payload (utils/resume.capture_train_state): mesh shape, dp
        axis, ZeRO stage, and the per-leaf PartitionSpecs — everything
        a resume needs to KNOW how the checkpoint was laid out, and to
        journal the `reshard` event when the current mesh differs. The
        restore path re-derives placements for the CURRENT mesh (a
        fresh ShardedTrainStep device_puts the restored host copies),
        so these specs are provenance, not instructions."""
        return {
            "mesh": {name: int(self.mesh.shape[name])
                     for name in self.mesh.axis_names},
            "dp_axis": self.dp_axis,
            "zero_stage": int(self.zero_stage),
            "exact_reshard": bool(self.exact_reshard),
            "param_specs": {n: _spec_doc(s)
                            for n, s in self.param_specs.items()},
            "opt_specs": {n: {sn: _spec_doc(s)
                              for sn, s in slots.items()}
                          for n, slots in self.opt_specs.items()},
        }

    def audit_sharding_decl(self):
        """Declared-sharding record for the mesh-aware program audit
        (tools/jxaudit/mesh_rules.py, threaded through the xprof
        registry's sharded_train_step spec). Hands out the LIVE
        PartitionSpec trees the compiled step was built with — the audit
        compares these against what XLA committed to in the optimized
        HLO, and because they are the same objects `jax.jit` received,
        the declarations cannot drift from the code.

        `in_specs` is keyed by positional argnum of `_step`
        (params, buffers, opt_state, acc); batch/scalar args are
        unconstrained at jit time and carry no declaration.
        `expected_collectives` whitelists collective opcodes the
        reshard-in-body rule must NOT flag: the flash-attention kernel's
        shifted-window slice/pad partitions into halo-exchange
        collective-permutes under GSPMD whenever the batch dim doesn't
        divide dp — data movement the kernel's math asked for, not an
        implicit reshard (their exact counts are still gated by the
        collective-budget rows)."""
        return {
            "mesh_axes": {name: int(self.mesh.shape[name])
                          for name in self.mesh.axis_names},
            "in_specs": {
                0: dict(self.param_specs),
                1: dict(self.buffer_specs),
                2: {n: dict(slots)
                    for n, slots in self.opt_specs.items()},
                3: {n: self.param_specs[n] for n in self.grad_acc},
            },
            # exact_reshard pins state/grads replicated via explicit
            # with_sharding_constraint sites; sharding-dropped checks the
            # traced program still carries them
            "constraint_specs": [repr(P())] if self.exact_reshard else [],
            "expected_collectives": ("collective-permute",),
        }
