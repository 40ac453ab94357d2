"""paddle.inference — the deployment surface (ref paddle/fluid/inference
AnalysisPredictor + api/paddle_inference_api.h + api/analysis_config.cc;
the TRT/Lite/capi engines are out of scope per SURVEY §7 — XLA is the
engine).

Two artifact families serve through one Predictor:
  * StableHLO bundles from paddle.jit.save (static/export.py)
  * reference-saved protobuf models (dirname/__model__ or protobuf
    .pdmodel + LoDTensor params) via static/paddle_compat.py

Config knobs are HONEST: each either takes real effect (memory_optim ->
input-buffer donation in the compiled call; ir_optim=False -> the
uncompiled per-call execution path; cpu_math_threads -> XLA:CPU thread
cap when set before backend init) or warns loudly that XLA owns the
concern (GPU/mkldnn/TensorRT switches).
"""
import os
import warnings

import numpy as np

from ..utils import telemetry


def _inert(knob, why):
    warnings.warn(
        f"paddle.inference.Config.{knob} has no effect on the TPU build: "
        f"{why}", stacklevel=3)


class Config:
    """ref paddle_infer.Config (api/analysis_config.cc)."""

    def __init__(self, model_dir=None, params_file=None):
        self.model_dir = model_dir
        self.params_file = params_file
        self._memory_optim = True
        self._ir_optim = True
        self._cpu_math_threads = None
        self._llm_opts = None
        self._fleet_opts = None
        self._metrics_exporter = None

    # ---- LLM serving engine (paddle_tpu.serving front door)
    def enable_llm_engine(self, num_slots=4, max_len=256, prefill_len=None,
                          eos_token_id=None, max_queue=None, paged=False,
                          block_size=16, num_blocks=None,
                          speculative=False, draft_config=None, k=4,
                          paged_kernel=None):
        """Arm this Config for create_llm_predictor: slot-count / cache
        horizon / prompt bucket for the continuous-batching engine
        (docs/serving.md). switch_ir_optim(False) carries over as the
        engine's uncompiled per-call path, the same meaning it has for
        the classic Predictor. paged=True serves from the block-table
        paged KV cache (docs/serving.md "Paged KV cache"): HBM scales
        with num_blocks (default: dense-equivalent capacity), prompts
        chunk through `prefill_len`-sized prefill chunks, and identical
        prompt prefixes share blocks. speculative=True (implies paged)
        adds draft-k/verify-once speculative decoding (docs/serving.md
        "Speculative decoding"): a small draft model proposes `k`
        tokens per slot per wave and the target verifies them in ONE
        batched forward, output distribution-identical (bitwise under
        greedy). The draft comes from create_llm_predictor's
        `draft_model=` (pass a model with TRAINED weights loaded — the
        engine snapshots its params at construction) or is built from
        `draft_config` (a config of the target model's family, same
        vocab) — note a draft_config-built draft is freshly
        initialized: correctness holds regardless (the verify step
        guarantees the target distribution), but acceptance — the whole
        speedup — needs a draft that actually predicts the target.
        paged_kernel pins the paged-attention core the engine compiles
        with ("reference" | "pallas"); the default None takes what the
        backend decides, "pallas" on a TPU and "reference" elsewhere
        (nn/paged_attention.py). The engine's /healthz reports the
        resolved core."""
        self._llm_opts = {
            "num_slots": int(num_slots),
            "max_len": int(max_len),
            "prefill_len": None if prefill_len is None else int(prefill_len),
            "eos_token_id": eos_token_id,
            "max_queue": max_queue,
            "paged": bool(paged) or bool(speculative),
            "block_size": int(block_size),
            "num_blocks": None if num_blocks is None else int(num_blocks),
            "speculative": bool(speculative),
            "draft_config": draft_config,
            "spec_k": int(k),
            "paged_kernel": paged_kernel,
        }
        return self

    def llm_engine_enabled(self):
        return self._llm_opts is not None

    def enable_llm_fleet(self, replicas=None, policy="affinity",
                         prefill_replicas=None, decode_replicas=None,
                         tenants=None):
        """Serve through a replica fleet instead of one scheduler
        (docs/serving.md "Serving fleet"): create_llm_predictor builds
        `replicas` engines from the enable_llm_engine knobs behind a
        FleetRouter (prefix-affinity routing, token-exact failover,
        elastic scale). Setting prefill_replicas/decode_replicas
        switches to the DISAGGREGATED topology (docs/serving.md
        "Disaggregated prefill/decode"): that many role-pinned prefill
        and decode replicas — a pure split fleet unless `replicas`
        explicitly asks for unified ones alongside (the default is 0
        unified in the split topology, 2 otherwise) — long prompts
        prefill on the prefill side and hand their KV blocks to a
        decode replica.
        `tenants` (an iterable of serving.Tenant, or a prebuilt
        QoSManager) arms multi-tenant QoS — per-tenant SLO windows,
        weighted-fair admission under pool pressure, priority
        preemption (docs/serving.md "Multi-tenant QoS"); submit() then
        accepts tenant=/priority=."""
        disagg = prefill_replicas is not None or decode_replicas is not None
        if replicas is None:
            replicas = 0 if disagg else 2
        self._fleet_opts = {
            "replicas": int(replicas),
            "policy": str(policy),
            "prefill_replicas": (None if prefill_replicas is None
                                 else int(prefill_replicas)),
            "decode_replicas": (None if decode_replicas is None
                                else int(decode_replicas)),
            "tenants": tenants,
        }
        return self

    def llm_fleet_enabled(self):
        return self._fleet_opts is not None

    def enable_metrics_exporter(self, port=0, host="127.0.0.1"):
        """Arm the unified-telemetry /metrics exporter
        (docs/observability.md): create_llm_predictor starts a
        background stdlib-http.server thread serving /metrics
        (Prometheus), /metrics.json and /healthz. port=0 picks a free
        port — read it from predictor.metrics_server.port."""
        self._metrics_exporter = {"port": int(port), "host": str(host)}
        return self

    def metrics_exporter_enabled(self):
        return self._metrics_exporter is not None

    # ---- knobs with real effect
    def enable_memory_optim(self, flag=True):
        """memory_optim (ref analysis_config.cc EnableMemoryOptim):
        donate input buffers to the compiled call so XLA reuses them for
        activations/outputs."""
        self._memory_optim = bool(flag)

    def disable_memory_optim(self):
        self._memory_optim = False

    def switch_ir_optim(self, flag=True):
        """ir_optim=False (ref analysis_config.cc SwitchIrOptim) runs the
        UNOPTIMIZED path: per-call StableHLO replay with no cached
        compiled executable — the analog of serving without the IR pass
        pipeline."""
        self._ir_optim = bool(flag)

    def set_cpu_math_library_num_threads(self, n):
        """Takes effect only before the first backend use (XLA:CPU reads
        the flag at client init) — same constraint the reference has on
        thread-pool construction."""
        self._cpu_math_threads = int(n)
        import jax
        try:
            backend_up = jax._src.xla_bridge._backends  # noqa: SLF001
        except AttributeError:
            backend_up = {}
        if backend_up:
            _inert("set_cpu_math_library_num_threads",
                   "the XLA:CPU client is already initialized; set it "
                   "before the first jax computation")
        else:
            flags = os.environ.get("XLA_FLAGS", "")
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_cpu_multi_thread_eigen="
                f"{'true' if n > 1 else 'false'} "
                f"intra_op_parallelism_threads={n}").strip()

    def set_model(self, model_dir, params_file=None):
        self.model_dir = model_dir
        if params_file is not None:
            self.params_file = params_file

    # ---- knobs XLA owns: accepted for API compat, loudly inert
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        _inert("enable_use_gpu", "device placement is XLA's (the model "
               "runs on the available TPU/CPU backend)")

    def disable_gpu(self):
        pass                      # already not-GPU; nothing to disable

    def enable_mkldnn(self):
        _inert("enable_mkldnn", "XLA:CPU replaces the mkldnn kernels")

    def enable_tensorrt_engine(self, *args, **kwargs):
        _inert("enable_tensorrt_engine", "XLA is the execution engine; "
               "there is no TensorRT subgraph pass")

    def enable_lite_engine(self, *args, **kwargs):
        _inert("enable_lite_engine", "XLA is the execution engine")

    def model_path(self):
        return self.model_dir

    def memory_optim_enabled(self):
        return self._memory_optim

    def ir_optim(self):
        return self._ir_optim


class Predictor:
    """ref AnalysisPredictor: named input/output handles + run().

    StableHLO artifacts execute through ONE jitted call (params/buffers
    captured, inputs donated when memory_optim); reference protobuf
    models execute through the standard Executor."""

    def __init__(self, config):
        self._config = config
        path = config.model_path()
        self._mode = None
        self._pending = {}         # handle-fed inputs (ZeroCopyRun style)
        self._last_outputs = None
        if not path:
            raise ValueError(
                "inference Config has no model path — construct it as "
                "Config(model_dir) or call config.set_model(path)")
        if os.path.exists(path + ".meta.json"):
            self._init_stablehlo(path, config)
        else:
            self._init_program(path, config)

    # ---- StableHLO bundle (paddle.jit.save)
    def _init_stablehlo(self, path, config):
        import jax
        from ..static.export import load
        self._mode = "stablehlo"
        self._layer = load(path)
        ex = self._layer._exported

        def call(params, buffers, *xs):
            return ex.call(params, buffers, *xs)

        if config.ir_optim():
            # donate the per-call input buffers; params/buffers persist
            n_fixed = 2
            spec = self._layer._meta.get("inputs", [])
            donate = tuple(range(n_fixed, n_fixed + len(spec))) \
                if config.memory_optim_enabled() else ()
            self._run = jax.jit(call, donate_argnums=donate)
        else:
            self._run = call            # uncompiled per-call replay

    # ---- reference protobuf / native JSON program
    def _init_program(self, path, config):
        from ..static import load_inference_model, Executor
        self._mode = "program"
        prog, feeds, fetches = load_inference_model(
            path, params_filename=config.params_file)
        self._prog, self._feeds, self._fetches = prog, feeds, fetches
        self._exe = Executor()
        if not config.ir_optim():
            _inert("switch_ir_optim(False)",
                   "program-path serving always executes the jit-compiled "
                   "program (there is no unoptimized interpreter for it)")

    def get_input_names(self):
        if self._mode == "program":
            return list(self._feeds)
        spec = self._layer._meta.get("inputs", [])
        return [s.get("name") or f"x{i}" if isinstance(s, dict) else f"x{i}"
                for i, s in enumerate(spec)] or ["x0"]

    def get_output_names(self):
        if self._mode == "program":
            return list(self._fetches)
        return [f"out{i}"
                for i in range(self._layer._meta.get("n_outputs", 1))]

    def get_input_handle(self, name):
        """ref paddle_infer.Predictor.get_input_handle — the zero-copy
        serving surface: handle.reshape/copy_from_cpu, run(),
        output handle.copy_to_cpu()."""
        if name not in self.get_input_names():
            raise KeyError(f"no input named {name!r}; "
                           f"inputs: {self.get_input_names()}")
        return _TensorHandle(self, name, is_input=True)

    def get_output_handle(self, name):
        if name not in self.get_output_names():
            raise KeyError(f"no output named {name!r}; "
                           f"outputs: {self.get_output_names()}")
        return _TensorHandle(self, name, is_input=False)

    def run(self, inputs=None):
        """inputs: list of numpy arrays in input order — or None for the
        handle style (ref ZeroCopyRun: feed via get_input_handle, read
        via get_output_handle). Returns a list of numpy outputs."""
        import jax.numpy as jnp
        from ..framework.tensor import Tensor
        if inputs is None:
            names = self.get_input_names()
            missing = [n for n in names if n not in self._pending]
            if missing:
                raise RuntimeError(
                    "inputs not fed via get_input_handle()."
                    f"copy_from_cpu(): {missing}")
            outs = self.run([self._pending[n] for n in names])
            self._last_outputs = outs
            return True
        if self._mode == "program":
            outs = self._exe.run(self._prog,
                                 feed=dict(zip(self._feeds, inputs)),
                                 fetch_list=self._fetches)
            outs = [np.asarray(o) for o in outs]
            self._last_outputs = outs     # output handles track EVERY run
            return outs
        donating = (self._config.memory_optim_enabled()
                    and self._config.ir_optim())
        arrays = []
        for a in inputs:
            if isinstance(a, Tensor):
                # donation would invalidate the caller's live Tensor —
                # hand the compiled call its own copy instead
                arrays.append(jnp.copy(a._data) if donating else a._data)
            else:
                arrays.append(jnp.asarray(a))
        outs = self._run(self._layer._params, self._layer._buffers, *arrays)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        outs = [np.asarray(o.numpy() if isinstance(o, Tensor) else o)
                for o in outs]
        self._last_outputs = outs         # output handles track EVERY run
        return outs


class _TensorHandle:
    """ref paddle_api.h ZeroCopyTensor / paddle_infer.Tensor: the
    handle-based serving surface (reshape + copy_from_cpu on inputs,
    copy_to_cpu on outputs)."""

    def __init__(self, predictor, name, is_input):
        self._p = predictor
        self.name = name
        self._is_input = is_input
        self._shape = None

    def reshape(self, shape):
        self._shape = tuple(int(s) for s in shape)

    def copy_from_cpu(self, data):
        if not self._is_input:
            raise RuntimeError(f"'{self.name}' is an output handle")
        arr = np.asarray(data)
        if self._shape is not None:
            arr = arr.reshape(self._shape)
        self._p._pending[self.name] = arr

    def copy_to_cpu(self):
        if self._is_input:
            raise RuntimeError(f"'{self.name}' is an input handle")
        outs = self._p._last_outputs
        if outs is None:
            raise RuntimeError("run() has not been called yet")
        return outs[self._p.get_output_names().index(self.name)]

    def shape(self):
        if self._is_input:
            return list(self._shape or ())
        return list(self.copy_to_cpu().shape)


def create_predictor(config):
    return Predictor(config)


class LLMPredictor:
    """Serving-engine analog of Predictor: one Config-built Scheduler +
    ServingEngine pair with a blocking generate() for the simple case and
    the full submit()/run() surface for continuous batching."""

    def __init__(self, config, model, draft_model=None):
        from ..serving import Scheduler
        from ..serving.fleet import DisaggFleetRouter, FleetRouter
        opts = config._llm_opts or {}
        self._eos_token_id = opts.get("eos_token_id")
        telemetry.install_compile_tracking()
        factory = _engine_factory(config, opts, model, draft_model)
        self.router = None
        fleet_opts = config._fleet_opts
        if fleet_opts is None:
            self.engine = factory()
            self.scheduler = Scheduler(self.engine,
                                       max_queue=opts.get("max_queue"))
        else:
            # fleet front door: replicas built from the SAME factory the
            # single-engine path uses, so every enable_llm_engine knob
            # (paged, speculative, kernel choice, ir_optim) carries over
            sched_kw = ({} if opts.get("max_queue") is None
                        else {"max_queue": opts["max_queue"]})
            if (fleet_opts["prefill_replicas"] is not None
                    or fleet_opts["decode_replicas"] is not None):
                self.router = DisaggFleetRouter(
                    factory,
                    prefill_replicas=fleet_opts["prefill_replicas"] or 0,
                    decode_replicas=fleet_opts["decode_replicas"] or 0,
                    unified_replicas=fleet_opts["replicas"],
                    qos=fleet_opts["tenants"],
                    policy=fleet_opts["policy"],
                    scheduler_kwargs=sched_kw)
            else:
                self.router = FleetRouter(
                    factory, replicas=fleet_opts["replicas"],
                    policy=fleet_opts["policy"],
                    scheduler_kwargs=sched_kw)
            self.engine = None
            self.scheduler = None
        self.metrics_server = None
        if config.metrics_exporter_enabled():
            target = self.engine if self.router is None else self.router
            self.metrics_server = target.start_metrics_server(
                **config._metrics_exporter)

    def close(self, drain=True):
        """Graceful shutdown: drain the scheduler (accepted requests
        complete, new submits are shed with finish_reason "rejected")
        and stop the background metrics exporter. drain=False skips the
        wave loop for a hard stop and gives the engine's pool back to
        the device at once (`ServingEngine.release`): nothing more can
        be served. The engine's compiled programs need no teardown."""
        if self.router is not None:
            if drain:
                self.router.shutdown()
            else:
                self.router.stop_metrics_server()
        elif drain:
            self.scheduler.shutdown()
        else:
            self.engine.stop_metrics_server()
            self.engine.release()
        self.metrics_server = None

    def generate(self, prompt, **kw):
        kw.setdefault("eos_token_id", self._eos_token_id)
        if self.router is not None:
            return self.router.generate(prompt, **kw)
        return self.scheduler.generate(prompt, **kw)

    def submit(self, **kw):
        kw.setdefault("eos_token_id", self._eos_token_id)
        if self.router is not None:
            return self.router.submit(**kw)
        return self.scheduler.submit(**kw)

    def run(self, **kw):
        if self.router is not None:
            return self.router.run(**kw)
        return self.scheduler.run(**kw)

    def health(self):
        """Engine (or fleet) health payload — what /healthz serves."""
        if self.router is not None:
            return self.router.health()
        return self.engine._health()

    @property
    def metrics(self):
        if self.router is not None:
            return self.router.metrics
        return self.scheduler.metrics


def _engine_factory(config, opts, model, draft_model):
    """One closure building the Config-described engine — called once
    for a single-engine predictor, once per replica for a fleet."""
    from ..serving import (PagedServingEngine, ServingEngine,
                           SpeculativePagedEngine)
    if opts.get("speculative") and draft_model is None:
        draft_cfg = opts.get("draft_config")
        if draft_cfg is None:
            raise ValueError(
                "speculative serving needs a draft model: pass "
                "draft_model= to create_llm_predictor or "
                "draft_config= to enable_llm_engine")
        # same family as the target: the configs carry the family, the
        # model class carries the architecture. Built ONCE here so a
        # fleet's replicas share one draft (digest-identical state).
        draft_model = type(model)(draft_cfg)

    def factory():
        """The engine, as a `startup/engine` span (one a replica)."""
        with telemetry.startup_span(
                "engine", num_slots=opts.get("num_slots", 4)) as span:
            engine = build()
            pool = getattr(engine, "block_pool", None)
            span.ids.update(
                blocks=pool.num_blocks if pool is not None else 0,
                pool_bytes=engine.pool_bytes)
        return engine

    def build():
        if opts.get("speculative"):
            return SpeculativePagedEngine(
                model, draft_model,
                spec_k=opts.get("spec_k", 4),
                num_slots=opts.get("num_slots", 4),
                max_len=opts.get("max_len", 256),
                block_size=opts.get("block_size", 16),
                num_blocks=opts.get("num_blocks"),
                prefill_chunk_len=opts.get("prefill_len"),
                paged_kernel=opts.get("paged_kernel"),
                jit_compile=config.ir_optim())
        if opts.get("paged"):
            return PagedServingEngine(
                model,
                num_slots=opts.get("num_slots", 4),
                max_len=opts.get("max_len", 256),
                block_size=opts.get("block_size", 16),
                num_blocks=opts.get("num_blocks"),
                prefill_chunk_len=opts.get("prefill_len"),
                paged_kernel=opts.get("paged_kernel"),
                jit_compile=config.ir_optim())
        return ServingEngine(
            model,
            num_slots=opts.get("num_slots", 4),
            max_len=opts.get("max_len", 256),
            prefill_len=opts.get("prefill_len"),
            jit_compile=config.ir_optim())
    return factory


def create_llm_predictor(config, model=None, draft_model=None):
    """Front door from the inference Config to paddle_tpu.serving: the
    Config carries the engine knobs (enable_llm_engine: slots, cache
    horizon, prefill bucket, eos, queue bound, speculative draft;
    switch_ir_optim(False) -> uncompiled engine;
    set_cpu_math_library_num_threads applies as for any predictor) and
    `model` is a causal LM exposing prefill/decode_step/init_cache
    (nlp.LlamaForCausalLM, nlp.GPTForPretraining). `draft_model` (same
    family + vocab, typically far fewer layers) serves the speculative
    configuration. LLM weights load through the model constructors +
    paddle.load — there is no protobuf/StableHLO artifact path for the
    decode-cache entry points."""
    if model is None:
        raise ValueError(
            "create_llm_predictor needs `model` (a causal LM with "
            "prefill/decode_step/init_cache); the classic artifact paths "
            "(create_predictor) have no KV-cache decode entry points")
    if not config.llm_engine_enabled():
        config.enable_llm_engine()
    return LLMPredictor(config, model, draft_model=draft_model)
