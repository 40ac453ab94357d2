"""Tracked-program registry: the compiled programs the repo gates on.

Each entry is a deterministic abstract-shape lowering spec — a tiny
canonical configuration (2 layers, hidden <= 128: HLO structure, not
capacity, is what's audited, and tier-1 shares the 870s budget) of a
REAL hot path:

  * `serving_decode_wave` / `serving_prefill` — the ServingEngine's two
    programs, lowered from the engine's own raw closures (the engine
    stashes them precisely so this audit and the serving path cannot
    drift apart);
  * `paged_decode_wave` / `paged_prefill_chunk` — the
    PagedServingEngine's two programs (block-table KV cache, chunked
    prefill; serving/paged), same stashed-closure discipline — jxaudit
    verifies the block POOL leaves stay donation-aliased at engine
    shapes;
  * `paged_decode_attention` — the block-table decode core
    (page write/gather through traced tables + the GQA cached core) — the
    reference oracle the Pallas core is held to (the kernel itself is
    compiled for a described v5e in tests/test_tpu_compile.py);
  * `train_step` — `jit.TrainStep` (forward + backward + AdamW, donated
    state) on the canonical 2-layer GPT config — the same topology
    bench.py's CPU smoke compiles, so the persistent compile cache is
    shared;
  * `sharded_train_step` — `distributed.sharded.ShardedTrainStep`
    (GSPMD, ZeRO-1 dp-sharded optimizer state) on the same 2-layer GPT
    config over the tier-1 8-CPU-device dp mesh, active dropout so the
    PRNG key stays a live entry parameter — jxaudit's donation rule
    verifies the dp-SHARDED opt-state leaves are actually aliased in
    the partitioned HLO (the PR-7 eager-optimizer donation bug, sharded
    incarnation); `sharded_train_step_z3` is the same step at ZeRO-3
    (params dp-sharded too, gather-on-use);
  * `sharded_decode_wave` — the dense engine's decode wave pjit'd with
    head-sharded K/V caches over the 8-device `mp` mesh (the ROADMAP
    item-1 tensor-parallel serving scaffold, gated by the mesh-aware
    audit before the real TP engine lands);
  * `cached_decode_attention` — the GQA single-token cached attention
    core from nn/transformer.py with a per-slot position VECTOR (the
    serving decode regime);
  * `prefill_flash_attention` — the causal prompt-phase attention array
    kernel the prefill paths route through.

Specs are dicts: {name, fn | jitted, args, jit_kwargs, description}.
Builders reset the global seed so repeated snapshots are
bit-deterministic; parameter VALUES never reach the lowering anyway —
only shapes/dtypes do.
"""

# serving canonical shape (mirrors tests/test_serving.py scale)
SERVING = dict(vocab=128, hidden=64, layers=2, heads=4, max_len=64,
               prefill_len=16, num_slots=4)
# paged-serving canonical shape (mirrors tests/test_serving_paged.py):
# same model topology, block-table cache
PAGED = dict(vocab=128, hidden=64, layers=2, heads=4, max_len=64,
             block_size=8, num_blocks=33, chunk_len=16, num_slots=4)
# speculative canonical shape (mirrors tests/test_serving_spec.py):
# the PAGED target plus a 1-layer draft GPT and k=3
SPEC = dict(PAGED, spec_k=3, draft_hidden=32, draft_layers=1,
            draft_heads=2)
# train canonical shape == bench.py CPU-smoke config
TRAIN = dict(vocab=512, hidden=128, layers=2, heads=4, seq=128, batch=2)
# sharded-train canonical mesh: the tier-1 8-CPU-device dp mesh
# (conftest's --xla_force_host_platform_device_count=8), ZeRO-1. The
# batch (2) is not divisible by dp, so it rides replicated — the
# exact-reshard regime chaos_train proves bitwise. The z3 sibling
# (`sharded_train_step_z3`) dp-shards the PARAMETERS too — gather-on-use
# in the partitioned HLO, the regime test_zero3.py proves — so the
# mesh-aware audit gates both ZeRO points the repo ships.
SHARDED_TRAIN = dict(TRAIN, dp=8, zero_stage=1, dropout=0.1)
# tensor-parallel decode canonical shape: the dense serving wave with
# heads == mp so the per-slot K/V caches shard cleanly head-wise over
# the tier-1 8-device mesh — the ROADMAP item-1 (TP sharded serving)
# scaffold the mesh-aware audit gates before the real engine lands
SHARDED_SERVING = dict(SERVING, heads=8, mp=8)

TRACKED_PROGRAMS = ("serving_decode_wave", "serving_prefill",
                    "paged_decode_wave", "paged_prefill_chunk",
                    "paged_spec_draft_wave", "paged_spec_verify",
                    "train_step", "sharded_train_step",
                    "sharded_train_step_z3", "sharded_decode_wave",
                    "cached_decode_attention",
                    "paged_decode_attention",
                    "prefill_flash_attention")


def engine_program_specs(engine, prefix=None):
    """Audit specs for a LIVE engine's compiled programs, with the
    engine's actual shapes — used on the canonical engines below.
    Dispatches on the engine flavour: a paged engine (block_pool)
    audits its decode-wave-with-tables and prefill-chunk programs; a
    speculative engine (draft_model) audits its draft/verify/prefill
    trio."""
    if hasattr(engine, "draft_model"):
        return _spec_engine_specs(engine, prefix or "paged_spec")
    if hasattr(engine, "block_pool"):
        return _paged_engine_specs(engine, prefix or "paged")
    return _dense_engine_specs(engine, prefix or "serving")


def _greedy(engine):
    """A request's sampling surface with every knob at rest."""
    return engine._sampling_state(False, 1.0, 0, 1.0, None, False)


def _all_lanes(engine):
    """(active, poison) of a wave with every lane decoding."""
    import numpy as np
    S = engine.num_slots
    return np.ones((S,), bool), np.zeros((S,), bool)


def _dense_engine_specs(engine, prefix):
    """The engine's own closures with the engine's own argument tuples
    (`_wave_args`, `_prompt_args`): what an audit lowers is what a
    dispatch sends, so the two cannot drift."""
    import numpy as np

    S = engine.num_slots
    jit_kwargs = {"donate_argnums": engine._program_donate_argnums}
    decode_args = engine._wave_args(*_all_lanes(engine), engine._key)
    prefill_args = (
        engine._params, engine._buffers, engine._caches,
        *engine._prompt_args(0, np.zeros((engine.prefill_len,), np.int32),
                             0, 1, 0, _greedy(engine)))
    return [
        {"name": f"{prefix}_decode_wave", "fn": engine._decode_wave_fn,
         "args": decode_args, "jit_kwargs": jit_kwargs,
         "description": f"one batched decode token for every slot "
                        f"(slots={S}, max_len={engine.max_len})"},
        {"name": f"{prefix}_prefill", "fn": engine._prefill_fn,
         "args": prefill_args, "jit_kwargs": jit_kwargs,
         "description": f"one prompt bucket admission "
                        f"(prefill_len={engine.prefill_len})"},
    ]


def _chunk_args(engine):
    """The prefill-chunk program's arguments for one chunk of slot 0."""
    import numpy as np
    C = engine.prefill_chunk_len
    return (*engine._prefill_chunk_args(0),
            *engine._prompt_args(0, np.zeros((C,), np.int32), 0, 1, 0,
                                 _greedy(engine), engine._tables[0]))


def _paged_engine_specs(engine, prefix):
    S = engine.num_slots
    C = engine.prefill_chunk_len
    jit_kwargs = {"donate_argnums": engine._program_donate_argnums}
    # the block tables ride in the packed lanes: traced values
    decode_args = engine._wave_args(*_all_lanes(engine), engine._key)
    return [
        {"name": f"{prefix}_decode_wave", "fn": engine._decode_wave_fn,
         "args": decode_args, "jit_kwargs": jit_kwargs,
         "description": f"one batched decode token for every slot "
                        f"through block tables (slots={S}, "
                        f"blocks={engine.block_pool.num_blocks}x"
                        f"{engine.block_size})"},
        {"name": f"{prefix}_prefill_chunk", "fn": engine._prefill_fn,
         "args": _chunk_args(engine), "jit_kwargs": jit_kwargs,
         "description": f"one prompt chunk admission through a block "
                        f"table (chunk={C})"},
    ]


def _spec_engine_specs(engine, prefix):
    """Audit specs for a LIVE SpeculativePagedEngine's three programs:
    the draft wave (k+1 draft decode steps in one executable), the
    verify wave (chunk-scored target forward + exact acceptance-
    rejection tail), and the dual-model prefill chunk. jxaudit's
    donation rule runs over these to prove BOTH the target and draft
    KV-pool leaves stay aliased; hlo_audit banks the verify program's
    bytes-accessed so a k+1-disproportionate regression gates."""
    import numpy as np

    S, k, V = engine.num_slots, engine.spec_k, engine.vocab_size
    C = engine.prefill_chunk_len
    jit_kwargs = {"donate_argnums": engine._program_donate_argnums}
    # both waves take the lane state (the draft reads it, the verify
    # takes it donated) and the one packed argument (tables, lanes,
    # spec_len)
    lanes, bias = engine._lane_args(
        *_all_lanes(engine), engine._tables, np.ones((S,), np.int32))
    draft_args = (engine._draft_params, engine._draft_buffers,
                  engine._caches, engine._lane_tok, engine._lane_pos,
                  lanes, bias, engine._key)
    verify_args = (
        engine._params, engine._buffers, engine._caches,
        engine._lane_tok, engine._lane_pos, lanes, bias,
        np.zeros((S, k), np.int32),                 # draft tokens
        np.zeros((S, k, V), np.float32),            # draft probs
        engine._key)
    return [
        {"name": f"{prefix}_draft_wave", "fn": engine._draft_wave_fn,
         "args": draft_args,
         "jit_kwargs": {"donate_argnums": engine._draft_donate_argnums},
         "description": f"k+1={engine.spec_k + 1} draft decode steps "
                        f"in one executable (slots={S})"},
        {"name": f"{prefix}_verify", "fn": engine._decode_wave_fn,
         "args": verify_args, "jit_kwargs": jit_kwargs,
         "description": f"verify-once: one chunk-scored target forward "
                        f"over C=k+1={engine.spec_k + 1} positions + "
                        "exact acceptance-rejection"},
        {"name": f"{prefix}_prefill_chunk", "fn": engine._prefill_fn,
         "args": _chunk_args(engine),
         "jit_kwargs": {"donate_argnums": engine._prefill_donate_argnums},
         "description": f"dual-model prompt chunk admission (target + "
                        f"draft K/V, chunk={C})"},
    ]


def _serving_specs():
    import paddle_tpu as pt
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    cfg = GPTConfig(vocab_size=SERVING["vocab"],
                    hidden_size=SERVING["hidden"],
                    num_layers=SERVING["layers"],
                    num_heads=SERVING["heads"],
                    max_seq_len=SERVING["max_len"],
                    dropout=0.0, attn_dropout=0.0)
    engine = ServingEngine(GPTForPretraining(cfg),
                           num_slots=SERVING["num_slots"],
                           max_len=SERVING["max_len"],
                           prefill_len=SERVING["prefill_len"])
    return engine_program_specs(engine)


def _paged_serving_specs():
    import paddle_tpu as pt
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import PagedServingEngine

    pt.seed(0)
    cfg = GPTConfig(vocab_size=PAGED["vocab"],
                    hidden_size=PAGED["hidden"],
                    num_layers=PAGED["layers"],
                    num_heads=PAGED["heads"],
                    max_seq_len=PAGED["max_len"],
                    dropout=0.0, attn_dropout=0.0)
    engine = PagedServingEngine(GPTForPretraining(cfg),
                                num_slots=PAGED["num_slots"],
                                max_len=PAGED["max_len"],
                                block_size=PAGED["block_size"],
                                num_blocks=PAGED["num_blocks"],
                                prefill_chunk_len=PAGED["chunk_len"])
    return engine_program_specs(engine)


def _spec_serving_specs():
    import paddle_tpu as pt
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import SpeculativePagedEngine

    C = SPEC
    pt.seed(0)
    cfg = GPTConfig(vocab_size=C["vocab"], hidden_size=C["hidden"],
                    num_layers=C["layers"], num_heads=C["heads"],
                    max_seq_len=C["max_len"], dropout=0.0,
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    dcfg = GPTConfig(vocab_size=C["vocab"],
                     hidden_size=C["draft_hidden"],
                     num_layers=C["draft_layers"],
                     num_heads=C["draft_heads"],
                     max_seq_len=C["max_len"], dropout=0.0,
                     attn_dropout=0.0)
    engine = SpeculativePagedEngine(model, GPTForPretraining(dcfg),
                                    spec_k=C["spec_k"],
                                    num_slots=C["num_slots"],
                                    max_len=C["max_len"],
                                    block_size=C["block_size"],
                                    num_blocks=C["num_blocks"],
                                    prefill_chunk_len=C["chunk_len"])
    return engine_program_specs(engine)


def train_step_spec(step, inputs, labels):
    """Audit spec for a LIVE TrainStep: lowers the step's own compiled
    callable with its current state (injection needs a raw fn, which
    TrainStep does not expose — gate regressions via the registry's
    canonical instance instead)."""
    import jax
    import jax.numpy as jnp
    args = (step.params, step.buffers, step.opt_state, step.grad_acc,
            jax.random.PRNGKey(0), jnp.asarray(1e-4, jnp.float32),
            jnp.asarray(1, jnp.int32), tuple(inputs), tuple(labels))
    return {"name": "train_step", "jitted": step._compiled, "args": args,
            # donation metadata for the semantic audit (tools/jxaudit):
            # a prebuilt jitted carries no introspectable donate info,
            # so the spec passes the TrainStep's own declaration through
            "donate_argnums": getattr(step, "_donate_argnums", ()),
            "arg_names": ("params", "buffers", "opt_state", "acc", "key",
                          "lr", "step_i", "inputs", "labels"),
            "description": "forward+backward+optimizer, one donated "
                           "executable (canonical 2-layer GPT)"}


def _train_step_spec():
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss

    pt.seed(0)
    cfg = GPTConfig(vocab_size=TRAIN["vocab"], hidden_size=TRAIN["hidden"],
                    num_layers=TRAIN["layers"], num_heads=TRAIN["heads"],
                    max_seq_len=TRAIN["seq"], dropout=0.0,
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    step = TrainStep(model, gpt_pretrain_loss, opt, donate=True)
    ids = np.zeros((TRAIN["batch"], TRAIN["seq"]), np.int32)
    return train_step_spec(step, (ids,), (ids,))


def sharded_train_step_spec(step, inputs, labels,
                            name="sharded_train_step"):
    """Audit spec for a LIVE ShardedTrainStep: lowers the step's own
    compiled (pjit'd, in/out-sharded, donated) callable with its
    current sharded state — the program a mesh training run actually
    dispatches. `inputs`/`labels` are global batch arrays; they ride
    through the step's own `_shard_batch` so the lowering sees the same
    placements a real step does. The `sharding` entry is the step's own
    declaration of record (`audit_sharding_decl`) — what the mesh-aware
    rules (tools/jxaudit/mesh_rules.py) compare against the compiled
    module's committed annotations."""
    import jax
    import jax.numpy as jnp
    args = (step.params, step.buffers, step.opt_state, step.grad_acc,
            jax.random.PRNGKey(0), jnp.asarray(1e-4, jnp.float32),
            jnp.asarray(1, jnp.int32), step._shard_batch(tuple(inputs)),
            step._shard_batch(tuple(labels)))
    return {"name": name, "jitted": step._compiled,
            "args": args,
            "donate_argnums": getattr(step, "_donate_argnums", ()),
            "arg_names": ("params", "buffers", "opt_state", "acc", "key",
                          "lr", "step_i", "inputs", "labels"),
            "sharding": step.audit_sharding_decl(),
            "description": "GSPMD forward+backward+AdamW with ZeRO "
                           f"stage-{step.zero_stage} dp-sharded opt "
                           "state, one donated executable "
                           f"(mesh {dict(zip(step.mesh.axis_names, step.mesh.devices.shape))})"}


def _sharded_train_step_spec(zero_stage=None,
                             name="sharded_train_step"):
    import numpy as np
    import jax
    from jax.sharding import Mesh

    import paddle_tpu as pt
    from paddle_tpu.distributed.sharded import ShardedTrainStep
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss

    C = SHARDED_TRAIN
    if zero_stage is None:
        zero_stage = C["zero_stage"]
    pt.seed(0)
    cfg = GPTConfig(vocab_size=C["vocab"], hidden_size=C["hidden"],
                    num_layers=C["layers"], num_heads=C["heads"],
                    max_seq_len=C["seq"], dropout=C["dropout"],
                    attn_dropout=0.0)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    # an explicit mesh, NOT make_mesh: building an audit spec must not
    # install (or leak) global mesh state into whatever runs next
    devs = jax.devices()
    dp = min(C["dp"], len(devs))
    mesh = Mesh(np.asarray(devs[:dp]).reshape(dp), ("dp",))
    step = ShardedTrainStep(model, gpt_pretrain_loss, opt, mesh=mesh,
                            zero_stage=zero_stage)
    ids = np.zeros((C["batch"], C["seq"]), np.int32)
    return sharded_train_step_spec(step, (ids,), (ids,), name=name)


def _sharded_decode_wave_spec():
    """pjit'd tensor-parallel decode wave on the 8-device CPU mesh: the
    dense engine's OWN decode-wave closure, re-jitted with the per-slot
    K/V caches sharded head-wise (`P(None, 'mp', None, None)`) and
    params/buffers replicated — a faithful scaffold of ROADMAP item 1's
    TP serving regime, with the caches still donated so the mesh-aware
    donation rule proves aliasing survives pjit at shard shapes."""
    import numpy as np
    import jax
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.serving import ServingEngine

    C = SHARDED_SERVING
    pt.seed(0)
    cfg = GPTConfig(vocab_size=C["vocab"], hidden_size=C["hidden"],
                    num_layers=C["layers"], num_heads=C["heads"],
                    max_seq_len=C["max_len"], dropout=0.0,
                    attn_dropout=0.0)
    engine = ServingEngine(GPTForPretraining(cfg),
                           num_slots=C["num_slots"],
                           max_len=C["max_len"],
                           prefill_len=C["prefill_len"])
    devs = jax.devices()
    mp = min(C["mp"], len(devs))
    mesh = Mesh(np.asarray(devs[:mp]).reshape(mp), ("mp",))
    ns = lambda spec: NamedSharding(mesh, spec)
    cache_spec = P(None, "mp", None, None)    # [slots, HEADS, len, d]
    base = _dense_engine_specs(engine, "sharded")[0]
    # caches are argnum 2 (the donated state); everything else —
    # params, buffers, per-slot control vectors, key — is replicated.
    # in_shardings rides pytree PREFIXES: one NamedSharding per argnum
    # covers every leaf of that arg.
    in_sh = tuple(ns(cache_spec) if i == 2 else ns(P())
                  for i in range(len(base["args"])))
    args = tuple(jax.device_put(a, sh)
                 for a, sh in zip(base["args"], in_sh))
    return {
        "name": "sharded_decode_wave", "fn": base["fn"], "args": args,
        "jit_kwargs": dict(base["jit_kwargs"], in_shardings=in_sh),
        "donate_argnums": base["jit_kwargs"]["donate_argnums"],
        "sharding": {
            "mesh_axes": {a: int(mesh.shape[a])
                          for a in mesh.axis_names},
            "in_specs": {2: cache_spec},
            "constraint_specs": [],
            "expected_collectives": (),
        },
        "description": "tensor-parallel batched decode token: head-"
                       f"sharded K/V caches over mp={mp} "
                       f"(slots={C['num_slots']}, heads={C['heads']})"}


def _attention_specs():
    import jax.numpy as jnp
    from paddle_tpu.nn.paged_attention import (gather_block_kv,
                                               write_block_kv)
    from paddle_tpu.nn.transformer import cached_decode_attention
    from paddle_tpu.ops.pallas.flash_attention import _flash_array

    b, h, hkv, L, d = 4, 4, 2, 64, 16
    bs, nblk, num_blocks = 8, 8, 17        # nblk * bs == L

    def decode_attn(q, ck, cv, pos):
        return cached_decode_attention(q, ck, cv, pos,
                                       scale=1.0 / (d ** 0.5))

    decode_args = (jnp.zeros((b, h, 1, d), jnp.float32),
                   jnp.zeros((b, hkv, L, d), jnp.float32),
                   jnp.zeros((b, hkv, L, d), jnp.float32),
                   jnp.zeros((b,), jnp.int32))

    def paged_decode_attn(q, kv_t, pool, tables, pos):
        # the serving paged decode core: write the step's K/V through
        # the tables (whole pages, the pool in its stored form), attend
        # over the gathered per-row views; the updated pool rides out
        # (donated in-place, like the engine's)
        pool = write_block_kv(pool, kv_t, kv_t, tables, pos)
        ck, cv = gather_block_kv(pool, tables)
        out = cached_decode_attention(q, ck, cv, pos,
                                      scale=1.0 / (d ** 0.5))
        return out, pool

    paged_args = (jnp.zeros((b, h, 1, d), jnp.float32),
                  jnp.zeros((b, hkv, 1, d), jnp.float32),
                  jnp.zeros((num_blocks, hkv, bs, 2 * d), jnp.float32),
                  jnp.zeros((b, nblk), jnp.int32),
                  jnp.zeros((b,), jnp.int32))

    def prefill_attn(q, k, v):
        return _flash_array(q, k, v, causal=True)

    prefill_args = (jnp.zeros((2, h, L, d), jnp.float32),
                    jnp.zeros((2, h, L, d), jnp.float32),
                    jnp.zeros((2, h, L, d), jnp.float32))
    return [
        {"name": "cached_decode_attention", "fn": decode_attn,
         "args": decode_args,
         "description": "GQA cached decode attention core, per-slot "
                        "position vector"},
        {"name": "paged_decode_attention", "fn": paged_decode_attn,
         "args": paged_args,
         "jit_kwargs": {"donate_argnums": (2,)},
         "description": "block-table decode attention core: KV page "
                        "write/gather through traced tables + the "
                        "GQA cached core (the Pallas core's reference "
                        "oracle)"},
        {"name": "prefill_flash_attention", "fn": prefill_attn,
         "args": prefill_args,
         "description": "causal prompt-phase attention array kernel"},
    ]


def tracked_program_specs(names=None):
    """Build the registry (or the named subset). Builders run lazily so
    `--programs cached_decode_attention` never constructs an engine."""
    want = set(names) if names else set(TRACKED_PROGRAMS)
    unknown = want - set(TRACKED_PROGRAMS)
    if unknown:
        raise ValueError(f"unknown tracked programs {sorted(unknown)}; "
                         f"registry has {list(TRACKED_PROGRAMS)}")
    specs = []
    if want & {"serving_decode_wave", "serving_prefill"}:
        specs += [s for s in _serving_specs() if s["name"] in want]
    if want & {"paged_decode_wave", "paged_prefill_chunk"}:
        specs += [s for s in _paged_serving_specs() if s["name"] in want]
    if want & {"paged_spec_draft_wave", "paged_spec_verify"}:
        specs += [s for s in _spec_serving_specs() if s["name"] in want]
    if "train_step" in want:
        specs.append(_train_step_spec())
    if "sharded_train_step" in want:
        specs.append(_sharded_train_step_spec())
    if "sharded_train_step_z3" in want:
        specs.append(_sharded_train_step_spec(
            zero_stage=3, name="sharded_train_step_z3"))
    if "sharded_decode_wave" in want:
        specs.append(_sharded_decode_wave_spec())
    if want & {"cached_decode_attention", "paged_decode_attention",
               "prefill_flash_attention"}:
        specs += [s for s in _attention_specs() if s["name"] in want]
    return specs
