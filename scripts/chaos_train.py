#!/usr/bin/env python
"""chaos_train — kill/resume parity proof for exact-resume elastic
training, single-chip AND sharded/ZeRO with elastic reshard.

The claim under test (docs/robustness.md): a training run killed at ANY
step boundary and resumed from its latest full-state checkpoint
(`Model.load_latest` + `fit(resume=True)`) produces a per-step
(loss, grad-norm) trajectory BITWISE-identical to the same run never
having been killed. Full-state means params + optimizer accumulators +
LR-scheduler step + the PRNG key chain (dropout streams resume
mid-epoch) + the numpy RNG / data cursor (the shuffle permutation
replays) + the global step — all under one versioned manifest entry
(`.pdparams`/`.pdopt`/`.pdtrain`).

With `--mesh dp=N` the same contract is proven for the SHARDED step
(`distributed/sharded.ShardedTrainStep`, ZeRO stage via
`--zero-stage`): the checkpoint gathers dp-sharded optimizer slots
into host copies and records the mesh/zero/PartitionSpec provenance,
and `--resume-mesh dp=M` resumes onto a DIFFERENT replica count
(elastic reshard) — the stitched trajectory must STILL be bitwise
golden, the resumed process must compile exactly once on the new mesh,
a `reshard` journal event must name both layouts, and the restored
opt-state leaves must actually carry their dp sharding (not silently
replicated, which would undo ZeRO's memory win). The sharded batch is
chosen indivisible by every tested dp so the global math is
dp-invariant (see the exact_reshard contract in sharded.py).

Each boundary scenario arms a deterministic `chaos.TRAIN_STEP` raise as
the kill (host-side, between steps — the SIGKILL analog), resumes into
a model built from a DIFFERENT seed (restore must overwrite, not get
lucky), and compares trajectories with exact float equality.

`--inject` is the positive-control discipline (hlo_audit/jxaudit/
chaos_serving): each arms a fault that breaks one property this
checker claims to verify, and the run must exit 1:

  rng-drop / cursor-drop   drop that key from the captured train state
  spec-drop                drop the `sharding` provenance record — the
                           resumed run can no longer journal the
                           reshard it performed (sharded mode)
  stale-shard              zero one parameter's gathered opt-state
                           slots at checkpoint time, a shard gather
                           that silently missed the dp updates
                           (sharded mode)

    python scripts/chaos_train.py                    # all boundaries
    python scripts/chaos_train.py --smoke            # tier-1 entry
    python scripts/chaos_train.py --mesh dp=2 --resume-mesh dp=4
    python scripts/chaos_train.py --mesh dp=4 --resume-mesh dp=2 \\
        --zero-stage 3 --boundaries mid_epoch
    python scripts/chaos_train.py --inject rng-drop      # must exit 1
    python scripts/chaos_train.py --inject spec-drop     # must exit 1
    python scripts/chaos_train.py --json --journal train_chaos.jsonl

Exit codes: 0 every parity invariant holds, 1 violated invariant,
2 internal error. Tier-1 drives this in-process (tests/test_chaos.py
smoke + injections, tests/test_resume.py per-boundary,
tests/test_sharded_resume.py reshard matrix).
"""
import argparse
import json
import os
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# the sharded scenarios need a multi-device mesh; standalone on a
# 1-device CPU host this must land BEFORE jax initializes (same flag
# tests/conftest.py sets — a no-op when jax is already imported, i.e.
# when tier-1 drives this module in-process)
_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" {_FLAG}=8").strip()

import jax

from paddle_tpu.utils import compile_cache  # noqa: E402
compile_cache.enable()

import numpy as np

import paddle_tpu as pt
from paddle_tpu import hapi
from paddle_tpu.io import TensorDataset
from paddle_tpu.utils import chaos, flight_recorder

# tiny-but-real config: 2-layer GPT with ACTIVE dropout (the RNG chain
# must matter, else the rng-drop control could never diverge) and a
# stepping LR schedule (scheduler state must matter too).
VOCAB, HIDDEN, LAYERS, HEADS, SEQ = 128, 64, 2, 4, 32
EPOCHS = 2
SEED, RESUME_SEED = 11, 4242


class Config:
    """One parity-proof configuration: mesh layout (or single-chip),
    ZeRO stage, and a batch geometry whose leading dim the tested
    meshes cannot dp-shard (sharded mode: batch 3 vs dp in {2,4,8} —
    replicated batch keeps the global math dp-invariant, the bitwise
    elastic-reshard precondition)."""

    def __init__(self, mesh=None, resume_mesh=None, zero_stage=1):
        self.mesh = mesh                          # {"dp": N} or None
        self.resume_mesh = resume_mesh or mesh
        self.zero_stage = int(zero_stage) if mesh else 0
        if mesh:
            self.batch, self.n_samples = 3, 9
        else:
            self.batch, self.n_samples = 2, 8
        self.steps_per_epoch = self.n_samples // self.batch
        self.total_steps = self.steps_per_epoch * EPOCHS

    @property
    def sharded(self):
        return self.mesh is not None

    @property
    def reshards(self):
        return self.sharded and dict(self.resume_mesh) != dict(self.mesh)

    def boundaries(self):
        """Kill boundaries: global step at which the TRAIN_STEP raise
        fires (the step never runs; the checkpoint on disk is from the
        previous step). `before_first_step` kills with NO checkpoint
        written yet — resume degrades to a fresh seeded run and must
        still match golden."""
        return {
            "before_first_step": 1,
            "after_save": 2,
            "mid_epoch": 3,
            "epoch_end": self.steps_per_epoch + 1,
        }

    def key(self):
        return (tuple(sorted((self.mesh or {}).items())), self.zero_stage)


# positive controls: break one verified property at checkpoint time;
# the parity check MUST exit 1 (tests/test_chaos.py asserts it).
# value = (boundary, TRAIN_STATE keys dropped or None, sharded-only)
INJECTIONS = {
    "rng-drop": ("mid_epoch", ("rng",), False),
    "cursor-drop": ("mid_epoch", ("cursor",), False),
    "spec-drop": ("mid_epoch", ("sharding",), True),
    "stale-shard": ("mid_epoch", None, True),      # arms SHARD_STATE
}

_CACHE = {}


def _dataset(cfg):
    key = ("data", cfg.batch, cfg.n_samples)
    if key not in _CACHE:
        rng = np.random.RandomState(3)
        _CACHE[key] = rng.randint(0, VOCAB,
                                  (cfg.n_samples, SEQ)).astype(np.int32)
    ids = _CACHE[key]
    return TensorDataset([ids, ids])


def make_model(seed, cfg):
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss
    pt.seed(seed)
    gcfg = GPTConfig(vocab_size=VOCAB, hidden_size=HIDDEN,
                     num_layers=LAYERS, num_heads=HEADS, max_seq_len=SEQ,
                     dropout=0.1, attn_dropout=0.0)
    model = hapi.Model(GPTForPretraining(gcfg))
    sched = pt.optimizer.lr.StepDecay(1e-3, step_size=3, gamma=0.5)
    opt = pt.optimizer.AdamW(learning_rate=sched,
                             parameters=model.parameters())
    if cfg.sharded and cfg.zero_stage:
        # the production route into ShardedTrainStep's ZeRO stage: the
        # fleet sharding strategy (meta_optimizers.ShardingOptimizer)
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet.base import DistributedStrategy
        strat = DistributedStrategy()
        strat.sharding = True
        # exact_reshard: the dp-invariant-math mode — the precondition
        # for BITWISE parity across a dp-count change (sharded.py)
        strat.sharding_configs = {"stage": cfg.zero_stage,
                                  "exact_reshard": True}
        opt = fleet.distributed_optimizer(opt, strat)
    model.prepare(opt, gpt_pretrain_loss)
    return model


def _install_mesh(shape):
    from paddle_tpu.distributed import mesh as mesh_mod
    if shape is None:
        mesh_mod.set_mesh(None)
    else:
        mesh_mod.make_mesh(dict(shape))


def _trajectory(rec):
    """Per-step (step, loss, grad_norm) from a run's journal events —
    compared with EXACT equality: bitwise resume or bust."""
    return [(e["step"], e["loss"], e["grad_norm"])
            for e in rec.events() if e.get("ev") == "step"]


def _fit(model, rec, cfg, ckpt_dir=None, resume=False):
    model.fit(_dataset(cfg), batch_size=cfg.batch, epochs=EPOCHS,
              shuffle=True, verbose=0, flight_recorder=rec,
              save_dir=ckpt_dir, save_steps=1 if ckpt_dir else None,
              resume=resume)


def golden_trajectory(cfg):
    """The uninterrupted seeded run on the ORIGINAL mesh (computed once
    per (mesh, zero_stage) per process)."""
    key = ("golden", cfg.key())
    if key not in _CACHE:
        _install_mesh(cfg.mesh)
        model = make_model(SEED, cfg)
        rec = flight_recorder.FlightRecorder(None)
        _fit(model, rec, cfg)
        _CACHE[key] = _trajectory(rec)
    return _CACHE[key]


def _check(violations, cond, msg):
    if not cond:
        violations.append(msg)


def _fmt(traj):
    return [(s, float(l), float(g)) for s, l, g in traj[:3]]


def _check_sharded_resume(v, cfg, model2, rec_resumed):
    """The elastic-reshard invariants on top of trajectory parity."""
    from paddle_tpu.distributed.sharded import ShardedTrainStep
    step_obj = model2._train_step
    _check(v, isinstance(step_obj, ShardedTrainStep),
           f"resumed under an active mesh but the rebuilt step is "
           f"{type(step_obj).__name__}, not ShardedTrainStep — the "
           "resume silently downgraded to single-device")
    if not isinstance(step_obj, ShardedTrainStep):
        return
    _check(v, step_obj.zero_stage == cfg.zero_stage,
           f"resumed step zero_stage {step_obj.zero_stage} != "
           f"{cfg.zero_stage}")
    if cfg.zero_stage >= 1:
        # the restored opt-state leaves must ACTUALLY be dp-sharded on
        # the new mesh — accidentally-replicated state would quietly
        # undo ZeRO's memory win while every trajectory check passes
        dp = cfg.resume_mesh["dp"]
        sharded_leaves = 0
        for n, slots in step_obj.opt_state.items():
            for sn, arr in slots.items():
                spec = step_obj.opt_specs[n][sn]
                if "dp" not in str(spec):
                    continue
                sharded_leaves += 1
                shard = arr.sharding.shard_shape(arr.shape)
                if int(np.prod(shard)) * dp != int(np.prod(arr.shape)):
                    _check(v, False,
                           f"opt-state leaf {n}.{sn} declared {spec} but "
                           f"shard shape {shard} is not 1/{dp} of "
                           f"{arr.shape} — restored state is not "
                           "actually dp-sharded")
                    break
        _check(v, sharded_leaves > 0,
               "no opt-state leaf carries a dp sharding after resume — "
               "restored state came back fully replicated")
    reshard_evs = [e for e in rec_resumed.events()
                   if e.get("ev") == "reshard"]
    if cfg.reshards:
        _check(v, len(reshard_evs) == 1,
               f"mesh changed {cfg.mesh}->{cfg.resume_mesh} but the "
               f"resumed journal has {len(reshard_evs)} reshard events, "
               "expected exactly 1 (did the checkpoint lose its "
               "sharding record?)")
        if reshard_evs:
            ev = reshard_evs[0]
            _check(v, ev.get("from_dp") == cfg.mesh.get("dp")
                   and ev.get("to_dp") == cfg.resume_mesh.get("dp"),
                   f"reshard event names dp {ev.get('from_dp')}->"
                   f"{ev.get('to_dp')}, the run went "
                   f"{cfg.mesh.get('dp')}->{cfg.resume_mesh.get('dp')}")
            _check(v, ev.get("zero_stage") == cfg.zero_stage,
                   f"reshard event zero_stage {ev.get('zero_stage')} != "
                   f"checkpoint's {cfg.zero_stage}")
    else:
        _check(v, not reshard_evs,
               "mesh unchanged across resume but a reshard event was "
               "journaled")


def scenario_kill_resume(name, kill_step, cfg, inject=None, journal=None):
    """Kill at `kill_step` on cfg.mesh, resume on cfg.resume_mesh,
    prove bitwise parity. Returns the list of violated invariants
    (empty = pass)."""
    v = []
    golden = golden_trajectory(cfg)
    faults = [chaos.Fault(chaos.TRAIN_STEP, times=(kill_step,))]
    inj_point = None
    if inject is not None:
        _, drop, _ = INJECTIONS[inject]
        if drop is not None:
            inj_point = chaos.TRAIN_STATE
            faults.append(chaos.Fault(chaos.TRAIN_STATE, action="payload",
                                      payload=list(drop)))
        else:                                      # stale-shard
            inj_point = chaos.SHARD_STATE
            faults.append(chaos.Fault(chaos.SHARD_STATE, action="payload",
                                      payload=True))
    with tempfile.TemporaryDirectory(prefix="chaos_train_") as ckpt_dir:
        # ---- the killed run (original mesh) ---------------------------
        _install_mesh(cfg.mesh)
        model = make_model(SEED, cfg)
        rec_killed = flight_recorder.FlightRecorder(journal)
        monkey = chaos.ChaosMonkey(faults)
        killed = False
        try:
            with chaos.active(monkey):
                _fit(model, rec_killed, cfg, ckpt_dir=ckpt_dir)
        except chaos.ChaosError:
            killed = True
        _check(v, killed, f"kill injection never fired at step {kill_step}")
        if inject is not None:
            _check(v, any(p == inj_point for p, _, _ in monkey.fired),
                   f"--inject {inject}: the fault at {inj_point} never "
                   "fired")
        crashed = _trajectory(rec_killed)
        killed_run_id = rec_killed.run_id
        _check(v, crashed == golden[:kill_step - 1],
               f"pre-kill trajectory diverged from golden: "
               f"{_fmt(crashed)} vs {_fmt(golden[:kill_step - 1])}")

        # ---- the resumed run (resume mesh — may differ: reshard) ------
        # DIFFERENT construction seed: if parity still holds, it holds
        # because the checkpoint restored everything, not by luck
        _install_mesh(cfg.resume_mesh)
        model2 = make_model(RESUME_SEED, cfg)
        prefix = model2.load_latest(ckpt_dir)
        if prefix is None:
            # killed before the first checkpoint: resume degrades to a
            # fresh seeded run — re-seed and run uninterrupted. A fresh
            # run has no layout to inherit, so it must start on the
            # ORIGINAL mesh to reproduce golden.
            _check(v, kill_step == 1,
                   f"no checkpoint found after {kill_step - 1} steps")
            _install_mesh(cfg.mesh)
            model2 = make_model(SEED, cfg)
        rec_resumed = flight_recorder.FlightRecorder(journal)
        _fit(model2, rec_resumed, cfg, resume=prefix is not None)
        resumed = _trajectory(rec_resumed)

        # ---- parity ---------------------------------------------------
        full = crashed + resumed
        _check(v, len(full) == len(golden),
               f"stitched trajectory has {len(full)} steps, golden "
               f"{len(golden)} — resume re-ran or skipped work")
        for i, (a, b) in enumerate(zip(full, golden)):
            if a != b:
                _check(v, False,
                       f"trajectory diverged at position {i}: "
                       f"step/loss/grad_norm {a} != golden {b}")
                break

        # ---- compile-once in the resumed process ----------------------
        step_obj = model2._train_step
        cache_size = step_obj._compiled._cache_size() \
            if step_obj is not None else None
        _check(v, cache_size == 1,
               f"resumed train step compiled {cache_size} executables, "
               "expected exactly 1 (resume changed traced shapes/dtypes?)")
        compiles = sum(int(e.get("count", 1)) for e in rec_resumed.events()
                      if e.get("ev") == "compile")
        _check(v, compiles == 1,
               f"resumed journal shows {compiles} compile events, "
               "expected 1")

        # ---- sharded/reshard invariants -------------------------------
        if cfg.sharded and prefix is not None:
            _check_sharded_resume(v, cfg, model2, rec_resumed)

        # ---- resume bookkeeping --------------------------------------
        if prefix is not None:
            res_evs = [e for e in rec_resumed.events()
                       if e.get("ev") == "resume"]
            _check(v, len(res_evs) == 1,
                   "resumed run journaled no `resume` event")
            if res_evs:
                _check(v, res_evs[0].get("prior_run_id") == killed_run_id,
                       f"resume event names prior run "
                       f"{res_evs[0].get('prior_run_id')!r}, the killed "
                       f"run was {killed_run_id!r}")
                _check(v, res_evs[0].get("step") == kill_step - 1,
                       f"resume event step {res_evs[0].get('step')}, "
                       f"expected {kill_step - 1}")
        rec_killed.close()
        rec_resumed.close()
    return v


def _parse_mesh(text):
    """'dp=2' / 'dp=2,mp=2' -> {'dp': 2, 'mp': 2}."""
    if not text:
        return None
    out = {}
    for part in text.split(","):
        if "=" not in part:
            raise ValueError(f"mesh spec {text!r}: expected axis=N parts")
        k, _, n = part.partition("=")
        out[k.strip()] = int(n)
    return out


def run(argv=None):
    ap = argparse.ArgumentParser(
        prog="chaos_train",
        description="kill/resume bitwise-parity proof for elastic "
                    "training (single-chip and sharded/ZeRO with "
                    "elastic reshard)")
    ap.add_argument("--boundaries", default=None,
                    help="comma-separated subset of "
                         "before_first_step,after_save,mid_epoch,"
                         "epoch_end")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 entry point: every kill boundary at the "
                         "canonical tiny scale (identical to the default "
                         "run; the flag names the contract)")
    ap.add_argument("--mesh", default=None,
                    help="run the SHARDED step on this mesh (e.g. dp=2); "
                         "default: single-chip (pins the mesh to None so "
                         "a leaked global mesh can't flip the step type)")
    ap.add_argument("--resume-mesh", default=None,
                    help="resume onto this mesh (e.g. dp=4) — elastic "
                         "reshard; default: same as --mesh")
    ap.add_argument("--zero-stage", type=int, default=1,
                    help="ZeRO stage for --mesh runs (default 1)")
    ap.add_argument("--inject", default=None, choices=sorted(INJECTIONS),
                    help="positive control: break one verified property "
                         "at checkpoint time and prove this checker "
                         "exits 1")
    ap.add_argument("--json", action="store_true", dest="as_json")
    ap.add_argument("--journal", default=None,
                    help="append the runs' flight-recorder journals to "
                         "this JSONL path")
    args = ap.parse_args(argv)

    mesh = _parse_mesh(args.mesh)
    resume_mesh = _parse_mesh(args.resume_mesh)
    if resume_mesh and not mesh:
        print("chaos_train: --resume-mesh requires --mesh",
              file=sys.stderr)
        return 2
    if args.inject is not None and INJECTIONS[args.inject][2] and not mesh:
        # sharded-only control without an explicit mesh: the canonical
        # reshard pair
        mesh, resume_mesh = {"dp": 2}, {"dp": 4}
    # mesh validations AFTER the inject auto-mesh, so e.g.
    # `--inject stale-shard --zero-stage 0` cannot slip past them into
    # a strategy-less run that exits 1 for the wrong reason
    if mesh and args.zero_stage < 1:
        # the fleet sharding strategy is the route into the sharded
        # step's ZeRO stage AND its exact_reshard mode; stage 0 has no
        # strategy to ride
        print("chaos_train: --mesh runs need --zero-stage >= 1",
              file=sys.stderr)
        return 2
    if mesh and ("dp" not in mesh or "dp" not in (resume_mesh or mesh)):
        # the sharded invariants (batch indivisibility, _zero_spec
        # placements, reshard event dp sizes) are all keyed on the
        # canonical 'dp' axis
        print("chaos_train: --mesh/--resume-mesh need a 'dp' axis",
              file=sys.stderr)
        return 2
    cfg = Config(mesh=mesh, resume_mesh=resume_mesh,
                 zero_stage=args.zero_stage)
    if args.inject == "spec-drop" and not cfg.reshards:
        # the control's teeth are the MISSING reshard event — without a
        # mesh change there is no event to miss and the run would
        # vacuously pass its must-exit-1 contract
        print("chaos_train: --inject spec-drop needs a resharding "
              "--mesh/--resume-mesh pair", file=sys.stderr)
        return 2
    boundaries = cfg.boundaries()

    if args.inject is not None:
        names = [INJECTIONS[args.inject][0]]
    elif args.boundaries:
        names = [s.strip() for s in args.boundaries.split(",") if s.strip()]
        unknown = set(names) - set(boundaries)
        if unknown:
            print(f"chaos_train: unknown boundary(s) {sorted(unknown)}",
                  file=sys.stderr)
            return 2
    else:
        names = list(boundaries)

    # mesh discipline: tier-1 drives this in-process, where an earlier
    # test file may have left a global device mesh set. Single-chip
    # runs pin the mesh to None (build_train_step would otherwise
    # silently swap ShardedTrainStep in); sharded runs install exactly
    # the requested meshes. Either way the caller's mesh is restored.
    from paddle_tpu.distributed import mesh as mesh_mod
    prev_mesh = mesh_mod.get_mesh()
    results = {}
    try:
        for name in names:
            try:
                violations = scenario_kill_resume(
                    name, boundaries[name], cfg, inject=args.inject,
                    journal=args.journal)
            except Exception as e:   # noqa: BLE001 — a fault ESCAPED
                violations = [f"fault escaped the resume layer: "
                              f"{type(e).__name__}: {e}"]
            results[name] = violations
            if not args.as_json:
                mark = "ok" if not violations else "FAIL"
                print(f"== kill at {name} (step {boundaries[name]}"
                      + (f", {cfg.mesh}->{cfg.resume_mesh} zero"
                         f"{cfg.zero_stage}" if cfg.sharded else "")
                      + f"): {mark} ==")
                for msg in violations:
                    print(f"   violated: {msg}")
    finally:
        mesh_mod.set_mesh(prev_mesh)

    failed = {k: v for k, v in results.items() if v}
    if args.as_json:
        print(json.dumps({
            "version": 2,
            "status": "ok" if not failed else "violations",
            "inject": args.inject,
            "mesh": cfg.mesh, "resume_mesh": cfg.resume_mesh,
            "zero_stage": cfg.zero_stage,
            "total_steps": cfg.total_steps,
            "boundaries": results,
        }, indent=2))
    else:
        print(f"chaos_train: {len(results) - len(failed)}/{len(results)} "
              f"boundaries bitwise-identical"
              + (f" (inject={args.inject}: expected to FAIL)"
                 if args.inject else ""), file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(run())
