"""On-chip OpTest sweep: run the registry battery (eager finite-ness,
cross-place numeric parity vs the host CPU backend, desc round-trip
replay) on the REAL TPU backend — the analog of the reference running
OpTest on every registered place (ref
python/paddle/fluid/tests/unittests/op_test.py:1033
check_output_with_place — CPU *and* device place, not just CPU).
Finite differences are CPU-suite-only: on the accelerator f32
effectively carries bf16 precision, so FD perturbations vanish
(observed fd=0 across elementwise AND matmul ops).

The specs are the single source of truth in
tests/test_op_registry_sweep.py (SPECS); this script re-executes them
without the conftest CPU-forcing so jax picks the TPU backend.

Resumable: every op's verdict is appended to
docs/perf/op_sweep_tpu.jsonl as it lands, and a rerun skips ops that
already have a numeric verdict (pass/fail) while retrying infra
verdicts (error/timeout), so an interrupted sweep converges. The
summary line carries "bankable": true only when every op has a numeric
verdict.

Usage: python scripts/op_sweep_tpu.py [--allow-cpu] [--only op ...]
"""
import argparse
import json
import os
import signal
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

RESULTS = os.path.join(REPO, "docs", "perf", "op_sweep_tpu.jsonl")
SUMMARY = os.path.join(REPO, "docs", "perf", "op_sweep_tpu.json")
MAX_ATTEMPTS = 2       # error/timeout verdicts become final after this
# bump when the check battery changes: pass/fail rows from an older
# battery are re-run, not resume-skipped (v2 = cross-place parity)
BATTERY_VERSION = 2


class OpTimeout(Exception):
    pass


def _alarm(signum, frame):
    raise OpTimeout()


def load_done(backend):
    """Latest record and attempt count per op FOR THIS BACKEND — an
    interleaved --allow-cpu smoke run must not erase banked TPU
    verdicts (records are keyed by (op, backend), last line wins)."""
    done, attempts = {}, {}
    if os.path.exists(RESULTS):
        with open(RESULTS) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("backend") != backend:
                    continue
                done[rec["op"]] = rec
                attempts[rec["op"]] = attempts.get(rec["op"], 0) + 1
    return done, attempts


def run_op(tsw, name, replay_tol):
    """One op through the SHARED battery
    (tests/test_op_registry_sweep.py — one implementation for the CPU
    suite and the on-chip sweep); returns a verdict record. The
    desc-replay bound is looser than the CPU suite's (different
    compilations may reassociate reductions)."""
    rec = {"op": name}
    try:
        # (a) finite outputs + (c) desc replay on the accelerator; FD is
        # skipped (probes=0): the MXU's bf16 tile precision swallows FD
        # perturbations (observed fd=0 on every matmul/conv-backed op)
        tsw.run_spec_checks(name, probes=0, replay_tol=replay_tol)
        # (b) cross-place parity vs the host CPU backend — the on-chip
        # numeric check proper (ref op_test.py:1033 per-place outputs)
        tsw.run_cross_place_checks(name)
    except tsw.OpCheckFailure as f:
        rec.update(verdict="fail", check=f.check, detail=f.detail)
        return rec
    rec["verdict"] = "pass"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--allow-cpu", action="store_true",
                    help="run even on the CPU backend (script smoke test)")
    ap.add_argument("--per-op-timeout", type=int, default=180)
    ap.add_argument("--only", nargs="*", help="run just these ops")
    ap.add_argument("--worker", action="store_true",
                    help="internal: run the sweep loop in THIS process")
    args = ap.parse_args()

    if not args.worker:
        # Orchestrate workers: an op the backend can't compile POISONS the
        # process (observed: the first UNIMPLEMENTED — complex dtypes —
        # makes every later compile in that process fail the same way). The worker banks the triggering op as
        # "unsupported" and exits 3; respawning continues the sweep after
        # it, so one bad op costs one backend re-init, not the battery.
        import subprocess
        fwd = [sys.executable, os.path.abspath(__file__), "--worker",
               "--per-op-timeout", str(args.per_op_timeout)]
        if args.allow_cpu:
            fwd.append("--allow-cpu")
        if args.only:
            fwd += ["--only"] + args.only
        while True:
            before = (os.path.getsize(RESULTS)
                      if os.path.exists(RESULTS) else 0)
            rc = subprocess.call(fwd)
            if rc != 3:
                return rc
            after = (os.path.getsize(RESULTS)
                     if os.path.exists(RESULTS) else 0)
            if after <= before:
                print(json.dumps(
                    {"error": "poisoned worker made no progress"}))
                return 1

    import jax
    backend = jax.default_backend()
    if backend == "cpu" and not args.allow_cpu:
        print(json.dumps({"error": "cpu backend; needs a TPU"}))
        return 1
    # request full f32 contractions; NOTE the TPU backend has been
    # observed to carry bf16 precision regardless (fd=0 on elementwise
    # ops too), which is why the battery compares places instead of FD
    jax.config.update("jax_default_matmul_precision", "highest")

    import test_op_registry_sweep as tsw  # noqa: E402 (needs sys.path)

    names = sorted(tsw.SPECS)
    if args.only:
        names = [n for n in names if n in set(args.only)]
    done, attempts = load_done(backend)

    def settled(n):
        """A verdict we stop retrying: numeric outcomes and place-level
        unsupported immediately; error/timeout after MAX_ATTEMPTS (a
        DETERMINISTIC failure must not wedge the watchdog battery in a
        forever-retry loop — after that it banks as a final verdict and
        counts toward bankable)."""
        rec = done.get(n, {})
        v = rec.get("verdict")
        if v in ("pass", "fail"):
            return rec.get("battery") == BATTERY_VERSION
        return v == "unsupported" or (
            v in ("error", "timeout") and attempts.get(n, 0) >= MAX_ATTEMPTS)

    todo = [n for n in names if not settled(n)]
    print(f"[op_sweep_tpu] backend={backend} total={len(names)} "
          f"resume-skip={len(names) - len(todo)} todo={len(todo)}",
          flush=True)

    signal.signal(signal.SIGALRM, _alarm)
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "a") as outf:
        for k, name in enumerate(todo):
            t0 = time.time()
            signal.alarm(args.per_op_timeout)
            try:
                rec = run_op(tsw, name, replay_tol=5e-4)
            except OpTimeout:
                rec = {"op": name, "verdict": "timeout"}
            except Exception as e:  # noqa: BLE001 — bank the verdict
                if "UNIMPLEMENTED" in str(e):
                    # the backend can't compile this op's program — a
                    # final place-level verdict (ref OpTest skips ops on
                    # places that don't support them), and this process
                    # is now poisoned: exit for the parent to respawn
                    rec = {"op": name, "verdict": "unsupported",
                           "detail": f"{type(e).__name__}: {e}"[:300],
                           "secs": round(time.time() - t0, 2),
                           "backend": backend}
                    signal.alarm(0)
                    outf.write(json.dumps(rec) + "\n")
                    outf.flush()
                    print(f"[{k + 1}/{len(todo)}] {name}: unsupported "
                          f"(poisons the process; respawning)", flush=True)
                    sys.exit(3)
                rec = {"op": name, "verdict": "error",
                       "detail": f"{type(e).__name__}: {e}"[:300]}
            finally:
                signal.alarm(0)
            rec["secs"] = round(time.time() - t0, 2)
            rec["backend"] = backend
            rec["battery"] = BATTERY_VERSION
            outf.write(json.dumps(rec) + "\n")
            outf.flush()
            done[name] = rec
            attempts[name] = attempts.get(name, 0) + 1
            if rec["verdict"] != "pass" or k % 25 == 0:
                print(f"[{k + 1}/{len(todo)}] {name}: {rec['verdict']} "
                      f"({rec['secs']}s) {rec.get('detail', '')}",
                      flush=True)

    counts = {}
    for n in names:
        v = done.get(n, {}).get("verdict", "missing")
        v = "infra" if v == "error" else v  # '"error"' is the watchdog's
        counts[v] = counts.get(v, 0) + 1    # step-failure grep token
    bankable = all(settled(n) for n in names)
    summary = {"backend": backend, "ops": len(names), "counts": counts,
               "bankable": bankable,
               "fails": sorted(n for n in names
                               if done.get(n, {}).get("verdict") == "fail")}
    with open(SUMMARY, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"summary": summary}), flush=True)
    return 0 if bankable else 1


if __name__ == "__main__":
    sys.exit(main())
