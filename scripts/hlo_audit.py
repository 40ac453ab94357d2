#!/usr/bin/env python
"""hlo_audit CLI — compile-level audit of the repo's tracked XLA
programs (the xprof observatory, paddle_tpu/tools/xprof/).

    python scripts/hlo_audit.py --diff               # gate vs baseline
    python scripts/hlo_audit.py --json               # print the snapshot
    python scripts/hlo_audit.py --update-baseline    # re-baseline
    python scripts/hlo_audit.py --diff --programs train_step
    python scripts/hlo_audit.py --diff --inject serving_decode_wave

Exit codes: 0 clean (every tracked metric within tolerance of
scripts/hlo_baseline.json — notes alone don't gate), 1 regressions
(bytes-accessed / fusion count / peak memory / flops beyond tolerance,
or a tracked program vanished), 2 internal error / bad usage.

`--inject NAME` deliberately de-optimizes one tracked program (an extra
un-fusable full pass over its float inputs) — the gate's positive
control, used by tests/test_hlo_audit.py to prove a de-optimized decode
wave exits 1. Never use it when banking a baseline.

Snapshots are deterministic: two consecutive runs on one backend
produce identical JSON (program structure only — no timestamps, no
values of the randomly initialized weights).
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "scripts", "hlo_baseline.json")


def build_parser():
    p = argparse.ArgumentParser(
        prog="hlo_audit",
        description="HLO fusion/memory audit of tracked XLA programs")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file (default scripts/hlo_baseline"
                        ".json)")
    p.add_argument("--diff", action="store_true",
                   help="compare against the baseline; exit 1 on "
                        "regressions beyond tolerance")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="print the full snapshot as JSON on stdout")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline from this snapshot "
                        "(keeps hand-edited per-program tolerances)")
    p.add_argument("--programs", default=None,
                   help="comma-separated subset of tracked programs "
                        "(default: all)")
    p.add_argument("--inject", default=None, metavar="PROGRAM",
                   help="TEST ONLY: de-optimize this tracked program "
                        "before snapshotting (proves the gate fires)")
    p.add_argument("--no-publish", action="store_true",
                   help="skip exporting xla_program_* telemetry gauges")
    return p


def run(argv):
    args = build_parser().parse_args(argv)
    if not (args.diff or args.as_json or args.update_baseline):
        print("nothing to do: pass --diff, --json and/or "
              "--update-baseline", file=sys.stderr)
        return 2
    if args.inject and args.update_baseline:
        print("refusing --update-baseline with --inject: a degraded "
              "program must never become the baseline", file=sys.stderr)
        return 2

    import jax
    from paddle_tpu.utils import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    from paddle_tpu.tools import xprof

    names = None
    if args.programs:
        names = [s.strip() for s in args.programs.split(",") if s.strip()]
    specs = xprof.tracked_program_specs(names)
    inject = [args.inject] if args.inject else []
    snapshot = xprof.snapshot_programs(specs, inject=inject)
    if not args.no_publish:
        xprof.publish(snapshot)

    if args.as_json:
        print(json.dumps(snapshot, indent=1, sort_keys=True))

    rc = 0
    if args.update_baseline:
        previous = None
        if os.path.exists(args.baseline):
            previous = xprof.audit.load_baseline(args.baseline)
        try:
            baseline = xprof.audit.make_baseline(
                snapshot, previous=previous, keep_missing=bool(names))
        except ValueError as e:       # cross-backend subset merge
            print(f"hlo_audit: {e}", file=sys.stderr)
            return 2
        xprof.audit.save_baseline(baseline, args.baseline)
        print(f"hlo_audit: wrote {args.baseline} "
              f"({len(baseline['programs'])} programs, backend="
              f"{baseline['backend']})", file=sys.stderr)

    if args.diff:
        if not os.path.exists(args.baseline):
            print(f"hlo_audit: no baseline at {args.baseline} "
                  "(run --update-baseline first)", file=sys.stderr)
            return 2
        baseline = xprof.audit.load_baseline(args.baseline)
        if names:
            # subset audit: only gate the selected programs — the
            # unselected ones were never snapshotted, which must not
            # read as "tracked program missing"
            baseline = dict(baseline, programs={
                k: v for k, v in baseline.get("programs", {}).items()
                if k in set(names)})
        findings, notes = xprof.diff(snapshot, baseline)
        text = xprof.audit.render_findings(findings, notes)
        if text:
            # with --json, stdout is reserved for the one JSON document
            print(text, file=sys.stderr if args.as_json else sys.stdout)
        if findings:
            print(f"hlo_audit: {len(findings)} regression(s) vs "
                  f"{os.path.relpath(args.baseline, REPO)}",
                  file=sys.stderr)
            rc = 1
        else:
            print("hlo_audit: clean "
                  f"({len(snapshot['programs'])} programs within "
                  "tolerance)", file=sys.stderr)
    return rc


def main():
    try:
        sys.exit(run(sys.argv[1:]))
    except SystemExit:
        raise
    except Exception:
        import traceback
        traceback.print_exc()
        sys.exit(2)


if __name__ == "__main__":
    main()
