#!/usr/bin/env python
"""jxaudit CLI — program-level (jaxpr / compiled-HLO) semantic audit of
the repo's tracked XLA programs (paddle_tpu/tools/jxaudit/).

    python scripts/jxaudit.py                         # audit + gate
    python scripts/jxaudit.py --json                  # machine-readable
    python scripts/jxaudit.py --select donation-dropped,host-callback
    python scripts/jxaudit.py --programs serving_decode_wave
    python scripts/jxaudit.py --inject dtype-leak     # positive control
    python scripts/jxaudit.py --baseline-update       # regrandfather
    python scripts/jxaudit.py --list-rules

Exit codes (ptlint's contract): 0 clean — no findings beyond the
baseline and every baseline entry justified; 1 findings; 2 internal
error / bad usage. Analyses that this jax build cannot answer degrade
to a reason note (reported, non-gating), mirroring hlo_audit.

`--inject CLASS` audits a deliberately-defective COPY of the serving
decode wave carrying that one defect class (dropped donation / f32
upcast / baked constant / host callback), with the baseline disabled
and the audit narrowed to the matching rule — it must exit 1; tier-1
proves it does. Refused with --baseline-update.

The baseline (scripts/jxaudit_baseline.json) grandfathers findings by
(rule, program, message) identity with counts and REQUIRED per-entry
justifications — ptlint's exact machinery; the program name rides in
the entry's "path" slot. Rule catalog: docs/static_analysis.md
("Program-level rules").
"""
import argparse
import json
import os
import sys
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "scripts", "jxaudit_baseline.json")
INJECT_TARGET = "serving_decode_wave"


def build_parser():
    p = argparse.ArgumentParser(
        prog="jxaudit",
        description="program-level semantic audit (donation, dtype "
                    "leaks, baked constants, host callbacks)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable report on stdout")
    p.add_argument("--select", default=None,
                   help="comma-separated rule ids to run (default: all)")
    p.add_argument("--programs", default=None,
                   help="comma-separated subset of audited programs "
                        "(default: all)")
    p.add_argument("--inject", default=None, metavar="CLASS",
                   help="TEST ONLY: audit a copy of the decode wave "
                        "carrying this defect class (must exit 1)")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file (default scripts/jxaudit_baseline"
                        ".json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline (report every finding)")
    p.add_argument("--baseline-update", action="store_true",
                   help="rewrite the baseline from this run's findings")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--list-programs", action="store_true",
                   help="print the audited program names and exit")
    return p


def run(argv):
    args = build_parser().parse_args(argv)

    from paddle_tpu.tools import jxaudit
    from paddle_tpu.tools.lint import baseline as lintbase

    if args.list_rules:
        for rule_id in sorted(jxaudit.RULES):
            print(f"{rule_id}: {jxaudit.RULES[rule_id].rationale}")
        return 0

    select = None
    if args.select:
        select = {s.strip() for s in args.select.split(",") if s.strip()}

    import jax
    from paddle_tpu.utils import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    if args.list_programs:
        for name in jxaudit.tracked_program_names():
            print(name)
        return 0

    no_baseline = args.no_baseline
    if args.inject:
        if args.baseline_update:
            print("jxaudit: refusing --baseline-update with --inject: a "
                  "deliberately-defective program must never be "
                  "grandfathered", file=sys.stderr)
            return 2
        if args.inject not in jxaudit.INJECTIONS:
            print(f"jxaudit: unknown injection {args.inject!r}; have "
                  f"{sorted(jxaudit.INJECTIONS)}", file=sys.stderr)
            return 2
        if select is not None and args.inject not in select:
            print(f"jxaudit: --select {args.select} excludes the "
                  f"injected class {args.inject!r} — the positive "
                  "control would vacuously pass", file=sys.stderr)
            return 2
        spec, = jxaudit.tracked_specs([INJECT_TARGET])
        specs = [jxaudit.inject_spec(spec, args.inject)]
        if select is None:
            # attribute the exit-1 to the injected class (and skip the
            # compile the donation rule would otherwise force on the
            # jaxpr-only injections)
            select = {args.inject}
        no_baseline = True
    else:
        names = None
        if args.programs:
            names = [s.strip() for s in args.programs.split(",")
                     if s.strip()]
        try:
            specs = jxaudit.tracked_specs(names)
        except ValueError as e:
            print(f"jxaudit: {e}", file=sys.stderr)
            return 2

    try:
        findings, report = jxaudit.audit_programs(specs, select=select)
    except ValueError as e:              # unknown rule in --select
        print(f"jxaudit: {e}", file=sys.stderr)
        return 2

    entries = [] if no_baseline else lintbase.load(args.baseline)
    if args.baseline_update:
        audited_names = {s["name"] for s in specs}

        def in_scope(e):
            if select is not None and e["rule"] not in select:
                return False
            return e["path"] in audited_names

        kept = [e for e in entries if not in_scope(e)]
        entries = lintbase.update(findings, entries, args.baseline,
                                  keep=kept)
        todo = lintbase.undocumented(entries)
        print(f"jxaudit: baseline rewritten with {len(entries)} "
              f"entr{'y' if len(entries) == 1 else 'ies'} covering "
              f"{len(findings)} finding(s) -> {args.baseline}")
        if todo:
            print(f"jxaudit: {len(todo)} entr"
                  f"{'y needs' if len(todo) == 1 else 'ies need'} a "
                  "justification (edit the TODO markers before "
                  "committing)", file=sys.stderr)
        return 0

    new, suppressed, undocumented, clean = lintbase.gate(findings,
                                                         entries)
    # journal the POST-baseline verdict — what the gate decided, not
    # the raw count a justified grandfathered entry would inflate
    jxaudit.publish_summary(new, report, suppressed=suppressed)
    degraded = {name: row["unavailable"]
                for name, row in report["programs"].items()
                if row.get("unavailable")}

    if args.as_json:
        print(json.dumps({
            "version": 1,
            "status": "clean" if clean else "findings",
            "counts": {
                "findings": len(new),
                "baseline_suppressed": suppressed,
                "baseline_undocumented": len(undocumented),
            },
            "findings": [f.to_dict() for f in new],
            "undocumented_baseline": undocumented,
            "report": report,
        }, indent=2))
    else:
        for f in new:
            print(f.render())
        for e in undocumented:
            print(f"{e['path']}: [baseline] entry for {e['rule']} lacks "
                  "a justification (edit "
                  f"{os.path.relpath(args.baseline, REPO)})")
        for name, reasons in sorted(degraded.items()):
            for what, why in sorted(reasons.items()):
                print(f"note: {name}.{what} unavailable on this jax "
                      f"build: {why}", file=sys.stderr)
        if not clean:
            n = len(new) + len(undocumented)
            print(f"jxaudit: {n} finding(s) ({suppressed} baselined); "
                  "see docs/static_analysis.md for the baseline "
                  "workflow", file=sys.stderr)
        else:
            print(f"jxaudit: clean ({len(report['programs'])} programs, "
                  f"{suppressed} baselined finding(s))", file=sys.stderr)
    return 0 if clean else 1


def main(argv=None):
    try:
        return run(sys.argv[1:] if argv is None else argv)
    except SystemExit as e:              # argparse --help / usage errors
        return e.code if isinstance(e.code, int) else 2
    except Exception:
        traceback.print_exc()
        print("jxaudit: internal error", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
