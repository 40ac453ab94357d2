#!/usr/bin/env python
"""shaudit CLI — mesh-aware sharding & collective semantic audit of the
repo's pjit'd tracked programs (paddle_tpu/tools/jxaudit/mesh_rules.py).

    python scripts/shaudit.py                          # audit + gate
    python scripts/shaudit.py --json                   # machine-readable
    python scripts/shaudit.py --select sharding-dropped
    python scripts/shaudit.py --programs sharded_train_step
    python scripts/shaudit.py --inject reshard-in-body # positive control
    python scripts/shaudit.py --baseline-update        # regrandfather
    python scripts/shaudit.py --list-rules

Exit codes (ptlint's contract): 0 clean; 1 findings; 2 internal error /
bad usage. Rules degrade to a reason note (reported, non-gating) on
builds whose compiled text carries no sharding annotations or whose
lower() fails — never misattribution.

The audited surface is the registry's sharded programs
(`sharded_train_step` z1/z3, `sharded_decode_wave`); each spec carries
its declaration of record (`spec["sharding"]`, threaded from the live
step so declarations can't drift from code). The collective-budget rule
gates against the per-opcode {count, bytes} rows banked in
scripts/hlo_baseline.json — attached here, and only when the banked
backend matches this process's (cross-backend collective counts are not
comparable; the rule degrades with the reason instead).

`--inject CLASS` audits a purpose-built mis-sharded probe program
carrying that one defect class (tools/jxaudit/mesh_inject.py), baseline
disabled, audit narrowed to the matching rule — it must exit 1 under
the tier-1 8-device env; tier-1 proves it does. Refused with
--baseline-update, and refused (exit 2, never a vacuous exit 0) on a
single-device process where no probe axis can exceed size 1.

The baseline (scripts/shaudit_baseline.json) grandfathers findings by
(rule, program, message) identity with counts and REQUIRED per-entry
justifications — ptlint's exact machinery. Rule catalog:
docs/static_analysis.md ("Mesh-aware rules").
"""
import argparse
import json
import os
import sys
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

DEFAULT_BASELINE = os.path.join(REPO, "scripts", "shaudit_baseline.json")
HLO_BASELINE = os.path.join(REPO, "scripts", "hlo_baseline.json")


def build_parser():
    p = argparse.ArgumentParser(
        prog="shaudit",
        description="mesh-aware sharding & collective semantic audit "
                    "(dropped shardings, accidental replication, "
                    "donation through pjit, collective budgets, "
                    "implicit reshards)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable report on stdout")
    p.add_argument("--select", default=None,
                   help="comma-separated rule ids to run (default: all)")
    p.add_argument("--programs", default=None,
                   help="comma-separated subset of audited programs "
                        "(default: all sharded tracked programs)")
    p.add_argument("--inject", default=None, metavar="CLASS",
                   help="TEST ONLY: audit a purpose-built mis-sharded "
                        "probe carrying this defect class (must exit 1)")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file (default scripts/shaudit_baseline"
                        ".json)")
    p.add_argument("--hlo-baseline", default=HLO_BASELINE,
                   help="banked collective rows (default scripts/"
                        "hlo_baseline.json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore the baseline (report every finding)")
    p.add_argument("--baseline-update", action="store_true",
                   help="rewrite the baseline from this run's findings")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    p.add_argument("--list-programs", action="store_true",
                   help="print the audited program names and exit")
    return p


def attach_collective_budgets(specs, path):
    """Attach each spec's banked collective rows (hlo_baseline.json)
    under spec["sharding"]["collective_baseline"], merging global and
    per-program tolerance overrides. A missing file, a backend
    mismatch, or a program without banked rows leaves a reason behind
    instead — the collective-budget rule degrades with it."""
    import jax
    try:
        with open(path) as f:
            base = json.load(f)
    except Exception as e:
        reason = (f"banked collective rows unreadable ({path}): "
                  f"{type(e).__name__}")
        for spec in specs:
            spec.setdefault("sharding", {})[
                "collective_baseline_reason"] = reason
        return
    backend = jax.default_backend()
    if base.get("backend") != backend:
        reason = (f"collective rows banked on backend "
                  f"{base.get('backend')!r}, this process is "
                  f"{backend!r} — not comparable; re-bank via "
                  "scripts/hlo_audit.py --update-baseline")
        for spec in specs:
            spec.setdefault("sharding", {})[
                "collective_baseline_reason"] = reason
        return
    tols = base.get("tolerances") or {}
    for spec in specs:
        row = (base.get("programs") or {}).get(spec["name"]) or {}
        meta = spec.setdefault("sharding", {})
        if "collectives" not in row:
            meta["collective_baseline_reason"] = (
                "no banked collective rows for this program — bank "
                "them via scripts/hlo_audit.py --update-baseline")
            continue
        merged = {k: dict(tols.get(k) or {})
                  for k in ("collective_count", "collective_bytes")}
        for k, v in (row.get("tolerances") or {}).items():
            if k in merged:
                merged[k] = dict(v)
        meta["collective_baseline"] = {
            "collectives": row["collectives"], "tolerances": merged}


def run(argv):
    args = build_parser().parse_args(argv)

    from paddle_tpu.tools import jxaudit
    from paddle_tpu.tools.lint import baseline as lintbase

    if args.list_rules:
        for rule_id in sorted(jxaudit.MESH_RULES):
            print(f"{rule_id}: "
                  f"{jxaudit.MESH_RULES[rule_id].rationale}")
        return 0

    if args.list_programs:
        for name in jxaudit.MESH_PROGRAMS:
            print(name)
        return 0

    select = None
    if args.select:
        select = {s.strip() for s in args.select.split(",") if s.strip()}

    import jax
    from paddle_tpu.utils import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

    no_baseline = args.no_baseline
    if args.inject:
        if args.baseline_update:
            print("shaudit: refusing --baseline-update with --inject: a "
                  "deliberately mis-sharded program must never be "
                  "grandfathered", file=sys.stderr)
            return 2
        if args.inject not in jxaudit.MESH_INJECTIONS:
            print(f"shaudit: unknown injection {args.inject!r}; have "
                  f"{sorted(jxaudit.MESH_INJECTIONS)}", file=sys.stderr)
            return 2
        if select is not None and args.inject not in select:
            print(f"shaudit: --select {args.select} excludes the "
                  f"injected class {args.inject!r} — the positive "
                  "control would vacuously pass", file=sys.stderr)
            return 2
        specs = [jxaudit.build_injected_spec(args.inject)]
        axes = (specs[0].get("sharding") or {}).get("mesh_axes") or {}
        if max(axes.values(), default=1) < 2:
            print("shaudit: --inject needs a multi-device mesh (this "
                  "process has 1 device, so every probe axis has size "
                  "1 and the positive control would vacuously pass) — "
                  "run under the tier-1 env (XLA_FLAGS=--xla_force_"
                  "host_platform_device_count=8)", file=sys.stderr)
            return 2
        if select is None:
            select = {args.inject}
        no_baseline = True
    else:
        names = None
        if args.programs:
            names = [s.strip() for s in args.programs.split(",")
                     if s.strip()]
        try:
            specs = jxaudit.mesh_specs(names)
        except ValueError as e:
            print(f"shaudit: {e}", file=sys.stderr)
            return 2
        attach_collective_budgets(specs, args.hlo_baseline)

    try:
        findings, report = jxaudit.audit_programs(
            specs, select=select, rules=jxaudit.MESH_RULES)
    except ValueError as e:              # unknown rule in --select
        print(f"shaudit: {e}", file=sys.stderr)
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
        print(f"shaudit: baseline rewritten with {len(entries)} "
              f"entr{'y' if len(entries) == 1 else 'ies'} covering "
              f"{len(findings)} finding(s) -> {args.baseline}")
        if todo:
            print(f"shaudit: {len(todo)} entr"
                  f"{'y needs' if len(todo) == 1 else 'ies need'} a "
                  "justification (edit the TODO markers before "
                  "committing)", file=sys.stderr)
        return 0

    new, suppressed, undocumented, clean = lintbase.gate(findings,
                                                         entries)
    # journal the POST-baseline verdict, same as jxaudit
    jxaudit.publish_mesh_summary(new, report, suppressed=suppressed)
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
            "summary": jxaudit.summarize_mesh(new, report),
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
            print(f"shaudit: {n} finding(s) ({suppressed} baselined); "
                  "see docs/static_analysis.md for the baseline "
                  "workflow", file=sys.stderr)
        else:
            print(f"shaudit: clean ({len(report['programs'])} programs, "
                  f"{suppressed} baselined finding(s))", file=sys.stderr)
    return 0 if clean else 1


def main(argv=None):
    try:
        return run(sys.argv[1:] if argv is None else argv)
    except SystemExit as e:              # argparse --help / usage errors
        return e.code if isinstance(e.code, int) else 2
    except Exception:
        traceback.print_exc()
        print("shaudit: internal error", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
