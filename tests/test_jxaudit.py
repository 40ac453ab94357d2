"""jxaudit: program-level semantic audit (paddle_tpu/tools/jxaudit +
scripts/jxaudit.py).

Contracts under test:

  * each rule FIRES on a toy program carrying its defect and STAYS
    SILENT on the clean twin (false-positive drift in a gate is a
    broken build for everyone);
  * the serving decode wave's donated KV-cache buffers are ACTUALLY
    aliased by XLA at the engine's real shapes — a refactor that
    changes an output dtype/shape and silently drops the donation
    fails here, not on the next HBM-OOM;
  * the eager optimizer update donates (and XLA aliases) its state;
  * the CLI exit contract: every `--inject` defect class exits 1
    (positive controls), `--baseline-update --inject` is refused, and
    a baseline entry without a justification fails the clean check —
    ptlint's exact machinery;
  * analyses degrade to reasons, never crashes, on jax builds that
    can't answer;
  * the audit journals a `jxaudit` summary event through the flight
    recorder.

The repo-audits-clean gate itself runs once through
tests/test_check_static.py (ptlint + hlo_audit + jxaudit in one
process).
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.tools import jxaudit
from paddle_tpu.tools.jxaudit.core import ProgramContext
from paddle_tpu.utils import flight_recorder as fr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "jxaudit.py")


def _cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, SCRIPT, *args],
                          capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=500)


def _audit(spec, select=None):
    return jxaudit.audit_programs([spec], select=select)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# donation-dropped / donation-missing
# ---------------------------------------------------------------------------

def test_donation_dropped_fires_on_dtype_mismatch():
    """A donated bf16 cache whose outputs are all f32 can alias
    nothing: XLA drops the donation and the rule must say so, with the
    wasted HBM quantified."""
    def f(cache, x):
        return cache.astype(jnp.float32) + x

    cache = jnp.zeros((64, 64), jnp.bfloat16)
    spec = {"name": "toy", "fn": f,
            "args": (cache, jnp.ones((64, 64), jnp.float32)),
            "jit_kwargs": {"donate_argnums": (0,)}}
    findings, report = _audit(spec, select={"donation-dropped"})
    assert _rules(findings) == ["donation-dropped"]
    (fd,) = findings
    assert fd.details["wasted_bytes"] == cache.nbytes
    assert fd.details["argnum"] == 0
    assert "'cache'" in fd.message


def test_donation_dropped_silent_when_aliased():
    def f(cache, x):
        return cache + x

    spec = {"name": "toy", "fn": f,
            "args": (jnp.zeros((64, 64), jnp.float32),
                     jnp.ones((64, 64), jnp.float32)),
            "jit_kwargs": {"donate_argnums": (0,)}}
    findings, report = _audit(spec, select={"donation-dropped"})
    assert findings == []
    assert "unavailable" not in report["programs"]["toy"]


def test_donation_dropped_correct_when_unused_arg_pruned():
    """jit's keep_unused=False prunes unused args from the executable,
    shifting HLO parameter indices — the type-based leaf/parameter
    alignment must keep the attribution right (clean here: the donated
    cache IS aliased, at a shifted parameter index)."""
    def f(unused, cache, x):
        return cache + x

    spec = {"name": "toy", "fn": f,
            "args": (jnp.zeros((32, 32), jnp.float32),
                     jnp.zeros((64, 64), jnp.float32),
                     jnp.ones((64, 64), jnp.float32)),
            "jit_kwargs": {"donate_argnums": (1,)}}
    findings, report = _audit(spec, select={"donation-dropped"})
    assert findings == []
    assert "unavailable" not in report["programs"]["toy"]
    # and a REAL drop behind a pruned arg is still attributed
    def g(unused, cache, x):
        return cache.astype(jnp.float32) + x

    spec2 = {"name": "toy", "fn": g,
             "args": (jnp.zeros((32, 32), jnp.float32),
                      jnp.zeros((64, 64), jnp.bfloat16),
                      jnp.ones((64, 64), jnp.float32)),
             "jit_kwargs": {"donate_argnums": (1,)}}
    findings2, _ = _audit(spec2, select={"donation-dropped"})
    assert len(findings2) == 1 and "'cache'" in findings2[0].message


def test_donation_dropped_degrades_on_ambiguous_pruning():
    """A pruned leaf whose type also occurs among kept parameters is
    textually indistinguishable — the rule must degrade with a reason
    rather than risk misattributing aliasing."""
    def f(unused, cache, x):
        return cache + x

    same = (64, 64)
    spec = {"name": "toy", "fn": f,
            "args": (jnp.zeros(same, jnp.float32),    # same type as kept
                     jnp.zeros(same, jnp.float32),
                     jnp.ones(same, jnp.float32)),
            "jit_kwargs": {"donate_argnums": (1,)}}
    findings, report = _audit(spec, select={"donation-dropped"})
    assert findings == []
    reason = report["programs"]["toy"]["unavailable"]["donation-dropped"]
    assert "ambiguous" in reason


def test_donation_missing_fires_on_large_undonated_state():
    def f(params, opt_state, g):
        return params - g, tuple(s + 1 for s in opt_state)

    big = (jnp.zeros((128, 256), jnp.float32),) * 2    # 256 KiB
    spec = {"name": "toy", "fn": f,
            "args": (jnp.zeros((128, 256)), big, jnp.zeros((128, 256)))}
    findings, _ = _audit(spec, select={"donation-missing"})
    assert _rules(findings) == ["donation-missing"]
    assert "'opt_state'" in findings[0].message
    # donated twin is clean
    spec2 = dict(spec, jit_kwargs={"donate_argnums": (1,)})
    findings2, _ = _audit(spec2, select={"donation-missing"})
    assert findings2 == []
    # sub-threshold state is not worth a finding
    small = (jnp.zeros((4, 4), jnp.float32),) * 2
    spec3 = dict(spec, args=(jnp.zeros((4, 4)), small, jnp.zeros((4, 4))))
    findings3, _ = _audit(spec3, select={"donation-missing"})
    assert findings3 == []


# ---------------------------------------------------------------------------
# dtype-leak
# ---------------------------------------------------------------------------

def test_dtype_leak_fires_on_large_upcast_in_bf16_program():
    def f(w, x):
        return w.astype(jnp.float32) @ x

    spec = {"name": "toy", "fn": f,
            "args": (jnp.zeros((128, 128), jnp.bfloat16),   # 32 KiB bf16
                     jnp.zeros((128, 8), jnp.float32))}
    findings, _ = _audit(spec, select={"dtype-leak"})
    assert _rules(findings) == ["dtype-leak"]
    assert "bfloat16[128,128] -> float32" in findings[0].message


def test_dtype_leak_silent_on_f32_program_and_small_casts():
    def f(w, x):
        return w @ x + jnp.float32(1)

    spec = {"name": "toy", "fn": f,
            "args": (jnp.zeros((128, 128), jnp.float32),
                     jnp.zeros((128, 8), jnp.float32))}
    findings, _ = _audit(spec, select={"dtype-leak"})
    assert findings == []
    # a sub-threshold bf16 cast in a bf16-dominated program is noise
    def g(w):
        small = w[0, :64].astype(jnp.float32)       # 128 B upcast
        return w + small.sum().astype(jnp.bfloat16)

    spec2 = {"name": "toy", "fn": g,
             "args": (jnp.zeros((128, 128), jnp.bfloat16),)}
    findings2, _ = _audit(spec2, select={"dtype-leak"})
    assert findings2 == []


def test_dtype_leak_flags_f64_on_device_path():
    """float64 avals anywhere in the jaxpr are an x64 leak regardless
    of size or domination."""
    prev = jax.config.jax_enable_x64
    try:
        jax.config.update("jax_enable_x64", True)

        def f(x):
            return (x.astype(jnp.float64) * 2.0).astype(jnp.float32)

        spec = {"name": "toy", "fn": f,
                "args": (jnp.zeros((8,), jnp.float32),)}
        findings, _ = _audit(spec, select={"dtype-leak"})
    finally:
        jax.config.update("jax_enable_x64", prev)
    assert any("float64" in f.message and f.severity == "error"
               for f in findings), findings


# ---------------------------------------------------------------------------
# baked-constant / host-callback
# ---------------------------------------------------------------------------

def test_baked_constant_fires_above_threshold_only():
    big = jnp.arange(32768, dtype=jnp.float32)          # 128 KiB
    small = jnp.arange(64, dtype=jnp.float32)

    def f(x):
        return x + big.sum()

    findings, _ = _audit({"name": "toy", "fn": f,
                          "args": (jnp.zeros(4),)},
                         select={"baked-constant"})
    assert _rules(findings) == ["baked-constant"]
    assert findings[0].details["bytes"] == big.nbytes

    def g(x):
        return x + small.sum()

    findings2, _ = _audit({"name": "toy", "fn": g,
                           "args": (jnp.zeros(4),)},
                          select={"baked-constant"})
    assert findings2 == []


def test_host_callback_fires_on_debug_print_and_pure_callback():
    def f(x):
        jax.debug.print("x={x}", x=x[0])
        return x * 2

    findings, _ = _audit({"name": "toy", "fn": f,
                          "args": (jnp.zeros(4),)},
                         select={"host-callback"})
    assert _rules(findings) == ["host-callback"]
    assert "debug_callback" in findings[0].message

    def g(x):
        y = jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1

    findings2, _ = _audit({"name": "toy", "fn": g,
                           "args": (jnp.zeros(4),)},
                          select={"host-callback"})
    assert any("pure_callback" in f.message for f in findings2)

    def clean(x):
        return x * 2

    findings3, _ = _audit({"name": "toy", "fn": clean,
                           "args": (jnp.zeros(4),)},
                          select={"host-callback"})
    assert findings3 == []


def test_host_callback_seen_through_control_flow():
    """Callback primitives inside scan/cond bodies (nested jaxprs) are
    still reachable from the hot program."""
    def f(x):
        def body(c, t):
            jax.debug.print("c={c}", c=c)
            return c + t, t
        out, _ = jax.lax.scan(body, x[0], x)
        return out

    findings, _ = _audit({"name": "toy", "fn": f,
                          "args": (jnp.zeros(4),)},
                         select={"host-callback"})
    assert _rules(findings) == ["host-callback"]


# ---------------------------------------------------------------------------
# degradation: null + reason, never a crash
# ---------------------------------------------------------------------------

class _TraceRaises:
    def trace(self, *a, **kw):
        raise RuntimeError("no trace on this build")

    def lower(self, *a, **kw):
        raise RuntimeError("no lower on this build")


def test_degrades_to_reasons_when_jax_cannot_answer():
    spec = {"name": "toy", "jitted": _TraceRaises(),
            "args": (jnp.zeros(4),), "donate_argnums": (0,)}
    findings, report = jxaudit.audit_programs([spec])
    assert findings == []
    reasons = report["programs"]["toy"]["unavailable"]
    # every rule that needed an un-answerable analysis left a reason
    for rule_id in ("donation-dropped", "dtype-leak", "baked-constant",
                    "host-callback"):
        assert rule_id in reasons or "jaxpr" in reasons, reasons
    s = jxaudit.summarize(findings, report)
    assert s["degraded"] == 1 and s["findings"] == 0


def test_publish_summary_journals_jxaudit_event():
    def f(x):
        jax.debug.print("x={x}", x=x[0])
        return x

    findings, report = _audit({"name": "toy", "fn": f,
                               "args": (jnp.zeros(4),)},
                              select={"host-callback"})
    rec = fr.FlightRecorder()           # memory-only
    ev = jxaudit.publish_summary(findings, report, recorder=rec)
    assert ev["ev"] == "jxaudit"
    assert ev["findings"] == 1
    assert ev["by_rule"] == {"host-callback": 1}
    assert ev["programs"] == 1


# ---------------------------------------------------------------------------
# registry: decorator + unknown names
# ---------------------------------------------------------------------------

def test_audited_decorator_registers_program():
    from paddle_tpu.tools.jxaudit import registry as jreg

    @jxaudit.audited("toy_registered",
                     args=lambda: (jnp.zeros((8, 8), jnp.float32),),
                     description="decorator smoke")
    def toy(x):
        return x * 2

    try:
        assert "toy_registered" in jxaudit.tracked_program_names()
        (spec,) = jxaudit.tracked_specs(["toy_registered"])
        assert spec["fn"] is toy
        findings, report = jxaudit.audit_programs([spec])
        assert findings == []
        assert "toy_registered" in report["programs"]
    finally:
        del jreg.AUDITED["toy_registered"]


def test_audited_decorator_rejects_builtin_name_collision():
    with pytest.raises(ValueError, match="already registered"):
        @jxaudit.audited("optimizer_update", args=())
        def clash(x):
            return x
    assert jxaudit.tracked_program_names().count("optimizer_update") == 1


def test_unknown_program_and_injection_rejected():
    with pytest.raises(ValueError, match="unknown audited programs"):
        jxaudit.tracked_specs(["nope"])
    with pytest.raises(ValueError, match="unknown injection"):
        jxaudit.inject_spec({"name": "x", "fn": lambda: 0}, "nope")
    with pytest.raises(ValueError, match="no raw fn"):
        jxaudit.inject_spec({"name": "x", "jitted": object()},
                            "host-callback")


# ---------------------------------------------------------------------------
# the engine / optimizer regression satellites (real shapes)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def decode_wave_ctx():
    (spec,) = jxaudit.tracked_specs(["serving_decode_wave"])
    return ProgramContext(spec)


def _lane_state_leaves(ctx, argnum, after):
    """How many leaves the two lane-state vectors are (one each), which
    follow the caches at `after`."""
    ranges = ctx.leaf_index_ranges()
    assert ranges[argnum] == (after, 1) and ranges[argnum + 1] == (
        after + 1, 1)
    return 2


def test_decode_wave_kv_donation_actually_aliased(decode_wave_ctx):
    """The engine's donated batched KV cache must be aliased by XLA at
    the engine's real shapes — every cache leaf, not just 'no findings'.
    A refactor that changes an output dtype/shape (silently dropping
    the donation and transiently doubling the cache in HBM every wave)
    fails HERE."""
    ctx = decode_wave_ctx
    # the batched KV cache, and the lanes' tokens and positions
    assert ctx.donate_argnums == (2, 3, 4)
    first, n = ctx.leaf_index_ranges()[2]
    assert n == 4                              # 2 layers x (k, v)
    aliased = ctx.aliased_param_indices
    assert aliased is not None, ctx.unavailable
    n += _lane_state_leaves(ctx, 3, first + n)
    missing = [i for i in range(first, first + n) if i not in aliased]
    assert missing == [], \
        f"decode-wave KV cache leaves {missing} lost donation aliasing"
    assert list(jxaudit.RULES["donation-dropped"].check(ctx)) == []


def test_decode_wave_full_audit_clean(decode_wave_ctx):
    findings, report = jxaudit.audit_programs(
        [decode_wave_ctx.spec])
    assert findings == [], [f.render() for f in findings]


def test_paged_decode_wave_pool_donation_actually_aliased():
    """The paged engine's donated block POOLS must be aliased by XLA at
    the engine's real shapes — every pool leaf, exactly like the dense
    KV-cache regression above. The block-table arg rides as a traced
    input (never donated, never a baked constant)."""
    (spec,) = jxaudit.tracked_specs(["paged_decode_wave"])
    ctx = ProgramContext(spec)
    assert ctx.donate_argnums == (2, 3, 4)     # the block pools, the lanes
    first, n = ctx.leaf_index_ranges()[2]
    assert n == 2                              # 2 layers, one K/V pool each
    aliased = ctx.aliased_param_indices
    assert aliased is not None, ctx.unavailable
    n += _lane_state_leaves(ctx, 3, first + n)
    missing = [i for i in range(first, first + n) if i not in aliased]
    assert missing == [], \
        f"paged decode-wave pool leaves {missing} lost donation aliasing"
    assert list(jxaudit.RULES["donation-dropped"].check(ctx)) == []


def test_paged_prefill_chunk_pool_donation_actually_aliased():
    (spec,) = jxaudit.tracked_specs(["paged_prefill_chunk"])
    ctx = ProgramContext(spec)
    assert ctx.donate_argnums == (2, 3, 4)
    first, n = ctx.leaf_index_ranges()[2]
    assert n == 2
    aliased = ctx.aliased_param_indices
    assert aliased is not None, ctx.unavailable
    n += _lane_state_leaves(ctx, 3, first + n)
    missing = [i for i in range(first, first + n) if i not in aliased]
    assert missing == [], \
        f"paged prefill-chunk pool leaves {missing} lost donation " \
        "aliasing"


def test_spec_programs_target_and_draft_pools_actually_aliased():
    """The speculative trio donates ONE bundle (target pools, draft
    pools): every leaf of BOTH halves must be aliased by XLA at engine
    shapes in the draft wave AND the verify wave — the draft wave
    passes the target pools through untouched (and vice versa is never
    true: verify updates only target), so a pass-through that lost its
    alias would double the wave's HBM footprint silently."""
    specs = jxaudit.tracked_specs(["paged_spec_draft_wave",
                                   "paged_spec_verify"])
    assert len(specs) == 2
    for spec in specs:
        ctx = ProgramContext(spec)
        # the draft wave reads the lane state and leaves it to the
        # verify wave, which takes it donated beside the bundle
        verify = spec["name"] == "paged_spec_verify"
        assert ctx.donate_argnums == ((2, 3, 4) if verify else (2,)), \
            spec["name"]
        first, n = ctx.leaf_index_ranges()[2]
        # 2 target layers + 1 draft layer, one K/V pool each
        assert n == 3, spec["name"]
        aliased = ctx.aliased_param_indices
        assert aliased is not None, (spec["name"], ctx.unavailable)
        if verify:
            n += _lane_state_leaves(ctx, 3, first + n)
        missing = [i for i in range(first, first + n)
                   if i not in aliased]
        assert missing == [], \
            f"{spec['name']}: pool leaves {missing} (target+draft " \
            "bundle) lost donation aliasing"
        assert list(jxaudit.RULES["donation-dropped"].check(ctx)) == []


def test_optimizer_update_state_donated_and_aliased():
    """The eager opt.step() executable must donate param AND state (the
    first full jxaudit sweep caught state as donation-missing; this
    locks the fix)."""
    from paddle_tpu.optimizer.optimizer import UPDATE_DONATE_ARGNUMS
    assert 4 in UPDATE_DONATE_ARGNUMS          # state tuple
    (spec,) = jxaudit.tracked_specs(["optimizer_update"])
    ctx = ProgramContext(spec)
    findings = list(jxaudit.RULES["donation-missing"].check(ctx))
    findings += list(jxaudit.RULES["donation-dropped"].check(ctx))
    assert findings == [], [f.render() for f in findings]
    first, n = ctx.leaf_index_ranges()[4]      # (m, v)
    aliased = ctx.aliased_param_indices
    assert aliased is not None, ctx.unavailable
    assert set(range(first, first + n)) <= aliased


def test_sharded_train_step_opt_state_actually_aliased():
    """The eager-optimizer donation bug from PR 7, in its SHARDED
    incarnation: the ZeRO dp-sharded optimizer-state leaves of
    `sharded_train_step` must be ACTUALLY aliased in the PARTITIONED
    HLO — at their per-shard entry shapes, which is also the regression
    gate on the shard-aware leaf->param alignment (a degrade here would
    let a dropped sharded donation pass silently: the audit is only a
    gate while the mapping resolves)."""
    (spec,) = jxaudit.tracked_specs(["sharded_train_step"])
    ctx = ProgramContext(spec)
    assert ctx.donate_argnums == (0, 1, 2, 3)
    mapping = ctx.leaf_param_map
    assert mapping is not None, ctx.unavailable    # alignment resolved
    aliased = ctx.aliased_param_indices
    assert aliased is not None, ctx.unavailable
    first, n = ctx.leaf_index_ranges()[2]          # opt_state
    assert n > 0
    opt_leaves = dict(ctx.arg_leaves)[2]
    # the leaves ZeRO actually shards (per-device slice != full shape)
    dp_sharded = [i for i, leaf in enumerate(opt_leaves)
                  if jxaudit.core.leaf_shard_shape(leaf)
                  not in (None, tuple(leaf.shape))]
    assert dp_sharded, "no opt-state leaf is dp-sharded at audit shapes"
    missing = [first + i for i in dp_sharded
               if mapping.get(first + i) not in aliased]
    assert missing == [], \
        f"dp-sharded opt-state leaves {missing} lost donation aliasing " \
        "in the partitioned HLO"
    assert list(jxaudit.RULES["donation-dropped"].check(ctx)) == []


# ---------------------------------------------------------------------------
# CLI: exit contract + positive controls (tier-1's gate-fires proof)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("defect", sorted(jxaudit.INJECTIONS))
def test_cli_injected_defect_exits_1(defect):
    out = _cli("--inject", defect)
    assert out.returncode == 1, \
        f"injected {defect} passed the audit:\n{out.stdout}\n{out.stderr}"
    assert defect in out.stdout                # the matching rule fired


def test_cli_refuses_baseline_update_with_inject():
    out = _cli("--inject", "host-callback", "--baseline-update")
    assert out.returncode == 2
    assert "refusing" in out.stderr


def test_cli_unknown_select_and_injection_exit_2():
    out = _cli("--select", "no-such-rule", "--programs",
               "cached_decode_attention")
    assert out.returncode == 2
    out2 = _cli("--inject", "no-such-class")
    assert out2.returncode == 2
    # --select that excludes the injected class would let the positive
    # control vacuously pass — refused
    out3 = _cli("--inject", "host-callback", "--select",
                "donation-missing")
    assert out3.returncode == 2
    assert "vacuously" in out3.stderr


def test_cli_undocumented_baseline_entry_fails(tmp_path):
    """A baseline entry without a justification is rejected even when
    the tree itself is clean — ptlint's contract, same machinery."""
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"version": 1, "findings": [{
        "rule": "host-callback", "path": "cached_decode_attention",
        "message": "grandfathered without explanation", "count": 1}]}))
    out = _cli("--programs", "cached_decode_attention",
               "--baseline", str(base))
    assert out.returncode == 1, out.stdout + out.stderr
    assert "lacks a justification" in out.stdout


def test_cli_json_reports_clean_subset():
    out = _cli("--programs", "cached_decode_attention,"
               "prefill_flash_attention", "--json")
    assert out.returncode == 0, out.stdout + out.stderr
    doc = json.loads(out.stdout)
    assert doc["status"] == "clean"
    assert set(doc["report"]["programs"]) == {
        "cached_decode_attention", "prefill_flash_attention"}
