"""Online anomaly detection over the live metric plane, with a
journaled alert manager.

utils/timeseries.py banks what every metric just did; this module
judges it.  Two detector families:

* `RobustEWMA` — an exponentially-weighted mean + mean-absolute-
  deviation tracker with a z-score trigger and hysteresis.  It catches
  both spikes and step-changes: a level shift scores a large z the
  moment it lands (firing), then the EWMA absorbs the new level and the
  z decays back under the clear threshold (cleared) — so a one-time
  regime change is exactly one firing/cleared pair, never a flood.
* rule detectors — closed-form checks that need no statistics:
  recompile-after-warmup (`xla_compiles_total` delta on a labeled
  function), prefix-cache hit-rate collapse (windowed hit rate against
  its own EWMA baseline), and fleet replica queue-skew imbalance.

An `AlertRule` names one check; the `AlertManager` runs the set and
latches per-rule state with the same transition discipline as the SLO
engine (serving/slo.py): state changes bump `alerts_fired_total{rule}`,
move `alerts_active{rule}`, and journal an `alert` flight-recorder
event — steady state journals nothing.  `health()` merges into
/healthz and `FleetRouter.health()`; `summary()` is the rollup
bench.py embeds in its JSON line.

Every `AlertRule` id constructed in code must be documented in the
alert table of docs/observability.md — the `alert-rule-documented`
ptlint rule enforces it, same contract as metric names.
"""

import math
import sys
import threading

from . import flight_recorder, telemetry

_FIRED = telemetry.counter(
    "alerts_fired_total",
    "Alert firing transitions per rule (cleared->firing edges only; "
    "steady-state breach does not re-count)", labelnames=("rule",))
_ACTIVE = telemetry.gauge(
    "alerts_active",
    "1 while the rule's alert is firing, 0 otherwise",
    labelnames=("rule",))


class RobustEWMA:
    """Robust online z-score with hysteresis.

    Tracks an EWMA of the value and of its absolute deviation (a
    robust scale proxy — one outlier moves it by alpha, not
    quadratically).  `update(x)` scores x against the *pre-update*
    statistics, then folds x in, so a spike cannot mask itself; because
    the statistics keep adapting while firing, a sustained level shift
    clears on its own once the baseline catches up.

    `direction` gates which side of the baseline can FIRE: "up" (only
    x above the mean — latency/queue/utilization alerts), "down" (only
    x below — acceptance-rate alerts), "both".  One-sided rules do not
    re-fire on the recovery edge: latency falling back to normal is the
    resolution, not a second anomaly.  Clearing is always two-sided."""

    def __init__(self, alpha=0.25, z_fire=4.0, z_clear=1.25, warmup=8,
                 min_delta=0.0, rel_floor=0.05, abs_floor=1e-9,
                 direction="both"):
        if direction not in ("up", "down", "both"):
            raise ValueError(f"direction {direction!r} not in "
                             f"('up', 'down', 'both')")
        self.alpha = float(alpha)
        self.z_fire = float(z_fire)
        self.z_clear = float(z_clear)
        self.warmup = int(warmup)
        self.min_delta = float(min_delta)
        self.rel_floor = float(rel_floor)
        self.abs_floor = float(abs_floor)
        self.direction = direction
        self.mean = None
        self.mad = 0.0
        self.n = 0
        self.firing = False
        self.last_z = 0.0

    def update(self, x):
        x = float(x)
        if not math.isfinite(x):
            return self.firing
        if self.mean is None:
            self.mean, self.n = x, 1
            return False
        dev = abs(x - self.mean)
        scale = max(self.mad, self.rel_floor * abs(self.mean),
                    self.abs_floor)
        z = dev / scale
        self.last_z = z
        side_ok = (self.direction == "both"
                   or (self.direction == "up" and x > self.mean)
                   or (self.direction == "down" and x < self.mean))
        if self.firing:
            if z < self.z_clear:
                self.firing = False
        elif (side_ok and self.n >= self.warmup and z >= self.z_fire
              and dev > self.min_delta):
            self.firing = True
        self.mean += self.alpha * (x - self.mean)
        self.mad += self.alpha * (dev - self.mad)
        self.n += 1
        return self.firing


class AlertRule:
    """One named check.  `check(ctx)` returns None (not evaluable this
    round — missing metric, warming up) or a dict with at least
    `firing: bool`; extra keys (value, z, function, ...) ride the
    journal event as detail.  The id must appear in the
    docs/observability.md alert table (ptlint `alert-rule-documented`)."""

    def __init__(self, rule_id, check, description="",
                 severity="warning"):
        self.id = str(rule_id)
        self.check = check
        self.description = str(description)
        self.severity = str(severity)


# ---------------------------------------------------------------------------
# value sources (read-only registry probes — never create a series)
# ---------------------------------------------------------------------------

def _hist_pct(name, q):
    def read():
        m = telemetry.REGISTRY.get(name)
        if m is None or m.kind != "histogram":
            return None
        child = m.peek()
        if child is None or child.count() == 0:
            return None
        return child.percentile(q)
    return read


def _gauge_value(name):
    return lambda: telemetry.value(name)


# ---------------------------------------------------------------------------
# detector -> check adapters
# ---------------------------------------------------------------------------

def ewma_check(value_fn, detector=None, **detector_kw):
    """Wrap a value source + RobustEWMA into an AlertRule check."""
    det = detector or RobustEWMA(**detector_kw)

    def check(ctx):
        v = value_fn()
        if v is None:
            return None
        firing = det.update(v)
        return {"firing": firing, "value": float(v),
                "z": round(det.last_z, 3),
                "baseline": None if det.mean is None
                else round(det.mean, 6)}
    return check


def recompile_check(functions=None, ignore=("unattributed",)):
    """Fires when `xla_compiles_total{function=...}` moves AFTER that
    function's warmup compile was already seen — a recompile mid-stream,
    the silent latency cliff the fusion literature warns about.  Clears
    on the next evaluation with no new delta (a recompile is an event,
    not a state)."""
    watch = tuple(functions) if functions else None
    seen = {}

    def check(ctx):
        m = telemetry.REGISTRY.get("xla_compiles_total")
        if m is None:
            return None
        hot = []
        for label_values, child in m._series():
            fn = label_values[0] if label_values else ""
            if fn in ignore or (watch is not None and fn not in watch):
                continue
            count = child.value()
            prior = seen.get(fn)
            if prior is not None and prior >= 1 and count > prior:
                hot.append(fn)
            seen[fn] = count
        if hot:
            return {"firing": True, "functions": sorted(hot)}
        return {"firing": False}
    return check


def prefix_hit_collapse_check(min_events=8, fire_ratio=0.25,
                              clear_ratio=0.5, min_baseline=0.2,
                              alpha=0.25):
    """Windowed prefix-cache hit rate (delta of hits/misses since the
    last evaluation) collapsing against its own EWMA baseline: firing
    when the window's rate drops under `fire_ratio` x baseline, cleared
    back above `clear_ratio` x baseline.  Needs an established baseline
    (>= min_baseline) so a cache that never hit cannot 'collapse'."""
    state = {"hits": None, "misses": None, "ewma": None, "firing": False}

    def check(ctx):
        hits = telemetry.value("serving_prefix_cache_hits_total")
        misses = telemetry.value("serving_prefix_cache_misses_total")
        if hits is None or misses is None:
            return None
        if state["hits"] is None:
            state["hits"], state["misses"] = hits, misses
            return None
        dh, dm = hits - state["hits"], misses - state["misses"]
        state["hits"], state["misses"] = hits, misses
        if dh + dm < min_events:
            return {"firing": state["firing"]}
        rate = dh / (dh + dm)
        baseline = state["ewma"]
        if baseline is not None and baseline >= min_baseline:
            if state["firing"]:
                if rate >= clear_ratio * baseline:
                    state["firing"] = False
            elif rate < fire_ratio * baseline:
                state["firing"] = True
        # the baseline only absorbs non-firing windows: a collapse must
        # not drag its own reference down until it reads as normal
        if not state["firing"]:
            state["ewma"] = (rate if baseline is None
                             else baseline + alpha * (rate - baseline))
        return {"firing": state["firing"], "hit_rate": round(rate, 4),
                "baseline": None if state["ewma"] is None
                else round(state["ewma"], 4)}
    return check


def queue_skew_check(skew_fire=1.5, skew_clear=1.0, min_mean_depth=1.0,
                     consecutive=2):
    """Fleet replica queue imbalance: (max - min) / mean over the live
    replicas' queue depths (the router passes them in the evaluation
    context).  Fires after `consecutive` skewed rounds — one lopsided
    round during admission bursts is normal; a sustained skew means
    routing or a replica is sick."""
    state = {"streak": 0, "firing": False}

    def check(ctx):
        depths = (ctx or {}).get("replica_queue_depths")
        if not depths or len(depths) < 2:
            state["streak"] = 0
            if state["firing"]:
                state["firing"] = False
                return {"firing": False}
            return None
        vals = [float(v) for v in depths.values()]
        mean = sum(vals) / len(vals)
        if mean < min_mean_depth:
            state["streak"] = 0
            state["firing"] = False
            return {"firing": False, "mean_depth": round(mean, 3)}
        skew = (max(vals) - min(vals)) / mean
        if state["firing"]:
            if skew <= skew_clear:
                state["firing"] = False
                state["streak"] = 0
        elif skew >= skew_fire:
            state["streak"] += 1
            if state["streak"] >= consecutive:
                state["firing"] = True
        else:
            state["streak"] = 0
        return {"firing": state["firing"], "skew": round(skew, 3),
                "mean_depth": round(mean, 3)}
    return check


# ---------------------------------------------------------------------------
# default rule sets (ids literal at the AlertRule call, for the lint)
# ---------------------------------------------------------------------------

def default_serving_rules(detector_kw=None):
    """The serving-side detector set the scheduler evaluates once per
    working round.  `detector_kw` overrides RobustEWMA parameters for
    every statistical rule (tests tighten warmup there)."""
    # one-sided by default: a latency/queue/utilization alert is an
    # upper bound, acceptance rate a lower bound — the recovery edge
    # must not read as a second anomaly. detector_kw still wins.
    up = dict({"direction": "up"}, **(detector_kw or {}))
    down = dict({"direction": "down"}, **(detector_kw or {}))
    return [
        AlertRule("ttft_p99_anomaly",
                  ewma_check(_hist_pct("serving_ttft_seconds", 99), **up),
                  "step-change/spike in p99 time-to-first-token"),
        AlertRule("tpot_p99_anomaly",
                  ewma_check(_hist_pct("serving_tpot_seconds", 99), **up),
                  "step-change/spike in p99 inter-token latency"),
        AlertRule("queue_depth_anomaly",
                  ewma_check(_gauge_value("serving_queue_depth"), **up),
                  "queue depth step-change (admission outrunning decode)"),
        AlertRule("spec_acceptance_anomaly",
                  ewma_check(
                      _gauge_value("serving_spec_acceptance_rate"),
                      **down),
                  "speculative acceptance rate drifted (draft quality)"),
        AlertRule("recompile_after_warmup", recompile_check(),
                  "a warmed compiled function compiled AGAIN mid-stream",
                  severity="critical"),
        AlertRule("prefix_hit_collapse", prefix_hit_collapse_check(),
                  "prefix-cache hit rate collapsed vs its own baseline"),
    ]


def default_train_rules(detector_kw=None):
    """Training-side set (hapi TelemetryCallback evaluates per step)."""
    up = dict({"direction": "up"}, **(detector_kw or {}))
    return [
        AlertRule("train_step_time_anomaly",
                  ewma_check(_hist_pct("train_step_seconds", 99), **up),
                  "p99 train-step wall time step-change"),
        AlertRule("recompile_after_warmup", recompile_check(),
                  "a warmed compiled function compiled AGAIN mid-run",
                  severity="critical"),
    ]


def default_fleet_rules(detector_kw=None):
    """Router-side set: serving rules plus the cross-replica skew check
    (only the router knows per-replica depths)."""
    return default_serving_rules(detector_kw) + [
        AlertRule("fleet_queue_skew", queue_skew_check(),
                  "sustained queue-depth imbalance across fleet replicas"),
    ]


class AlertManager:
    """Runs an AlertRule set and latches firing/cleared per rule.

    Same transition discipline as the SLO engine's burn-rate latch: a
    state CHANGE bumps `alerts_fired_total{rule}`, flips
    `alerts_active{rule}`, and journals ONE `alert` event through the
    current flight recorder; a steady breach (or steady calm) does
    nothing.  A raising detector is contained and counted — observers
    must never take the serving loop down."""

    def __init__(self, rules=None, recorder=None):
        self.rules = list(rules) if rules is not None \
            else default_serving_rules()
        self._recorder = recorder
        self._lock = threading.Lock()
        self._state = {}
        self.check_errors = 0
        #: path of the most recent incident bundle snapped by a firing
        #: transition (None until a black-box recorder with a bundle_dir
        #: is attached and a rule latches)
        self.last_bundle = None
        for rule in self.rules:
            self._state[rule.id] = {"active": False, "fired": 0,
                                    "cleared": 0, "last": None}
            _ACTIVE.labels(rule=rule.id).set(0.0)

    def evaluate(self, context=None):
        """One detection round over every rule.  Returns the transitions
        it journaled as (rule_id, "firing"|"cleared") pairs."""
        ctx = context or {}
        transitions = []
        with self._lock:
            for rule in self.rules:
                try:
                    res = rule.check(ctx)
                except Exception:   # noqa: BLE001 — observer, not actor
                    self.check_errors += 1
                    continue
                if res is None:
                    continue
                st = self._state[rule.id]
                st["last"] = res
                firing = bool(res.get("firing"))
                if firing == st["active"]:
                    continue
                st["active"] = firing
                action = "firing" if firing else "cleared"
                st["fired" if firing else "cleared"] += 1
                if firing:
                    _FIRED.labels(rule=rule.id).inc()
                _ACTIVE.labels(rule=rule.id).set(1.0 if firing else 0.0)
                detail = {k: v for k, v in res.items() if k != "firing"}
                if firing:
                    bundle = self._snapshot_incident(rule, detail)
                    if bundle is not None:
                        detail["bundle"] = bundle
                        self.last_bundle = bundle
                rec = self._recorder or flight_recorder.get_recorder()
                if rec is not None:
                    rec.alert(rule=rule.id, action=action,
                              severity=rule.severity, **detail)
                transitions.append((rule.id, action))
        return transitions

    def _snapshot_incident(self, rule, detail):
        """Freeze a self-contained incident bundle through the serving
        black-box recorder, if one is attached with a bundle_dir.
        Resolved through sys.modules, not an import: utils must not
        depend on serving, and a recorder can only exist if the blackbox
        module was already imported by whoever installed it."""
        bb_mod = sys.modules.get("paddle_tpu.serving.blackbox")
        if bb_mod is None:
            return None
        try:
            bb = bb_mod.get_recorder()
            if bb is None or bb.bundle_dir is None:
                return None
            return bb.incident_bundle(rule=rule.id,
                                      severity=rule.severity,
                                      detail=dict(detail))
        except Exception:   # noqa: BLE001 — observer, not actor
            self.check_errors += 1
            return None

    # ------------------------------------------------------------- readers
    def active(self):
        with self._lock:
            return sorted(r for r, st in self._state.items()
                          if st["active"])

    def counts(self, rule_id):
        with self._lock:
            st = self._state[rule_id]
            return {"fired": st["fired"], "cleared": st["cleared"],
                    "active": st["active"]}

    def summary(self):
        """Per-rule fired/cleared rollup (the BENCH JSON embed)."""
        with self._lock:
            rules = {r: {"fired": st["fired"], "cleared": st["cleared"],
                         "active": st["active"]}
                     for r, st in sorted(self._state.items())}
            return {
                "rules": rules,
                "fired_total": sum(s["fired"] for s in rules.values()),
                "active": sorted(r for r, s in rules.items()
                                 if s["active"]),
                "check_errors": self.check_errors,
            }

    def health(self):
        """The /healthz + FleetRouter.health() merge fragment."""
        s = self.summary()
        return {"alerts": {"active": s["active"],
                           "fired_total": s["fired_total"]}}
