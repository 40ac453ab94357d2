"""Fleet metrics: replica states, routing decisions, migrations and
restarts.

Same two-sink discipline as serving/metrics.py: the typed process-wide
registry (docs/observability.md catalogs the names below) feeds
/metrics, while a `FleetMetrics` instance aggregates per-router tallies
(`snapshot()`).
"""
import threading

from ...utils import flight_recorder, telemetry

_REPLICAS = telemetry.gauge(
    "fleet_replicas", "Replicas in the router's rotation by state",
    labelnames=("state",))
_MIGRATIONS = telemetry.counter(
    "fleet_migrations_total",
    "In-flight requests resubmitted (prompt + tokens generated so far) "
    "from a dead or degraded replica to a healthy one — token-exact for "
    "greedy requests (the preemption-by-recompute contract)")
_ROUTED = telemetry.counter(
    "fleet_routed_total",
    "Requests routed by decision policy: affinity (prefix-cache blocks "
    "matched on the chosen replica), least_loaded (no replica held the "
    "prefix), or round_robin (A/B baseline policy)",
    labelnames=("policy",))
_RESTARTS = telemetry.counter(
    "fleet_replica_restarts_total",
    "Replacement replicas spawned after a kill/degradation (warm start: "
    "weights digest-checked against the fleet's reference state)")
_DISPATCH_RETRIES = telemetry.counter(
    "fleet_dispatch_retries_total",
    "Dispatch attempts rerouted to the next candidate replica after a "
    "dispatch fault or a replica-side rejection — an accepted request "
    "is never lost to a single bad hand-off")
_ROLES = telemetry.gauge(
    "fleet_replica_role", "Replicas in the rotation by disaggregation "
    "role (prefill / decode / unified)", labelnames=("role",))
_HANDOFF_BLOCKS = telemetry.counter(
    "fleet_handoff_blocks_total",
    "KV blocks shipped prefill->decode via the block-level handoff "
    "path (digest-verified; the bytes-not-recompute transfer)")
_HANDOFF_BYTES = telemetry.counter(
    "fleet_handoff_bytes_total",
    "Device bytes shipped in block-level KV handoff payloads")


class FleetMetrics:
    """Per-router aggregation (the process-wide counters keep
    accumulating for /metrics; a fresh router — or a bench load point
    via `FleetRouter.reset_metrics()` — gets fresh tallies)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._routed = {}            # policy -> count
        self._migrations = 0
        self._restarts = 0
        self._dispatch_retries = 0
        self._rejected = 0
        self._kills = 0
        self._scale_ups = 0
        self._scale_downs = 0
        self._handoffs = 0
        self._handoff_blocks = 0
        self._handoff_bytes = 0

    # ---------------------------------------------------------- recording
    def on_routed(self, policy):
        _ROUTED.labels(policy=policy).inc()
        with self._lock:
            self._routed[policy] = self._routed.get(policy, 0) + 1

    def on_migration(self, request_id=None, src=None, dst=None):
        _MIGRATIONS.inc()
        with self._lock:
            self._migrations += 1
        rec = flight_recorder.get_recorder()
        if rec is not None:
            rec.fault(kind="replica_migration", action="resubmitted",
                      request_id=request_id,
                      error=f"replica {src} -> {dst}")

    def on_handoff(self, request_id=None, src=None, dst=None, blocks=0,
                   nbytes=0):
        """One block-level prefill->decode KV handoff dispatched. The
        journal event's kind is distinct from replica_migration so the
        runlog's fleet table can count bytes-moved handoffs separately
        from recompute migrations."""
        _HANDOFF_BLOCKS.inc(blocks)
        _HANDOFF_BYTES.inc(nbytes)
        with self._lock:
            self._handoffs += 1
            self._handoff_blocks += blocks
            self._handoff_bytes += nbytes
        rec = flight_recorder.get_recorder()
        if rec is not None:
            rec.fault(kind="replica_handoff", action="resubmitted",
                      request_id=request_id,
                      error=f"replica {src} -> {dst} "
                            f"({blocks} blocks, {nbytes} bytes)",
                      blocks=int(blocks), nbytes=int(nbytes))

    def on_restart(self):
        _RESTARTS.inc()
        with self._lock:
            self._restarts += 1

    def on_dispatch_retry(self):
        _DISPATCH_RETRIES.inc()
        with self._lock:
            self._dispatch_retries += 1

    def on_rejected(self):
        """One request refused fleet-wide. Counted HERE, once per
        request — the per-replica serving counters tick once per
        candidate walked, so summing them across the rotation would
        inflate the shed count by up to the replica count."""
        with self._lock:
            self._rejected += 1

    def on_kill(self):
        with self._lock:
            self._kills += 1

    def on_scale(self, direction):
        with self._lock:
            if direction == "up":
                self._scale_ups += 1
            else:
                self._scale_downs += 1

    def publish_states(self, replicas, dead_total=0):
        """Export the rotation's state census (called once per fleet
        step). Every known state is set — including back to 0 — so a
        replica leaving a state is visible, not sticky. Dead replicas
        leave the rotation at retirement, so the `dead` series carries
        the router's CUMULATIVE kill/degrade count instead (a census of
        the rotation alone could never show a nonzero dead bucket)."""
        counts = {"ok": 0, "degraded": 0, "draining": 0,
                  "dead": dead_total}
        roles = {"prefill": 0, "decode": 0, "unified": 0}
        for r in replicas:
            counts[r.state] = counts.get(r.state, 0) + 1
            role = getattr(r, "role", "unified")
            roles[role] = roles.get(role, 0) + 1
        for state, n in counts.items():
            _REPLICAS.labels(state=state).set(n)
        for role, n in roles.items():
            _ROLES.labels(role=role).set(n)

    # ---------------------------------------------------------- reporting
    def snapshot(self):
        """Router-level tallies for bench rows: routing mix + affinity
        hit rate, migrations, restarts, rebalance (scale) events."""
        with self._lock:
            routed = dict(self._routed)
            total = sum(routed.values())
            return {
                "routed": routed,
                "routed_total": total,
                "affinity_hit_rate": (routed.get("affinity", 0) / total
                                      if total else None),
                "migrations": self._migrations,
                "handoffs": self._handoffs,
                "handoff_blocks": self._handoff_blocks,
                "handoff_bytes": self._handoff_bytes,
                "rejected": self._rejected,
                "replica_kills": self._kills,
                "replica_restarts": self._restarts,
                "dispatch_retries": self._dispatch_retries,
                "rebalances": self._scale_ups + self._scale_downs,
                "scale_ups": self._scale_ups,
                "scale_downs": self._scale_downs,
            }


class FleetRegistry:
    """Fleet-wide /metrics view: a duck-typed telemetry registry over a
    FleetRouter, served by `FleetRouter.start_metrics_server()`.

    The process-wide registry cannot distinguish replicas — every
    engine's `serving_*` gauges overwrite one series. This facade
    builds, per scrape, a fresh registry of per-replica gauges labeled
    `replica` (queue depth, active slots, pool occupancy, health state)
    plus fleet-summed counters that stay COHERENT across kill/replace
    cycles (retired replicas' final metric snapshots are folded in, so
    work done before a kill never vanishes from the totals), and
    appends the process-wide exposition after it. Building per scrape
    also means a replica leaving the rotation drops its series instead
    of freezing at its last value.
    """

    def __init__(self, router):
        self._router = router

    def _build(self):
        reg = telemetry.Registry()
        depth = reg.gauge(
            "fleet_replica_queue_depth",
            "Requests queued on each replica", ("replica",))
        slots = reg.gauge(
            "fleet_replica_slots_active",
            "Slots decoding on each replica", ("replica",))
        used = reg.gauge(
            "fleet_replica_cache_blocks_used",
            "KV blocks referenced by live requests, per replica",
            ("replica",))
        total = reg.gauge(
            "fleet_replica_cache_blocks_total",
            "Usable KV blocks in each replica's pool", ("replica",))
        state = reg.gauge(
            "fleet_replica_state",
            "1 for each replica's current health state (a replica "
            "changing state moves the 1 between series)",
            ("replica", "state"))
        router = self._router
        # one atomic capture: a replica mid-retirement lands in exactly
        # one of the two lists, keeping the summed counters monotonic
        reps, retired = router.metric_view()
        for r in reps:
            h = r.health()
            lbl = str(r.replica_id)
            depth.labels(replica=lbl).set(h.get("queue_depth", 0))
            slots.labels(replica=lbl).set(h.get("slots_active", 0))
            if "cache_blocks_used" in h:
                used.labels(replica=lbl).set(h["cache_blocks_used"])
                total.labels(replica=lbl).set(h["cache_blocks_total"])
            state.labels(replica=lbl, state=h.get("status", "ok")).set(1)
        snaps = [r.scheduler.metrics.snapshot() for r in reps] + retired
        reg.counter(
            "fleet_tokens_generated_total",
            "Tokens generated across the whole fleet — live rotation "
            "plus replicas retired since the last reset, so a "
            "kill/replace cycle never loses counted work").inc(
            sum(s["tokens_generated"] for s in snaps))
        reg.counter(
            "fleet_requests_completed_total",
            "Requests completed across the whole fleet (same retired-"
            "replica folding as the token counter)").inc(
            sum(s["requests_completed"] for s in snaps))
        return reg

    # -- duck-typed registry surface (what make_metrics_handler calls) --
    def render_prometheus(self, include_monitor=True):
        return (self._build().render_prometheus(include_monitor=False)
                + telemetry.REGISTRY.render_prometheus(include_monitor))

    def snapshot(self, include_monitor=True):
        out = telemetry.REGISTRY.snapshot(include_monitor)
        out["metrics"].update(
            self._build().snapshot(include_monitor=False)["metrics"])
        return out
