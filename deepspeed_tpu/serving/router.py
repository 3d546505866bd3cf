"""Multi-replica serving router: the tier above the engine.

One ``ServingEngine`` is one replica; the "millions of users" topology is N
replicas behind a load-aware dispatcher (the DeepSpeed-Inference serving-tier
shape, arXiv:2207.00032). The router extends the single-replica
shed-with-reason admission control into cross-replica balancing:

- **Load-aware dispatch.** Replicas are scored on queue depth, slot
  occupancy and paged-block occupancy (the same signals
  ``ServingMetrics.snapshot()`` reports); ``least_loaded`` picks the
  arg-min, ``round_robin`` cycles. A request is only offered to replicas
  with queue room — when every live replica is saturated the router sheds
  ``all_replicas_saturated`` instead of letting one replica OOM its queue.
- **Session & prefix affinity.** Requests with a ``session_id`` stick to
  one replica. Stateless requests are matched against a shared prefix
  index: the paged pool's SHA-256 prefix chain keys (``kv_pool.
  prefix_chain_keys``) mapped to the replica that last served them, so an
  identical system prompt routes to the replica whose blocks already hold
  its prefix (suffix-only prefill there). An affinity target that is
  overloaded relative to the best candidate is overridden (a *rebalance*).
- **Drain / rejoin.** ``drain(i)`` stops new admissions to a replica while
  its in-flight requests finish (the PR 11 teardown discipline: quiesce,
  then tear down); ``rejoin(i)`` re-registers it (optionally with a fresh
  engine after a restart, which purges its affinity state).

Everything is host-side policy over per-replica virtual (or wall) clocks, so
the whole topology is assertable in tier-1: ``serve()`` runs a conservative
discrete-event simulation — always stepping the replica whose local clock is
furthest behind — which makes N "parallel" replicas exactly reproducible on
one process.
"""

import collections
import os

from ..telemetry.digest import LatencyDigest, evaluate_slo
from .clock import VirtualClock
from .control import Autoscaler, BurnSensor
from .kv_pool import prefix_chain_keys
from .metrics import percentile, slo_digest_events
from .migration import advance_rng
from .request import (FINISH_UNHEALTHY, REJECT_ALL_REPLICAS_SATURATED,
                      REJECT_REPLICA_FAILED, RequestState, TokenEvent,
                      as_request)


class _Replica:
    """Router-side replica handle: the engine plus drain/health state."""

    def __init__(self, sv, idx=0):
        self.sv = sv
        self.idx = idx
        self.draining = False
        # failure-recovery state machine: "live" -> "degraded" (stalled —
        # its clock jumped ahead, the DES starves it until the fleet
        # catches up; still correct, still routable) -> "dead" (killed:
        # in-flight work failed over to survivors, never routed again)
        self.health = "live"
        self.stall_until = 0.0
        # disaggregated-fleet role (serving.pools): "mixed" (default) |
        # "prefill" | "decode" — assigned at Router construction
        self.role = "mixed"

    @property
    def dead(self):
        return self.health == "dead"

    @property
    def busy(self):
        return bool(self.sv._slots or self.sv.queue.depth
                    or self.sv._prefill_jobs)

    @property
    def saturated(self):
        """Submitting now would shed ``queue_full``."""
        return self.sv.queue.depth >= self.sv.cfg.max_queue_depth

    def load_score(self, cfg):
        sv = self.sv
        score = cfg.queue_weight * sv.queue.depth \
            / max(sv.cfg.max_queue_depth, 1)
        score += cfg.slot_weight \
            * (len(sv._slots) + len(sv._prefill_jobs)) / max(sv.n_slots, 1)
        # O(1) accessor, not the full stats() dict: this runs per routed
        # request per live replica
        score += cfg.block_weight * sv.pool_mgr.occupancy()
        return score

    def prefill_score(self, cfg):
        """Prefill-pool dispatch score: queue depth + PENDING PROMPT
        TOKENS (queued prompts plus in-flight prefill-job remainders,
        normalized by the pool's token capacity) — slot/block occupancy is
        the wrong signal for a pool whose slots recycle at first-token
        time; what queues work here is un-prefilled prompt length."""
        sv = self.sv
        score = cfg.queue_weight * sv.queue.depth \
            / max(sv.cfg.max_queue_depth, 1)
        pending = sum(r.prompt_len for r in sv.queue._q)
        pending += sum(len(j.ids) - j.pos for j in sv._prefill_jobs)
        score += pending / max(sv.n_slots * sv.max_len, 1)
        return score

    def decode_score(self, cfg):
        """Decode-pool dispatch score: slot + paged-block occupancy only
        (a decode replica's queue holds just splices in flight — imminent
        slots, so they count toward batch fullness: a score blind to them
        would see a just-landed move as free capacity and the rebalancer
        would oscillate instead of settling inside the hysteresis band)."""
        sv = self.sv
        score = cfg.slot_weight * (len(sv._slots) + sv.queue.depth) \
            / max(sv.n_slots, 1)
        score += cfg.block_weight * sv.pool_mgr.occupancy()
        return score

    def pool_score(self, cfg):
        """The role-appropriate dispatch score."""
        if self.role == "prefill":
            return self.prefill_score(cfg)
        if self.role == "decode":
            return self.decode_score(cfg)
        return self.load_score(cfg)


class RouterMetrics:
    """Cross-replica counters + the Serving/router_* monitor events.

    ``snapshot()`` is the machine-readable rollup (the bench artifact's
    ``router`` block); ``emit_events`` writes the same numbers through the
    existing MonitorMaster fan-out — tier-1 asserts the two stay coherent
    (the PR 4 trace==metrics discipline, router edition)."""

    def __init__(self, router, monitor=None, interval=32):
        self._router = router
        self.monitor = monitor
        self.interval = max(int(interval), 1)
        self._loop_calls = 0
        self.routed = 0
        self.shed_saturated = 0
        self.session_hits = 0
        self.prefix_hits = 0
        self.prefix_lookups = 0
        self.rebalances = 0
        self.drains = 0
        self.rejoins = 0
        # failure recovery, each counted distinctly: cross-replica
        # re-dispatches after a replica death, unhealthy_slot retries on a
        # different replica, terminal replica_failed sheds, and the raw
        # fault counts the chaos schedule fired
        self.failovers = 0
        self.retries = 0
        self.shed_replica_failed = 0
        self.replica_kills = 0
        self.replica_stalls = 0
        # disaggregated fleet: completed first-token prefill->decode
        # handoffs, and live rebalance moves (voluntary mid-flight stream
        # migrations off hot replicas — distinct from ``rebalances``
        # above, which counts affinity overrides at ROUTING time)
        self.handoffs = 0
        self.pool_rebalances = 0
        # cumulative replica scheduler steps — the autoscaler acceptance
        # currency: a parked replica steps zero times, so a right-sized
        # fleet's total is strictly below an always-max static fleet's
        self.replica_steps = 0
        self.per_replica_routed = collections.Counter()
        self._events_emitted = 0
        # fleet-level SLO bookkeeping (emit intervals with >=1 violated
        # target, mirroring ServingMetrics.slo_violations per replica)
        self.slo_violations = 0

    @property
    def affinity_hit_rate(self):
        """Prefix-affinity hit rate: routed-by-prefix / prefix lookups."""
        return self.prefix_hits / self.prefix_lookups \
            if self.prefix_lookups else 0.0

    # ------------------------------------------------- fleet-merged rollups
    def fleet_digests(self):
        """Fleet latency digests: the EXACT merge of every replica's
        (integer bucket addition — associative, so the fleet percentile is
        independent of replica count and merge order)."""
        reps = self._router._replicas
        return {name: LatencyDigest.merged(
            [r.sv.metrics.latency_digests()[name] for r in reps])
            for name in ("ttft", "tpot", "queue_wait")}

    def fleet_goodput(self):
        """Fleet goodput: replica token counters summed (same currency)."""
        reps = self._router._replicas
        keys = ("prefill_device_tokens", "decode_tokens", "replay_tokens",
                "padding_tokens", "prefix_saved_tokens")
        tot = {k: sum(getattr(r.sv.metrics, k) for r in reps) for k in keys}
        total = tot["prefill_device_tokens"] + tot["decode_tokens"]
        wasted = tot["replay_tokens"] + tot["padding_tokens"]
        tot["wasted_tokens"] = wasted
        tot["goodput_frac"] = round((total - wasted) / total, 4) \
            if total else 1.0
        return tot

    def fleet_migration(self):
        """Fleet live-migration rollup: replica snapshot/splice counters
        summed, plus the router-side recovery counts — the ``resilience``
        block bench artifacts commit."""
        reps = self._router._replicas
        keys = ("kv_snapshots", "migrations_out", "migrations_in",
                "migrated_saved_tokens")
        out = {k: sum(getattr(r.sv.metrics, k) for r in reps) for k in keys}
        out["failovers"] = self.failovers
        out["retries"] = self.retries
        out["shed_replica_failed"] = self.shed_replica_failed
        out["replica_kills"] = self.replica_kills
        out["replica_stalls"] = self.replica_stalls
        return out

    def fleet_slo(self, digests=None):
        """``digests``: pass an already-merged ``fleet_digests()`` result to
        avoid re-merging (snapshot() runs on per-replica hooks)."""
        return evaluate_slo(
            self._router._slo.targets_ms() if self._router._slo is not None
            else {}, digests if digests is not None else self.fleet_digests())

    def fleet_tenancy(self):
        """Fleet per-tenant rollup: every replica's tenant counters summed
        and tenant digests exact-merged (same associative bucket addition
        as ``fleet_digests``), then graded against the tenant class's SLO
        targets — the ``tenancy`` block of fleet.json / bench artifacts."""
        reps = self._router._replicas
        merged = {}
        grader = None
        for r in reps:
            m = r.sv.metrics
            if m.tenants_cfg is not None:
                grader = m
            for tid, t in m.tenants.items():
                g = merged.get(tid)
                if g is None:
                    g = merged[tid] = {
                        "class": t["class"], "submitted": 0, "finished": 0,
                        "tokens": 0, "shed": collections.Counter(),
                        "ttft": LatencyDigest(), "tpot": LatencyDigest(),
                    }
                g["submitted"] += t["submitted"]
                g["finished"] += t["finished"]
                g["tokens"] += t["tokens"]
                g["shed"].update(t["shed"])
                g["ttft"].merge(t["ttft_digest"])
                g["tpot"].merge(t["tpot_digest"])
        if grader is None and reps:
            grader = reps[0].sv.metrics
        out = {}
        for tid in sorted(merged):
            g = merged[tid]
            digests = {"ttft": g["ttft"], "tpot": g["tpot"]}
            out[tid] = {
                "class": g["class"],
                "submitted": g["submitted"],
                "finished": g["finished"],
                "shed": dict(g["shed"]),
                "tokens": g["tokens"],
                "ttft_p99_ms": g["ttft"].quantile_ms(99),
                "tpot_p99_ms": g["tpot"].quantile_ms(99),
                "slo": evaluate_slo(
                    grader.tenant_slo_targets(g["class"]), digests),
            }
        return out

    def pool_rollup(self):
        """Per-pool topology rollup: routed counts, mean occupancy and the
        TTFT split by pool (a handed-off stream's first token fires on its
        PREFILL replica, so pool membership of the recording replica is
        the attribution) — the bench artifact's ``topology`` block."""
        reps = self._router._replicas
        to_ms = lambda v: None if v is None else v * 1e3
        out = {"enabled": self._router._pools_on,
               "roles": [r.role for r in reps]}
        for role in ("prefill", "decode", "mixed"):
            members = [r for r in reps if r.role == role]
            if not members:
                continue
            ttft = [s for r in members for s in r.sv.metrics.ttft_samples]
            out[role] = {
                "replicas": [r.idx for r in members],
                "routed": sum(self.per_replica_routed[r.idx]
                              for r in members),
                "occupancy": round(sum(
                    r.sv.pool_mgr.occupancy()
                    for r in members) / len(members), 4),
                "ttft_ms": {"p50": to_ms(percentile(ttft, 50)),
                            "p99": to_ms(percentile(ttft, 99))},
            }
        return out

    def snapshot(self):
        reps = self._router._replicas
        return {
            "replicas": len(reps),
            "routed": self.routed,
            "per_replica_routed": [self.per_replica_routed[i]
                                   for i in range(len(reps))],
            "per_replica_queue_depth": [r.sv.queue.depth for r in reps],
            "per_replica_active_slots": [len(r.sv._slots) for r in reps],
            "per_replica_occupancy": [
                round(r.sv.pool_mgr.occupancy(), 4) for r in reps],
            "draining": [i for i, r in enumerate(reps) if r.draining],
            "health": [r.health for r in reps],
            "migration": self.fleet_migration(),
            "session_hits": self.session_hits,
            "prefix_hits": self.prefix_hits,
            "prefix_lookups": self.prefix_lookups,
            "affinity_hit_rate": round(self.affinity_hit_rate, 4),
            "rebalances": self.rebalances,
            "drains": self.drains,
            "rejoins": self.rejoins,
            "shed_all_replicas_saturated": self.shed_saturated,
            # disaggregated topology: pool roles + the handoff/rebalance
            # counters (coherent with Serving/handoffs|rebalances events)
            "roles": [r.role for r in reps],
            "handoffs": self.handoffs,
            "pool_rebalances": self.pool_rebalances,
            "pools": self.pool_rollup(),
            "replica_steps": self.replica_steps,
        }

    def maybe_emit(self):
        """Rate-limited emit for the serve/step loops (every ``interval``
        scheduler rounds, mirroring ServingMetrics.monitor_interval)."""
        self._loop_calls += 1
        if self.monitor is not None and self._loop_calls % self.interval == 0:
            self.emit_events()

    def emit_events(self):
        """Serving/router_* scalars through the monitor fan-out — one event
        stream per scalar, per-replica queue depths suffixed _r<i>."""
        if self.monitor is None:
            return
        self._events_emitted += 1
        step = self._events_emitted
        snap = self.snapshot()
        events = [
            ("Serving/router_routed", float(snap["routed"]), step),
            ("Serving/router_affinity_hit_rate",
             float(snap["affinity_hit_rate"]), step),
            ("Serving/router_rebalances", float(snap["rebalances"]), step),
            ("Serving/router_drains", float(snap["drains"]), step),
            ("Serving/router_sheds",
             float(snap["shed_all_replicas_saturated"]), step),
            # fleet recovery scalars (live KV migration + failover): the
            # committed Serving/migrations / Serving/failovers streams
            ("Serving/migrations",
             float(snap["migration"]["migrations_in"]), step),
            ("Serving/failovers", float(snap["migration"]["failovers"]),
             step),
            ("Serving/router_retries",
             float(snap["migration"]["retries"]), step),
            ("Serving/router_shed_replica_failed",
             float(snap["migration"]["shed_replica_failed"]), step),
            # disaggregated topology: first-token handoffs + live rebalance
            # moves, the same numbers snapshot() reports (tier-1 coherence)
            ("Serving/handoffs", float(snap["handoffs"]), step),
            ("Serving/rebalances", float(snap["pool_rebalances"]), step),
        ]
        if snap["pools"]["enabled"]:
            for role in ("prefill", "decode"):
                pool = snap["pools"].get(role)
                if pool is None:
                    continue
                events.append((f"Serving/pool_{role}_routed",
                               float(pool["routed"]), step))
                events.append((f"Serving/pool_{role}_occupancy",
                               float(pool["occupancy"]), step))
        for i, depth in enumerate(snap["per_replica_queue_depth"]):
            events.append((f"Serving/router_r{i}_queue_depth", float(depth),
                           step))
        for i, occ in enumerate(snap["per_replica_occupancy"]):
            events.append((f"Serving/router_r{i}_occupancy", float(occ),
                           step))
        # fleet-merged digest P99s / goodput / SLO grade, same event names
        # as the per-replica cadence (this monitor sees the FLEET numbers —
        # the acceptance pin reads Serving/ttft_p99_ms here)
        goodput = self.fleet_goodput()
        events.extend(slo_digest_events(
            self.fleet_digests(), goodput["goodput_frac"],
            self._router._slo, step, tracer=self._router.tracer,
            counter=self))
        self.monitor.write_events(events)


class Router:
    """Load-aware dispatcher over N ``ServingEngine`` replicas."""

    def __init__(self, replicas, config=None, monitor=None, tracer=None):
        if not replicas:
            raise ValueError("Router needs at least one replica")
        self.cfg = config if config is not None else replicas[0].cfg.router
        self._replicas = [_Replica(sv, i) for i, sv in enumerate(replicas)]
        self._sessions = {}                        # session_id -> replica idx
        self._prefix_index = collections.OrderedDict()  # chain key -> idx
        self._rr_next = 0
        self._next_id = 0
        # failure recovery: in-flight request registry (request_id ->
        # (Request, replica idx)) so a replica death / unhealthy shed can
        # re-dispatch the actual Request object; entries drop as their
        # done events stream. Homogeneous-fleet knob like slo below.
        self._requests = {}
        self._retry_limit = int(replicas[0].cfg.retry_limit)
        self._chaos = []                          # (ReplicaEvent, ...) queue
        self._chaos_pos = 0
        # fleet SLO targets: the serving.slo block (homogeneous fleet — the
        # first replica's config speaks for all, like cfg.router above)
        self._slo = replicas[0].cfg.slo
        # disaggregated prefill/decode pools (serving.pools): the first
        # ``prefill_replicas`` indices prefill-to-first-token and hand off,
        # the rest decode — per-pool overrides applied per replica here
        # (the shared config object is never mutated)
        self._pools = replicas[0].cfg.pools
        self._pools_on = bool(self._pools.enabled)
        if self._pools_on:
            want = self._pools.prefill_replicas + self._pools.decode_replicas
            if want != len(self._replicas):
                raise ValueError(
                    f"serving.pools: prefill_replicas "
                    f"({self._pools.prefill_replicas}) + decode_replicas "
                    f"({self._pools.decode_replicas}) must equal the fleet "
                    f"size ({len(self._replicas)})")
            for rep in self._replicas:
                if rep.idx < self._pools.prefill_replicas:
                    rep.role = "prefill"
                    rep.sv.set_pool_role(
                        "prefill",
                        chunk_size=self._pools.prefill_chunk_size,
                        speculation=self._pools.prefill_speculation)
                else:
                    rep.role = "decode"
                    rep.sv.set_pool_role(
                        "decode",
                        chunk_size=self._pools.decode_chunk_size,
                        speculation=self._pools.decode_speculation)
        # live rebalancing (serving.rebalance): hysteresis-guarded actuator
        # over the migration machinery, evaluated on its own cadence
        self._rebalance_cfg = replicas[0].cfg.rebalance
        self._rebalance_calls = 0
        self._rebalance_next = 0.0   # cooldown gate (fleet-frontier time)
        # SLO-armed rebalance retarget: per-replica windowed burn sensors
        # (idx -> BurnSensor), consulted only when serving.slo is armed
        self._rebalance_sensors = {}
        self.metrics = RouterMetrics(self, monitor=monitor)
        self.tracer, self._fleet_dir = self._setup_tracing(tracer)
        self._rehome_replica_monitors()
        for rep in self._replicas:
            # per-replica snapshots gain the cross-replica view (coherent
            # with the Serving/router_* events, asserted tier-1)
            rep.sv.metrics.router = self.metrics.snapshot
        # SLO-driven autoscaling (serving.autoscaler): parks the fleet to
        # its floor NOW (drains are instant pre-traffic), then scales the
        # active set from the router loop — constructed last so the park
        # events land on live metrics/tracer state
        auto_cfg = replicas[0].cfg.autoscaler
        self._autoscaler = Autoscaler(self, auto_cfg) \
            if auto_cfg is not None and auto_cfg.enabled else None

    def _setup_tracing(self, tracer):
        """Arm fleet tracing when the replicas trace. Replicas built from
        one shared telemetry config all point at the SAME output dir (their
        flushes would clobber each other) — re-home each to
        ``<base>/replica<i>``, put the router's own decision stream at
        ``<base>/router``, and reserve ``<base>`` itself for the MERGED
        fleet files (trace.json / spans.jsonl / requests.jsonl /
        fleet.json, written by ``write_fleet_trace``). Replicas the caller
        pointed at DISTINCT dirs are deliberate — leave them untouched and
        skip the automatic fleet write (``write_fleet_trace(output_dir)``
        still merges on demand)."""
        from ..telemetry import SpanTracer

        dirs = [r.sv.tracer.output_dir for r in self._replicas
                if r.sv.tracer.enabled and r.sv.tracer.output_dir]
        # the fleet base (merged files + auto write) requires the common
        # shared-config case: every enabled tracer on ONE dir. Mixed
        # configs still get COLLIDING groups re-homed (same-path flushes
        # truncate each other) — just no automatic fleet dir.
        base = dirs[0] if dirs and len(set(dirs)) == 1 else None
        by_dir = {}
        for i, rep in enumerate(self._replicas):
            t = rep.sv.tracer
            if t.enabled and t.output_dir:
                by_dir.setdefault(t.output_dir, []).append((i, rep))
        for d, group in by_dir.items():
            if len(group) < 2 and base is None:
                continue  # unique dir in a mixed config: deliberate
            for i, rep in group:
                rep.sv.tracer.output_dir = os.path.join(d, f"replica{i}")
        if tracer is None:
            # the router's clock is the fleet frontier: route decisions
            # happen at the newest clock any replica has reached
            tracer = SpanTracer(
                enabled=bool(dirs), clock=self._frontier,
                output_path=base or "", job_name="router",
                chrome_trace=False, meta={"process": "router"})
        return tracer, base

    def _frontier(self):
        return max(r.sv.clock.now() for r in self._replicas)

    def _rehome_replica_monitors(self):
        """N replicas auto-built from ONE shared engine config each carry
        their own MonitorMaster over the SAME file paths: their Serving/*
        series would interleave in one CSV / scalars.jsonl with duplicate
        step counters. Re-home colliding file-backed backends to
        ``<path>/replica<i>`` (mirroring the tracer re-homing); writer-
        holding backends (TensorBoard/W&B) cannot be re-pointed — warn
        once. Distinct monitor OBJECTS only: a single master deliberately
        shared across replicas is left alone."""
        from ..monitor.monitor import CSVMonitor, TraceFileMonitor
        from ..utils.logging import logger

        by_path = {}
        unmovable = collections.Counter()
        for i, rep in enumerate(self._replicas):
            m = rep.sv.metrics.monitor
            for b in getattr(m, "backends", []):
                if not b.enabled:
                    continue
                if isinstance(b, CSVMonitor) and b.output_path:
                    by_path.setdefault(("csv", b.output_path), {})[id(b)] = \
                        (i, b)
                elif isinstance(b, TraceFileMonitor) and b.path:
                    by_path.setdefault(("scalars", b.path), {})[id(b)] = \
                        (i, b)
                elif type(b).__name__ in ("TensorBoardMonitor",
                                          "WandbMonitor"):
                    # writer-holding backends can't be re-pointed; a real
                    # collision means replicas share ONE engine config
                    # (deliberately-distinct configs don't warn)
                    unmovable[(type(b).__name__,
                               id(rep.sv.engine.config))] += 1
        for (kind, path), items in by_path.items():
            if len(items) < 2:
                continue
            for i, b in items.values():
                if kind == "csv":
                    b.output_path = os.path.join(path, f"replica{i}")
                    os.makedirs(b.output_path, exist_ok=True)
                else:
                    d = os.path.join(os.path.dirname(path), f"replica{i}")
                    os.makedirs(d, exist_ok=True)
                    b.path = os.path.join(d, "scalars.jsonl")
                    # fresh run, fresh stream (write_events appends): a
                    # rerun into the same dir must not concatenate two
                    # runs' series — TraceFileMonitor.__init__ truncates
                    # its original path for exactly this reason
                    open(b.path, "w").close()
        shared = max(unmovable.values(), default=0)
        if shared > 1:
            logger.warning(
                "Router: %d replicas write TensorBoard/W&B streams from one "
                "shared config; their Serving/* series will interleave — "
                "give replicas distinct job names or monitor at the router "
                "only", shared)

    # ------------------------------------------------------------- dispatch
    def submit(self, request):
        """Route one request to a replica (or shed it router-side).

        Returns the Request; ``state is REJECTED`` with ``reject_reason ==
        'all_replicas_saturated'`` means no live replica had queue room —
        the cross-replica generalization of ``queue_full``. Request-
        intrinsic sheds (``prompt_too_long``, ``no_free_blocks``) propagate
        from the chosen replica unchanged: a homogeneous fleet would shed
        them everywhere, so there is nothing to retry."""
        req = as_request(request)
        if req.request_id is None:
            # router-global ids: replicas must not hand out colliding ones
            req.request_id = self._next_id
            self._next_id += 1
        if req.trace_id is None:
            # fleet-global trace id: every span/instant on every replica
            # inherits it, so the merger stitches one cross-replica journey
            req.trace_id = f"req-{req.request_id:06d}"
        now = req.arrival_time if req.arrival_resolved else self._frontier()
        live = [i for i, r in enumerate(self._replicas)
                if not r.draining and not r.saturated and not r.dead]
        if not live:
            req.state = RequestState.REJECTED
            req.reject_reason = REJECT_ALL_REPLICAS_SATURATED
            self.metrics.shed_saturated += 1
            self.tracer.instant("route/shed", cat="router", ts=now,
                                request_id=req.request_id,
                                trace_id=req.trace_id,
                                reason=REJECT_ALL_REPLICAS_SATURATED)
            return req
        idx, decision = self._route(req, live)
        # the route/decision instant: full score breakdown + why this
        # replica — the wide event's "routing" block, recorded BEFORE the
        # replica touches the request so a replica-side shed still has it
        self.tracer.instant("route/decision", cat="router", ts=now,
                            request_id=req.request_id,
                            trace_id=req.trace_id, replica=idx, **decision)
        self._replicas[idx].sv.submit(req)
        if req.state is RequestState.REJECTED:
            # request-intrinsic shed (prompt_too_long / no_free_blocks):
            # not routed work — and registering its prefix/session would
            # build affinity toward blocks that never materialized
            return req
        self.metrics.routed += 1
        self.metrics.per_replica_routed[idx] += 1
        self._requests[req.request_id] = (req, idx)
        if req.session_id is not None and self.cfg.session_affinity:
            self._sessions[req.session_id] = idx
        self._register_prefix(req, idx)
        return req

    def _route(self, req, live):
        """Pick a replica index from ``live``: affinity target if healthy,
        else the load-policy choice (overriding affinity = a rebalance).
        Returns ``(index, decision)`` — the decision dict is the
        ``route/decision`` instant's score breakdown (per-replica load
        scores, affinity kind honored, rebalance flag), i.e. WHY this
        replica, postmortem-readable."""
        scores = {i: self._replicas[i].load_score(self.cfg) for i in live}
        # disaggregated pools: FRESH work dispatches into the prefill pool
        # (scored on queue depth + pending prompt tokens); affinity may
        # still pull it to ANY live replica — a decode-side prefix hit
        # routes there directly (suffix-only prefill, no handoff needed).
        # An all-dead/draining prefill pool degrades to the whole fleet.
        if self._pools_on:
            cands = [i for i in live
                     if self._replicas[i].role == "prefill"] or live
            pool_scores = {i: self._replicas[i].pool_score(self.cfg)
                           for i in cands}
        else:
            cands, pool_scores = live, scores
        decision = {"policy": self.cfg.policy,
                    "scores": {str(i): round(s, 6)
                               for i, s in scores.items()},
                    "affinity": None, "rebalanced": False}
        if self._pools_on:
            decision["pool_scores"] = {str(i): round(s, 6)
                                       for i, s in pool_scores.items()}
        if self.cfg.policy == "round_robin":
            # round_robin ignores load AND affinity (no lookups, no hit
            # counting) — it is the baseline the affinity/load policies are
            # measured against. Under pools it cycles the prefill pool.
            for _ in range(len(self._replicas)):
                cand = self._rr_next % len(self._replicas)
                self._rr_next += 1
                if cand in pool_scores:
                    self._note_pool(decision, cand)
                    return cand, decision
            self._note_pool(decision, cands[0])
            return cands[0], decision
        target = kind = None
        if self.cfg.session_affinity and req.session_id is not None:
            t = self._sessions.get(req.session_id)
            if t in scores:
                target, kind = t, "session"
        if target is None and self.cfg.prefix_affinity:
            target = self._prefix_lookup(req, scores)
            kind = "prefix" if target is not None else None
        best = min(cands, key=lambda i: (pool_scores[i], i))
        if target is not None:
            if scores[target] - scores[best] <= self.cfg.rebalance_margin:
                # hits count ONLY when the affinity target is actually used:
                # affinity_hit_rate means "routed by affinity", and a
                # rebalanced-away lookup must not inflate it
                if kind == "session":
                    self.metrics.session_hits += 1
                else:
                    self.metrics.prefix_hits += 1
                decision["affinity"] = kind
                self._note_pool(decision, target)
                return target, decision
            # affinity would pile onto an overloaded replica: rebalance
            self.metrics.rebalances += 1
            decision["rebalanced"] = True
            decision["affinity_overridden"] = kind
        self._note_pool(decision, best)
        return best, decision

    def _note_pool(self, decision, idx):
        if self._pools_on:
            decision["pool"] = self._replicas[idx].role

    def _prefix_lookup(self, req, scores):
        """Longest prefix-chain-key hit among live replicas (the paged
        pool's SHA-256 chain keys as the cross-replica currency)."""
        bs = self._chain_block_size()
        if bs is None or req.prompt_len <= bs:
            return None
        self.metrics.prefix_lookups += 1
        # longest-first: the deepest cached prefix wins (its replica saves
        # the most prefill). The hit counter moves in _route — a target
        # rebalanced away for load is a lookup, not a hit.
        keys = prefix_chain_keys(req.prompt, bs, req.prompt_len - 1)
        for key, _end in reversed(keys):
            idx = self._prefix_index.get(key)
            if idx is not None and idx in scores:
                self._prefix_index.move_to_end(key)
                return idx
        return None

    def _register_prefix(self, req, idx):
        """Record the request's full prompt blocks as living on ``idx``
        (last-writer-wins; bounded LRU)."""
        bs = self._chain_block_size()
        if bs is None or not self.cfg.prefix_affinity:
            return
        for key, _end in prefix_chain_keys(req.prompt, bs,
                                           req.prompt_len - 1):
            self._prefix_index[key] = idx
            self._prefix_index.move_to_end(key)
        while len(self._prefix_index) > self.cfg.prefix_index_cap:
            self._prefix_index.popitem(last=False)

    def _chain_block_size(self):
        """The chain-key granularity: the block size of the first replica
        that shares prefixes (None when none does)."""
        for r in self._replicas:
            if r.sv.cfg.kv_pool.prefix_cache:
                return r.sv.pool_mgr.block_size
        return None

    # ------------------------------------------------------ drain / rejoin
    def drain(self, idx, migrate=False):
        """Stop routing new work to replica ``idx``.

        ``migrate=False`` (wait-for-finish): in-flight requests keep
        decoding to completion (``drained(idx)`` turns True) — the safe
        moment to ``sv.destroy()`` for a restart. ``migrate=True``
        (drain-by-migration): every in-flight stream is captured as a
        FRESH snapshot and live-moved to a peer replica instead, so the
        replica empties after ONE evacuation pass and its restart loses
        zero computed tokens (a fresh snapshot splices with zero
        recompute). Voluntary moves never burn the retry budget. Returns
        the shed TokenEvents the evacuation produced (normally empty)."""
        rep = self._replicas[idx]
        if not rep.draining:
            rep.draining = True
            self.metrics.drains += 1
        if not migrate or rep.dead:
            return []
        moved = rep.sv.evacuate()
        started = [r for r in moved if r.tokens
                   or r.prefill_start_time is not None]
        started_ids = {id(r) for r in started}
        queued = [r for r in moved if id(r) not in started_ids]
        events = []
        # started streams land at their target's queue head — dispatch in
        # REVERSE seniority so successive push_fronts leave the most
        # senior request at the head
        for req in reversed(started):
            events.extend(self._failover(req, idx, "drain",
                                         count_retry=False))
        for req in queued:
            events.extend(self._failover(req, idx, "drain",
                                         count_retry=False))
        return events

    def kill_replica(self, idx):
        """Seeded fault surface: replica ``idx`` crashes NOW. Its device
        state is gone — no capture, no release — so affected requests fail
        over to survivors from their last periodic snapshot (splice + tail
        replay) or, with no snapshot, replay prompt + committed tokens as
        a chunkable resume prefill (counted as replay tokens in goodput).
        Each started re-dispatch burns one unit of the bounded retry
        budget (``serving.retry_limit``); the terminal fallback is a
        shed-with-reason ``replica_failed``. The dead replica's affinity
        state is purged so nothing routes toward vanished blocks. Returns
        the TokenEvents (terminal sheds) the failover produced."""
        rep = self._replicas[idx]
        if rep.dead:
            return []
        rep.health = "dead"
        rep.draining = True
        self.metrics.replica_kills += 1
        self.tracer.instant("replica/killed", cat="router",
                            ts=self._frontier(), replica=idx,
                            inflight=len(rep.sv._slots)
                            + len(rep.sv._prefill_jobs)
                            + rep.sv.queue.depth)
        for key in [k for k, v in self._prefix_index.items() if v == idx]:
            del self._prefix_index[key]
        for sid in [s for s, v in self._sessions.items() if v == idx]:
            del self._sessions[sid]
        affected = rep.sv.abandon_inflight()
        started = [r for r in affected if r.tokens
                   or r.prefill_start_time is not None]
        started_ids = {id(r) for r in started}
        queued = [r for r in affected if id(r) not in started_ids]
        events = []
        for req in reversed(started):
            events.extend(self._failover(req, idx, "replica_killed"))
        for req in queued:
            events.extend(self._failover(req, idx, "replica_killed"))
        return events

    def stall_replica(self, idx, duration):
        """Seeded fault surface: replica ``idx`` freezes for ``duration``
        seconds (a GC pause / preemptible-host interruption). Its clock
        jumps forward, so the conservative DES starves it until the rest
        of the fleet catches up — every co-resident request eats the
        latency, no state is lost. Health reads ``degraded`` until the
        fleet frontier passes the stall."""
        rep = self._replicas[idx]
        if rep.dead:
            return
        rep.sv.clock.sleep(float(duration))
        rep.stall_until = rep.sv.clock.now()
        rep.health = "degraded"
        self.metrics.replica_stalls += 1
        self.tracer.instant("replica/stalled", cat="router",
                            ts=self._frontier(), replica=idx,
                            duration=float(duration))

    def _update_health(self):
        """Degraded -> live once every surviving clock passed the stall."""
        alive = [r.sv.clock.now() for r in self._replicas if not r.dead]
        if not alive:
            return
        floor = min(alive)
        for rep in self._replicas:
            if rep.health == "degraded" and floor >= rep.stall_until:
                rep.health = "live"

    def _failover(self, req, from_idx, why, count_retry=True):
        """Re-dispatch one request off a dead (or migrating) replica.

        STARTED requests (committed tokens / prefill begun) are the
        expensive case: each involuntary move counts against
        ``serving.retry_limit`` (``count_retry``), the resume rng is
        re-derived (snapshot chain advanced host-side, or the insert-time
        chain key re-derived when no snapshot exists), and the request
        lands at the least-loaded survivor's QUEUE HEAD — committed
        tokens outrank queued arrivals, and ``push_front`` deliberately
        bypasses depth bounds. Queued-only requests re-route free through
        normal admission. Never goes through ``submit()``: that would
        reset ``submit_time`` and double-count ``record_submit``."""
        started = bool(req.tokens) or req.prefill_start_time is not None
        if started and count_retry:
            req.failovers += 1
            if req.failovers > self._retry_limit:
                return self._shed_failed(req, from_idx, "retry_limit")
        live = [i for i, r in enumerate(self._replicas)
                if r.health != "dead" and not r.draining]
        if not live:
            return self._shed_failed(req, from_idx, "no_live_replica")
        scores = {i: self._replicas[i].load_score(self.cfg) for i in live}
        if started:
            # disaggregated pools: a started stream is decode work — it
            # recovers into the decode pool (any survivor when none lives)
            target = min(self._pool_candidates(live, "decode"),
                         key=lambda i: (scores[i], i))
            sv = self._replicas[target].sv
            snap = req.migration
            if req.tokens:
                if snap is not None and len(req.tokens) >= len(snap.tokens):
                    # re-join the original rng chain at the current commit
                    # point: the tokens since the capture replay as
                    # teacher-forced prefill
                    req.resume_rng = advance_rng(
                        snap.rng, len(req.tokens) - len(snap.tokens))
                elif req.resume_rng is None:
                    req.resume_rng = sv.chain_key_for_resume(req)
            req.slot = None
            req.state = RequestState.QUEUED
            req.reject_reason = None
            req.finish_reason = None
            sv.queue.push_front(req)
            if count_retry:
                self.metrics.failovers += 1
        else:
            candidates = [i for i in live
                          if not self._replicas[i].saturated]
            if not candidates:
                return self._shed_failed(req, from_idx, "all_saturated")
            # a queued request still owes its whole prefill: prefill pool
            target = min(self._pool_candidates(candidates, "prefill"),
                         key=lambda i: (scores[i], i))
            sv = self._replicas[target].sv
            reason = sv.queue.admit(req, sv.max_len,
                                    kv_fits=sv.pool_mgr.fits_ever)
            if reason is not None:
                return self._shed_failed(req, from_idx, reason)
        self._requests[req.request_id] = (req, target)
        self.tracer.instant("route/failover", cat="router",
                            ts=self._frontier(), request_id=req.request_id,
                            trace_id=req.trace_id, replica=from_idx,
                            target=target, why=why, started=started,
                            n_tokens=len(req.tokens),
                            snapshot=req.migration is not None,
                            failovers=req.failovers)
        return []

    def _shed_failed(self, req, from_idx, why):
        """Terminal failover fallback: shed with reason ``replica_failed``
        (budget spent / no survivor with room). Router-side count, like
        ``all_replicas_saturated``."""
        req.state = RequestState.REJECTED
        req.reject_reason = REJECT_REPLICA_FAILED
        req.finish_reason = None
        req.slot = None
        self.metrics.shed_replica_failed += 1
        self._requests.pop(req.request_id, None)
        now = self._frontier()
        self.tracer.instant("route/shed", cat="router", ts=now,
                            request_id=req.request_id,
                            trace_id=req.trace_id,
                            reason=REJECT_REPLICA_FAILED, detail=why,
                            replica=from_idx)
        return [TokenEvent(req.request_id, -1, len(req.tokens), True,
                           f"rejected:{REJECT_REPLICA_FAILED}", now)]

    def _retry_unhealthy(self, req, from_idx):
        """Satellite of the failover machinery: an ``unhealthy_slot`` shed
        on a multi-replica fleet retries ONCE (bounded by
        ``serving.retry_limit``) on a DIFFERENT replica before the shed
        becomes terminal — the poisoned prefill fired before the first
        token streamed, so nothing user-visible rewinds. Returns True
        (event swallowed, fleet will finish the request), a list of
        terminal shed events, or None (no candidate: the original
        unhealthy event stands)."""
        live = [i for i, r in enumerate(self._replicas)
                if i != from_idx and r.health == "live" and not r.draining
                and not r.saturated]
        if not live:
            return None
        req.reset_for_retry()
        req.retries += 1
        self.metrics.retries += 1
        scores = {i: self._replicas[i].load_score(self.cfg) for i in live}
        # the poisoned prefill never streamed a token: it is prefill work
        target = min(self._pool_candidates(live, "prefill"),
                     key=lambda i: (scores[i], i))
        sv = self._replicas[target].sv
        reason = sv.queue.admit(req, sv.max_len,
                                kv_fits=sv.pool_mgr.fits_ever)
        if reason is not None:
            return self._shed_failed(req, from_idx, reason)
        self._requests[req.request_id] = (req, target)
        self.tracer.instant("route/retry", cat="router",
                            ts=self._frontier(), request_id=req.request_id,
                            trace_id=req.trace_id,
                            reason=FINISH_UNHEALTHY, replica=from_idx,
                            target=target, retries=req.retries)
        return True

    def _pool_candidates(self, live, role):
        """Restrict ``live`` to the given pool under disaggregation; the
        whole list when pools are off or the pool has no live member (a
        decode-pool wipeout degrades to mixed service, never to an outage)."""
        if not self._pools_on:
            return live
        return [i for i in live if self._replicas[i].role == role] or live

    # -------------------------------------------- first-token handoff
    def _handoff(self, req, from_idx):
        """Move a stream that just committed its FIRST token off its
        prefill replica into the decode pool: capture a fresh snapshot
        (partial tail block included — zero recompute on splice, and
        delta-to-capture is 0 so the rng chain passes through unchanged:
        the decode replica's stream is bitwise the prefill replica's
        continuation), free the prefill slot (it re-admits the next prompt
        immediately — the TTFT win), and queue-head the request at the
        least-occupied decode replica. A handoff failure is not terminal:
        with no live decode replica the stream simply keeps decoding where
        it is, and a target that dies mid-splice recovers through the
        normal failover path (the request carries the snapshot)."""
        decode = [i for i, r in enumerate(self._replicas)
                  if r.role == "decode" and r.health != "dead"
                  and not r.draining]
        if not decode:
            return False
        target = min(decode,
                     key=lambda i: (self._replicas[i].decode_score(self.cfg),
                                    i))
        rep = self._replicas[from_idx]
        if not rep.sv.evacuate_request(req, instant="request/handoff_out"):
            return False
        req.handoff_pending = True
        now = rep.sv.clock.now()
        self._push_started(req, target, now)
        # the decode replica now owns the stream's blocks: future
        # identical prompts route straight to it (cross-pool dedupe —
        # prefix affinity both directions)
        self._register_prefix(req, target)
        self.metrics.handoffs += 1
        self.tracer.instant("route/handoff", cat="router", ts=now,
                            request_id=req.request_id,
                            trace_id=req.trace_id, replica=from_idx,
                            target=target, n_tokens=len(req.tokens))
        return True

    def _push_started(self, req, target, now):
        """Land a moved started stream at ``target``'s queue head.
        Causality under the DES: an IDLE target's clock may lag the move
        instant — idle time passes before the splice can land (a busy
        target's skew is already bounded by the laggard-first stepping)."""
        rep = self._replicas[target]
        if not rep.busy:
            gap = now - rep.sv.clock.now()
            if gap > 0:
                rep.sv.clock.sleep(gap)
        rep.sv.queue.push_front(req)
        self._requests[req.request_id] = (req, target)

    # -------------------------------------------------- live rebalancing
    def _move_delta(self, hot, cold, req):
        """Predicted total score shift of moving ``req`` hot -> cold: the
        slot term leaves one side and lands on the other, and the stream's
        blocks migrate between the pools. The overshoot guard compares the
        measured gap against this BEFORE moving — the units are the same
        (both are load-score points), so the comparison is exact up to
        on-demand pool growth."""
        d = self.cfg.slot_weight * (1.0 / max(hot.sv.n_slots, 1)
                                    + 1.0 / max(cold.sv.n_slots, 1))
        blocks = -(-(req.prompt_len + len(req.tokens))
                   // hot.sv.pool_mgr.block_size)
        d += self.cfg.block_weight * blocks * (
            1.0 / max(hot.sv.pool_mgr.n_blocks, 1)
            + 1.0 / max(cold.sv.pool_mgr.n_blocks, 1))
        return d

    def _maybe_rebalance(self):
        """The bounded, hysteresis-guarded rebalance trigger (serving.
        rebalance): when the hottest decode replica's score exceeds the
        coldest's by more than ``min_gain``, migrate up to
        ``max_concurrent`` longest-tail streams hot -> cold, then cool
        down. Thrash-proof by construction: a stream moves only when the
        measured gap ALSO exceeds its predicted score shift minus
        ``min_gain`` (the overshoot guard — the post-move REVERSE gap
        ``delta - gap`` stays strictly inside the hysteresis band, so the
        move itself can never arm the opposite trigger; only an external
        load change can), moves stop the moment the RE-MEASURED gap falls
        inside the band, every trigger is followed by a ``cooldown``
        window, and voluntary moves never burn the retry budget."""
        cfg = self._rebalance_cfg
        if not cfg.enabled:
            return
        self._rebalance_calls += 1
        if self._rebalance_calls % cfg.interval:
            return
        now = self._frontier()
        if now < self._rebalance_next:
            return
        cands = [r for r in self._replicas
                 if r.health == "live" and not r.draining
                 and (not self._pools_on or r.role == "decode")]
        if len(cands) < 2:
            return
        score = lambda r: r.decode_score(self.cfg)
        if self._slo is not None and self._slo.armed:
            # SLO-armed retarget: hot/cold selection scores each replica
            # by its WINDOWED burn contribution (the latency damage it is
            # doing to the fleet SLO right now), decode occupancy only
            # breaking ties — a replica can sit at modest occupancy yet
            # burn the budget (long-tail streams), and it is the one worth
            # unloading. A move still requires a strictly positive
            # occupancy gap toward the cold replica and passes the same
            # per-stream overshoot guard below, so the no-thrash argument
            # carries over: the guard bounds every move's reverse gap
            # inside the hysteresis band regardless of how hot/cold were
            # chosen, and burn windows re-baseline per evaluation.
            targets = self._slo.targets_ms()
            burns = {}
            for r in cands:
                sensor = self._rebalance_sensors.setdefault(
                    r.idx, BurnSensor())
                burns[r.idx] = sensor.update(
                    targets, r.sv.metrics.latency_digests())
            hot = max(cands, key=lambda r: (burns[r.idx], score(r), r.idx))
            cold = min(cands, key=lambda r: (burns[r.idx], score(r), r.idx))
            if hot is cold or burns[hot.idx] <= burns[cold.idx]:
                return  # no burn differential: nothing to unload
            gap_floor = 0.0   # burn triggered the move; any headroom helps
            if score(hot) - score(cold) <= gap_floor:
                return  # the cold replica has no spare capacity to absorb
        else:
            gap_floor = cfg.min_gain
            hot = max(cands, key=lambda r: (score(r), r.idx))
            cold = min(cands, key=lambda r: (score(r), r.idx))
            if hot is cold or score(hot) - score(cold) <= cfg.min_gain:
                return
        # longest-tail first: the streams with the most decode left
        # amortize the splice cost best (and vacate the most future work)
        streams = sorted(
            (r for r in hot.sv._slots.values() if r.tokens),
            key=lambda r: r.max_new_tokens - len(r.tokens), reverse=True)
        moved = 0
        for req in streams:
            gap = score(hot) - score(cold)
            if moved >= cfg.max_concurrent or gap <= gap_floor:
                break
            if gap <= self._move_delta(hot, cold, req) - cfg.min_gain:
                # overshoot guard: this stream is heavy enough that moving
                # it would swing the pair past equality by more than the
                # hysteresis band and re-trigger in reverse — a lighter
                # stream further down the tail may still fit
                continue
            if not hot.sv.evacuate_request(req):
                continue
            req.rebalances += 1
            self._push_started(req, cold.idx, now)
            self._register_prefix(req, cold.idx)
            self.metrics.pool_rebalances += 1
            self.tracer.instant("route/rebalance", cat="router", ts=now,
                                request_id=req.request_id,
                                trace_id=req.trace_id, replica=hot.idx,
                                target=cold.idx, n_tokens=len(req.tokens),
                                remaining=req.max_new_tokens
                                - len(req.tokens))
            moved += 1
        if moved:
            self._rebalance_next = now + cfg.cooldown

    def _filter_events(self, idx, raw):
        """Every replica step's events pass through here: unhealthy_slot
        sheds get the cross-replica retry (swallowed on success — the
        consumer never sees a request fail that the fleet then finishes),
        a prefill replica's FIRST-token events trigger the prefill->decode
        handoff, and finished requests leave the in-flight registry."""
        out = []
        prefill_side = self._pools_on \
            and self._replicas[idx].role == "prefill"
        for ev in raw:
            if ev.finish_reason == FINISH_UNHEALTHY:
                entry = self._requests.get(ev.request_id)
                req = entry[0] if entry is not None else None
                if req is not None and not req.tokens \
                        and req.retries < self._retry_limit:
                    res = self._retry_unhealthy(req, idx)
                    if res is True:
                        continue
                    if res is not None:
                        out.extend(res)
                        continue
            if prefill_side and not ev.done and ev.index == 0:
                # first token committed on the prefill side: hand the
                # stream off (the event itself still streams — the token
                # is committed; only the REST of the decode moves)
                entry = self._requests.get(ev.request_id)
                if entry is not None and entry[1] == idx:
                    self._handoff(entry[0], idx)
            if ev.done:
                self._requests.pop(ev.request_id, None)
            out.append(ev)
        return out

    # ------------------------------------------------------- chaos schedule
    def apply_chaos(self, schedule):
        """Arm a seeded replica-level fault schedule
        (``testing.fault_injection.ReplicaChaosSchedule`` or any iterable
        of ``(time, kind, replica, duration)``): events fire inside the
        serve/step loops when the fleet frontier reaches their instant —
        same seed, same schedule, same recovery, deterministically."""
        events = getattr(schedule, "events", schedule)
        self._chaos = sorted(tuple(e) for e in events)
        self._chaos_pos = 0

    def _fire_chaos(self):
        """Fire every armed fault whose instant the frontier has reached;
        returns the terminal shed TokenEvents the failovers produced."""
        out = []
        while self._chaos_pos < len(self._chaos):
            t, kind, idx, duration = self._chaos[self._chaos_pos]
            if self._frontier() < t:
                break
            self._chaos_pos += 1
            if self._replicas[idx].dead:
                continue
            if kind == "kill":
                out.extend(self.kill_replica(idx))
            elif kind == "stall":
                self.stall_replica(idx, duration)
        return out

    def pull_queued(self, from_idx, to_idx, n):
        """Move up to ``n`` not-yet-started requests from the TAIL of
        replica ``from_idx``'s queue onto replica ``to_idx`` (relative
        order preserved). The autoscaler's scale-up companion: queued
        requests were routed before the new capacity existed — without the
        pull a rejoined standby idles while the hot queue drains one
        prefill per step. Tail-side so preemption returners and senior
        arrivals keep their position; admission control is bypassed like
        ``push_front`` (the requests already passed it at submit). Returns
        the number of requests moved."""
        src = self._replicas[from_idx].sv
        dst_rep = self._replicas[to_idx]
        moved = []
        for _ in range(max(int(n), 0)):
            if not len(src.queue) or src.queue.peek_at(
                    len(src.queue) - 1).admit_time is not None:
                break  # never pull a preemption returner off its replica
            moved.append(src.queue.pop_at(len(src.queue) - 1))
        if not moved:
            return 0
        now = self._frontier()
        # an idle target's clock may lag the move (cf. _push_started)
        if not dst_rep.busy:
            gap = now - dst_rep.sv.clock.now()
            if gap > 0:
                dst_rep.sv.clock.sleep(gap)
        for req in reversed(moved):   # popped back-to-front: re-append in order
            dst_rep.sv.queue._q.append(req)
            self._requests[req.request_id] = (req, to_idx)
        self.tracer.instant("route/pull_queued", cat="router", ts=now,
                            replica=from_idx, target=to_idx,
                            moved=len(moved))
        return len(moved)

    def drained(self, idx):
        """True once the draining replica has no in-flight work left."""
        return not self._replicas[idx].busy

    def rejoin(self, idx, engine=None):
        """Re-admit replica ``idx``. ``engine``: a replacement ServingEngine
        after a restart — its pool is empty, so the router purges the
        replica's prefix-index entries and session stickiness (stale
        affinity would route cache misses at it)."""
        rep = self._replicas[idx]
        if engine is not None:
            rep.sv = engine
            engine.metrics.router = self.metrics.snapshot
            for key in [k for k, v in self._prefix_index.items() if v == idx]:
                del self._prefix_index[key]
            for sid in [s for s, v in self._sessions.items() if v == idx]:
                del self._sessions[sid]
        elif rep.dead:
            raise ValueError(
                f"rejoin({idx}): a killed replica's device state is gone — "
                "pass a replacement engine")
        rep.draining = False
        rep.health = "live"
        rep.stall_until = 0.0
        self.metrics.rejoins += 1

    # ------------------------------------------------------------- the loop
    def step(self):
        """One scheduler step on every busy replica (the wall-clock /
        manual-driving path). Returns the concatenated TokenEvents."""
        events = list(self._fire_chaos())
        self._update_health()
        self._maybe_rebalance()
        if self._autoscaler is not None:
            self._autoscaler.maybe_scale()
        for rep in self._replicas:
            if rep.busy and not rep.dead:
                self.metrics.replica_steps += 1
                events.extend(self._filter_events(rep.idx, rep.sv.step()))
        self.metrics.maybe_emit()
        return events

    def serve(self, requests=None, yield_rejections=True):
        """Streaming frontend over the fleet: feed ``requests`` (each
        optionally carrying an ``arrival_time`` offset) through the router,
        yielding TokenEvents as replicas produce them.

        Under virtual clocks this is a conservative discrete-event
        simulation of N PARALLEL replicas: each replica advances its own
        clock by its own work, and the router always steps the busy replica
        whose local clock is furthest behind, dispatching arrivals due by
        that horizon first. Makespan is ``max`` over replica clocks, not the
        sum — which is what makes least-loaded measurably beat round-robin
        in tier-1. With wall clocks every busy replica steps each loop."""
        pending = sorted((as_request(r) for r in (requests or [])),
                         key=lambda r: r.arrival_time or 0.0)
        virtual = all(isinstance(r.sv.clock, VirtualClock)
                      for r in self._replicas)
        t0 = max(r.sv.clock.now() for r in self._replicas)
        for r in pending:
            if not r.arrival_resolved:
                r.arrival_time = t0 + (r.arrival_time or 0.0)
                r.arrival_resolved = True
            elif r.arrival_time is None:
                r.arrival_time = t0
        try:
            while pending or any(r.busy and not r.dead
                                 for r in self._replicas):
                # armed faults fire at the frontier BEFORE new work lands:
                # a killed replica's failovers re-dispatch first, so this
                # round's routing already sees the shrunken fleet
                for ev in self._fire_chaos():
                    yield ev
                self._update_health()
                self._maybe_rebalance()
                if self._autoscaler is not None:
                    self._autoscaler.maybe_scale()
                busy = [r for r in self._replicas if r.busy and not r.dead]
                if busy:
                    horizon = min(r.sv.clock.now() for r in busy)
                else:
                    horizon = pending[0].arrival_time if pending else None
                while pending and horizon is not None \
                        and pending[0].arrival_time <= horizon:
                    for ev in self._dispatch(pending.pop(0),
                                             yield_rejections):
                        yield ev
                    busy = [r for r in self._replicas
                            if r.busy and not r.dead]
                if not busy:
                    if not pending:
                        break
                    # everyone idle: jump to the next arrival
                    self._catch_up_all(pending[0].arrival_time)
                    continue
                if virtual:
                    # advance the laggard one step: no replica's clock ever
                    # runs ahead of another's un-simulated past
                    rep = min(busy, key=lambda r: r.sv.clock.now())
                    self.metrics.replica_steps += 1
                    for ev in self._filter_events(rep.idx, rep.sv.step()):
                        yield ev
                else:
                    for rep in busy:
                        self.metrics.replica_steps += 1
                        for ev in self._filter_events(rep.idx,
                                                      rep.sv.step()):
                            yield ev
                self.metrics.maybe_emit()
        finally:
            # serve() completing (or dying) is the fleet's terminal edge:
            # flush EVERY tracer (replica tail spans would otherwise only
            # land at destroy()) and force one final metrics interval —
            # the rate-limited maybe_emit cadence must not swallow a short
            # run's only (or last) window of events
            for rep in self._replicas:
                rep.sv.tracer.flush()
                rep.sv.metrics.emit_events()
            self.metrics.emit_events()
            self.tracer.flush()
            if self._fleet_dir is not None:
                self.write_fleet_trace()

    def _dispatch(self, req, yield_rejections):
        # an idle target's clock may lag the arrival: idle time passes
        req = as_request(req)
        self._catch_up_idle(req.arrival_time)
        routed = self.submit(req)
        if routed.state is RequestState.REJECTED and yield_rejections:
            now = req.arrival_time if req.arrival_time is not None else 0.0
            return [TokenEvent(routed.request_id, -1, -1, True,
                               f"rejected:{routed.reject_reason}", now)]
        return []

    def _catch_up_idle(self, t):
        if t is None:
            return
        for rep in self._replicas:
            if not rep.busy:
                gap = t - rep.sv.clock.now()
                if gap > 0:
                    rep.sv.clock.sleep(gap)

    def _catch_up_all(self, t):
        for rep in self._replicas:
            gap = t - rep.sv.clock.now()
            if gap > 0:
                rep.sv.clock.sleep(gap)

    def run(self, requests):
        """Non-streaming convenience: serve to completion and return
        ``(finished, rejected, snapshot)`` (cf. ``ServingEngine.run``)."""
        reqs = [as_request(r) for r in (requests or [])]
        for _ in self.serve(reqs, yield_rejections=False):
            pass
        finished = [r for r in reqs if r.state is RequestState.FINISHED]
        rejected = [r for r in reqs if r.state is RequestState.REJECTED]
        return finished, rejected, self.snapshot()

    # -------------------------------------------------------------- rollups
    def snapshot(self):
        """Fleet rollup: the router block plus per-replica ServingMetrics
        snapshots and aggregate latency percentiles."""
        reps = [r.sv.metrics.snapshot() for r in self._replicas]
        ttft = [s for r in self._replicas
                for s in r.sv.metrics.ttft_samples]
        tpot = [s for r in self._replicas
                for s in r.sv.metrics.tpot_samples]
        to_ms = lambda v: None if v is None else v * 1e3
        digests = self.metrics.fleet_digests()
        return {
            "router": self.metrics.snapshot(),
            "replicas": reps,
            "finished": sum(r["finished"] for r in reps),
            "total_tokens": sum(r["total_tokens"] for r in reps),
            "ttft_ms": {"p50": to_ms(percentile(ttft, 50)),
                        "p99": to_ms(percentile(ttft, 99))},
            "tpot_ms": {"p50": to_ms(percentile(tpot, 50)),
                        "p99": to_ms(percentile(tpot, 99))},
            # fleet-merged streaming digests: percentile rollup + the raw
            # bucket snapshots (so fleet.json readers can rebuild and
            # compare digests exactly), the SLO grade, goodput accounting
            "percentiles": {name + "_ms": d.percentiles_ms()
                            for name, d in digests.items()},
            "digests": {name: d.snapshot() for name, d in digests.items()},
            "slo": self.metrics.fleet_slo(digests),
            "goodput": self.metrics.fleet_goodput(),
            # multi-tenant QoS: fleet-merged per-tenant counters/digests/
            # grades, plus the autoscaler's scale-event timeline (both
            # blocks always present so artifact readers need no probing)
            "tenancy": self.metrics.fleet_tenancy(),
            "autoscaler": self._autoscaler.snapshot()
            if self._autoscaler is not None else {"enabled": False},
            # >0 means the live digests were restarted mid-run (warmup
            # exclusion) and no longer cover the whole trace
            "window_resets": sum(r.sv.metrics.window_resets
                                 for r in self._replicas),
            "makespan": max(r.sv.clock.now() for r in self._replicas),
        }

    def write_fleet_trace(self, output_dir=None):
        """Merge the router + per-replica span streams into the fleet dir
        (``telemetry/fleet.py``): Chrome ``trace.json`` with one process
        lane per source, merged ``spans.jsonl``, per-request wide events
        (``requests.jsonl``) and the live ``fleet.json`` rollup. Defaults
        to the telemetry base dir the replicas were re-homed under."""
        out = output_dir if output_dir is not None else self._fleet_dir
        if out is None:
            raise ValueError(
                "no fleet output dir: enable telemetry on the replicas or "
                "pass output_dir")
        from ..telemetry.fleet import write_fleet_trace

        sources = [("router", self.tracer.events)]
        sources += [(f"replica{i}", rep.sv.tracer.events)
                    for i, rep in enumerate(self._replicas)]
        return write_fleet_trace(out, sources, fleet=self.snapshot())

    def compile_counts(self):
        return [r.sv.compile_counts() for r in self._replicas]

    def destroy(self):
        self.tracer.flush()
        for rep in self._replicas:
            rep.sv.destroy()
