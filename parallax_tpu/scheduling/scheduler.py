"""The global scheduler orchestrator.

Capability parity: reference ``src/scheduling/scheduler.py:29-649`` — event
queues for join/leave/update, bootstrap gating on a minimum node count,
heartbeat timeout sweeping, request dispatch, and serialized global
rebalance on topology changes.

Threading model mirrors the reference: one event thread owns all topology
mutations; a dispatch thread assigns routing tables; callers only enqueue.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable

from parallax_tpu.config import ModelConfig
from parallax_tpu.scheduling.layer_allocation import (
    BaseLayerAllocator,
    DPLayerAllocator,
    GreedyLayerAllocator,
)
from parallax_tpu.scheduling.node import Node
from parallax_tpu.scheduling.node_management import NodeManager, NodeState, Pipeline
from parallax_tpu.scheduling.request_routing import (
    RequestMeta,
    RoutingStrategy,
    make_router,
)
from parallax_tpu.utils import get_logger
from parallax_tpu.utils.hw import HardwareInfo
from parallax_tpu.analysis.sanitizer import make_lock
from parallax_tpu.obs import names as mnames

logger = get_logger(__name__)


@dataclasses.dataclass
class PendingRequest:
    request_id: str
    # Routing context (tokenized prompt for prefix-digest matching);
    # None keeps the pre-meta behavior for internal callers.
    meta: "RequestMeta | None" = None
    enqueue_time: float = dataclasses.field(default_factory=time.monotonic)
    # The dispatcher retries routing until this deadline before giving up
    # (reference RequestHandler retry ladder, request_handler.py:100-245).
    deadline: float = dataclasses.field(
        default_factory=lambda: time.monotonic() + 10.0
    )
    # Filled by the dispatcher.
    path_ids: list[str] | None = None
    event: threading.Event = dataclasses.field(default_factory=threading.Event)
    # Set by a caller that gave up waiting; the dispatcher then drops the
    # request instead of charging load for a path nobody will use.
    cancelled: bool = False


class GlobalScheduler:
    """Assigns layers to nodes and node paths to requests."""

    # Heartbeat-sweep probation: a node whose last beat reported an
    # in-progress engine reload/compile gets this multiple of the base
    # timeout before _handle_leave fires (first-compile storms on fresh
    # joins must not be declared dead) ...
    BUSY_GRACE_FACTOR = 5.0
    # ... while a node a peer's async sender reported unreachable gets
    # this FRACTION of it (floored at one sweep period) — the report is
    # evidence, a missing heartbeat on top of it is confirmation.
    PEER_DOWN_GRACE_FACTOR = 0.25

    def __init__(
        self,
        model: ModelConfig,
        min_nodes_bootstrapping: int = 1,
        allocator: str = "greedy",
        routing: str = "rr",
        heartbeat_timeout_s: float = 30.0,
        routing_kwargs: dict | None = None,
        slo: "SLOConfig | None" = None,
        qos: "QoSConfig | None" = None,
        passive: bool = False,
    ):
        self.model = model
        # Scheduler HA (parallax_tpu/ha, docs/ha.md): ``epoch`` rides
        # heartbeat replies and fences a revived old primary; a
        # ``passive`` scheduler is a warm-standby mirror — its event/
        # dispatch threads stay parked and the service refuses mutating
        # RPCs until StandbyScheduler.promote() flips it active; a
        # ``fenced`` scheduler saw proof (a worker echoing a higher
        # epoch) that a standby promoted past it and refuses to mutate.
        self.epoch = 1
        self.passive = passive
        self.fenced = False
        # Installed by ha.journal.install_journal; None = HA off (every
        # _journal() hook is a no-op).
        self.journal = None
        self._journaled_pipelines = None
        self.min_nodes = min_nodes_bootstrapping
        self.manager = NodeManager(model.num_hidden_layers)
        alloc_cls: type[BaseLayerAllocator] = (
            GreedyLayerAllocator if allocator == "greedy" else DPLayerAllocator
        )
        self.allocator = alloc_cls(model.num_hidden_layers)
        self.routing_name = routing
        self.routing_kwargs = dict(routing_kwargs or {})
        self.router: RoutingStrategy = make_router(
            routing, self.manager, **self.routing_kwargs
        )
        self.heartbeat_timeout_s = heartbeat_timeout_s
        self.bootstrapped = threading.Event()
        # rid -> (predicted cached tokens, prompt tokens): dispatch-time
        # predictions awaiting the head's request_complete actuals
        # (bounded — an abandoned request must not leak an entry).
        from collections import OrderedDict

        self._predictions: OrderedDict[str, tuple[int, int]] = OrderedDict()
        self._predictions_cap = 4096
        # Aggregate predicted-vs-actual hit telemetry (cluster_status
        # "routing" section + the metrics registry).
        self.routing_accuracy = {
            "requests": 0, "predicted_tokens": 0, "actual_tokens": 0,
            "abs_error_tokens": 0,
        }

        self._events: queue.Queue = queue.Queue()
        self._requests: queue.Queue[PendingRequest] = queue.Queue()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # node_id -> callback payload for the next heartbeat reply
        # (layer reallocations are piggybacked on heartbeats, reference
        # p2p/server.py announcer).
        self._lock = make_lock("scheduling.scheduler", reentrant=True)
        self.refit_version = 0
        self.refit_index: dict[str, str] = {}
        # Live migration: rid -> the head node now serving it (reported
        # by targets via ``migration_done``); pollers that lost their
        # head ask ``where_is`` here before falling back to a client
        # resume. Bounded — finished requests age out of the LRU.
        self._migrations: "OrderedDict[str, str]" = OrderedDict()
        self.migration_stats = {"drains": 0, "targets_chosen": 0,
                                "recorded": 0}
        # Disaggregation handoff telemetry (docs/disaggregation.md):
        # decode-pool target queries from prefill heads, targets chosen,
        # and queries that found no serviceable decode/mixed pipeline
        # (the head then keeps the request local).
        self.disagg_stats = {"target_queries": 0, "targets_chosen": 0,
                             "no_target": 0}
        # Cluster event timeline (obs/timeline.py): workers ship
        # sequence-numbered flight-event batches in heartbeats; the ring
        # merges them — plus the scheduler's own join/leave/peer_down
        # decisions — into one causally-ordered swarm story served at
        # /debug/timeline.
        from parallax_tpu.obs.timeline import ClusterTimeline

        self.timeline = ClusterTimeline()
        # SLO tracker (obs/slo.py): declarative TTFT/TPOT/availability
        # objectives evaluated over the cluster-merged histograms each
        # time cluster_status() runs (the status stream's poll cadence
        # is the sampling cadence). None = no objectives declared.
        self.slo_tracker = None
        if slo is not None:
            from parallax_tpu.obs.slo import SLOTracker

            self.slo_tracker = SLOTracker(slo)
        # Multi-tenant QoS control plane (parallax_tpu/qos, docs/qos.md):
        # the cluster-scope admission controller watches the merged
        # per-class TTFT histograms workers ship in heartbeats and
        # relays its shed verdict back through heartbeat replies
        # (``qos_shed``); the pool autoscaler re-roles pipelines between
        # the prefill/decode pools from queue depth + goodput-per-chip.
        # Both tick on the event thread. None = QoS off (no work, no
        # reply fields).
        self.qos_config = qos
        self.qos_controller = None
        self.autoscaler = None
        self._qos_last_sample = 0.0
        if qos is not None:
            from parallax_tpu.qos import AdmissionController, PoolAutoscaler

            self.qos_controller = AdmissionController(qos, scope="cluster")
            if qos.autoscale:
                self.autoscaler = PoolAutoscaler(
                    self.manager, qos, timeline=self.timeline,
                )
        # Control-plane counters whose running totals already live in
        # the stats dicts above: adopted at scrape time (set_total) so
        # the hot paths stay metric-free. The registry holds collectors
        # by weakref — the strong ref on self keeps ours alive.
        try:
            from parallax_tpu.obs.registry import get_registry

            reg = get_registry()
            c_drains = reg.counter(
                mnames.SCHEDULER_DRAINS_TOTAL,
                "Drain directives issued to pipeline heads around dead "
                "peers",
            )
            c_targets = reg.counter(
                mnames.SCHEDULER_MIGRATION_TARGETS_TOTAL,
                "Migration targets chosen for parked requests "
                "(CacheIndex-scored)",
            )
            c_recorded = reg.counter(
                mnames.SCHEDULER_MIGRATIONS_RECORDED_TOTAL,
                "migration_done reports recorded into the where_is "
                "table",
            )
            c_disagg = reg.counter(
                mnames.SCHEDULER_DISAGG_TARGETS_TOTAL,
                "Decode-pool handoff targets chosen for finished "
                "prompts",
            )

            def _collect_scheduler_stats() -> None:
                with self._lock:
                    mig = dict(self.migration_stats)
                    dis = dict(self.disagg_stats)
                c_drains.set_total(mig.get("drains") or 0)
                c_targets.set_total(mig.get("targets_chosen") or 0)
                c_recorded.set_total(mig.get("recorded") or 0)
                c_disagg.set_total(dis.get("targets_chosen") or 0)

            self._metrics_collector = _collect_scheduler_stats
            reg.register_collector(_collect_scheduler_stats)
        except Exception:  # pragma: no cover - metrics never break serving
            self._metrics_collector = None

    # -- public API (thread-safe enqueues) --------------------------------

    def enqueue_join(
        self, node_id: str, hardware: HardwareInfo,
        wire_formats: list | None = None, role: str | None = None,
    ) -> None:
        self._events.put(("join", node_id, hardware, wire_formats, role))

    def enqueue_leave(self, node_id: str) -> None:
        self._events.put(("leave", node_id))

    def enqueue_update(
        self,
        node_id: str,
        layer_latency_ms: float | None = None,
        load: int | None = None,
        rtt_s: dict | None = None,
        is_ready: bool | None = None,
        refit_version: int | None = None,
        lora_adapters: list | None = None,
        step_timing: dict | None = None,
        cache_stats: dict | None = None,
        transport: dict | None = None,
        metrics: dict | None = None,
        cache_digests: dict | None = None,
        busy: bool | None = None,
        goodput: dict | None = None,
        health: dict | None = None,
        events: dict | None = None,
        kernel: dict | None = None,
        spec: dict | None = None,
        constrained: dict | None = None,
        device: dict | None = None,
    ) -> None:
        self._events.put(
            ("update", node_id, layer_latency_ms, load, rtt_s, is_ready,
             refit_version, lora_adapters, step_timing, cache_stats,
             transport, metrics, cache_digests, busy, goodput, health,
             events, kernel, spec, constrained, device)
        )

    def enqueue_peer_down(self, reporter: str, peer: str,
                          reason: str = "") -> None:
        """A worker's async sender declared ``peer`` unreachable: mark
        its CacheIndex stale NOW (the cache-aware router must stop
        scoring a dead replica's prefixes — don't wait for the staleness
        decay) and put it under the accelerated heartbeat sweep."""
        self._events.put(("peer_down", reporter, peer, reason))

    def receive_request(
        self, request_id: str, meta: RequestMeta | None = None,
        arrival_time: float | None = None,
    ) -> PendingRequest:
        """``arrival_time`` (monotonic) preserves the ORIGINAL arrival
        when a request is re-enqueued after its dispatched path died —
        the retry must not jump the FCFS ladder nor look newly arrived
        to timeout accounting."""
        pr = PendingRequest(request_id, meta=meta)
        if arrival_time is not None:
            pr.enqueue_time = arrival_time
        self._requests.put(pr)
        return pr

    def get_node_allocation(self, node_id: str) -> dict | None:
        """The worker's view of its assignment (heartbeat reply payload)."""
        node = self.manager.get(node_id)
        if node is None or not node.has_allocation:
            return None
        alloc = {
            "start_layer": node.start_layer,
            "end_layer": node.end_layer,
            "model_name": self.model.model_name,
            "refit_version": self.refit_version,
        }
        if self.router.wants_digests:
            # Cache-aware routing: workers build their engine with digest
            # tracking on (the flag rides the allocation into the reload)
            # and publish delta payloads on subsequent heartbeats.
            alloc["want_digests"] = True
        # Phase role: normally the worker's own join-time choice echoed
        # back, but the QoS autoscaler may have re-roled this node's
        # pipeline — the worker adopts the new role in place (same
        # layers, no reload; docs/qos.md).
        alloc["role"] = node.role
        if self.qos_controller is not None:
            # Cluster shed verdict: workers OR it with their local
            # controller so a cluster-wide interactive burn protects
            # every head at once.
            alloc["qos_shed"] = self.qos_controller.shedding
        return alloc

    def drain_requested(self, node_id: str) -> list[str]:
        """Consume a head node's pending drain directives (dead peers
        whose in-flight requests it must checkpoint away); relayed on
        the heartbeat reply."""
        node = self.manager.get(node_id)
        if node is None or not node.pending_drain:
            return []
        # Runs on the heartbeat handler thread while _handle_leave (event
        # thread) may be adding; the lock makes consume-and-clear atomic
        # so a directive added mid-consume is never wiped unsent.
        with self._lock:
            dead = sorted(node.pending_drain)
            node.pending_drain.clear()
        return dead

    # -- live migration ----------------------------------------------------

    def choose_migration_targets(
        self, requests: list[dict], exclude: "set[str] | None" = None,
        pool: str | None = None,
    ) -> dict:
        """Pick a surviving pipeline per parked request, scored the
        cache-aware way: ``alpha * predicted_uncached + beta *
        head_load`` against each head's heartbeat-fed CacheIndex mirror
        (``requests`` carry the restored prompt's block-hash chains), so
        a migrating request lands where its prefix is already cached and
        the restore degrades to re-prefill of only the uncovered
        suffix. Requests without a usable chain fall back to
        least-loaded. Charges router load per chosen path (released by
        the target head's eventual request_complete).

        ``pool="decode"`` restricts candidates to the decode phase pool
        (disaggregation handoff targets, docs/disaggregation.md): the
        decode phase never falls back to prefill specialists — an empty
        result tells the prefill head to keep the request local."""
        from parallax_tpu.scheduling.request_routing import (
            eligible_pipelines,
        )

        excl = set(exclude or ())
        out: dict = {}
        candidates = [
            p for p in eligible_pipelines(self.manager, phase=pool)
            if not (set(p.node_ids) & excl)
        ]
        if pool == "decode":
            with self._lock:
                self.disagg_stats["target_queries"] += len(requests)
                if not candidates:
                    self.disagg_stats["no_target"] += len(requests)
        if not candidates:
            return out
        for r in requests:
            rid = r.get("rid")
            if not isinstance(rid, str):
                continue
            lora = r.get("lora_id")
            prompt_tokens = int(r.get("prompt_tokens") or 0)
            chains = r.get("chains") or {}
            best = best_score = None
            best_hit = 0
            for i, p in enumerate(candidates):
                if lora and not all(
                    lora in n.lora_adapters for n in p.nodes
                ):
                    continue
                head = p.nodes[0]
                hit = 0
                idx = head.cache_index
                chain = chains.get(idx.block) or chains.get(str(idx.block))
                # Adapter requests score too: their chains arrive
                # pre-namespaced with the deterministic per-adapter
                # salt, matching the digests the target's radix tree
                # publishes (cache_manager.derive_ns_salt).
                if idx.block > 0 and chain:
                    try:
                        hit = idx.predict_cached_tokens(
                            [int(c) for c in chain], idx.block,
                            prompt_tokens,
                        )
                    except (TypeError, ValueError):
                        hit = 0
                score = (
                    max(0, prompt_tokens - hit) + 256.0 * head.load,
                    (i + self.migration_stats["targets_chosen"])
                    % len(candidates),
                )
                if best_score is None or score < best_score:
                    best, best_score, best_hit = p, score, hit
            if best is None:
                continue
            self.router.on_dispatch(best.nodes)
            # migrate_target / disagg_target RPCs land on the service
            # thread while the sweep/heartbeat threads read these stats
            # for /cluster/status.
            with self._lock:
                if pool == "decode":
                    self.disagg_stats["targets_chosen"] += 1
                else:
                    self.migration_stats["targets_chosen"] += 1
            out[rid] = {
                "path": list(best.node_ids),
                "head_layers": [
                    best.nodes[0].start_layer, best.nodes[0].end_layer,
                ],
                "predicted_cached_tokens": best_hit,
            }
        return out

    def record_migration(self, request_id: str, head: str) -> None:
        """A target head restored ``request_id``: pollers that lost the
        old head find the new one via ``migrated_head``."""
        with self._lock:
            self._migrations[request_id] = head
            self._migrations.move_to_end(request_id)
            while len(self._migrations) > 4096:
                self._migrations.popitem(last=False)
            self.migration_stats["recorded"] += 1
        self.timeline.record(
            "migration_done", node=head, request_id=request_id,
        )
        self._journal("migration_done", {"rid": request_id, "head": head})

    def migrated_head(self, request_id: str) -> str | None:
        with self._lock:
            return self._migrations.get(request_id)

    def digests_resync_requested(self, node_id: str) -> bool:
        """Consume a node's pending digest-resync flag (set when a delta
        arrived out of sequence); the heartbeat reply relays it so the
        worker's next beat carries a full snapshot."""
        node = self.manager.get(node_id)
        if node is None or not node.digests_need_resync:
            return False
        node.digests_need_resync = False
        return True

    # -- scheduler HA (parallax_tpu/ha, docs/ha.md) ------------------------

    def fence(self, epoch: int) -> None:
        """A worker echoed a scheduler epoch higher than ours: a standby
        promoted while we were partitioned/paused. Stop mutating — the
        promoted scheduler owns the swarm now (split-brain guard)."""
        if self.fenced:
            return
        self.fenced = True
        logger.warning(
            "scheduler fenced: worker echoed epoch %d > our %d — a "
            "standby promoted past us; refusing further mutations",
            epoch, self.epoch,
        )
        self.timeline.record("ha_fenced", epoch=epoch, our_epoch=self.epoch)

    def _journal(self, kind: str, data: dict) -> None:
        """Replicate one state mutation (no-op while HA is off)."""
        if self.journal is None:
            return
        try:
            self.journal.record(kind, data)
        except Exception:  # pragma: no cover - HA must never break serving
            logger.exception("journal record %r failed", kind)

    def _journal_pipelines(self) -> None:
        """Journal the pipeline/allocation table when it changed since
        the last call. Allocation is DERIVED state (the allocator is
        deterministic only given identical arrival order), so the
        primary's actual decision is replicated rather than recomputed
        by the standby — covering bootstrap, extend, dynamic-join
        replicas, turning-point trims, rebalances and autoscaler
        re-roles through one diff point."""
        if self.journal is None:
            return
        members: set[str] = set()
        pipelines = []
        for p in self.manager.pipelines:
            pipelines.append({
                "id": p.pipeline_id,
                "nodes": [
                    [n.node_id, n.start_layer, n.end_layer, n.role]
                    for n in p.nodes
                ],
            })
            members.update(p.node_ids)
        replicas = [
            [n.node_id, n.start_layer, n.end_layer]
            for n in self.manager.nodes(NodeState.ACTIVE)
            if n.node_id not in members and n.has_allocation
        ]
        cur = {
            "bootstrapped": self.bootstrapped.is_set(),
            "next_id": self.manager.next_pipeline_id,
            "pipelines": pipelines,
            "replicas": replicas,
        }
        if cur != self._journaled_pipelines:
            self._journaled_pipelines = cur
            self._journal("pipelines", cur)

    # -- synchronous drivers (standby mirror + virtual-time harness) -------

    def apply_event(self, ev: tuple) -> None:
        """Apply one topology event synchronously — the churn harness
        drives the REAL handler without the event thread."""
        self._handle_event(ev)

    def drain_events(self) -> int:
        """Drain and handle every queued event now (synchronous twin of
        one _event_loop pass). Returns the number handled."""
        n = 0
        while True:
            try:
                ev = self._events.get_nowait()
            except queue.Empty:
                return n
            try:
                self._handle_event(ev)
            except Exception:
                logger.exception("event %r failed", ev[0])
            n += 1

    def sweep_once(self) -> None:
        """One heartbeat-sweep + QoS-tick + journal-diff pass
        (synchronous twin of the _event_loop's 1 Hz housekeeping)."""
        self._sweep_heartbeats()
        self._qos_tick(time.monotonic())
        self._journal_pipelines()

    def dispatch_once(self) -> bool:
        """Route one queued request now (synchronous twin of one
        _dispatch_loop pass). Returns False when the queue was empty."""
        try:
            pr = self._requests.get_nowait()
        except queue.Empty:
            return False
        self._dispatch_one(pr)
        return True

    # -- lifecycle --------------------------------------------------------

    def start(self) -> None:
        for fn in (self._event_loop, self._dispatch_loop):
            t = threading.Thread(target=fn, daemon=True, name=fn.__name__)
            t.start()
            self._threads.append(t)

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=2.0)

    # -- event loop (single thread owns topology) -------------------------

    def _event_loop(self) -> None:
        last_sweep = time.monotonic()
        while not self._stop.is_set():
            try:
                ev = self._events.get(timeout=0.05)
            except queue.Empty:
                ev = None
            if ev is not None:
                try:
                    self._handle_event(ev)
                except Exception:
                    # The topology thread must survive malformed
                    # network-fed payloads (update fields arrive from
                    # workers' heartbeats verbatim).
                    logger.exception("event %r failed", ev[0])
            now = time.monotonic()
            if now - last_sweep > 1.0:
                self._sweep_heartbeats()
                self._qos_tick(now)
                # Autoscaler re-roles and sweep-driven churn change the
                # allocation table off the join/leave paths; the 1 Hz
                # diff catches them for the HA journal.
                self._journal_pipelines()
                last_sweep = now

    def _handle_event(self, ev: tuple) -> None:
        kind = ev[0]
        if self.fenced:
            # A promoted standby owns the swarm; a fenced old primary
            # mutating its registry would fork the control plane.
            return
        try:
            from parallax_tpu.obs.registry import get_registry

            get_registry().counter(
                mnames.SCHEDULER_EVENTS_TOTAL,
                "Topology events handled by the scheduler event thread, "
                "by kind (join / leave / peer_down / update)",
                labelnames=("kind",),
            ).labels(kind=kind).inc()
        except Exception:  # pragma: no cover - metrics never break serving
            pass
        if kind == "join":
            _, node_id, hardware, *rest = ev
            node = Node(node_id=node_id, hardware=hardware, model=self.model)
            if rest and rest[0]:
                node.wire_formats = tuple(rest[0])
            if len(rest) > 1 and rest[1]:
                # Phase specialization (docs/disaggregation.md): the
                # allocator keeps pipelines role-homogeneous and the
                # router phase-filters pools. Unknown strings degrade
                # to mixed — a newer worker build must still serve.
                role = str(rest[1]).lower()
                node.role = (
                    role if role in ("prefill", "decode", "mixed")
                    else "mixed"
                )
            self.manager.add(node)
            logger.info("node %s joined (%s x%d, role=%s)", node_id,
                        hardware.device_kind, hardware.num_chips,
                        node.role)
            self._journal("join", {
                "node_id": node_id,
                "hardware": hardware.to_dict(),
                "wire_formats": list(node.wire_formats),
                "role": node.role,
            })
            self._try_bootstrap_or_extend()
            self._journal_pipelines()
        elif kind == "leave":
            self._handle_leave(ev[1])
        elif kind == "peer_down":
            _, reporter, peer, reason = ev
            node = self.manager.get(peer)
            if node is None:
                return
            stale = len(node.cache_index)
            node.cache_index.clear()
            if node.peer_down_at is None:
                node.peer_down_at = time.monotonic()
                logger.warning(
                    "peer_down: %s reported %s unreachable (%s); "
                    "%d cache-index digests dropped, sweep accelerated",
                    reporter, peer, reason or "?", stale,
                )
                self.timeline.record(
                    "peer_down", node=peer, reporter=reporter,
                    reason=reason or "?",
                )
                self._journal("peer_down", {
                    "reporter": reporter, "peer": peer,
                    "reason": reason or "",
                })
        elif kind == "update":
            (_, node_id, lat, load, rtt, ready, refit, adapters, timing,
             cache_stats, *rest) = ev
            transport = rest[0] if rest else None
            metrics = rest[1] if len(rest) > 1 else None
            cache_digests = rest[2] if len(rest) > 2 else None
            busy = rest[3] if len(rest) > 3 else None
            goodput = rest[4] if len(rest) > 4 else None
            health = rest[5] if len(rest) > 5 else None
            events = rest[6] if len(rest) > 6 else None
            kernel = rest[7] if len(rest) > 7 else None
            spec = rest[8] if len(rest) > 8 else None
            constrained = rest[9] if len(rest) > 9 else None
            device = rest[10] if len(rest) > 10 else None
            if events is not None:
                # Merge the node's flight-event batch even for unknown
                # nodes: a churn victim's last beats are exactly the
                # interesting ones.
                self.timeline.ingest(node_id, events)
            node = self.manager.get(node_id)
            if node is None:
                return
            node.touch()
            # A live beat disproves any dead-peer report or probation.
            node.peer_down_at = None
            node.suspect = False
            if busy is not None:
                node.reported_busy = bool(busy)
            if lat is not None:
                node.measured_layer_latency_ms = lat
            if load is not None:
                node.load = load
            if rtt:
                node.rtt_s.update(rtt)
            if ready is not None:
                node.is_ready = ready
            if refit is not None:
                node.refit_version = refit
            if adapters is not None:
                node.lora_adapters = tuple(adapters)
            if timing is not None:
                node.step_timing = timing
            if cache_stats is not None:
                node.cache_stats = cache_stats
            if kernel is not None:
                node.kernel = kernel
            if spec is not None:
                node.spec = spec
            if constrained is not None:
                node.constrained = constrained
            if transport is not None:
                node.transport = transport
            if metrics is not None:
                node.metrics = metrics
            if goodput is not None:
                node.goodput = goodput
            if device is not None:
                node.device = device
            if health is not None:
                prev = (node.health or {}).get("status")
                node.health = health
                status = health.get("status")
                if status != prev and status in ("degraded", "stalled"):
                    # Surface sick-but-alive loudly: the node still
                    # heartbeats (so the sweep won't touch it) but its
                    # watchdog says a component stopped making progress.
                    # The timeline gets the transition even if the
                    # node's own flight batch is delayed.
                    logger.warning(
                        "node %s reports health %s: %s", node_id, status,
                        "; ".join(health.get("causes") or ()) or "?",
                    )
                    self.timeline.record(
                        "node_health", node=node_id, status=status,
                        causes=list(health.get("causes") or ()),
                    )
            if cache_digests is not None:
                if node.cache_index.apply(cache_digests):
                    node.digests_need_resync = True
            # Bounded heartbeat-replay window: a promoted standby
            # re-derives soft state (load charges, readiness, digest
            # continuity) from these instead of trusting a snapshot of
            # someone else's clocks.
            self._journal("hb", {
                "node_id": node_id,
                "load": load,
                "ready": ready,
                "busy": busy,
                "latency_ms": lat,
                "refit_version": refit,
                "digests": cache_digests,
            })

    def _try_bootstrap_or_extend(self) -> None:
        standby = self.manager.nodes(NodeState.STANDBY)
        if not self.bootstrapped.is_set():
            if len(self.manager) < self.min_nodes:
                return
            pipelines = self.allocator.allocate_role_aware(standby)
            if not pipelines:
                return
            self.manager.register_pipelines(pipelines)
            self.bootstrapped.set()
            self._log_allocation("bootstrap")
        else:
            # Serving already: extend with new pipelines when standby nodes
            # suffice (reference RR extend path).
            pipelines = self.allocator.allocate_role_aware(standby)
            if pipelines:
                self.manager.register_pipelines(pipelines)
                self._log_allocation("extend")
        # Leftover standby nodes that cannot complete a pipeline still
        # help under dynamic routing: replicate an existing stage range
        # (reference dynamic_join, layer_allocation.py:193-214). Runs on
        # the bootstrap branch too — a global rebalance standbys every
        # node, and stranded replicas must re-join without waiting for an
        # unrelated membership event.
        if self.router.supports_partial_replicas and self.bootstrapped.is_set():
            from parallax_tpu.scheduling.layer_allocation import (
                assign_to_lightest_layers,
            )

            active = self.manager.nodes(NodeState.ACTIVE)
            for node in self.manager.nodes(NodeState.STANDBY):
                if active and assign_to_lightest_layers(
                    node, active, self.model.num_hidden_layers
                ):
                    self.manager.set_active(node.node_id)
                    active.append(node)
                    self._log_allocation("dynamic-join")
            self._apply_turning_point_trims()

    def _apply_turning_point_trims(self) -> None:
        """Trim replica shard segments the optimal route never uses
        (reference find_turning_points warm-up trimming,
        request_routing.py:86-177): layer-level DP over the active
        nodes' (possibly drift-overlapped) ranges yields head/tail
        truncation advice; applying it to PARTIAL REPLICAS frees their
        HBM for KV. Registered pipeline members are never trimmed —
        their contiguity contract is what RR routing validates."""
        from parallax_tpu.scheduling.request_routing import (
            find_turning_points,
        )

        active = self.manager.nodes(NodeState.ACTIVE)
        members = {
            n.node_id for p in self.manager.pipelines for n in p.nodes
        }
        for node_id, layer, kind in find_turning_points(
            active, self.model.num_hidden_layers
        ):
            node = self.manager.get(node_id)
            if node is None or node_id in members:
                continue
            # Trimming changes the allocation, which the next heartbeat
            # turns into an engine reload aborting that replica's in-flight
            # requests — only act on evidence, never on roofline defaults:
            # the node must have reported a measured layer latency and be
            # idle right now.
            if node.measured_layer_latency_ms is None or node.load > 0:
                continue
            if kind == "tail" and node.start_layer < layer < node.end_layer:
                logger.info(
                    "turning-point trim: %s tail [%d, %d) -> [%d, %d)",
                    node_id, node.start_layer, node.end_layer,
                    node.start_layer, layer,
                )
                node.set_layers(node.start_layer, layer)
            elif kind == "head" and node.start_layer < layer < node.end_layer:
                logger.info(
                    "turning-point trim: %s head [%d, %d) -> [%d, %d)",
                    node_id, node.start_layer, node.end_layer,
                    layer, node.end_layer,
                )
                node.set_layers(layer, node.end_layer)

    def _qos_tick(self, now: float) -> None:
        """QoS control-plane pass (event thread, ~1 Hz): feed the
        cluster admission controller the merged per-class TTFT counts
        from heartbeat histogram snapshots, run its hysteresis, and
        tick the pool autoscaler. The shed verdict reaches workers via
        their next heartbeat reply (``qos_shed``)."""
        ctl = self.qos_controller
        if ctl is None:
            return
        under, total = self._qos_cluster_counts()
        if total:
            ctl.observe_cumulative(under, total, now)
        if ctl.tick(now):
            self.timeline.record(
                "qos_shed" if ctl.shedding else "qos_release",
                burn=round(ctl.last_burn, 3),
            )
        if self.autoscaler is not None:
            self.autoscaler.tick(now)

    def _qos_cluster_counts(self) -> tuple[float, int]:
        """Cluster-cumulative (under-budget, total) counts of the
        protected class's TTFT, summed over every pipeline member's
        heartbeat-shipped ``parallax_qos_ttft_ms`` children."""
        from parallax_tpu.obs.slo import fraction_below

        ctl = self.qos_controller
        budget = ctl.protected.deadline_ms
        under, total = 0.0, 0
        for p in self.manager.pipelines:
            for n in p.nodes:
                children = (n.metrics or {}).get(mnames.QOS_TTFT_MS)
                if not isinstance(children, dict):
                    continue
                for label, snap in children.items():
                    if ctl.protected.name not in str(label):
                        continue
                    u, t = fraction_below(snap, budget)
                    under += u
                    total += t
        return under, total

    def _handle_leave(self, node_id: str) -> None:
        # Drain, don't abort: every pipeline through the dying node has
        # a head that owns full request state — flag it (consumed by its
        # next heartbeat reply) so it checkpoints its in-flight requests
        # to a surviving pipeline instead of abort-storming them. When
        # the head IS the dying node, the client-side resume ladder is
        # the recovery path (SwarmClient mirrors the token stream).
        for p in self.manager.pipelines:
            if node_id not in p.node_ids:
                continue
            head = p.nodes[0]
            if head.node_id != node_id:
                # Locked against drain_requested's consume-and-clear on
                # the heartbeat handler thread.
                with self._lock:
                    head.pending_drain.add(node_id)
                    self.migration_stats["drains"] += 1
        displaced = self.manager.remove(node_id)
        logger.info("node %s left; %d displaced", node_id, len(displaced))
        self.timeline.record(
            "node_leave", node=node_id, displaced=len(displaced),
        )
        self._journal("leave", {"node_id": node_id})
        active = list(self.manager.nodes(NodeState.ACTIVE))
        if not self.manager.pipelines or self.allocator.should_global_rebalance(
            active
        ):
            self._global_rebalance()
        else:
            self._try_bootstrap_or_extend()
        self._journal_pipelines()

    def _global_rebalance(self) -> None:
        """Tear everything down and re-allocate from scratch (reference
        scheduler.py:581-636). Workers detect new ranges via heartbeat
        replies and reload."""
        logger.info("global rebalance")
        try:
            from parallax_tpu.obs.registry import get_registry

            get_registry().counter(
                mnames.SCHEDULER_REBALANCES_TOTAL,
                "Global rebalances (full teardown + re-allocation of "
                "every pipeline)",
            ).inc()
        except Exception:  # pragma: no cover - metrics never break serving
            pass
        self.manager.standby_all()
        self.bootstrapped.clear()
        self._try_bootstrap_or_extend()

    def _sweep_heartbeats(self) -> None:
        for node in self.manager.nodes():
            # Standby nodes may legitimately sit in a long blocking join;
            # give them a much longer leash before eviction.
            factor = 1.0 if node.has_allocation else 10.0
            timeout = self.heartbeat_timeout_s * factor
            # A dead-peer report overrides busy probation: the report is
            # hard evidence (a send failed), and a genuinely-busy node
            # disproves it with its next beat — don't let a stale busy
            # flag defer the drain by BUSY_GRACE_FACTOR x timeout.
            if node.reported_busy and node.peer_down_at is None:
                # Probation, not eviction: an engine reload/compile can
                # out-last the base timeout (first-compile storms on
                # fresh joins); the node said so in its last beat.
                extended = timeout * self.BUSY_GRACE_FACTOR
                if node.is_stale(timeout) and not node.is_stale(extended):
                    if not node.suspect:
                        node.suspect = True
                        logger.warning(
                            "heartbeat overdue but %s reported a "
                            "reload/compile in progress: suspect, "
                            "grace extended x%.0f",
                            node.node_id, self.BUSY_GRACE_FACTOR,
                        )
                    continue
                timeout = extended
            if node.peer_down_at is not None:
                # A peer already reported it dead; a missing heartbeat
                # on top of the report is confirmation — don't wait the
                # full horizon to start draining its pipelines.
                timeout = min(
                    timeout,
                    max(1.5, timeout * self.PEER_DOWN_GRACE_FACTOR),
                )
            if node.is_stale(timeout):
                logger.warning("heartbeat timeout: %s", node.node_id)
                try:
                    from parallax_tpu.obs.registry import get_registry

                    get_registry().counter(
                        mnames.SCHEDULER_HEARTBEAT_EVICTIONS_TOTAL,
                        "Nodes evicted by the heartbeat sweep "
                        "(missed-beat leaves, as opposed to clean "
                        "node_leave departures)",
                    ).inc()
                except Exception:  # pragma: no cover
                    pass
                self._handle_leave(node.node_id)

    # -- dispatch loop ----------------------------------------------------

    def _dispatch_loop(self) -> None:
        while not self._stop.is_set():
            try:
                pr = self._requests.get(timeout=0.05)
            except queue.Empty:
                continue
            if not self._dispatch_one(pr):
                time.sleep(0.02)

    def _dispatch_one(self, pr: PendingRequest) -> bool:
        """Route one pending request (shared by the dispatch thread and
        the synchronous :meth:`dispatch_once` driver). Returns False
        when the request was re-queued for a later retry."""
        if pr.cancelled:
            pr.event.set()
            return True
        try:
            path = self.router.find_path(pr.meta)
        except Exception:
            # A router bug must not kill the dispatch thread — every
            # later request would silently time out to 503. Treat as
            # "no path now" and let the retry ladder run.
            logger.exception("find_path failed for %s", pr.request_id)
            path = None
        if path is not None:
            self.router.on_dispatch(path)
            pr.path_ids = [n.node_id for n in path]
            if pr.meta is not None and pr.meta.prompt_ids:
                self._record_prediction(
                    pr.request_id,
                    pr.meta.predicted_cached_tokens,
                    pr.meta.num_prompt_tokens,
                )
            pr.event.set()
            return True
        if time.monotonic() < pr.deadline:
            # No serviceable pipeline right now (bootstrap in flight,
            # all busy, refit) — retry until the deadline.
            self._requests.put(pr)
            return False
        pr.event.set()
        return True

    def _record_prediction(self, request_id: str, predicted: int,
                           prompt_tokens: int) -> None:
        with self._lock:
            self._predictions[request_id] = (predicted, prompt_tokens)
            while len(self._predictions) > self._predictions_cap:
                self._predictions.popitem(last=False)

    def complete_request(self, path_ids: list[str],
                         request_id: str | None = None,
                         cached_tokens: int | None = None) -> None:
        self.router.on_complete(path_ids)
        if request_id is None:
            return
        # Predicted-vs-actual prefix-hit telemetry: the head engine
        # reports its real admission-time hit on request_complete; fold
        # it against the dispatch-time prediction.
        with self._lock:
            pred = self._predictions.pop(request_id, None)
            if pred is None or cached_tokens is None:
                return
            predicted, _prompt_tokens = pred
            acc = self.routing_accuracy
            acc["requests"] += 1
            acc["predicted_tokens"] += predicted
            acc["actual_tokens"] += int(cached_tokens)
            acc["abs_error_tokens"] += abs(predicted - int(cached_tokens))
        try:
            from parallax_tpu.obs.registry import get_registry

            reg = get_registry()
            reg.counter(
                mnames.ROUTING_PREDICTED_CACHED_TOKENS_TOTAL,
                "Dispatch-time predicted prefix-cache hit tokens",
            ).inc(predicted)
            reg.counter(
                mnames.ROUTING_ACTUAL_CACHED_TOKENS_TOTAL,
                "Admission-time actual prefix-cache hit tokens "
                "(head engine, via request_complete)",
            ).inc(int(cached_tokens))
        except Exception:  # pragma: no cover - metrics never break serving
            pass

    # -- weight refit ------------------------------------------------------

    def begin_refit(self, index_map: dict[str, str]) -> int:
        """Register a new weight version (name -> content id); nodes pick it
        up from heartbeat replies (reference backend/main.py:42-73)."""
        with self._lock:
            self.refit_version += 1
            self.refit_index = dict(index_map)
            version = self.refit_version
        self._journal("refit", {"version": version,
                                "index": dict(index_map)})
        return version

    # -- introspection ----------------------------------------------------

    def cluster_status(self) -> dict:
        report = self.manager.capacity_report()
        report["bootstrapped"] = self.bootstrapped.is_set()
        # Cluster-wide latency percentiles: merge every node's heartbeat
        # histogram snapshots (same bucket lattice by convention) into
        # one p50/p95/p99 summary per metric — TTFT/TPOT across the
        # whole swarm, not per worker.
        from parallax_tpu.obs.registry import (
            merge_histogram_snapshots,
            summarize_snapshots,
        )

        node_snaps = [
            n.metrics for p in self.manager.pipelines for n in p.nodes
            if n.metrics
        ]
        merged_snaps = None
        if node_snaps:
            merged_snaps = merge_histogram_snapshots(node_snaps)
            report["metrics"] = summarize_snapshots(merged_snaps)
        # Goodput: cluster-merged token usefulness (summed buckets,
        # goodput fraction, tokens-useful-per-chip-second) — the signal
        # autoscaling reads instead of raw throughput.
        from parallax_tpu.obs.goodput import merge_goodput

        all_nodes = [n for p in self.manager.pipelines for n in p.nodes]
        cluster_goodput = merge_goodput(
            [n.goodput for n in all_nodes if n.goodput]
        )
        if cluster_goodput is not None:
            report["goodput"] = cluster_goodput
        # Device attribution: cluster-merged HBM ledger (classes
        # unioned, capacity/tracked/untracked summed, invariants ANDed),
        # compile observatory (per-family compiles by cause) and
        # per-program device time — heterogeneous nodes contribute
        # disjoint classes/families and the merge unions them; nodes
        # without a device payload are counted as skips (mirrors
        # parallax_obs_merge_skipped_total semantics).
        from parallax_tpu.obs.device import merge_device

        cluster_device = merge_device([n.device for n in all_nodes])
        if cluster_device is not None:
            report["device"] = cluster_device
        # Health rollup: worst watchdog status across the swarm plus the
        # sick list (alive-but-stalled nodes the binary sweep misses).
        from parallax_tpu.obs.watchdog import worst_status

        health_reports = {
            n.node_id: n.health for n in all_nodes if n.health
        }
        if health_reports:
            report["health"] = {
                "status": worst_status(
                    h.get("status") for h in health_reports.values()
                ),
                "sick_nodes": sorted(
                    nid for nid, h in health_reports.items()
                    if h.get("status") in ("degraded", "stalled")
                ),
            }
        # SLO attainment + burn rates over the merged histograms and the
        # merged availability counts; each cluster_status() call is one
        # tracker sample (the status stream's interval sets the cadence).
        if self.slo_tracker is not None:
            req_counts = (cluster_goodput or {}).get("requests") or {}
            report["slo"] = self.slo_tracker.observe_and_evaluate({
                "hists": merged_snaps or {},
                "finished": req_counts.get("finished") or 0,
                "aborted": req_counts.get("aborted") or 0,
            })
        # Timeline counters (the events themselves live at
        # /debug/timeline).
        report["timeline"] = {
            "ingested": self.timeline.ingested,
            "gaps": self.timeline.gaps,
            "resets": self.timeline.resets,
        }
        # Routing telemetry: strategy, per-strategy decision counters
        # (chosen_by_cache / chosen_by_load / fallback_imbalance for the
        # cache-aware router), per-pipeline dispatch counts and the
        # predicted-vs-actual prefix-hit aggregate.
        with self._lock:
            accuracy = dict(self.routing_accuracy)
            disagg = dict(self.disagg_stats)
        # Per-phase pool breakdown (docs/disaggregation.md): operators
        # must see prefill-pool vs decode-pool saturation SEPARATELY —
        # a swarm can be prompt-bound with an idle decode pool (or vice
        # versa) while the aggregate load looks healthy. ``in_flight``
        # is the heads' heartbeat-reported engine depth (running + the
        # worker-side wait queue), so it IS the pool's queue depth;
        # ``queued_unrouted`` counts requests still waiting for a path.
        from parallax_tpu.qos.autoscaler import pool_report

        # Shared with the QoS autoscaler (qos/autoscaler.py) so the
        # numbers operators read here are exactly what the re-roling
        # loop acts on (adds goodput_per_chip per pool).
        pools = pool_report(self.manager.pipelines)
        report["routing"] = {
            "strategy": self.routing_name,
            "decisions": dict(self.router.decision_counters),
            "pipeline_dispatches": {
                str(pid): n
                for pid, n in self.router.pipeline_dispatches.items()
            },
            "predicted_vs_actual": accuracy,
            "pools": pools,
            "queued_unrouted": self._requests.qsize(),
        }
        # Disaggregated serving rollup: active when a prefill pool and a
        # decode-capable pool are both registered; handoff counters from
        # the decode-pool target chooser.
        report["disagg"] = {
            "active": "prefill" in pools
            and any(r in pools for r in ("decode", "mixed")),
            **disagg,
        }
        # Node-churn robustness: drain directives issued, migration
        # targets chosen, restores reported back by target heads.
        report["migrations"] = dict(self.migration_stats)
        # Multi-tenant QoS control plane (docs/qos.md): cluster shed
        # state + burn, class table, and the autoscaler's re-role
        # ledger. Absent entirely when QoS is off.
        if self.qos_controller is not None:
            report["qos"] = {
                "enabled": True,
                "classes": [
                    {"name": c.name, "priority": c.priority,
                     "deadline_ms": c.deadline_ms,
                     "sheddable": c.sheddable}
                    for c in self.qos_config.classes
                ],
                "admission": self.qos_controller.payload(),
                "autoscaler": (
                    self.autoscaler.payload()
                    if self.autoscaler is not None
                    else {"enabled": False}
                ),
            }
        report["pipelines"] = [
            {
                "id": p.pipeline_id,
                # Phase pool this pipeline serves (docs/disaggregation.md).
                "role": p.role,
                "nodes": [
                    {
                        "node_id": n.node_id,
                        "layers": [n.start_layer, n.end_layer],
                        "load": n.load,
                        "ready": n.is_ready,
                        # Phase specialization from node_join.
                        "role": n.role,
                        # Probation (busy-reload grace) / dead-peer
                        # report state from the heartbeat sweep.
                        "suspect": n.suspect,
                        # Watchdog health state machine (ok/degraded/
                        # stalled + causes) from heartbeats; None until
                        # the node reports one (watchdog off).
                        "health": n.health,
                        # Per-node goodput ledger payload (cluster merge
                        # in the top-level "goodput" section).
                        "goodput": n.goodput,
                        # Per-node device attribution payload (HBM
                        # ledger, compile observatory, device time);
                        # cluster merge in the top-level "device"
                        # section (obs/device.py).
                        "device": n.device,
                        # Overlapped decode loop telemetry (host_ms /
                        # readback_wait_ms EWMAs + overlap fraction).
                        "step_timing": n.step_timing,
                        # Prefix-cache / memory-tier counters (hit
                        # rates, occupancy, demotions, swap-ins,
                        # preemptions) from heartbeats.
                        "cache_stats": n.cache_stats,
                        # Attention-kernel impl (pallas-fused /
                        # pallas-split / xla) + per-path dispatch
                        # counts from heartbeats (docs/kernels.md).
                        "kernel": n.kernel,
                        # Speculative-decoding ledger from heartbeats:
                        # per-source proposed/accepted/rejected totals,
                        # acceptance rate, accepted tokens per
                        # chip-second (docs/decode_loop.md). None while
                        # speculation is off on the node.
                        "spec": n.spec,
                        # Constrained-decoding ledger from heartbeats:
                        # in-window grammar rows, device mask steps,
                        # table builds vs cache hits, host-sync
                        # fallbacks (docs/decode_loop.md). None until
                        # the node serves a feature batch.
                        "constrained": n.constrained,
                        # Per-link activation-transport telemetry
                        # (bytes each way, serialize/send ms, queue
                        # depth, compression ratio) from heartbeats.
                        "transport": n.transport,
                        # Wire dtypes this node's build can decode
                        # (node_join capability) — which links can
                        # negotiate bf16/fp8 compression.
                        "wire_formats": list(n.wire_formats),
                        # Scheduler-side prefix-digest mirror (cache-
                        # aware routing): how many cached prefixes this
                        # head advertises, at what block granularity.
                        "cache_index": {
                            "digests": len(n.cache_index),
                            "block": n.cache_index.block,
                        } if len(n.cache_index) else None,
                    }
                    for n in p.nodes
                ],
            }
            for p in self.manager.pipelines
        ]
        return report

    def _log_allocation(self, event: str) -> None:
        for p in self.manager.pipelines:
            logger.info(
                "%s: pipeline %d = %s",
                event,
                p.pipeline_id,
                " -> ".join(
                    f"{n.node_id}[{n.start_layer},{n.end_layer})"
                    for n in p.nodes
                ),
            )
