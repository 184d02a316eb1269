"""WorkerNode: the per-host serving daemon.

Capability parity: reference ``GradientServer``
(``src/parallax/p2p/server.py:341-976``): join the scheduler, heartbeat
announcer with reallocation detection, the node sender loop grouping
outbound packets by next peer, abort/release broadcast, and elastic reload
when the scheduler moves the node's layer range.

TPU re-design: one process per host (TP lives inside the engine's mesh, no
rank subprocesses), a single step thread owning the engine, and an inbox
queue decoupling transport callbacks from compute. Worker node ids are
their transport addresses (``host:port``) — the DHT indirection of libp2p
is unnecessary on DCN.
"""

from __future__ import annotations

import os
import queue
import threading
import time

import jax
import jax.numpy as jnp

from parallax_tpu.config import ModelConfig, resolve_role, resolve_wire_dtype
from parallax_tpu.models.base import StageModel
from parallax_tpu.models.registry import create_stage_model
from parallax_tpu.p2p import proto
from parallax_tpu.p2p.transport import (
    NO_HANDLER_MARK,
    AsyncSender,
    Transport,
)
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.request import (
    IntermediateRequest,
    Request,
    RequestStatus,
)
from parallax_tpu.utils import get_logger
from parallax_tpu.utils.hw import detect_hardware
from parallax_tpu.analysis.sanitizer import make_lock
from parallax_tpu.obs import names as mnames

logger = get_logger(__name__)


class WorkerNode:
    """Joins a swarm, serves its layer range, forwards activations."""

    # Live migration: a request parked for checkpoint shipping that finds
    # no target pipeline within this long is aborted (the client resume
    # ladder is the rung below).
    MIGRATION_PARK_TIMEOUT_S = 20.0
    # Backoff between target-query attempts while no pipeline is
    # serviceable (bootstrap/rebalance in flight).
    MIGRATION_RETRY_S = 1.0
    # Disaggregation handoff (docs/disaggregation.md): a prefill head's
    # parked request that has not landed on a decode replica within this
    # long restores LOCALLY (mixed-mode decode) — never aborts.
    HANDOFF_PARK_TIMEOUT_S = 20.0
    # Backoff between ship attempts after a retryable failure.
    HANDOFF_RETRY_S = 0.5
    # A KV transfer whose decode-side result has not arrived within this
    # long is presumed lost (target death, lane failure): fall back to a
    # checkpoint-only re-ship. The target acks duplicates without a
    # second submit, so a merely-lost result cannot double-decode.
    HANDOFF_RESULT_TIMEOUT_S = 15.0

    def __init__(
        self,
        transport: Transport,
        scheduler_peer: str,
        model_config: ModelConfig,
        engine_config: EngineConfig | None = None,
        load_params=None,          # callable (StageModel) -> params
        heartbeat_interval_s: float = 2.0,
        mesh=None,
        sp_mesh=None,
        tp_size: int = 1,
        refit_cache_dir: str | None = None,
        resolve_model=None,  # callable (name) -> (ModelConfig, load_params|None)
        tokenizer_path: str | None = None,
        lora_adapters: dict | None = None,  # name -> PEFT dir or tree
        static_peers: list[str] | None = None,
        layers: tuple[int, int] | None = None,
        watchdog: bool = False,
        watchdog_degraded_s: float = 5.0,
        watchdog_stalled_s: float = 15.0,
        role: str | None = None,
        kv_transfer_chunk_bytes: int | None = None,
        scheduler_standby: list[str] | None = None,
    ):
        """``scheduler_peer=None`` enters SCHEDULER-LESS mode (reference:
        DHT announce + dijkstra routing, ``p2p/server.py:569-626``): the
        worker self-assigns ``layers``, gossips its block over
        ``static_peers``, and — when it hosts layer 0 — computes its own
        fewest-hops routing table from the announcements, so a swarm
        keeps serving with no scheduler as rendezvous."""
        self.transport = transport
        self.scheduler_peer = scheduler_peer
        self.model_config = model_config
        # Own copy: allocation replies mutate it (cache_digests rides
        # want_digests), and callers legitimately share one EngineConfig
        # across workers — a shared flip would make a sibling's
        # digests_switched check see "already on" and skip its rebuild.
        import dataclasses as _dc

        self.engine_config = _dc.replace(engine_config or EngineConfig())
        self.load_params = load_params or self._random_params
        self.heartbeat_interval_s = heartbeat_interval_s
        self.mesh = mesh
        self.sp_mesh = sp_mesh
        self.tp_size = tp_size
        self.resolve_model = resolve_model
        self.tokenizer_path = tokenizer_path
        self.lora_adapters = dict(lora_adapters or {})
        self.static_peers = list(static_peers or [])
        self.standalone = scheduler_peer is None
        # Scheduler HA (docs/ha.md): every scheduler RPC routes through
        # a failover wrapper that retries with jittered exponential
        # backoff under the caller's deadline and rotates to a promoted
        # standby on connection failure or a not_primary redirect. The
        # wrapper also tracks the highest scheduler epoch seen; we echo
        # it on heartbeats so a superseded old primary fences itself.
        self.sched_transport = None
        if not self.standalone:
            from parallax_tpu.ha.failover import SchedulerFailover

            self.sched_transport = SchedulerFailover(
                transport, [scheduler_peer, *(scheduler_standby or [])],
            )
        if self.standalone and layers is None:
            raise ValueError(
                "scheduler-less mode requires explicit layers=(start, end)"
            )
        # Phase specialization (docs/disaggregation.md): "prefill" heads
        # hand finished prompts to the decode pool over the KV-transfer
        # lane; "decode" nodes advertise themselves as handoff targets;
        # "mixed" (default) serves both phases with no handoffs.
        self.role = resolve_role(role)
        if self.standalone and self.role != "mixed":
            logger.warning(
                "--role %s ignored in scheduler-less mode: no scheduler "
                "to assign decode-pool targets; this worker serves both "
                "phases", self.role,
            )
            self.role = "mixed"
        self._self_layers = layers
        # Boot epoch: travels in gossip announcements so peers can tell
        # a restarted process (possibly a different build — different
        # wire caps) from a continuing one even when the restart is
        # faster than the announcement TTL.
        import uuid as _uuid

        self._epoch = _uuid.uuid4().hex[:12]
        # Gossip registry (scheduler-less): node_id -> block announcement.
        self._peer_blocks: dict[str, dict] = {}
        self._peer_lock = make_lock("node.peers")
        self._gossip_pool = None
        self.peer_ttl_s = max(10.0, 5 * heartbeat_interval_s)
        self._grammar_vocab: tuple | None = None
        self._served_model_name: str | None = None
        self.refit_store = None
        if refit_cache_dir:
            from parallax_tpu.p2p.refit import RefitVersionStore

            self.refit_store = RefitVersionStore(refit_cache_dir)

        self.node_id = transport.peer_id
        self.engine: StageEngine | None = None
        self.start_layer = -1
        self.end_layer = -1
        # Prefix-digest publishing (cache-aware routing): monotonically
        # increasing per-payload sequence number + full-snapshot flag.
        # Ordering is self-healing: a lost heartbeat leaves a seq gap the
        # scheduler answers with digests_resync, and the next beat ships
        # a full snapshot.
        self._digests_seq = 0
        self._digests_full_next = True
        self._inbox: queue.Queue = queue.Queue()
        # Set by every _post(): the step thread parks on it when idle
        # instead of polling, and wakes the instant work arrives.
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._reload = threading.Event()
        self._threads: list[threading.Thread] = []
        self._allocated = threading.Event()
        self.refit_version = 0
        self._refit_fetching = False
        # Head-node bookkeeping: finished requests awaiting pickup.
        self._finished: queue.Queue[Request] = queue.Queue()
        self._request_events: dict[str, threading.Event] = {}
        # Live migration (docs/resilience.md). All three maps are
        # step-thread state except _migrated_to, which pollers read from
        # transport threads (entries are write-once strings).
        # rid -> dead peer: flagged for parking, still draining out of
        # the engine (in-flight steps must resolve first).
        self._migration_pending: dict[str, str] = {}
        # rid -> park entry (request, optional KV image, timestamps).
        self._migration_parked: dict[str, dict] = {}
        # rid -> target head: chat_poll redirects followers here after
        # the request shipped away (bounded; see _record_migrated).
        from collections import OrderedDict as _OD

        self._migrated_to: "_OD[str, str]" = _OD()
        # Engine reload/compile in progress — rides heartbeats so the
        # scheduler sweep extends this node's grace instead of declaring
        # a first-compile storm dead.
        self._busy_reloading = False
        # Stall watchdog (obs/watchdog.py, opt-in): progress probes over
        # the step loop, sender queues, migration parks and the admission
        # queue. Off (the default) = no monitor thread, no per-step work.
        self._watchdog = None
        self._watchdog_cfg = (
            (watchdog_degraded_s, watchdog_stalled_s) if watchdog else None
        )
        # Migration progress counter for the watchdog: parks, ship
        # results and restores all count — a parked set whose counter
        # stops moving is a wedged migration path.
        self._migration_progress = 0
        # Cluster timeline shipping: flight events after this cursor
        # ride the next heartbeat in a bounded batch; the cursor only
        # advances when the scheduler's reply lands, so a lost beat just
        # re-ships (the scheduler-side ring dedupes by sequence).
        # _events_assigned maps ring seq -> this node's shipped seq (see
        # _event_batch): assignment is stable across retries so resends
        # reuse their numbers while newer events always number higher.
        self._events_cursor = 0
        self._events_assigned: dict[int, int] = {}
        self._events_seq = 0
        # Async sender pipeline: serialization + socket latency leave
        # the step thread entirely (per-peer bounded in-order queues);
        # overflow or send failure feeds the abort_path flow.
        self.sender = AsyncSender(
            transport, on_failure=self._on_send_failure
        )
        # Disaggregation KV-handoff state (docs/disaggregation.md).
        # The transfer lane is a SECOND AsyncSender: KV page bulk rides
        # its own per-peer FIFOs, so a multi-megabyte handoff can never
        # head-of-line block FORWARD/RELEASE traffic (or vice versa —
        # the data plane keeps its own queue-depth failure horizon).
        from parallax_tpu.runtime.kv_handoff import (
            DEFAULT_CHUNK_BYTES,
            HandoffAssembler,
        )

        self.kv_transfer_chunk_bytes = int(
            kv_transfer_chunk_bytes or DEFAULT_CHUNK_BYTES
        )
        self.kv_sender = AsyncSender(
            transport, max_queue=64,
            on_failure=self._on_kv_send_failure, name="kv",
        )
        # Inbound transfer reassembly (this node as a decode target);
        # swept from the announcer so orphaned partials never linger.
        self._kv_assembler = HandoffAssembler()
        # Source-side ledger (this node as a prefill head). Step-thread
        # state, mirroring the migration maps: rid -> flag time for
        # rows draining out of the in-flight window, rid -> park entry
        # for checkpointed requests moving through the ship ladder.
        self._handoff_pending: dict[str, float] = {}
        self._handoff_parked: dict[str, dict] = {}
        # Watchdog progress for the kv_shipper component: ship results,
        # transfer results and local restores count — parks do not (a
        # churning park stream must not mask a wedged ship path).
        self._handoff_progress = 0
        self._handoff_warned = False
        # Fail fast on a bad wire dtype: deferred to the sender workers
        # it would masquerade as per-frame link failures and abort
        # traffic with a misleading "peer unreachable" reason.
        resolve_wire_dtype(
            self.engine_config.wire_dtype, model_config.dtype
        )
        # Negotiated wire dtype per link: peer -> (dtype | None,
        # expires_at); None means "ship native frames". Entries are
        # written by sender workers (and probe threads) and popped by
        # gossip/heartbeat threads; writes that follow a slow read or
        # RPC go through _wire_lock plus the forget generation counter
        # so a freshly invalidated decision can never be resurrected by
        # an in-flight probe. (The hot-path fresh-hit read stays
        # lock-free — a single atomic get of an immutable tuple.)
        self._wire_dtypes: dict[str, tuple[str | None, float]] = {}
        self._wire_lock = make_lock("node.wire_caps")
        # Per-peer forget counts (never reset — a reset would make an
        # in-flight probe's stale snapshot match again). Ints only,
        # grown per ever-invalidated peer; per-peer so churn on one
        # link never discards another link's probe result.
        self._wire_forget_gen: dict[str, int] = {}
        # Links we already warned about falling back to native frames
        # ("warn once, cached" — steady-state re-confirmations log at
        # debug); cleared when a link negotiates compression so a later
        # degrade warns again.
        self._wire_warned_native: set[str] = set()
        # Per-source receive counters for the transport telemetry,
        # bumped from concurrent transport-dispatch threads and reaped
        # from the heartbeat thread — += is not atomic, so all three
        # paths take the lock (same contract as the sender's per-link
        # stats_lock).
        self._rx_stats: dict[str, dict] = {}
        self._rx_lock = make_lock("node.rx_stats")

        transport.register(proto.FORWARD, self._on_forward)
        transport.register(proto.ABORT, self._on_abort)
        transport.register(proto.RELEASE, self._on_release)
        transport.register("__announce__", self._on_announce)
        transport.register(proto.CHAT_READY, self._on_chat_ready)
        transport.register(proto.CHAT_SUBMIT, self._on_chat_submit)
        transport.register(proto.CHAT_POLL, self._on_chat_poll)
        transport.register(proto.CHAT_STOP, self._on_chat_stop)
        transport.register(proto.WIRE_CAPS, self._on_wire_caps)
        transport.register(proto.CHECKPOINT, self._on_checkpoint)
        transport.register(proto.KV_TRANSFER, self._on_kv_transfer)
        transport.register(proto.KV_RESULT, self._on_kv_result)
        transport.register(proto.PROFILE, self._on_profile)
        transport.register("__ping__", lambda *_: "pong")
        # Cluster-scope profiling (POST /profile/start {"pipeline": ...}):
        # whether THIS stage currently runs a JAX device trace, plus the
        # auto-stop deadline timer (a forgotten cluster profile must not
        # buffer device events without bound).
        self._profiling = False
        self._profile_dir: str | None = None
        self._profile_timer: threading.Timer | None = None
        self._profile_lock = make_lock("node.profile")
        # Head-node chat requests by id (polled by the HTTP frontend;
        # reference: TransformerConnectionHandler.chat_completion proxies to
        # the local HTTP frontend, p2p/server.py:185-221).
        self._chat_requests: dict[str, Request] = {}

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Join, then serve. The join RPC only fetches the allocation; the
        (slow) engine build happens on the step thread so heartbeats flow
        from the first moment — the reference loads its executor in separate
        processes for the same reason (launch.py:250-309)."""
        self.transport.start()
        if self.standalone:
            s, e = self._self_layers
            alloc = {"start_layer": s, "end_layer": e}
            logger.info(
                "%s: scheduler-less, self-assigned layers [%d, %d)",
                self.node_id, s, e,
            )
        else:
            alloc = self._join()
        if self._watchdog_cfg is not None:
            self._start_watchdog(*self._watchdog_cfg)
        for fn in (self._announcer_loop, self._step_loop):
            t = threading.Thread(target=fn, daemon=True, name=fn.__name__)
            t.start()
            self._threads.append(t)
        if "start_layer" in alloc:
            self._post(("reload", alloc))
        else:
            logger.info("%s: joined as standby", self.node_id)

    def stop(self) -> None:
        self._stop.set()
        if self._watchdog is not None:
            self._watchdog.stop()
        for t in self._threads:
            t.join(timeout=3.0)
        self.sender.close()
        self.kv_sender.close()
        if self._gossip_pool is not None:
            self._gossip_pool.shutdown(wait=False, cancel_futures=True)
        if not self.standalone:
            try:
                self.sched_transport.call(
                    self.scheduler_peer, proto.NODE_LEAVE,
                    {"node_id": self.node_id}, timeout=5.0,
                )
            except Exception:
                pass
        self.transport.stop()

    # -- join + elastic reload ----------------------------------------------

    def _sched_peer(self) -> str:
        """Current scheduler address for fire-and-forget sender traffic
        (PEER_DOWN / REQUEST_COMPLETE / MIGRATION_DONE ride the async
        sender, which has no retry-rotate loop of its own — so they at
        least target whichever peer the failover wrapper last proved
        alive; a frame lost across the promotion window is best_effort
        by contract)."""
        st = self.sched_transport
        return st.active_peer if st is not None else self.scheduler_peer

    def _is_scheduler(self, peer: str) -> bool:
        """True for the primary OR any standby: scheduler addresses are
        exempt from peer_down reporting (the failover wrapper handles
        scheduler death; reporting the scheduler to itself is noise)."""
        st = self.sched_transport
        if st is not None:
            return peer in st.peers
        return peer == self.scheduler_peer

    def _join(self) -> dict:
        hw = detect_hardware()
        reply = self.sched_transport.call(
            self.scheduler_peer,
            proto.NODE_JOIN,
            {
                "node_id": self.node_id,
                "hardware": hw.to_dict(),
                # Wire-format capability advertisement: the dtype names
                # this build can decode on activation frames (per-link
                # senders re-confirm via wire_caps before compressing).
                "wire_formats": list(proto.WIRE_DTYPES),
                # Phase specialization: the scheduler keeps pipelines
                # role-homogeneous and phase-filters routing pools.
                "role": self.role,
            },
            timeout=300.0,
        )
        if not reply or ("start_layer" not in reply and "standby" not in reply):
            raise RuntimeError(f"join rejected: {reply}")
        return reply

    def _apply_allocation(self, alloc: dict) -> None:
        if "start_layer" not in alloc:
            return
        self._busy_reloading = True
        try:
            self._apply_allocation_inner(alloc)
        finally:
            self._busy_reloading = False

    def _apply_allocation_inner(self, alloc: dict) -> None:
        model_switched = self._maybe_switch_model(alloc.get("model_name"))
        # Cache-aware routing: the scheduler's join/reload replies carry
        # want_digests, and the engine must be built with digest tracking
        # to honor it (the radix tree keeps its delta log only when
        # asked). A flip without a layer change — strategy switch via
        # scheduler restart — still forces a rebuild; in-flight requests
        # abort, exactly like a reallocation.
        want_digests = bool(alloc.get("want_digests"))
        digests_switched = want_digests != self.engine_config.cache_digests
        if digests_switched:
            self.engine_config.cache_digests = want_digests
        start, end = alloc["start_layer"], alloc["end_layer"]
        if not model_switched and not digests_switched and (start, end) == (
            self.start_layer, self.end_layer
        ):
            return
        logger.info(
            "%s: (re)loading layers [%d, %d)", self.node_id, start, end
        )
        # The old engine's in-flight requests can never finish on the new
        # one (different layers/weights): abort them NOW so polling
        # clients see finished_abort instead of hanging to their deadline,
        # and peers holding mirrors release their pages.
        self._abort_in_flight("node reallocated")
        self.start_layer, self.end_layer = start, end
        model = create_stage_model(
            self.model_config, start, end, tp_size=self.tp_size
        )
        params = self.load_params(model)
        engine = StageEngine(
            model, params, self.engine_config, mesh=self.mesh,
            sp_mesh=self.sp_mesh,
        )
        for name, source in self.lora_adapters.items():
            # Each (re)allocation re-registers every adapter against the
            # stage's new layer range — BEFORE the engine is published:
            # a heartbeat firing mid-registration would otherwise report
            # is_ready with an empty adapter list and transiently drop
            # every advertised adapter variant cluster-wide.
            try:
                engine.load_adapter(name, source)
            except (ValueError, OSError) as e:
                logger.warning("adapter %r failed to load: %s", name, e)
        self.engine = engine
        if (
            self.role == "prefill"
            and engine.host_tier is None
            and not self._handoff_warned
        ):
            # Registered gate (analysis/gates.py): page shipping needs
            # the PR 2 host tier on the source to harvest images.
            self._handoff_warned = True
            logger.warning(
                "%s: kv-image handoff disabled: no host KV tier on this "
                "prefill-role worker — handoffs ship checkpoints only "
                "and the decode pool re-prefills (set --host-cache-bytes "
                "to enable page shipping)", self.node_id,
            )
        # Fresh engine = empty radix tree: the scheduler's digest mirror
        # for this node is stale; the next heartbeat ships a snapshot.
        self._digests_full_next = True
        if model.is_last:
            self._wire_grammar()
        self._restore_refit_cache()
        self._allocated.set()

    def _wire_grammar(self) -> None:
        """Enable json_schema enforcement on a last-stage worker: build the
        tokenizer byte vocabulary once and hand it to the engine. Without a
        real tokenizer on disk, constrained requests abort with a clear
        reason instead of being silently unenforced."""
        if self._grammar_vocab is None:
            if not self.tokenizer_path:
                logger.warning(
                    "%s: no tokenizer path (e.g. after switching to a "
                    "preset model); json_schema requests will be rejected",
                    self.node_id,
                )
                return
            try:
                from parallax_tpu.constrained import (
                    grammar_vocab_from_tokenizer,
                )
                from parallax_tpu.utils.tokenizer import (
                    SimpleTokenizer,
                    load_tokenizer,
                )

                tok = load_tokenizer(self.tokenizer_path)
                if isinstance(tok, SimpleTokenizer):
                    # The byte fallback's ids won't match a real model's
                    # vocabulary — masks built from it would be garbage.
                    raise ValueError(
                        f"no tokenizer files at {self.tokenizer_path}"
                    )
                self._grammar_vocab = grammar_vocab_from_tokenizer(tok)
            except Exception as e:
                logger.warning("%s: grammar vocab unavailable (%s); "
                               "json_schema requests will be rejected",
                               self.node_id, e)
                return
        self.engine.set_grammar_vocab(*self._grammar_vocab)

    def _abort_in_flight(self, reason: str) -> None:
        eng = self.engine
        if eng is None:
            return
        sched = eng.scheduler
        reqs = list(sched.running.values()) + list(sched.wait_queue.values())
        aborted = 0
        for req in reqs:
            if (
                not req.status.is_finished
                and req.request_id in self._migration_pending
            ):
                # Flagged for migration and the engine is going away:
                # park the token-level state NOW (force — the engine and
                # its KV are being discarded wholesale, so no image
                # harvest and no in-flight hazard).
                dead = self._migration_pending.pop(req.request_id)
                self._park_request(eng, req, dead, force=True)
                continue
            if not req.status.is_finished:
                req.abort(reason)
            sched.release_request(req)
            self._finish(req)
            aborted += 1
        if aborted:
            logger.warning("%s: aborted %d in-flight requests (%s)",
                           self.node_id, aborted, reason)

    def _maybe_switch_model(self, model_name: str | None) -> bool:
        """Live model switch (/scheduler/init): the allocation names a
        different model than previous allocations — re-resolve config +
        weights via ``resolve_model`` or refuse the allocation (the worker
        cannot serve weights it does not have). The FIRST allocation's name
        is recorded, not compared: scheduler and worker may spell the same
        model differently (preset key vs checkpoint _name_or_path)."""
        if not model_name:
            return False
        if self._served_model_name is None or (
            model_name == self._served_model_name
        ):
            self._served_model_name = model_name
            return False
        if self.resolve_model is None:
            raise RuntimeError(
                f"scheduler switched to {model_name!r} but this worker has "
                f"only {self.model_config.model_name!r} locally (no "
                "resolver); restart the worker with the new --model-path"
            )
        config, load_params = self.resolve_model(model_name)
        # Record the new name only AFTER a successful resolve: a failed
        # switch must keep retrying on later heartbeats, never silently
        # serve the old model under the new name.
        self._served_model_name = model_name
        logger.warning("%s: switching model %s -> %s", self.node_id,
                       self.model_config.model_name, model_name)
        self.model_config = config
        if load_params is not None:
            self.load_params = load_params
        else:
            self.load_params = self._random_params
        # The new model's tokenizer differs: rebuild the grammar vocab
        # lazily from the new checkpoint (presets have no tokenizer).
        self._grammar_vocab = None
        self.tokenizer_path = model_name if os.path.isdir(model_name) else None
        return True

    def _restore_refit_cache(self) -> None:
        """Reload the newest cached refit version after a (re)start so a
        crashed worker resumes serving pushed weights (the reference keeps
        3 disk versions for the same reason, p2p/server.py:434-446)."""
        if self.refit_store is None or self.engine is None:
            return
        from parallax_tpu.p2p.refit import apply_prefetched

        # Newest first, falling back through older intact versions (a crash
        # mid-save could have left the newest unreadable). Versions cached
        # for a different model or layer range must never be applied — the
        # stage-local keys would shape-check but hold other layers' weights.
        for version in reversed(self.refit_store.versions()):
            if version <= self.refit_version:
                return
            meta = self.refit_store.load_meta(version)
            if meta is None or (
                meta.get("model_name") != self.model_config.model_name
                or meta.get("start_layer") != self.start_layer
                or meta.get("end_layer") != self.end_layer
            ):
                logger.info(
                    "refit cache v%d skipped (cached for %s [%s, %s))",
                    version, (meta or {}).get("model_name"),
                    (meta or {}).get("start_layer"),
                    (meta or {}).get("end_layer"),
                )
                continue
            try:
                tensors = self.refit_store.load(version)
                apply_prefetched(self.engine, tensors, version)
                self.refit_version = version
                return
            except Exception:
                logger.exception("refit cache restore v%d failed", version)

    def _random_params(self, model: StageModel):
        dtype = (
            jnp.bfloat16
            if self.engine_config.kv_dtype == "bfloat16"
            else jnp.float32
        )
        # Deterministic per layer range so every run of a stage agrees.
        return model.init_params(
            jax.random.key(model.start_layer * 1000 + model.end_layer),
            dtype=dtype,
        )

    # -- stall watchdog ------------------------------------------------------

    def _start_watchdog(self, degraded_s: float, stalled_s: float) -> None:
        """Build and start the per-node stall watchdog (docs/
        observability.md): each component registers a (pending, progress)
        probe; pending work whose progress counter stops moving walks the
        ok -> degraded -> stalled state machine, emits flight events (so
        the stall lands in the cluster timeline) and flips the deep
        ``/healthz``. The probes run on the monitor thread at poll
        cadence — the step/sender hot paths pay one dict increment."""
        from parallax_tpu.obs.watchdog import StallWatchdog

        wd = StallWatchdog(
            node_id=self.node_id,
            degraded_after_s=degraded_s,
            stalled_after_s=stalled_s,
        )

        def _step_pending() -> float:
            eng = self.engine
            if eng is None:
                return 0.0
            return float(eng.scheduler.num_requests())

        wd.register_beat("step_loop", _step_pending)

        def _sender_probe():
            # Both lanes: the data plane and the KV-transfer lane — a
            # wedged kv lane stalls handoffs exactly like a wedged
            # FORWARD link stalls decode.
            stats = dict(self.sender.stats())
            for p, s in self.kv_sender.stats().items():
                stats[f"kv:{p}"] = s
            pending = sum(
                s.get("queue_depth", 0) or 0 for s in stats.values()
            )
            # Frames leaving the queue EITHER way is progress: a dead
            # peer's drops route through abort_path, which is handling,
            # not a stall.
            progress = sum(
                (s.get("frames_out", 0) or 0)
                + (s.get("drops", 0) or 0)
                + (s.get("errors", 0) or 0)
                for s in stats.values()
            )
            worst = max(
                (s.get("queue_depth", 0) or 0 for s in stats.values()),
                default=0,
            )
            return float(pending), float(progress), f"deepest queue {worst}"

        wd.register("sender", _sender_probe)

        def _migration_probe():
            pending = len(self._migration_pending) + len(
                self._migration_parked
            )
            return (
                float(pending), float(self._migration_progress),
                f"{len(self._migration_parked)} parked",
            )

        wd.register("migration", _migration_probe)

        def _kv_shipper_probe():
            # Disaggregation handoff path: flagged + parked requests on
            # this (prefill) head plus inbound transfers assembling on
            # this (decode) head. Progress counts ship rounds, transfer
            # results and local restores PLUS frame-level movement both
            # ways (outbound lane frames_out, inbound assembler feeds):
            # a large image legitimately spends many seconds in flight,
            # and frames moving steadily must read as progress — only a
            # parked/assembling set with NOTHING moving is a wedged
            # shipper lane (the PR 8 false-instant-stall lesson).
            pending = (
                len(self._handoff_pending)
                + len(self._handoff_parked)
                + self._kv_assembler.partial_count()
            )
            frames_out = sum(
                (s.get("frames_out", 0) or 0)
                for s in self.kv_sender.stats().values()
            )
            progress = (
                self._handoff_progress
                + self._kv_assembler.frames_total
                + frames_out
            )
            return (
                float(pending), float(progress),
                f"{len(self._handoff_parked)} parked, "
                f"{self._kv_assembler.partial_count()} assembling",
            )

        wd.register("kv_shipper", _kv_shipper_probe)

        def _admission_probe():
            eng = self.engine
            if eng is None:
                return 0.0, 0.0, ""
            sched = eng.scheduler
            return (
                float(len(sched.wait_queue)),
                float(sched.admitted_total),
                f"{len(sched.running)} running",
            )

        wd.register("admission", _admission_probe)

        # Recompile-storm probe: the device plane's compile observatory
        # advances progress only while no program family is storming, so
        # a storm freezes the counter and walks ok -> degraded ->
        # stalled like any other wedged component (docs/kernels.md).
        from parallax_tpu.obs.device import get_device_plane

        wd.register("compile", get_device_plane().compile.probe)
        wd.start()
        self._watchdog = wd

    def health_summary(self) -> dict:
        """Deep-health payload: the watchdog's component state machine
        (or a shallow ok when the watchdog is off). Rides heartbeats and
        backs ``/healthz`` on worker frontends."""
        wd = self._watchdog
        if wd is None:
            return {"status": "ok", "components": {}, "causes": []}
        return wd.summary()

    # -- announcer (heartbeat) ----------------------------------------------

    def _announcer_loop(self) -> None:
        if self.standalone:
            while not self._stop.is_set():
                try:
                    self._gossip_beat()
                    self._reap_rx_stats()
                except Exception as e:
                    logger.warning("gossip beat failed: %s", e)
                self._stop.wait(self.heartbeat_interval_s)
            return
        while not self._stop.is_set():
            try:
                self._reap_rx_stats()
                # Inbound KV transfers whose source died mid-flight are
                # discarded here (the request recovers through the
                # source's result timeout / the client resume ladder).
                self._kv_assembler.sweep()
                logger.debug("%s: heartbeat", self.node_id)
                if self.node_id.startswith("relay:") and hasattr(
                    self.transport, "register_at_relay"
                ):
                    # Refresh the reverse route every beat: idempotent,
                    # and it re-establishes the route after a dropped
                    # relay connection without any extra liveness logic.
                    self.transport.register_at_relay(
                        self.node_id.rsplit("@", 1)[1]
                    )
                eng = self.engine
                ev_batch, ev_cursor = self._event_batch()
                reply = self.sched_transport.call(
                    self.scheduler_peer,
                    proto.NODE_UPDATE,
                    {
                        "node_id": self.node_id,
                        # Highest scheduler epoch this worker has seen:
                        # the fencing signal — a primary hearing a
                        # higher epoch than its own knows a standby
                        # promoted past it and refuses further
                        # mutations (docs/ha.md).
                        "epoch": self.sched_transport.epoch,
                        # Prefix-digest delta for the scheduler's routing
                        # index (None unless cache-aware routing enabled
                        # digest tracking via the allocation).
                        "cache_digests": self._digest_heartbeat(eng),
                        "is_ready": eng is not None,
                        "load": eng.scheduler.num_requests() if eng else 0,
                        "layer_latency_ms": (
                            eng.layer_latency_ms_ewma if eng else None
                        ),
                        "step_timing": (
                            eng.step_timing.summary() if eng else None
                        ),
                        "cache_stats": (
                            eng.cache_stats() if eng else None
                        ),
                        # Active attention-kernel impl + per-path
                        # dispatch counts (pallas-fused / pallas-split /
                        # xla) — surfaced per node in /cluster/status.
                        "kernel": (
                            eng.kernel_dispatch_summary() if eng else None
                        ),
                        # Speculative-decoding ledger (acceptance rate +
                        # accepted-tokens/chip-s; None while spec is
                        # off) — surfaced per node in /cluster/status.
                        "spec": (
                            eng.spec_summary() if eng else None
                        ),
                        # Constrained-decoding ledger (in-window grammar
                        # rows, mask steps, table builds/cache hits,
                        # sync fallbacks; None until a feature batch
                        # runs) — surfaced per node in /cluster/status.
                        "constrained": (
                            eng.constrained_summary() if eng else None
                        ),
                        # Per-link activation-transport telemetry
                        # (bytes/frames each way, serialize/send ms,
                        # queue depth, compression ratio) — surfaced in
                        # /cluster/status.
                        "transport": self.transport_stats(),
                        # Histogram snapshots (TTFT/TPOT/step timing/
                        # batch size) from the local metrics registry —
                        # the scheduler merges them into cluster-wide
                        # percentiles in /cluster/status.
                        "metrics": self._metrics_snapshot(),
                        "refit_version": self.refit_version,
                        "lora_adapters": (
                            eng.adapter_names() if eng else []
                        ),
                        # Engine reload/compile in progress: the
                        # scheduler's sweep extends our grace instead of
                        # declaring the compile dead (suspect state).
                        "busy": self._busy_reloading,
                        # Goodput ledger payload (useful/wasted token
                        # buckets + serve/compile/swap/migrate time) —
                        # merged cluster-wide in /cluster/status.
                        "goodput": self._goodput_heartbeat(),
                        # Device attribution plane (HBM ledger classes,
                        # compile observatory, per-program device time)
                        # — merged cluster-wide in /cluster/status and
                        # served raw via GET /debug/device.
                        "device": self._device_heartbeat(),
                        # Watchdog health state machine (None when off):
                        # the scheduler surfaces sick-but-alive nodes,
                        # not just dead ones.
                        "health": (
                            self._watchdog.summary()
                            if self._watchdog is not None else None
                        ),
                        # Bounded flight-event batch for the cluster
                        # timeline (sequence-numbered; resends dedupe).
                        "events": ev_batch,
                    },
                    timeout=10.0,
                )
                # The reply landed, so the scheduler ingested this batch:
                # advance the cursor and prune the acked seq
                # assignments. A failed beat re-ships from the old
                # cursor with the SAME numbers (stable assignment) and
                # the timeline dedupes by sequence.
                self._events_cursor = ev_cursor
                if self._events_assigned:
                    self._events_assigned = {
                        rs: s for rs, s in self._events_assigned.items()
                        if rs > ev_cursor
                    }
                if reply and reply.get("drain"):
                    # A pipeline through these dead peers is dissolving:
                    # checkpoint the affected requests to a surviving
                    # pipeline instead of aborting them. Posted BEFORE
                    # any reload below so the step thread parks them
                    # while their state still exists.
                    self._post((
                        "drain", [str(x) for x in reply["drain"]]
                    ))
                if reply and reply.get("digests_resync"):
                    # The scheduler saw a sequence gap (its restart, a
                    # dropped beat): ship a full snapshot next beat.
                    self._digests_full_next = True
                if (
                    reply and isinstance(reply.get("role"), str)
                    and reply["role"] in ("prefill", "decode", "mixed")
                    and reply["role"] != self.role
                ):
                    # QoS autoscaler re-role (docs/qos.md): adopt the
                    # new phase in place — same layers, no reload. A
                    # decode->prefill move drains its in-flight decodes
                    # through the ordinary handoff machinery on the
                    # next step-loop passes (zero aborts).
                    old_role = self.role
                    self.role = reply["role"]
                    logger.warning(
                        "%s: re-roled %s -> %s by the scheduler",
                        self.node_id, old_role, self.role,
                    )
                    from parallax_tpu.obs.flight import get_flight

                    get_flight().event(
                        "qos_rerole", node=self.node_id,
                        role=self.role, prev=old_role,
                    )
                if reply and "qos_shed" in reply:
                    # Cluster shed verdict: OR'd with the engine's own
                    # local controller (docs/qos.md).
                    eng = self.engine
                    if eng is not None and eng.scheduler.qos is not None:
                        eng.scheduler.qos.set_remote_shed(
                            bool(reply["qos_shed"])
                        )
                if reply and reply.get("rejoin"):
                    # Scheduler lost us (restart or heartbeat eviction):
                    # auto-rejoin (reference rpc_connection_handler.py:71-113).
                    logger.warning("%s: scheduler asked for rejoin", self.node_id)
                    rejoin_alloc = self._join()
                    if "start_layer" in rejoin_alloc:
                        self._post(("reload", rejoin_alloc))
                elif reply and reply.get("start_layer") is not None:
                    if (
                        reply["start_layer"],
                        reply["end_layer"],
                    ) != (self.start_layer, self.end_layer):
                        # Scheduler moved us: reload on the step thread.
                        self._post(("reload", reply))
                    elif (
                        reply.get("refit_index")
                        and reply.get("refit_version", 0) > self.refit_version
                    ):
                        self._post((
                            "refit",
                            reply["refit_version"],
                            reply["refit_index"],
                        ))
            except Exception as e:
                logger.warning("heartbeat failed: %s", e)
            self._stop.wait(self.heartbeat_interval_s)

    def _event_batch(self) -> tuple[dict | None, int]:
        """Next bounded flight-event batch for the cluster timeline,
        plus the (ring-domain) cursor to adopt
        once the scheduler's reply confirms the batch landed. Tagged
        with our boot epoch so a restart resets the scheduler-side gap
        accounting instead of counting a false gap.

        Shipped events are RENUMBERED into this node's own contiguous
        sequence: in-process swarms share one flight ring whose global
        sequence interleaves siblings, and shipping those raw numbers
        would make the scheduler count every interleave as a loss. The
        ring-seq -> shipped-seq assignment (``_events_assigned``) is
        STABLE across retries — a resend after a lost reply reuses the
        numbers the events were first shipped under (so the timeline
        dedupes them), while events newly recorded since always get
        fresh, higher numbers (so the dedupe cannot swallow them even
        if the ring evicted part of the unacked window in between).
        Assignments are pruned on ack. Real losses — the ring evicting
        events faster than beats ship them — surface as an explicit
        ``lost`` count instead."""
        try:
            from parallax_tpu.obs.flight import get_flight

            fl = get_flight()
            events, cursor = fl.events_since(
                self._events_cursor, limit=256, node=self.node_id
            )
            # Ring overrun: events between our cursor and the ring's
            # oldest survivor were evicted before we could ship them.
            # (In-process swarms share the ring, so this is an upper
            # bound — sibling-tagged evictions inflate it.)
            lost = 0
            oldest = fl.oldest_seq()
            if self._events_cursor and oldest > self._events_cursor + 1:
                lost = oldest - self._events_cursor - 1
            cursor = max(cursor, oldest - 1 if oldest else 0)
        except Exception:  # pragma: no cover - obs never breaks beats
            return None, self._events_cursor
        if not events and not lost:
            return None, cursor
        batch = []
        for e in events:
            ring_seq = int(e.get("seq") or 0)
            seq = self._events_assigned.get(ring_seq)
            if seq is None:
                self._events_seq += 1
                seq = self._events_seq
                self._events_assigned[ring_seq] = seq
            batch.append(dict(e, seq=seq))
        payload = {"epoch": self._epoch, "batch": batch}
        if lost:
            payload["lost"] = lost
        return payload, cursor

    def _goodput_heartbeat(self) -> dict | None:
        """Per-node goodput payload (never raises)."""
        try:
            import jax

            from parallax_tpu.obs.goodput import get_goodput

            return get_goodput().payload(chips=jax.local_device_count())
        except Exception:  # pragma: no cover - obs never breaks beats
            return None

    def _device_heartbeat(self) -> dict | None:
        """Per-node device-attribution payload (never raises)."""
        try:
            from parallax_tpu.obs.device import get_device_plane

            return get_device_plane().payload()
        except Exception:  # pragma: no cover - obs never breaks beats
            return None

    def _digest_heartbeat(self, eng) -> dict | None:
        """Prefix-digest payload for one heartbeat: a delta normally, a
        full snapshot after (re)build or a scheduler resync request.
        Sequence-numbered per payload; a beat lost in transit leaves a
        gap the scheduler answers with ``digests_resync``. None (zero
        bytes, zero work) unless the allocation asked for digests."""
        if eng is None or not self.engine_config.cache_digests:
            return None
        try:
            payload = eng.cache_digest_payload(full=self._digests_full_next)
        except Exception:  # pragma: no cover - telemetry never kills beats
            logger.exception("digest payload failed")
            return None
        if payload is None:
            return None
        self._digests_full_next = False
        self._digests_seq += 1
        payload["seq"] = self._digests_seq
        return payload

    # -- scheduler-less gossip (reference DHT announce + dijkstra routing,
    # p2p/server.py:569-626) -------------------------------------------------

    def _fresh_peer_ids(self, now: float) -> set[str]:
        """Peers whose announcements are within the TTL (THE liveness
        definition — route computation, gossip fan-out and the standalone
        sweep must all agree on it)."""
        with self._peer_lock:
            return {
                nid for nid, b in self._peer_blocks.items()
                if now - b["t"] <= self.peer_ttl_s
            }

    def _known_blocks(self) -> list[dict]:
        """Fresh announcements incl. our own, with ages so receivers can
        order third-party info correctly."""
        now = time.monotonic()
        out = []
        if self.start_layer >= 0:
            out.append({
                "node_id": self.node_id, "start": self.start_layer,
                "end": self.end_layer, "ready": self.engine is not None,
                "age_s": 0.0, "epoch": self._epoch,
            })
        with self._peer_lock:
            for nid, b in self._peer_blocks.items():
                age = now - b["t"]
                if age <= self.peer_ttl_s:
                    out.append({
                        "node_id": nid, "start": b["start"], "end": b["end"],
                        "ready": b["ready"], "age_s": age,
                        "epoch": b.get("epoch"),
                    })
        return out

    def _merge_blocks(
        self, blocks: list[dict], from_peer: str | None = None
    ) -> None:
        now = time.monotonic()
        with self._peer_lock:
            for b in blocks or []:
                nid = b.get("node_id")
                if not nid or nid == self.node_id:
                    continue
                t = now - float(b.get("age_s", 0.0))
                prev = self._peer_blocks.get(nid)
                if prev is None or t > prev["t"]:
                    new_ep = b.get("epoch")
                    prev_ep = prev.get("epoch") if prev else None
                    # A peer's OWN announcement is authoritative for its
                    # boot epoch — including an absent one (it restarted
                    # as an epoch-less older build). Third-party blocks
                    # are not: an epoch-less intermediary strips the
                    # field on relay, so there a missing epoch keeps the
                    # known one — otherwise direct/relayed alternation
                    # would thrash the cache.
                    direct = nid == from_peer
                    epoch = new_ep if direct else (new_ep or prev_ep)
                    # A changed boot epoch means the peer restarted —
                    # possibly as a different build — faster than the
                    # TTL could notice. Its negotiated wire dtype is
                    # stale (the new process may not decode it, and a
                    # one-way FORWARD would fail silently on the
                    # receiver), so the next frame must re-probe. An
                    # epoch appearing where none was known (old build
                    # restarting as a current one) or disappearing from
                    # a direct announcement (downgrade) counts too.
                    changed = (
                        epoch != prev_ep if direct
                        else bool(new_ep) and new_ep != prev_ep
                    )
                    if prev is not None and changed:
                        self._forget_wire_dtype(nid)
                    self._peer_blocks[nid] = {
                        "start": int(b["start"]), "end": int(b["end"]),
                        "ready": bool(b.get("ready")), "t": t,
                        "epoch": epoch,
                    }

    def _gossip_beat(self) -> None:
        """Announce our block to every static peer and every FRESH known
        peer; merge what they know back (transitive discovery). Expired
        entries are pruned — dead peers must not be re-dialed forever
        (each dial burns a connect timeout, which would starve live
        announcements past the TTL and flap routes)."""
        blocks = self._known_blocks()
        now = time.monotonic()
        with self._peer_lock:
            for nid, b in list(self._peer_blocks.items()):
                if now - b["t"] > 3 * self.peer_ttl_s:
                    del self._peer_blocks[nid]
                    # Forget the negotiated wire dtype with the peer: if
                    # it rejoins it may be a different build, and the
                    # first frame to it must re-run the caps probe.
                    # (The inbound counters are reaped separately by
                    # _reap_rx_stats, under the rx lock.)
                    self._forget_wire_dtype(nid)
        known = self._fresh_peer_ids(now)
        timeout = min(5.0, max(1.0, self.heartbeat_interval_s))

        def announce(peer: str) -> None:
            try:
                reply = self.transport.call(
                    peer, "__announce__", {"blocks": blocks},
                    timeout=timeout,
                )
            except Exception as e:
                logger.debug("announce to %s failed: %s", peer, e)
                return
            if isinstance(reply, dict):
                self._merge_blocks(reply.get("blocks"), from_peer=peer)

        # Concurrent dials off a persistent pool: dead STATIC peers
        # (never pruned — they are the operator-given bootstrap list)
        # must not serialize connect timeouts past the TTL and flap live
        # routes, and a fixed peer set must not churn a thread per peer
        # per beat. The bounded pool also caps in-flight dials when a
        # blackholed peer's call overruns the beat.
        if self._gossip_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._gossip_pool = ThreadPoolExecutor(
                max_workers=min(16, 4 + len(self.static_peers)),
                thread_name_prefix="gossip",
            )
        futures = [
            self._gossip_pool.submit(announce, p)
            for p in set(self.static_peers) | known if p != self.node_id
        ]
        from concurrent.futures import wait as _fwait

        _fwait(futures, timeout=timeout + 1.0)

        # The gossip TTL doubles as the standalone liveness sweep: an
        # in-flight request routed through an expired peer would
        # otherwise hang to its request timeout when the peer died
        # BETWEEN packets (nothing in flight -> no send failure to
        # trigger abort_path). Scheduler mode gets this from the
        # heartbeat sweep; here the announcements are the heartbeats.
        # The request scan itself runs on the step thread (the scheduler
        # dicts are single-threaded state); this beat only ships the
        # freshness snapshot over.
        if self.engine is not None:
            fresh = self._fresh_peer_ids(time.monotonic())
            fresh.add(self.node_id)
            self._post(("liveness", fresh))

    def _on_announce(self, peer: str, payload: dict):
        self._merge_blocks((payload or {}).get("blocks"), from_peer=peer)
        return {"blocks": self._known_blocks()}

    def _on_chat_ready(self, _peer: str, _payload):
        """Readiness probe for standalone chat hosts: can this head serve
        a request submitted with an EMPTY routing table right now? A
        standalone head routes via gossip; a scheduler-managed worker can
        only if it hosts the whole model (partial shards need the
        scheduler's routing, which the chat host bypasses). Maps
        not-ready to the frontend's retryable 503 instead of a
        post-submit 502."""
        if self.engine is None:
            return {"ready": False}
        if self.standalone:
            return {"ready": self.local_route() is not None}
        full = (
            self.start_layer == 0
            and self.end_layer == self.model_config.num_hidden_layers
        )
        return {"ready": full}

    def local_route(self) -> list[str] | None:
        """Head-side routing table with no scheduler: fewest-hops chain of
        announced READY blocks from our end layer to num_layers (the
        reference's dijkstra over layer boundaries with unit edge cost)."""
        if self.start_layer != 0 or self.engine is None:
            return None
        num_layers = self.model_config.num_hidden_layers
        fresh = self._fresh_peer_ids(time.monotonic())
        by_start: dict[int, list[tuple[str, int]]] = {}
        with self._peer_lock:
            for nid, b in self._peer_blocks.items():
                if nid not in fresh or not b["ready"]:
                    continue
                by_start.setdefault(b["start"], []).append((nid, b["end"]))

        best: dict[int, list[str] | None] = {num_layers: []}

        def chain(boundary: int) -> list[str] | None:
            if boundary in best:
                return best[boundary]
            best[boundary] = None          # cycle guard
            result = None
            for nid, end in by_start.get(boundary, []):
                if end <= boundary:
                    continue
                tail = chain(end)
                if tail is not None and (
                    result is None or 1 + len(tail) < len(result)
                ):
                    result = [nid] + tail
            best[boundary] = result
            return result

        tail = chain(self.end_layer)
        if tail is None:
            # Diagnose the common operator error: layers are all hosted but
            # block boundaries don't meet exactly (e.g. [0,14) + [10,28)).
            # Stages are jit-compiled for their whole slice, so a route
            # cannot enter a block mid-way — boundaries must match.
            covered = set(range(self.start_layer, self.end_layer))
            for start, blocks in by_start.items():
                for _nid, end in blocks:
                    covered.update(range(start, end))
            if covered >= set(range(num_layers)):
                logger.warning(
                    "no route: every layer is hosted but block boundaries "
                    "do not meet exactly (blocks chain only when one "
                    "worker's --end-layer equals the next's --start-layer)"
                )
            return None
        return [self.node_id] + tail

    # -- wire-format negotiation + transport telemetry -----------------------

    def _on_wire_caps(self, _peer: str, _payload):
        """Per-link capability answer: the tensor dtypes this build can
        decode. A sender only compresses a link after the receiving peer
        lists the requested wire dtype here."""
        return {"formats": list(proto.WIRE_DTYPES)}

    def _on_profile(self, _peer: str, payload: dict):
        """Cluster-scope profiling fanout target: start/stop a JAX device
        trace on THIS stage. The frontend fans the same action to every
        node of a pipeline so all stages trace one wall-clock window;
        the reply feeds the per-node trace-dir manifest. ``max_seconds``
        arms a local auto-stop timer — a frontend that dies mid-profile
        must not leave workers buffering device events forever."""
        payload = payload or {}
        action = str(payload.get("action") or "")
        try:
            import jax
        except Exception as e:  # pragma: no cover - jax always present
            return {"node_id": self.node_id, "error": str(e)}
        with self._profile_lock:
            if action == "start":
                if self._profiling:
                    return {
                        "node_id": self.node_id,
                        "error": "profiler already running",
                        "dir": self._profile_dir,
                    }
                out_dir = str(payload.get("dir") or "/tmp/parallax-profile")
                try:
                    max_seconds = float(payload.get("max_seconds") or 120.0)
                except (TypeError, ValueError):
                    max_seconds = 120.0
                try:
                    jax.profiler.start_trace(out_dir)
                except Exception as e:
                    return {"node_id": self.node_id, "error": str(e)}
                self._profiling = True
                self._profile_dir = out_dir
                self._profile_timer = threading.Timer(
                    max(1.0, max_seconds), self._profile_autostop
                )
                self._profile_timer.daemon = True
                self._profile_timer.start()
                return {
                    "node_id": self.node_id, "profiling": True,
                    "dir": out_dir,
                }
            if action == "stop":
                if not self._profiling:
                    return {
                        "node_id": self.node_id,
                        "error": "profiler not running",
                    }
                if self._profile_timer is not None:
                    self._profile_timer.cancel()
                    self._profile_timer = None
                try:
                    jax.profiler.stop_trace()
                except Exception as e:
                    return {"node_id": self.node_id, "error": str(e)}
                finally:
                    self._profiling = False
                return {
                    "node_id": self.node_id, "profiling": False,
                    "dir": self._profile_dir,
                }
        return {"node_id": self.node_id,
                "error": f"unknown action {action!r}"}

    def _profile_autostop(self) -> None:
        """max_seconds deadline fired without an explicit stop."""
        with self._profile_lock:
            if not self._profiling:
                return
            self._profiling = False
            self._profile_timer = None
            logger.warning(
                "%s: profiler auto-stop: max_seconds deadline reached",
                self.node_id,
            )
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:  # pragma: no cover - trace teardown races
                logger.exception("profiler auto-stop failed")

    # Cached wire-dtype decisions re-probe after this long. Gossip mode
    # catches a restarted peer through its boot epoch; scheduler mode
    # has no such signal when the restart leaves the topology unchanged
    # (same address, same layers -> no reload, and a quiescent link sees
    # no send failure), so the cache itself must age out. One capability
    # RPC per link per interval, on the sender worker.
    WIRE_DTYPE_REFRESH_S = 300.0
    # Retry horizon after a TRANSIENT probe failure: frames ship native
    # meanwhile. Without this negative cache, every frame on a link
    # whose call path is degraded (but whose one-way sends succeed)
    # would block the sender worker a full probe timeout — throttling
    # the queue into overflow and aborting a deliverable path.
    WIRE_PROBE_RETRY_S = 30.0

    def _wire_dtype_for(self, peer: str) -> str | None:
        """Negotiated wire dtype for one link (cached). Runs on the
        sender worker, never the step thread — the first frame to a peer
        pays one short capability RPC. Peers that cannot answer (older
        build, interop) get native-precision frames."""
        want = resolve_wire_dtype(
            self.engine_config.wire_dtype, self.model_config.dtype
        )
        if want is None:
            return None
        now = time.monotonic()
        # Lock-free fresh-hit read: the entry can be popped concurrently
        # (epoch change, TTL prune, send failure) and a check-then-index
        # pair would KeyError into the worker's failure path, aborting a
        # healthy link.
        entry = self._wire_dtypes.get(peer)
        if entry is not None and now < entry[1]:
            return entry[0]
        if entry is not None:
            # Expired mid-life: serve the stale decision and revalidate
            # OFF this worker. A blocking probe here stalls every frame
            # queued behind it, and a mid-life queue can be deep — a
            # slow answer at decode cadence would overflow it and
            # hard-abort a healthy link. The placeholder horizon also
            # stops a probe stampede while the answer is in flight. The
            # placeholder is written under the lock AFTER re-reading:
            # if a forget raced in, the stale decision must not come
            # back (the peer may be a different build now).
            with self._wire_lock:
                entry = self._wire_dtypes.get(peer)
                if entry is None:
                    stale = None     # forgotten: ship native, re-probe
                else:
                    stale = entry[0]
                    self._wire_dtypes[peer] = (
                        stale, now + self.WIRE_PROBE_RETRY_S
                    )
            if entry is not None:
                threading.Thread(
                    target=self._negotiate_wire_dtype,
                    args=(peer, want, 10.0),
                    daemon=True, name=f"wirecaps-{peer}",
                ).start()
                return stale
        # No entry: first contact, or a forget raced in. A SHORT
        # blocking probe is only safe against a near-empty queue (first
        # contact, where it keeps the first hop's frames compressed);
        # measure rather than assume — after an epoch-change forget on
        # a busy link the queue can be deep, and blocking 1 s in front
        # of it could overflow it into a hard abort.
        if self.sender.queue_depth(peer) <= 8:
            self._negotiate_wire_dtype(peer, want, timeout=1.0)
            entry = self._wire_dtypes.get(peer)
            return entry[0] if entry is not None else None
        now = time.monotonic()
        with self._wire_lock:
            if self._wire_dtypes.get(peer) is None:
                self._wire_dtypes[peer] = (
                    None, now + self.WIRE_PROBE_RETRY_S
                )
        threading.Thread(
            target=self._negotiate_wire_dtype, args=(peer, want, 10.0),
            daemon=True, name=f"wirecaps-{peer}",
        ).start()
        return None

    def _negotiate_wire_dtype(
        self, peer: str, want: str, timeout: float
    ) -> None:
        """Blocking capability probe + cache update. Called inline for a
        brand-new link, from a one-shot background thread on refresh.
        The result is discarded if THIS peer was invalidated while the
        RPC was in flight (per-peer generation count): a forget during
        the probe means the answer may describe a process that no
        longer exists, and re-caching it for the full horizon would
        resurrect exactly the decision the forget killed. Forgets are
        rare; a discarded answer just re-probes on the next frame."""
        gen = self._wire_forget_gen.get(peer, 0)
        # "Warn once, cached": the first native fallback on a link is
        # news for the operator; the periodic refresh re-confirming it
        # is steady state and logs at debug. A link that upgrades to
        # compression re-arms the warning for a later degrade.
        def log_native(msg, *args):
            if peer not in self._wire_warned_native:
                self._wire_warned_native.add(peer)
                logger.warning(msg, *args)
            else:
                logger.debug(msg, *args)
        try:
            caps = self.transport.call(
                peer, proto.WIRE_CAPS, None, timeout=timeout
            )
        except Exception as e:
            if NO_HANDLER_MARK in str(e):
                # Definitive answer: an older/interop build with no
                # WIRE_CAPS handler will not grow one mid-life, so
                # cache the native decision for the full horizon —
                # re-probing (and warning) per frame would stall the
                # sender at decode cadence. A restart that adds
                # support invalidates this like any other rebuild
                # (epoch change / link failure / TTL expiry).
                log_native(
                    "%s: peer %s has no wire_caps handler (older "
                    "build?); sending native frames on this link",
                    self.node_id, peer,
                )
                self._cache_wire_dtype(
                    peer, None, self.WIRE_DTYPE_REFRESH_S, gen
                )
                return
            # Transient probe failure (peer still booting, blip):
            # frames ship native under a SHORT negative cache, so a
            # startup race never disables compression for the link's
            # lifetime, and a degraded call path never stalls the
            # sender worker once per frame.
            log_native(
                "%s: wire_caps probe to %s failed (%s); sending native "
                "frames, retrying in %ds",
                self.node_id, peer, e, int(self.WIRE_PROBE_RETRY_S),
            )
            self._cache_wire_dtype(
                peer, None, self.WIRE_PROBE_RETRY_S, gen
            )
            return
        got = None
        formats = set((caps or {}).get("formats") or ())
        if want in formats:
            got = want
            self._wire_warned_native.discard(peer)
        else:
            log_native(
                "%s: peer %s cannot decode wire dtype %s; sending "
                "native frames on this link", self.node_id, peer, want,
            )
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "wire_dtype", node=self.node_id, peer=peer, want=want,
            negotiated=got,
        )
        self._cache_wire_dtype(peer, got, self.WIRE_DTYPE_REFRESH_S, gen)

    def _cache_wire_dtype(
        self, peer: str, dtype: str | None, ttl: float, gen: int
    ) -> None:
        with self._wire_lock:
            if self._wire_forget_gen.get(peer, 0) == gen:
                self._wire_dtypes[peer] = (dtype, time.monotonic() + ttl)

    def _forget_wire_dtype(self, peer: str) -> None:
        """Drop a link's negotiated wire dtype — the peer failed,
        restarted or departed, and may come back as a different build;
        the next frame re-probes. Bumps the peer's generation count so
        a probe already in flight to it discards its (possibly
        pre-restart) answer instead of resurrecting it."""
        with self._wire_lock:
            self._wire_forget_gen[peer] = (
                self._wire_forget_gen.get(peer, 0) + 1
            )
            self._wire_dtypes.pop(peer, None)

    def _on_send_failure(self, peer: str, reason: str) -> None:
        """Sender pipeline failure (queue overflow or dead peer): route
        into the abort_path flow on the step thread — exactly what a
        synchronous send failure used to trigger inline. The negotiated
        wire dtype is dropped with the link: a failed peer may come back
        as a different build (e.g. without fp8 decode), so the next
        frame re-probes instead of shipping frames it cannot parse."""
        logger.error("%s: async send to %s failed: %s",
                     self.node_id, peer, reason)
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "abort_path", node=self.node_id, peer=peer, reason=reason,
        )
        self._forget_wire_dtype(peer)
        if not self.standalone and not self._is_scheduler(peer):
            # Tell the scheduler NOW: it marks the peer's CacheIndex
            # stale immediately (the cache-aware router must stop
            # scoring a dead replica's prefixes) and accelerates the
            # heartbeat sweep, so the drain directive arrives while the
            # affected requests are still parked here.
            self.sender.send(
                self._sched_peer(), proto.PEER_DOWN,
                {"reporter": self.node_id, "peer": peer,
                 "reason": reason},
                best_effort=True,
            )
        self._post(("abort_path", peer))

    def _count_rx(self, peer: str, wire_req: dict) -> None:
        self._count_rx_bytes(
            peer, proto.tensor_nbytes(wire_req.get("hidden_states"))
        )

    def _count_rx_bytes(self, peer: str, nbytes: int) -> None:
        with self._rx_lock:
            rx = self._rx_stats.setdefault(
                peer or "?", {"frames_in": 0, "bytes_in": 0}
            )
            rx["frames_in"] += 1
            rx["bytes_in"] += nbytes
            rx["t"] = time.monotonic()

    def _reap_rx_stats(self, idle_s: float | None = None) -> None:
        """Drop inbound counters for peers that stopped sending (same
        idle horizon as the sender's link reap, so tx and rx telemetry
        rows retire together). Runs from the announcer in BOTH modes —
        scheduler-managed swarms churn too, and a departed peer must
        not grow every heartbeat forever."""
        if idle_s is None:
            idle_s = self.sender.idle_reap_s
        now = time.monotonic()
        with self._rx_lock:
            for peer in [
                p for p, rx in self._rx_stats.items()
                if now - rx.get("t", now) > idle_s
            ]:
                del self._rx_stats[peer]

    def transport_stats(self) -> dict | None:
        """Per-link telemetry for heartbeats / status surfaces: the
        sender pipeline's outbound counters merged with inbound
        frame/byte counts per source peer. Also republishes the totals
        into the metrics registry so a worker's ``/metrics`` (and the
        single-process swarm probes) expose transport series."""
        links = self.sender.stats()
        # KV-transfer lane telemetry rides the same payload under a
        # "kv:" peer prefix, so /cluster/status shows the handoff lane's
        # bytes/queue separately from the data plane's.
        for p, s in self.kv_sender.stats().items():
            links[f"kv:{p}"] = s
        with self._rx_lock:
            rx_snapshot = {p: dict(rx) for p, rx in self._rx_stats.items()}
        for peer, rx in rx_snapshot.items():
            rx.pop("t", None)
            links.setdefault(peer, {}).update(rx)
        try:
            self._publish_transport_metrics(links)
        except Exception:  # pragma: no cover - metrics never break serving
            pass
        return links or None

    def _publish_transport_metrics(self, links: dict) -> None:
        from parallax_tpu.obs.registry import get_registry

        reg = get_registry()
        peers = ("peer",)
        c_bytes_out = reg.counter(
            mnames.TRANSPORT_BYTES_OUT_TOTAL,
            "Wire bytes sent per link", labelnames=peers,
        )
        c_bytes_in = reg.counter(
            mnames.TRANSPORT_BYTES_IN_TOTAL,
            "Wire bytes received per link", labelnames=peers,
        )
        c_frames_out = reg.counter(
            mnames.TRANSPORT_FRAMES_OUT_TOTAL,
            "Frames sent per link", labelnames=peers,
        )
        c_drops = reg.counter(
            mnames.TRANSPORT_DROPS_TOTAL,
            "Frames dropped per link (overflow / dead peer)",
            labelnames=peers,
        )
        g_depth = reg.gauge(
            mnames.TRANSPORT_QUEUE_DEPTH,
            "Sender frames currently queued per link", labelnames=peers,
        )
        for peer, s in links.items():
            c_bytes_out.labels(peer=peer).set_total(s.get("bytes_out", 0))
            c_bytes_in.labels(peer=peer).set_total(s.get("bytes_in", 0))
            c_frames_out.labels(peer=peer).set_total(s.get("frames_out", 0))
            c_drops.labels(peer=peer).set_total(s.get("drops", 0))
            g_depth.labels(peer=peer).set(s.get("queue_depth", 0))

    def _metrics_snapshot(self) -> dict | None:
        """Histogram snapshots for the heartbeat payload (scheduler-side
        merge into cluster percentiles); None when nothing observed yet."""
        try:
            from parallax_tpu.obs.registry import get_registry

            snaps = get_registry().histogram_snapshots()
            # Strip empty children: idle engines would otherwise ship a
            # full lattice of zeros every beat.
            out = {}
            for name, children in snaps.items():
                kept = {
                    lbl: c for lbl, c in children.items() if c.get("count")
                }
                if kept:
                    out[name] = kept
            return out or None
        except Exception:  # pragma: no cover - metrics never break serving
            return None

    # -- transport handlers (any thread) -------------------------------------

    def _on_forward(self, peer: str, payload):
        if isinstance(payload, (bytes, bytearray)):
            # Reference-protocol peer: a raw protobuf ForwardRequest
            # (heterogeneous-swarm interop, p2p/interop.py). Counted
            # whole-frame — cross-build links are exactly where an
            # operator reads the inbound telemetry.
            from parallax_tpu.p2p import interop

            self._count_rx_bytes(peer, len(payload))
            for ireq in interop.forward_bytes_to_ireqs(payload):
                self._post(("forward", ireq))
            return "ok"
        for wire_req in payload["reqs"]:
            self._count_rx(peer, wire_req)
            self._post(("forward", proto.ireq_from_wire(wire_req)))
        return "ok"

    def _on_abort(self, _peer: str, payload):
        if isinstance(payload, (bytes, bytearray)):
            from parallax_tpu.p2p import interop

            for rid in interop.abort_bytes_to_rids(payload):
                self._post(("release", rid, True))
            return "ok"
        for rid in payload["rids"]:
            self._post(("release", rid, True))
        return "ok"

    def _on_release(self, _peer: str, payload: dict):
        for rid in payload["rids"]:
            self._post(("release", rid, payload.get("abort", False)))
        return "ok"

    def _on_chat_submit(self, _peer: str, payload: dict):
        from parallax_tpu.runtime.request import SamplingParams

        req = Request(
            request_id=payload["rid"],
            prompt_ids=list(payload["prompt_ids"]),
            sampling_params=SamplingParams.from_dict(
                payload.get("sampling_params") or {}
            ),
            routing_table=list(payload.get("routing_table") or []),
            eos_token_ids=tuple(payload.get("eos_token_ids") or ()),
            lora_id=payload.get("lora_id"),
            # QoS context (docs/qos.md): the deadline ships as a
            # REMAINING budget and re-anchors on this process's
            # monotonic clock (absolute values don't cross processes).
            qos_class=payload.get("qos_class"),
            deadline=(
                time.monotonic() + float(payload["deadline_ms"]) / 1e3
                if payload.get("deadline_ms") is not None else None
            ),
            tenant_id=payload.get("tenant"),
        )
        replay = payload.get("replay_ids")
        if replay:
            # Client resume rung (docs/disaggregation.md): the
            # submitting frontend mirrors tokens it already streamed
            # from a head that died (e.g. a prefill node mid-handoff).
            # Teacher-forcing them through ordinary decode steps makes
            # the continuation bit-identical and the user never sees a
            # re-sampled token — the same replay machinery checkpoint
            # restores use.
            req.replay_ids = [int(x) for x in replay]
            lps = payload.get("replay_logprobs") or []
            req.replay_logprobs = (
                [float(x) for x in lps]
                if len(lps) == len(req.replay_ids) else []
            )
        self._chat_requests[req.request_id] = req
        self.submit(req)
        return "ok"

    def _on_chat_stop(self, _peer: str, payload: dict):
        """Stop-string early finish: gracefully end the request with
        FINISHED_STOP (unlike abort, the generated text stands)."""
        self._post(("stop", payload["rid"]))
        return "ok"

    def _on_chat_poll(self, _peer: str, payload: dict):
        req = self._chat_requests.get(payload["rid"])
        if req is None:
            # Shipped away in a live migration: redirect the poller to
            # the head that owns the request now (docs/resilience.md).
            head = self._migrated_to.get(payload["rid"])
            if head:
                return {"migrated": head}
            return {"error": "unknown request"}
        out = {
            # The FULL logical stream: a migrated-in request folds its
            # pre-migration outputs into the prompt, and the poller's
            # mirror must keep seeing them (identical to output_ids for
            # never-migrated requests).
            "output_ids": list(req.full_output_ids),
            "output_logprobs": list(req.full_output_logprobs),
            "status": req.status.value,
            "finished": req.status.is_finished,
        }
        if req.status.is_finished:
            self._chat_requests.pop(payload["rid"], None)
        return out

    def submit(self, request: Request) -> threading.Event:
        """Head-node API: enqueue a user request; the returned event fires
        when it finishes."""
        ev = threading.Event()
        self._request_events[request.request_id] = ev
        self._post(("submit", request))
        return ev

    def pop_finished(self) -> list[Request]:
        out = []
        while True:
            try:
                out.append(self._finished.get_nowait())
            except queue.Empty:
                return out

    # -- step loop (owns the engine) -----------------------------------------

    def _post(self, item: tuple) -> None:
        """Enqueue work for the step thread and wake it (the idle path
        parks on ``_wake`` instead of busy-polling)."""
        self._inbox.put(item)
        self._wake.set()

    def _step_loop(self) -> None:
        from parallax_tpu.runtime.engine import drive_step

        # The overlapped two-phase loop keeps exactly ONE step in flight:
        # drive_step dispatches step N+1 (host-side plan forming and
        # batch assembly) BEFORE resolving step N, so the host schedules
        # the next batch while the device computes the current one.
        pending = None
        pending_engine = None
        while not self._stop.is_set():
            try:
                wd = self._watchdog
                if wd is not None:
                    # One dict increment per loop pass: a drive_step that
                    # hangs stops the beats, and the monitor thread walks
                    # step_loop through degraded -> stalled.
                    wd.beat("step_loop")
                worked = self._drain_inbox()
                eng = self.engine
                if pending is not None and pending_engine is not eng:
                    # Elastic reload swapped the engine mid-flight: the
                    # old engine's requests were already aborted; its
                    # ticket resolves against dead state — drop it.
                    pending = None
                if self._migration_pending or self._migration_parked:
                    # Park drained requests as checkpoints and ship the
                    # parked ones to their target pipelines.
                    self._migration_tick(eng)
                if self.role == "prefill" and not self.standalone:
                    # Disaggregation: move finished prompts to the
                    # decode pool (flag -> park -> ship -> result).
                    self._handoff_tick(eng)
                if eng is None:
                    self._wake.wait(0.01)
                    self._wake.clear()
                    continue
                outs, pending = drive_step(eng, pending)
                pending_engine = eng
                for out in outs:
                    self._route_outputs(out)
                    worked = worked or out.num_tokens > 0
                if not worked and pending is None:
                    # Event-driven idle wait: submits/forwards/releases
                    # all land through _post and set the wake event, so
                    # an idle node parks instead of burning a core on a
                    # 1 ms poll; the timeout only bounds housekeeping
                    # (request-timeout sweeps), not wake latency.
                    if self._inbox.empty():
                        self._wake.wait(0.05)
                    self._wake.clear()
            except Exception:
                # The step thread must survive: a dead step loop with a live
                # announcer would look healthy to the scheduler forever.
                logger.exception("step loop error")
                if pending is not None:
                    # Only retry a ticket that is genuinely still
                    # unresolved (the failure was elsewhere, e.g. in
                    # dispatch or routing); a ticket whose own resolve
                    # failed was already abandoned by the engine and
                    # re-running its emit path would double-commit.
                    try:
                        if pending_engine.is_inflight(pending):
                            self._route_outputs(
                                pending_engine.resolve(pending)
                            )
                    except Exception:
                        logger.exception("in-flight step resolution failed")
                    pending = None
                time.sleep(0.1)

    def _drain_inbox(self) -> bool:
        worked = False
        while True:
            try:
                item = self._inbox.get_nowait()
            except queue.Empty:
                return worked
            worked = True
            kind = item[0]
            if kind == "forward":
                ireq: IntermediateRequest = item[1]
                if ireq.next_token_id is not None:
                    self.engine.commit_token(
                        ireq.request_id, ireq.next_token_id,
                        ireq.token_logprob,
                    )
                elif ireq.spec_accepted is not None:
                    self.engine.commit_spec_result(
                        ireq.request_id, ireq.spec_accepted
                    )
                else:
                    self.engine.submit_intermediate(ireq)
            elif kind == "submit":
                try:
                    req = item[1]
                    if self.standalone and not req.routing_table:
                        route = self.local_route()
                        if route is None:
                            raise RuntimeError(
                                "no route to the last layer from gossip "
                                "announcements"
                            )
                        req.routing_table = route
                    self.engine.submit(req)
                except Exception as e:
                    req: Request = item[1]
                    req.abort(str(e))
                    self._finish(req)
            elif kind == "release":
                rid, aborted = item[1], item[2]
                eng = self.engine
                req = None
                if eng is not None:
                    req = eng.scheduler.running.get(rid) or (
                        eng.scheduler.wait_queue.get(rid)
                    )
                    eng.release(rid, abort=aborted)
                # A release broadcast can end a request this HEAD is still
                # tracking for a client (e.g. a downstream stage
                # reallocated and aborted its mirrors): complete it for
                # the waiters instead of leaving them hanging. No re-
                # broadcast / no request_complete here — the originating
                # node already did both.
                if req is not None:
                    ev = self._request_events.pop(rid, None)
                    if ev is not None:
                        self._finished.put(req)
                        ev.set()
            elif kind == "stop":
                self.engine.stop_request(item[1])
            elif kind == "abort_path":
                # A next-hop peer is unreachable. Scheduler-managed HEAD
                # requests are flagged for migration instead of aborted:
                # their full state lives here, the scheduler's drain/
                # migrate_target flow (accelerated by the peer_down
                # report) hands them a surviving pipeline, and the parked
                # checkpoints resume there bit-identically. Mirrors and
                # standalone swarms keep the abort behavior — mirrors
                # own no restartable state, and a scheduler-less swarm
                # has nobody to pick a target.
                # (Posted by the sender workers too, which can outlive an
                # engine teardown — nothing to abort then.)
                if self.engine is None:
                    continue
                peer = item[1]
                # Whatever declared the path dead (send failure posts
                # this, but so can future callers), the link's
                # negotiated wire dtype dies with it: a peer that comes
                # back may be a different build.
                self._forget_wire_dtype(peer)
                migratable = (
                    not self.standalone and self.engine.model.is_first
                )
                sched = self.engine.scheduler
                for req in (
                    list(sched.running.values())
                    + list(sched.wait_queue.values())
                ):
                    if peer not in req.routing_table or req.status.is_finished:
                        continue
                    if migratable and not getattr(req, "is_mirror", False):
                        self._flag_for_migration(req, peer)
                    else:
                        req.abort(f"peer {peer} unreachable")
            elif kind == "drain":
                # Scheduler directive (heartbeat reply): these peers are
                # dead and our pipeline through them is dissolving —
                # checkpoint every affected head request away.
                if self.engine is None or not self.engine.model.is_first:
                    continue
                dead_peers = set(item[1])
                sched = self.engine.scheduler
                for req in (
                    list(sched.running.values())
                    + list(sched.wait_queue.values())
                ):
                    if req.status.is_finished or getattr(
                        req, "is_mirror", False
                    ):
                        continue
                    hit = dead_peers & set(req.routing_table)
                    if hit:
                        self._flag_for_migration(req, sorted(hit)[0])
            elif kind == "restore":
                self._restore_checkpoint(item[1], item[2])
            elif kind == "migration_shipped":
                self._on_migration_shipped(item[1])
            elif kind == "handoff_shipped":
                self._on_handoff_shipped(item[1])
            elif kind == "handoff_result":
                self._on_handoff_result(item[1])
            elif kind == "handoff_confirmed":
                # Park-deadline ownership check came back (the entry is
                # already out of the parked map).
                rid, e, owner = item[1], item[2], item[3]
                if isinstance(owner, str) and owner != self.node_id:
                    # The transfer DID land there: the target's finish
                    # releases the retained path charge; ours releases
                    # the old path via _finish_handoff.
                    e.pop("pinned_charged", None)
                    self._finish_handoff(rid, e, owner, with_kv=True)
                else:
                    self._handoff_restore_local(e, "park deadline")
            elif kind == "kv_lane_down":
                # The transfer lane to a decode head died: transfers
                # awaiting its result cannot complete — fall back to a
                # checkpoint-only re-ship now instead of waiting out
                # the result timeout.
                peer = item[1]
                now = time.monotonic()
                for rid, e in self._handoff_parked.items():
                    if (
                        e.get("awaiting_since") is not None
                        and e.get("target") == peer
                    ):
                        self._handoff_transfer_failed(
                            rid, e, "transfer_failed", now
                        )
            elif kind == "liveness":
                # Standalone gossip sweep (freshness snapshot from the
                # announcer thread): abort requests routed through peers
                # whose announcements expired — one scan per beat.
                fresh = item[1]
                sched = self.engine.scheduler
                for req in (
                    list(sched.running.values())
                    + list(sched.wait_queue.values())
                ):
                    dead = [p for p in req.routing_table if p not in fresh]
                    if dead and not req.status.is_finished:
                        req.abort(f"peer {dead[0]} unreachable")
            elif kind == "reload":
                self._apply_allocation(item[1])
            elif kind == "refit":
                version, index = item[1], item[2]
                if (
                    version <= self.refit_version
                    or self.engine is None
                    or self._refit_fetching
                ):
                    continue
                # Download + checksum off the step thread: decoding must not
                # stall on network IO (reference downloads in the p2p
                # daemon, p2p/server.py:224-339).
                self._refit_fetching = True
                threading.Thread(
                    target=self._fetch_refit, args=(version, index),
                    daemon=True, name="refit-fetch",
                ).start()
            elif kind == "refit_apply":
                version, tensors = item[1], item[2]
                from parallax_tpu.p2p.refit import apply_prefetched

                try:
                    if version > self.refit_version:
                        apply_prefetched(self.engine, tensors, version)
                        self.refit_version = version
                except Exception:
                    logger.exception("refit v%d apply failed", version)

    def _fetch_refit(self, version: int, index: dict) -> None:
        from parallax_tpu.p2p.refit import fetch_refit_tensors

        try:
            tensors = fetch_refit_tensors(self.engine, index)
            if self.refit_store is not None:
                # Persist + GC to the newest 3 versions (reference
                # check_and_release_disk_weight, p2p/server.py:434-446).
                try:
                    self.refit_store.save(version, tensors, meta={
                        "model_name": self.model_config.model_name,
                        "start_layer": self.start_layer,
                        "end_layer": self.end_layer,
                    })
                except Exception:
                    logger.exception("refit v%d disk cache failed", version)
            self._post(("refit_apply", version, tensors))
        except Exception:
            logger.exception("refit v%d fetch failed", version)
        finally:
            self._refit_fetching = False

    # -- live migration (docs/resilience.md) ---------------------------------
    #
    # Node churn flow on a HEAD node: a downstream peer dies (send
    # failure or a scheduler drain directive) -> affected requests are
    # FLAGGED (the local scheduler stops scheduling them) -> once out of
    # any in-flight step they are PARKED: KV preempted to the host tier
    # and harvested into a checkpoint image where possible, the request
    # extracted from the engine, its old-path mirrors released -> the
    # scheduler picks a target pipeline per request (CacheIndex-scored,
    # so the restore lands where the prefix is already cached) -> the
    # checkpoint ships head->head over an acknowledged RPC -> the target
    # restores it (image swap-in via the PREEMPTED/resume_from_host path,
    # or re-prefill of the radix-uncovered suffix) and decode continues
    # bit-identically. Pollers follow via chat_poll {"migrated": head} or
    # the scheduler's where_is table.

    def _flag_for_migration(self, req: Request, dead_peer: str) -> None:
        rid = req.request_id
        if rid in self._migration_pending or rid in self._migration_parked:
            return
        if rid in self._handoff_pending or rid in self._handoff_parked:
            # Already leaving through the disaggregation handoff path —
            # its own ladder (re-ship / local restore) recovers it.
            return
        req.migrating = True
        self._migration_pending[rid] = dead_peer
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "migrate_flag", node=self.node_id, request_id=rid,
            dead_peer=dead_peer,
        )

    def _migration_tick(self, eng) -> None:
        """One step-loop pass of the migration state machine: park
        flagged requests that left the in-flight window, ship parked
        ones, abort the ones nobody could take before the deadline."""
        now = time.monotonic()
        if self._migration_pending and eng is not None:
            inflight = eng.inflight_rids()
            for rid, dead in list(self._migration_pending.items()):
                sched = eng.scheduler
                req = sched.running.get(rid) or sched.wait_queue.get(rid)
                if req is None or req.status.is_finished:
                    self._migration_pending.pop(rid, None)
                    continue
                if rid in inflight:
                    continue    # its pages are being written; next pass
                self._migration_pending.pop(rid)
                self._park_request(eng, req, dead)
        ready = [
            rid for rid, e in self._migration_parked.items()
            if not e["shipping"] and now >= e["next_attempt"]
        ]
        if ready:
            for rid in ready:
                self._migration_parked[rid]["shipping"] = True
            entries = {
                rid: self._migration_parked[rid] for rid in ready
            }
            threading.Thread(
                target=self._ship_checkpoints, args=(entries,),
                daemon=True, name="migrate-ship",
            ).start()
        for rid, e in list(self._migration_parked.items()):
            if not e["shipping"] and now > e["deadline"]:
                self._migration_parked.pop(rid)
                self._migration_progress += 1
                req = e["req"]
                req.abort("migration: no serviceable pipeline")
                self._finish(req)

    @staticmethod
    def _harvestable(req: Request) -> bool:
        """Whether a park can carry this request's KV as a checkpoint
        image: a decode row past prefill (the classic case), or a
        MID-PREFILL row with computed tokens of its own — its partial
        image lets the target resume the chunked prefill at the
        computed-token mark instead of recomputing from token zero
        (resumable partial-prefill checkpoints). A PREFILLING row whose
        computed span is all radix-shared has nothing of its own to
        ship (``preempt_to_host`` would refuse anyway); PREEMPTED rows
        already live in the host tier and restore via replay."""
        from parallax_tpu.runtime.request import RequestStatus

        return (
            req.status is RequestStatus.DECODING and req.is_prefill_done
        ) or (
            req.status is RequestStatus.PREFILLING
            and req.num_computed_tokens > 0
        )

    def _park_request(
        self, eng, req: Request, dead_peer: str, force: bool = False
    ) -> None:
        """Checkpoint one request out of the engine. Must run on the
        step thread (cache bookkeeping is single-threaded state)."""
        from parallax_tpu.runtime.request import RequestStatus

        rid = req.request_id
        image = None
        if not force and eng.host_tier is not None and self._harvestable(req):
            # The committed KV image parks in the host tier exactly like
            # a preemption (PR 2); the checkpoint serializes it so a
            # layout-compatible target swaps it in instead of
            # recomputing. Failure just means re-prefill at the target.
            # A mid-prefill park (resumable partial-prefill checkpoints)
            # first trims the owned pages down to the computed span —
            # prompt pages were allocated upfront, and the ones holding
            # no KV yet must not ship.
            preempt = getattr(eng.cache, "preempt_to_host", None)
            try:
                if req.status is RequestStatus.PREFILLING:
                    trim = getattr(eng.cache, "trim_uncomputed_pages", None)
                    if trim is not None:
                        trim(req)
                if preempt is not None and preempt(req):
                    image = eng.harvest_kv_image(req)
            except Exception:
                logger.exception("%s: KV harvest for %s failed (falling "
                                 "back to re-prefill)", self.node_id, rid)
                image = None
        extracted = eng.extract(rid, force=force)
        if extracted is None:
            # Raced back into flight; re-flag and retry next pass.
            self._migration_pending[rid] = dead_peer
            return
        old_table = list(req.routing_table)
        try:
            eng.cache.release(req)
        except Exception:
            logger.exception("%s: cache release for parked %s failed",
                             self.node_id, rid)
        # Old-path survivors drop their mirrors now, not at timeout.
        for peer in old_table:
            if peer != self.node_id and peer != dead_peer:
                self.sender.send(
                    peer, proto.RELEASE,
                    {"rids": [rid], "abort": True}, best_effort=True,
                )
        now = time.monotonic()
        # NOT counted as watchdog progress: under continuous churn new
        # parks would keep the counter moving and mask a wedged SHIP
        # path — only ship results and deadline aborts advance it.
        self._migration_parked[rid] = {
            "req": req,
            "image": image,
            "old_table": old_table,
            "dead": dead_peer,
            "parked_wall": time.time(),
            "deadline": now + self.MIGRATION_PARK_TIMEOUT_S,
            "next_attempt": now,
            "shipping": False,
        }
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "migrate_park", node=self.node_id, request_id=rid,
            kv_pages=(len(image.layers[0]) if image is not None else 0),
            tokens=len(req.full_output_ids),
        )
        if req.traced:
            # The park span ships with the checkpoint (spans are
            # snapshotted at ship time), so the target's stitched trace
            # carries the churn boundary.
            from parallax_tpu.obs.trace import get_trace_store

            get_trace_store().add(
                rid, self.node_id, "migrate_park",
                t0=time.perf_counter(), dur=0.0,
                args={"dead_peer": dead_peer},
            )

    def _ship_checkpoints(self, entries: dict[str, dict]) -> None:
        """Background thread: ask the scheduler for CacheIndex-scored
        targets, ship each checkpoint over an acknowledged RPC, report
        the outcomes back to the step thread. Reads only parked (frozen)
        request state — the step thread stopped touching it at park.
        Every entry ALWAYS gets a result posted — an unexpected error
        maps to "retry", never to a permanently ``shipping`` entry that
        the park-timeout abort ladder could no longer reach."""
        results: dict[str, tuple] = {}
        try:
            self._ship_checkpoints_inner(entries, results)
        except Exception:
            logger.exception("%s: checkpoint ship failed", self.node_id)
        finally:
            for rid in entries:
                results.setdefault(rid, ("retry", "ship error"))
            self._post(("migration_shipped", results))

    def _target_descriptor(self, req: Request, page: int) -> dict:
        """CacheIndex-scoring descriptor for one parked request (shared
        by the migration and handoff target queries): the FULL token
        history — a previously-resumed request's prompt already folds
        prior outputs in, and outputs still awaiting teacher-forced
        replay count too — so the scheduler's chain prediction sees the
        same tokens the restore will re-prefill."""
        from parallax_tpu.runtime.cache_manager import derive_ns_salt
        from parallax_tpu.runtime.radix_cache import block_hash_chain

        history = list(req.all_token_ids) + list(req.replay_ids)
        d = {
            "rid": req.request_id,
            "prompt_tokens": len(history),
            "lora_id": req.lora_id,
        }
        if req.lora_id is not None:
            # Adapter requests hash in the adapter's own digest
            # namespace — deterministic per adapter id, so the
            # scheduler's CacheIndex mirrors (fed from equally-salted
            # radix trees on every replica) can score them too.
            salt = derive_ns_salt(req.lora_id)
            history = [t ^ salt for t in history]
        d["chains"] = {str(page): block_hash_chain(history, page)}
        return d

    def _ship_checkpoints_inner(
        self, entries: dict[str, dict], results: dict[str, tuple]
    ) -> None:
        from parallax_tpu.runtime.checkpoint import (
            checkpoint_from_request,
            checkpoint_to_wire,
        )

        page = self.engine_config.page_size
        descriptors = [
            self._target_descriptor(e["req"], page)
            for e in entries.values()
        ]
        try:
            reply = self.sched_transport.call(
                self.scheduler_peer, proto.MIGRATE_TARGET,
                {
                    "requests": descriptors,
                    "exclude": sorted({e["dead"] for e in entries.values()}),
                },
                timeout=15.0,
            )
            targets = (reply or {}).get("targets") or {}
        except Exception as exc:
            logger.warning("%s: migrate_target query failed: %s",
                           self.node_id, exc)
            targets = {}
        by_head: dict[str, list] = {}
        for rid, e in entries.items():
            t = targets.get(rid)
            if not isinstance(t, dict) or not t.get("path"):
                results[rid] = ("retry", "no serviceable pipeline")
                continue
            path = [str(x) for x in t["path"]]
            image = e["image"]
            # Raw-KV adoption only makes sense when the target head runs
            # the exact same stage: a single-stage pipeline over our
            # layer range. Anything else re-prefills (which also feeds
            # downstream stages their chunks).
            kv_ok = (
                image is not None
                and len(path) == 1
                and list(t.get("head_layers") or [])
                == [image.start_layer, image.end_layer]
            )
            grammar = None
            eng = self.engine
            if eng is not None and e["req"].sampling_params.json_schema:
                # Harvest the head's grammar-DFA mirror so the target
                # can restore the automaton position without replaying
                # the stream (hash-validated on adoption).
                grammar = eng.grammar_checkpoint_fields(rid)
            ckpt = checkpoint_from_request(
                e["req"], routing_table=path,
                kv=image if kv_ok else None,
                grammar=grammar,
            )
            ckpt.parked_wall = e["parked_wall"]
            by_head.setdefault(path[0], []).append(
                (rid, path, checkpoint_to_wire(ckpt))
            )
        for head, batch in by_head.items():
            try:
                reply = self.transport.call(
                    head, proto.CHECKPOINT,
                    {"checkpoints": [w for _r, _p, w in batch]},
                    timeout=30.0,
                )
            except Exception as exc:
                # The chosen target died between choice and ship — the
                # load charge must not leak, and the request retries
                # against whatever pipeline the next query finds.
                for rid, path, _w in batch:
                    results[rid] = ("retry", f"target {head} unreachable")
                    self.sender.send(
                        self._sched_peer(), proto.REQUEST_COMPLETE,
                        {"path": path}, best_effort=True,
                    )
                logger.warning("%s: checkpoint ship to %s failed: %s",
                               self.node_id, head, exc)
                continue
            accepted = set((reply or {}).get("accepted") or ())
            rejected = (reply or {}).get("rejected") or {}
            for rid, path, _w in batch:
                if rid in accepted:
                    results[rid] = ("ok", head)
                else:
                    results[rid] = (
                        "failed",
                        str(rejected.get(rid) or "target rejected"),
                    )
                    self.sender.send(
                        self._sched_peer(), proto.REQUEST_COMPLETE,
                        {"path": path}, best_effort=True,
                    )

    def _on_migration_shipped(self, results: dict[str, tuple]) -> None:
        self._migration_progress += 1
        for rid, (status, info) in results.items():
            e = self._migration_parked.get(rid)
            if e is None:
                continue
            if status == "ok":
                self._migration_parked.pop(rid)
                self._record_migrated(rid, info)
                # The request lives on the target now: pollers get the
                # {"migrated": head} redirect, and a direct submitter's
                # done-event is retired unfired (finishing happens on
                # the target; chat_poll is the follow channel).
                self._chat_requests.pop(rid, None)
                self._request_events.pop(rid, None)
                # Release the OLD path's load charge; the target's own
                # request_complete covers the new path when it finishes.
                if not self.standalone:
                    self.sender.send(
                        self._sched_peer(), proto.REQUEST_COMPLETE,
                        {"path": e["old_table"] or [self.node_id]},
                        best_effort=True,
                    )
                from parallax_tpu.obs.flight import get_flight

                get_flight().event(
                    "migrate_out", node=self.node_id, request_id=rid,
                    target=info,
                    with_kv=e["image"] is not None,
                )
                if e["req"].traced:
                    # The linked twin of the target's migrate_in span:
                    # the SOURCE trace records where the request went.
                    from parallax_tpu.obs.trace import get_trace_store

                    get_trace_store().add(
                        rid, self.node_id, "migrate_out",
                        t0=time.perf_counter(), dur=0.0,
                        args={"target": info},
                    )
                try:
                    from parallax_tpu.obs.registry import get_registry

                    get_registry().counter(
                        mnames.MIGRATION_CHECKPOINTS_TOTAL,
                        "Requests checkpointed away from this head "
                        "during node-churn drains",
                    ).inc()
                except Exception:
                    pass
            else:
                # Both "retry" (target unreachable / no pipeline) and
                # "failed" (target rejected: queue full, incompatible
                # frame) re-enter the park loop — the next target query
                # may pick another pipeline, and the park deadline
                # bounds how long we keep trying before the abort rung.
                if status == "failed":
                    logger.warning(
                        "%s: migration of %s rejected (%s); retrying "
                        "until the park deadline", self.node_id, rid,
                        info,
                    )
                e["shipping"] = False
                e["next_attempt"] = (
                    time.monotonic() + self.MIGRATION_RETRY_S
                )

    def _record_migrated(self, rid: str, head: str) -> None:
        self._migrated_to[rid] = head
        while len(self._migrated_to) > 4096:
            self._migrated_to.popitem(last=False)

    # -- disaggregated prefill/decode handoff (docs/disaggregation.md) -------
    #
    # Prefill-role head flow, one step-loop pass at a time: a request
    # crosses the prefill/decode boundary (prompt KV computed, first
    # token committed) -> FLAGGED (``migrating`` stops the local
    # scheduler from planning it into further decode steps) -> once out
    # of the in-flight window it is PARKED exactly like a migration
    # (KV preempted to the host tier and harvested into an image,
    # request extracted, pages released) -> the scheduler picks a
    # CacheIndex-scored DECODE-POOL target -> the image streams over the
    # dedicated kv lane as layer-chunked KV_TRANSFER frames (begin /
    # layers / end) -> the decode head assembles, validates through the
    # strict checkpoint decoder, admits the request like a preempted
    # resume (all-or-nothing page reservation; PREEMPTED parking under
    # pressure) and answers KV_RESULT. Fallback ladder on any miss:
    # checkpoint-only re-ship (re-prefill from the target's radix +
    # teacher-forced replay), then local restore (mixed-mode decode
    # here), then — only if the engine itself is gone — abort.

    def _handoff_tick(self, eng) -> None:
        """One step-loop pass of the handoff state machine: flag, park,
        ship, resolve result timeouts and the park deadline."""
        now = time.monotonic()
        if eng is not None and eng.model.is_first:
            for rid in eng.handoff_ready_rids():
                if (
                    rid in self._handoff_pending
                    or rid in self._handoff_parked
                    or rid in self._migration_pending
                ):
                    continue
                req = eng.scheduler.running.get(rid)
                if req is None or req.status.is_finished:
                    continue
                if getattr(req, "handoff_local", False):
                    continue
                req.migrating = True
                self._handoff_pending[rid] = now
                from parallax_tpu.obs.flight import get_flight

                get_flight().event(
                    "handoff_flag", node=self.node_id, request_id=rid,
                )
        if self._handoff_pending and eng is not None:
            inflight = eng.inflight_rids()
            for rid in list(self._handoff_pending):
                sched = eng.scheduler
                req = sched.running.get(rid) or sched.wait_queue.get(rid)
                if req is None or req.status.is_finished:
                    self._handoff_pending.pop(rid, None)
                    continue
                if rid in inflight:
                    continue    # pages still being written; next pass
                self._handoff_pending.pop(rid)
                self._park_for_handoff(eng, req)
        ready = [
            rid for rid, e in self._handoff_parked.items()
            if not e["shipping"] and e["awaiting_since"] is None
            and now >= e["next_attempt"]
        ]
        if ready:
            for rid in ready:
                self._handoff_parked[rid]["shipping"] = True
            entries = {rid: self._handoff_parked[rid] for rid in ready}
            threading.Thread(
                target=self._ship_handoffs, args=(entries,),
                daemon=True, name="kv-handoff-ship",
            ).start()
        for rid, e in list(self._handoff_parked.items()):
            if (
                e["awaiting_since"] is not None
                and now - e["awaiting_since"] > self.HANDOFF_RESULT_TIMEOUT_S
            ):
                self._handoff_transfer_failed(rid, e, "result_timeout", now)
            elif (
                not e["shipping"]
                and e["awaiting_since"] is None
                and now > e["deadline"]
            ):
                # Park deadline: nobody (provably) took it — decode it
                # HERE. The mixed-mode rung, never an abort. Entries
                # that ever had a pinned target first confirm ownership
                # against the scheduler's where_is table: under an
                # asymmetric partition the target may have accepted the
                # transfer (and reported migration_done) while every
                # result/re-ship back to us was lost — restoring
                # locally then would fork the request onto two heads.
                self._handoff_parked.pop(rid)
                self._handoff_progress += 1
                if e.get("pinned_target"):
                    threading.Thread(
                        target=self._confirm_then_restore_local,
                        args=(rid, e), daemon=True,
                        name="kv-handoff-confirm",
                    ).start()
                else:
                    self._handoff_restore_local(e, "park deadline")

    def _confirm_then_restore_local(self, rid: str, e: dict) -> None:
        """Background thread (the where_is RPC must not block the step
        thread): if the scheduler records another head owning ``rid``,
        the earlier transfer actually landed — finish the handoff
        instead of forking a local copy. Unknown/unreachable answers
        restore locally (availability first)."""
        owner = None
        try:
            reply = self.sched_transport.call(
                self.scheduler_peer, proto.WHERE_IS, {"rid": rid},
                timeout=5.0,
            )
            owner = (reply or {}).get("head")
        except Exception:
            owner = None
        self._post(("handoff_confirmed", rid, e, owner))

    def _handoff_transfer_failed(
        self, rid: str, e: dict, reason: str, now: float,
        pin: bool = True,
    ) -> None:
        """A KV transfer died (nack, lane failure, result timeout):
        release the charged target path and drop to the checkpoint-only
        rung on the next ship attempt.

        ``pin`` (timeouts and lane failures — anywhere the target's
        verdict is UNKNOWN) routes that re-ship back to the SAME
        target: if the slow transfer actually succeeded there, the
        duplicate ack resolves it in place, whereas a fresh target
        would leave two heads decoding the same request. An explicit
        nack from the target (it does NOT own the request) re-ships
        pin-free."""
        from parallax_tpu.runtime import kv_handoff

        kv_handoff.record_fallback(reason)
        path = e.get("target_path")
        if pin and e.get("target"):
            # Verdict unknown: the target MAY own (and later finish)
            # the request, and its finish releases the path charge —
            # releasing here too would double-decrement the decode
            # head's load and over-admit onto it. Retain the charge
            # with the pin; it is released only once the pinned re-ship
            # proves the target does NOT own the request (reject /
            # unreachable) or the park deadline restores locally.
            e["pinned_target"] = e["target"]
            e["pinned_path"] = list(path or [e["target"]])
            e["pinned_charged"] = bool(path)
        elif path:
            # Explicit nack (or no known target): the target never took
            # ownership, so nothing else releases the router charge the
            # scheduler made when it chose this path.
            self.sender.send(
                self._sched_peer(), proto.REQUEST_COMPLETE,
                {"path": list(path)}, best_effort=True,
            )
        e["awaiting_since"] = None
        e["target"] = None
        e["target_path"] = None
        e["kv_failed"] = True
        e["next_attempt"] = now

    def _release_pinned_charge(self, e: dict) -> None:
        """Release the router charge retained across a pinned re-ship —
        called exactly once, when the pinned target is proven NOT to
        own the request (reject/unreachable) or the request restores
        locally."""
        if e.pop("pinned_charged", False) and e.get("pinned_path"):
            self.sender.send(
                self._sched_peer(), proto.REQUEST_COMPLETE,
                {"path": list(e["pinned_path"])}, best_effort=True,
            )

    def _park_for_handoff(self, eng, req: Request) -> None:
        """Checkpoint one finished prompt out of the prefill engine
        (step thread — cache bookkeeping is single-threaded state).
        Identical mechanics to a migration park: host-tier preempt +
        image harvest where possible, extract, release."""
        from parallax_tpu.runtime.request import RequestStatus

        rid = req.request_id
        image = None
        if eng.host_tier is not None and self._harvestable(req):
            preempt = getattr(eng.cache, "preempt_to_host", None)
            try:
                if req.status is RequestStatus.PREFILLING:
                    trim = getattr(eng.cache, "trim_uncomputed_pages", None)
                    if trim is not None:
                        trim(req)
                if preempt is not None and preempt(req):
                    image = eng.harvest_kv_image(req)
            except Exception:
                logger.exception(
                    "%s: KV harvest for handoff of %s failed (decode "
                    "pool will re-prefill)", self.node_id, rid,
                )
                image = None
        extracted = eng.extract(rid)
        if extracted is None:
            # Raced back into flight; re-flag and retry next pass.
            self._handoff_pending[rid] = time.monotonic()
            return
        old_table = list(req.routing_table)
        try:
            eng.cache.release(req)
        except Exception:
            logger.exception("%s: cache release for handoff %s failed",
                             self.node_id, rid)
        # Multi-stage prefill pipeline: downstream mirrors drop now.
        for peer in old_table:
            if peer != self.node_id:
                self.sender.send(
                    peer, proto.RELEASE,
                    {"rids": [rid], "abort": True}, best_effort=True,
                )
        now = time.monotonic()
        self._handoff_parked[rid] = {
            "req": req,
            "image": image,
            "old_table": old_table,
            "parked_wall": time.time(),
            "deadline": now + self.HANDOFF_PARK_TIMEOUT_S,
            "next_attempt": now,
            "shipping": False,
            "awaiting_since": None,
            "target": None,
            "target_path": None,
            "t_ship": None,
            "kv_failed": False,
            # Set by a result-timeout/lane failure: the next ship goes
            # back to this target (checkpoint-only) so a slow-but-
            # successful transfer resolves via the duplicate ack
            # instead of double-decoding on a fresh target.
            "pinned_target": None,
            "pinned_path": None,
            # Static ladder rungs already counted for this entry: the
            # retry loop re-derives the same reason every attempt, and
            # re-counting would inflate the fallback telemetry ~40x
            # over a full park window.
            "fallbacks_counted": set(),
        }
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "handoff_park", node=self.node_id, request_id=rid,
            kv_pages=(len(image.layers[0]) if image is not None else 0),
            tokens=len(req.full_output_ids),
        )
        if req.traced:
            from parallax_tpu.obs.trace import get_trace_store

            get_trace_store().add(
                rid, self.node_id, "kv_handoff_park",
                t0=time.perf_counter(), dur=0.0, args={},
            )

    def _ship_handoffs(self, entries: dict[str, dict]) -> None:
        """Background thread: decode-pool targets from the scheduler,
        then per request either stream the KV image over the kv lane or
        ship the checkpoint inline (re-prefill rungs). Reads only parked
        (frozen) state; every entry ALWAYS gets a result posted."""
        results: dict[str, tuple] = {}
        try:
            self._ship_handoffs_inner(entries, results)
        except Exception:
            logger.exception("%s: handoff ship failed", self.node_id)
        finally:
            for rid in entries:
                results.setdefault(rid, ("retry", "ship error"))
            self._post(("handoff_shipped", results))

    def _ship_handoffs_inner(
        self, entries: dict[str, dict], results: dict[str, tuple]
    ) -> None:
        from parallax_tpu.runtime import kv_handoff
        from parallax_tpu.runtime.checkpoint import checkpoint_to_wire

        page = self.engine_config.page_size
        descriptors = [
            self._target_descriptor(e["req"], page)
            for e in entries.values()
            if not e.get("pinned_target")   # known target: no query
        ]
        targets = {}
        if descriptors:
            try:
                reply = self.sched_transport.call(
                    self.scheduler_peer, proto.DISAGG_TARGET,
                    {"requests": descriptors, "exclude": [self.node_id]},
                    timeout=15.0,
                )
                targets = (reply or {}).get("targets") or {}
            except Exception as exc:
                logger.warning("%s: disagg_target query failed: %s",
                               self.node_id, exc)
        for rid, e in entries.items():
            pinned = e.get("pinned_target")
            if pinned:
                # Post-timeout re-ship: BACK to the original target,
                # checkpoint-only. If the slow transfer succeeded
                # there, the duplicate ack resolves it in place; no
                # fresh router charge was made for this path.
                path = [str(x) for x in (e.get("pinned_path") or [pinned])]
                head, kv_ok, charged = path[0], False, False
            else:
                t = targets.get(rid)
                if not isinstance(t, dict) or not t.get("path"):
                    # No decode/mixed pipeline serviceable: keep it
                    # local (mixed-mode decode) — visible in the
                    # scheduler's disagg.no_target counter, never a
                    # queue nobody sees.
                    results[rid] = (
                        "local", "no serviceable decode pipeline"
                    )
                    continue
                path = [str(x) for x in t["path"]]
                head = path[0]
                charged = True
                image = e["image"]
                predicted = int(t.get("predicted_cached_tokens") or 0)
                reason = None
                if image is None:
                    reason = "no_image"   # no host tier / partial park
                elif e["kv_failed"]:
                    pass                  # counted at the failure site
                elif len(path) != 1 or list(
                    t.get("head_layers") or []
                ) != [image.start_layer, image.end_layer]:
                    reason = "layout"     # raw pages cannot adopt there
                elif predicted >= image.computed_tokens - page:
                    # Smart skip: the target's radix already covers
                    # (within a page of) everything the image holds —
                    # re-prefilling there is ~one page of compute,
                    # cheaper than the wire.
                    reason = "prefix_warm"
                kv_ok = (
                    image is not None and not e["kv_failed"]
                    and reason is None
                )
                if reason is not None and reason not in e["fallbacks_counted"]:
                    e["fallbacks_counted"].add(reason)
                    kv_handoff.record_fallback(reason)
            ckpt = kv_handoff.handoff_checkpoint(e["req"], path, kv=None)
            ckpt.parked_wall = e["parked_wall"]
            wire = checkpoint_to_wire(ckpt)
            if kv_ok:
                frames = kv_handoff.image_to_frames(
                    rid, wire, image, self.kv_transfer_chunk_bytes
                )
                total_b = sum(b for _f, b in frames)
                if not self._enqueue_kv_frames(head, frames):
                    # Backpressure deadline hit (lane wedged or the
                    # image simply outruns the link): the assembler's
                    # sequence check nacks whatever partial landed, and
                    # this request takes the checkpoint-only rung NOW.
                    kv_handoff.record_fallback("transfer_failed")
                    e["kv_failed"] = True
                    results[rid] = ("retry", "kv lane backpressure")
                    self.sender.send(
                        self._sched_peer(), proto.REQUEST_COMPLETE,
                        {"path": path}, best_effort=True,
                    )
                    continue
                kv_handoff.record_transfer(
                    "out", frames=len(frames), nbytes=total_b,
                )
                results[rid] = ("sent", (head, path))
            else:
                # Checkpoint-only rung: the acknowledged migration wire;
                # the target re-prefills from its own radix and
                # teacher-forces the recorded tokens.
                try:
                    reply = self.transport.call(
                        head, proto.CHECKPOINT,
                        {"checkpoints": [wire]}, timeout=30.0,
                    )
                except Exception:
                    results[rid] = ("retry", f"target {head} unreachable")
                    if charged:
                        self.sender.send(
                            self._sched_peer(), proto.REQUEST_COMPLETE,
                            {"path": path}, best_effort=True,
                        )
                    # A pinned target stays pinned on an UNREACHABLE
                    # outcome: a call timeout to a live-but-overloaded
                    # head is indistinguishable from death here, and
                    # shipping to a fresh target while the pinned one
                    # may own the request would fork it onto two heads.
                    # A genuinely dead target resolves at the park
                    # deadline (local restore); its retained charge
                    # dies with the node the scheduler evicts.
                    continue
                accepted = set((reply or {}).get("accepted") or ())
                if rid in accepted:
                    results[rid] = ("ok", head)
                else:
                    rejected = (reply or {}).get("rejected") or {}
                    results[rid] = (
                        "retry",
                        str(rejected.get(rid) or "target rejected"),
                    )
                    if charged:
                        self.sender.send(
                            self._sched_peer(), proto.REQUEST_COMPLETE,
                            {"path": path}, best_effort=True,
                        )
                    if pinned:
                        # Explicit rejection: the pinned target does
                        # NOT own the request — release the retained
                        # charge and free the next round to pick any
                        # decode replica.
                        self._release_pinned_charge(e)
                        e["pinned_target"] = None
                        e["pinned_path"] = None

    # Ship-thread backpressure on the kv lane: stop enqueueing while
    # the peer's queue holds this many frames (well under the lane's
    # max_queue of 64, so bursts from concurrent ship batches still
    # fit) and give a wedged lane this long before falling back.
    KV_LANE_HIGH_WATER = 32
    KV_LANE_DRAIN_TIMEOUT_S = 60.0

    def _enqueue_kv_frames(self, head: str, frames: list) -> bool:
        """Feed one transfer's frames onto the kv lane WITH
        backpressure (runs on the ship thread, which may block): an
        unbounded enqueue of a many-frame image would overflow the
        lane's bounded queue — destroying the transfer and falsely
        reporting a healthy decode head as peer-down — because enqueue
        is instantaneous while the drain runs at wire speed. False on
        deadline; the caller falls back to checkpoint-only."""
        deadline = time.monotonic() + self.KV_LANE_DRAIN_TIMEOUT_S
        for f, b in frames:
            while self.kv_sender.queue_depth(head) >= self.KV_LANE_HIGH_WATER:
                if time.monotonic() > deadline or self._stop.is_set():
                    return False
                time.sleep(0.005)
            # Lazy tuple payload feeds the lane's telemetry; frames are
            # already serialized dicts (built on the ship thread, never
            # the step thread), so the worker only packs.
            self.kv_sender.send(
                head, proto.KV_TRANSFER, (lambda f=f, b=b: (f, b, b)),
            )
        return True

    def _on_handoff_shipped(self, results: dict[str, tuple]) -> None:
        """Step thread: fold one ship round's outcomes back into the
        parked ledger."""
        from parallax_tpu.runtime import kv_handoff

        self._handoff_progress += 1
        now = time.monotonic()
        for rid, (status, info) in results.items():
            e = self._handoff_parked.get(rid)
            if e is None:
                continue
            e["shipping"] = False
            if status == "ok":
                self._handoff_parked.pop(rid)
                self._finish_handoff(rid, e, info, with_kv=False)
            elif status == "sent":
                head, path = info
                e["awaiting_since"] = now
                e["t_ship"] = now
                e["target"] = head
                e["target_path"] = list(path)
                early = e.pop("early_result", None)
                if early is not None:
                    # The decode head answered before this ship round's
                    # results event landed (loopback dispatch is
                    # synchronous; TCP can race too): consume the
                    # stashed result now instead of stalling to the
                    # result timeout and re-shipping a request the
                    # target already owns.
                    self._on_handoff_result(early)
            elif status == "local":
                self._handoff_parked.pop(rid)
                kv_handoff.record_fallback("no_decode_pool")
                self._handoff_restore_local(e, str(info))
            else:   # retry
                e["next_attempt"] = now + self.HANDOFF_RETRY_S

    def _on_handoff_result(self, payload: dict) -> None:
        """Step thread: a decode head's KV_RESULT for one transfer."""
        from parallax_tpu.runtime import kv_handoff

        rid = str(payload.get("rid") or "")
        e = self._handoff_parked.get(rid)
        if e is None:
            return      # late/duplicate result; already resolved
        if e["awaiting_since"] is None:
            if e["shipping"]:
                # Raced ahead of the ship round's own results event:
                # stash it — the "sent" transition consumes it.
                e["early_result"] = dict(payload)
            return
        self._handoff_progress += 1
        if payload.get("ok"):
            self._handoff_parked.pop(rid)
            if e["t_ship"] is not None:
                # Out-leg latency: first frame enqueued -> accept.
                kv_handoff.record_transfer(
                    "out", frames=0, nbytes=0,
                    ms=(time.monotonic() - e["t_ship"]) * 1e3,
                )
            self._finish_handoff(
                rid, e, e.get("target") or "?", with_kv=True
            )
        else:
            logger.warning(
                "%s: kv transfer of %s rejected by %s (%s); falling "
                "back to checkpoint-only", self.node_id, rid,
                e.get("target"), payload.get("reason") or "?",
            )
            # Explicit nack: the target does NOT own the request —
            # the re-ship is free to pick any decode replica.
            self._handoff_transfer_failed(
                rid, e, "transfer_failed", time.monotonic(), pin=False,
            )

    def _finish_handoff(
        self, rid: str, e: dict, head: str, with_kv: bool
    ) -> None:
        """The decode head owns the request now: redirect pollers,
        release the old (prefill) path's load charge, count it."""
        self._record_migrated(rid, head)
        self._chat_requests.pop(rid, None)
        self._request_events.pop(rid, None)
        if not self.standalone:
            self.sender.send(
                self._sched_peer(), proto.REQUEST_COMPLETE,
                {"path": e["old_table"] or [self.node_id]},
                best_effort=True,
            )
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "handoff_out", node=self.node_id, request_id=rid,
            target=head, with_kv=with_kv,
        )
        if e["req"].traced:
            from parallax_tpu.obs.trace import get_trace_store

            get_trace_store().add(
                rid, self.node_id, "kv_handoff_out",
                t0=time.perf_counter(), dur=0.0,
                args={"target": head, "with_kv": with_kv},
            )

    def _handoff_restore_local(self, e: dict, reason: str) -> None:
        """Mixed-mode rung: decode the parked request HERE. Goes through
        the same checkpoint-restore path a decode target runs (including
        KV-image re-adoption via the host tier), so the continuation is
        bit-identical whichever rung serves it.

        The restored request keeps its ORIGINAL routing table: on a
        multi-stage prefill pipeline the head only hosts its own layer
        slice, so decode must still flow through the downstream stages
        (whose mirrors the replay re-prefill rebuilds), and the finish
        then releases exactly the path the dispatcher charged. The KV
        image is only re-adopted on a single-stage head — adopting it
        on a multi-stage head would skip the re-prefill that feeds the
        downstream stages their KV."""
        from parallax_tpu.runtime import kv_handoff

        req = e["req"]
        rid = req.request_id
        logger.info("%s: restoring handoff of %s locally (%s)",
                    self.node_id, rid, reason)
        self._release_pinned_charge(e)
        table = list(e["old_table"] or [self.node_id])
        ckpt = kv_handoff.handoff_checkpoint(
            req, table, kv=e["image"] if len(table) == 1 else None
        )
        ckpt.parked_wall = e["parked_wall"]
        self._restore_checkpoint(ckpt, self.node_id)

    def _on_kv_transfer(self, peer: str, payload):
        """Decode-target side of the kv lane: assemble layer-chunked
        frames; on the end frame, admit like an rpc_checkpoint batch and
        answer KV_RESULT (the source releases its state only on ok)."""
        res = self._kv_assembler.feed(peer, payload)
        if res is None:
            return "ok"
        kind, val = res
        rid = payload.get("rid") if isinstance(payload, dict) else None
        if kind == "error":
            logger.warning("%s: kv transfer from %s rejected: %s",
                           self.node_id, peer, val)
            if rid:
                self.sender.send(
                    peer, proto.KV_RESULT,
                    {"rid": str(rid), "ok": False, "reason": str(val)},
                    best_effort=True,
                )
            return "ok"
        ckpt = val
        ok, reason = self._admit_restore(ckpt, peer)
        self.sender.send(
            peer, proto.KV_RESULT,
            {"rid": ckpt.request_id, "ok": ok, "reason": reason},
            best_effort=True,
        )
        return "ok"

    def _on_kv_result(self, _peer: str, payload: dict):
        self._post(("handoff_result", dict(payload or {})))
        return "ok"

    def _on_kv_send_failure(self, peer: str, reason: str) -> None:
        """KV-transfer lane failure. Unlike the data-plane sender,
        nothing routed through ``peer`` still runs here — handed-off
        requests were parked/extracted first — so no abort_path scan.
        Report the peer down (evidence for the sweep) and fail the
        awaiting transfers over to the checkpoint-only rung."""
        logger.error("%s: kv lane to %s failed: %s",
                     self.node_id, peer, reason)
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "kv_lane_down", node=self.node_id, peer=peer, reason=reason,
        )
        if not self.standalone and not self._is_scheduler(peer):
            self.sender.send(
                self._sched_peer(), proto.PEER_DOWN,
                {"reporter": self.node_id, "peer": peer,
                 "reason": f"kv lane: {reason}"},
                best_effort=True,
            )
        self._post(("kv_lane_down", peer))

    def _admit_restore(self, ckpt, peer: str) -> tuple[bool, str]:
        """Shared admission gate for migrated/handed-off checkpoints
        (inline rpc_checkpoint batches and assembled KV transfers):
        duplicate ships ack WITHOUT a second submit, saturation rejects
        so the source retries elsewhere, and the poll mirror registers
        BEFORE the ack so redirected pollers never see "unknown
        request"."""
        from parallax_tpu.runtime.checkpoint import build_resumed_request

        if self.engine is None:
            return False, "no engine"
        if ckpt.request_id in self._chat_requests:
            # Duplicate ship (our previous ack was lost in flight): the
            # request is already restoring/running here — ack again
            # WITHOUT a second submit, or the stream would decode twice.
            return True, "duplicate"
        sched = self.engine.scheduler
        if len(sched.wait_queue) >= sched.max_queue_size:
            # Acceptance transfers ownership, so the engine submit
            # (later, on the step thread) must be going to succeed:
            # reject while saturated and let the source retry — on us
            # once the queue drains, or on another pipeline.
            return False, "target queue full"
        self._chat_requests[ckpt.request_id] = build_resumed_request(ckpt)
        self._post(("restore", ckpt, peer))
        return True, ""

    def _on_checkpoint(self, peer: str, payload):
        """Target side: validate and accept a batch of migrating
        requests. Acceptance transfers ownership — the source releases
        its state only for acknowledged rids; a malformed frame is
        rejected cleanly (CheckpointError) and the source falls back."""
        from parallax_tpu.runtime.checkpoint import (
            CheckpointError,
            checkpoint_from_wire,
        )

        accepted: list[str] = []
        rejected: dict[str, str] = {}
        frames = (payload or {}).get("checkpoints")
        if not isinstance(frames, list):
            return {"accepted": [], "rejected": {"?": "no checkpoints"}}
        for i, wire in enumerate(frames):
            rid = (
                wire.get("rid") if isinstance(wire, dict) else None
            ) or f"frame-{i}"
            try:
                ckpt = checkpoint_from_wire(wire)
            except CheckpointError as e:
                logger.warning("%s: rejected checkpoint %s from %s: %s",
                               self.node_id, rid, peer, e)
                rejected[str(rid)] = str(e)
                continue
            ok, reason = self._admit_restore(ckpt, peer)
            if ok:
                accepted.append(ckpt.request_id)
            else:
                rejected[ckpt.request_id] = reason
        return {"accepted": accepted, "rejected": rejected}

    def _restore_checkpoint(self, ckpt, from_peer: str) -> None:
        """Step thread: rebuild the request and resume it — KV-image
        swap-in when the layouts match, else re-prefill of the ORIGINAL
        prompt (radix-uncovered suffix only) plus teacher-forced replay
        of the recorded outputs. Either way the continuation is
        bit-identical (decode-shape compute everywhere the original run
        used it; seeded draws key on the stream-relative output step the
        checkpoint preserved)."""
        from parallax_tpu.runtime.checkpoint import build_resumed_request

        eng = self.engine
        req = build_resumed_request(ckpt)
        rid = req.request_id
        adopted = False
        if eng is None:
            req.abort("migration target has no engine")
            self._chat_requests[rid] = req
            self._finish(req)
            return
        if ckpt.kv is not None:
            try:
                adopted = eng.adopt_checkpoint_kv(req, ckpt.kv)
            except Exception:
                logger.exception("%s: KV adoption for %s failed; "
                                 "re-prefilling", self.node_id, rid)
                adopted = False
        if not adopted:
            # No image to swap in: restart from the original prompt and
            # replay the recorded outputs through decode steps.
            req = build_resumed_request(ckpt, replay=True)
        if getattr(ckpt, "handoff", False) and from_peer == self.node_id:
            # Local-restore rung: this PREFILL head is decoding the
            # request itself (no decode pool). Pin it local or the next
            # handoff tick would re-flag it the moment it resumes —
            # a park/restore ping-pong that decodes one token per
            # scheduler round trip.
            req.handoff_local = True  # type: ignore[attr-defined]
        self._chat_requests[rid] = req
        try:
            ok = eng.submit(req)
        except Exception as e:
            ok = False
            req.abort(str(e))
        if not ok:
            if not req.status.is_finished:
                req.abort("migration target queue full")
            try:
                eng.cache.release(req)   # frees adopted handles, if any
            except Exception:
                logger.exception("restore cleanup failed for %s", rid)
            self._finish(req)
            return
        handoff = bool(getattr(ckpt, "handoff", False))
        logger.info(
            "%s: restored %s request %s from %s (%d prior tokens, %s)",
            self.node_id, "handed-off" if handoff else "migrated", rid,
            from_peer, len(ckpt.output_ids),
            "KV image adopted" if adopted else "re-prefill + replay",
        )
        if not self.standalone:
            # Handoffs report through the same where_is table: pollers
            # that lose the prefill head still find the decode head.
            self.sender.send(
                self._sched_peer(), proto.MIGRATION_DONE,
                {"rid": rid, "head": self.node_id}, best_effort=True,
            )
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            "handoff_in" if handoff else "migrate_in",
            node=self.node_id, request_id=rid,
            source=from_peer, kv_adopted=adopted,
            prior_tokens=len(ckpt.output_ids),
        )
        if ckpt.traced:
            # Stitch the source head's spans into this process's trace
            # (bounded, sanitized), then link the boundary with a
            # migrate_in span — /debug/trace/<rid> here now shows one
            # timeline across heads.
            try:
                from parallax_tpu.obs.trace import get_trace_store
                from parallax_tpu.runtime.checkpoint import spans_from_wire

                store = get_trace_store()
                if ckpt.trace_spans:
                    store.adopt(rid, spans_from_wire(ckpt.trace_spans))
                store.add(
                    rid, self.node_id,
                    "kv_handoff_in" if handoff else "migrate_in",
                    t0=time.perf_counter(), dur=0.0,
                    args={"source": from_peer, "kv_adopted": adopted},
                )
            except Exception:  # pragma: no cover - tracing is best-effort
                logger.exception("trace adoption failed for %s", rid)
        if handoff:
            # Planned phase handoffs count under their own families so
            # churn dashboards (parallax_migrations_*) stay churn-only.
            from parallax_tpu.runtime import kv_handoff as _kvh

            _kvh.record_handoff(
                "local" if from_peer == self.node_id
                else ("kv_image" if adopted else "reprefill")
            )
        else:
            self._count_migration_in(
                "kv_image" if adopted else "replay", ckpt.parked_wall
            )

    def _count_migration_in(self, mode: str, parked_wall: float) -> None:
        """parallax_migrations_total + the park->resume latency
        histogram (the bench churn probe and the CI chaos smoke read
        both)."""
        try:
            from parallax_tpu.obs.registry import get_registry

            reg = get_registry()
            reg.counter(
                mnames.MIGRATIONS_TOTAL,
                "Requests restored on this head after a live migration "
                "or client resume",
                labelnames=("mode",),
            ).labels(mode=mode).inc()
            if parked_wall:
                park_s = max(0.0, time.time() - parked_wall)
                reg.histogram(
                    mnames.MIGRATION_MS,
                    "Park -> resume latency of migrated requests, ms",
                ).observe(park_s * 1e3)
                # Goodput time split: park->resume is churn overhead,
                # not serving time.
                from parallax_tpu.obs.goodput import get_goodput

                get_goodput().add_time("migrate", park_s)
        except Exception:  # pragma: no cover - metrics never break serving
            pass

    def _route_outputs(self, out) -> None:
        """Group packets by next hop and hand them to the sender
        pipeline (reference start_node_sender, p2p/server.py:628-755).
        Serialization and socket latency run on the per-peer sender
        workers — the step thread only enqueues; a dead or backed-up
        link surfaces as abort_path via the sender's failure callback."""
        by_peer: dict[str, list] = {}
        for ireq in out.forward:
            table = ireq.routing_table
            if ireq.next_token_id is not None or ireq.spec_accepted is not None:
                target = table[0] if table else self.node_id
            else:
                try:
                    idx = table.index(self.node_id)
                    target = table[idx + 1]
                except (ValueError, IndexError):
                    logger.error(
                        "%s: no next hop for %s (table=%s)",
                        self.node_id, ireq.request_id, table,
                    )
                    continue
            if target == self.node_id:
                self._post(("forward", ireq))
            else:
                # Detach from the step's batch array before queueing:
                # _emit_hidden hands out VIEWS into the full hidden_out,
                # and a queued frame holding one pins the whole batch
                # (every queued frame, every peer) until the worker
                # drains it — on a backed-up link that is max_queue
                # full-batch arrays, not max_queue frames. The copy is
                # one memcpy of the forwarded rows on the step thread
                # (serialization stays on the sender worker), skipped
                # when the view already spans its whole base (single
                # request: holding the view pins nothing extra).
                h = ireq.hidden_states
                base = getattr(h, "base", None)
                if base is not None and h.nbytes < base.nbytes:
                    ireq.hidden_states = h.copy()
                by_peer.setdefault(target, []).append(ireq)
        for peer, ireqs in by_peer.items():
            self.sender.send(
                peer, proto.FORWARD, self._forward_payload(peer, ireqs)
            )

        for req in out.finished:
            self._finish(req)

    def _forward_payload(self, peer: str, ireqs: list):
        """Lazy FORWARD serialization for the sender worker: negotiate
        the link's wire dtype (first use only), pack the tensors, and
        report raw vs wire bytes for the compression telemetry."""

        def build():
            t0 = time.perf_counter()
            wd = self._wire_dtype_for(peer)
            raw = sum(
                i.hidden_states.nbytes
                for i in ireqs if i.hidden_states is not None
            )
            reqs = [proto.ireq_to_wire(i, wire_dtype=wd) for i in ireqs]
            wire = sum(
                proto.tensor_nbytes(r.get("hidden_states")) for r in reqs
            )
            traced = [i for i in ireqs if i.trace]
            if traced:
                from parallax_tpu.obs.trace import get_trace_store

                store = get_trace_store()
                dur = time.perf_counter() - t0
                for i in traced:
                    store.add(
                        i.request_id, self.node_id, "transport_send",
                        t0=t0, dur=dur, args={"peer": peer, "bytes": wire},
                        merge=True,
                    )
            return {"reqs": reqs}, raw, wire

        return build

    def _finish(self, req: Request) -> None:
        # Broadcast release to the rest of the path (reference abort
        # broadcast, p2p/server.py:713-749) — through the async sender:
        # these ride the same per-peer FIFO as the data frames, so a
        # RELEASE never overtakes the request's final FORWARD, and the
        # step thread never blocks on a slow peer's socket.
        aborted = req.status.value == "finished_abort"
        for peer in req.routing_table:
            if peer == self.node_id:
                continue
            # best_effort: a lost RELEASE leaks a mirror until its
            # timeout — same contract as the old swallowed-exception
            # path; it must never escalate to aborting live requests.
            self.sender.send(
                peer, proto.RELEASE,
                {"rids": [req.request_id], "abort": aborted},
                best_effort=True,
            )
        if not self.standalone:
            # Fire-and-forget: the scheduler's round trip happens on its
            # link's sender worker.
            self.sender.send(
                self._sched_peer(), proto.REQUEST_COMPLETE,
                {
                    "path": req.routing_table or [self.node_id],
                    # Predicted-vs-actual routing telemetry: this head's
                    # admission-time prefix-cache hit for the request.
                    "rid": req.request_id,
                    "cached_tokens": req.num_cached_tokens,
                },
                best_effort=True,
            )
        self._finished.put(req)
        ev = self._request_events.pop(req.request_id, None)
        if ev is not None:
            ev.set()
