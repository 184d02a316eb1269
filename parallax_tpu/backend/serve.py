"""Single-host serving: engine pipeline + OpenAI HTTP frontend in one process.

Capability parity: the reference single-node path (``launch.py`` + vllm-rs
HTTP frontend + executor). Here the stage engines and the aiohttp frontend
share the process; a runner thread steps the pipeline continuously.
"""

from __future__ import annotations

import collections
import gc
import threading
import time

from parallax_tpu.backend.http_server import (
    BackendUnavailable,
    OpenAIFrontend,
    load_tokenizer,
)
from parallax_tpu.obs import names as mnames
from parallax_tpu.obs.registry import DEFAULT_COUNT_BUCKETS, get_registry
from parallax_tpu.obs.trace import (
    HostPauseMeter,
    get_slow_visits,
    host_span,
)
from parallax_tpu.runtime.engine import (
    EngineConfig,
    StageEngine,
    hybrid_state_slots,
)
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)


# What the idle loop waits for at most before it looks again: it wakes
# at once for an arrival or ``stop()``; the time-out only keeps the
# watchdog's beat coming (its poll interval is 1 s).
IDLE_WAIT_S = 0.5


class LocalRunner:
    """Steps an in-process pipeline on a background thread and completes
    per-request events.

    The loop's thread is the pipeline's only owner: ``submit`` and
    ``stop_request`` put an entry on the inbox and return, and the loop
    takes the entries in at the top of every round. No other thread
    calls ``pipeline.submit``, ``head.stop_request`` or ``step_round``,
    and nothing here takes a lock that is held while a round runs."""

    def __init__(self, pipeline: InProcessPipeline, watchdog=None):
        self.pipeline = pipeline
        # Optional stall watchdog (obs/watchdog.py): one beat per loop
        # pass — a step round that hangs stops the beats.
        self.watchdog = watchdog
        # The exception that ended the step loop, if one did. A dead
        # loop answers nothing, so it is terminal for the process:
        # waiting requests are failed, ``submit`` refuses, ``/healthz``
        # reads 503 and ``on_failure(exc)`` — set by ``serve`` to stop
        # the HTTP server so the process exits non-zero — is called once.
        self.failure: BaseException | None = None
        self.on_failure = None
        # request id -> (request, completion event) of unfinished work
        # the loop has taken in. The loop's own (and ``stop()``'s once
        # the loop has ended).
        self._pending: dict[str, tuple[Request, threading.Event]] = {}
        # ``(request, event)`` of a submit, a request id of a stop: put
        # by any thread, taken by the loop (``deque.append`` and
        # ``popleft`` are atomic). ``_arrival`` wakes the idle loop.
        self._inbox: collections.deque = collections.deque()
        self._arrival = threading.Event()
        self._stop = threading.Event()
        reg = get_registry()
        self._h_loop_gap = reg.histogram(
            mnames.LOOP_GAP_MS, mnames.help_text(mnames.LOOP_GAP_MS)
        )
        self._h_inbox_drained = reg.histogram(
            mnames.INBOX_DRAINED, mnames.help_text(mnames.INBOX_DRAINED),
            buckets=DEFAULT_COUNT_BUCKETS,
        )
        # The loop gap is held against its baseline like an engine's
        # phases (obs/trace.py ``SlowVisits``), and while the loop runs
        # a meter counts the moments the whole process stood still.
        get_slow_visits().bind_registry()
        self.pause_meter = HostPauseMeter()
        # Programs the engines had built when the heap was last settled.
        self._settled_programs = 0
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="pipeline-runner"
        )

    def _settle_heap(self) -> None:
        """The loop is idle and a program was built (compiled, or loaded
        from the compile cache) since the last time: collect once, now,
        and move what is alive to the collector's permanent generation
        (``gc.freeze``).
        Every compiled program leaves ~10^5 long-lived objects (its
        jaxpr, one equation an operation of every unrolled layer), and
        a full collection walks them all, holding the GIL: seconds with
        some tens of programs, at a moment the allocation counters
        choose — in the middle of a decode window as readily as here
        (PERF.md, PR 42). Frozen, they are walked never again; what a
        step allocates is collected as before."""
        programs = sum(e.programs_built for e in self.pipeline.engines)
        if programs == self._settled_programs:
            return
        self._settled_programs = programs
        t0 = time.perf_counter()
        gc.collect()
        seconds = time.perf_counter() - t0
        gc.freeze()
        logger.info(
            "heap settled after %d programs: the full collection took "
            "%.3f s, %d objects frozen",
            programs, seconds, gc.get_freeze_count(),
        )

    def start(self) -> None:
        self.pause_meter.start()
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._arrival.set()
        self._thread.join(timeout=3.0)
        self.pause_meter.stop()
        # The loop keeps one step in flight; a loop that has ended
        # leaves none (a failed loop discarded its own in ``_fail``),
        # and with it ended this thread is the pipeline's only one.
        if not self._thread.is_alive() and self.failure is None:
            try:
                self._wake(self.pipeline.settle())
            except Exception as e:
                self._fail(e)
        self._refuse_inbox("server stopped")

    def submit(self, request: Request) -> threading.Event:
        """Hand a request to the loop; returns at once with the event
        its finish sets. What can be refused without the loop's state
        is refused here, as ``StageEngine.submit`` and the scheduler
        would (the same exceptions, so the same HTTP answers): a failed
        loop, a prompt the engine's configuration rules out, a full
        wait queue (counted with what the inbox still holds)."""
        ev = threading.Event()
        with host_span("http.submit"):
            self._refuse_if_failed()
            head = self.pipeline.head
            head.check_prompt(request)
            sched = head.scheduler
            if (len(self._inbox) + len(sched.wait_queue)
                    >= sched.max_queue_size):
                raise RuntimeError("engine queue full")
            self._post((request, ev))
            # A loop that died between the check and the post may have
            # swept the inbox already (``_fail``).
            self._refuse_if_failed()
        return ev

    def _refuse_if_failed(self) -> None:
        if self.failure is not None:
            reason = f"step loop failed: {self.failure!r}"
            self._refuse_inbox(reason)
            raise BackendUnavailable(reason)

    def stop_request(self, request_id: str) -> None:
        """Gracefully finish a request early (stop-string match): the
        loop's next round marks it, collects and releases it."""
        self._post(request_id)

    def _post(self, entry) -> None:
        self._inbox.append(entry)
        self._arrival.set()

    def _take_inbox(self):
        """The inbox's entries in arrival order, until it is empty
        (whoever else takes from it meanwhile: each entry goes once)."""
        while True:
            try:
                yield self._inbox.popleft()
            except IndexError:
                return

    def _drain_inbox(self) -> None:
        """The loop's thread, top of a round: take in what arrived (a
        stop behind its own submit finds the request in the wait
        queue)."""
        drained = 0
        for entry in self._take_inbox():
            drained += 1
            if isinstance(entry, str):
                self.pipeline.head.stop_request(entry)
                continue
            request, ev = entry
            # Registered first: a submit that raises fails the loop, and
            # ``_fail`` wakes what is registered.
            self._pending[request.request_id] = entry
            if not self.pipeline.submit(request):
                # Two submits passed the count together at the last
                # free place: the later one learns it here.
                del self._pending[request.request_id]
                request.abort("engine queue full")
                ev.set()
        if drained:
            self._h_inbox_drained.observe(drained)

    def _refuse_inbox(self, reason: str) -> None:
        """No loop will take the inbox's entries in: wake the waiters of
        its submits with an aborted request."""
        for entry in self._take_inbox():
            if not isinstance(entry, str):
                request, ev = entry
                if not request.status.is_finished:
                    request.abort(reason)
                ev.set()

    def _loop(self) -> None:
        # ``runner.loop_gap``: from the end of one step round to the
        # start of the next (waking finished requests, the watchdog's
        # beat, the inbox, ``has_work``). It ends where the loop finds
        # no work instead.
        gap = None

        def end_gap():
            nonlocal gap
            if gap is not None:
                gap.__exit__(None, None, None)
                gap = None

        while not self._stop.is_set():
            if self.watchdog is not None:
                self.watchdog.beat("step_loop")
            # Cleared before the inbox is read: an entry put after this
            # sets it again, and the idle wait below returns at once.
            self._arrival.clear()
            try:
                self._drain_inbox()
                if not self.pipeline.has_work():
                    end_gap()
                    self._settle_heap()
                    with host_span("runner.idle"):
                        self._arrival.wait(IDLE_WAIT_S)
                    continue
                end_gap()
                with host_span("runner.step_round"):
                    finished = self.pipeline.step_round()
            except Exception as e:
                self._fail(e)
                break
            gap = host_span(
                "runner.loop_gap", self._h_loop_gap,
                visit=self.pipeline.visits,
            ).__enter__()
            self._wake(finished)
        end_gap()
        if self.failure is not None and self.on_failure is not None:
            self.on_failure(self.failure)

    def _wake(self, finished: list[Request]) -> None:
        """Complete the events of requests a step round finished."""
        for req in finished:
            _, ev = self._pending.pop(req.request_id, (None, None))
            if ev is not None:
                ev.set()

    def _fail(self, exc: BaseException) -> None:
        """The step loop died (its own thread, or ``stop()``'s after it
        ended): record why and release every waiter with an aborted
        request, so HTTP handlers answer 5xx instead of waiting out
        their timeout against a dead thread. The step the loop kept in
        flight is discarded with it."""
        logger.error("step loop failed; failing %d pending request(s)",
                     len(self._pending), exc_info=exc)
        self.failure = exc
        try:
            self.pipeline.settle(discard=True)
        except Exception:
            logger.exception("discarding the in-flight step failed")
        reason = f"step loop failed: {type(exc).__name__}: {exc}"
        for req, ev in self._pending.values():
            if not req.status.is_finished:
                req.abort(reason)
            ev.set()
        self._pending.clear()
        self._refuse_inbox(reason)

    def health(self) -> dict | None:
        """``/healthz`` component for the step loop: None while it runs."""
        if self.failure is None:
            return None
        return {
            "status": "failed",
            "error": f"{type(self.failure).__name__}: {self.failure}",
        }


def build_local_frontend(
    engines: list[StageEngine],
    tokenizer,
    model_name: str = "parallax-tpu",
    wire: bool = False,
    watchdog: bool = False,
    slo_config=None,
    qos_config=None,
) -> tuple[OpenAIFrontend, LocalRunner]:
    """``wire=True`` routes inter-stage packets through the real wire
    format (the in-process twin of the networked hop) — exercised by the
    observability tests so stitched traces cover the transport leg.
    ``watchdog=True`` runs the stall watchdog over the runner loop and
    each stage's admission queue (deep ``/healthz``); ``slo_config``
    (obs/slo.py SLOConfig) adds windowed SLO attainment / burn rates to
    the status payload."""
    pipeline = InProcessPipeline(engines, wire=wire)
    wd = None
    if watchdog:
        from parallax_tpu.obs.watchdog import StallWatchdog

        wd = StallWatchdog(node_id="local")
        wd.register_beat(
            "step_loop",
            lambda: sum(e.scheduler.num_requests() for e in engines),
        )
        for i, e in enumerate(engines):
            sched = e.scheduler

            def _admission(sched=sched):
                return (
                    float(len(sched.wait_queue)),
                    float(sched.admitted_total),
                    f"{len(sched.running)} running",
                )

            wd.register(f"admission[{i}]", _admission)
        wd.start()
    slo_tracker = None
    if slo_config is not None:
        from parallax_tpu.obs.slo import SLOTracker

        slo_tracker = SLOTracker(slo_config)
    runner = LocalRunner(pipeline, watchdog=wd)

    # Grammar-constrained decoding lives on the LAST stage (where sampling
    # happens); wire the tokenizer's raw byte vocabulary into it.
    last = engines[-1]
    if last.model.is_last:
        try:
            from parallax_tpu.constrained import grammar_vocab_from_tokenizer

            vocab, eos = grammar_vocab_from_tokenizer(tokenizer)
            last.set_grammar_vocab(vocab, eos)
        except Exception as e:  # no EOS id / no recoverable vocab
            logger.warning("grammar vocab unavailable (%s); "
                           "json_schema requests will be rejected", e)

    # Weight bytes resident on each local device (fixed after load): a
    # TP stage must show its shards spread, not parked on device 0.
    import jax as _jax

    param_bytes: dict[int, int] = {}
    for e in engines:
        for leaf in _jax.tree.leaves(e.params):
            for shard in getattr(leaf, "addressable_shards", ()):
                param_bytes[shard.device.id] = (
                    param_bytes.get(shard.device.id, 0) + shard.data.nbytes
                )

    def hardware():
        from parallax_tpu.utils.hw import device_report

        report = device_report()
        for dev in report["devices"]:
            dev["param_bytes"] = param_bytes.get(dev["id"], 0)
        return report

    def status():
        from parallax_tpu.obs.device import get_device_plane
        from parallax_tpu.obs.goodput import get_goodput
        from parallax_tpu.obs.registry import (
            get_registry,
            summarize_snapshots,
        )

        snaps = get_registry().histogram_snapshots()
        goodput = get_goodput().payload(
            chips=_jax.local_device_count()
        )
        out = {
            "mode": "single-host",
            # What JAX reports this process runs on (platform, kind,
            # count) and each local device's live memory counters.
            "hardware": hardware(),
            # Device attribution plane: the HBM ledger (per-class
            # bytes, headroom, invariant), compile observatory and
            # per-program device-time split — the single-host twin of
            # the swarm's /cluster/status device merge (obs/device.py).
            "device": get_device_plane().payload(),
            # Latency percentiles (TTFT/TPOT/e2e/step timing) from the
            # process registry — the single-host twin of the swarm's
            # cluster-wide heartbeat merge.
            "metrics": summarize_snapshots(snaps),
            # Goodput ledger: token usefulness buckets + the serve/
            # compile/swap/migrate/idle time split.
            "goodput": goodput,
            "stages": [
                {
                    "layers": [e.model.start_layer, e.model.end_layer],
                    # loop_passes, kv_cache_layers, kv_bytes_per_token:
                    # what a page id addresses (docs/memory.md).
                    **e.kv_layout(),
                    "running": len(e.scheduler.running),
                    "waiting": len(e.scheduler.wait_queue),
                    "num_pages": e.cfg.num_pages,
                    "free_pages": e.cache.num_free_pages,
                    "cached_pages": e.cache.prefix_cache.num_cached_pages,
                    # Two-phase decode telemetry (host_ms and
                    # readback_wait_ms EWMAs + overlap fraction).
                    "step_timing": e.step_timing.summary(),
                    # Prefix-cache / memory-tier counters (hit rates
                    # split device/host, occupancy, demotions,
                    # swap-ins, preemptions).
                    "cache_stats": e.cache_stats(),
                    # Active attention-kernel impl (pallas-fused /
                    # pallas-split / xla) + per-path dispatch counts —
                    # a silent fallback to the split or XLA path is
                    # visible here (docs/kernels.md).
                    "kernel": e.kernel_dispatch_summary(),
                    # Speculative-decoding ledger: per-source proposed/
                    # accepted/rejected, acceptance rate (the tuning
                    # signal, docs/decode_loop.md) and accepted tokens
                    # per chip-second. None while speculation is off.
                    "spec": e.spec_summary(),
                    # Constrained-decoding ledger: in-window feature
                    # rows, device mask steps, grammar-table builds vs
                    # cache hits, spec mask rejections and host-sync
                    # fallbacks (docs/decode_loop.md). None until a
                    # feature batch runs.
                    "constrained": e.constrained_summary(),
                }
                for e in engines
            ],
        }
        if wd is not None:
            out["health"] = wd.summary()
        if slo_tracker is not None:
            # Each status poll is one tracker sample: attainment + burn
            # over the local histograms and the ledger's finished/
            # aborted counts.
            req = goodput.get("requests") or {}
            out["slo"] = slo_tracker.observe_and_evaluate({
                "hists": snaps,
                "finished": req.get("finished") or 0,
                "aborted": req.get("aborted") or 0,
            })
        # Multi-tenant QoS (docs/qos.md): the head stage's class table,
        # shed/burn state and admission/shed/park counters.
        head_qos = engines[0].scheduler.qos
        if head_qos is not None:
            out["qos"] = head_qos.payload()
        return out

    def adapters():
        from parallax_tpu.ops.lora import intersect_adapter_names

        return intersect_adapter_names(
            e.adapter_names() for e in engines
        )

    from parallax_tpu.obs.timeline import LocalTimeline

    local_timeline = LocalTimeline(node_id="local")

    def timeline(fmt: str, limit: int):
        if fmt == "chrome":
            return local_timeline.export_chrome()
        return local_timeline.snapshot(limit=limit)

    def healthz():
        summary = (
            wd.summary() if wd is not None
            else {"status": "ok", "components": {}, "causes": []}
        )
        loop_health = runner.health()
        if loop_health is not None:
            summary["status"] = "failed"
            summary["components"]["step_loop"] = loop_health
            summary["causes"].append(loop_health["error"])
        return summary

    def request_spans(rate):
        for e in engines:
            e.sample_request_spans(rate)

    frontend = OpenAIFrontend(
        tokenizer,
        submit_fn=runner.submit,
        status_fn=status,
        model_name=model_name,
        stop_fn=runner.stop_request,
        adapters_fn=adapters,
        healthz_fn=healthz,
        timeline_fn=timeline,
        qos_config=qos_config,
        request_spans_fn=request_spans,
    )
    runner.start()
    return frontend, runner


def serve_main(args) -> int:
    """``parallax-tpu serve`` entry."""
    import os

    # Compile-time hygiene: restarts reload compiled executables from
    # disk instead of paying a recompilation storm (docs/decode_loop.md).
    from parallax_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache(getattr(args, "compilation_cache_dir", None))

    from parallax_tpu.config import (
        load_config,
        resolve_speculative_tokens,
    )
    from parallax_tpu.models.loader import load_stage_params
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.cache_manager import derive_num_pages
    from parallax_tpu.utils.hw import (
        default_host_cache_bytes,
        device_free_memory_bytes,
    )

    if not os.path.isdir(args.model_path) and "/" in args.model_path:
        # HF repo id: fetch just this stage's shard files (reference
        # selective_model_download; requires network reachability).
        from parallax_tpu.utils.model_download import selective_download

        args.model_path = selective_download(
            args.model_path, args.start_layer or 0, args.end_layer
        )
    config = load_config(args.model_path)
    start = args.start_layer or 0
    end = args.end_layer or config.num_hidden_layers

    tp_size = getattr(args, "tp_size", 0)
    sp_size = getattr(args, "sp_size", 0) or 0
    prefill_seq_parallel = bool(getattr(args, "prefill_seq_parallel", False))
    if prefill_seq_parallel and sp_size <= 1 and (tp_size or 0) <= 1:
        # One-knob sequence-parallel prefill: claim every local chip for
        # the seq axis when neither --sp-size nor TP spoke for them. The
        # engine gates the single-chip case with a registered warning.
        import jax as _jax

        sp_size = len(_jax.local_devices())
    from parallax_tpu.parallel.sp import sp_eligible

    if sp_size > 1 and not sp_eligible(config):
        # Models the engine refuses SP for must not claim (and waste)
        # sp x devices on a silently inert ring path.
        logger.warning(
            "--sp-size %d ignored: %s does not support ring-attention "
            "prefill (MLA/sparse/hybrid/window/sink attention)",
            sp_size, config.architecture,
        )
        sp_size = 0
    if sp_size > 1 and not tp_size:
        # SP claims the devices; TP defaults to off unless explicitly set.
        tp_size = 1
    mesh = None
    if tp_size != 1:
        import jax as _jax

        n = len(_jax.local_devices())
        if not tp_size:
            tp_size = n
        if tp_size > 1:
            from parallax_tpu.parallel import make_mesh

            # SP x TP: one combined mesh; the engine detects the sp axis
            # and runs the ring body inside the TP shard_map.
            mesh = make_mesh(tp_size=tp_size, sp_size=max(1, sp_size))
    model = create_stage_model(config, start, end, tp_size=max(1, tp_size))
    # LoRA merges into full-precision weights pre-finalize; on-load
    # quantization runs after the merge inside the loader.
    params = load_stage_params(
        model, args.model_path,
        quantize=getattr(args, "quantization", None),
        lora_path=getattr(args, "lora_path", None),
        mesh=mesh,
    )

    page_size = args.page_size
    if config.eva is not None:
        # A chunk within one page, a window's summaries and tokens on
        # whole pages (the published 16 / 2048 take the default 64).
        page_size = config.eva.fit_page_size(page_size)
        if page_size != args.page_size:
            logger.info(
                "--page-size %d -> %d: EVA chunks of %d in windows of %d",
                args.page_size, page_size, config.eva.chunk_size,
                config.eva.window_size,
            )
    sp_mesh = None
    sp_threshold = None
    if sp_size > 1:
        sp_threshold = getattr(args, "sp_threshold", 2048)
        if tp_size <= 1:
            from parallax_tpu.parallel import make_mesh

            sp_mesh = make_mesh(sp_size=sp_size, tp_size=1)
        # tp > 1: the combined mesh above carries the sp axis instead.
    draft = None
    draft_path = getattr(args, "draft_model_path", None)
    if draft_path:
        from parallax_tpu.runtime.engine import DraftProposer

        # Speculation runs only on the single-stage unsharded greedy fast
        # path; loading a draft model in a configuration where it can
        # never fire would silently waste HBM.
        if tp_size and tp_size > 1:
            raise ValueError("--draft-model-path requires tp-size 1 "
                             "(speculation runs unsharded)")
        if start != 0 or end != config.num_hidden_layers:
            raise ValueError("--draft-model-path requires a full "
                             "single-stage model (no layer split)")
        if config.is_hybrid:
            raise ValueError("--draft-model-path does not support hybrid "
                             "linear-attention main models")
        # Built AFTER enable_compilation_cache() above: the draft
        # engine re-traces its own prefill/decode lattice, and without
        # the persistent cache enabling speculation would pay a SECOND
        # compile storm on every restart (DraftProposer asserts the
        # reuse; tests/test_speculative.py pins it).
        draft_cfg = load_config(draft_path)
        draft_model = create_stage_model(
            draft_cfg, 0, draft_cfg.num_hidden_layers
        )
        draft_engine = StageEngine(
            draft_model,
            load_stage_params(draft_model, draft_path),
            EngineConfig(
                page_size=16,   # small pages -> small prefix-recompute tail
                num_pages=max(
                    512,
                    args.max_batch_size
                    * ((args.max_model_len + 15) // 16 + 1),
                ),
                max_batch_size=args.max_batch_size,
                max_model_len=args.max_model_len,
                kv_dtype=getattr(args, "kv_dtype", "bfloat16"),
                decode_lookahead=max(
                    1, getattr(args, "speculative_tokens", 0) or 4
                ),
            ),
        )
        draft = DraftProposer(draft_engine)
    # HBM budget, capped by the most pages the configured batch can ever
    # address (small models would otherwise derive absurd page counts).
    # Derived AFTER the draft engine exists so its params + KV are already
    # subtracted from free memory.
    addressable = (
        ((args.max_model_len + page_size - 1) // page_size + 1)
        * args.max_batch_size * 2
    )
    # Pages are counted over the stage's cache layers (the layers that
    # attend, once a pass of a looped stack); a hybrid's
    # state slots (the engine's 2 x batch active + prefix snapshot slots
    # + the null slot) come off the budget first.
    state_slots = 0
    if model.has_linear_layers:
        state_slots = hybrid_state_slots(
            args.max_batch_size,
            0 if getattr(args, "no_prefix_cache", False)
            else getattr(args, "linear_prefix_slots", 32),
        )
    num_pages = min(
        derive_num_pages(
            device_free_memory_bytes(args.kv_utilization),
            config, config.num_cache_layers(start, end), page_size,
            state_bytes=state_slots * config.state_bytes_per_slot(start, end),
        ),
        addressable,
    )
    engine = StageEngine(
        model,
        params,
        EngineConfig(
            page_size=page_size,
            num_pages=num_pages,
            max_batch_size=args.max_batch_size,
            max_model_len=args.max_model_len,
            max_num_tokens_per_batch=getattr(
                args, "max_num_tokens_per_batch", 2048
            ),
            prefill_chunk_size=getattr(args, "prefill_chunk_size", 1024),
            kv_dtype=getattr(args, "kv_dtype", "bfloat16"),
            enable_prefix_cache=not getattr(args, "no_prefix_cache", False),
            # Host-DRAM KV tier: sized from host RAM unless pinned by
            # flag (CPU backends default off — see
            # utils.hw.default_host_cache_bytes).
            host_cache_bytes=default_host_cache_bytes(
                override=getattr(args, "host_cache_bytes", None)
            ),
            linear_prefix_slots=getattr(args, "linear_prefix_slots", 32),
            sp_threshold=sp_threshold,
            # None/0 = adaptive multi-step decode (engine default).
            decode_lookahead=getattr(args, "decode_lookahead", None) or None,
            decode_pipeline=getattr(args, "decode_pipeline", 1) or 1,
            # Fused decode kernels (None = auto-on-TPU; docs/kernels.md).
            decode_fused=getattr(args, "decode_fused", None),
            # Fused ragged-prefill kernel + prefix-aware chunk skipping
            # + seq-parallel long-context prefill (docs/kernels.md).
            prefill_fused=getattr(args, "prefill_fused", None),
            prefill_chunk_skip=getattr(args, "prefill_chunk_skip", True),
            prefill_seq_parallel=prefill_seq_parallel,
            # A configured draft model implies speculation (default k=4).
            speculative_tokens=resolve_speculative_tokens(
                getattr(args, "speculative_tokens", 0),
                has_draft=draft is not None,
            ),
            speculative_ngram=getattr(args, "speculative_ngram", 3) or 3,
            # Single-host serving has no network hop; carried so a
            # worker spawned from this config inherits the operator's
            # wire choice (docs/networking.md).
            wire_dtype=getattr(args, "wire_dtype", None),
            # Observability: lifecycle-trace sampling + slow-request
            # flight threshold (docs/observability.md).
            trace_sample_rate=getattr(args, "trace_sample_rate", 0.0) or 0.0,
            slow_request_ms=getattr(args, "slow_request_ms", 30_000.0),
            # Multi-tenant QoS spec (docs/qos.md): classes + deadline
            # EDF + shed/park on this engine's local scheduler. The
            # default "off" wires no policy — zero per-step cost.
            qos=getattr(args, "qos", None),
            lora_max_adapters=getattr(args, "lora_max_adapters", 0) or 0,
        ),
        mesh=mesh,
        sp_mesh=sp_mesh,
        draft=draft,
    )
    from parallax_tpu.ops.lora import parse_adapter_spec

    for name, path in parse_adapter_spec(
        getattr(args, "lora_adapters", None)
    ).items():
        engine.load_adapter(name, path)
    tokenizer = load_tokenizer(args.model_path)
    slo_config = None
    slo_spec = getattr(args, "slo", None)
    if slo_spec:
        from parallax_tpu.obs.slo import parse_slo_spec

        # Fails fast on a malformed spec — a typo'd objective must not
        # silently track nothing.
        slo_config = parse_slo_spec(
            slo_spec,
            window_s=getattr(args, "slo_window_s", 300.0),
        )
    qos_config = None
    qos_spec = getattr(args, "qos", None)
    if qos_spec:
        from parallax_tpu.qos import parse_qos_spec

        # Fails fast on a malformed spec, like --slo.
        qos_config = parse_qos_spec(qos_spec)
        if qos_config is not None and qos_config.autoscale:
            # Registered gate (analysis/gates.py): the pool autoscaler
            # re-roles pipelines between the swarm's phase pools — a
            # single-host engine has no pools to rebalance.
            logger.warning(
                "qos autoscaler disabled: single-host serving has no "
                "phase pools to re-role (run a swarm scheduler with "
                "--qos ...,autoscale=1 for pool autoscaling)"
            )
    frontend, runner = build_local_frontend(
        [engine], tokenizer, model_name=args.model_path,
        watchdog=bool(getattr(args, "watchdog", False)),
        slo_config=slo_config,
        qos_config=qos_config,
    )
    # A dead step loop ends the process: the requests it held have been
    # failed; give their handlers a moment to answer, then stop serving.
    runner.on_failure = lambda exc: frontend.shutdown(delay_s=2.0)
    logger.info("serving %s layers [%d, %d) on :%d",
                args.model_path, start, end, args.port)
    frontend.run(host=args.host, port=args.port)
    runner.stop()
    return 1 if runner.failure is not None else 0
