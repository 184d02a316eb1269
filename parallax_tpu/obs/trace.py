"""Request-lifecycle tracing: a lightweight span recorder.

One bounded process-global :class:`TraceStore` collects spans for sampled
requests (``EngineConfig.trace_sample_rate``; default 0 = off). The trace
id is the request id; the sampled flag rides the FORWARD wire frames
(``IntermediateRequest.trace`` -> ``p2p/proto.py``), so spans emitted on
different pipeline stages — and across the in-process wire roundtrip —
stitch into ONE trace retrievable as Chrome trace-event JSON via
``GET /debug/trace/<request_id>`` (load it in ``chrome://tracing`` or
Perfetto).

Cost model: when tracing is off nothing here runs — the engine's
dispatch/resolve hot path guards every hook behind an empty-set check,
so the overlapped decode loop's dispatch median is unaffected. When a
request IS sampled, per-step decode spans coalesce into "decode" epochs
(adjacent same-name spans within ``MERGE_GAP_S`` merge, bumping a step
counter) so a 10k-token generation yields a bounded span list, not 10k
events.

Span timestamps use ``time.perf_counter()`` seconds; export rebases them
to the trace's first span so the JSON is viewer-friendly.

Host spans (:func:`host_span`, :func:`visit_span`) are the second kind of
span here: what the HOST does at each layer boundary of the serve hot
path, written into the JAX profiler's own trace (``.xplane.pb`` host
plane, the same clock as the device's ``XLA Ops`` line) while a profile
runs, and nowhere while none does. A span may also feed a registry
histogram, from the same two instants. :func:`clock_sync` marks a
``perf_counter`` reading on the profiler's clock, so the per-request
spans above can be laid on a device trace by whoever reads both.

What disturbed a visit is said by the same spans: the phases of a visit
that have a series are held against a running baseline of their own
duration (:class:`SlowVisits`: a span far over it is a *slow visit*, its
excess split by cause from process-wide clocks read at the span's two
instants), the loop thread's off-CPU time is summed over every visit,
and :class:`HostPauseMeter` counts the moments at which the whole
process stood still.
"""

from __future__ import annotations

import gc
import threading
import time
from collections import OrderedDict

from parallax_tpu.analysis.sanitizer import make_lock
from parallax_tpu.obs import names as mnames
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)

# Adjacent same-name spans on the same stage closer than this merge into
# one epoch span (decode steps arrive every few ms; a scheduling gap
# larger than this is interesting and breaks the epoch).
MERGE_GAP_S = 0.25


class TraceStore:
    """Bounded LRU store of per-request span lists (thread-safe)."""

    def __init__(self, capacity: int = 256, max_spans: int = 2048):
        self.capacity = capacity
        self.max_spans = max_spans
        self._traces: OrderedDict[str, list[dict]] = OrderedDict()
        self._lock = make_lock("obs.trace")

    # -- recording ---------------------------------------------------------

    def begin(self, trace_id: str) -> None:
        """Ensure a trace exists (idempotent — downstream stages call this
        when a sampled frame arrives for an id they have not seen)."""
        with self._lock:
            if trace_id in self._traces:
                return
            self._traces[trace_id] = {"spans": [], "open": {},
                                      "counters": []}
            while len(self._traces) > self.capacity:
                self._traces.popitem(last=False)

    def has(self, trace_id: str) -> bool:
        with self._lock:
            return trace_id in self._traces

    def add(
        self,
        trace_id: str,
        stage: str,
        name: str,
        t0: float,
        dur: float = 0.0,
        args: dict | None = None,
        merge: bool = False,
    ) -> None:
        """Record one complete span. ``merge=True`` coalesces it into the
        trace's previous span of the same (stage, name) when that span
        ends within ``MERGE_GAP_S`` of this one's start — the decode-epoch
        mechanism. Per-(stage, name) merging keeps epochs intact even
        when stages interleave (multi-stage pipelines alternate decode
        spans across stages every token)."""
        with self._lock:
            trace = self._traces.get(trace_id)
            if trace is None:
                return
            spans = trace["spans"]
            if merge:
                last = trace["open"].get((stage, name))
                if (
                    last is not None
                    and t0 - (last["t0"] + last["dur"]) <= MERGE_GAP_S
                ):
                    last["dur"] = max(last["dur"], t0 + dur - last["t0"])
                    la = last.setdefault("args", {})
                    la["steps"] = la.get("steps", 1) + 1
                    if args:
                        for k, v in args.items():
                            if isinstance(v, (int, float)) and k in la:
                                la[k] += v
                            else:
                                la[k] = v
                    return
            if len(spans) >= self.max_spans:
                return
            span = {"name": name, "stage": stage, "t0": t0, "dur": dur}
            if args:
                span["args"] = dict(args)
            spans.append(span)
            if merge:
                trace["open"][(stage, name)] = span

    def counter(
        self,
        trace_id: str,
        stage: str,
        name: str,
        t0: float,
        values: dict,
    ) -> None:
        """Record one counter sample (device attribution plane: HBM
        headroom, per-program device-time share). Exports as a Chrome
        counter track (``ph: "C"``) alongside the span lanes; bounded by
        ``max_spans`` like everything else in the store."""
        with self._lock:
            trace = self._traces.get(trace_id)
            if trace is None:
                return
            counters = trace.setdefault("counters", [])
            if len(counters) >= self.max_spans:
                return
            counters.append({
                "name": name, "stage": stage, "t0": t0,
                "values": {
                    str(k): v for k, v in values.items()
                    if isinstance(v, (int, float))
                },
            })

    def adopt(self, trace_id: str, spans: list[dict]) -> int:
        """Seed a trace with spans recorded on ANOTHER host (live
        migration: the source head ships its TraceStore spans inside the
        checkpoint frame so ``/debug/trace/<rid>`` on the target shows
        one stitched timeline across heads). Spans are sanitized
        field-by-field — they arrive off the wire — and bounded by
        ``max_spans``; returns how many were adopted. Caller owns any
        clock rebasing (``t0`` must already be in this process's
        ``perf_counter`` domain)."""
        self.begin(trace_id)
        adopted = 0
        with self._lock:
            trace = self._traces.get(trace_id)
            if trace is None:
                return 0
            out = trace["spans"]
            for s in spans or ():
                if len(out) >= self.max_spans:
                    break
                if not isinstance(s, dict):
                    continue
                try:
                    span = {
                        "name": str(s["name"])[:64],
                        "stage": str(s.get("stage") or "?")[:64],
                        "t0": float(s["t0"]),
                        "dur": max(0.0, float(s.get("dur") or 0.0)),
                    }
                except (KeyError, TypeError, ValueError):
                    continue
                args = s.get("args")
                if isinstance(args, dict):
                    span["args"] = {
                        str(k)[:64]: v for k, v in list(args.items())[:16]
                        if isinstance(v, (int, float, str, bool))
                        or v is None
                    }
                out.append(span)
                adopted += 1
        return adopted

    # -- export ------------------------------------------------------------

    def spans(self, trace_id: str) -> list[dict] | None:
        with self._lock:
            trace = self._traces.get(trace_id)
            if trace is None:
                return None
            return [dict(s) for s in trace["spans"]]

    def counters(self, trace_id: str) -> list[dict]:
        with self._lock:
            trace = self._traces.get(trace_id)
            if trace is None:
                return []
            return [dict(c) for c in trace.get("counters", ())]

    def export_chrome(self, trace_id: str) -> dict | None:
        """Chrome trace-event JSON (``chrome://tracing`` / Perfetto):
        complete ("X") events, one thread lane per pipeline stage, plus
        counter ("C") tracks for the device attribution samples."""
        spans = self.spans(trace_id)
        if spans is None:
            return None
        counters = self.counters(trace_id)
        base = min(
            (s["t0"] for s in spans + counters), default=0.0
        )
        events = [
            {
                "name": s["name"],
                "cat": "request",
                "ph": "X",
                "ts": round((s["t0"] - base) * 1e6, 3),
                "dur": round(s["dur"] * 1e6, 3),
                "pid": 1,
                "tid": s["stage"],
                "args": s.get("args", {}),
            }
            for s in sorted(spans, key=lambda s: s["t0"])
        ]
        events.extend(
            {
                "name": c["name"],
                "cat": "device",
                "ph": "C",
                "ts": round((c["t0"] - base) * 1e6, 3),
                "pid": 1,
                "tid": c["stage"],
                "args": c["values"],
            }
            for c in sorted(counters, key=lambda c: c["t0"])
        )
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "metadata": {"trace_id": trace_id},
        }

    def breakdown(self, trace_id: str) -> dict | None:
        """Total ms per span name — the flight recorder's slow-request
        breakdown payload."""
        spans = self.spans(trace_id)
        if not spans:
            return None
        out: dict[str, float] = {}
        for s in spans:
            out[s["name"]] = round(
                out.get(s["name"], 0.0) + s["dur"] * 1e3, 3
            )
        return out


# -- host spans on the profiler's clock ---------------------------------------

SPAN_PREFIX = "parallax."

# jax.profiler's annotation classes, bound on first use: this module is
# imported by processes that never load JAX (scheduler, lint).
_annotations = None
# The visit the calling thread is inside (``visit_span``).
_current = threading.local()


def _annotation_types():
    global _annotations
    if _annotations is None:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        _annotations = (TraceAnnotation, StepTraceAnnotation)
    return _annotations


class host_span:
    """``with host_span("engine.pack", series, rows=8):`` — one span of
    host work.

    Enters a ``jax.profiler.TraceAnnotation("parallax.<name>", **args)``:
    while the profiler runs the span lands in the trace's host plane; while
    it does not, the annotation is a flag check. Inside a
    :func:`visit_span` the span carries ``visit=<n>`` unless ``args``
    gives one. Where ``series`` (a registry histogram child) is given,
    the span's duration in ms is observed into it on exit, taken with
    ``time.perf_counter`` at the annotation's own two ends; setting
    ``span.series = None`` inside the block withdraws that. ``span.ms``
    holds the duration after exit.

    A span with a series whose name is one of :data:`SLOW_PHASES` is
    also held against the baseline of its phase and ``span.kind`` (the
    program or plan kind, set any time before exit) by
    :class:`SlowVisits`.
    """

    __slots__ = ("series", "ms", "kind", "name", "args", "_annotation",
                 "_t0", "_cpu_bound", "_marks")

    def __init__(self, name: str, series=None, **args):
        visit = current_visit()
        if visit is not None:
            args.setdefault("visit", visit)
        self._annotation = _annotation_types()[0](SPAN_PREFIX + name, **args)
        self.series = series
        self.ms = 0.0
        self.kind = ""
        self.name = name
        self.args = args
        self._cpu_bound = None if series is None else SLOW_PHASES.get(name)

    def __enter__(self) -> "host_span":
        self._annotation.__enter__()
        if self._cpu_bound is not None:
            self._marks = _clock_marks(self._cpu_bound)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.ms = (time.perf_counter() - self._t0) * 1e3
        self._annotation.__exit__(*exc)
        if self.series is not None:
            self.series.observe(self.ms)
            if self._cpu_bound is not None:
                _SLOW_VISITS.close(self)

    @property
    def perf_counter_ns(self) -> int:
        """``time.perf_counter_ns()`` at the span's start."""
        return int(self._t0 * 1e9)


class visit_span:
    """One visit of the step loop: a
    ``jax.profiler.StepTraceAnnotation("parallax.visit", step_num=n)``
    whose number every :class:`host_span` entered inside it (on this
    thread) carries as ``visit``."""

    __slots__ = ("_n", "_annotation")

    def __init__(self, n: int):
        self._n = n
        self._annotation = _annotation_types()[1](
            SPAN_PREFIX + "visit", step_num=n
        )

    def __enter__(self) -> "visit_span":
        _current.visit = self._n
        self._annotation.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        _current.visit = None


def current_visit() -> int | None:
    """The visit the calling thread is inside (``visit_span``), or None."""
    return getattr(_current, "visit", None)


def _marker(name: str, **args) -> None:
    """A zero-length ``parallax.<name>`` span: on the trace while a
    profile runs, a flag check while none does."""
    with _annotation_types()[0](SPAN_PREFIX + name, **args):
        pass


def clock_sync() -> int:
    """Mark this instant on the profiler's clock: a zero-length
    ``parallax.clock_sync`` span whose ``perf_counter_ns`` argument is
    this process's ``time.perf_counter_ns()`` reading, returned too.
    Emitted when a profile starts and stops; the offset between the two
    clocks is the marker's trace timestamp minus its argument."""
    now = time.perf_counter_ns()
    _marker("clock_sync", perf_counter_ns=now)
    return now


def traced_device_end_ns(xplane: str) -> int | None:
    """This process's ``perf_counter_ns`` at the end of the last device
    event in one ``.xplane.pb``. The profiler stops its device tracer
    last, some ms after ``stop_trace`` is called (12-18 ms on the v5e;
    PERF.md, PR 26), so a device that never idles leaves events past the
    stop's :func:`clock_sync`. The trace's clock is laid on this
    process's by its ``parallax.clock_sync`` marks (the smallest offset:
    a mark's timestamp can only lag its reading). None where the trace
    holds no device plane or no mark (the CPU backend writes none)."""
    from jax.profiler import ProfileData

    end = offset = None
    for plane in ProfileData.from_file(xplane).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device:
                    t = ev.start_ns + ev.duration_ns
                    if end is None or t > end:
                        end = t
                elif ev.name == SPAN_PREFIX + "clock_sync":
                    off = ev.start_ns - int(dict(ev.stats)["perf_counter_ns"])
                    if offset is None or off < offset:
                        offset = off
    if end is None or offset is None:
        return None
    return int(end - offset)


# -- what disturbed a visit ----------------------------------------------------

# The phases of a visit held against a baseline -> whether the phase is
# CPU-bound by design (the read-back wait is the one that is not).
SLOW_PHASES = {
    "sched.form_plan": True,
    "engine.pack": True,
    "engine.commit": True,
    "runner.loop_gap": True,
    "engine.readback_wait": False,
}
# A span is slow where it ran longer than max(floor, factor x baseline).
# Chosen on the chip (PERF.md, PR 44) so that the clean probes read ~0:
# the floor is over a hybrid's snapshot commits (3 copies of 1.7 ms
# where 0.4 is normal), the factor over the spread of a pack.
SLOW_FLOOR_MS = 10.0
SLOW_FACTOR = 1.5
# ... and, for a (phase, kind) whose spans spread by structure, longer
# than baseline + this many mean deviations: a prefill step's read-back
# wait is ~5 ms or ~100 as a window was queued ahead of it or not
# (serve's full batch on the chip, PERF.md, PR 44), which is no
# disturbance. A steady kind's deviation is a fraction of its floor.
SLOW_DEVIATIONS = 4.0
# Spans of one (phase, kind) before its baseline is trusted: until then
# the baseline is the least seen and only what a known cause took
# (compile, trace, gc) can make a span slow.
SLOW_WARM_SPANS = 8
_BASELINE_SHARE = 1.0 / 16
# Causes of a slow CPU-bound phase, and of a slow read-back wait, in
# the order in which they are taken out of the excess; the last is the
# rest.
CPU_CAUSES = ("compile", "trace", "gc", "off_cpu", "python")
WAIT_CAUSES = ("gc", "paused", "device")
# One WARNING line at most every so often, but always for a visit that
# ran this far over a trusted baseline: the stall someone will look for.
_WARN_EVERY_S = 10.0
_WARN_ALWAYS_MS = 100.0


class _JitSeconds(threading.local):
    """Seconds the JAX monitoring listener (utils/compile_cache.py) saw
    on the calling thread: backend compiles (loads from the persistent
    cache with them), and traces to jaxprs with lowerings to MLIR; and
    the decoder blocks traced on it (``note_block_trace``)."""

    compile = 0.0
    trace = 0.0
    blocks = 0


_jit = _JitSeconds()
# Process-wide: seconds inside the cyclic collector, and the start of
# the pass in progress (``gc.callbacks``).
_gc = [0.0, 0.0]
# The running pause meter (``HostPauseMeter.start``), or None.
_pause_meter = None


def note_jit_seconds(kind: str, seconds: float) -> None:
    """``kind`` is ``"compile"`` or ``"trace"``: called by the monitoring
    listener on the thread that compiled or traced."""
    if kind == "compile":
        _jit.compile += seconds
    else:
        _jit.trace += seconds
        _SLOW_VISITS.count_trace(seconds * 1e3)


def jit_trace_seconds() -> float:
    """Trace and lowering seconds seen on the calling thread so far."""
    return _jit.trace


def note_block_trace() -> None:
    """The Python body of a decoder block runs (models/base.py
    ``StageModel._block``): under a trace, never in a step."""
    _jit.blocks += 1
    _SLOW_VISITS.count_block_trace()


def block_traces() -> int:
    """Decoder blocks traced on the calling thread so far."""
    return _jit.blocks


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc[1] = time.perf_counter()
    else:
        _gc[0] += time.perf_counter() - _gc[1]


def _clock_marks(cpu_bound: bool) -> tuple:
    """The clocks a slow span's excess is split by, as they read now:
    this thread's CPU seconds (a CPU-bound phase) or the pause meter's
    ms (the read-back wait), then compile, trace and collector
    seconds."""
    meter = _pause_meter
    return (
        time.thread_time() if cpu_bound
        else 0.0 if meter is None else meter.read(),
        _jit.compile, _jit.trace, _gc[0],
    )


class SlowVisits:
    """The slow-visit ledger of the step loop's host spans.

    Per (phase, kind) a running baseline of the span's duration; a span
    longer than ``max(SLOW_FLOOR_MS, SLOW_FACTOR * baseline)`` is a slow
    visit:
    its excess over the baseline goes to
    ``parallax_slow_visit_excess_ms_total{phase,cause}`` split by cause
    (the causes sum to the excess), the visit to
    ``parallax_slow_visits_total{phase}``, and one record to the flight
    recorder's event ring (``slow_visit``), a rate-limited WARNING and
    a ``parallax.slow_visit`` marker on the trace. Beside it
    ``parallax_loop_offcpu_ms_total`` grows by wall minus thread CPU
    over every CPU-bound span, slow or not.

    Baseline and deviation follow a slow span only as far as the limit
    it broke, so one stall does not raise them and a lasting change is absorbed
    after some tens of spans; the limit also stands
    ``SLOW_DEVIATIONS`` mean deviations over the baseline, so a kind
    whose spans spread by structure is not slow half the time. The hot path takes no lock: a phase's
    spans come from the loop's one thread.
    """

    def __init__(self):
        # (phase, kind) -> [spans seen, baseline ms, baseline off-CPU ms,
        # mean deviation from the baseline ms]
        self._base: dict[tuple[str, str], list] = {}
        self._c_slow: dict[str, object] = {}
        self._c_excess: dict[tuple[str, str], object] = {}
        self._c_offcpu = None
        self._c_trace = None
        self._c_blocks = None
        self._warned_at = 0.0
        self._unwarned = 0

    def bind_registry(self, registry=None) -> None:
        """Create every series at 0 (a scrape that lacks a series reads
        as nothing, not as none), and hook the collector's clock."""
        if self._c_offcpu is not None and registry is None:
            return
        if registry is None:
            from parallax_tpu.obs.registry import get_registry

            registry = get_registry()
        slow = registry.counter(
            mnames.SLOW_VISITS_TOTAL,
            mnames.help_text(mnames.SLOW_VISITS_TOTAL),
            labelnames=("phase",),
        )
        excess = registry.counter(
            mnames.SLOW_VISIT_EXCESS_MS_TOTAL,
            mnames.help_text(mnames.SLOW_VISIT_EXCESS_MS_TOTAL),
            labelnames=("phase", "cause"),
        )
        for phase, cpu_bound in SLOW_PHASES.items():
            self._c_slow[phase] = slow.labels(phase=phase)
            for cause in CPU_CAUSES if cpu_bound else WAIT_CAUSES:
                self._c_excess[phase, cause] = excess.labels(
                    phase=phase, cause=cause
                )
        self._c_trace = registry.counter(
            mnames.JIT_TRACE_MS_TOTAL,
            mnames.help_text(mnames.JIT_TRACE_MS_TOTAL),
        ).labels()
        self._c_blocks = registry.counter(
            mnames.BLOCK_TRACES_TOTAL,
            mnames.help_text(mnames.BLOCK_TRACES_TOTAL),
        ).labels()
        self._c_offcpu = registry.counter(
            mnames.LOOP_OFFCPU_MS_TOTAL,
            mnames.help_text(mnames.LOOP_OFFCPU_MS_TOTAL),
        ).labels()
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def count_trace(self, ms: float) -> None:
        if self._c_trace is not None:
            self._c_trace.inc(ms)

    def count_block_trace(self) -> None:
        if self._c_blocks is not None:
            self._c_blocks.inc()

    def baseline_ms(self, phase: str, kind: str = "") -> float | None:
        b = self._base.get((phase, kind))
        return None if b is None else b[1]

    def close(self, span: host_span) -> None:
        """Hold a finished span against its baseline."""
        m0, m1 = span._marks, _clock_marks(span._cpu_bound)
        ms = span.ms
        compile_ms, trace_ms, gc_ms = (
            max(0.0, (m1[i] - m0[i]) * 1e3) for i in (1, 2, 3)
        )
        off_ms = 0.0
        if span._cpu_bound:
            off_ms = max(0.0, ms - (m1[0] - m0[0]) * 1e3)
            if self._c_offcpu is not None:
                self._c_offcpu.inc(off_ms)
        key = span.name, span.kind
        b = self._base.get(key)
        if b is None:
            b = self._base[key] = [0, ms, off_ms, 0.0]
        n, base, base_off, dev = b
        if n < SLOW_WARM_SPANS:
            # No baseline yet: slow only by what a known cause took (a
            # new program's first span holds its compile), and such a
            # span says nothing of the normal one.
            took = min(ms, compile_ms + trace_ms + gc_ms)
            if took > SLOW_FLOOR_MS:
                self._slow(span, ms - took,
                           (compile_ms, trace_ms, gc_ms, 0.0), known=False)
                if n == 0:
                    del self._base[key]
            else:
                # The least seen, and the widest it was left by.
                low = min(base, ms)
                b[:] = (n + 1, low, min(base_off, off_ms),
                        max(dev + base, ms) - low)
            return
        limit = max(SLOW_FLOOR_MS, SLOW_FACTOR * base,
                    base + SLOW_DEVIATIONS * dev)
        b[3] = dev + (abs(min(ms, limit) - base) - dev) * _BASELINE_SHARE
        if ms > limit:
            self._slow(span, base, (
                (compile_ms, trace_ms, gc_ms, off_ms - base_off)
                if span._cpu_bound else (gc_ms, m1[0] - m0[0])
            ))
            ms = limit
        else:
            b[2] = base_off + (off_ms - base_off) * _BASELINE_SHARE
        b[1] = base + (ms - base) * _BASELINE_SHARE

    def _slow(self, span: host_span, base: float, amounts: tuple,
              known: bool = True) -> None:
        """Count one slow visit: its excess over ``base`` split by
        cause — ``amounts`` is what each cause but the last could have
        taken, in the order of ``CPU_CAUSES`` (or ``WAIT_CAUSES``); each
        takes what is left at most, the last the rest. ``known``: the
        baseline is a trusted one (not a new program's first spans,
        whose builds set-up is full of)."""
        ms = span.ms
        rest = excess = ms - base
        causes = CPU_CAUSES if span._cpu_bound else WAIT_CAUSES
        split = {}
        for cause, amount in zip(causes, amounts):
            split[cause] = part = min(rest, max(0.0, amount))
            rest -= part
        split[causes[-1]] = rest
        phase = span.name
        if self._c_offcpu is not None:
            self._c_slow[phase].inc()
            for cause, part in split.items():
                if part > 0.0:
                    self._c_excess[phase, cause].inc(part)
        args = span.args
        record = {
            "visit": args.get("visit", -1),
            "phase": phase,
            "ms": round(ms, 3),
            "baseline_ms": round(base, 3),
            "excess_ms": round(excess, 3),
            **{c: round(part, 3) for c, part in split.items()},
            "rows": args.get("rows", -1),
            "tokens": args.get("tokens", -1),
            "program": span.kind,
            "perf_counter_ns": span.perf_counter_ns,
        }
        _marker("slow_visit", **record)
        from parallax_tpu.obs.flight import get_flight

        get_flight().event("slow_visit", **record)
        now = time.monotonic()
        if (not (known and excess >= _WARN_ALWAYS_MS)
                and now - self._warned_at < _WARN_EVERY_S):
            self._unwarned += 1
            return
        logger.warning(
            "slow visit %s: %s took %.1f ms where %.1f is normal (%s); "
            "%d more since the last such line",
            record["visit"], phase, ms, base,
            ", ".join(f"{c} {part:.1f}" for c, part in split.items()
                      if part > 0.0),
            self._unwarned,
        )
        self._warned_at, self._unwarned = now, 0


_SLOW_VISITS = SlowVisits()


def get_slow_visits() -> SlowVisits:
    """The process-wide slow-visit ledger (every ``host_span`` closes
    into it)."""
    return _SLOW_VISITS


class HostPauseMeter:
    """Counts the moments at which the whole process stood still.

    A daemon thread sleeps ``INTERVAL_S`` again and again; the part of
    an oversleep beyond ``THRESHOLD_MS`` is a pause
    (``parallax_host_pause_ms_total``, ``parallax_host_pauses_total``,
    a ``parallax.host_pause`` marker with ``ms``): the machine froze
    every process, the process was stopped, or every core was taken for
    that long. The threshold stands over what waiting for the GIL behind
    a busy step loop costs the thread (PERF.md, PR 44, has the
    oversleeps it was set against). :meth:`read` counts the sleep in
    progress too, so a span that ends before this thread has woken
    reads its pause all the same.
    """

    INTERVAL_S = 0.01
    THRESHOLD_MS = 20.0

    def __init__(self, clock=time.perf_counter, sleep=None, registry=None):
        self._clock = clock
        self._stop = threading.Event()
        self._sleep = sleep or self._stop.wait
        # (pause ms counted so far, when the sleep in progress is due to
        # end or None): replaced whole, so a reader sees one or the other.
        self._state = (0.0, None)
        self._thread = None
        if registry is None:
            from parallax_tpu.obs.registry import get_registry

            registry = get_registry()
        self._c_ms = registry.counter(
            mnames.HOST_PAUSE_MS_TOTAL,
            mnames.help_text(mnames.HOST_PAUSE_MS_TOTAL),
        ).labels()
        self._c_pauses = registry.counter(
            mnames.HOST_PAUSES_TOTAL,
            mnames.help_text(mnames.HOST_PAUSES_TOTAL),
        ).labels()

    def read(self) -> float:
        """Pause ms so far, with what the sleep in progress has already
        overrun its threshold by."""
        total, due = self._state
        if due is None:
            return total
        return total + max(
            0.0, (self._clock() - due) * 1e3 - self.THRESHOLD_MS
        )

    def tick(self) -> None:
        """One sleep, and the count of what it overran."""
        due = self._clock() + self.INTERVAL_S
        self._state = (self._state[0], due)
        self._sleep(self.INTERVAL_S)
        pause = (self._clock() - due) * 1e3 - self.THRESHOLD_MS
        if pause <= 0.0:
            self._state = (self._state[0], None)
            return
        self._state = (self._state[0] + pause, None)
        self._c_ms.inc(pause)
        self._c_pauses.inc()
        _marker("host_pause", ms=round(pause, 3))

    def start(self) -> None:
        global _pause_meter
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="host-pause-meter"
        )
        _pause_meter = self
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            self.tick()

    def stop(self) -> None:
        global _pause_meter
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)
        if _pause_meter is self:
            _pause_meter = None


_STORE = TraceStore()


def get_trace_store() -> TraceStore:
    """The process-wide trace store (all pipeline stages in one process
    share it, which is what stitches multi-stage spans into one trace)."""
    return _STORE
