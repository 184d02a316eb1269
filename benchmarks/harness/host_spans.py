"""The device's idle time, by what the host was doing meanwhile.

The program writes its host spans (``parallax_tpu/obs/trace.py``
``host_span``: ``parallax.sched.form_plan``, ``parallax.engine.pack``,
...; one ``parallax.visit`` step a visit) into the profiler's own trace,
on the clock of the device's ``XLA Ops`` line. This module reads both
from one ``.xplane.pb`` with ``jax.profiler.ProfileData`` and nothing
else: the idle gaps of each device plane (the complement of the union
of its operations, between its first and its last), cut along the
boundaries of the spans of the thread that steps the engine (the one
whose line holds the ``parallax.visit`` events), each piece summed under
the innermost span that covers it, or ``unattributed`` under none.

A trace without ``parallax.visit`` events (a program older than the
spans) has nothing to attribute: ``attribute`` returns None and the two
readers built on it leave their metric out.

``python -m benchmarks.harness.host_spans <file-or-dir>`` prints the
attribution; ``--record <dir>`` records a small trace of a toy program
under the program's own spans on whatever device JAX has (the test
fixture was made so, on the chip).
"""

from __future__ import annotations

import functools
import json
import sys

from benchmarks.harness.trace_reduce import (
    DEVICE_PLANE,
    OPS_LINE,
    find_xplane,
)

SPAN_PREFIX = "parallax."
VISIT = "parallax.visit"
UNATTRIBUTED = "unattributed"
# Host time in which the engine had nothing to run is no boundary's.
NOT_A_BOUNDARY = ("runner.idle", UNATTRIBUTED)


def merged(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """The union of ``(start, end)`` intervals as disjoint sorted ones."""
    out: list[tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def innermost_timeline(spans: list[tuple[int, int, str]]):
    """Spans of one thread (properly nested, as a thread's are) as
    disjoint sorted pieces ``(start, end, name of the innermost span
    there)``."""
    points = []
    for i, (s, e, _) in enumerate(spans):
        if e > s:
            # At one instant ends sort before starts, and an outer span
            # opens before and closes after its children.
            points.append((s, 1, -(e - s), i))
            points.append((e, 0, e - s, i))
    points.sort()
    out, stack, at = [], [], None
    for t, opens, _, i in points:
        if stack and t > at:
            out.append((at, t, spans[stack[-1]][2]))
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
        at = t
    return out


def split_by_spans(gaps: list[tuple[int, int]], timeline) -> dict[str, int]:
    """``{span name: ns of the gaps under it}``, both lists sorted."""
    out: dict[str, int] = {}
    j = 0
    for g0, g1 in gaps:
        while j < len(timeline) and timeline[j][1] <= g0:
            j += 1
        covered, k = 0, j
        while k < len(timeline) and timeline[k][0] < g1:
            a, b = max(g0, timeline[k][0]), min(g1, timeline[k][1])
            if b > a:
                out[timeline[k][2]] = out.get(timeline[k][2], 0) + b - a
                covered += b - a
            k += 1
        if g1 - g0 > covered:
            out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0) + g1 - g0 - covered
    return out


def read(path: str) -> dict | None:
    """Each device's busy intervals and the step thread's spans of one
    trace (ns, the trace's clock), or None where there is no trace file."""
    from jax.profiler import ProfileData

    file = find_xplane(path)
    if file is None:
        return None
    devices, spans = [], []
    for plane in ProfileData.from_file(file).planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    busy = merged([
                        (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in line.events])
                    if busy:
                        devices.append(busy)
            continue
        for line in plane.lines:
            ours = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                     ev.name) for ev in line.events
                    if ev.name.startswith(SPAN_PREFIX)]
            if any(name == VISIT for _, _, name in ours):
                spans += ours
    return {"file": file, "devices": devices, "spans": spans}


@functools.lru_cache(maxsize=4)
def attribute(path: str) -> dict | None:
    """The attribution of one trace (kept by path: a run's readers and
    its ``breakdown`` share one parse of the file):

    ``idle_s`` device idle seconds inside the traced span (mean over the
    chips), ``span_s`` that span, ``by_span`` the idle seconds by
    innermost host span (names without the ``parallax.`` prefix, largest
    first), ``visits`` the visits the span held (span over the mean
    period between the starts of the ``parallax.visit`` steps inside
    it), ``idle_ms_per_visit`` and ``attributed_share`` (% of the idle
    time under a span that is a boundary of the hot path)."""
    raw = read(path)
    if raw is None or not raw["devices"] or not raw["spans"]:
        return None
    spans = raw["spans"]
    timeline = innermost_timeline(spans)
    chips = len(raw["devices"])
    idle_ns = span_ns = 0
    by_span: dict[str, float] = {}
    periods = []
    for busy in raw["devices"]:
        t0, t1 = busy[0][0], busy[-1][1]
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        span_ns += t1 - t0
        idle_ns += sum(b - a for a, b in gaps)
        for name, ns in split_by_spans(gaps, timeline).items():
            name = name.removeprefix(SPAN_PREFIX)
            by_span[name] = by_span.get(name, 0.0) + ns * 1e-9 / chips
        starts = sorted(s for s, _, name in spans
                        if name == VISIT and t0 <= s < t1)
        if len(starts) >= 2:
            periods.append((starts[-1] - starts[0]) / (len(starts) - 1))
    if not periods or idle_ns <= 0:
        return None
    idle_s, span_s = idle_ns * 1e-9 / chips, span_ns * 1e-9 / chips
    visits = span_s / (sum(periods) / len(periods) * 1e-9)
    owned = sum(s for name, s in by_span.items()
                if name not in NOT_A_BOUNDARY)
    return {
        "file": raw["file"], "chips": chips,
        "idle_s": idle_s, "span_s": span_s, "visits": visits,
        "by_span": dict(sorted(by_span.items(), key=lambda kv: -kv[1])),
        "idle_ms_per_visit": idle_s * 1e3 / visits,
        "attributed_share": 100.0 * owned / idle_s,
    }


def idle_gaps(path: str) -> list | None:
    """``breakdown.idle_gaps`` by host span: ``[[name, seconds], ...]``,
    largest first; None where the trace holds no span."""
    att = attribute(path)
    return None if att is None else [[k, v] for k, v in att["by_span"].items()]


def record(out_dir: str) -> str:
    """A small trace of a toy program under the program's own spans: four
    visits that pack, wait for the read-back and then "commit" for 4 ms
    while the device idles, 2 ms under no span between them."""
    import time

    import jax
    import jax.numpy as jnp

    from parallax_tpu.obs.trace import clock_sync, host_span, visit_span

    @jax.jit
    def toy(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x.sum()

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    toy(x, w).block_until_ready()
    jax.profiler.start_trace(out_dir)
    clock_sync()
    for n in range(1, 5):
        with visit_span(n):
            with host_span("engine.pack", rows=1):
                y = toy(x, w)
            with host_span("engine.readback_wait"):
                y.block_until_ready()
            with host_span("engine.commit"):
                time.sleep(0.004)
        time.sleep(0.002)
    clock_sync()
    jax.profiler.stop_trace()
    return find_xplane(out_dir)


def main(argv: list[str]) -> int:
    if argv and argv[0] == "--record":
        print(record(argv[1]))
        return 0
    att = attribute(argv[0])
    if att is None:
        print("no device operations, or no parallax.visit span",
              file=sys.stderr)
        return 1
    print(json.dumps(att, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
