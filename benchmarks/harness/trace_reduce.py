"""From a profiler trace (``.xplane.pb``) to device seconds by name.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A
device is a plane named ``/device:TPU:<n>``; on it the line ``XLA Ops``
holds one event per executed operation (fusions, custom calls = Pallas
kernels, copies), and ``XLA Modules`` one event per executed program.
Busy time is the union of the ``XLA Ops`` intervals; per-name sums and
counts are averaged over the chips used. Given a window on the program's
clock (``perf_counter_ns``, which the program's ``parallax.clock_sync``
marks lay on the trace's), every device event is cut to it: what lies
outside counts for nothing, so busy time cannot pass the window.

``python -m benchmarks.harness.trace_reduce <file-or-dir>`` prints the
reduction (used once, by hand, to choose the patterns in
``benchmarks/layer_metrics/``); ``--record <dir>`` records a small trace
of a toy program on whatever device JAX has (the test fixture was made
so, on the chip).
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CONTAINERS = ("while", "conditional", "call")
CLOCK_SYNC = "parallax.clock_sync"


def find_xplane(path: str) -> str | None:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: list[tuple[float, float]], top: int = 10):
    """The longest gaps between merged busy intervals: (start, seconds)."""
    gaps, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            gaps.append((cur_e, s - cur_e))
        cur_e = e if cur_e is None else max(cur_e, e)
    return sorted(gaps, key=lambda g: -g[1])[:top]


def _short(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion.123``; keeps kernel names."""
    name = name.split(" = ")[0].strip()
    return name[1:] if name.startswith("%") else name


def family(name: str) -> str:
    """An operation's family: its name without the trailing counter, so
    that the same fusion in 24 unrolled layers sums under one name."""
    return re.sub(r"[._]\d+$", "", re.sub(r"\.\d+(\.clone)?(\.\d+)?$", "", name))


def program_clock_offset_ns(data, device_plane=DEVICE_PLANE) -> int | None:
    """Trace clock minus the program's ``perf_counter_ns``, from the
    ``parallax.clock_sync`` marks the program leaves in a host plane
    (each carries its reading; a mark's timestamp can only lag it, so
    the smallest difference is the nearest). None without a mark."""
    offset = None
    for plane in data.planes:
        if device_plane.match(plane.name):
            continue     # the device's events are most of the file
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CLOCK_SYNC:
                    off = int(ev.start_ns) - int(
                        dict(ev.stats)["perf_counter_ns"])
                    if offset is None or off < offset:
                        offset = off
    return offset


def reduce_trace(path: str, device_plane=DEVICE_PLANE,
                 ops_line: str = OPS_LINE,
                 modules_line: str = MODULES_LINE,
                 window_ns: tuple[int, int] | None = None) -> dict | None:
    """The reduction, or None where no device plane holds an operation.
    ``window_ns``: two readings of the program's ``perf_counter_ns``;
    every event is cut to the span between them (an event cut to a part
    counts as that part of an execution). A trace without the program's
    clock marks cannot be cut and is reduced whole (``clipped`` False)."""
    from jax.profiler import ProfileData

    file = find_xplane(path)
    if file is None:
        return None
    data = ProfileData.from_file(file)
    lo, hi = float("-inf"), float("inf")
    offset = (program_clock_offset_ns(data, device_plane) if window_ns
              else None)
    if offset is not None:
        lo, hi = (t + offset for t in window_ns)

    def cut(ev):
        """The event's ``(start, seconds, share)`` inside the window."""
        s, d = ev.start_ns, ev.duration_ns
        a, b = max(s, lo), min(s + d, hi)
        if b < a or (b == a and d > 0):
            return None
        return a * 1e-9, (b - a) * 1e-9, ((b - a) / d if d > 0 else 1.0)

    chips = 0
    busy = span = 0.0
    op_s: dict[str, float] = {}
    op_n: dict[str, float] = {}
    mod_s: dict[str, float] = {}
    mod_n: dict[str, float] = {}
    gaps: list = []
    for plane in data.planes:
        if not device_plane.match(plane.name):
            continue
        intervals = []
        for line in plane.lines:
            if line.name not in (ops_line, modules_line):
                continue
            sums, counts = ((op_s, op_n) if line.name == ops_line
                            else (mod_s, mod_n))
            for ev in line.events:
                part = cut(ev)
                if part is None:
                    continue
                s, d, share = part
                if line.name == ops_line:
                    intervals.append((s, s + d))
                name = _short(ev.name)
                sums[name] = sums.get(name, 0.0) + d
                counts[name] = counts.get(name, 0) + share
        if not intervals:
            continue
        chips += 1
        busy += union_seconds(intervals)
        span += max(e for _, e in intervals) - min(s for s, _ in intervals)
        gaps.extend(idle_gaps(intervals))
    if chips == 0:
        return None
    fam_s: dict[str, float] = {}
    for name, s in op_s.items():
        fam_s[family(name)] = fam_s.get(family(name), 0.0) + s

    def avg(d):
        return {k: v / chips for k, v in d.items()}

    return {
        "file": file, "chips": chips, "clipped": offset is not None,
        "busy_s": busy / chips, "span_s": span / chips,
        "op_seconds": avg(op_s), "op_counts": avg(op_n),
        "module_seconds": avg(mod_s), "module_counts": avg(mod_n),
        "family_seconds": avg(fam_s),
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time (by family) and the longest idle gaps. The package has no named
    scopes or host spans on the trace's clock yet, so a gap is named by
    its position only."""
    # ``while`` is the K-step scan's container: its body's operations are
    # on the same line and would be counted twice.
    ops = sorted(((k, v) for k, v in red["family_seconds"].items()
                  if k not in CONTAINERS), key=lambda kv: -kv[1])[:top]
    t_min = min((g[0] for g in red["idle_gaps"]), default=0.0)
    return {
        "device_ops": [[k, v] for k, v in ops],
        "idle_gaps": [[f"gap_at_{g[0] - t_min:.3f}s_unattributed", g[1]]
                      for g in red["idle_gaps"][:top]],
    }


def record(out_dir: str) -> str:
    """A small trace of a toy program, for the test fixture."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def toy(x, w):
        for _ in range(3):
            x = jnp.tanh(x @ w)
        return x.sum()

    x = jnp.ones((256, 256), jnp.bfloat16)
    w = jnp.ones((256, 256), jnp.bfloat16) * 0.01
    toy(x, w).block_until_ready()
    jax.profiler.start_trace(out_dir)
    for _ in range(4):
        toy(x, w).block_until_ready()
    jax.profiler.stop_trace()
    return find_xplane(out_dir)


def main(argv: list[str]) -> int:
    if argv and argv[0] == "--record":
        print(record(argv[1]))
        return 0
    if argv and argv[0] == "--lines":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(find_xplane(argv[1]))
        for plane in data.planes:
            for line in plane.lines:
                events = list(line.events)
                print(json.dumps([plane.name, line.name, len(events),
                                  [e.name[:80] for e in events[:4]]]))
        return 0
    red = reduce_trace(argv[0])
    if red is None:
        print("no device plane with operations", file=sys.stderr)
        return 1
    top = lambda d, n=40: sorted(d.items(), key=lambda kv: -kv[1])[:n]
    print(json.dumps({
        "chips": red["chips"], "busy_s": red["busy_s"],
        "span_s": red["span_s"],
        "modules": [[k, v, red["module_counts"][k]]
                    for k, v in top(red["module_seconds"])],
        "families": top(red["family_seconds"], 60),
        "ops": [[k, v, red["op_counts"][k]] for k, v in top(red["op_seconds"], 60)],
        "idle_gaps": red["idle_gaps"],
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
