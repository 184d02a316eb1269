"""From a profiler trace (``.xplane.pb``) to device seconds by name.

Reads the file with ``jax.profiler.ProfileData`` and nothing else. A
device is a plane named ``/device:TPU:<n>``; on it the line ``XLA Ops``
holds one event per executed operation (fusions, custom calls = Pallas
kernels, copies), and ``XLA Modules`` one event per executed program.
Busy time is the union of the ``XLA Ops`` intervals; per-name sums and
counts are averaged over the chips used.

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


def reduce_trace(path: str, device_plane=DEVICE_PLANE,
                 ops_line: str = OPS_LINE,
                 modules_line: str = MODULES_LINE) -> dict | None:
    """The reduction, or None where no device plane holds an operation."""
    from jax.profiler import ProfileData

    file = find_xplane(path)
    if file is None:
        return None
    data = ProfileData.from_file(file)
    chips = 0
    busy = span = 0.0
    op_s: dict[str, float] = {}
    op_n: dict[str, int] = {}
    mod_s: dict[str, float] = {}
    mod_n: dict[str, int] = {}
    gaps: list = []
    for plane in data.planes:
        if not device_plane.match(plane.name):
            continue
        intervals = []
        for line in plane.lines:
            if line.name == ops_line:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    d = ev.duration_ns * 1e-9
                    intervals.append((s, s + d))
                    name = _short(ev.name)
                    op_s[name] = op_s.get(name, 0.0) + d
                    op_n[name] = op_n.get(name, 0) + 1
            elif line.name == modules_line:
                for ev in line.events:
                    name = _short(ev.name)
                    mod_s[name] = mod_s.get(name, 0.0) + ev.duration_ns * 1e-9
                    mod_n[name] = mod_n.get(name, 0) + 1
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
        "file": file, "chips": chips,
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
