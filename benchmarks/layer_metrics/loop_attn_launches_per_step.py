"""Launches of the decode attention kernel per decode step, as the device
counts them: the events of the work file's ``DECODE_KERNEL`` inside one
execution of its ``DECODE_PROGRAM`` (the K-step decode window) over the
window's 8 steps. A looped stack launches the kernel once a pass and
layer, each launch on a cache layer of its own:
``total_ut_steps x num_hidden_layers`` (192 for Ouro-2.6B) says that
every pass ran on the device, which no host counter can.

Counted execution by execution from the trace itself, over those that
lie whole in it (not the first and not the last the trace holds), and
the median taken: the tracer cuts the execution it starts in and the one
it stops in, each still counts as one in the reduction's
``module_counts``, and where the span holds 16 executions of 250 ms
(Ouro's; a dense stage's holds 30-60) the quotient of the two sums read
182 for the device's 192 (PERF.md, PR 46). None on a trace without the
kernel or without three executions of the program."""

import re
import statistics
from bisect import bisect_left

from benchmarks.harness import trace_reduce

STEPS_PER_EXECUTION = 8


def whole_executions(ctx):
    """``[(launches, kernel seconds)]``, one for every execution of the
    decode program that lies whole in the trace; None where there is
    nothing to read."""
    tr, stage = ctx.get("trace"), ctx.get("work")
    if tr is None or stage is None or not tr.get("file"):
        return None
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(tr["file"]).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        runs, kernels = [], []
        for line in plane.lines:
            if line.name == trace_reduce.MODULES_LINE:
                runs = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if re.search(stage["program"],
                                 trace_reduce._short(ev.name)))
            elif line.name == trace_reduce.OPS_LINE:
                kernels = sorted(
                    (ev.start_ns, ev.duration_ns) for ev in line.events
                    if re.search(stage["kernel"],
                                 trace_reduce._short(ev.name)))
        starts = [s for s, _ in kernels]
        for lo, hi in runs[1:-1]:
            inside = kernels[bisect_left(starts, lo):bisect_left(starts, hi)]
            out.append((len(inside), sum(d for _, d in inside) * 1e-9))
    return [e for e in out if e[0]] or None


def reduce(ctx):
    runs = whole_executions(ctx)
    if runs is None:
        return None
    return statistics.median(n for n, _ in runs) / STEPS_PER_EXECUTION
