"""Device milliseconds of decode attention per decode step: the device
time of the work file's ``DECODE_KERNEL`` events inside one execution of
its ``DECODE_PROGRAM`` over the window's 8 steps, i.e. the cache's share
of ``decode_step_device_ms`` beside the weight stream (for a looped
stack: all of a step's launches, one a pass and layer). The median over
the executions that lie whole in the trace, counted as
``loop_attn_launches_per_step`` counts them (and for its reason). A
kernel's seconds are an upper bound on what speeding it up returns, not
a budget (PERF.md: part of the weight stream runs under it). None on a
trace without the kernel or without three executions of the program."""

import os
import statistics

from benchmarks.harness import spec

launches = spec.import_file("layer_metric_", os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "loop_attn_launches_per_step.py"))


def reduce(ctx):
    runs = launches.whole_executions(ctx)
    if runs is None:
        return None
    return (statistics.median(s for _, s in runs) * 1e3
            / launches.STEPS_PER_EXECUTION)
