"""Device microseconds of the routed experts' grouped matmuls per expert
layer and decode step: the device time of the ``gmm`` kernel's events
(three a layer: gate, up, down) inside the executions of the work file's
``DECODE_PROGRAM`` (the K-step decode window) that lie whole in the
trace, over those executions' steps times the stage's expert layers
(``experts_held`` > 0 in the configuration's work file).
``works/axk1.py`` ``expert_layer_work`` has a layer's bytes and
operations; its share of its own roofline is reckoned by hand (PERF.md
section 5). None on a trace without the kernel or without three
executions of the program, or a stage without an expert layer.

``per_layer_step_us`` is shared with ``moe_dispatch_us_per_layer_step``
and ``mla_append_us_per_layer_step``: those operations are XLA fusions
with numbered names, so they are told by the shape of what they write,
which a device event's name (the instruction's text) begins with."""

import re
from bisect import bisect_left

from benchmarks.harness import trace_reduce, warmup

# The steps of one execution of the decode program: the harness's K.
STEPS_PER_EXECUTION = warmup.DECODE_K
GMM = "^gmm"
RESULT = re.compile(r"= \(?(\w+)\[([\d,]*)\]")


def result_of(text: str):
    """``(dtype, dims)`` of what an instruction's text says it writes
    (a tuple's first member), or None."""
    m = RESULT.search(text)
    if m is None:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)


def decode_ops(ctx):
    """``(executions, [(short name, text, seconds)])``: the decode
    program's executions that lie whole in the trace, and the device
    operations inside them. Read once a run (kept in ``ctx``)."""
    if "_decode_ops" in ctx:
        return ctx["_decode_ops"]
    tr, stage = ctx.get("trace"), ctx.get("work")
    got = None
    if tr is not None and stage is not None and tr.get("file"):
        from jax.profiler import ProfileData

        executions, ops = 0, []
        for plane in ProfileData.from_file(tr["file"]).planes:
            if not trace_reduce.DEVICE_PLANE.match(plane.name):
                continue
            runs, events = [], []
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    runs = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                        if re.search(stage["program"],
                                     trace_reduce._short(ev.name)))
                elif line.name == trace_reduce.OPS_LINE:
                    events = sorted(
                        ((ev.start_ns, ev.duration_ns, ev.name)
                         for ev in line.events), key=lambda e: e[0])
            starts = [e[0] for e in events]
            for lo, hi in runs[1:-1]:
                executions += 1
                for _, d, text in events[bisect_left(starts, lo):
                                         bisect_left(starts, hi)]:
                    name = trace_reduce._short(text)
                    if trace_reduce.family(name) not in trace_reduce.CONTAINERS:
                        ops.append((name, text, d * 1e-9))
        if executions and ops:
            got = (executions, ops)
    ctx["_decode_ops"] = got
    return got


def per_layer_step_us(ctx, layers: int, match):
    """Device microseconds a layer and decode step of the operations
    ``match(name, text)`` accepts; None where nothing is read."""
    got = decode_ops(ctx)
    if got is None or layers <= 0:
        return None
    executions, ops = got
    seconds = sum(s for name, text, s in ops if match(name, text))
    if seconds <= 0:
        return None
    return seconds * 1e6 / (executions * STEPS_PER_EXECUTION * layers)


def reduce(ctx):
    stage = ctx.get("work")
    if stage is None:
        return None
    return per_layer_step_us(ctx, stage["expert_layers"],
                             lambda name, text: re.search(GMM, name))
