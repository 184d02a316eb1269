"""% of the device's idle time in the traced span that lies under a
``parallax.*`` host span other than ``runner.idle``
(``harness/host_spans.py``); under 90% a boundary of the hot path has no
span. None on a trace without the program's host spans."""

from benchmarks.harness import host_spans


def reduce(ctx):
    trace = ctx.get("trace")
    att = host_spans.attribute(trace["file"]) if trace else None
    return None if att is None else att["attributed_share"]
