"""Device idle ms a visit: the device's idle seconds inside the traced
span over the ``parallax.visit`` steps it held (``harness/host_spans.py``).
None on a trace without the program's host spans."""

from benchmarks.harness import host_spans


def reduce(ctx):
    trace = ctx.get("trace")
    att = host_spans.attribute(trace["file"]) if trace else None
    return None if att is None else att["idle_ms_per_visit"]
