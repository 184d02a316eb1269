"""Share of its roofline the decode attention kernel reaches on EVA's
reads (%): the least time for the entries the traced span's decode steps
attended (``parallax_eva_entries_attended`` between the span's two
scrapes x bytes per entry x layers at the chip's HBM rate, or their
FLOPs at its peak, whichever is larger; ``benchmarks/eva_work.py``) over
the device time of the fused decode kernel's events in the span.

The count is the program's, so it is held against what the clients saw:
a decode token at context ``c`` attends between ``c / chunk_size``
entries (all summaries) and ``c`` (no summary), so the count must lie
between ``decode_context_sum / chunk_size`` and ``decode_context_sum``
(``ctx["span_work"]``). Outside that, or on a program without the
series or the kernel, there is nothing to read: None."""

import re

from benchmarks import eva_work
from benchmarks.harness import work

SERIES = "parallax_eva_entries_attended"
KERNEL = "^gqa_fused_decode_pallas"


def reduce(ctx):
    tr, sw = ctx.get("trace"), ctx.get("span_work")
    t0, t1 = ctx.get("scrape_t0"), ctx.get("scrape_t1")
    model = ctx.get("model") or {}
    if None in (tr, sw, t0, t1) or SERIES not in t1 or "chunk_size" not in model:
        return None
    entries = t1[SERIES] - t0.get(SERIES, 0.0)
    seen = sw["decode_context_sum"]
    if not (0 < seen / model["chunk_size"] <= entries <= seen):
        return None
    seconds = sum(s for name, s in tr["op_seconds"].items()
                  if re.search(KERNEL, name))
    if seconds <= 0:
        return None
    need = work.least_seconds(
        eva_work.decode_read_work(model, entries, sw["decode_tokens"]),
        ctx["peaks"])
    return 100.0 * need / seconds
