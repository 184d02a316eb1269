"""Device microseconds per EVA chunk summary: the device time of the
summary-write kernel's events in the traced span (``eva_summary``: one
launch per layer and step, dead lanes included) over the chunks whose
summaries the span's steps wrote (``parallax_eva_chunks_summarized``
between the span's two scrapes, counted once, not per layer). None on a
program without the series or the kernel."""

import re

SERIES = "parallax_eva_chunks_summarized"
KERNEL = "^eva_summary"


def reduce(ctx):
    tr = ctx.get("trace")
    t0, t1 = ctx.get("scrape_t0"), ctx.get("scrape_t1")
    if None in (tr, t0, t1) or SERIES not in t1:
        return None
    chunks = t1[SERIES] - t0.get(SERIES, 0.0)
    seconds = sum(s for name, s in tr["op_seconds"].items()
                  if re.search(KERNEL, name))
    if chunks <= 0 or seconds <= 0:
        return None
    return seconds * 1e6 / chunks
