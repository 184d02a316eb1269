"""Share of its roofline the decode attention kernel reaches (%), for
any architecture: the least time for the entries the traced span's
decode steps attended in the layers that hold pages (an entry read once,
plus each row-step's query, output row and new entry; bytes at the
chip's HBM rate or operations at its peak, whichever is larger:
``work.decode_read_work``, ``peaks.json``) over the device time of the
kernel's events in the span. Memory-bound.

The layers, an entry's bytes and operations and the kernel's name come
from the configuration's work file (``ctx["work"]``, ``work.stage``;
the dense block and ``gqa_fused_decode_pallas`` without one), so another
decode kernel is a line in a new file, never a second roofline metric.
The entries are the program's own count where the work file names a
series and the count fits what the clients saw, else the clients'
context sum (``work.span_decode_attention``). None on a trace without
the kernel - a stage of which no layer holds pages has none - or where
the entries cannot be told."""

import re

from benchmarks.harness import work


def reduce(ctx):
    tr, sw, stage = ctx.get("trace"), ctx.get("span_work"), ctx["work"]
    if tr is None or sw is None or stage["paged_layers"] == 0:
        return None
    seconds = sum(s for name, s in tr["op_seconds"].items()
                  if re.search(stage["kernel"], name))
    attn = work.span_decode_attention(
        stage, sw, ctx.get("scrape_t0"), ctx.get("scrape_t1"))
    if seconds <= 0 or attn is None:
        return None
    return 100.0 * work.least_seconds(attn, ctx["peaks"]) / seconds
