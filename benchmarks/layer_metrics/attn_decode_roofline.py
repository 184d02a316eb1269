"""Share of its roofline the fused decode attention kernel reaches (%),
for any architecture it serves: the least time for the entries the
traced span's decode steps attended (K and V of every KV head a layer,
read once, plus each row-step's query, output row and new K/V; bytes at
the chip's HBM rate or operations at its peak, whichever is larger:
``work.decode_read_work``, ``peaks.json``) over the device time of the
kernel's events in the span. Memory-bound.

The entries are the program's own count where it exports one that fits
what the clients saw, else the clients' context sum
(``work.span_decode_attention``). Another decode kernel extends this
reader by its name pattern and its entry's bytes; it brings no second
roofline metric. None on a trace without the kernel, or where the
entries cannot be told."""

import re

from benchmarks.harness import work

KERNEL = "^gqa_fused_decode_pallas"


def reduce(ctx):
    tr, sw = ctx.get("trace"), ctx.get("span_work")
    if tr is None or sw is None:
        return None
    seconds = sum(s for name, s in tr["op_seconds"].items()
                  if re.search(KERNEL, name))
    attn = work.span_decode_attention(
        ctx["model"], sw, ctx.get("scrape_t0"), ctx.get("scrape_t1"))
    if seconds <= 0 or attn is None:
        return None
    return 100.0 * work.least_seconds(attn, ctx["peaks"]) / seconds
