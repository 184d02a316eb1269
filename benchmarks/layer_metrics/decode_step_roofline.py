"""How near the whole decode step is to what the chip allows (%): the
least time for the decode steps the traced span ran, summed layer by
layer from the configuration's work file (``ctx["work"]``,
``work.stage``; the dense block without one) - a step reads what every
layer and the head need whatever the rows are (not the embedding table)
and multiplies it into its rows, reads each held expert its rows hit
once, reads and writes every row's recurrent state, and the steps
together attend the entries of ``attn_decode_roofline`` in the layers
that hold pages - bytes at the chip's HBM rate or operations at its
peak, whichever is larger, over the device time of the K-step
decode-window program's executions in the span. Steps are executions x
K as ``decode_step_device_ms`` counts them; rows a step are the decode
tokens the clients saw in the span over the steps.

The family split of a step cannot be read as a budget (part of the
weight stream runs under the attention kernel); only the whole step
can. None on a trace without the program, or where the entries or the
experts read cannot be told (``work.span_decode_attention``,
``work.span_experts``)."""

import re

from benchmarks.harness import work

STEPS_PER_EXECUTION = 8


def reduce(ctx):
    tr, sw, stage = ctx.get("trace"), ctx.get("span_work"), ctx["work"]
    if tr is None or sw is None:
        return None
    hit = [k for k in tr["module_seconds"] if re.search(stage["program"], k)]
    seconds = sum(tr["module_seconds"][k] for k in hit)
    steps = sum(tr["module_counts"][k] for k in hit) * STEPS_PER_EXECUTION
    scrapes = ctx.get("scrape_t0"), ctx.get("scrape_t1")
    attn = work.span_decode_attention(stage, sw, *scrapes)
    experts = work.span_experts(stage, sw, steps, *scrapes)
    if seconds <= 0 or steps <= 0 or attn is None or experts is None:
        return None
    need = work.least_seconds(
        work.decode_step_work(stage, steps, sw["decode_tokens"], attn,
                              experts), ctx["peaks"])
    return 100.0 * need / seconds
