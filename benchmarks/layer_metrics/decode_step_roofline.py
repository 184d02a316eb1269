"""How near the whole decode step is to what the chip allows (%): the
least time for the decode steps the traced span ran - a step reads the
weights it must (every layer, the final norm, one head matrix; not the
embedding table: ``work.decode_step_weight_elements``) and multiplies
them into its rows, and the steps together attend the entries of
``attn_decode_roofline`` - bytes at the chip's HBM rate or operations
at its peak, whichever is larger, over the device time of the K-step
decode-window program's executions in the span. Steps are executions x
K as ``decode_step_device_ms`` counts them; rows a step are the decode
tokens the clients saw in the span over the steps.

The family split of a step cannot be read as a budget (part of the
weight stream runs under the attention kernel); only the whole step
can. None on a trace without the program, or where the entries cannot
be told."""

import re

from benchmarks.harness import work

PROGRAM = r"^jit_fn\("
STEPS_PER_EXECUTION = 8


def reduce(ctx):
    tr, sw = ctx.get("trace"), ctx.get("span_work")
    if tr is None or sw is None:
        return None
    hit = [k for k in tr["module_seconds"] if re.search(PROGRAM, k)]
    seconds = sum(tr["module_seconds"][k] for k in hit)
    steps = sum(tr["module_counts"][k] for k in hit) * STEPS_PER_EXECUTION
    attn = work.span_decode_attention(
        ctx["model"], sw, ctx.get("scrape_t0"), ctx.get("scrape_t1"))
    if seconds <= 0 or steps <= 0 or attn is None:
        return None
    need = work.least_seconds(
        work.decode_step_work(ctx["model"], steps, sw["decode_tokens"], attn),
        ctx["peaks"])
    return 100.0 * need / seconds
