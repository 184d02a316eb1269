"""Cache entries a decode step reads per token it produces, over the
window: ``parallax_eva_entries_attended`` (visible chunk summaries plus
open-window tokens, per step and row) over the tokens of the visits
dispatched (``parallax_step_batch_tokens``' sum; in a decode probe every
visit is a decode window). What the model step reads in the context's
place (~1.6k against ~10k). None on a program without the series."""

ENTRIES = "parallax_eva_entries_attended"
TOKENS = "parallax_step_batch_tokens_sum"


def reduce(ctx):
    w0, w1 = ctx.get("scrape_w0"), ctx.get("scrape_w1")
    if None in (w0, w1) or ENTRIES not in w1 or TOKENS not in w1:
        return None
    tokens = w1[TOKENS] - w0.get(TOKENS, 0.0)
    entries = w1[ENTRIES] - w0.get(ENTRIES, 0.0)
    if tokens <= 0 or entries <= 0:
        return None
    return entries / tokens
