"""Share of the held routed experts that a decode step's rows hit (%),
over the window: the growth of ``parallax_moe_experts_read`` (distinct
held experts hit, summed over expert layers and steps) over the decode
steps of the window (the visits dispatched,
``parallax_step_batch_tokens``' count, times the K steps of a decode
window, ``warmup.DECODE_K``: in a decode probe every visit is one) times the experts the
stage holds over all its expert layers (the configuration's work file).
The cell's validity: at 128 rows a held expert is hit with probability
1 - (184/192)^128 = 0.996 a step, so a step's bytes do not follow the
seed's routing. The counter grows at a window's resolve and the visits
at its dispatch: the quotient is a window in ~1,000 out. None on a
program without the series or a stage without an expert layer."""

from benchmarks.harness import warmup

READ = "parallax_moe_experts_read"
VISITS = "parallax_step_batch_tokens_count"
STEPS_PER_VISIT = warmup.DECODE_K


def window_steps(ctx, series):
    """``(growth of series, decode steps)`` over the window, or None."""
    w0, w1 = ctx.get("scrape_w0"), ctx.get("scrape_w1")
    if None in (w0, w1) or series not in w1 or VISITS not in w1:
        return None
    grown = w1[series] - w0.get(series, 0.0)
    steps = (w1[VISITS] - w0.get(VISITS, 0.0)) * STEPS_PER_VISIT
    if grown <= 0 or steps <= 0:
        return None
    return grown, steps


def reduce(ctx):
    stage, got = ctx.get("work"), window_steps(ctx, READ)
    if stage is None or got is None or not stage["experts_held"]:
        return None
    return 100.0 * got[0] / (got[1] * stage["experts_held"])
