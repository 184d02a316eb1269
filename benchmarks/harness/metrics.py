"""Arithmetic from timestamps, scrapes and the reduced trace to metrics.

TTFT and TPOT: the clock starts when a request was *due*, not when it
was sent, and tokens are the tokens the stream delivered.
"""

from __future__ import annotations

import re

from benchmarks.harness import spec, work


def percentile(values: list[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tokens_in_window(chunks: list, w0: float, w1: float) -> float:
    """Output tokens of one stream produced inside ``[w0, w1)``.

    A K-step decode window delivers its tokens together (8 a row, the
    whole batch at once), so a plain count of timestamps moves in steps
    of one window - 1.1% of a 15 s count at 8 rows, more than the spread
    of everything else. The ``n`` tokens of a chunk were produced between
    the stream's previous chunk and this one, so they are credited to
    that interval and the part of it inside the window counts. A
    stream's first chunk has no earlier one and counts at its timestamp.
    Over a whole stream the credits add up to the tokens delivered."""
    total, prev = 0.0, None
    for t, n in chunks:
        if prev is None or t <= prev:
            total += n if w0 <= t < w1 else 0.0
        else:
            overlap = min(t, w1) - max(prev, w0)
            if overlap > 0:
                total += n * overlap / (t - prev)
        prev = t
    return total


def live_in_window(results: list, w0: float, w1: float) -> list:
    """The requests the window had to serve: sent before it closed and
    not finished before it opened. A decode probe's rows are all of this
    kind and none is *due* inside the window."""
    out = []
    for r in results:
        if not r.sent_t or r.sent_t >= w1:
            continue
        ended = r.last_t if r.usage is not None else None
        if ended is None or ended >= w0:
            out.append(r)
    return out


def live_context_tokens(results: list, t: float) -> int:
    """Tokens of context (prompt plus what has been delivered) held at
    client time ``t`` by the requests not yet finished."""
    return sum(
        len(r.req.prompt) + sum(n for ct, n in r.chunks if ct <= t)
        for r in results
        if r.sent_t and r.sent_t <= t
        and not (r.usage is not None and r.last_t and r.last_t <= t))


def end_to_end(results: list, w0: float, w1: float, chips: int) -> dict:
    """The client-side numbers of one window. ``results`` are
    ``loadgen.Result``. ``attempted``/``failed`` count the requests live
    in ``[w0, w1)``; latencies (TTFT, TPOT) are taken over the *judged*
    requests only, those due inside the window, so a cell in which none
    is due there reports no latency."""
    live = live_in_window(results, w0, w1)
    live_ok = [r for r in live if r.ok]
    judged_ok = [r for r in results if r.req.judged and r.ok]
    ttft = [(r.first_t - r.due_t) * 1e3 for r in judged_ok]
    tpot = [(r.last_t - r.first_t) * 1e3 / (r.n_tokens - 1)
            for r in judged_ok if r.n_tokens > 1]
    in_window = sum(tokens_in_window(r.chunks, w0, w1) for r in results)
    lag = [(r.sent_t - r.due_t) * 1e3 for r in live]
    prompt = sum(len(r.req.prompt) for r in live_ok)
    cached = sum(
        (r.usage.get("prompt_tokens_details") or {}).get("cached_tokens", 0)
        for r in live_ok
    )
    out = {
        "attempted": len(live),
        "failed": len(live) - len(live_ok),
        "judged": len(judged_ok),
        "out_tok_s": in_window / (w1 - w0) / chips,
        "gen_lag_p95_ms": percentile(lag, 95) if lag else None,
        "prefix_hit_share": 100.0 * cached / prompt if prompt else None,
    }
    if ttft:
        out["ttft_p50_ms"] = percentile(ttft, 50)
        out["ttft_p95_ms"] = percentile(ttft, 95)
    if tpot:
        out["tpot_p95_ms"] = percentile(tpot, 95)
        out["tpot_p50_ms"] = percentile(tpot, 50)
    return out


# --------------------------------------------------------------------------
# /metrics (Prometheus text) scrapes.
# --------------------------------------------------------------------------

_LINE = re.compile(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{[^}]*\})?\s+(\S+)$")


def parse_prometheus(text: str) -> dict:
    """``{series name: sum over its label sets}`` (histogram ``_bucket``
    lines are left out; ``_sum`` and ``_count`` are kept)."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m or m.group(1).endswith("_bucket"):
            continue
        try:
            out[m.group(1)] = out.get(m.group(1), 0.0) + float(m.group(3))
        except ValueError:
            pass
    return out


def series_delta(a: dict, b: dict, series: str, reduce: str):
    """A counter's growth, a histogram's mean between two scrapes, or a
    gauge as the later scrape read it."""
    if reduce == "last":
        return b.get(series)
    if reduce == "delta":
        if series not in b:
            return None
        return b[series] - a.get(series, 0.0)
    if reduce == "delta_sum_over_delta_count":
        n = b.get(series + "_count", 0.0) - a.get(series + "_count", 0.0)
        if n <= 0:
            return None
        return (b.get(series + "_sum", 0.0) - a.get(series + "_sum", 0.0)) / n
    raise ValueError(f"unknown reduction {reduce!r}")


# --------------------------------------------------------------------------
# Per-layer metrics: one reader per metric, found by name.
# --------------------------------------------------------------------------


def read_layer_metric(reader: dict, ctx: dict):
    """The metric's value, or None where there is nothing to read.

    ``ctx`` holds: ``scrape_w0``/``scrape_w1`` (parsed /metrics at the
    window's ends), ``scrape_t0``/``scrape_t1`` (at the traced span's
    ends), ``client`` (``end_to_end``'s dict), ``trace`` (the reduced
    trace, or None), ``span_work`` (what the traced span computed, from
    ``work.span_work``), ``model`` (the config.json run), ``work`` (the
    stage layer by layer as its work is counted, ``work.stage`` of the
    configuration's work file), ``peaks``."""
    if "py" in reader:
        return spec.import_file("layer_metric_", reader["py"]).reduce(ctx)
    src = reader["source"]
    kind = src["kind"]
    if kind == "metrics_series":
        span = src.get("span", "window")
        a, b = (("scrape_w0", "scrape_w1") if span == "window"
                else ("scrape_t0", "scrape_t1"))
        if ctx.get(a) is None or ctx.get(b) is None:
            return None
        v = series_delta(ctx[a], ctx[b], src["series"], src["reduce"])
        return None if v is None else v * src.get("scale", 1)
    if kind == "client_field":
        return ctx["client"].get(src["field"])
    if kind == "status_delta":
        a, b = ctx.get("status_w0"), ctx.get("status_w1")
        if a is None or b is None:
            return None

        def at(d, path):
            for key in path.split("."):
                d = d[key]
            return d

        return sum(at(b, p) - at(a, p) for p in src["paths"])
    if kind == "trace_module":
        tr, sw = ctx.get("trace"), ctx.get("span_work")
        if tr is None or sw is None:
            return None
        hit = [k for k in tr["module_seconds"] if re.search(src["pattern"], k)]
        seconds = sum(tr["module_seconds"][k] for k in hit)
        runs = sum(tr["module_counts"][k] for k in hit)
        if seconds <= 0:
            return None
        if src["reduce"] == "ms_per_step":
            return seconds * 1e3 / (runs * src["steps_per_execution"])
        units = sw.get(src["unit_of_work"], 0)
        return seconds * 1e3 / units if units > 0 else None
    if kind == "trace":
        tr, sw = ctx.get("trace"), ctx.get("span_work")
        if tr is None or sw is None:
            return None
        seconds = sum(
            s for name, s in tr["op_seconds"].items()
            if re.search(src["pattern"], name)
        )
        if seconds <= 0:
            return None
        if src["reduce"] == "ms_per_unit":
            units = sw.get(src["unit_of_work"], 0)
            return seconds * 1e3 / units if units > 0 else None
        if src["reduce"] == "roofline_share":
            need = work.least_seconds(sw[src["work"]], ctx["peaks"])
            return 100.0 * need / seconds
        raise ValueError(f"unknown trace reduction {src['reduce']!r}")
    raise ValueError(f"unknown source kind {kind!r}")
