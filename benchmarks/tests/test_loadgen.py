"""The generator's steadiness rule, its due-time clock, the percentiles."""

import asyncio

import numpy as np
import pytest

from benchmarks.harness import loadgen, metrics, spec


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    xs = rng.exponential(1.0, 137).tolist()
    for p in (50, 95, 99):
        assert metrics.percentile(xs, p) == pytest.approx(np.percentile(xs, p))
    assert metrics.percentile([3.0], 95) == 3.0


OPEN = {
    "loop": "open", "arrival": {"process": "poisson", "rate_rps": 12.0},
    "warm_seconds": 5.0,
    "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 16, "max": 2048},
    "output_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.7, "min": 16, "max": 512},
    "sampling": {"temperature": 0.7, "top_k": 20, "seed": "per_request"},
}


def test_every_seed_gets_the_same_sizes_in_another_order():
    t = OPEN
    a = loadgen.Traffic(t, 1000, 1, 20.0)
    b = loadgen.Traffic(t, 1000, 3_000_000_007, 20.0)
    n = round(t["arrival"]["rate_rps"] * 20.0)
    win_a = [r for r in a.schedule if r.judged]
    win_b = [r for r in b.schedule if r.judged]
    assert len(win_a) == len(win_b) == n
    assert sorted(len(r.prompt) for r in win_a) == sorted(len(r.prompt) for r in win_b)
    assert sorted(r.max_tokens for r in win_a) == sorted(r.max_tokens for r in win_b)
    assert [len(r.prompt) for r in win_a] != [len(r.prompt) for r in win_b]
    gaps_a = np.diff([r.due for r in win_a])
    gaps_b = np.diff([r.due for r in win_b])
    assert win_a[0].due == pytest.approx(a.warm) and win_a[-1].due < a.warm + 20.0
    # The same gaps but the one that follows the last arrival.
    assert abs(gaps_a.sum() - gaps_b.sum()) < 20.0 / n * 10
    assert win_a[0].prompt != win_b[0].prompt


def test_gaps_offer_exactly_the_rate():
    for arrival in ({"process": "poisson", "rate_rps": 7.0},
                    {"process": "gamma", "rate_rps": 7.0, "burstiness": 0.25}):
        g = loadgen.arrival_gaps(arrival, 140, np.random.default_rng(1))
        assert g.sum() == pytest.approx(20.0) and (g > 0).all()


@pytest.mark.parametrize("name, prompts, outputs, longest", [
    ("decode-probe8", (64, 256), (3072, 3328), 8192),
    ("decode-probe8-5k", (64, 256), (7424, 7936), 8192),
    ("decode-probe8-8k", (6144, 10240), (5632, 6144), 16384)])
def test_closed_loop_sends_a_blocker_and_the_same_sizes_for_every_seed(
        name, prompts, outputs, longest):
    t = spec.load_traffic(name)
    tr = loadgen.Traffic(t, 1000, 5, 30.0)
    assert tr.blocker_tokens == 1024
    first = [tr.closed_next(0.02) for _ in range(tr.clients)]
    assert all(prompts[0] <= len(r.prompt) <= prompts[1]
               and outputs[0] <= r.max_tokens <= outputs[1] for r in first)
    # A row fits its configuration's --max-model-len whole (serve's
    # default 8,192; EvaByte's serve_flags say 16,384), the rows a
    # finished client would send next too.
    assert all(p + o <= longest for p, o, _ in tr._pool)
    # Latencies are judged only for requests due inside the window.
    assert not any(r.judged for r in first)
    assert tr.closed_next(tr.warm + 10.5).judged
    assert not tr.closed_next(tr.warm + 30.5).judged
    # Every seed's first requests are the same set of sizes.
    other = loadgen.Traffic(t, 1000, 3_000_000_007, 30.0)
    first_o = [other.closed_next(0.02) for _ in range(other.clients)]
    assert sorted((len(r.prompt), r.max_tokens) for r in first) == sorted(
        (len(r.prompt), r.max_tokens) for r in first_o)
    assert [len(r.prompt) for r in first] != [len(r.prompt) for r in first_o]


def test_ttft_runs_from_the_due_time_not_the_send():
    req = loadgen.Req(due=0.0, prompt=[1, 2, 3], max_tokens=3, seed=0, judged=True)
    r = loadgen.Result(req=req, due_t=10.0, sent_t=10.4, first_t=10.5, last_t=10.9)
    r.chunks, r.n_tokens = [(10.5, 1), (10.9, 2)], 3
    r.usage = {"completion_tokens": 3, "prompt_tokens_details": {"cached_tokens": 0}}
    out = metrics.end_to_end([r], 10.0, 11.0, 1)
    assert out["ttft_p50_ms"] == pytest.approx(500.0)      # not 100
    assert out["gen_lag_p95_ms"] == pytest.approx(400.0)
    assert out["tpot_p95_ms"] == pytest.approx(200.0)       # 400 ms / 2 gaps
    assert out["out_tok_s"] == pytest.approx(3.0)   # 1 at 10.5, 2 over (10.5, 10.9]
    assert (out["attempted"], out["failed"]) == (1, 0)
    r.n_tokens = 2          # a short answer is a failure
    assert metrics.end_to_end([r], 10.0, 11.0, 1)["failed"] == 1


def test_attempted_counts_the_requests_live_in_the_window():
    def result(sent, first, last, judged, done=True):
        req = loadgen.Req(due=0.0, prompt=[1], max_tokens=2, seed=0, judged=judged)
        r = loadgen.Result(req=req, due_t=sent, sent_t=sent, first_t=first, last_t=last)
        r.chunks, r.n_tokens = [(first, 1), (last, 1)], 2
        r.usage = {"completion_tokens": 2} if done else None
        return r

    before = result(1.0, 1.5, 9.0, False)        # over before the window
    across = result(2.0, 2.5, 15.0, False)       # a probe's row: due before, live in
    inside = result(12.0, 12.5, 13.0, True)
    after = result(21.0, 21.5, 22.0, False)      # sent after it closed
    out = metrics.end_to_end([before, across, inside, after], 10.0, 20.0, 1)
    assert (out["attempted"], out["failed"], out["judged"]) == (2, 0, 1)
    assert out["ttft_p95_ms"] == pytest.approx(500.0)      # of `inside` alone
    # Without a request due inside the window there is no latency to report.
    out = metrics.end_to_end([before, across], 10.0, 20.0, 1)
    assert out["attempted"] == 1 and "ttft_p95_ms" not in out
    hung = result(3.0, 3.5, 4.0, False, done=False)
    assert metrics.end_to_end([hung], 10.0, 20.0, 1)["failed"] == 1


def test_tokens_are_credited_to_the_interval_that_produced_them():
    chunks = [(1.0, 1), (2.0, 8), (3.0, 8), (4.0, 8)]
    # Whole stream inside: every token counts.
    assert metrics.tokens_in_window(chunks, 0.0, 5.0) == 25
    # The window ends half-way between two chunks: half of the later one.
    assert metrics.tokens_in_window(chunks, 0.0, 2.5) == pytest.approx(1 + 8 + 4)
    # It starts a quarter of the way into an interval: three quarters of it.
    assert metrics.tokens_in_window(chunks, 2.25, 5.0) == pytest.approx(6 + 8)
    # The first chunk counts at its timestamp.
    assert metrics.tokens_in_window(chunks, 1.5, 1.9) == pytest.approx(8 * 0.4)
    assert metrics.tokens_in_window(chunks[:1], 1.5, 3.0) == 0
    # Two adjoining windows share out a stream without loss.
    assert (metrics.tokens_in_window(chunks, 0.0, 2.6)
            + metrics.tokens_in_window(chunks, 2.6, 9.0)) == pytest.approx(25)


def test_prometheus_parse_and_deltas():
    a = metrics.parse_prometheus(
        'x_sum{stage="0"} 10\nx_count{stage="0"} 4\nx_bucket{le="1"} 3\n'
        'c_total{stage="0"} 1\nc_total{stage="1"} 2\n# HELP x\n')
    b = dict(a, x_sum=40.0, x_count=10.0, c_total=7.0)
    assert a["c_total"] == 3 and "x_bucket" not in a
    assert metrics.series_delta(a, b, "x", "delta_sum_over_delta_count") == 5.0
    assert metrics.series_delta(a, b, "c_total", "delta") == 4.0
    assert metrics.series_delta(a, a, "x", "delta_sum_over_delta_count") is None
    assert metrics.series_delta(a, dict(a, g=0.25), "g", "last") == 0.25
    assert metrics.series_delta(a, a, "g", "last") is None


def test_count_tokens():
    assert loadgen.count_tokens(" t318 t296 t404") == 3
    assert loadgen.count_tokens("") == 0


def test_live_context_counts_prompt_and_delivered_tokens_of_unfinished_requests():
    req = loadgen.Req(due=0.0, prompt=[1] * 10, max_tokens=4, seed=0)
    r = loadgen.Result(req=req, sent_t=1.0, first_t=1.5, last_t=3.0)
    r.chunks, r.usage = [(1.5, 1), (2.0, 2), (3.0, 1)], {"completion_tokens": 4}
    assert metrics.live_context_tokens([r], 0.5) == 0       # not sent yet
    assert metrics.live_context_tokens([r], 2.5) == 10 + 3
    assert metrics.live_context_tokens([r], 3.5) == 0       # finished


# -- a probe's ramp is whole, or the run says so at the window's opening ---

PROBE = {
    "loop": "closed", "clients": 3, "warm_seconds": 0.3,
    "ramp_blocker_tokens": 16, "ramp_whole": True,
    "prompt_tokens": {"dist": "uniform", "min": 4, "max": 8},
    "output_tokens": {"dist": "fixed", "value": 400},
    "sampling": {"temperature": 0.7, "seed": "per_request"},
}


@pytest.mark.parametrize("name", ["decode-probe8", "decode-probe8-5k",
                                  "decode-probe8-8k"])
def test_a_probe_asks_for_a_whole_ramp_and_a_rehearsal_does_not(name):
    t = spec.load_traffic(name)
    assert loadgen.Traffic(t, 1000, 5, 30.0).ramp_whole
    assert not loadgen.Traffic(t, 1000, 5, 4.0, rehearse=True).ramp_whole
    # The key draws nothing anew: every size is what it was without it.
    bare = {k: v for k, v in t.items() if k != "ramp_whole"}
    a, b = (loadgen.Traffic(x, 1000, 3_000_000_007, 30.0) for x in (t, bare))
    assert a._pool == b._pool and not b.ramp_whole
    assert not loadgen.Traffic(OPEN, 1000, 5, 20.0).ramp_whole


def _row(sent, first=None, done=False, error=None):
    r = loadgen.Result(req=loadgen.Req(0.0, [1], 400, 1), sent_t=sent,
                       first_t=first, error=error)
    r.usage = {"completion_tokens": 400} if done else None
    return r


@pytest.mark.parametrize("rows, whole", [
    # Every client's row streams, the blocker has ended.
    ([_row(1.0, 1.2, done=True)] + [_row(1.02, 1.3 + i / 100) for i in range(3)],
     True),
    # One row is still waiting for its first token (admitted late).
    ([_row(1.02, 1.3), _row(1.02, 1.3), _row(1.02)], False),
    # One client has not sent at all.
    ([_row(1.02, 1.3), _row(1.02, 1.3), _row(0.0)], False),
    # The blocker itself is still in flight.
    ([_row(1.0)] + [_row(1.02, 1.3) for _ in range(3)], False),
    # A row failed.
    ([_row(1.02, 1.3), _row(1.02, 1.3), _row(1.02, error="HTTP 500")], False),
])
def test_the_ramp_is_whole_when_every_clients_row_streams(rows, whole):
    run = loadgen.Run(loadgen.Traffic(PROBE, 1000, 5, 1.0), "http://x")
    assert run.ramp_whole
    run.results = rows
    if whole:
        run.check_ramp()
        assert run.ramp["streaming"] == run.ramp["in_flight"] == 3
        assert run.ramp["first_token_after_send_s"] == [0.28, 0.29, 0.3]
    else:
        with pytest.raises(loadgen.RampSplit) as e:
            run.check_ramp()
        assert e.value.detail["clients"] == 3
        assert e.value.detail["streaming"] < 3 or e.value.detail["in_flight"] > 3


async def _fake_serve(tokens_every_s):
    """A server that streams ``max_tokens`` one-token chunks."""
    import json

    from aiohttp import web

    async def completions(request):
        body = await request.json()
        resp = web.StreamResponse()
        await resp.prepare(request)
        n = body["max_tokens"]
        for i in range(n):
            chunk = {"choices": [{"text": f" t{i}", "finish_reason": None}]}
            if i == n - 1:
                chunk["usage"] = {"completion_tokens": n}
            await resp.write(b"data: " + json.dumps(chunk).encode() + b"\n\n")
            await asyncio.sleep(tokens_every_s)
        await resp.write(b"data: [DONE]\n\n")
        return resp

    app = web.Application()
    app.router.add_post("/v1/completions", completions)
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


@pytest.mark.parametrize("hold_back_s, split", [(0.0, False), (5.0, True)])
def test_a_run_whose_ramp_split_ends_at_the_windows_opening(hold_back_s, split):
    """Rows of 400 tokens at 5 ms a token outlast warm phase and window;
    with one client held back past the opening the run raises there and
    then, and does not wait for the rows to end."""
    import time

    async def go():
        server, base = await _fake_serve(0.005)
        run = loadgen.Run(loadgen.Traffic(PROBE, 1000, 5, 0.5), base,
                          hold_back_s=hold_back_s)
        t = time.monotonic()
        try:
            await run.go()
        finally:
            await server.cleanup()
        return run, time.monotonic() - t

    if split:
        t = time.monotonic()
        with pytest.raises(loadgen.RampSplit) as e:
            asyncio.run(go())
        assert time.monotonic() - t < 1.5
        assert e.value.detail["in_flight"] == e.value.detail["streaming"] == 2
    else:
        run, took = asyncio.run(go())
        assert run.ramp["streaming"] == 3 and run.in_flight_at["w1"] == 3
        assert all(r.ok for r in run.results) and took > 2.0
