"""``--trace 2``: ``--trace 0`` to the letter until the window closes,
then a traced tail of the same traffic, one last line with both kinds of
metric (``benchmarks/TRACING.md``)."""

import json
import os

import pytest
from test_loadgen import OPEN
from test_rehearse import cells, rehearse

from benchmarks.harness import loadgen, spec


def test_a_tail_changes_no_request_of_warm_phase_or_window():
    """The traffic goes on after the window; what is sent up to its end
    is what ``--trace 0`` sends, draw for draw."""
    sessions = dict(OPEN, sessions={
        "turns": {"dist": "uniform", "min": 2, "max": 4},
        "think_s": {"dist": "fixed", "value": 1}})

    def key(r):
        return (r.due, r.prompt, r.max_tokens, r.seed, r.judged, r.session,
                r.turns_left)

    for t in (OPEN, sessions):
        plain = loadgen.Traffic(t, 1000, 3_000_000_007, 20.0)
        tailed = loadgen.Traffic(t, 1000, 3_000_000_007, 20.0, tail=12.0)
        n = len(plain.schedule)
        assert [key(r) for r in tailed.schedule[:n]] == [
            key(r) for r in plain.schedule]
        extra = tailed.schedule[n:]
        assert len(extra) == round(t["arrival"]["rate_rps"] * 12.0)
        assert all(not r.judged and 25.0 <= r.due < 37.0 for r in extra)
        assert [r.session for r in extra] == list(range(n, n + len(extra)))
        # What is drawn while the run goes (later turns, token ids) too.
        assert plain._tokens(7) == tailed._tokens(7)
        assert plain._seed() == tailed._seed()
    probe = spec.load_traffic("decode-probe8")
    a = loadgen.Traffic(probe, 1000, 11, 30.0)
    b = loadgen.Traffic(probe, 1000, 11, 30.0, tail=12.0)
    for now in (0.02, 0.02, 14.0, 32.9):
        x, y = a.closed_next(now), b.closed_next(now)
        assert (x.prompt, x.max_tokens, x.seed, x.judged) == (
            y.prompt, y.max_tokens, y.seed, y.judged)
    assert not b.closed_next(34.0).judged       # sent in the tail


def sent_in_window(cell):
    """(prompt length, tokens asked) of the kept run's requests sent
    before the window closed, in the order sent."""
    path = os.path.join(spec.ROOT, ".bench_work", cell, "requests.jsonl")
    with open(path) as f:
        rows = [json.loads(x) for x in f]
    return [(r["prompt"], r["want"]) for r in rows if r["sent"] < 3.0]


@pytest.mark.parametrize("cell", cells())
def test_trace_2_is_trace_0_and_then_a_traced_tail(cell):
    """One last line with the end-to-end metrics' place (a rehearsal
    writes no time or rate) and the per-layer counts side by side; up to
    the window's end the requests are ``--trace 0``'s."""
    plain = rehearse(cell, trace=0, extra=("--keep-work",))
    sent_0 = sent_in_window(cell)
    traced = rehearse(cell, trace=2, extra=("--keep-work",))
    sent_2 = sent_in_window(cell)
    assert plain["metrics"] == {}
    assert {"batch_tokens_per_visit", "kv_preemptions", "prefix_hit_share",
            "compiles_in_window"} <= set(traced["metrics"])
    assert "breakdown" not in traced           # no device trace on the CPU
    n = min(len(sent_0), len(sent_2))
    assert n >= 8 and sent_0[:n] == sent_2[:n]
