"""The A.X-K1 cell through ``--rehearse`` and ``--trace 2``, as
``test_rehearse.py`` and ``test_trace2.py`` hold every cell, but for one
thing: those two share an ``assert_compared`` that holds ``compared`` to
the exact keys of a reference without ``choice_margin``, and this cell's
reference returns one (``references/axk1.py``), so its line has the four
keys more that ``server.compared`` then gives. Their cases on this cell
are red for that alone, and an accepted file is no later PR's to edit
(PERF.md section 7); until a ``benchmark`` PR lets them take the keys
from ``server.compared``, this file does, and holds everything else as
they do, through their own code."""

import json
import os

import pytest

from benchmarks.harness import server, spec
from benchmarks.tests import test_rehearse as accepted

CELL = "a.x-k1-ep16-d7.decode-probe128-3k"
# What a replay hands ``server.compared`` when some row said how near its
# positions came to choosing otherwise.
REF_CHECK = dict.fromkeys(
    ("positions_agreed", "ties", "untied", "max_logprob_gap",
     "max_tie_top2_gap", "positions_unsure", "max_unsure_logprob_gap"), 0)


def assert_compared(line, stderr):
    """``test_rehearse.assert_compared``, the keys from the harness."""
    numbers = line["compared"]
    assert list(line)[-1] == "compared"
    assert list(numbers) == list(server.compared(REF_CHECK))
    assert {"positions_sure", "positions_unsure", "choice_tie"} < set(numbers)
    assert numbers["logprob_gap_max"]["limit"] == server.LOGPROB_TOL
    assert numbers["tie_top2_gap_max"]["limit"] == 2 * server.LOGPROB_TOL
    assert numbers["positions_sure"]["limit"] == server.SURE_MIN
    assert numbers["choice_tie"]["value"] == server.CHOICE_TIE
    last = stderr.strip().splitlines()[-len(numbers):]
    assert [x.split(":")[0] for x in last] == [
        f"compared {name}" for name in numbers]


@pytest.fixture
def rehearse(monkeypatch):
    monkeypatch.setattr(accepted, "assert_compared", assert_compared)
    # Under ``tests/conftest.py`` the variable asks for eight CPU devices,
    # and ``serve`` would shard the toy stage over them.
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    return accepted.rehearse


def test_the_accepted_helper_fails_on_this_cell_for_the_keys_alone():
    """Why this file exists; when it fails, the accepted cases are green
    on this cell and this file can go."""
    numbers = server.compared(REF_CHECK)
    with pytest.raises(AssertionError):
        accepted.assert_compared({"compared": numbers}, "")
    plain = {k: v for k, v in REF_CHECK.items()
             if k not in ("positions_unsure", "max_unsure_logprob_gap")}
    line = {"compared": server.compared(plain)}
    stderr = "\n".join(f"compared {name}: x" for name in line["compared"])
    accepted.assert_compared(line, stderr)


def test_rehearse_the_cell(rehearse, monkeypatch):
    """``test_rehearse_each_cell`` on this cell, and the two counters of
    the expert layer's share beside the accepted ones."""
    lines = []
    monkeypatch.setattr(
        accepted, "rehearse",
        lambda *a, **k: lines.append(rehearse(*a, **k)) or lines[-1])
    accepted.test_rehearse_each_cell(CELL)
    got = lines[0]["metrics"]
    assert 0 < got["moe_held_hit_share"]["value"] <= 100
    assert got["moe_pairs_held_per_step"]["value"] > 0
    assert lines[0]["compared"]["positions_sure"]["value"] >= server.SURE_MIN


def sent_in_window(cell):
    """(prompt length, tokens asked) of the kept run's requests sent
    before the window closed, in the order sent (``test_trace2``'s)."""
    path = os.path.join(spec.ROOT, ".bench_work", cell, "requests.jsonl")
    with open(path) as f:
        rows = [json.loads(x) for x in f]
    return [(r["prompt"], r["want"]) for r in rows if r["sent"] < 3.0]


def test_trace_2_is_trace_0_and_then_a_traced_tail(rehearse):
    """``test_trace2``'s case on this cell: one last line with the
    end-to-end metrics' place and the per-layer counts side by side; up
    to the window's end the requests are ``--trace 0``'s."""
    plain = rehearse(CELL, trace=0, extra=("--keep-work",))
    sent_0 = sent_in_window(CELL)
    traced = rehearse(CELL, trace=2, extra=("--keep-work",))
    sent_2 = sent_in_window(CELL)
    assert plain["metrics"] == {}
    assert {"batch_tokens_per_visit", "kv_preemptions", "prefix_hit_share",
            "compiles_in_window", "moe_held_hit_share",
            "moe_pairs_held_per_step"} <= set(traced["metrics"])
    assert "breakdown" not in traced           # no device trace on the CPU
    n = min(len(sent_0), len(sent_2))
    assert n >= 8 and sent_0[:n] == sent_2[:n]
