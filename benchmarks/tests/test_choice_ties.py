"""``correct`` and a choice made inside the model: ``server.replay_reference``
and ``server.compared`` over a scripted stand-in for the server (pure
Python, no JAX, no ``serve``).

Rows without ``choice_margin`` must read what they read before the key
existed, key for key and value for value (the five cells' references
return none); rows with it are held to the same limits at every sure
position, to nothing at an unsure one, and need ``SURE_MIN`` sure ones."""

import math
import sys
import types

import pytest

from benchmarks.harness import server, server_child

PROMPT = [1, 2, 3]
# The reference's four tokens, with the probability it gives each and
# the gap between its two best logits there.
TOKENS, PROBS, TOP2 = [10, 11, 12, 13], [0.9, 0.5, 0.9, 0.9], [2.0, 0.05, 2.0, 2.0]
OTHER = 21       # what a server that diverges at position 1 says there


def reference_row(**more) -> dict:
    return {"prompt": PROMPT, "tokens": TOKENS,
            "logprobs": [math.log(p) for p in PROBS], "top2_gap": TOP2, **more}


def agreeing(n: int = len(TOKENS)) -> list[dict]:
    """A server's distributions that are the reference's."""
    return [{TOKENS[i % 4]: PROBS[i % 4], 99: 1 - PROBS[i % 4]}
            for i in range(n)]


def with_position_1(dist: dict) -> list[dict]:
    dists = agreeing()
    dists[1] = dist
    return dists


class Scripted:
    """What ``replay_reference`` asks of a server: ``/v1/completions``
    with a token-array prompt, greedy, ``logit_bias`` honoured. The
    distribution at a position is scripted by row and position, whatever
    the tokens before it were."""

    def __init__(self, *rows: tuple[list[int], list[dict]]):
        self.rows = rows
        self.requests = []

    def post(self, path: str, body: dict) -> dict:
        assert path == "/v1/completions" and body["temperature"] == 0.0
        self.requests.append(body)
        context, bias = body["prompt"], body.get("logit_bias", {})
        prompt, dists = next(r for r in self.rows
                             if context[:len(r[0])] == r[0])
        at = len(context) - len(prompt)
        ids, lps = [], []
        for dist in dists[at:at + body["max_tokens"]]:
            logits = {t: math.log(p) + bias.get(str(t), 0.0)
                      for t, p in dist.items()}
            norm = math.log(sum(math.exp(x) for x in logits.values()))
            tok = max(logits, key=logits.get)
            ids.append(tok)
            lps.append(logits[tok] - norm)
        return {"usage": {"completion_tokens": len(ids)},
                "choices": [{"token_ids": ids,
                             "logprobs": {"token_logprobs": lps}}]}


def today(positions, gap, ties, top2, untied) -> dict:
    """``compared`` of a replay as it read before rows could carry
    ``choice_margin``: these keys in this order, these limits."""
    def entry(value, limit, rule):
        return {"value": value, "limit": limit, "rule": rule}

    return {"positions": entry(positions, 1, ">="),
            "logprob_gap_max": entry(gap, 0.1, "<="),
            "ties": entry(ties, None, None),
            "tie_top2_gap_max": entry(top2, 0.2, "<="),
            "divergences_untied": entry(untied, 0, "=="),
            "repeat_identical": entry(None, 1, "=="),
            "requests_attempted": entry(None, 1, ">="),
            "requests_failed_or_short": entry(None, 0, "==")}


def assert_reads(numbers: dict, want: dict) -> None:
    assert list(numbers) == list(want)
    for name, n in numbers.items():
        assert (n["limit"], n["rule"]) == (want[name]["limit"],
                                           want[name]["rule"]), name
        assert n["value"] == pytest.approx(want[name]["value"], abs=1e-9), name


def at_1(p_reference: float, log_odds_other: float) -> dict:
    """Position 1 as a server sees it: the reference's token at
    ``p_reference``, another preferred to it by ``log_odds_other``, the
    rest spread over tokens that never lead."""
    p_other = p_reference * math.exp(log_odds_other)
    rest = (1.0 - p_reference - p_other) / 4
    assert 0 < rest < min(p_reference, p_other)
    return {11: p_reference, OTHER: p_other, **{95 + i: rest for i in range(4)}}


SAME = at_1(0.5, -0.5)                       # the reference's own
TIE = at_1(0.46, 0.08)                       # another token leads by 0.08
ELSEWHERE = at_1(0.25, 0.85)                 # and by 0.85: no tie
FAR = at_1(0.5 * math.exp(-0.4), -0.2)       # its token, 0.4 further down


@pytest.mark.parametrize("dist, top2_at_1, want, fails", [
    (SAME, 0.05, today(4, 0.0, 0, 0.0, 0), None),
    (TIE, 0.05, today(4, math.log(0.5 / 0.46), 1, 0.05, 0), None),
    (ELSEWHERE, 0.05, today(1, 0.0, 0, 0.05, 1), "no tie"),
    # A tie by the server's logits, of a token the reference was sure of.
    (TIE, 0.5, today(1, 0.0, 0, 0.5, 0), "sure of"),
    (FAR, 0.05, today(1, 0.4, 0, 0.0, 0), "another logprob"),
], ids=["agreement", "tie", "untied_divergence", "sure_token_left",
        "gap_failure"])
def test_rows_without_the_key_read_what_they_read(dist, top2_at_1, want,
                                                  fails):
    row = reference_row(top2_gap=[2.0, top2_at_1, 2.0, 2.0])
    srv = Scripted((PROMPT, with_position_1(dist)))
    check = server.replay_reference(srv, [row])
    assert set(check) == {"positions_agreed", "ties", "max_logprob_gap",
                          "max_tie_top2_gap", "untied", "failed"}
    assert (check["failed"] is None) == (fails is None)
    assert fails is None or fails in check["failed"]
    numbers = server.compared(check)
    assert_reads(numbers, want)
    assert not server.holds(numbers)      # no repeat, no window yet
    whole = server.compared(check, True, {"attempted": 8, "failed": 0})
    assert server.holds(whole) == (fails is None)


def test_a_tie_resumes_from_the_references_context():
    srv = Scripted((PROMPT, with_position_1(TIE)))
    server.replay_reference(srv, [reference_row()])
    # The replay, the tie's question (one token, the bias on the
    # reference's), the replay again from the reference's context.
    assert [(r["prompt"], r["max_tokens"], r.get("logit_bias"))
            for r in srv.requests] == [
        (PROMPT, 4, None),
        (PROMPT + TOKENS[:1], 1, {"11": server.LOGPROB_TOL}),
        (PROMPT + TOKENS[:2], 2, None)]


CHOICE_KEYS = ["positions", "logprob_gap_max", "ties", "tie_top2_gap_max",
               "divergences_untied", "positions_sure", "positions_unsure",
               "unsure_logprob_gap_max", "choice_tie", "repeat_identical",
               "requests_attempted", "requests_failed_or_short"]


def test_a_gap_passes_at_an_unsure_position_and_fails_at_a_sure_one():
    near, off = server.CHOICE_TIE / 2, server.CHOICE_TIE
    unsure = server.replay_reference(
        Scripted((PROMPT, with_position_1(FAR))),
        [reference_row(choice_margin=[1.0, near, 1.0, 1.0])])
    assert unsure["failed"] is None
    numbers = server.compared(unsure)
    assert list(numbers) == CHOICE_KEYS
    got = {k: n["value"] for k, n in numbers.items()}
    assert (got["positions"], got["positions_sure"],
            got["positions_unsure"]) == (3, 3, 1)
    assert got["logprob_gap_max"] == 0.0
    assert got["unsure_logprob_gap_max"] == pytest.approx(0.4)
    assert got["choice_tie"] == server.CHOICE_TIE
    # Counted, no limit of their own; the sure ones have one.
    assert [numbers[k]["rule"] for k in CHOICE_KEYS[5:9]] == [
        ">=", None, None, None]
    assert numbers["positions_sure"]["limit"] == server.SURE_MIN == 32

    # A margin AT the constant is sure: today's limit, today's failure.
    sure = server.replay_reference(
        Scripted((PROMPT, with_position_1(FAR))),
        [reference_row(choice_margin=[1.0, off, 1.0, 1.0])])
    assert "another logprob" in sure["failed"]
    got = {k: n["value"] for k, n in server.compared(sure).items()}
    assert got["logprob_gap_max"] == pytest.approx(0.4)
    assert (got["positions"], got["positions_unsure"]) == (1, 0)


def test_a_divergence_at_an_unsure_position_resumes_and_asks_nothing():
    """The program went to another expert and says another token, far
    from any tie: no failure, no ``tied_logprob`` question, and the
    replay goes on from the reference's context."""
    srv = Scripted((PROMPT, with_position_1(ELSEWHERE)))
    check = server.replay_reference(
        srv, [reference_row(top2_gap=[2.0, 2.0, 2.0, 2.0],
                            choice_margin=[1.0, 0.0, 1.0, 1.0])])
    assert check["failed"] is None
    assert (check["positions_agreed"], check["ties"], check["untied"],
            check["positions_unsure"]) == (3, 0, 0, 1)
    assert check["max_unsure_logprob_gap"] == 0.0    # not its token's
    assert [(r["prompt"], r["max_tokens"], r.get("logit_bias"))
            for r in srv.requests] == [
        (PROMPT, 4, None), (PROMPT + TOKENS[:2], 2, None)]
    # The same divergence at a sure position is what it was.
    sure = server.replay_reference(
        Scripted((PROMPT, with_position_1(ELSEWHERE))),
        [reference_row(top2_gap=[2.0, 2.0, 2.0, 2.0],
                       choice_margin=[1.0, 1.0, 1.0, 1.0])])
    assert "sure of" in sure["failed"]


def long_replay(margins: list[float]) -> dict:
    """One row of as many agreeing positions as margins, replayed and
    compared with a repeat and a window that held."""
    n = len(margins)
    row = {"prompt": PROMPT, "tokens": [TOKENS[i % 4] for i in range(n)],
           "logprobs": [math.log(PROBS[i % 4]) for i in range(n)],
           "top2_gap": [2.0] * n, "choice_margin": margins}
    check = server.replay_reference(Scripted((PROMPT, agreeing(n))), [row])
    assert check["failed"] is None
    return server.compared(check, True, {"attempted": 8, "failed": 0})


@pytest.mark.parametrize("sure, holds", [(31, False), (32, True)])
def test_enough_positions_must_be_sure(sure, holds):
    numbers = long_replay([1.0] * sure + [0.0] * 9)
    assert numbers["positions_sure"]["value"] == sure
    assert numbers["positions_unsure"]["value"] == 9
    assert server.holds(numbers) is holds


def test_margins_of_nought_excuse_nothing():
    numbers = long_replay([0.0] * 64)
    assert numbers["positions"]["value"] == 0
    assert numbers["positions_sure"]["value"] == 0
    assert numbers["positions_unsure"]["value"] == 64
    assert not server.holds(numbers)


def test_one_row_with_the_key_is_enough_to_count_sure_positions():
    """Rows without the key beside one with it: theirs are sure."""
    keyed = reference_row(choice_margin=[0.0, 1.0, 0.0, 1.0])
    plain = dict(reference_row(), prompt=[7, 8])
    check = server.replay_reference(
        Scripted((PROMPT, agreeing()), ([7, 8], agreeing())), [keyed, plain])
    got = {k: n["value"] for k, n in server.compared(check).items()}
    assert (got["positions_sure"], got["positions_unsure"]) == (6, 2)


@pytest.mark.parametrize("margin, says", [
    ([1.0, 1.0, 1.0], "3 choice margins for 4 tokens"),
    ([1.0, -0.5, 1.0, 1.0], "not -0.5"),
    ([1.0, 1.0, float("nan"), 1.0], "not nan"),
], ids=["wrong_length", "negative", "nan"])
def test_run_reference_refuses_a_margin_that_is_none(tmp_path, monkeypatch,
                                                     margin, says):
    module = types.ModuleType("benchmarks.references.scripted_for_test")
    module.greedy_continuations = lambda params, cfg, prompts, n: [
        reference_row(choice_margin=margin)]
    monkeypatch.setitem(sys.modules, module.__name__, module)
    ref = {"module": "scripted_for_test", "import": module.__name__,
           "rows": [{"prompts": 1, "prompt_tokens": 3, "new_tokens": 4}]}
    out = tmp_path / "reference.json"
    with pytest.raises(ValueError, match=says):
        server_child.run_reference(None, {"vocab_size": 50}, 7, str(out), ref)
    assert not out.exists()
    # What it takes: margins as many as tokens, none below nought.
    module.greedy_continuations = lambda params, cfg, prompts, n: [
        reference_row(choice_margin=[0.0, 0.3, 1.0, float("inf")])]
    server_child.run_reference(None, {"vocab_size": 50}, 7, str(out), ref)
    assert out.exists()
