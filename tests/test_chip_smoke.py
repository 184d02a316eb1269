"""``chip_smoke.py``'s own rules, on the CPU: what the parent decides
from the answers it gets. The chip run itself is the builder's
(``chiprun -- python3 chip_smoke.py``); nothing here is one.
"""

import json
import math
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import chip_smoke  # the script at the repo root; its parent is numpy only


# -- the TP=1 / TP=4 comparison rule ----------------------------------------


class FakeLayout:
    """Stands in for one served layout: next-token logits are a fixed
    function of the context, plus whatever ``disturb`` does to them."""

    VOCAB = 50

    def __init__(self, disturb=None, sharpness=1.0):
        self.disturb = disturb
        self.sharpness = sharpness

    def logits(self, context):
        rng = np.random.default_rng(list(context))
        out = rng.standard_normal(self.VOCAB) * self.sharpness
        if self.disturb is not None:
            self.disturb(context, out)
        return out

    def post(self, path, body):
        assert path == "/v1/completions" and body["temperature"] == 0.0
        context, ids, lps = list(body["prompt"]), [], []
        for _ in range(body["max_tokens"]):
            logits = self.logits(context)
            for tok, bias in (body.get("logit_bias") or {}).items():
                logits[int(tok)] += bias
            tok = int(np.argmax(logits))
            lps.append(float(logits[tok] - np.log(np.exp(logits).sum())))
            ids.append(tok)
            context.append(tok)
        return {"usage": {"completion_tokens": len(ids)},
                "choices": [{"token_ids": ids,
                             "logprobs": {"token_logprobs": lps}}]}


PROMPTS = [[1, 2, 3], [4, 5, 6, 7, 8]]
AT = len(PROMPTS[0]) + 3          # position 3 of the first stream


def _top2(logits):
    second, first = np.argsort(logits)[-2:]
    return first, second


def runner_up_wins_by(margin):
    def disturb(context, logits):
        if len(context) == AT and context[:3] == PROMPTS[0]:
            first, second = _top2(logits)
            logits[second] = logits[first] + margin
    return disturb


def swap_top_two(context, logits):
    if len(context) == AT and context[:3] == PROMPTS[0]:
        first, second = _top2(logits)
        logits[first], logits[second] = logits[second], logits[first]


def inflate_the_winner(context, logits):
    logits[np.argmax(logits)] += 0.5


@pytest.mark.parametrize("disturb,sharpness,verdict", [
    (None, 1.0, {"ties": 0}),
    # A flipped near-tie is accepted, and the stream is compared on.
    (runner_up_wins_by(0.03), 1.0, {"ties": 1}),
    # Beyond the tolerance it is a different answer.
    (runner_up_wins_by(0.2), 1.0, "no tie"),
    # Both layouts equally sure of different tokens: the chosen tokens'
    # logprobs are equal, which says nothing — the other layout's
    # token has to be looked up in this one's distribution.
    (swap_top_two, 6.0, "no tie"),
    # Same argmax everywhere, another distribution.
    (inflate_the_winner, 1.0, "different logprobs"),
], ids=["same", "near-tie", "beyond-tolerance", "both-confident",
        "same-argmax-other-logprob"])
def test_tp_comparison_rule(disturb, sharpness, verdict):
    n = chip_smoke.TP_TOKENS
    ref = FakeLayout(sharpness=sharpness)
    reference = [chip_smoke.greedy(ref, p, n) for p in PROMPTS]
    layout = FakeLayout(disturb, sharpness)
    if isinstance(verdict, str):
        with pytest.raises(chip_smoke.SmokeFailed, match=verdict):
            chip_smoke.replay_reference(layout, PROMPTS, reference)
        return
    out = chip_smoke.replay_reference(layout, PROMPTS, reference)
    assert out["ties"] == verdict["ties"]
    # Every position of every stream was compared.
    assert out["positions_agreed"] + out["ties"] == n * len(PROMPTS)
    assert out["max_logprob_gap"] <= chip_smoke.TP_LOGPROB_TOL


def test_tied_logprob_takes_the_bias_out_again():
    layout = FakeLayout()
    context = PROMPTS[1]
    logits = layout.logits(context)
    first, second = _top2(logits)
    logits[second] = logits[first] - 0.05      # within the tolerance
    layout.logits = lambda _context: logits.copy()
    want = float(logits[second] - np.log(np.exp(logits).sum()))
    got = chip_smoke.tied_logprob(layout, context, int(second))
    assert math.isclose(got, want, abs_tol=1e-9)


# -- depth is not an option --------------------------------------------------


def test_depth_is_a_constant_at_or_above_the_half_model():
    assert 14 <= chip_smoke.LAYERS <= chip_smoke.QWEN25_7B[
        "num_hidden_layers"]
    proc = subprocess.run(
        [sys.executable, chip_smoke.__file__, "--layers", "2"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2, proc
    assert "unrecognized arguments: --layers" in proc.stderr
    assert proc.stdout == ""


# -- what the smoke relies on in ``serve``: a failure is an exit code --------


def test_serve_exits_nonzero_after_a_step_loop_failure(tmp_path,
                                                       monkeypatch):
    """``serve_main`` end to end on the smoke's own toy checkpoint: the
    step loop dies at the first request (on a chip: a kernel the
    compiler refuses), the waiting request gets its 5xx, ``/healthz``
    turns 503, the HTTP server stops by itself and ``serve`` returns 1."""
    from parallax_tpu import cli
    from parallax_tpu.backend.serve import serve_main
    from parallax_tpu.runtime.pipeline import InProcessPipeline

    ckpt = str(tmp_path / "checkpoint")
    chip_smoke.write_checkpoint(ckpt, dict(chip_smoke.TINY), seed=0)

    def refused(self):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(InProcessPipeline, "step_round", refused)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    args = cli.build_parser().parse_args([
        "serve", "--model-path", ckpt, "--host", "127.0.0.1",
        "--port", str(port), "--tp-size", "1", "--max-model-len", "256",
        "--max-batch-size", "4", "--compilation-cache-dir", "off",
    ])
    codes = []
    thread = threading.Thread(
        target=lambda: codes.append(serve_main(args)), daemon=True
    )
    thread.start()
    base = f"http://127.0.0.1:{port}"

    def healthz():
        try:
            with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code
        except OSError:
            return None

    deadline = time.monotonic() + 120
    while healthz() != 200:
        assert thread.is_alive() and time.monotonic() < deadline, codes
        time.sleep(0.2)
    req = urllib.request.Request(
        base + "/v1/chat/completions",
        data=json.dumps({"messages": [{"role": "user", "content": "hi"}],
                         "max_tokens": 4, "temperature": 0}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with pytest.raises(urllib.error.HTTPError) as failed:
        urllib.request.urlopen(req, timeout=60)
    assert failed.value.code == 502
    assert "Mosaic failed" in failed.value.read().decode()
    assert healthz() == 503
    thread.join(timeout=30)
    assert not thread.is_alive(), "serve kept running on a dead step loop"
    assert codes == [1]
