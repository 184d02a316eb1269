"""The parent's handle on the child that holds the chip, and the
reference check it runs through it.

``Server`` (spawn, ``wait_ready``, ``post``, ``status``, ``close``),
``child_env`` and the comparison rule (``replay_reference`` /
``tied_logprob``) are copied from ``chip_smoke.py`` and changed where
noted: the reference is the plain float32 forward of
``benchmarks/harness/reference.py`` in TP=1's place, and the compile
cache is given a fixed directory inside the checkout.
"""

from __future__ import annotations

import json
import math
import operator
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from benchmarks.harness.spec import ROOT

# How far the served logprob of a token may sit from the float32
# reference's, and how close two logits must be to count as tied.
# Reason: the server computes in bf16 (8 bits of mantissa) through 24-36
# layers; against a float32 "highest" reference its logits move by a few
# 1e-2 (chip_smoke measured 0.045 between two bf16 layouts; the largest
# gap this benchmark has measured on the chip is written in PERF.md).
# Computing in a lower precision than bf16 (fp8/int8 weights or KV)
# moves logits by several 1e-1 and fails this.
LOGPROB_TOL = 0.1

# How near a choice made INSIDE the model (top-k experts, blocks of a
# sparse attention) may stand to its boundary before the position is
# left out of the comparison: the unit is ``references.choice_margin``'s,
# the distance from a candidate's score to the selection boundary over
# the standard deviation of that layer's scores. Reason: a bf16 program
# and the float32 reference rank the same scores and, within rounding
# of the boundary, rank them otherwise; the position then differs by a
# whole expert, not by rounding, with nothing wrong on either side.
# Measured (``benchmarks/tests/choice_flips.py``; PERF.md section 2,
# PR 50): one dense and six routed layers of 192 sigmoid-scored experts,
# 8 a token, 12 held, bf16 against the same weights in float32 under
# "highest". A router logit moves by 0.5-0.8% of the logits' spread at
# the median (99%: 2.2-3.3%), at width 1,024 on the CPU in three
# classes of the registry and at 7,168 on the chip alike; 6.0-8.9% of
# positions choose another held expert somewhere, and of 1,414 such
# first flips in 19,456 positions the largest stood at 0.048, 0.064,
# 0.040 (CPU) and 0.051 (chip): 0.05 fails sound runs. At 0.1 none of
# ~4,700 sure positions had flipped (1.56 times the largest margin
# seen; the tail falls a decade in ~0.02), and a quarter of the
# positions is still sure (half at 0.05): over 0.1 too few are. The
# same program with its weights rounded to float8 moves a logit by
# 9.7% of the spread and flips a third of the positions 0.1 calls
# sure: it fails there, besides failing ``LOGPROB_TOL``.
# ``SURE_MIN`` positions at least must stand further off than that, so a
# reference cannot excuse itself by calling every position near.
CHOICE_TIE = 0.1
SURE_MIN = 32


class BenchFailed(Exception):
    pass


def check(cond: bool, what: str, **ctx) -> None:
    if not cond:
        raise BenchFailed(f"{what} {json.dumps(ctx, default=str)[:2000]}")


def tail(path: str, n: int = 6000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError as e:
        return f"<{e}>"


def child_env(rehearse: bool) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # The release-freshness probe is the one thing that wants a network.
    env["PARALLAX_TPU_NO_VERSION_CHECK"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    # The compile cache: one fixed directory inside the checkout (the
    # path is part of the cache's key), whatever the machine sets, and
    # no cap on its size (one stage's programs are ~20 MB each; under
    # the chip machine's 192 MiB cap nothing ever hit - CHANGES.md, PR 22).
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env.pop("BENCH_RUN", None)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return env


class Server:
    """The child: ``server_child.py`` on a free port."""

    def __init__(self, work: str, config_file: str, seed: int, chips: int,
                 rehearse: bool):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self.work = work
        self.log = os.path.join(work, "serve.log")
        cmd = [sys.executable,
               os.path.join(ROOT, "benchmarks", "harness", "server_child.py"),
               "--config-file", config_file, "--workdir", work,
               "--port", str(self.port), "--seed", str(seed),
               "--chips", str(chips)]
        if rehearse:
            cmd.append("--rehearse")
        self.t_spawn = time.monotonic()
        with open(self.log, "wb") as log:
            self.proc = subprocess.Popen(
                cmd, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
                env=child_env(rehearse), start_new_session=True,
            )

    def fail(self, what: str):
        raise BenchFailed(f"{what}\n--- {self.log} ---\n{tail(self.log)}")

    def get(self, path: str, timeout: float = 30.0):
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return r.status, r.read()

    def wait_ready(self, deadline_s: float) -> float:
        while time.monotonic() - self.t_spawn < deadline_s:
            if self.proc.poll() is not None:
                self.fail(f"the server exited {self.proc.returncode} before "
                          "/healthz answered")
            try:
                status, body = self.get("/healthz", timeout=5)
                if status == 200 and json.loads(body).get("status") == "ok":
                    return time.monotonic() - self.t_spawn
            except (urllib.error.URLError, OSError, ValueError):
                pass
            time.sleep(0.25)
        self.fail(f"/healthz not ready after {deadline_s:.0f}s")

    def post(self, path: str, body: dict, timeout: float = 600.0) -> dict:
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            self.fail(f"POST {path} -> {e.code}: {e.read()[:500]!r}")
        except (urllib.error.URLError, OSError) as e:
            self.fail(f"POST {path} failed: {e!r}")

    def status(self) -> dict:
        code, body = self.get("/cluster/status_json")
        check(code == 200, f"/cluster/status_json -> {code}")
        return json.loads(body)

    def device(self) -> dict:
        with open(os.path.join(self.work, "device.json")) as f:
            return json.load(f)

    def close(self, grace_s: float = 20.0) -> int | None:
        """SIGTERM the child's group, SIGKILL what is left, wait."""
        proc = self.proc
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGTERM)
            except ProcessLookupError:
                pass
            try:
                proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        return proc.returncode


# --------------------------------------------------------------------------
# The reference check (chip_smoke's rule, the reference in TP=1's place).
# --------------------------------------------------------------------------


def greedy(srv, prompt_ids: list[int], n: int, **extra):
    """``n`` greedy tokens after ``prompt_ids``: (ids, their logprobs)."""
    r = srv.post("/v1/completions", dict(
        model="bench", prompt=prompt_ids, max_tokens=n, logprobs=True,
        temperature=0.0, ignore_eos=True, **extra,
    ))
    check(r["usage"]["completion_tokens"] == n, "reference replay: "
          "completion_tokens", usage=r["usage"], want=n)
    choice = r["choices"][0]
    return choice["token_ids"], choice["logprobs"]["token_logprobs"]


def tied_logprob(srv, context: list[int], token: int) -> float | None:
    """``token``'s logprob after ``context`` if it ties the server's own
    maximum there, else None. With ``LOGPROB_TOL`` added to that one logit
    (``logit_bias``) a greedy step picks ``token`` exactly when it was
    within the tolerance of the maximum. The logprob comes back with the
    bias in it (``q = p e^B / (1 - p + p e^B)``) and is returned with it
    taken out."""
    bias = LOGPROB_TOL
    ids, (biased,) = greedy(srv, context, 1, logit_bias={str(token): bias})
    if ids != [token]:
        return None
    q = math.exp(biased)
    return biased - math.log(math.exp(bias) * (1.0 - q) + q)


def replay_reference(srv, rows: list[dict]) -> dict:
    """The server generates greedily and at every position must give the
    reference's token the reference's logprob (within ``LOGPROB_TOL``),
    and either choose it or hold it tied with what it chose. After a tie
    its stream has left the reference's context, so it starts again from
    there: every position of every row is compared in the reference's
    context. A tie is believed only where the reference itself saw its
    two best logits within the tolerance.

    A row may carry ``choice_margin`` (``benchmarks/references``): how
    near each position's forward pass came to choosing otherwise inside
    the model. A position under ``CHOICE_TIE`` is unsure: its logprob
    gap is recorded where the server chose the reference's token, held
    to nothing, and a token that differs is no failure; the replay
    starts again from the reference's context as after a tie. Every
    other position is held to all of the above, and a row without the
    key is sure throughout.

    Returns what was compared (``compared`` reads it): the positions
    that agreed, the ties, the largest logprob gap, the largest gap of
    the reference's two best logits at a divergence and the divergences
    that were no tie, all of the sure positions, and, only where a row
    carried the key, the unsure positions and their largest logprob
    gap; the replay ends at the first position that fails, and
    ``failed`` says where and why (None where none did)."""
    out = {"positions_agreed": 0, "ties": 0, "max_logprob_gap": 0.0,
           "max_tie_top2_gap": 0.0, "untied": 0, "failed": None}
    if any("choice_margin" in row for row in rows):
        out.update(positions_unsure=0, max_unsure_logprob_gap=0.0)

    def failed(what: str, **ctx) -> dict:
        out["failed"] = f"{what} {json.dumps(ctx, default=str)[:500]}"
        return out

    for i, row in enumerate(rows):
        prompt, ref_ids, ref_lps = row["prompt"], row["tokens"], row["logprobs"]
        margin = row.get("choice_margin")
        done = 0
        while done < len(ref_ids):
            ids, lps = greedy(srv, prompt + ref_ids[:done],
                              len(ref_ids) - done)
            for tok, lp in zip(ids, lps):
                want = ref_ids[done]
                if margin is not None and margin[done] < CHOICE_TIE:
                    out["positions_unsure"] += 1
                    if tok == want:
                        out["max_unsure_logprob_gap"] = max(
                            out["max_unsure_logprob_gap"],
                            abs(lp - ref_lps[done]))
                    done += 1
                    if tok != want:
                        break    # resume from the reference's context
                    continue
                if tok != want:
                    top2 = row["top2_gap"][done]
                    out["max_tie_top2_gap"] = max(out["max_tie_top2_gap"], top2)
                    if top2 > 2 * LOGPROB_TOL:
                        return failed(
                            f"row {i} position {done}: the server left a "
                            "token the reference was sure of",
                            reference=want, server=tok,
                            reference_top2_gap=top2)
                    lp = tied_logprob(srv, prompt + ref_ids[:done], want)
                    if lp is None:
                        out["untied"] += 1
                        return failed(
                            f"row {i} position {done}: a divergence that is "
                            "no tie: the reference's token is not within "
                            f"{LOGPROB_TOL} of the server's maximum",
                            reference=want, server=tok)
                gap = abs(lp - ref_lps[done])
                out["max_logprob_gap"] = max(out["max_logprob_gap"], gap)
                if gap > LOGPROB_TOL:
                    return failed(
                        f"row {i} position {done}: token {want} has another "
                        "logprob than the reference gives it",
                        reference=ref_lps[done], server=lp)
                done += 1
                if tok != want:
                    out["ties"] += 1
                    break        # resume from the reference's context
                out["positions_agreed"] += 1
    return out


def repeat_agrees(srv, prompt: list[int], n: int) -> bool:
    """One greedy prompt sent twice comes back the same. The repeat is
    served from the prefix cache, so its prefill runs other shapes; with
    random weights two logits are often closer than bf16 resolves (1-8
    ties in 64 positions against the reference), and one seed in six
    flipped a token here on the chip. So: token-identical, or identical
    up to a position where the first answer's token ties the repeat's
    choice (``tied_logprob``) with the logprobs before it agreeing."""
    a, lps_a = greedy(srv, prompt, n)
    b, lps_b = greedy(srv, prompt, n)
    if a == b:
        return True
    i = next(j for j in range(n) if a[j] != b[j])
    if any(abs(x - y) > LOGPROB_TOL for x, y in zip(lps_a[:i], lps_b[:i])):
        return False
    tied = tied_logprob(srv, prompt + a[:i], a[i])
    return tied is not None and abs(tied - lps_a[i]) <= LOGPROB_TOL


RULES = {"<=": operator.le, ">=": operator.ge, "==": operator.eq}


def compared(ref_check: dict, repeat_ok: bool | None = None,
             client: dict | None = None) -> dict:
    """Every number that decides ``correct``, each beside its limit:
    ``{name: {"value", "limit", "rule"}}``, ``value`` None for what the
    run did not reach (the replay failed, so no repeat was sent and no
    window opened). ``holds`` says whether all of it holds."""
    def entry(value, limit, rule):
        return {"value": value, "limit": limit, "rule": rule}

    sure = ref_check["positions_agreed"] + ref_check["ties"]
    choices = {}
    if "positions_unsure" in ref_check:
        # Some row said how near its positions came to choosing
        # otherwise inside the model: the numbers above it in the line
        # then speak of the sure positions, and enough must be left.
        choices = {
            "positions_sure": entry(sure, SURE_MIN, ">="),
            "positions_unsure": entry(
                ref_check["positions_unsure"], None, None),
            "unsure_logprob_gap_max": entry(
                ref_check["max_unsure_logprob_gap"], None, None),
            "choice_tie": entry(CHOICE_TIE, None, None),
        }
    return {
        "positions": entry(sure, 1, ">="),
        "logprob_gap_max": entry(
            ref_check["max_logprob_gap"], LOGPROB_TOL, "<="),
        "ties": entry(ref_check["ties"], None, None),
        "tie_top2_gap_max": entry(
            ref_check["max_tie_top2_gap"], 2 * LOGPROB_TOL, "<="),
        "divergences_untied": entry(ref_check["untied"], 0, "=="),
        **choices,
        "repeat_identical": entry(
            None if repeat_ok is None else int(repeat_ok), 1, "=="),
        "requests_attempted": entry(
            client and client["attempted"], 1, ">="),
        "requests_failed_or_short": entry(
            client and client["failed"], 0, "=="),
    }


def holds(numbers: dict) -> bool:
    return all(
        n["value"] is not None and RULES[n["rule"]](n["value"], n["limit"])
        for n in numbers.values() if n["rule"] is not None)
