"""``LocalRunner``'s inbox (backend/serve.py): the step loop's thread is
the pipeline's only owner. Submits and stops return while a round runs
and are taken in at the top of the next one; what ``submit`` refused
before it still refuses, with the same exceptions.

Rounds are counted, not seconds: a hook holds one round of the loop
until the test lets it go, so "while a round runs" is a fact and not a
race. Every wait has a limit of its own (``LIMIT_S``).
"""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from parallax_tpu.analysis import sanitizer
from parallax_tpu.backend.http_server import BackendUnavailable
from parallax_tpu.backend.serve import LocalRunner
from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.obs import names as mnames
from parallax_tpu.obs.registry import get_registry
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, RequestStatus, SamplingParams

TINY = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"],
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128, vocab_size=258 + 8,
    max_position_embeddings=512,
))

LIMIT_S = 60.0


def build_engine():
    m = StageModel(TINY, 0, 2, use_pallas=False)
    return StageEngine(
        m, m.init_params(jax.random.key(0), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=256, max_model_len=512,
                     max_batch_size=32, kv_dtype="float32"),
    )


def request(rid, max_tokens=8, prompt=(1, 2, 3, 4, 5)):
    return Request(rid, prompt_ids=list(prompt),
                   sampling_params=SamplingParams(
                       temperature=0.0, max_new_tokens=max_tokens,
                       ignore_eos=True))


def drained():
    """(sum, count) of ``parallax_inbox_drained``."""
    snaps = get_registry().histogram_snapshots().get(
        mnames.INBOX_DRAINED) or {}
    return (sum(s["sum"] for s in snaps.values()),
            sum(s["count"] for s in snaps.values()))


class HeldLoop:
    """A runner whose loop steps a real pipeline, with a hook around
    ``step_round`` (the loop's thread): it counts the rounds, notes the
    round in which each watched request first left PENDING (a plan held
    it), and can hold one round open until ``release()``."""

    def __init__(self):
        self.engine = build_engine()
        self.pipeline = InProcessPipeline([self.engine])
        self.runner = LocalRunner(self.pipeline)
        self.started = 0            # rounds begun
        self.finished = 0           # rounds ended
        self.watched: list[Request] = []
        self.planned_in: dict[str, int] = {}
        self.done_in: dict[str, int] = {}
        self._hold = threading.Event()      # ask the next round to wait
        self.holding = threading.Event()    # a round is waiting
        self._go = threading.Event()
        real = self.pipeline.step_round

        def step_round():
            self.started += 1
            if self._hold.is_set():
                self._hold.clear()
                self.holding.set()
                assert self._go.wait(LIMIT_S), "the held round was never let go"
                self.holding.clear()
            out = real()
            for req in self.watched:
                rid = req.request_id
                if (rid not in self.planned_in
                        and req.status is not RequestStatus.PENDING):
                    self.planned_in[rid] = self.started
                if rid not in self.done_in and req.status.is_finished:
                    self.done_in[rid] = self.started
            self.finished += 1
            return out

        self.pipeline.step_round = step_round

    def __enter__(self):
        self.runner.start()
        return self

    def __exit__(self, *exc):
        self._go.set()
        self.runner.stop()

    def hold_a_round(self) -> int:
        """Returns once a round is being held; its number."""
        self._go.clear()
        self._hold.set()
        assert self.holding.wait(LIMIT_S), "no round came to be held"
        return self.started

    def release(self):
        self._go.set()

    def keep_busy(self, tokens=2000):
        """A row that keeps the loop stepping."""
        keeper = request("keeper", max_tokens=tokens)
        self.runner.submit(keeper)
        wait_for(lambda: keeper.output_ids, "the keeper never decoded")
        return keeper


def wait_for(cond, what):
    deadline = time.monotonic() + LIMIT_S
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.001)


# -- (i) submits beside a running round ------------------------------------------


def test_sixteen_threads_submit_while_a_round_runs_and_are_planned_next():
    with HeldLoop() as h:
        h.keep_busy()
        reqs = [request(f"t{i}") for i in range(16)]
        h.watched = reqs
        held = h.hold_a_round()
        before = drained()
        events = {}

        def send(req):
            events[req.request_id] = h.runner.submit(req)

        threads = [threading.Thread(target=send, args=(r,)) for r in reqs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(LIMIT_S)
        # Every submit has returned and the round it arrived beside is
        # still running: nobody waited for the loop.
        assert not any(t.is_alive() for t in threads)
        assert len(events) == 16
        assert h.holding.is_set() and h.finished == held - 1
        assert all(r.status is RequestStatus.PENDING for r in reqs)
        h.release()
        for r in reqs:
            assert events[r.request_id].wait(LIMIT_S), r.request_id
        # In a plan no later than the second round begun after the
        # submit returned; here all sixteen in the very next one, taken
        # from the inbox together.
        assert set(h.planned_in) == {r.request_id for r in reqs}
        assert all(n <= held + 2 for n in h.planned_in.values()), h.planned_in
        assert set(h.planned_in.values()) == {held + 1}
        after = drained()
        assert (after[0] - before[0], after[1] - before[1]) == (16.0, 1)
        assert all(len(r.output_ids) == 8 for r in reqs)


def test_submit_and_stop_request_take_no_instrumented_lock():
    """With the lock-order sanitizer on, every lock of the package is
    instrumented: a submit and a stop beside a held round acquire none
    (``backend.serve`` is no node of the graph any more)."""
    was = sanitizer.is_enabled()
    sanitizer.enable()
    try:
        with HeldLoop() as h:
            h.keep_busy()
            h.hold_a_round()
            before = sanitizer.report()["acquisitions"]
            done = h.runner.submit(request("quiet"))
            h.runner.stop_request("quiet")
            assert sanitizer.report()["acquisitions"] == before
            assert "backend.serve" not in sanitizer.report()["locks"]
            h.release()
            assert done.wait(LIMIT_S)
    finally:
        if not was:
            sanitizer.disable()


# -- (ii) what submit refuses -----------------------------------------------------


def _over_long(h):
    return request("long", prompt=[1] * 600)


def _empty(h):
    return request("empty", prompt=())


def _queue_full(h):
    h.engine.scheduler.max_queue_size = 2
    h.hold_a_round()
    h.runner.submit(request("q0"))
    h.runner.submit(request("q1"))
    return request("q2")


def _failed_loop(h):
    def boom():
        raise RuntimeError("boom")

    h.pipeline.step_round = boom
    done = h.runner.submit(request("victim"))
    assert done.wait(LIMIT_S)
    wait_for(lambda: h.runner.failure is not None, "the loop never failed")
    return request("late")


@pytest.mark.parametrize("make, exc, text", [
    (_over_long, ValueError, "prompt length 600 exceeds max_model_len 512"),
    (_empty, ValueError, "prompt must contain at least one token"),
    (_queue_full, RuntimeError, "engine queue full"),
    (_failed_loop, BackendUnavailable,
     "step loop failed: RuntimeError('boom')"),
], ids=["over-long", "empty", "queue-full", "failed-loop"])
def test_submit_refuses_what_it_refused_with_the_same_exception(
        make, exc, text):
    with HeldLoop() as h:
        if make is not _failed_loop:
            h.keep_busy()
        req = make(h)
        with pytest.raises(exc) as got:
            h.runner.submit(req)
        assert str(got.value) == text
        # BackendUnavailable is a RuntimeError: the frontend tells them
        # apart by type (503 against 429).
        assert type(got.value) is exc
        assert req.request_id not in h.runner._pending
        assert req.status is RequestStatus.PENDING


def test_a_submit_the_scheduler_refuses_at_the_drain_wakes_its_waiter():
    """Two submits that passed the count together at the last free
    place: the loop's own ``pipeline.submit`` refuses the later one, and
    its waiter is woken with an aborted request."""
    with HeldLoop() as h:
        h.keep_busy()
        h.engine.scheduler.max_queue_size = 1
        h.engine.scheduler.max_batch_size = 1     # the keeper's alone
        h.hold_a_round()
        entries = [(request(f"race{i}"), threading.Event()) for i in range(2)]
        for entry in entries:
            h.runner._post(entry)
        h.release()
        (_, _), (late, woken) = entries
        assert woken.wait(LIMIT_S)
        assert late.status is RequestStatus.FINISHED_ABORT
        assert late.abort_reason == "engine queue full"
        assert "race1" not in h.runner._pending
        assert "race0" in h.runner._pending


# -- (iii) a stop through the inbox -----------------------------------------------


def test_stop_request_returns_beside_a_round_and_ends_the_row_at_the_next():
    with HeldLoop() as h:
        row = request("row", max_tokens=2000)
        h.watched = [row]
        done = h.runner.submit(row)
        wait_for(lambda: len(row.output_ids) >= 8, "the row never decoded")
        held = h.hold_a_round()
        h.runner.stop_request("row")
        # Returned while the round runs; nothing is marked yet.
        assert h.holding.is_set() and h.finished == held - 1
        assert not row.status.is_finished
        h.release()
        assert done.wait(LIMIT_S)
        assert row.status is RequestStatus.FINISHED_STOP
        assert h.done_in["row"] == held + 1
        assert 8 <= len(row.output_ids) < 2000


def test_a_stop_behind_its_own_submit_finds_the_request():
    with HeldLoop() as h:
        h.keep_busy()
        h.hold_a_round()
        req = request("brief", max_tokens=2000)
        done = h.runner.submit(req)
        h.runner.stop_request("brief")
        h.release()
        assert done.wait(LIMIT_S)
        assert req.status is RequestStatus.FINISHED_STOP
        assert len(req.output_ids) == 0


# -- (iv) stop() with entries in the inbox ----------------------------------------


def test_stop_wakes_the_waiters_of_what_the_inbox_still_holds():
    h = HeldLoop()
    h.runner.start()
    keeper = h.keep_busy()
    h.hold_a_round()
    reqs = [request(f"left{i}") for i in range(3)]
    events = [h.runner.submit(r) for r in reqs]
    h.runner.stop_request("left0")
    stopper = threading.Thread(target=h.runner.stop)
    stopper.start()
    wait_for(h.runner._stop.is_set, "stop() never began")
    h.release()
    stopper.join(LIMIT_S)
    assert not stopper.is_alive() and not h.runner._thread.is_alive()
    for req, ev in zip(reqs, events):
        assert ev.is_set()
        assert req.status is RequestStatus.FINISHED_ABORT
        assert req.abort_reason == "server stopped"
    assert not h.runner._inbox
    # The loop's own row keeps what it had: stop() settled its step.
    assert not keeper.status.is_finished and h.runner.failure is None
    assert h.pipeline._pending is None


# -- (v) the series ------------------------------------------------------------


def test_inbox_drained_grows_by_what_each_round_took():
    with HeldLoop() as h:
        h.keep_busy()
        wait_for(lambda: h.finished >= 2, "the loop never stepped")
        h.hold_a_round()
        s0, n0 = drained()
        events = [h.runner.submit(request(f"d{i}")) for i in range(3)]
        h.runner.stop_request("d1")
        h.release()
        for ev in events:
            assert ev.wait(LIMIT_S)
        # Three submits and a stop in one round; rounds that found the
        # inbox empty observed nothing.
        s1, n1 = drained()
        assert (s1 - s0, n1 - n0) == (4.0, 1)
