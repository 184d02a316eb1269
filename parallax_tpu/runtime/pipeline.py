"""In-process pipeline driver: chains StageEngines by direct calls.

This is the loopback-transport execution mode — the same engine code that
runs under the networked P2P daemon, wired stage-to-stage in one process.
Used by tests (the reference tests multi-stage the same way,
``tests/test_executor.py``) and by single-host multi-stage debugging.

``wire=True`` routes every inter-stage packet through the real wire
format (msgpack frame encode/decode + tensor serialization from
``p2p/proto.py``, optionally at a compressed ``wire_dtype``) — the
in-process twin of the networked hop, used by the exactness tests that
pin multi-stage streams bit-identical to the direct-call path.
"""

from __future__ import annotations

from parallax_tpu.obs.trace import visit_span
from parallax_tpu.runtime.engine import StageEngine, StepTicket, drive_step
from parallax_tpu.runtime.request import Request


class InProcessPipeline:
    """Ring of engines: stage0 (head) -> ... -> stageN-1 -> head."""

    def __init__(
        self,
        engines: list[StageEngine],
        wire: bool = False,
        wire_dtype: str | None = None,
    ):
        assert engines and engines[0].model.is_first and engines[-1].model.is_last
        self.engines = engines
        self.wire = wire or wire_dtype is not None
        self.wire_dtype = wire_dtype
        self.finished: list[Request] = []
        # Step rounds so far: the number of the ``parallax.visit`` span.
        self.visits = 0
        # A single full stage steps through the one-in-flight loop
        # (``drive_step``): the ticket a round left unresolved, which the
        # next round resolves after it has dispatched its own.
        self._pending: StepTicket | None = None

    @property
    def head(self) -> StageEngine:
        return self.engines[0]

    def submit(self, request: Request) -> bool:
        return self.head.submit(request)

    def has_work(self) -> bool:
        return self._pending is not None or any(
            e.has_work() for e in self.engines
        )

    def settle(self, discard: bool = False) -> list[Request]:
        """Leave no ticket in flight: resolve the one the last round
        left (its tokens commit; the finished requests are returned), or
        ``discard`` it where a failed step makes a resolve meaningless
        (its rows are aborted)."""
        ticket, self._pending = self._pending, None
        if ticket is None:
            return []
        head = self.head
        if discard or not head.is_inflight(ticket):
            head.discard(ticket)
            return []
        finished = head.resolve(ticket).finished
        self.finished.extend(finished)
        return finished

    def _wire_roundtrip(self, ireq):
        """One packet through the full wire path: serialize (with the
        configured wire dtype), msgpack-frame, decode, deserialize.
        Traced packets record the hop as a ``transport`` span — the
        in-process twin of the networked send/recv pair."""
        import time

        from parallax_tpu.p2p import proto

        t0 = time.perf_counter()
        frame = proto.encode_frame(
            proto.FORWARD,
            {"reqs": [proto.ireq_to_wire(ireq, wire_dtype=self.wire_dtype)]},
        )
        out = proto.ireq_from_wire(
            proto.decode_frame(frame)["p"]["reqs"][0]
        )
        if ireq.trace:
            from parallax_tpu.obs.trace import get_trace_store

            get_trace_store().add(
                ireq.request_id, "wire", "transport",
                t0=t0, dur=time.perf_counter() - t0,
                args={"bytes": len(frame)}, merge=True,
            )
        return out

    def step_round(self) -> list[Request]:
        """One step of every stage, routing packets around the ring:
        one visit (``parallax.visit``; the engines' spans carry its
        number)."""
        self.visits += 1
        with visit_span(self.visits):
            newly_finished = self._step_stages()
        self.finished.extend(newly_finished)
        return newly_finished

    def _step_stages(self) -> list[Request]:
        if len(self.engines) == 1:
            # One visit = dispatch(N+1), then resolve(N): the host forms,
            # packs and enqueues the next step while the device computes
            # this one. A ring of several in-process engines steps each
            # synchronously (a stage's input is the stage before's output
            # of the same round).
            outs, self._pending = drive_step(self.head, self._pending)
            return self._route(0, outs)
        newly_finished: list[Request] = []
        for i, engine in enumerate(self.engines):
            newly_finished += self._route(i, [engine.step()])
        return newly_finished

    def _route(self, i: int, outs) -> list[Request]:
        """Send stage ``i``'s packets on around the ring and release its
        finished requests on the other stages."""
        engine = self.engines[i]
        newly_finished: list[Request] = []
        for out in outs:
            for ireq in out.forward:
                if self.wire:
                    ireq = self._wire_roundtrip(ireq)
                if ireq.next_token_id is not None:
                    self.head.commit_token(
                        ireq.request_id, ireq.next_token_id,
                        ireq.token_logprob,
                    )
                elif ireq.spec_accepted is not None:
                    self.head.commit_spec_result(
                        ireq.request_id, ireq.spec_accepted
                    )
                else:
                    self.engines[i + 1].submit_intermediate(ireq)
            for req in out.finished:
                newly_finished.append(req)
                aborted = req.status.value == "finished_abort"
                for other in self.engines:
                    if other is not engine:
                        other.release(req.request_id, abort=aborted)
        return newly_finished

    def run_until_complete(self, max_rounds: int = 10000) -> list[Request]:
        for _ in range(max_rounds):
            if not self.has_work():
                break
            self.step_round()
        return self.finished
