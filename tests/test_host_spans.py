"""Host spans on the profiler's clock (obs/trace.py ``host_span``), the
per-phase series of a visit, the admission wait, and the profiler
control of the HTTP frontend (``POST /profile/start|stop``).
"""

import asyncio
import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from parallax_tpu.backend.http_server import OpenAIFrontend, SimpleTokenizer
from parallax_tpu.backend.serve import LocalRunner, build_local_frontend
from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.obs import names as mnames
from parallax_tpu.obs import trace as obs_trace
from parallax_tpu.obs.registry import MetricsRegistry, get_registry
from parallax_tpu.obs.trace import TraceStore, host_span, visit_span
from parallax_tpu.runtime.engine import EngineConfig, StageEngine, StepOutputs
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, RequestStatus, SamplingParams

TINY = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"],
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128, vocab_size=258 + 8,
    max_position_embeddings=512,
))

PHASES = (mnames.VISIT_PLAN_MS, mnames.VISIT_PACK_MS,
          mnames.VISIT_READBACK_WAIT_MS, mnames.VISIT_COMMIT_MS)


def build_engine(**cfg_kw):
    m = StageModel(TINY, 0, 2, use_pallas=False)
    return StageEngine(
        m, m.init_params(jax.random.key(0), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=128, max_model_len=256,
                     kv_dtype="float32", **cfg_kw),
    )


def request(rid, max_tokens=24):
    return Request(rid, prompt_ids=[1, 2, 3, 4, 5],
                   sampling_params=SamplingParams(
                       temperature=0.0, max_new_tokens=max_tokens,
                       ignore_eos=True))


def series(name):
    """(sum, count) of a histogram over all of its label sets."""
    snaps = get_registry().histogram_snapshots().get(name) or {}
    return (sum(s["sum"] for s in snaps.values()),
            sum(s["count"] for s in snaps.values()))


def with_client(app, fn):
    async def go():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await fn(client)
        finally:
            await client.close()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(go())
    finally:
        loop.close()


class Recorder:
    """Stands in for jax.profiler's annotation classes: records what was
    entered and left, in order."""

    def __init__(self):
        self.log = []
        outer = self

        class Annotation:
            def __init__(self, name, **args):
                self.name, self.args = name, args

            def __enter__(self):
                outer.log.append(("enter", self.name, self.args))

            def __exit__(self, *exc):
                outer.log.append(("exit", self.name, self.args))

        self.types = (Annotation, Annotation)

    def entered(self):
        return [(name, args) for what, name, args in self.log
                if what == "enter"]


@pytest.fixture
def recorder(monkeypatch):
    rec = Recorder()
    monkeypatch.setattr(obs_trace, "_annotations", rec.types)
    return rec


# -- the helper ----------------------------------------------------------------


def test_host_span_nests_names_and_carries_the_visit(recorder):
    with visit_span(7):
        with host_span("engine.pack", rows=8):
            with host_span("engine.compile", program="decode", visit=3):
                pass
    with host_span("runner.idle"):
        pass
    assert [(w, n) for w, n, _ in recorder.log] == [
        ("enter", "parallax.visit"),
        ("enter", "parallax.engine.pack"),
        ("enter", "parallax.engine.compile"),
        ("exit", "parallax.engine.compile"),
        ("exit", "parallax.engine.pack"),
        ("exit", "parallax.visit"),
        ("enter", "parallax.runner.idle"),
        ("exit", "parallax.runner.idle"),
    ]
    args = dict(recorder.entered())
    assert args["parallax.visit"] == {"step_num": 7}
    assert args["parallax.engine.pack"] == {"rows": 8, "visit": 7}
    # An explicit visit wins; outside a visit none is added.
    assert args["parallax.engine.compile"]["visit"] == 3
    assert args["parallax.runner.idle"] == {}


def test_host_span_observes_its_series_once(recorder):
    h = MetricsRegistry().histogram("t_ms", "t").labels()
    with host_span("engine.commit", h) as span:
        time.sleep(0.01)
    snap = h.snapshot()
    assert snap["count"] == 1
    assert snap["sum"] == span.ms and 9.0 < span.ms < 500.0
    # Withdrawn inside the block: the span still runs, nothing observed.
    with host_span("sched.form_plan", h) as span:
        span.series = None
    assert h.snapshot()["count"] == 1 and span.ms >= 0.0


def test_host_span_with_the_profiler_off_touches_nothing_else(monkeypatch):
    """No profile runs: the real TraceAnnotation is a flag check, the
    TraceStore is never called and only the given series moves."""
    def boom(*a, **k):
        raise AssertionError("TraceStore touched by a host span")

    for name in ("begin", "add", "counter", "adopt"):
        monkeypatch.setattr(TraceStore, name, boom)
    reg = MetricsRegistry()
    h = reg.histogram("only_ms", "the span's series").labels()
    before = get_registry().render()
    with visit_span(1):
        with host_span("engine.pack", h, rows=2):
            pass
    assert h.snapshot()["count"] == 1
    assert list(reg.histogram_snapshots()) == ["only_ms"]

    def samples(text):
        return [line for line in text.splitlines()
                if not line.startswith("parallax_tpu_uptime")]

    assert samples(get_registry().render()) == samples(before)


# -- the engine's spans ----------------------------------------------------------


def test_step_round_spans_in_order_with_one_compile(recorder):
    eng = build_engine()
    pipe = InProcessPipeline([eng])
    pipe.submit(request("spans-a"))
    # A visit of the one-in-flight loop dispatches step N+1, then
    # resolves step N.
    pipe.step_round()          # prefill enqueued (a new program)
    pipe.step_round()          # first decode window (another); prefill read
    pipe.step_round()          # the same window again (none); window 1 read
    pipe.step_round()
    names = [n.removeprefix("parallax.") for n, _ in recorder.entered()]
    per_visit = []
    for n in names:
        if n == "visit":
            per_visit.append([])
        else:
            per_visit[-1].append(n)
    steady = ["sched.form_plan", "engine.pack", "engine.readback_wait",
              "engine.commit"]
    assert per_visit[0] == steady[:2] + ["engine.compile"]
    assert per_visit[1] == steady[:2] + ["engine.compile"] + steady[2:]
    assert per_visit[2] == per_visit[3] == steady
    # Every child carries its visit; pack says what it packed.
    for name, args in recorder.entered():
        if name == "parallax.visit":
            visit = args["step_num"]
        else:
            assert args["visit"] == visit, (name, args)
    packs = [a for n, a in recorder.entered() if n == "parallax.engine.pack"]
    assert packs[0]["rows"] == 1 and packs[0]["tokens"] == 5
    compiles = [a["program"] for n, a in recorder.entered()
                if n == "parallax.engine.compile"]
    assert compiles == ["prefill", "decode_window"]


def test_phase_series_sum_to_step_host_ms():
    eng = build_engine()
    pipe = InProcessPipeline([eng])
    for i in range(3):
        pipe.submit(request(f"sum-{i}", max_tokens=40))
    pipe.run_until_complete()          # compiles included
    before = {n: series(n) for n in PHASES + (mnames.STEP_HOST_MS,)}
    for i in range(3):
        pipe.submit(request(f"sum2-{i}", max_tokens=64))
    pipe.run_until_complete()
    delta = {n: (series(n)[0] - before[n][0], series(n)[1] - before[n][1])
             for n in before}
    host_ms, visits = delta[mnames.STEP_HOST_MS]
    assert visits >= 8
    # An empty plan observes no phase: plan and pack count the visits.
    assert delta[mnames.VISIT_PLAN_MS][1] == visits
    assert delta[mnames.VISIT_PACK_MS][1] == visits
    assert delta[mnames.VISIT_COMMIT_MS][1] == visits
    phases = sum(delta[n][0] for n in PHASES)
    # The spans leave out the few lines between them and take in the
    # finish collection after step_host_ms's end: microseconds a visit.
    assert abs(phases - host_ms) <= 0.05 * host_ms + 0.05 * visits, delta


def test_xplane_holds_the_visit_its_phases_and_the_clock_marker(tmp_path):
    from jax.profiler import ProfileData

    eng = build_engine()
    pipe = InProcessPipeline([eng])
    pipe.submit(request("xplane", max_tokens=40))
    pipe.step_round()
    pipe.step_round()                  # warm: prefill and one window
    jax.profiler.start_trace(str(tmp_path))
    try:
        t_ns = obs_trace.clock_sync()
        pipe.step_round()
        pipe.step_round()
        obs_trace.clock_sync()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    events = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("parallax."):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    names = [e[0] for e in events]
    assert names.count("parallax.visit") == 2
    assert names.count("parallax.clock_sync") == 2
    marks = [e for e in events if e[0] == "parallax.clock_sync"]
    assert int(marks[0][3]["perf_counter_ns"]) == t_ns
    visits = sorted(e for e in events if e[0] == "parallax.visit")
    for _, v0, v1, stats in visits:
        inside = {name: st for name, s, e, st in events
                  if name != "parallax.visit" and v0 <= s and e <= v1}
        assert set(inside) == {
            "parallax.sched.form_plan", "parallax.engine.pack",
            "parallax.engine.readback_wait", "parallax.engine.commit"}
        for st in inside.values():
            assert int(st["visit"]) == int(stats["step_num"])


# -- the step loop and admission ------------------------------------------------


def test_runner_loop_spans_gap_and_submit(recorder):
    eng = build_engine()
    runner = LocalRunner(InProcessPipeline([eng]))
    gap0 = series(mnames.LOOP_GAP_MS)[1]
    runner.start()
    try:
        done = runner.submit(request("loop", max_tokens=20))
        assert done.wait(120.0)
        time.sleep(0.02)               # the loop goes idle
    finally:
        runner.stop()
    names = [n.removeprefix("parallax.") for n, _ in recorder.entered()]
    assert names.count("http.submit") == 1
    rounds = names.count("runner.step_round")
    assert rounds >= 3 and names.count("visit") == rounds
    # One gap after every round, closed before the next round or the
    # idle wait opens; each observed once.
    assert names.count("runner.loop_gap") == rounds
    assert series(mnames.LOOP_GAP_MS)[1] - gap0 == rounds
    assert "runner.idle" in names
    opened = set()
    for what, name, _ in recorder.log:
        if name in ("parallax.runner.step_round", "parallax.runner.idle"):
            assert "parallax.runner.loop_gap" not in opened, recorder.log
        (opened.add if what == "enter" else opened.discard)(name)
    gaps = [a for n, a in recorder.entered() if n.endswith("loop_gap")]
    assert [a["visit"] for a in gaps] == list(range(1, rounds + 1))


def test_runner_loop_packs_the_next_window_before_it_reads_this_one(
        recorder):
    """``serve``'s loop keeps one step in flight: within one visit the
    ``engine.pack`` of window N+1 comes before the
    ``engine.readback_wait`` of window N, all four phases stay inside
    the visit, and every such window counts 1 in
    ``parallax_visit_window_ahead``."""
    eng = build_engine()                      # adaptive K = 8
    runner = LocalRunner(InProcessPipeline([eng]))
    ahead0 = series(mnames.VISIT_WINDOW_AHEAD)
    runner.start()
    try:
        done = runner.submit(request("ahead", max_tokens=81))
        assert done.wait(120.0)
    finally:
        runner.stop()
    assert not eng._inflight and runner.pipeline._pending is None
    visits, inside = [], None
    for what, name, args in recorder.log:
        name = name.removeprefix("parallax.")
        if name == "visit":
            inside = [] if what == "enter" else None
            if what == "enter":
                visits.append((args["step_num"], inside))
        elif what == "enter" and name.split(".")[0] in ("sched", "engine"):
            assert inside is not None, (name, "outside every visit")
            inside.append((name, args))
    phases = ["sched.form_plan", "engine.pack", "engine.readback_wait",
              "engine.commit"]
    windows = 0
    for n, children in visits:
        assert all(a["visit"] == n for _, a in children), (n, children)
        names = [c for c, _ in children if c != "engine.compile"]
        if names == phases:                   # dispatch(N+1), resolve(N)
            pack = dict(children)["engine.pack"]
            windows += pack["rows"] == 1 and pack["tokens"] == 1
    # 80 tokens after the prefill's one: ten windows, the first behind
    # the prefill, nine behind a window each.
    assert windows >= 9, visits
    s1, c1 = series(mnames.VISIT_WINDOW_AHEAD)
    assert (s1 - ahead0[0], c1 - ahead0[1]) == (9.0, 10)


@pytest.mark.parametrize("how", ["stop", "fail"])
def test_runner_leaves_no_ticket_in_flight(how):
    """``stop()`` resolves the step the loop kept in flight (its tokens
    commit); a failing step discards it (its rows abort)."""
    eng = build_engine()
    runner = LocalRunner(InProcessPipeline([eng]))
    req = request("inflight", max_tokens=200)
    if how == "fail":
        real, calls = eng.resolve, []

        def resolve(ticket):
            calls.append(ticket)
            if len(calls) == 4:
                raise RuntimeError("boom")
            return real(ticket)

        eng.resolve = resolve
    runner.start()
    try:
        done = runner.submit(req)
        if how == "stop":
            deadline = time.monotonic() + 120.0
            while len(req.output_ids) < 20 and time.monotonic() < deadline:
                time.sleep(0.001)
        else:
            assert done.wait(120.0)
    finally:
        runner.stop()
    assert not eng._inflight and runner.pipeline._pending is None
    if how == "stop":
        assert 20 <= len(req.output_ids) < 200
        assert not req.status.is_finished and runner.failure is None
        # Nothing was lost: the stream is the uninterrupted one's start.
        whole = request("whole", max_tokens=200)
        pipe = InProcessPipeline([build_engine()])
        pipe.submit(whole)
        pipe.run_until_complete()
        assert req.output_ids == whole.output_ids[:len(req.output_ids)]
    else:
        assert isinstance(runner.failure, RuntimeError)
        assert req.status is RequestStatus.FINISHED_ABORT
        assert req.window_pending == 0


def test_admit_wait_is_observed_at_the_first_plan_of_every_request():
    eng = build_engine()
    pipe = InProcessPipeline([eng])
    s0, n0 = series(mnames.ADMIT_WAIT_MS)
    a, b = request("admit-a"), request("admit-b")
    a.arrival_time -= 0.25             # waited a quarter second already
    pipe.submit(a)
    pipe.submit(b)
    assert eng._unplanned == {"admit-a", "admit-b"}
    pipe.step_round()
    s1, n1 = series(mnames.ADMIT_WAIT_MS)
    assert n1 - n0 == 2 and not eng._unplanned
    assert 250.0 <= s1 - s0 < 5000.0
    pipe.run_until_complete()
    assert series(mnames.ADMIT_WAIT_MS)[1] == n1    # once a request
    # A request that leaves before any plan held it is forgotten.
    c = request("admit-c")
    pipe.submit(c)
    eng.release("admit-c", abort=True)
    assert not eng._unplanned
    assert series(mnames.ADMIT_WAIT_MS)[1] == n1


# -- names ----------------------------------------------------------------------


def test_renamed_series_are_in_metrics_and_the_old_names_are_gone():
    fe, runner = build_local_frontend(
        [build_engine()], SimpleTokenizer(), model_name="tiny")

    async def fn(client):
        resp = await client.post("/v1/completions", json={
            "prompt": "hello", "max_tokens": 12, "temperature": 0})
        assert resp.status == 200, await resp.text()
        resp = await client.get("/metrics")
        status = await (await client.get("/cluster/status_json")).json()
        return await resp.text(), status

    try:
        text, status = with_client(fe.app, fn)
    finally:
        runner.stop()
    for name in PHASES + (mnames.LOOP_GAP_MS, mnames.ADMIT_WAIT_MS):
        assert f"# TYPE {name} histogram" in text, name
        count = sum(float(line.split()[-1]) for line in text.splitlines()
                    if line.startswith(name + "_count"))
        assert count > 0, name
    assert "parallax_program_visit_seconds_total{program=" in text
    for gone in ("parallax_step_device_ms", "parallax_device_time_seconds"):
        assert gone not in text
    timing = status["stages"][0]["step_timing"]
    assert "readback_wait_ms_ewma" in timing and "device_ms_ewma" not in timing
    assert "readback_wait_ms" in StepOutputs.__dataclass_fields__
    assert "device_ms" not in StepOutputs.__dataclass_fields__


# -- the control ------------------------------------------------------------------


class SlowBackend:
    """Emits one token every few ms for as long as asked."""

    def __init__(self, interval_s=0.004):
        self.interval_s = interval_s

    def submit(self, req):
        ev = threading.Event()

        def run():
            for t in range(req.sampling_params.max_new_tokens):
                req.output_ids.append(10 + t % 200)
                time.sleep(self.interval_s)
            req.status = RequestStatus.FINISHED_LENGTH
            ev.set()

        threading.Thread(target=run, daemon=True).start()
        return ev


def stub_profiler(monkeypatch, stop_sleep_s=0.0):
    calls = {"start": 0, "stop": 0, "stop_threads": [], "options": None}

    def start(*a, profiler_options=None, **k):
        calls["start"] += 1
        calls["options"] = profiler_options

    def stop(*a, **k):
        calls["stop_threads"].append(threading.current_thread().name)
        time.sleep(stop_sleep_s)
        calls["stop"] += 1

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop)
    return calls


def test_profile_stop_runs_off_the_event_loop(monkeypatch, tmp_path):
    calls = stub_profiler(monkeypatch, stop_sleep_s=0.6)
    fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=SlowBackend().submit,
                        stream_poll_s=0.002)

    async def fn(client):
        t_before = time.perf_counter_ns()
        resp = await client.post("/profile/start",
                                 json={"dir": str(tmp_path)})
        started = await resp.json()
        assert resp.status == 200 and started["profiling"] is True
        assert t_before < started["perf_counter_ns"] < time.perf_counter_ns()
        stream = await client.post("/v1/completions", json={
            "prompt": "p", "max_tokens": 400, "stream": True})
        assert stream.status == 200
        await stream.content.readline()           # the stream is flowing

        async def stop():
            t0 = time.monotonic()
            r = await client.post("/profile/stop")
            return r.status, await r.json(), t0, time.monotonic()

        stopping = asyncio.ensure_future(stop())
        await asyncio.sleep(0.1)                  # stop_trace is asleep
        assert fe._profiling and fe._profile_stopping
        second = await client.post("/profile/stop")
        restart = await client.post("/profile/start", json={})
        chunk_times = []
        while not stopping.done():
            line = await stream.content.readline()
            if line.startswith(b"data: "):
                chunk_times.append(time.monotonic())
        status, body, t0, t1 = await stopping
        stream.close()
        return second.status, restart.status, status, body, [
            t for t in chunk_times if t0 + 0.1 < t < t1]

    second, restart, status, body, during = with_client(fe.app, fn)
    assert second == 409 and restart == 409    # one stop, and it is not over
    assert status == 200 and body["profiling"] is False
    assert body["stop_seconds"] >= 0.6 and body["perf_counter_ns"] > 0
    assert body["xplane"] is None              # the stub wrote nothing
    assert len(during) >= 20, len(during)      # ~100 chunks in 0.5 s
    assert calls["stop"] == 1
    assert not calls["stop_threads"][0].startswith("MainThread")
    assert fe._profiling is False and fe._profile_stopping is False


def test_profile_autostop_goes_through_the_same_stop(monkeypatch):
    calls = stub_profiler(monkeypatch, stop_sleep_s=0.05)
    fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

    async def fn(client):
        resp = await client.post("/profile/start",
                                 json={"max_seconds": 0.1})
        assert resp.status == 200
        await asyncio.sleep(0.12)
        # The deadline's stop is under way: an explicit one conflicts.
        resp = await client.post("/profile/stop")
        assert resp.status == 409
        await asyncio.sleep(0.3)
        assert fe._profiling is False and calls["stop"] == 1
        assert (await client.post("/profile/stop")).status == 409
        assert (await client.post("/profile/start", json={})).status == 200
        assert (await client.post("/profile/stop")).status == 200

    with_client(fe.app, fn)
    assert (calls["start"], calls["stop"]) == (2, 2)
    assert calls["options"] is None        # the profiler's defaults


def test_request_spans_sets_and_restores_the_trace_rate(monkeypatch):
    stub_profiler(monkeypatch)
    eng = build_engine(trace_sample_rate=0.25)
    fe, runner = build_local_frontend(
        [eng], SimpleTokenizer(), model_name="tiny")

    async def fn(client):
        for bad in (1.5, -0.1, "x"):
            resp = await client.post("/profile/start",
                                     json={"request_spans": bad})
            assert resp.status == 400
        assert eng._trace_rate == 0.25 and fe._profiling is False
        resp = await client.post("/profile/start",
                                 json={"request_spans": 1.0})
        assert resp.status == 200 and eng._trace_rate == 1.0
        resp = await client.post("/v1/completions", json={
            "prompt": "hello", "max_tokens": 4, "temperature": 0})
        rid = (await resp.json())["id"]
        assert (await client.post("/profile/stop")).status == 200
        assert eng._trace_rate == 0.25
        traced = await client.get(f"/debug/trace/{rid}")
        assert traced.status == 200
        names = {e["name"] for e in (await traced.json())["traceEvents"]}
        assert {"queue_wait", "prefill", "decode"} <= names
        # Without the key nothing changes hands.
        assert (await client.post("/profile/start", json={})).status == 200
        assert eng._trace_rate == 0.25
        assert (await client.post("/profile/stop")).status == 200
        assert eng._trace_rate == 0.25

    try:
        with_client(fe.app, fn)
    finally:
        runner.stop()
    # A frontend with no engines behind it refuses the key.
    bare = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

    async def refused(client):
        resp = await client.post("/profile/start",
                                 json={"request_spans": 0.5})
        return resp.status, bare._profiling

    assert with_client(bare.app, refused) == (501, False)


def test_profiler_options_reach_the_profiler(monkeypatch):
    calls = stub_profiler(monkeypatch)
    fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

    async def fn(client):
        resp = await client.post("/profile/start", json={
            "profiler_options": {"python_tracer_level": 0}})
        assert resp.status == 200
        assert calls["options"].python_tracer_level == 0
        assert calls["options"].host_tracer_level >= 1   # TraceMe spans stay
        assert (await client.post("/profile/stop")).status == 200
        for bad in ({"no_such_field": 1}, {"python_tracer_level": "x"},
                    {"__class__": 1}):
            resp = await client.post("/profile/start",
                                     json={"profiler_options": bad})
            assert resp.status == 400 and fe._profiling is False
        resp = await client.post("/profile/start", json={})
        assert resp.status == 200 and calls["options"] is None
        assert (await client.post("/profile/stop")).status == 200

    with_client(fe.app, fn)


def test_profile_stop_reports_the_trace_it_wrote(tmp_path):
    """The real profiler on the CPU: the reply names the .xplane.pb."""
    fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

    async def fn(client):
        resp = await client.post("/profile/start",
                                 json={"dir": str(tmp_path)})
        assert resp.status == 200
        jnp.ones((8, 8)).sum().block_until_ready()
        resp = await client.post("/profile/stop")
        return resp.status, await resp.json()

    status, body = with_client(fe.app, fn)
    assert status == 200, body
    assert body["xplane"].endswith(".xplane.pb")
    assert body["xplane"].startswith(str(tmp_path))
    assert body["stop_seconds"] > 0
    assert json.dumps(body)


# A trace the chip wrote (the benchmark's fixture): its device plane ends
# between its two ``parallax.clock_sync`` marks.
CHIP_TRACE = "benchmarks/fixtures/host_spans_v5e.xplane.pb"
CHIP_TRACE_MARKS = (35553879917, 35585106466)
CHIP_TRACE_DEVICE_END = 35576089416


def test_traced_device_end_is_on_this_process_clock(tmp_path):
    import os

    here = os.path.join(os.path.dirname(os.path.dirname(__file__)), CHIP_TRACE)
    end = obs_trace.traced_device_end_ns(here)
    assert end == CHIP_TRACE_DEVICE_END
    assert CHIP_TRACE_MARKS[0] < end < CHIP_TRACE_MARKS[1]
    # The CPU backend writes no device plane: nothing to lay on the clock.
    jax.profiler.start_trace(str(tmp_path))
    try:
        obs_trace.clock_sync()
        jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    [cpu] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert obs_trace.traced_device_end_ns(cpu) is None


def test_profile_replies_bracket_the_trace(monkeypatch, tmp_path):
    """start's reading is from before ``start_trace`` was called; stop's
    is the end of the trace's last device event where that is later than
    the call of ``stop_trace`` (a device that never idles)."""
    import os
    import shutil

    here = os.path.join(os.path.dirname(os.path.dirname(__file__)), CHIP_TRACE)
    seen = {}

    def start(*a, **k):
        seen["start_called"] = time.perf_counter_ns()

    def stop(*a, **k):
        seen["stop_called"] = time.perf_counter_ns()
        shutil.copy(here, tmp_path / "host.xplane.pb")

    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop)
    fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

    real = obs_trace.traced_device_end_ns
    shift = [0]
    monkeypatch.setattr(obs_trace, "traced_device_end_ns",
                        lambda path: real(path) + shift[0])

    async def once(client):
        resp = await client.post("/profile/start",
                                 json={"dir": str(tmp_path)})
        started = await resp.json()
        resp = await client.post("/profile/stop")
        return started, await resp.json()

    async def fn(client):
        # The trace's device events end long before this process's stop ...
        shift[0] = -CHIP_TRACE_DEVICE_END
        started, stopped = await once(client)
        assert started["perf_counter_ns"] < seen["start_called"]
        assert started["perf_counter_ns"] < stopped["perf_counter_ns"]
        assert stopped["perf_counter_ns"] < seen["stop_called"]
        assert stopped["xplane"] == str(tmp_path / "host.xplane.pb")
        # ... and long after it: the reply follows the device.
        shift[0] = time.perf_counter_ns() + 10**12 - CHIP_TRACE_DEVICE_END
        started, stopped = await once(client)
        assert stopped["perf_counter_ns"] > seen["stop_called"] + 10**11

    with_client(fe.app, fn)
