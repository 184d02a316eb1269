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
    pipe.step_round()          # first decode window (another, behind the
                               # gather of its device-fed row); prefill read
    pipe.step_round()          # the same window again (none); window 1 read
    pipe.step_round()
    names = [n.removeprefix("parallax.") for n, _ in recorder.entered()]
    # A pack that compiled is a slow visit (its own test below): its
    # marker follows the pack's end and is no span of the visit.
    slow = [a for n, a in recorder.entered() if n == "parallax.slow_visit"]
    assert [(a["phase"], a["visit"]) for a in slow
            if a.get("compile", 0) > 0] == [
        ("engine.pack", 1), ("engine.pack", 2)]
    names = [n for n in names if n != "slow_visit"]
    per_visit = []
    for n in names:
        if n == "visit":
            per_visit.append([])
        else:
            per_visit[-1].append(n)
    steady = ["sched.form_plan", "engine.pack", "engine.readback_wait",
              "engine.commit"]
    assert per_visit[0] == steady[:2] + ["engine.compile"]
    assert per_visit[1] == steady[:2] + ["engine.compile"] * 2 + steady[2:]
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
    assert compiles == ["prefill", "feed_gather", "decode_window"]


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
        # (A pack the profiler slowed past its baseline leaves a marker.)
        inside.pop("parallax.slow_visit", None)
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


# -- what disturbed a visit: the slow-visit ledger --------------------------------


class ScriptedSpan:
    """A finished span as ``SlowVisits.close`` reads it."""

    def __init__(self, name, ms, kind="decode_window", cpu_bound=True, **args):
        self.name, self.ms, self.kind, self.args = name, ms, kind, args
        self._cpu_bound = cpu_bound
        self._marks = (0.0, 0.0, 0.0, 0.0)
        self.perf_counter_ns = 123


def slow_counts(reg):
    """{(phase, cause): ms} and {phase: visits} of a registry's render."""
    ms, visits = {}, {}
    for line in reg.render().splitlines():
        if line.startswith(mnames.SLOW_VISIT_EXCESS_MS_TOTAL + "{"):
            labels, value = line.split("{")[1].split("} ")
            pairs = dict(p.split("=") for p in labels.split(","))
            ms[pairs["phase"].strip('"'), pairs["cause"].strip('"')] = (
                float(value))
        elif line.startswith(mnames.SLOW_VISITS_TOTAL + "{"):
            labels, value = line.split("{")[1].split("} ")
            visits[labels.split("=")[1].strip('"')] = float(value)
    return ms, visits


@pytest.fixture
def ledger(monkeypatch, recorder):
    """A ledger of its own on a registry of its own, its clocks
    scripted: ``clocks(cpu_s=, compile_s=, trace_s=, gc_s=)`` is what
    the next span's exit reads (its entry read zeros)."""
    reg = MetricsRegistry()
    led = obs_trace.SlowVisits()
    led.bind_registry(reg)
    marks = [(0.0, 0.0, 0.0, 0.0)]
    monkeypatch.setattr(obs_trace, "_clock_marks",
                        lambda cpu_bound: marks[0])

    def clocks(first=0.0, compile_s=0.0, trace_s=0.0, gc_s=0.0):
        marks[0] = (first, compile_s, trace_s, gc_s)

    led.reg, led.clocks = reg, clocks
    return led


def warm(ledger, name, ms, kind="decode_window", cpu_bound=True):
    for _ in range(obs_trace.SLOW_WARM_SPANS):
        # On the CPU all of it: no off-CPU time in the baseline.
        ledger.clocks(first=ms / 1e3 if cpu_bound else 0.0)
        ledger.close(ScriptedSpan(name, ms, kind, cpu_bound))
    assert ledger.baseline_ms(name, kind) == pytest.approx(ms)


def test_both_families_and_the_totals_read_zero_before_any_visit():
    reg = MetricsRegistry()
    obs_trace.SlowVisits().bind_registry(reg)
    obs_trace.HostPauseMeter(registry=reg)
    text = reg.render()
    ms, visits = slow_counts(reg)
    assert set(visits) == set(obs_trace.SLOW_PHASES)
    assert set(visits.values()) == {0.0}
    assert {p for p, _ in ms} == set(obs_trace.SLOW_PHASES)
    assert {c for p, c in ms if p == "engine.pack"} == set(
        obs_trace.CPU_CAUSES)
    assert {c for p, c in ms if p == "engine.readback_wait"} == set(
        obs_trace.WAIT_CAUSES)
    assert set(ms.values()) == {0.0}
    for total in (mnames.LOOP_OFFCPU_MS_TOTAL, mnames.HOST_PAUSE_MS_TOTAL,
                  mnames.HOST_PAUSES_TOTAL, mnames.JIT_TRACE_MS_TOTAL):
        assert f"\n{total} 0\n" in text, total
    # The process's own registry has them from an engine's start.
    build_engine()
    live = get_registry().render()
    for name in (mnames.SLOW_VISITS_TOTAL, mnames.SLOW_VISIT_EXCESS_MS_TOTAL,
                 mnames.LOOP_OFFCPU_MS_TOTAL, mnames.JIT_TRACE_MS_TOTAL,
                 mnames.WINDOW_NOT_AHEAD_TOTAL):
        assert f"\n{name}" in live, name


def test_a_span_over_its_baseline_is_counted_once_and_one_under_is_not(
        ledger, recorder):
    warm(ledger, "engine.pack", 9.0)
    # Under max(floor, factor x baseline) = 13.5: no slow visit.
    ledger.clocks(first=0.013)
    ledger.close(ScriptedSpan("engine.pack", 13.0))
    assert slow_counts(ledger.reg)[1]["engine.pack"] == 0
    base = ledger.baseline_ms("engine.pack", "decode_window")
    assert 9.0 < base < 9.5                  # it follows, a sixteenth a span
    # Over it: one visit, its excess over the baseline, all of it Python
    # (the thread was on the CPU throughout).
    ledger.clocks(first=0.109)
    ledger.close(ScriptedSpan("engine.pack", 109.0, rows=8, tokens=8,
                              visit=41))
    ms, visits = slow_counts(ledger.reg)
    assert visits == {**dict.fromkeys(obs_trace.SLOW_PHASES, 0.0),
                      "engine.pack": 1.0}
    assert ms["engine.pack", "python"] == pytest.approx(109.0 - base)
    assert sum(ms.values()) == pytest.approx(109.0 - base)
    # The stall did not become the baseline: as far as its limit only.
    limit = obs_trace.SLOW_FACTOR * base
    assert ledger.baseline_ms("engine.pack", "decode_window") == (
        pytest.approx(base + (limit - base) / 16))
    # A floor under small baselines: 8 ms of loop gap where 0.004 is
    # normal is no slow visit, 12 ms is.
    warm(ledger, "runner.loop_gap", 0.004, kind="")
    ledger.clocks(first=0.008)
    ledger.close(ScriptedSpan("runner.loop_gap", 8.0, kind=""))
    ledger.clocks(first=0.012)
    ledger.close(ScriptedSpan("runner.loop_gap", 12.0, kind=""))
    assert slow_counts(ledger.reg)[1]["runner.loop_gap"] == 1
    # A kind whose spans spread by structure (a prefill step's wait is
    # ~5 ms or ~100 as a window was queued ahead of it or not) is not
    # slow half the time: the limit stands four mean deviations over
    # the baseline too, and learns them within some tens of spans.
    kind = "prefill/512"
    for i in range(120):
        ms = 100.0 if i % 3 == 0 else 5.0
        ledger.close(ScriptedSpan("engine.readback_wait", ms, kind,
                                  cpu_bound=False))
        if i == 59:
            early = slow_counts(ledger.reg)[1]["engine.readback_wait"]
    late = slow_counts(ledger.reg)[1]["engine.readback_wait"] - early
    assert early <= 20 and late == 0, (early, late)
    # ... while a stall far outside the spread still is.
    ledger.close(ScriptedSpan("engine.readback_wait", 900.0, kind,
                              cpu_bound=False))
    assert slow_counts(ledger.reg)[1]["engine.readback_wait"] == early + 1
    # Another program has another baseline: a prefill chunk's pack of
    # 40 ms beside decode windows of 9 is its own normal.
    warm(ledger, "engine.pack", 40.0, kind="prefill/1024")
    ledger.clocks(first=0.045)
    ledger.close(ScriptedSpan("engine.pack", 45.0, kind="prefill/1024"))
    assert slow_counts(ledger.reg)[1]["engine.pack"] == 1
    # One record a slow visit: the marker on the trace and the flight
    # ring's event say the same.
    marks = [a for n, a in recorder.entered() if n == "parallax.slow_visit"
             and a["phase"] != "engine.readback_wait"]
    assert [m["phase"] for m in marks] == ["engine.pack", "runner.loop_gap"]
    rec = marks[0]
    assert (rec["visit"], rec["rows"], rec["tokens"], rec["program"]) == (
        41, 8, 8, "decode_window")
    assert rec["perf_counter_ns"] == 123
    assert rec["ms"] == 109.0 and rec["baseline_ms"] == round(base, 3)
    from parallax_tpu.obs.flight import get_flight

    (event,) = [e for e in get_flight().snapshot()["events"]
                if e["kind"] == "slow_visit" and e["visit"] == 41
                and e["perf_counter_ns"] == 123]
    assert {k: event[k] for k in rec} == rec


@pytest.mark.parametrize("name, cpu_bound, clocks, want", [
    # 100 ms over a 10 ms pack: 30 compiling, 20 tracing, 10 collecting,
    # 25 off the CPU (110 of wall, 85 of CPU, 60 of them in the three
    # before), the rest Python.
    ("engine.pack", True,
     dict(first=0.085, compile_s=0.030, trace_s=0.020, gc_s=0.010),
     {"compile": 30.0, "trace": 20.0, "gc": 10.0, "off_cpu": 25.0,
      "python": 15.0}),
    # Clocks that say more than the excess holds (nested traces count
    # twice): each cause takes what is left, at most.
    ("engine.commit", True,
     dict(first=0.110, compile_s=0.070, trace_s=0.090),
     {"compile": 70.0, "trace": 30.0, "gc": 0.0, "off_cpu": 0.0,
      "python": 0.0}),
    # The read-back wait is off the CPU by design: a pause of the
    # machine is what the meter counted meanwhile, the rest the device.
    ("engine.readback_wait", False,
     dict(first=80.0, gc_s=0.005),
     {"gc": 5.0, "paused": 80.0, "device": 15.0}),
])
def test_the_causes_of_a_slow_visit_sum_to_its_excess(
        ledger, recorder, name, cpu_bound, clocks, want):
    warm(ledger, name, 10.0, cpu_bound=cpu_bound)
    ledger.clocks(**clocks)
    ledger.close(ScriptedSpan(name, 110.0, cpu_bound=cpu_bound))
    ms, visits = slow_counts(ledger.reg)
    got = {c: v for (p, c), v in ms.items() if p == name}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(100.0)
    assert visits[name] == 1
    (rec,) = [a for n, a in recorder.entered() if n == "parallax.slow_visit"]
    assert sum(rec[c] for c in want) == pytest.approx(rec["excess_ms"])


def test_a_new_programs_first_span_is_slow_by_its_compile_only(ledger):
    """No baseline yet: what a known cause took is the excess, and the
    baseline starts from the rest."""
    ledger.clocks(first=5.2, compile_s=4.0, trace_s=1.0)
    ledger.close(ScriptedSpan("engine.pack", 5230.0, kind="prefill/512"))
    ms, visits = slow_counts(ledger.reg)
    assert visits["engine.pack"] == 1
    assert ms["engine.pack", "compile"] == pytest.approx(4000.0)
    assert ms["engine.pack", "trace"] == pytest.approx(1000.0)
    assert sum(ms.values()) == pytest.approx(5000.0)
    # A build's span says nothing of the normal one: the spans after
    # it, slower or faster, settle the baseline unseen.
    assert ledger.baseline_ms("engine.pack", "prefill/512") is None
    for ms in (25.0, 31.0, 22.0):
        ledger.clocks(first=ms / 1e3)
        ledger.close(ScriptedSpan("engine.pack", ms, kind="prefill/512"))
    assert slow_counts(ledger.reg)[1]["engine.pack"] == 1
    assert ledger.baseline_ms("engine.pack", "prefill/512") == 22.0


def test_the_loops_off_cpu_time_grows_over_every_cpu_bound_span(ledger):
    for ms, cpu_s in ((9.0, 0.007), (9.0, 0.008), (0.3, 0.0003)):
        ledger.clocks(first=cpu_s)
        ledger.close(ScriptedSpan("engine.pack", ms))
    ledger.clocks(first=0.0)
    ledger.close(ScriptedSpan("engine.readback_wait", 60.0, cpu_bound=False))
    text = ledger.reg.render()
    (line,) = [x for x in text.splitlines()
               if x.startswith(mnames.LOOP_OFFCPU_MS_TOTAL + " ")]
    assert float(line.split()[1]) == pytest.approx(2.0 + 1.0 + 0.0)


# -- ... injected into a toy engine's pack -------------------------------------


def burn(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def collect_garbage():
    """Garbage made while the collector is off, and then one pass: both
    inside the pack, so that nobody else's pass finds it first."""
    import gc

    gc.disable()
    try:
        junk = [[i] for i in range(400_000)]
        for a, b in zip(junk, junk[1:]):
            a.append(b)                 # cycles: the collector's to find
        del junk, a, b
        t0 = time.perf_counter()
        gc.collect()
        collect_garbage.ms = (time.perf_counter() - t0) * 1e3
    finally:
        gc.enable()


def compile_something_new():
    jax.jit(lambda x: jnp.tanh(x) * 3 + 1)(jnp.arange(7.0))


@pytest.mark.parametrize("inject, causes", [
    (lambda: time.sleep(0.08), ("off_cpu",)),
    (lambda: burn(0.08), ("python",)),
    (collect_garbage, ("gc",)),
    (compile_something_new, ("trace", "compile")),
], ids=["sleep", "busy-loop", "gc-collect", "jit-on-a-new-key"])
def test_a_stall_injected_into_pack_reads_its_cause(monkeypatch, inject,
                                                    causes):
    from parallax_tpu.obs.flight import get_flight

    # The ledger is the process's: what other tests' engines taught it
    # of a toy window's pack is not this engine's normal.
    obs_trace.get_slow_visits()._base.clear()
    eng = build_engine()
    pipe = InProcessPipeline([eng])
    pipe.submit(request("stalled", max_tokens=240))
    for _ in range(obs_trace.SLOW_WARM_SPANS + 18):
        # The window's baseline is warm, and its deviation has come
        # down from the first spans' range to this machine's own.
        pipe.step_round()
    ledger = obs_trace.get_slow_visits()
    base = ledger.baseline_ms("engine.pack", "decode_window")
    assert base is not None and base < obs_trace.SLOW_FLOOR_MS
    real = eng._dispatch_plan

    def stalled(*a, **kw):
        monkeypatch.setattr(eng, "_dispatch_plan", real)
        t0 = time.perf_counter()
        inject()
        stalled.ms = (time.perf_counter() - t0) * 1e3
        return real(*a, **kw)

    monkeypatch.setattr(eng, "_dispatch_plan", stalled)
    seq0 = get_flight().snapshot()["events"][-1]["seq"]
    pipe.step_round()
    pipe.step_round()
    slow = [e for e in get_flight().snapshot()["events"]
            if e["kind"] == "slow_visit" and e["seq"] > seq0]
    (rec,) = [e for e in slow if e["phase"] == "engine.pack"]
    assert rec["program"] == "decode_window" and rec["rows"] == 1
    assert rec["visit"] == pipe.visits - 1
    # The slow visit reads the stall within a tenth of its length (and
    # what a busy machine added to the rest of the pack) ...
    assert stalled.ms > obs_trace.SLOW_FLOOR_MS
    assert 0.9 * stalled.ms - 3.0 <= rec["excess_ms"] <= (
        1.1 * stalled.ms + 30.0), (stalled.ms, rec)
    # ... its causes sum to it, and the cause injected reads what was
    # injected: 80 ms off the CPU, 80 ms of this thread's CPU time (a
    # busy machine's share of the wall is off the CPU, rightly), the
    # collector's pass, the trace and the compile.
    split = {c: rec[c] for c in obs_trace.CPU_CAUSES}
    assert sum(split.values()) == pytest.approx(rec["excess_ms"], abs=0.01)
    if causes == ("gc",):
        assert split["gc"] >= 0.8 * collect_garbage.ms, split
    elif causes == ("trace", "compile"):
        assert split["trace"] > 0 and split["compile"] > 0
        assert split["trace"] + split["compile"] >= 0.5 * rec["excess_ms"]
    else:
        assert split[causes[0]] >= 0.8 * 80.0, split


# -- the pause meter --------------------------------------------------------------


def test_the_pause_meter_counts_only_what_is_over_its_threshold(recorder):
    class Clock:
        t = 100.0

        def __call__(self):
            return self.t

    clock, reg = Clock(), MetricsRegistry()
    oversleeps = iter([0.000, 0.006, 0.019, 0.120, 0.0005, 0.520])

    def sleep(interval):
        clock.t += interval + next(oversleeps)

    meter = obs_trace.HostPauseMeter(clock=clock, sleep=sleep, registry=reg)
    for _ in range(3):
        meter.tick()
    # GIL hand-over noise: under the threshold.
    assert meter.read() == 0.0
    assert f"\n{mnames.HOST_PAUSES_TOTAL} 0\n" in reg.render()
    meter.tick()                        # 120 ms late: 100 over
    meter.tick()
    meter.tick()                        # 520 ms late: 500 over
    assert meter.read() == pytest.approx(600.0)
    text = reg.render()
    assert f"\n{mnames.HOST_PAUSES_TOTAL} 2\n" in text
    (line,) = [x for x in text.splitlines()
               if x.startswith(mnames.HOST_PAUSE_MS_TOTAL + " ")]
    assert float(line.split()[1]) == pytest.approx(600.0)
    marks = [a["ms"] for n, a in recorder.entered()
             if n == "parallax.host_pause"]
    assert marks == pytest.approx([100.0, 500.0])


def test_the_meter_reads_a_pause_before_its_thread_has_woken():
    """A span that ends while the meter still sleeps through the same
    pause reads it: ``read`` counts the sleep in progress."""
    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()
    seen = []

    def sleep(interval):
        clock.t += interval + 0.3      # the machine stood still 300 ms
        seen.append(meter.read())       # ... and a span ends, now

    meter = obs_trace.HostPauseMeter(clock=clock, sleep=sleep,
                                     registry=MetricsRegistry())
    meter.tick()
    assert seen == [pytest.approx(280.0)]
    assert meter.read() == pytest.approx(280.0)     # counted once


def test_the_meters_thread_lives_and_ends_with_the_runner():
    def meters():
        return [t for t in threading.enumerate()
                if t.name == "host-pause-meter"]

    before = len(meters())
    runner = LocalRunner(InProcessPipeline([build_engine()]))
    assert len(meters()) == before and obs_trace._pause_meter is None
    runner.start()
    try:
        assert len(meters()) == before + 1
        assert obs_trace._pause_meter is runner.pause_meter
        done = runner.submit(request("metered", max_tokens=8))
        assert done.wait(120.0)
    finally:
        runner.stop()
    assert len(meters()) == before and obs_trace._pause_meter is None


def test_the_endpoints_say_what_disturbed_the_loop():
    """Single-host serve: ``/metrics`` has every miss's reason and the
    pause meter's two counters, and ``/cluster/status_json`` and
    ``/debug/device`` the key of a real compile beside JAX's own name
    for the function built."""
    engine = build_engine()
    # The registry is the process's, and an earlier test file on this
    # worker may have left series under this stage label or another:
    # hold this engine's own series, by what it adds.
    before = engine._c_not_ahead["no_window_in_flight"].value
    fe, runner = build_local_frontend(
        [engine], SimpleTokenizer(), model_name="tiny")

    async def fn(client):
        resp = await client.post("/v1/completions", json={
            "prompt": "hello", "max_tokens": 40, "temperature": 0})
        assert resp.status == 200, await resp.text()
        status = await (await client.get("/cluster/status_json")).json()
        device = await (await client.get("/debug/device")).json()
        scrape = await (await client.get("/metrics")).text()
        return status, device, scrape

    try:
        status, device, scrape = with_client(fe.app, fn)
    finally:
        runner.stop()
    series = {}
    for line in scrape.splitlines():
        if line.startswith("parallax_"):
            name, value = line.rsplit(" ", 1)
            series[name] = float(value)
    assert series[mnames.HOST_PAUSES_TOTAL] >= 0
    assert series[mnames.HOST_PAUSE_MS_TOTAL] >= 0
    assert series[mnames.JIT_TRACE_MS_TOTAL] > 0
    misses = {k: v for k, v in series.items()
              if k.startswith(mnames.WINDOW_NOT_AHEAD_TOTAL + "{")}
    (first,) = [v for k, v in misses.items()
                if 'reason="no_window_in_flight"' in k
                and f'stage="{engine._obs_stage}"' in k]
    assert first - before >= 1
    for payload in (status["device"], device):
        recent = payload["compile"]["recent"]
        assert 0 < len(recent) <= 16
        for r in recent:
            assert {"program", "cause", "key", "fun", "compile_ms",
                    "trace_ms", "block_traces", "visit",
                    "perf_counter_ns", "cache_hit"} == set(r)
            assert r["fun"]
        (window,) = [r for r in recent if r["program"] == "decode_window"][-1:]
        assert window["key"]["k"] >= 1 and "seq" in window["key"]


def test_the_feed_gather_of_a_step_is_a_program_with_a_name():
    """A one-step decode over device-fed rows swaps their token ids in
    by a small jitted gather of its own, one program a token bucket:
    the build nobody had declared inside serve's windows (PERF.md,
    PR 44). It is noted under ``feed_gather`` with its bucket."""
    eng = build_engine(decode_lookahead=1)
    runner = LocalRunner(InProcessPipeline([eng]))
    runner.start()
    try:
        done = [runner.submit(request(f"fed-{i}", max_tokens=12))
                for i in range(2)]
        assert all(d.wait(120.0) for d in done)
    finally:
        runner.stop()
    noted = [dict(key) for family, key in eng._noted_program_keys
             if family == "feed_gather"]
    assert noted and all(set(k) == {"tokens"} for k in noted)
