"""Observability layer tests: metrics registry exposition, request-
lifecycle trace stitching across pipeline stages, the flight recorder's
slow-request capture, the tracing-off overhead guard, and the HTTP
surfaces (/metrics, /debug/trace, /debug/flight, hardened status stream,
profiler auto-stop deadline).
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from parallax_tpu.backend.http_server import OpenAIFrontend, SimpleTokenizer
from parallax_tpu.backend.serve import build_local_frontend
from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.obs.flight import get_flight
from parallax_tpu.obs.registry import (
    EXPOSITION_CONTENT_TYPE,
    MetricsRegistry,
    merge_histogram_snapshots,
    snapshot_quantile,
    summarize_snapshots,
)
from parallax_tpu.obs.trace import TraceStore, get_trace_store
from parallax_tpu.runtime.engine import EngineConfig, StageEngine, drive_step
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

TINY = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"],
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128, vocab_size=258 + 8,
    max_position_embeddings=512,
))


def build_engines(bounds, **cfg_kw):
    engines = []
    for s, e in bounds:
        m = StageModel(TINY, s, e, use_pallas=False)
        engines.append(StageEngine(
            m, m.init_params(jax.random.key(0), dtype=jnp.float32),
            EngineConfig(page_size=8, num_pages=128, max_model_len=256,
                         kv_dtype="float32", **cfg_kw),
        ))
    return engines


def run_pipeline(pipe, rid, max_tokens=12, prompt=(1, 2, 3, 4, 5)):
    req = Request(rid, prompt_ids=list(prompt),
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=max_tokens))
    pipe.submit(req)
    pipe.run_until_complete()
    assert req.status.is_finished
    return req


def with_client(app, fn):
    async def go():
        server = TestServer(app)
        client = TestClient(server)
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


# -- registry exposition (golden) -------------------------------------------


def test_exposition_help_type_and_label_escaping():
    reg = MetricsRegistry()
    c = reg.counter("obs_requests_total", "Requests accepted")
    c.inc(3)
    g = reg.gauge("obs_depth", "Queue depth", labelnames=("stage",))
    g.labels(stage='a"b\\c\nd').set(7)
    h = reg.histogram("obs_lat_ms", "Latency", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    text = reg.render()
    lines = text.splitlines()

    assert "# HELP obs_requests_total Requests accepted" in lines
    assert "# TYPE obs_requests_total counter" in lines
    assert "obs_requests_total 3" in lines
    assert "# TYPE obs_depth gauge" in lines
    # Label escaping: backslash, quote, newline.
    assert 'obs_depth{stage="a\\"b\\\\c\\nd"} 7' in lines
    assert "# TYPE obs_lat_ms histogram" in lines
    # HELP/TYPE come before samples, once per family.
    assert text.count("# TYPE obs_lat_ms histogram") == 1
    # Histogram exposition: cumulative buckets, +Inf, sum, count.
    assert 'obs_lat_ms_bucket{le="1"} 1' in lines
    assert 'obs_lat_ms_bucket{le="10"} 2' in lines
    assert 'obs_lat_ms_bucket{le="100"} 3' in lines
    assert 'obs_lat_ms_bucket{le="+Inf"} 4' in lines
    assert "obs_lat_ms_count 4" in lines
    assert any(line.startswith("obs_lat_ms_sum ") for line in lines)


def test_histogram_bucket_monotonicity_and_inf_equals_count():
    reg = MetricsRegistry()
    h = reg.histogram("obs_mono_ms", "m")
    import random as _r

    rng = _r.Random(7)
    for _ in range(500):
        h.observe(rng.uniform(0.01, 200_000.0))
    cums = []
    for line in reg.render().splitlines():
        if line.startswith("obs_mono_ms_bucket"):
            cums.append(int(line.rsplit(" ", 1)[1]))
    assert cums == sorted(cums), "bucket counts must be cumulative"
    assert cums[-1] == 500  # +Inf bucket equals _count


def test_registry_get_or_create_and_type_collision():
    reg = MetricsRegistry()
    a = reg.counter("obs_x_total", "x")
    b = reg.counter("obs_x_total", "x")
    assert a is b
    with pytest.raises(ValueError):
        reg.gauge("obs_x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("obs_x_total", "x", labelnames=("other",))


def test_snapshot_merge_and_percentiles():
    reg1, reg2 = MetricsRegistry(), MetricsRegistry()
    for reg, vals in ((reg1, [1.0] * 50), (reg2, [1000.0] * 50)):
        h = reg.histogram("obs_merge_ms", "m")
        for v in vals:
            h.observe(v)
    merged = merge_histogram_snapshots([
        reg1.histogram_snapshots(), reg2.histogram_snapshots(),
    ])
    snap = merged["obs_merge_ms"][""]
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(50 * 1.0 + 50 * 1000.0)
    p50 = snapshot_quantile(snap, 0.5)
    p99 = snapshot_quantile(snap, 0.99)
    assert p50 < 10.0 < 500.0 < p99
    summary = summarize_snapshots(merged)["obs_merge_ms"][""]
    assert summary["count"] == 100
    assert set(summary) >= {"p50", "p95", "p99", "sum", "count"}


# -- trace stitching ---------------------------------------------------------


def test_two_stage_wire_trace_stitching():
    """A two-stage wire-mode pipeline request yields ONE trace: spans from
    both stages plus the transport hop, decode steps coalesced into
    epochs, exported as Chrome trace-event JSON."""
    engines = build_engines([(0, 1), (1, 2)], trace_sample_rate=1.0)
    pipe = InProcessPipeline(engines, wire=True)
    req = run_pipeline(pipe, "trace-stitch", max_tokens=16)

    store = get_trace_store()
    spans = store.spans("trace-stitch")
    assert spans, "sampled request recorded no spans"
    stages = {s["stage"] for s in spans}
    assert {"0-1", "1-2", "wire"} <= stages, stages
    names_by_stage = {
        st: [s["name"] for s in spans if s["stage"] == st] for st in stages
    }
    for st in ("0-1", "1-2"):
        assert "prefill" in names_by_stage[st]
        assert "decode" in names_by_stage[st]
    assert "transport" in names_by_stage["wire"]
    # Decode epochs: 16 tokens collapse into merged epoch spans, not one
    # span per step.
    decodes = [s for s in spans if s["name"] == "decode"]
    assert decodes and len(decodes) <= 4
    assert any(s.get("args", {}).get("steps", 1) > 4 for s in decodes)
    # Monotonic span ordering within each stage lane.
    for st in stages:
        ts = [s["t0"] for s in spans if s["stage"] == st]
        assert ts == sorted(ts)
    # The head's queue_wait starts no later than its prefill.
    head = [s for s in spans if s["stage"] == "0-1"]
    qw = next(s for s in head if s["name"] == "queue_wait")
    pf = next(s for s in head if s["name"] == "prefill")
    assert qw["t0"] <= pf["t0"]

    chrome = store.export_chrome("trace-stitch")
    assert chrome["metadata"]["trace_id"] == "trace-stitch"
    events = chrome["traceEvents"]
    # Span lanes export as complete ("X") events one-for-one; the device
    # attribution plane adds counter ("C") tracks alongside them.
    span_events = [e for e in events if e["ph"] == "X"]
    counter_events = [e for e in events if e["ph"] == "C"]
    assert len(span_events) == len(spans)
    assert len(span_events) + len(counter_events) == len(events)
    assert counter_events, "traced visit recorded no device counters"
    assert all(
        "hbm_headroom_mb" in e["args"] for e in counter_events
    )
    assert {e["tid"] for e in span_events} == stages
    assert min(e["ts"] for e in events) == 0.0
    assert req.output_ids  # the traced run actually generated


def test_trace_flag_survives_wire_roundtrip():
    from parallax_tpu.p2p import proto
    from parallax_tpu.runtime.request import IntermediateRequest

    ireq = IntermediateRequest(
        request_id="w", routing_table=[], context_len=4,
        num_new_tokens=1, token_ids=[3], trace=True,
    )
    frame = proto.encode_frame(
        proto.FORWARD, {"reqs": [proto.ireq_to_wire(ireq)]}
    )
    back = proto.ireq_from_wire(proto.decode_frame(frame)["p"]["reqs"][0])
    assert back.trace is True


def test_tracing_off_is_inert_and_streams_match(monkeypatch):
    """With trace_sample_rate=0 (the default) the dispatch path must do
    ZERO tracing work: TraceStore.add raising proves no per-step hook
    fires, and the token stream is bit-identical to a traced run."""
    engines = build_engines([(0, 2)], trace_sample_rate=1.0)
    traced_req = run_pipeline(InProcessPipeline(engines), "overhead-on")

    def boom(*a, **k):  # any tracing work under rate 0 is a failure
        raise AssertionError("TraceStore touched with tracing off")

    monkeypatch.setattr(TraceStore, "add", boom)
    monkeypatch.setattr(TraceStore, "begin", boom)
    engines_off = build_engines([(0, 2)])  # default: rate 0
    assert engines_off[0].cfg.trace_sample_rate == 0.0
    pending = None
    eng = engines_off[0]
    req = Request("overhead-off", prompt_ids=[1, 2, 3, 4, 5],
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=12))
    eng.submit(req)
    guard = 0
    while (eng.has_work() or pending is not None) and guard < 4000:
        _outs, pending = drive_step(eng, pending)
        guard += 1
    assert req.status.is_finished
    assert req.output_ids == traced_req.output_ids
    assert eng._traced == set()
    assert get_trace_store().spans("overhead-off") is None


# -- flight recorder ---------------------------------------------------------


def test_flight_recorder_slow_request_capture():
    engines = build_engines(
        [(0, 2)], trace_sample_rate=1.0, slow_request_ms=0.001
    )
    run_pipeline(InProcessPipeline(engines), "flight-slow", max_tokens=6)
    snap = get_flight().snapshot()
    slow = [r for r in snap["slow"] if r["request_id"] == "flight-slow"]
    assert slow, snap["slow"]
    rec = slow[-1]
    assert rec["e2e_ms"] > 0
    assert rec["output_tokens"] == 6
    assert rec["status"] == "finished_length"
    # Traced request: the slow record carries the full span breakdown.
    assert rec["breakdown"] and "decode" in rec["breakdown"]
    assert rec["ttft_ms"] is not None


def test_flight_recorder_fast_requests_skip_slow_ring():
    engines = build_engines([(0, 2)], slow_request_ms=10 * 60 * 1000.0)
    run_pipeline(InProcessPipeline(engines), "flight-fast", max_tokens=4)
    snap = get_flight().snapshot()
    assert not any(
        r["request_id"] == "flight-fast" for r in snap["slow"]
    )
    assert any(
        r["request_id"] == "flight-fast" for r in snap["requests"]
    )


def test_flight_event_ring():
    get_flight().event("wire_dtype", peer="w1", want="float8_e4m3fn",
                       negotiated=None)
    events = get_flight().snapshot()["events"]
    assert any(
        e["kind"] == "wire_dtype" and e["peer"] == "w1" for e in events
    )


# -- HTTP surfaces -----------------------------------------------------------


@pytest.fixture
def traced_frontend():
    # Wire mode: the acceptance path — a two-stage wire-mode pipeline
    # whose stitched trace (both stages + the transport hop) is
    # retrievable over HTTP.
    fe, runner = build_local_frontend(
        build_engines([(0, 1), (1, 2)], trace_sample_rate=1.0),
        SimpleTokenizer(), model_name="tiny-obs", wire=True,
    )
    yield fe
    runner.stop()


def test_step_loop_failure_fails_requests_and_flips_healthz():
    """A ``step_round()`` that raises (on a chip: a kernel the compiler
    refuses at the first prefill) must not leave a live HTTP server
    answering nothing: the waiting request gets a 5xx, ``/healthz``
    reads 503, later submissions are refused, and ``on_failure`` — how
    ``serve`` ends the process non-zero — is called."""
    fe, runner = build_local_frontend(
        build_engines([(0, 2)]), SimpleTokenizer(), model_name="tiny-fail",
    )

    def refused():
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    runner.pipeline.step_round = refused
    failures = []
    runner.on_failure = failures.append
    chat = {"messages": [{"role": "user", "content": "hello"}],
            "max_tokens": 4, "temperature": 0}

    async def fn(client):
        before = await client.get("/healthz")
        resp = await asyncio.wait_for(
            client.post("/v1/chat/completions", json=chat), timeout=60,
        )
        after = await client.get("/healthz")
        again = await client.post("/v1/chat/completions", json=chat)
        return (before.status, resp.status, await resp.json(),
                after.status, await after.json(), again.status)

    try:
        before, status, body, after, health, again = with_client(fe.app, fn)
    finally:
        runner.stop()
    assert before == 200
    assert status == 502, body
    assert "step loop failed" in body["error"]["message"], body
    assert "Mosaic failed" in body["error"]["message"], body
    assert after == 503, health
    assert health["status"] == "failed", health
    assert health["components"]["step_loop"]["status"] == "failed", health
    assert again == 503
    assert len(failures) == 1 and isinstance(failures[0], RuntimeError)
    assert not runner._thread.is_alive()


def test_metrics_endpoint_exposition(traced_frontend):
    async def fn(client):
        resp = await client.post(
            "/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "hello there"}],
                  "max_tokens": 5, "temperature": 0},
        )
        assert resp.status == 200, await resp.text()
        resp = await client.get("/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"] == EXPOSITION_CONTENT_TYPE
        return await resp.text()

    text = with_client(traced_frontend.app, fn)
    # Core engine + frontend series exist, typed, and are non-zero.
    assert "# TYPE parallax_ttft_ms histogram" in text
    assert "# TYPE parallax_tpu_requests_total counter" in text
    assert "# HELP parallax_step_host_ms " in text

    def series_value(name):
        vals = [
            float(line.rsplit(" ", 1)[1])
            for line in text.splitlines()
            if line.startswith(name) and not line.startswith("#")
        ]
        assert vals, f"series {name} missing"
        return max(vals)

    assert series_value("parallax_tpu_requests_total") > 0
    assert series_value("parallax_ttft_ms_count") > 0
    assert series_value("parallax_e2e_ms_count") > 0
    assert series_value("parallax_step_host_ms_count") > 0
    assert series_value("parallax_tpu_completion_tokens_total") > 0


def test_debug_trace_and_flight_endpoints(traced_frontend):
    async def fn(client):
        resp = await client.post(
            "/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "trace me"}],
                  "max_tokens": 6, "temperature": 0},
        )
        body = await resp.json()
        assert resp.status == 200, body
        rid = body["id"]
        resp = await client.get(f"/debug/trace/{rid}")
        assert resp.status == 200
        trace = await resp.json()
        assert trace["metadata"]["trace_id"] == rid
        assert trace["traceEvents"]
        stages = {e["tid"] for e in trace["traceEvents"]}
        # Both stages AND the transport hop stitched into ONE trace.
        assert {"0-1", "1-2", "wire"} <= stages
        resp = await client.get("/debug/trace/nope-unknown")
        assert resp.status == 404
        resp = await client.get("/debug/flight")
        assert resp.status == 200
        flight = await resp.json()
        assert any(
            r["request_id"] == rid for r in flight["requests"]
        )
        return True

    assert with_client(traced_frontend.app, fn)


def test_cluster_status_stream_survives_status_fn_errors():
    calls = {"n": 0}

    def status_fn():
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("scraper-visible failure")
        return {"ok": calls["n"]}

    fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None,
                        status_fn=status_fn)

    async def fn(client):
        resp = await client.get("/cluster/status?interval=0.01")
        records = []
        async for raw in resp.content:
            records.append(json.loads(raw.decode()))
            if len(records) == 3:
                break
        return records

    records = with_client(fe.app, fn)
    assert records[0] == {"ok": 1}
    assert "error" in records[1] and "scraper-visible" in records[1]["error"]
    assert records[2] == {"ok": 3}  # the stream kept going


def test_profile_start_autostop_deadline(monkeypatch):
    calls = {"start": 0, "stop": 0}
    import jax as _jax

    monkeypatch.setattr(
        _jax.profiler, "start_trace",
        lambda *a, **k: calls.__setitem__("start", calls["start"] + 1),
    )
    monkeypatch.setattr(
        _jax.profiler, "stop_trace",
        lambda *a, **k: calls.__setitem__("stop", calls["stop"] + 1),
    )
    fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

    async def fn(client):
        resp = await client.post(
            "/profile/start", json={"max_seconds": 0.15}
        )
        assert resp.status == 200
        body = await resp.json()
        assert body["max_seconds"] == 0.15
        await asyncio.sleep(0.5)  # deadline fires
        assert calls == {"start": 1, "stop": 1}
        assert fe._profiling is False
        # A forgotten profiler is stopped; a new start works again, and
        # an explicit stop cancels the timer so no double-stop later.
        resp = await client.post(
            "/profile/start", json={"max_seconds": 30}
        )
        assert resp.status == 200
        resp = await client.post("/profile/stop")
        assert resp.status == 200
        assert fe._profile_deadline_handle is None
        await asyncio.sleep(0.05)
        assert calls == {"start": 2, "stop": 2}
        # Bad input 400s.
        resp = await client.post(
            "/profile/start", json={"max_seconds": -1}
        )
        assert resp.status == 400
        return True

    assert with_client(fe.app, fn)


# -- cluster-wide heartbeat merge -------------------------------------------


def test_cluster_status_merges_node_histograms():
    from parallax_tpu.scheduling.node import Node
    from parallax_tpu.scheduling.node_management import Pipeline
    from parallax_tpu.scheduling.scheduler import GlobalScheduler
    from parallax_tpu.utils.hw import HardwareInfo

    hw = HardwareInfo(device_kind="cpu", num_chips=1, tflops_bf16=1.0,
                      hbm_gib=8.0, hbm_gbps=50.0, ici_gbps=1.0)
    sched = GlobalScheduler(TINY)
    nodes = []
    for i, vals in enumerate(([5.0] * 10, [500.0] * 10)):
        reg = MetricsRegistry()
        h = reg.histogram("parallax_ttft_ms", "ttft", labelnames=("stage",))
        for v in vals:
            h.labels(stage="0-2").observe(v)
        node = Node(node_id=f"n{i}", hardware=hw, model=TINY)
        node.set_layers(0 if i == 0 else 1, 1 if i == 0 else 2)
        node.metrics = reg.histogram_snapshots()
        sched.manager.add(node)
        nodes.append(node)
    sched.manager.register_pipelines([Pipeline(nodes=nodes)])
    status = sched.cluster_status()
    merged = status["metrics"]["parallax_ttft_ms"]
    entry = merged[next(iter(merged))]
    assert entry["count"] == 20
    # Percentiles span both nodes' populations: p50 in the low decade,
    # p99 in the high one.
    assert entry["p50"] < 50.0 < entry["p99"]


def test_scheduler_service_update_passes_metrics_through():
    from parallax_tpu.backend.scheduler_service import SchedulerService
    from parallax_tpu.p2p.transport import LoopbackTransport
    from parallax_tpu.scheduling.node import Node
    from parallax_tpu.scheduling.scheduler import GlobalScheduler
    from parallax_tpu.utils.hw import HardwareInfo

    hw = HardwareInfo(device_kind="cpu", num_chips=1, tflops_bf16=1.0,
                      hbm_gib=8.0, hbm_gbps=50.0, ici_gbps=1.0)
    sched = GlobalScheduler(TINY)
    node = Node(node_id="w0", hardware=hw, model=TINY)
    sched.manager.add(node)
    svc = SchedulerService(sched, LoopbackTransport("sched", {}))
    snap = {"parallax_ttft_ms": {"": {
        "bounds": [1.0], "counts": [1, 0], "sum": 0.5, "count": 1,
    }}}
    svc._on_update("w0", {"node_id": "w0", "metrics": snap})
    # The event is queued; drain it through the handler directly.
    ev = sched._events.get_nowait()
    sched._handle_event(ev)
    assert node.metrics == snap
