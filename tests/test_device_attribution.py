"""Device attribution plane (obs/device.py, docs/observability.md):
HBM ledger invariant + untracked excursion, compile observatory cause
derivation / LIFO matching / storm detection, per-program device-time
shares, the heterogeneous cluster merge (disjoint classes and program
families union; a node missing the payload is a COUNTED skip), the
scheduler's /cluster/status device section, the /debug/device endpoint,
the cluster profile fanout handler, and the flight recorder's trace_id
linkage."""

import asyncio
import threading
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from parallax_tpu.backend.http_server import OpenAIFrontend, SimpleTokenizer
from parallax_tpu.obs.device import (
    CompileObservatory,
    DevicePlane,
    ProgramVisitAttributor,
    HbmLedger,
    get_device_plane,
    merge_device,
)
from parallax_tpu.obs.flight import FlightRecorder, get_flight
from parallax_tpu.obs.registry import MetricsRegistry


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


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


class FakeDevice:
    def __init__(self, limit, in_use):
        self._stats = {"bytes_limit": limit, "bytes_in_use": in_use}

    def memory_stats(self):
        return self._stats


# -- HBM ledger --------------------------------------------------------------


class TestHbmLedger:
    def test_classes_aggregate_across_owners(self):
        led = HbmLedger(registry=MetricsRegistry())
        led.set_class("kv_pages", 100, owner="eng0")
        led.set_class("kv_pages", 50, owner="eng1")
        led.set_class("weights_float32", 200, owner="eng0")
        snap = led.snapshot()
        assert snap["classes"]["kv_pages"] == 150
        assert snap["classes"]["weights_float32"] == 200
        assert snap["tracked_bytes"] == 350
        assert snap["invariant_ok"] is True
        # set_class is idempotent per (owner, class): re-set replaces.
        led.set_class("kv_pages", 80, owner="eng0")
        assert led.snapshot()["classes"]["kv_pages"] == 130
        led.add_class("kv_pages", -30, owner="eng1")
        assert led.snapshot()["classes"]["kv_pages"] == 100

    def test_headroom_and_high_watermark(self):
        led = HbmLedger(registry=MetricsRegistry())
        led.set_capacity(1000)
        led.set_class("weights_float32", 600)
        snap = led.snapshot()
        assert snap["capacity_bytes"] == 1000
        assert snap["headroom_bytes"] == 400
        assert snap["high_watermark_bytes"] == 600
        # The watermark is monotone: a shrink does not lower it.
        led.set_class("weights_float32", 300)
        snap = led.snapshot()
        assert snap["headroom_bytes"] == 700
        assert snap["high_watermark_bytes"] == 600

    def test_device_refresh_accounts_untracked(self):
        led = HbmLedger(registry=MetricsRegistry())
        led.set_class("weights_float32", 700)
        assert led.refresh_from_device(FakeDevice(1000, 750)) is True
        snap = led.snapshot()
        assert snap["capacity_bytes"] == 1000
        assert snap["capacity_source"] == "device"
        assert snap["untracked_bytes"] == 50
        assert snap["device_total_bytes"] == 750
        assert snap["headroom_bytes"] == 250
        # 50 untracked of 1000 capacity is under the 10% threshold.
        assert snap["invariant_ok"] is True
        # A device-reported limit wins over a configured one.
        led.set_capacity(5000)
        assert led.snapshot()["capacity_bytes"] == 1000

    def test_untracked_excursion_emits_one_flight_event(self):
        led = HbmLedger(registry=MetricsRegistry())
        led.set_class("weights_float32", 100)
        seq0 = get_flight().snapshot()["events"]
        n0 = len([e for e in seq0 if e["kind"] == "hbm_untracked"])
        # 400/1000 untracked: way past the 10% threshold.
        assert led.refresh_from_device(FakeDevice(1000, 500)) is True
        assert led.snapshot()["invariant_ok"] is False
        events = [e for e in get_flight().snapshot()["events"]
                  if e["kind"] == "hbm_untracked"]
        assert len(events) == n0 + 1
        assert events[-1]["untracked_bytes"] == 400
        # Still flagged: a second refresh is NOT a second event.
        led.refresh_from_device(FakeDevice(1000, 510))
        events = [e for e in get_flight().snapshot()["events"]
                  if e["kind"] == "hbm_untracked"]
        assert len(events) == n0 + 1
        # Residual drops under threshold -> re-arms -> next excursion
        # fires again.
        led.refresh_from_device(FakeDevice(1000, 120))
        assert led.snapshot()["invariant_ok"] is True
        led.refresh_from_device(FakeDevice(1000, 500))
        events = [e for e in get_flight().snapshot()["events"]
                  if e["kind"] == "hbm_untracked"]
        assert len(events) == n0 + 2

    def test_cpu_build_invariant_holds_without_capacity(self):
        """CPU smoke semantics: no memory_stats, no capacity — the
        tracked sum stands in and the invariant is trivially true."""
        led = HbmLedger(registry=MetricsRegistry())
        led.set_class("kv_pages", 4096)
        snap = led.snapshot()
        assert snap["capacity_bytes"] == 0
        assert snap["untracked_bytes"] == 0
        assert snap["invariant_ok"] is True

    def test_gauges_export_per_class(self):
        reg = MetricsRegistry()
        led = HbmLedger()
        led.bind_registry(reg)
        led.set_class("kv_pages", 512)
        led.set_class("grammar_tables", 64)
        text = reg.render()
        assert 'parallax_hbm_bytes{class="kv_pages"} 512' in text
        assert 'parallax_hbm_bytes{class="grammar_tables"} 64' in text
        assert "parallax_hbm_high_watermark_bytes 576" in text


# -- compile observatory -----------------------------------------------------


class TestCompileObservatory:
    def test_cause_derivation_from_key_diff(self):
        clock = FakeClock()
        obs = CompileObservatory(registry=MetricsRegistry(), clock=clock)
        key = {"batch": 8, "k": 1, "feats": (), "spec": False}
        assert obs.note_program("decode", key) == "first"
        assert obs.note_program("decode", dict(key, batch=16)) == (
            "new_shape_bucket")
        assert obs.note_program(
            "decode", dict(key, batch=16, k=4)) == "k_change"
        assert obs.note_program(
            "decode", dict(key, batch=16, k=4, feats=("penalties",))
        ) == "sampling_feature"
        assert obs.note_program(
            "decode", dict(key, batch=16, k=4, feats=("penalties",),
                           spec=True)
        ) == "spec_toggle"
        # Identical key (a persistent-cache rebuild): falls to "other".
        assert obs.note_program(
            "decode", dict(key, batch=16, k=4, feats=("penalties",),
                           spec=True)
        ) == "other"
        # Shape wins over k when both change (most-specific first).
        assert obs.note_program("decode", dict(key, batch=32)) == (
            "new_shape_bucket")
        # Families diff independently.
        assert obs.note_program("prefill", {"chunk": 256}) == "first"

    def test_compile_attribution_lifo_and_unknown(self):
        clock = FakeClock()
        obs = CompileObservatory(registry=MetricsRegistry(), clock=clock)
        obs.note_program("prefill", {"chunk": 256})
        obs.on_compile(0.5)
        snap = obs.snapshot()
        assert snap["programs"]["prefill"]["by_cause"] == {"first": 1}
        assert snap["compiles_total"] == 1
        assert snap["unexplained_compiles"] == 0
        assert snap["compile_ms_total"] == 500.0
        # A compile nobody noted: other/unknown, counted unexplained.
        obs.on_compile(0.1)
        snap = obs.snapshot()
        assert snap["programs"]["other"]["by_cause"] == {"unknown": 1}
        assert snap["unexplained_compiles"] == 1

    def test_stale_notes_expire(self):
        clock = FakeClock()
        obs = CompileObservatory(registry=MetricsRegistry(), clock=clock)
        obs.note_program("decode", {"batch": 8})
        clock.t += CompileObservatory.NOTE_TTL_S + 1
        # The note aged out (persistent-cache hit never compiled);
        # a later unrelated compile must not steal it.
        obs.on_compile(0.2)
        snap = obs.snapshot()
        assert snap["unexplained_compiles"] == 1
        assert "decode" not in snap["programs"]

    def test_recent_holds_the_key_of_every_build_and_is_bounded(self):
        """``device.compile.recent``: the last 16 builds with the key
        their note gave, the trace and lowering ms since that note, the
        visit they fell in, and whether the persistent cache served
        them — so a count of compiles inside a window has names."""
        from parallax_tpu.obs.trace import note_jit_seconds, visit_span

        clock = FakeClock()
        obs = CompileObservatory(registry=MetricsRegistry(), clock=clock)
        note_jit_seconds("trace", 9.0)      # before any note: nobody's
        with visit_span(41):
            obs.note_program("prefill", {"tokens": 512, "seq": 16})
            note_jit_seconds("trace", 0.25)     # the listener's calls,
            note_jit_seconds("trace", 0.05)     # on this thread
            t0 = time.perf_counter_ns()
            obs.on_compile(4.0, "jit(_stage_fn)")
        (rec,) = obs.snapshot()["recent"]
        assert rec["program"] == "prefill" and rec["cause"] == "first"
        assert rec["fun"] == "jit(_stage_fn)"
        assert rec["key"] == {"tokens": 512, "seq": 16}
        assert rec["compile_ms"] == 4000.0
        assert rec["trace_ms"] == pytest.approx(300.0)
        assert rec["visit"] == 41 and rec["cache_hit"] is False
        assert t0 <= rec["perf_counter_ns"] <= time.perf_counter_ns()
        # A load from the persistent cache takes its note with it (a
        # later compile nobody noted does not inherit it) and is no
        # compile, but it is a build with a name.
        obs.note_program("prefill", {"tokens": 1024, "seq": 16})
        obs.on_cache_hit(0.125)
        with visit_span(43):
            obs.on_compile(0.5, "jit(_gather_feed)")
        snap = obs.snapshot()
        assert snap["cache_hits_total"] == 1 and snap["compiles_total"] == 2
        hit, unknown = snap["recent"][-2:]
        assert (hit["program"], hit["cause"], hit["cache_hit"]) == (
            "prefill", "new_shape_bucket", True)
        assert hit["key"]["tokens"] == 1024 and hit["compile_ms"] == 125.0
        assert hit["visit"] is None
        assert (unknown["program"], unknown["cause"], unknown["key"]) == (
            "other", "unknown", {})
        # Un-noted, but in its visit and under JAX's name for it.
        assert unknown["visit"] == 43
        assert unknown["fun"] == "jit(_gather_feed)"
        assert snap["unexplained_compiles"] == 1
        # Bounded: the newest 16, oldest first.
        for seq in range(40):
            obs.note_program("decode_window", {"seq": seq})
            obs.on_compile(0.01)
        recent = obs.snapshot()["recent"]
        assert len(recent) == CompileObservatory.RECENT == 16
        assert [r["key"]["seq"] for r in recent] == list(range(24, 40))

    def test_storm_detection_and_probe_freeze(self):
        clock = FakeClock()
        obs = CompileObservatory(registry=MetricsRegistry(), clock=clock,
                                 storm_window_s=30.0, storm_threshold=5)
        seq0 = len([e for e in get_flight().snapshot()["events"]
                    if e["kind"] == "recompile_storm"])
        # Four compiles: no storm yet, probe progresses.
        for _ in range(4):
            obs.note_program("decode", {"batch": clock.t})
            obs.on_compile(0.01)
            clock.t += 1.0
        _, prog1, _ = obs.probe()
        _, prog2, detail = obs.probe()
        assert prog2 > prog1 and detail == ""
        # Fifth compile inside the window: storm.
        obs.note_program("decode", {"batch": clock.t})
        obs.on_compile(0.01)
        snap = obs.snapshot()
        assert snap["storms"] == {"decode": 1}
        assert snap["storms_total"] == 1
        events = [e for e in get_flight().snapshot()["events"]
                  if e["kind"] == "recompile_storm"]
        assert len(events) == seq0 + 1
        assert events[-1]["program"] == "decode"
        # While storming, the probe reports pending work with FROZEN
        # progress — the watchdog walks ok -> degraded -> stalled.
        pend1, p1, detail = obs.probe()
        pend2, p2, _ = obs.probe()
        assert pend1 > 0 and p2 == p1
        assert "decode" in detail
        # One ongoing storm is ONE storm, not one per compile.
        obs.note_program("decode", {"batch": clock.t + 0.5})
        obs.on_compile(0.01)
        assert obs.snapshot()["storms_total"] == 1
        # Window drains -> storm ends, probe progresses again.
        clock.t += 31.0
        _, p3, _ = obs.probe()
        _, p4, _ = obs.probe()
        assert p4 > p3

    def test_unmatched_compiles_never_storm(self):
        """Startup runs dozens of eager op-by-op compiles nobody can
        note — they count as unexplained but must NOT trip the storm
        detector (a storm degrades the watchdog probe)."""
        clock = FakeClock()
        obs = CompileObservatory(registry=MetricsRegistry(), clock=clock,
                                 storm_window_s=30.0, storm_threshold=5)
        for _ in range(10):
            obs.on_compile(0.01)
            clock.t += 0.1
        snap = obs.snapshot()
        assert snap["unexplained_compiles"] == 10
        assert snap["storms_total"] == 0
        _, p1, detail = obs.probe()
        _, p2, _ = obs.probe()
        assert p2 > p1 and detail == ""

    def test_metrics_export_by_program_and_cause(self):
        reg = MetricsRegistry()
        clock = FakeClock()
        obs = CompileObservatory(clock=clock)
        obs.bind_registry(reg)
        obs.note_program("decode", {"batch": 8})
        obs.on_compile(0.25)
        obs.set_live_executables("decode", 3)
        text = reg.render()
        assert ('parallax_xla_compiles_total'
                '{cause="first",program="decode"} 1' in text
                or 'parallax_xla_compiles_total'
                   '{program="decode",cause="first"} 1' in text)
        assert 'parallax_xla_live_executables{program="decode"} 3' in text
        assert 'parallax_xla_compile_ms_total{program="decode"} 250' in text


# -- host-visit seconds by program --------------------------------------------


class TestDeviceTime:
    def test_shares_sum_to_one(self):
        dt = ProgramVisitAttributor(registry=MetricsRegistry())
        dt.add("decode_window", 3.0)
        dt.add("prefill", 1.0)
        dt.add("decode_window", 1.0)
        dt.add("swap_gather", 0.0)          # no-op: zero never lands
        snap = dt.snapshot()
        assert snap["seconds"] == {"decode_window": 4.0, "prefill": 1.0}
        assert snap["seconds_total"] == 5.0
        assert snap["share"]["decode_window"] == 0.8
        assert snap["share"]["prefill"] == 0.2
        assert abs(sum(snap["share"].values()) - 1.0) < 1e-6

    def test_empty_share_when_idle(self):
        dt = ProgramVisitAttributor(registry=MetricsRegistry())
        snap = dt.snapshot()
        assert snap["seconds_total"] == 0
        assert snap["share"] == {}


# -- plane payload -----------------------------------------------------------


def test_device_plane_payload_shape():
    plane = DevicePlane(registry=MetricsRegistry())
    plane.hbm.set_class("kv_pages", 1024)
    plane.compile.note_program("decode", {"batch": 4})
    plane.compile.on_compile(0.1)
    plane.time.add("decode", 2.0)
    p = plane.payload()
    assert set(p) == {"hbm", "compile", "programs"}
    assert p["hbm"]["classes"]["kv_pages"] == 1024
    assert p["compile"]["compiles_total"] == 1
    assert p["programs"]["seconds"]["decode"] == 2.0


def test_process_plane_singleton():
    assert get_device_plane() is get_device_plane()
    assert set(get_device_plane().payload()) == {
        "hbm", "compile", "programs"}


# -- cluster merge -----------------------------------------------------------


def _node_payload(classes=None, programs=None, compiles=None,
                  capacity=0, invariant_ok=True):
    tracked = sum((classes or {}).values())
    by_prog = {}
    total = 0
    unexplained = 0
    for fam, (cause, n) in (compiles or {}).items():
        by_prog[fam] = {"compiles": n, "by_cause": {cause: n},
                        "compile_ms": 10.0 * n}
        total += n
        if cause == "unknown":
            unexplained += n
    secs = dict(programs or {})
    return {
        "hbm": {
            "classes": dict(classes or {}),
            "tracked_bytes": tracked,
            "untracked_bytes": 0,
            "capacity_bytes": capacity,
            "headroom_bytes": max(0, capacity - tracked),
            "high_watermark_bytes": tracked,
            "invariant_ok": invariant_ok,
        },
        "compile": {
            "programs": by_prog,
            "compiles_total": total,
            "unexplained_compiles": unexplained,
            "compile_ms_total": 10.0 * total,
            "storms_total": 0,
        },
        "programs": {
            "seconds": secs,
            "seconds_total": sum(secs.values()),
            "share": {},
        },
    }


class TestMergeDevice:
    def test_disjoint_classes_and_families_union(self):
        """A heterogeneous swarm — one node speculates, the other runs
        grammar decoding — must show BOTH series, not the intersection."""
        a = _node_payload(
            classes={"kv_pages": 100, "spec_draft": 20},
            programs={"decode": 2.0, "spec_window": 1.0},
            compiles={"decode": ("first", 2)},
            capacity=1000,
        )
        b = _node_payload(
            classes={"kv_pages": 50, "grammar_tables": 8},
            programs={"decode": 1.0, "prefill": 1.0},
            compiles={"prefill": ("new_shape_bucket", 3)},
            capacity=500,
        )
        m = merge_device([a, b], registry=MetricsRegistry())
        assert m["nodes"] == 2 and m["nodes_skipped"] == 0
        assert m["hbm"]["classes"] == {
            "kv_pages": 150, "spec_draft": 20, "grammar_tables": 8}
        assert m["hbm"]["capacity_bytes"] == 1500
        assert m["hbm"]["tracked_bytes"] == 178
        assert m["hbm"]["invariant_ok"] is True
        assert m["compile"]["compiles_total"] == 5
        assert m["compile"]["programs"]["decode"]["by_cause"] == {
            "first": 2}
        assert m["compile"]["programs"]["prefill"]["by_cause"] == {
            "new_shape_bucket": 3}
        assert m["programs"]["seconds"] == {
            "decode": 3.0, "spec_window": 1.0, "prefill": 1.0}
        assert m["programs"]["seconds_total"] == 5.0
        assert abs(sum(m["programs"]["share"].values()) - 1.0) < 1e-6

    def test_one_bad_node_poisons_invariant(self):
        a = _node_payload(classes={"kv_pages": 1})
        b = _node_payload(classes={"kv_pages": 1}, invariant_ok=False)
        m = merge_device([a, b], registry=MetricsRegistry())
        assert m["hbm"]["invariant_ok"] is False

    def test_missing_payload_is_counted_skip(self):
        """A node whose heartbeat carries no device section (old build)
        degrades the merge LOUDLY: nodes_skipped in the result plus the
        parallax_device_merge_skipped_total counter."""
        reg = MetricsRegistry()
        a = _node_payload(classes={"kv_pages": 100})
        m = merge_device([a, None, {"not": "a device payload"}],
                         registry=reg)
        assert m["nodes"] == 1
        assert m["nodes_skipped"] == 2
        assert m["hbm"]["classes"] == {"kv_pages": 100}
        assert "parallax_device_merge_skipped_total 2" in reg.render()

    def test_no_valid_nodes_returns_none(self):
        assert merge_device([], registry=MetricsRegistry()) is None
        assert merge_device([None, None],
                            registry=MetricsRegistry()) is None


# -- scheduler /cluster/status -----------------------------------------------


class TestSchedulerDeviceSection:
    def wait_for(self, cond, timeout=5.0):
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if cond():
                return True
            time.sleep(0.01)
        return False

    def test_heterogeneous_merge_and_counted_skip(self):
        from parallax_tpu.config import normalize_config
        from parallax_tpu.scheduling import GlobalScheduler
        from parallax_tpu.utils.hw import HardwareInfo

        model = normalize_config(dict(
            architectures=["Qwen2ForCausalLM"],
            hidden_size=3584, num_hidden_layers=28,
            num_attention_heads=28, num_key_value_heads=4,
            intermediate_size=18944, vocab_size=152064,
        ))
        hw = HardwareInfo("v5e", 4, 197.0, 16.0, 819.0, 186.0)
        sched = GlobalScheduler(model, min_nodes_bootstrapping=2)
        sched.start()
        try:
            sched.enqueue_join("n0", hw)
            sched.enqueue_join("n1", hw)
            assert self.wait_for(sched.bootstrapped.is_set)
            dev0 = _node_payload(
                classes={"kv_pages": 100, "spec_draft": 32},
                programs={"decode_window": 4.0})
            dev1 = _node_payload(
                classes={"kv_pages": 60, "grammar_tables": 16},
                programs={"prefill": 1.0})
            sched.enqueue_update("n0", is_ready=True, device=dev0)
            sched.enqueue_update("n1", is_ready=True, device=dev1)
            assert self.wait_for(
                lambda: sched.manager.get("n1") is not None
                and sched.manager.get("n1").device is not None
            )
            status = sched.cluster_status()
            dev = status["device"]
            assert dev["nodes"] == 2 and dev["nodes_skipped"] == 0
            assert dev["hbm"]["classes"] == {
                "kv_pages": 160, "spec_draft": 32, "grammar_tables": 16}
            assert dev["programs"]["seconds"] == {
                "decode_window": 4.0, "prefill": 1.0}
            # The per-node pipeline listing carries each node's payload.
            per_node = {
                n["node_id"]: n
                for p in status["pipelines"] for n in p["nodes"]
            }
            assert per_node["n0"]["device"]["hbm"]["classes"][
                "spec_draft"] == 32
            assert per_node["n1"]["device"]["programs"]["seconds"] == {
                "prefill": 1.0}
            # A node that never shipped a device payload (old build):
            # merged view keeps going, the skip is counted.
            sched.enqueue_update("n1", device=None)  # no-op: stays set
            node0 = sched.manager.get("n0")
            node0.device = None
            status = sched.cluster_status()
            dev = status["device"]
            assert dev["nodes"] == 1
            assert dev["nodes_skipped"] == 1
            assert dev["hbm"]["classes"] == {
                "kv_pages": 60, "grammar_tables": 16}
        finally:
            sched.stop()


# -- HTTP surfaces -----------------------------------------------------------


class TestDebugDeviceEndpoint:
    def test_local_payload_without_device_fn(self):
        fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

        async def fn(client):
            resp = await client.get("/debug/device")
            assert resp.status == 200
            body = await resp.json()
            assert {"hbm", "compile", "programs"} <= set(body)
            return True

        assert with_client(fe.app, fn)

    def test_device_fn_override_and_error(self):
        calls = {"n": 0}

        def device_fn():
            calls["n"] += 1
            if calls["n"] == 2:
                raise RuntimeError("merge exploded")
            return {"cluster": {"nodes": 3}, "nodes": {}}

        fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None,
                            device_fn=device_fn)

        async def fn(client):
            resp = await client.get("/debug/device")
            assert resp.status == 200
            body = await resp.json()
            assert body["cluster"]["nodes"] == 3
            resp = await client.get("/debug/device")
            assert resp.status == 500
            return True

        assert with_client(fe.app, fn)


class TestProfileClusterFanout:
    def test_pipeline_body_fans_out(self):
        seen = []

        def profile_cluster(action, pipeline, out_dir, max_seconds):
            seen.append((action, pipeline, out_dir, max_seconds))
            return {"w0": {"profiling": action == "start",
                           "dir": out_dir},
                    "w1": {"error": "profiler already running"}}

        fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None,
                            profile_cluster_fn=profile_cluster)

        async def fn(client):
            resp = await client.post(
                "/profile/start",
                json={"pipeline": "all", "max_seconds": 7},
            )
            assert resp.status == 200
            body = await resp.json()
            assert body["profiling"] is True
            assert body["pipeline"] == "all"
            assert body["nodes"]["w0"]["profiling"] is True
            assert "error" in body["nodes"]["w1"]
            resp = await client.post("/profile/stop",
                                     json={"pipeline": "all"})
            assert resp.status == 200
            body = await resp.json()
            assert body["profiling"] is False
            return True

        assert with_client(fe.app, fn)
        assert seen[0][0] == "start" and seen[0][3] == 7.0
        assert seen[1][0] == "stop"

    def test_cluster_scope_unavailable_is_501(self):
        fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None)

        async def fn(client):
            resp = await client.post("/profile/start",
                                     json={"pipeline": "all"})
            return resp.status

        assert with_client(fe.app, fn) == 501

    def test_unknown_pipeline_is_400(self):
        def profile_cluster(action, pipeline, out_dir, max_seconds):
            raise ValueError(f"unknown pipeline {pipeline!r}")

        fe = OpenAIFrontend(SimpleTokenizer(), submit_fn=None,
                            profile_cluster_fn=profile_cluster)

        async def fn(client):
            resp = await client.post("/profile/start",
                                     json={"pipeline": "nope"})
            return resp.status

        assert with_client(fe.app, fn) == 400


class TestWorkerProfileHandler:
    """The RPC target each fanned-out PROFILE frame lands on
    (p2p/node.py _on_profile) — driven directly, jax.profiler stubbed."""

    def _stub(self, monkeypatch):
        from parallax_tpu.p2p.node import WorkerNode

        calls = {"start": [], "stop": 0}
        import jax

        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda d, **kw: calls["start"].append(d))

        def stop():
            calls["stop"] += 1

        monkeypatch.setattr(jax.profiler, "stop_trace", stop)
        node = object.__new__(WorkerNode)
        node.node_id = "w0"
        node._profiling = False
        node._profile_dir = None
        node._profile_timer = None
        node._profile_lock = threading.Lock()
        return node, calls

    def test_start_stop_roundtrip(self, monkeypatch):
        node, calls = self._stub(monkeypatch)
        out = node._on_profile("peer", {"action": "start",
                                        "dir": "/tmp/px-prof",
                                        "max_seconds": 30})
        assert out == {"node_id": "w0", "profiling": True,
                       "dir": "/tmp/px-prof"}
        assert calls["start"] == ["/tmp/px-prof"]
        assert node._profile_timer is not None    # auto-stop armed
        # Double start answers with an error, not a second trace.
        out = node._on_profile("peer", {"action": "start"})
        assert "error" in out and len(calls["start"]) == 1
        out = node._on_profile("peer", {"action": "stop"})
        assert out["profiling"] is False
        assert calls["stop"] == 1
        assert node._profile_timer is None
        # Stop when idle: error, no crash.
        out = node._on_profile("peer", {"action": "stop"})
        assert "error" in out and calls["stop"] == 1

    def test_autostop_deadline(self, monkeypatch):
        node, calls = self._stub(monkeypatch)
        node._on_profile("peer", {"action": "start", "max_seconds": 5})
        node._profile_autostop()
        assert calls["stop"] == 1
        assert node._profiling is False
        # The explicit stop after the deadline is a clean error.
        out = node._on_profile("peer", {"action": "stop"})
        assert "error" in out

    def test_unknown_action(self, monkeypatch):
        node, _ = self._stub(monkeypatch)
        out = node._on_profile("peer", {"action": "fondle"})
        assert "error" in out


# -- flight trace_id ---------------------------------------------------------


def test_flight_record_carries_trace_id_only_when_sampled():
    fr = FlightRecorder(capacity=8)
    fr.record_request("r-traced", status="finished", e2e_ms=12.0,
                      trace_id="r-traced")
    fr.record_request("r-plain", status="finished", e2e_ms=9.0)
    recs = {r["request_id"]: r for r in fr.snapshot()["requests"]}
    assert recs["r-traced"]["trace_id"] == "r-traced"
    assert "trace_id" not in recs["r-plain"]


def test_slow_ring_entry_links_trace():
    fr = FlightRecorder(capacity=8)
    fr.record_request("r-slow", status="finished", e2e_ms=5000.0,
                      slow_threshold_ms=100.0, trace_id="r-slow")
    slow = fr.snapshot()["slow"]
    assert slow and slow[-1]["trace_id"] == "r-slow"
