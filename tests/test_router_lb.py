"""Router LB tests: endpoint registry, strategies and session affinity,
and the proxy against a live local serving app."""

import asyncio

import jax
import jax.numpy as jnp
from aiohttp.test_utils import TestClient, TestServer

from parallax_tpu.backend.http_server import SimpleTokenizer
from parallax_tpu.backend.serve import build_local_frontend
from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.router.lb import Endpoint, Performance, Router, RoundRobin
from parallax_tpu.runtime.engine import EngineConfig, StageEngine

TINY = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"],
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128, vocab_size=266,
))


def tiny_frontend():
    m = StageModel(TINY, 0, 2, use_pallas=False)
    eng = StageEngine(
        m, m.init_params(jax.random.key(0), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=256, max_model_len=512,
                     kv_dtype="float32"),
    )
    return build_local_frontend([eng], SimpleTokenizer(), model_name="tiny")


class TestRouterStrategies:
    def make_eps(self):
        fast = Endpoint(url="http://fast", healthy=True)
        fast.ema_ttft_s, fast.ema_tpot_s = 0.05, 0.01
        slow = Endpoint(url="http://slow", healthy=True)
        slow.ema_ttft_s, slow.ema_tpot_s = 2.0, 0.2
        return [fast, slow]

    def test_performance_prefers_fast(self):
        eps = self.make_eps()
        strat = Performance(top_k=1, explore_ratio=0.0)
        picks = [strat.pick(eps).url for _ in range(10)]
        assert all(p == "http://fast" for p in picks)

    def test_error_penalty_flips_choice(self):
        eps = self.make_eps()
        eps[0].error_count = 10
        strat = Performance(top_k=1, explore_ratio=0.0)
        assert strat.pick(eps).url == "http://slow"

    def test_round_robin_cycles(self):
        eps = self.make_eps()
        rr = RoundRobin()
        assert {rr.pick(eps).url for _ in range(4)} == {
            "http://fast", "http://slow"
        }

    def test_ema_update(self):
        ep = Endpoint(url="x")
        ep.observe(1.0, 0.1)
        ep.observe(0.0, 0.0)
        assert 0.0 < ep.ema_ttft_s < 1.0


class TestSessionAffinity:
    def make_eps(self, n=3):
        return [
            Endpoint(url=f"http://ep{i}", healthy=True) for i in range(n)
        ]

    def test_same_key_pins_same_endpoint(self):
        from parallax_tpu.router.lb import SessionAffinity

        eps = self.make_eps()
        strat = SessionAffinity()
        picks = {strat.pick(eps, key="session-42").url for _ in range(10)}
        assert len(picks) == 1

    def test_keys_spread_across_endpoints(self):
        from parallax_tpu.router.lb import SessionAffinity

        eps = self.make_eps()
        strat = SessionAffinity()
        picks = {
            strat.pick(eps, key=f"user-{i}").url for i in range(64)
        }
        assert picks == {e.url for e in eps}

    def test_unhealthy_pin_falls_back_to_performance(self):
        from parallax_tpu.router.lb import SessionAffinity

        eps = self.make_eps()
        strat = SessionAffinity()
        strat._fallback.explore_ratio = 0.0
        strat._fallback.top_k = 1
        pinned = strat.pick(eps, key="sticky")
        pinned.healthy = False
        healthy = [e for e in eps if e.healthy]
        best = healthy[0]
        best.ema_ttft_s, best.ema_tpot_s = 0.01, 0.001
        got = strat.pick(healthy, key="sticky", all_endpoints=eps)
        assert got is not pinned
        assert got is best   # performance scoring, not a re-hash

    def test_flapping_other_endpoint_keeps_pin(self):
        # The pin hashes over ALL registered endpoints, so an unrelated
        # endpoint going unhealthy must not remap this session.
        from parallax_tpu.router.lb import SessionAffinity

        eps = self.make_eps()
        strat = SessionAffinity()
        pinned = strat.pick(eps, key="stable")
        other = next(e for e in eps if e is not pinned)
        other.healthy = False
        healthy = [e for e in eps if e.healthy]
        assert strat.pick(healthy, key="stable",
                          all_endpoints=eps) is pinned

    def test_no_key_uses_performance(self):
        from parallax_tpu.router.lb import SessionAffinity

        eps = self.make_eps()
        eps[1].ema_ttft_s, eps[1].ema_tpot_s = 0.01, 0.001
        strat = SessionAffinity()
        strat._fallback.explore_ratio = 0.0
        strat._fallback.top_k = 1
        assert strat.pick(eps, key=None) is eps[1]

    def test_affinity_key_extraction(self):
        from parallax_tpu.router.lb import Router

        class FakeReq:
            def __init__(self, headers):
                self.headers = headers

        key = Router._affinity_key
        assert key(FakeReq({"x-session-id": "s1"}), {}) == "s1"
        assert key(FakeReq({}), {"user": "u9"}) == "u9"
        # Multi-turn chat: the first USER message is the stable head of
        # the transcript...
        msgs = [{"role": "user", "content": "hello"}]
        k1 = key(FakeReq({}), {"messages": msgs})
        k2 = key(FakeReq({}), {"messages": msgs + [
            {"role": "assistant", "content": "hi"}
        ]})
        assert k1 == k2
        # ...and a SHARED system prompt must not collapse every user's
        # conversations onto one key (that would funnel all keyless
        # traffic to a single endpoint).
        sys_msg = {"role": "system", "content": "you are helpful"}
        ka = key(FakeReq({}), {"messages": [
            sys_msg, {"role": "user", "content": "alice turn"}
        ]})
        kb = key(FakeReq({}), {"messages": [
            sys_msg, {"role": "user", "content": "bob turn"}
        ]})
        assert ka != kb
        assert key(FakeReq({}), {"prompt": "abc"}) == "abc"
        assert key(FakeReq({}), {}) is None


def test_router_proxies_to_live_backend():
    fe, runner = tiny_frontend()

    async def go():
        backend_server = TestServer(fe.app)
        backend = TestClient(backend_server)
        await backend.start_server()
        router = Router(
            [f"http://{backend.host}:{backend.port}"],
            strategy="round_robin", probe_interval_s=0.2,
        )
        router_client = TestClient(TestServer(router.app))
        await router_client.start_server()
        try:
            await asyncio.sleep(0.5)  # allow a health probe
            status = await (await router_client.get("/router/status")).json()
            assert status["endpoints"][0]["healthy"], status

            r = await router_client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4, "temperature": 0,
            })
            body = await r.json()
            assert r.status == 200, body
            assert body["usage"]["completion_tokens"] == 4

            # streaming through the proxy
            r2 = await router_client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "go"}],
                "max_tokens": 3, "temperature": 0, "stream": True,
            })
            text = await r2.text()
            assert text.strip().endswith("data: [DONE]")

            status = await (await router_client.get("/router/status")).json()
            ep = status["endpoints"][0]
            assert ep["total_requests"] == 2
            assert ep["ema_tpot_s"] is not None

            # runtime config: switch strategy, add/remove endpoint
            r3 = await router_client.post(
                "/router/strategy", json={"strategy": "random"}
            )
            assert (await r3.json())["strategy"] == "random"
            r4 = await router_client.post(
                "/router/endpoints", json={"url": "http://nowhere:1"}
            )
            assert len((await r4.json())["endpoints"]) == 2
        finally:
            await router_client.close()
            await backend.close()

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(go())
    finally:
        loop.close()
        runner.stop()
