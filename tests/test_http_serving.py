"""HTTP plane tests: OpenAI-compatible serving, single-host and swarm mode.

Capability parity: the reference CI E2E (launch server, poll
``/v1/chat/completions`` until it answers) + request-handler retry tests.
"""

import asyncio
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest
from aiohttp import web
from aiohttp.test_utils import TestClient, TestServer

from parallax_tpu.backend.http_server import OpenAIFrontend, SimpleTokenizer
from parallax_tpu.backend.serve import build_local_frontend
from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.runtime.engine import EngineConfig, StageEngine

TINY = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"],
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, intermediate_size=128, vocab_size=258 + 8,
    max_position_embeddings=512,
))


def build_engines(bounds):
    engines = []
    for s, e in bounds:
        m = StageModel(TINY, s, e, use_pallas=False)
        engines.append(StageEngine(
            m, m.init_params(jax.random.key(0), dtype=jnp.float32),
            EngineConfig(page_size=8, num_pages=128, max_model_len=256,
                         kv_dtype="float32"),
        ))
    return engines


@pytest.fixture
def frontend():
    fe, runner = build_local_frontend(
        build_engines([(0, 2)]), SimpleTokenizer(), model_name="tiny"
    )
    yield fe
    runner.stop()


def with_client(app, fn):
    """Run all of a test's HTTP calls on one event loop (the app binds to
    the first loop it sees)."""

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


async def _json(client, method, path, json_body=None):
    resp = await client.request(method, path, json=json_body)
    if resp.content_type == "application/json":
        return resp.status, await resp.json()
    return resp.status, await resp.text()


def test_models_and_health(frontend):
    async def fn(client):
        status, body = await _json(client, "GET", "/v1/models")
        assert status == 200 and body["data"][0]["id"] == "tiny"
        status, _ = await _json(client, "GET", "/health")
        assert status == 200

    with_client(frontend.app, fn)


def test_chat_completion_non_stream(frontend):
    async def fn(client):
        status, body = await _json(client, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 6, "temperature": 0})
        assert status == 200, body
        assert body["object"] == "chat.completion"
        assert body["usage"]["completion_tokens"] == 6
        assert body["choices"][0]["message"]["role"] == "assistant"

    with_client(frontend.app, fn)


def test_qos_headers_tag_requests_and_reject_unknown_class():
    """QoS-enabled frontend (docs/qos.md): class/deadline/tenant parse
    from headers into the submitted Request (tenant defaults to the
    adapter), unknown classes 400, and a QoS-off frontend leaves every
    request untagged (off-inertness at the HTTP layer)."""
    from parallax_tpu.qos import parse_qos_spec

    seen = []

    for qos_cfg in (parse_qos_spec("on"), None):
        fe, runner = build_local_frontend(
            build_engines([(0, 2)]), SimpleTokenizer(), model_name="tiny",
            qos_config=qos_cfg,
        )
        real_submit = fe.submit_fn

        def submit(req, _real=real_submit):
            seen.append(req)
            return _real(req)

        fe.submit_fn = submit

        async def fn(client):
            t0 = time.monotonic()
            resp = await client.request(
                "POST", "/v1/completions",
                json={"prompt": "hello", "max_tokens": 2,
                      "temperature": 0},
                headers={"x-parallax-qos-class": "batch",
                         "x-parallax-deadline-ms": "1500",
                         "x-parallax-tenant": "acme"},
            )
            assert resp.status == 200, await resp.text()
            if fe.qos_config is not None:
                resp = await client.request(
                    "POST", "/v1/completions",
                    json={"prompt": "hello", "max_tokens": 2},
                    headers={"x-parallax-qos-class": "platinum"},
                )
                assert resp.status == 400
                body = await resp.json()
                assert "QoS" in body["error"]["message"]
            return t0

        try:
            t0 = with_client(fe.app, fn)
        finally:
            runner.stop()
        req = seen[-1]
        if qos_cfg is not None:
            assert req.qos_class == "batch"
            assert req.tenant_id == "acme"
            assert req.deadline is not None
            assert 0 < req.deadline - t0 < 2.0
        else:
            assert req.qos_class is None
            assert req.deadline is None
            assert req.tenant_id is None


def test_completions_endpoint(frontend):
    async def fn(client):
        status, body = await _json(client, "POST", "/v1/completions",
            {"prompt": "hello world", "max_tokens": 4, "temperature": 0})
        assert status == 200, body
        assert body["object"] == "text_completion"
        assert body["usage"]["completion_tokens"] == 4

    with_client(frontend.app, fn)


def test_completions_token_array_prompt_continues_a_stream(frontend):
    """OpenAI's token-array ``prompt``: the ``token_ids`` a choice
    carries go back in as they came out, so a greedy stream can be
    continued (or replayed) where the tokenizer has no text for an id."""
    text = "hello world"
    base = {"max_tokens": 6, "temperature": 0, "ignore_eos": True}

    async def fn(client):
        status, whole = await _json(client, "POST", "/v1/completions",
                                    dict(base, prompt=text))
        assert status == 200, whole
        ids = whole["choices"][0]["token_ids"]
        assert len(ids) == 6
        prompt_ids = SimpleTokenizer().encode(text) + ids[:3]
        status, rest = await _json(
            client, "POST", "/v1/completions",
            dict(base, prompt=prompt_ids, max_tokens=3),
        )
        assert status == 200, rest
        assert rest["usage"]["prompt_tokens"] == len(prompt_ids)
        assert rest["choices"][0]["token_ids"] == ids[3:]
        for bad in ([], [1, -2], [1, "2"], [1, 2.0], [True]):
            status, body = await _json(client, "POST", "/v1/completions",
                                       dict(base, prompt=bad))
            assert status == 400, (bad, body)

    with_client(frontend.app, fn)


def test_n_choices(frontend):
    async def fn(client):
        status, body = await _json(client, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "hi"}],
             "max_tokens": 5, "temperature": 0.9, "seed": 7, "n": 3})
        assert status == 200, body
        assert [c["index"] for c in body["choices"]] == [0, 1, 2]
        assert body["usage"]["completion_tokens"] == 15
        # n>1 + stream and out-of-range n are rejected up front.
        status, _ = await _json(client, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "x"}],
             "n": 2, "stream": True})
        assert status == 400
        status, _ = await _json(client, "POST", "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "x"}], "n": 0})
        assert status == 400

    with_client(frontend.app, fn)


def test_logit_bias_forces_and_bans_tokens(frontend):
    async def fn(client):
        base = {"messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 4, "temperature": 0}
        # +1e4 bias on byte 'Z' (id 90) dominates every raw logit: the
        # whole generation becomes 'Z's (reference REJECTS logit_bias —
        # engine_core_protocol.py:196 — so this is beyond-parity surface).
        status, body = await _json(client, "POST", "/v1/chat/completions",
                                   {**base, "logit_bias": {"90": 10000.0}})
        assert status == 200
        assert body["choices"][0]["message"]["content"] == "ZZZZ"
        # Relative bias: a slightly larger bias on 'Y' (89) outbids 'Z',
        # i.e. biases compose per token, not winner-takes-all.
        status, body = await _json(client, "POST", "/v1/chat/completions",
                                   {**base, "logit_bias": {"90": 10000.0,
                                                           "89": 10001.0}})
        assert status == 200
        assert body["choices"][0]["message"]["content"] == "YYYY"

    with_client(frontend.app, fn)


def test_streaming_chat(frontend):
    async def fn(client):
        resp = await client.post("/v1/chat/completions", json={
            "messages": [{"role": "user", "content": "count"}],
            "max_tokens": 5, "temperature": 0, "stream": True,
        })
        assert resp.status == 200
        return await resp.text()

    raw = with_client(frontend.app, fn)
    chunks = [json.loads(line[6:]) for line in raw.splitlines()
              if line.startswith("data: ") and line != "data: [DONE]"]
    assert raw.strip().endswith("data: [DONE]")
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert "usage" in chunks[-1]


def test_empty_prompt_400(frontend):
    async def fn(client):
        status, _ = await _json(client, "POST", "/v1/completions",
                                {"prompt": "", "max_tokens": 4})
        assert status == 400

    with_client(frontend.app, fn)


def test_cluster_status(frontend):
    async def fn(client):
        status, body = await _json(client, "GET", "/cluster/status_json")
        assert status == 200
        assert body["stages"][0]["layers"] == [0, 2]

    with_client(frontend.app, fn)


def test_swarm_http_end_to_end(monkeypatch):
    """Scheduler HTTP frontend -> route -> head worker RPC -> pipeline ->
    tokens streamed back. The full 'parallax run + join' path."""
    from parallax_tpu.backend.run import build_swarm_frontend
    from parallax_tpu.p2p.node import WorkerNode
    from parallax_tpu.p2p.transport import TcpTransport
    from parallax_tpu.scheduling import node as node_mod
    from parallax_tpu.scheduling.scheduler import GlobalScheduler

    monkeypatch.setattr(
        node_mod.RooflinePerformanceModel, "max_layers_in_memory",
        lambda self, kv_fraction=0.35: 1,
    )
    sched = GlobalScheduler(TINY, min_nodes_bootstrapping=2)
    st = TcpTransport("scheduler", "127.0.0.1")
    frontend, service, _client = build_swarm_frontend(
        sched, st, SimpleTokenizer(), "tiny-swarm"
    )
    service.start()

    workers = []
    for _ in range(2):
        t = TcpTransport("", "127.0.0.1")
        t.start()
        t.peer_id = t.address
        w = WorkerNode(
            transport=t, scheduler_peer=st.address, model_config=TINY,
            engine_config=EngineConfig(page_size=8, num_pages=64,
                                       max_model_len=256, kv_dtype="float32"),
            heartbeat_interval_s=0.2,
        )
        workers.append(w)
    threads = [threading.Thread(target=w.start) for w in workers]
    for th in threads:
        th.start()
    for th in threads:
        th.join(60)

    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        s = sched.cluster_status()
        if s["num_pipelines"] and all(
            n["ready"] for p in s["pipelines"] for n in p["nodes"]
        ):
            break
        time.sleep(0.05)

    try:
        async def fn(client):
            status, body = await _json(client, "POST", "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hello swarm"}],
                 "max_tokens": 5, "temperature": 0})
            assert status == 200, body
            assert body["usage"]["completion_tokens"] == 5
            assert body["choices"][0]["finish_reason"] in ("length", "stop")

        with_client(frontend.app, fn)
    finally:
        for w in workers:
            w.stop()
        service.stop()


class ScriptedBackend:
    """Deterministic fake backend: emits a scripted token sequence over
    time, honors stop_fn by finishing the request early."""

    def __init__(self, tokens, interval_s=0.004):
        self.tokens = tokens
        self.interval_s = interval_s
        self.stopped: list[str] = []
        self.requests = {}

    def submit(self, req):
        ev = threading.Event()
        self.requests[req.request_id] = req

        def run():
            from parallax_tpu.runtime.request import RequestStatus

            for t in self.tokens:
                if req.status.is_finished:
                    break
                req.output_ids.append(t)
                time.sleep(self.interval_s)
            if not req.status.is_finished:
                req.status = RequestStatus.FINISHED_LENGTH
            ev.set()

        threading.Thread(target=run, daemon=True).start()
        return ev

    def stop(self, rid):
        from parallax_tpu.runtime.request import RequestStatus

        self.stopped.append(rid)
        req = self.requests.get(rid)
        if req is not None and not req.status.is_finished:
            req.status = RequestStatus.FINISHED_STOP


class JoinTokenizer:
    """Context-dependent decode ('-'.joined ids): per-token-span decoding
    would produce wrong separators, so these tests prove the frontend
    decodes the full output and emits text deltas (the BPE-safe scheme)."""

    vocab_size = 1000
    eos_token_ids = ()

    def encode(self, text):
        return [1, 2, 3]

    def decode(self, ids):
        return "-".join(str(i) for i in ids)

    def apply_chat_template(self, messages):
        return "x"


def _scripted_frontend(tokens, stop_backend=True):
    backend = ScriptedBackend(tokens)
    fe = OpenAIFrontend(
        JoinTokenizer(),
        submit_fn=backend.submit,
        model_name="scripted",
        stream_poll_s=0.002,
        stop_fn=backend.stop if stop_backend else None,
    )
    return fe, backend


def test_stop_string_nonstream_trims_and_stops_backend():
    fe, backend = _scripted_frontend(list(range(10, 30)))
    async def fn(client):
        # decoded stream: "10-11-12-13-..."; stop at "13"
        status, body = await _json(client, "POST", "/v1/completions",
            {"prompt": "p", "max_tokens": 50, "stop": ["13"]})
        assert status == 200, body
        choice = body["choices"][0]
        assert choice["text"] == "10-11-12-"
        assert choice["finish_reason"] == "stop"

    with_client(fe.app, fn)
    assert backend.stopped  # backend was told to finish early


def test_stop_string_streaming_trims_and_holds_back():
    fe, backend = _scripted_frontend(list(range(10, 30)))
    async def fn(client):
        resp = await client.post("/v1/completions", json={
            "prompt": "p", "max_tokens": 50, "stream": True,
            "stop": ["15-16"]})
        assert resp.status == 200
        return await resp.text()

    raw = with_client(fe.app, fn)
    chunks = [json.loads(line[6:]) for line in raw.splitlines()
              if line.startswith("data: ") and line != "data: [DONE]"]
    text = "".join(c["choices"][0].get("text", "") for c in chunks)
    assert text == "10-11-12-13-14-"
    assert chunks[-1]["choices"][0]["finish_reason"] == "stop"
    assert backend.stopped


def test_streaming_deltas_decode_full_context():
    # No stop strings: concatenated SSE deltas must equal the full decode,
    # which per-span decoding cannot produce with a context-dependent
    # tokenizer.
    fe, _ = _scripted_frontend([7, 8, 9, 10])
    async def fn(client):
        resp = await client.post("/v1/completions", json={
            "prompt": "p", "max_tokens": 50, "stream": True})
        assert resp.status == 200
        return await resp.text()

    raw = with_client(fe.app, fn)
    chunks = [json.loads(line[6:]) for line in raw.splitlines()
              if line.startswith("data: ") and line != "data: [DONE]"]
    text = "".join(c["choices"][0].get("text", "") for c in chunks)
    assert text == "7-8-9-10"


def test_streaming_never_emits_partial_utf8():
    # "é" = bytes C3 A9 split across two tokens: a poll landing between
    # them must not emit U+FFFD; the final text must be the real character.
    from parallax_tpu.backend.http_server import SimpleTokenizer

    backend = ScriptedBackend([0xC3, 0xA9, 0x41], interval_s=0.02)
    fe = OpenAIFrontend(
        SimpleTokenizer(), submit_fn=backend.submit, model_name="bytes",
        stream_poll_s=0.002, stop_fn=backend.stop,
    )

    async def fn(client):
        resp = await client.post("/v1/completions", json={
            "prompt": "p", "max_tokens": 50, "stream": True})
        assert resp.status == 200
        return await resp.text()

    raw = with_client(fe.app, fn)
    chunks = [json.loads(line[6:]) for line in raw.splitlines()
              if line.startswith("data: ") and line != "data: [DONE]"]
    deltas = [c["choices"][0].get("text", "") for c in chunks]
    assert all("�" not in d for d in deltas), deltas
    assert "".join(deltas) == "éA"


def test_invalid_seed_returns_400():
    fe, _ = _scripted_frontend([1, 2, 3])

    async def fn(client):
        status, body = await _json(client, "POST", "/v1/completions",
            {"prompt": "p", "max_tokens": 4, "seed": "not-a-number"})
        assert status == 400

    with_client(fe.app, fn)


def test_logprobs_returned_single_and_multi_stage():
    """logprobs=true returns one logprob per sampled token (computed on
    the LAST stage and carried back over the ring for pipelines)."""
    import math

    for bounds in ([(0, 2)], [(0, 1), (1, 2)]):
        engines = build_engines(bounds)
        fe, runner = build_local_frontend(
            engines, SimpleTokenizer(), model_name="tiny"
        )

        async def fn(client):
            status, body = await _json(client, "POST", "/v1/completions",
                {"prompt": "hello world", "max_tokens": 5,
                 "temperature": 0, "logprobs": True, "ignore_eos": True})
            assert status == 200, body
            lp = body["choices"][0]["logprobs"]
            assert len(lp["token_logprobs"]) == 5
            assert all(isinstance(x, float) and x <= 0.0 and math.isfinite(x)
                       for x in lp["token_logprobs"])
            # chat format variant
            status, body = await _json(client, "POST", "/v1/chat/completions",
                {"messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 3, "temperature": 0, "logprobs": True,
                 "ignore_eos": True})
            assert status == 200, body
            content = body["choices"][0]["logprobs"]["content"]
            assert len(content) == 3
            assert all("logprob" in e and "token" in e for e in content)

        with_client(fe.app, fn)
        runner.stop()


def test_no_logprobs_by_default():
    engines = build_engines([(0, 2)])
    fe, runner = build_local_frontend(
        engines, SimpleTokenizer(), model_name="tiny"
    )

    async def fn(client):
        status, body = await _json(client, "POST", "/v1/completions",
            {"prompt": "hello", "max_tokens": 3, "temperature": 0,
             "ignore_eos": True})
        assert status == 200
        assert "logprobs" not in body["choices"][0]

    with_client(fe.app, fn)
    runner.stop()


def test_streaming_logprobs():
    engines = build_engines([(0, 2)])
    fe, runner = build_local_frontend(
        engines, SimpleTokenizer(), model_name="tiny"
    )

    async def fn(client):
        resp = await client.post("/v1/completions", json={
            "prompt": "hello", "max_tokens": 5, "temperature": 0,
            "stream": True, "logprobs": True, "ignore_eos": True})
        assert resp.status == 200
        return await resp.text()

    raw = with_client(fe.app, fn)
    runner.stop()
    chunks = [json.loads(line[6:]) for line in raw.splitlines()
              if line.startswith("data: ") and line != "data: [DONE]"]
    lps = []
    for c in chunks:
        lp = c["choices"][0].get("logprobs")
        if lp:
            lps.extend(lp["token_logprobs"])
    assert len(lps) == 5
    assert all(x <= 0.0 for x in lps)


def test_profile_endpoints():
    fe, backend = _scripted_frontend([1, 2, 3])

    async def fn(client):
        import os
        import tempfile

        d = tempfile.mkdtemp()
        r = await client.post("/profile/start", json={"dir": d})
        assert r.status == 200
        # double-start conflicts
        r2 = await client.post("/profile/start", json={"dir": d})
        assert r2.status == 409
        r3 = await client.post("/profile/stop")
        assert r3.status == 200
        # trace artifacts written
        assert any(os.scandir(d))
        r4 = await client.post("/profile/stop")
        assert r4.status == 409

    with_client(fe.app, fn)


def test_adapter_model_variants():
    """Registered LoRA adapters appear as <model>:<adapter> entries in
    /v1/models, and selecting that model name routes the request to the
    adapter (the multi-LoRA OpenAI convention)."""
    import numpy as np

    engines = build_engines([(0, 2)])
    rng = np.random.default_rng(2)
    engines[0].load_adapter("tenant-x", {0: {"self_attn.q_proj": (
        rng.standard_normal((4, 64)).astype(np.float32),
        rng.standard_normal((64, 4)).astype(np.float32), 0.9,
    )}})
    fe, runner = build_local_frontend(
        engines, SimpleTokenizer(), model_name="tiny"
    )
    try:
        async def go(client):
            models = await (await client.get("/v1/models")).json()
            ids = [m["id"] for m in models["data"]]
            assert ids == ["tiny", "tiny:tenant-x"]
            base_body = {
                "messages": [{"role": "user", "content": "hello"}],
                "max_tokens": 6, "temperature": 0.0, "ignore_eos": True,
            }
            r1 = await client.post("/v1/chat/completions",
                                   json={**base_body, "model": "tiny"})
            r2 = await client.post(
                "/v1/chat/completions",
                json={**base_body, "model": "tiny:tenant-x"},
            )
            t1 = (await r1.json())["choices"][0]["message"]["content"]
            t2 = (await r2.json())["choices"][0]["message"]["content"]
            assert r1.status == r2.status == 200
            assert t1 != t2          # the adapter changed the stream
            # Unknown adapter via model suffix fails loudly, not as base.
            r3 = await client.post(
                "/v1/chat/completions",
                json={**base_body, "model": "tiny:nope"},
            )
            assert r3.status == 502
            return True

        assert with_client(fe.app, go)
    finally:
        runner.stop()
