"""OpenAI-compatible HTTP frontend + cluster control endpoints.

Capability parity: reference ``src/backend/main.py:26-277`` —
``/v1/chat/completions`` (streaming SSE + non-stream), ``/v1/models``,
``/v1/completions``, ``/scheduler/init`` (model switch), ``/cluster/status``
(ndjson stream) + ``/cluster/status_json``, ``/weight/refit`` — and the
RequestHandler retry ladder (``src/backend/server/request_handler.py:24-248``:
no-route -> 503, empty-route retries -> 429, forward retry, SSE
passthrough, TPS/TTFT accounting).

Built on aiohttp (FastAPI is not in the image). Tokenization uses a HF
tokenizer when a model path is available, else a whitespace/byte fallback
so synthetic deployments still serve.
"""

from __future__ import annotations

import asyncio
import json
import time
import uuid

from aiohttp import web

from parallax_tpu.runtime.request import Request, SamplingParams
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)


# SimpleTokenizer / load_tokenizer live in utils.tokenizer (shared with
# frontend-less swarm workers); re-exported here for compatibility.
from parallax_tpu.utils.tokenizer import SimpleTokenizer, load_tokenizer  # noqa: E402,F401
from parallax_tpu.obs import names as mnames


def _schema_from_body(body: dict) -> str | None:
    """OpenAI ``response_format`` -> schema string for constrained decoding.

    ``{"type": "json_object"}`` -> "{}" (any JSON); ``{"type":
    "json_schema", "json_schema": {"schema": {...}}}`` -> that schema.
    Raises ValueError (mapped to 400 by the caller) on unknown types.
    """
    rf = body.get("response_format")
    if not rf:
        return None
    import json as _json

    kind = rf.get("type") if isinstance(rf, dict) else None
    if kind in (None, "text"):
        return None
    if kind == "json_object":
        schema = "{}"
    elif kind == "json_schema":
        spec = rf.get("json_schema") or {}
        schema_keys = (
            "type", "enum", "const", "anyOf", "oneOf", "properties",
        )
        if "schema" in spec:
            inner = spec["schema"]
        elif any(k in spec for k in schema_keys):
            inner = spec          # schema passed inline, unwrapped
        else:
            raise ValueError(
                "response_format.json_schema needs a 'schema' object"
            )
        schema = _json.dumps(inner)
    else:
        raise ValueError(f"unsupported response_format type: {kind!r}")
    from parallax_tpu.constrained import validate_schema

    # Compile-check so an unsupported schema 400s before any tokens run.
    # lru-cached on the schema string; first compile of a big schema is
    # pure-Python work, so the async handler runs this parse in a thread
    # (see _parse_generation_request).
    validate_schema(schema)
    return schema


def _sampling_from_body(body: dict, default_max: int = 512) -> SamplingParams:
    seed = body.get("seed")
    if seed is not None:
        seed = int(seed)  # ValueError -> 400 in the caller
    logit_bias = body.get("logit_bias") or None
    if logit_bias is not None:
        if not isinstance(logit_bias, dict):
            raise ValueError("logit_bias must be an object of "
                             "token_id -> bias")
        logit_bias = {int(k): float(v) for k, v in logit_bias.items()}
    return SamplingParams(
        json_schema=_schema_from_body(body),
        logit_bias=logit_bias,
        temperature=float(body.get("temperature", 1.0)),
        top_p=float(body.get("top_p", 1.0)),
        top_k=int(body.get("top_k", -1)),
        min_p=float(body.get("min_p", 0.0)),
        presence_penalty=float(body.get("presence_penalty", 0.0)),
        frequency_penalty=float(body.get("frequency_penalty", 0.0)),
        repetition_penalty=float(body.get("repetition_penalty", 1.0)),
        max_new_tokens=int(
            body.get("max_tokens")
            or body.get("max_completion_tokens")
            or default_max
        ),
        stop_strings=tuple(
            [body["stop"]] if isinstance(body.get("stop"), str)
            else body.get("stop") or ()
        ),
        ignore_eos=bool(body.get("ignore_eos", False)),
        seed=seed,
        logprobs=bool(body.get("logprobs", False)),
    )


class IncrementalDecoder:
    """Streaming detokenizer with bounded per-update work.

    BPE detokenization is context-dependent, so per-token-span decodes break
    leading spaces and multi-byte UTF-8. Decoding the whole output every
    poll is O(n^2) and stalls the event loop on long generations. This uses
    the standard two-offset scheme: decode a short window
    ``ids[prefix_offset:n]``, emit only once the window doesn't end in a
    partial character (U+FFFD), then slide the window.
    """

    def __init__(self, tokenizer):
        self.tok = tokenizer
        self.prefix_offset = 0
        self.read_offset = 0
        self.text = ""  # decoded-and-stable text; grows append-only

    def update(self, ids: list[int]) -> str:
        """Feed the full token list; returns the stable decoded text."""
        n = len(ids)
        if n > self.read_offset:
            prefix = self.tok.decode(ids[self.prefix_offset:self.read_offset])
            window = self.tok.decode(ids[self.prefix_offset:n])
            if len(window) > len(prefix) and not window.endswith("�"):
                self.text += window[len(prefix):]
                self.prefix_offset = self.read_offset
                self.read_offset = n
        return self.text

    def finalize(self, ids: list[int]) -> str:
        """Flush everything, including a trailing partial character."""
        prefix = self.tok.decode(ids[self.prefix_offset:self.read_offset])
        window = self.tok.decode(ids[self.prefix_offset:])
        if len(window) > len(prefix):
            self.text += window[len(prefix):]
            self.prefix_offset = self.read_offset = len(ids)
        return self.text


def _stop_holdback(text: str, stops) -> int:
    """Chars to hold back: the longest text suffix that is a proper prefix
    of some stop string (it may complete into a match next poll)."""
    hold = 0
    for s in stops:
        for n in range(min(len(s) - 1, len(text)), 0, -1):
            if text.endswith(s[:n]):
                hold = max(hold, n)
                break
    return hold


def _newest_xplane(trace_dir: str | None) -> str | None:
    """The ``.xplane.pb`` the profiler wrote last under ``trace_dir``
    (``<dir>/plugins/profile/<time>/<host>.xplane.pb``)."""
    import glob
    import os

    found = glob.glob(
        os.path.join(trace_dir or "", "**", "*.xplane.pb"), recursive=True
    )
    return max(found, key=os.path.getmtime) if found else None


class BackendUnavailable(RuntimeError):
    """Raised by a ``submit_fn`` whose backend can no longer serve (its
    step loop died): the frontend answers 503, not the 429 of a full
    queue."""


class _GenFailed(Exception):
    """A request aborted or timed out before completing."""


class _StopScanner:
    """Stop-string search that only rescans text appended since last call
    (minus a max-stop-length overlap), keeping per-poll cost O(delta)."""

    def __init__(self, stops):
        self.stops = [s for s in stops if s]
        self._overlap = max((len(s) for s in self.stops), default=1) - 1
        self._pos = 0

    def find(self, text: str) -> int | None:
        if not self.stops:
            return None
        start = max(0, self._pos - self._overlap)
        best = None
        for s in self.stops:
            i = text.find(s, start)
            if i != -1 and (best is None or i < best):
                best = i
        self._pos = len(text)
        return best


class OpenAIFrontend:
    """HTTP app serving one swarm (or one local engine pipeline).

    The ``submit_fn(request) -> threading.Event`` and ``route_fn(rid) ->
    list[str] | None`` callables abstract over local pipelines and the
    networked swarm, so the same frontend runs on the scheduler host and in
    single-node mode (reference node_chat_http_server.py does the same via
    RPC stubs). ``stop_fn(rid)`` asks the backend to gracefully finish a
    request early (stop-string match).
    """

    def __init__(
        self,
        tokenizer,
        submit_fn,
        route_fn=None,
        status_fn=None,
        model_name: str = "parallax-tpu",
        stream_poll_s: float = 0.02,
        refit_fn=None,
        stop_fn=None,
        scheduler_init_fn=None,
        adapters_fn=None,
        healthz_fn=None,
        timeline_fn=None,
        qos_config=None,
        device_fn=None,
        profile_cluster_fn=None,
        request_spans_fn=None,
    ):
        self.tokenizer = tokenizer
        self.submit_fn = submit_fn
        self.route_fn = route_fn
        # Cache-aware routing: newer route callables accept the tokenized
        # prompt (``prompt_ids``/``lora_id``) so the dispatcher can hash
        # the prompt's block chain once and score pipelines against the
        # workers' published prefix digests. Older single-arg callables
        # (tests, custom frontends) keep working.
        self._route_takes_meta = False
        self._route_takes_tenant = False
        if route_fn is not None:
            try:
                import inspect

                params = inspect.signature(route_fn).parameters
                self._route_takes_meta = "prompt_ids" in params
                # Per-tenant routing fairness (docs/qos.md): newer route
                # callables accept the request's tenant so the
                # cache-aware router can charge its fairness term.
                self._route_takes_tenant = "tenant_id" in params
            except (TypeError, ValueError):  # builtins / C callables
                pass
        self.status_fn = status_fn
        self.refit_fn = refit_fn
        self.stop_fn = stop_fn
        self.adapters_fn = adapters_fn
        self.scheduler_init_fn = scheduler_init_fn
        # Deep health (stall watchdog summary) and cluster timeline
        # providers — None keeps the endpoints serving shallow/empty
        # payloads so scrapers need no feature detection.
        self.healthz_fn = healthz_fn
        self.timeline_fn = timeline_fn
        # Device attribution plane (obs/device.py): ``device_fn``
        # overrides the local plane payload for GET /debug/device — the
        # scheduler frontend wires the cluster merge here. None serves
        # the process-local payload (single-host serve, worker nodes).
        self.device_fn = device_fn
        # Cluster-scope profiling: ``profile_cluster_fn(action, pipeline,
        # dir, max_seconds) -> manifest`` fans the JAX profiler to every
        # stage of a pipeline over RPC. None = single-process profiling
        # only (a {"pipeline": ...} body 501s).
        self.profile_cluster_fn = profile_cluster_fn
        # ``request_spans_fn(rate)`` sets the engines' request-span
        # sampling rate while a profile runs (``/profile/start``'s
        # ``"request_spans"`` key) and, with None, restores the
        # configured one. None = the key is refused (501).
        self.request_spans_fn = request_spans_fn
        # Multi-tenant QoS (parallax_tpu/qos, docs/qos.md): when a
        # QoSConfig is wired, requests carry a class (header
        # ``x-parallax-qos-class`` / body ``qos_class``), a deadline
        # (``x-parallax-deadline-ms`` / ``deadline_ms``) and a tenant
        # (``x-parallax-tenant`` / ``tenant``; defaults to the LoRA
        # adapter). None = QoS off — no parsing, untagged requests,
        # bit-identical behavior.
        self.qos_config = qos_config
        self.model_name = model_name
        self.stream_poll_s = stream_poll_s
        self.app = web.Application(client_max_size=64 * 1024 * 1024)
        self._counters = {"requests": 0, "completion_tokens": 0,
                          "prompt_tokens": 0, "started_at": time.time()}
        # Unified metrics registry (obs/registry.py): the HTTP counters
        # are registry series now — /metrics renders the whole process
        # surface (engine histograms, cache counters, transport links)
        # with proper HELP/TYPE lines. The legacy ``_counters`` dict is
        # kept in lockstep for callers that read it directly.
        from parallax_tpu.obs.registry import get_registry

        reg = get_registry()
        self._m_requests = reg.counter(
            mnames.HTTP_REQUESTS_TOTAL,
            "Generation requests accepted by the HTTP frontend",
        )
        self._m_prompt_tokens = reg.counter(
            mnames.HTTP_PROMPT_TOKENS_TOTAL,
            "Prompt tokens across accepted requests",
        )
        self._m_completion_tokens = reg.counter(
            mnames.HTTP_COMPLETION_TOKENS_TOTAL,
            "Completion tokens generated (counted at request end)",
        )
        self._m_uptime = reg.gauge(
            mnames.HTTP_UPTIME_SECONDS, "Frontend process uptime",
        )
        self._m_http_ttft = reg.histogram(
            mnames.HTTP_TTFT_MS,
            "Client-observed time to first streamed token, milliseconds",
        )
        self._m_http_e2e = reg.histogram(
            mnames.HTTP_E2E_MS,
            "Client-observed request latency, milliseconds",
        )
        # Strong ref on self: the registry holds only a weakref.
        self._obs_collector = lambda: self._m_uptime.set(
            time.time() - self._counters["started_at"]
        )
        reg.register_collector(self._obs_collector)
        self.app.add_routes([
            web.get("/", self._root_redirect),
            web.post("/v1/chat/completions", self.chat_completions),
            web.post("/v1/completions", self.completions),
            web.get("/v1/models", self.models),
            web.get("/health", self.health),
            web.get("/healthz", self.healthz),
            web.get("/metrics", self.metrics),
            web.get("/chat", self.chat_page),
            web.get("/cluster/status", self.cluster_status_stream),
            web.get("/cluster/status_json", self.cluster_status_json),
            web.get("/debug/trace/{request_id}", self.debug_trace),
            web.get("/debug/device", self.debug_device),
            web.get("/debug/flight", self.debug_flight),
            web.get("/debug/timeline", self.debug_timeline),
            web.post("/weight/refit", self.weight_refit),
            web.post("/scheduler/init", self.scheduler_init),
            web.post("/profile/start", self.profile_start),
            web.post("/profile/stop", self.profile_stop),
        ])
        # A profile runs from ``start_trace``'s return until
        # ``stop_trace`` has returned; the stop itself runs on a thread
        # (``_profile_stop_task`` holds the auto-stop's).
        self._profiling = False
        self._profile_stopping = False
        self._profile_dir = None
        self._profile_request_spans = False
        self._profile_deadline_handle = None
        self._profile_stop_task = None
        # The loop ``run`` serves on, for the thread-safe ``shutdown``.
        self._loop = None
        self.app.on_startup.append(self._remember_loop)

        # Built-in web UI (setup/join/cluster/chat — reference src/frontend).
        from parallax_tpu.backend.webui import register_ui

        try:
            from parallax_tpu.models.presets import MODEL_DB, PRESETS

            ui_models = [model_name] + sorted(
                set(list(PRESETS) + list(MODEL_DB)) - {model_name}
            )
        except Exception:  # pragma: no cover
            ui_models = [model_name]
        register_ui(self.app, ui_models)

    async def _remember_loop(self, _app) -> None:
        self._loop = asyncio.get_running_loop()

    def shutdown(self, delay_s: float = 0.0) -> None:
        """Make ``run`` return, from any thread: the backend is gone
        and the process should exit. ``delay_s`` leaves handlers that
        are already answering time to send their error responses."""
        def _exit():
            raise web.GracefulExit()

        if self._loop is not None:
            self._loop.call_soon_threadsafe(
                self._loop.call_later, delay_s, _exit
            )

    # -- endpoints ---------------------------------------------------------

    async def _root_redirect(self, _req):
        raise web.HTTPFound("/ui")

    async def health(self, _req):
        return web.json_response({"status": "ok"})

    async def healthz(self, _req):
        """Deep health: the stall watchdog's per-component state machine
        (docs/observability.md). Liveness alone is ``/health``; this one
        answers "is the serving path actually making progress" — 503
        when any component is stalled so orchestrators can act on
        sick-but-alive processes. Shallow ok when no watchdog runs."""
        if self.healthz_fn is None:
            return web.json_response(
                {"status": "ok", "components": {}, "causes": []}
            )
        try:
            summary = self.healthz_fn()
        except Exception as e:
            return web.json_response(
                {"status": "unknown", "error": str(e)}, status=500
            )
        sick = summary.get("status") in ("stalled", "failed")
        return web.json_response(summary, status=503 if sick else 200)

    async def debug_timeline(self, request):
        """The merged cluster event timeline (obs/timeline.py): one
        causally-ordered story of churn episodes across every node's
        flight recorder plus the scheduler's own decisions.
        ``?format=chrome`` exports Chrome trace-event JSON (one lane per
        node) for chrome://tracing / Perfetto; ``?limit=`` bounds the
        JSON event list (default 1000)."""
        if self.timeline_fn is None:
            return self._error(
                404,
                "no cluster timeline on this endpoint (serve it from "
                "the scheduler frontend, or enable the local timeline)",
            )
        fmt = request.query.get("format", "json")
        try:
            limit = max(1, int(request.query.get("limit", "1000")))
        except ValueError:
            limit = 1000
        try:
            data = self.timeline_fn(fmt, limit)
        except Exception as e:
            return self._error(500, f"timeline export failed: {e}")
        return web.json_response(data)

    async def metrics(self, _req):
        """Prometheus text exposition of the process-wide registry:
        frontend counters plus every engine/cache/transport series, with
        ``# HELP``/``# TYPE`` lines and the version=0.0.4 content type
        scrapers require."""
        from parallax_tpu.obs.registry import (
            EXPOSITION_CONTENT_TYPE,
            get_registry,
        )

        text = get_registry().render()
        return web.Response(
            body=text.encode("utf-8"),
            headers={"Content-Type": EXPOSITION_CONTENT_TYPE},
        )

    async def debug_trace(self, request):
        """Chrome trace-event JSON for one sampled request
        (``EngineConfig.trace_sample_rate``); load in chrome://tracing
        or Perfetto. 404 for unknown/unsampled ids."""
        from parallax_tpu.obs.trace import get_trace_store

        rid = request.match_info["request_id"]
        data = get_trace_store().export_chrome(rid)
        if data is None:
            return self._error(
                404,
                f"no trace recorded for {rid!r} (tracing is sampled: "
                "set trace_sample_rate > 0)",
            )
        return web.json_response(data)

    async def debug_device(self, _req):
        """Device attribution plane (docs/memory.md, docs/kernels.md):
        the HBM ledger (per-class device bytes + headroom + invariant),
        the compile observatory (per-program-family compiles by cause)
        and per-program device-time shares. On the scheduler frontend
        this is the cluster merge; elsewhere the process-local plane."""
        if self.device_fn is not None:
            try:
                return web.json_response(self.device_fn() or {})
            except Exception as e:
                return self._error(500, f"device payload failed: {e}")
        from parallax_tpu.obs.device import get_device_plane

        return web.json_response(get_device_plane().payload())

    async def debug_flight(self, _req):
        """Flight recorder dump: recent request timelines, the slow ring,
        and notable engine events (preempt/kv_oom/abort_path/wire-dtype
        renegotiation/queue overflow)."""
        from parallax_tpu.obs.flight import get_flight

        return web.json_response(get_flight().snapshot())

    def _count_accept(self, req) -> None:
        """Count a request at accept time (client disconnects mid-stream
        must still be visible in /metrics)."""
        self._counters["requests"] += 1
        self._counters["prompt_tokens"] += req.num_prompt_tokens
        self._m_requests.inc()
        self._m_prompt_tokens.inc(req.num_prompt_tokens)

    def _count_completion(self, req, t_start=None) -> None:
        """Count generated tokens (and, when the request ran to an end the
        caller timed, its e2e latency). TTFT is observed where it is
        measured — the streaming loop's first-delta branch."""
        self._counters["completion_tokens"] += req.num_output_tokens
        self._m_completion_tokens.inc(req.num_output_tokens)
        if t_start is not None:
            self._m_http_e2e.observe((time.monotonic() - t_start) * 1e3)

    async def chat_page(self, _req):
        """Minimal built-in chat UI (reference serves chat.html from the
        node chat server, node_chat_http_server.py)."""
        return web.Response(text=_CHAT_HTML, content_type="text/html")

    async def models(self, _req):
        """Base model plus one ``<model>:<adapter>`` variant per
        registered LoRA adapter (the multi-LoRA serving convention, so
        stock OpenAI clients can select a tenant via the model field)."""
        names = [self.model_name]
        if self.adapters_fn is not None:
            names += [
                f"{self.model_name}:{a}" for a in self.adapters_fn()
            ]
        return web.json_response({
            "object": "list",
            "data": [{
                "id": name,
                "object": "model",
                "owned_by": "parallax-tpu",
            } for name in names],
        })

    def _request_lora(self, body: dict) -> str | None:
        """Adapter selection: explicit ``"lora"`` field, or the
        ``<model>:<adapter>`` model-name convention."""
        lora = body.get("lora")
        if lora:
            return lora
        m = body.get("model") or ""
        prefix = f"{self.model_name}:"
        if m.startswith(prefix):
            return m[len(prefix):] or None
        return None

    async def cluster_status_json(self, _req):
        status = self.status_fn() if self.status_fn else {}
        return web.json_response(status)

    async def cluster_status_stream(self, request):
        """NDJSON status stream. ``?interval=<seconds>`` sets the poll
        cadence (floored at 0.25 s so a hostile query cannot spin the
        event loop); a raising ``status_fn`` emits an ``{"error": ...}``
        record and keeps streaming instead of killing the connection
        mid-scrape."""
        try:
            interval = float(
                request.query.get("interval")
                or request.query.get("interval_s") or 2.0
            )
        except (TypeError, ValueError):
            interval = 2.0
        interval = max(0.25, interval)
        resp = web.StreamResponse(
            headers={"Content-Type": "application/x-ndjson"}
        )
        await resp.prepare(request)
        try:
            while True:
                try:
                    status = self.status_fn() if self.status_fn else {}
                except Exception as e:
                    logger.exception("status_fn failed")
                    status = {"error": str(e)}
                try:
                    payload = json.dumps(status)
                except (TypeError, ValueError) as e:
                    status = {"error": f"unserializable status: {e}"}
                    payload = json.dumps(status)
                await resp.write((payload + "\n").encode())
                await asyncio.sleep(interval)
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        return resp

    async def scheduler_init(self, request):
        """Live model switch (reference backend/main.py:99-155): stop the
        current global scheduler and bootstrap a fresh one for the new
        model; workers rejoin via heartbeat and reload their stage."""
        if self.scheduler_init_fn is None:
            return web.json_response(
                {"type": "scheduler_init",
                 "error": "model switch unavailable in this mode"},
                status=501,
            )
        body = await request.json()
        model_name = body.get("model_name")
        init_nodes_num = body.get("init_nodes_num")
        if model_name is None:
            return web.json_response(
                {"type": "scheduler_init", "error": "model_name is required"},
                status=400,
            )
        if init_nodes_num is None:
            return web.json_response(
                {"type": "scheduler_init",
                 "error": "init_nodes_num is required"},
                status=400,
            )
        try:
            info = await asyncio.to_thread(
                self.scheduler_init_fn, model_name, int(init_nodes_num)
            )
        except ValueError as e:
            return web.json_response(
                {"type": "scheduler_init", "error": str(e)}, status=400
            )
        except Exception as e:
            logger.exception("scheduler init failed")
            return web.json_response(
                {"type": "scheduler_init", "error": str(e)}, status=500
            )
        self.model_name = model_name
        return web.json_response({
            "type": "scheduler_init",
            "data": {"model_name": model_name,
                     "init_nodes_num": init_nodes_num, **(info or {})},
        })

    async def profile_start(self, request):
        """Start a JAX/XLA device trace (TensorBoard-viewable) while
        serving — the TPU-native answer to per-step timing logs: captures
        kernel timelines, HBM transfers and host gaps on live traffic.
        Beyond reference parity (it ships no tracer).

        ``max_seconds`` (body, default 120) is an auto-stop deadline: a
        forgotten ``start_trace`` buffers device events without bound, so
        an unattended profile now ends itself; an explicit
        ``/profile/stop`` before the deadline cancels the timer.

        Cluster scope: a ``{"pipeline": <id>}`` body fans the start to
        EVERY stage of that pipeline over RPC (``"all"`` = every
        pipeline) so the whole serving path traces one wall-clock
        window; the response is a per-node trace-dir manifest instead
        of the single-process ack. Each worker arms its own
        ``max_seconds`` auto-stop.

        The reply comes once ``start_trace`` has returned and carries
        this process's ``perf_counter_ns`` from just before it was
        called: with the stop's reading it brackets every event of the
        trace (the ``parallax.clock_sync`` span, obs/trace.py, marks the
        return).
        ``"request_spans": <0..1>`` samples per-request ``TraceStore``
        spans at that rate for requests submitted while the profile
        runs; the stop restores the configured rate.
        ``"profiler_options": {name: value}`` sets fields of
        ``jax.profiler.ProfileOptions`` for this profile, e.g.
        ``{"python_tracer_level": 0}``: without the Python-call tracer
        the ``parallax.*`` host spans and the device planes stay and
        the traced host runs nearer its untraced pace (measured:
        PERF.md, PR 25)."""
        import jax

        try:
            body = await request.json()
        except Exception:
            body = {}
        out_dir = body.get("dir") or "/tmp/parallax-profile"
        try:
            max_seconds = float(body.get("max_seconds", 120.0))
        except (TypeError, ValueError):
            return self._error(400, "max_seconds must be a number")
        if max_seconds <= 0:
            return self._error(400, "max_seconds must be > 0")
        if body.get("pipeline") is not None:
            return await self._profile_cluster(
                "start", body["pipeline"], out_dir, max_seconds
            )
        spans_rate = body.get("request_spans")
        if spans_rate is not None:
            try:
                spans_rate = float(spans_rate)
            except (TypeError, ValueError):
                return self._error(400, "request_spans must be a number")
            if not 0.0 <= spans_rate <= 1.0:
                return self._error(400, "request_spans must be in 0..1")
            if self.request_spans_fn is None:
                return self._error(
                    501, "request_spans is unavailable in this mode"
                )
        options = None
        wanted = body.get("profiler_options")
        if wanted:
            options = jax.profiler.ProfileOptions()
            for name, value in dict(wanted).items():
                if name.startswith("_") or not hasattr(options, name):
                    return self._error(
                        400, f"profiler_options: no field {name!r}"
                    )
                try:
                    setattr(options, name, value)
                except (TypeError, ValueError) as e:
                    return self._error(400, f"profiler_options: {e}")
        # Check AFTER the awaits: no suspension between test and set.
        if self._profiling:
            return self._error(409, "profiler already running")
        from parallax_tpu.obs.trace import clock_sync

        before_ns = time.perf_counter_ns()
        try:
            jax.profiler.start_trace(out_dir, profiler_options=options)
        except Exception as e:
            return self._error(500, f"profiler start failed: {e}")
        clock_sync()
        self._profiling = True
        self._profile_dir = out_dir
        if spans_rate is not None:
            self.request_spans_fn(spans_rate)
            self._profile_request_spans = True
        self._profile_deadline_handle = asyncio.get_running_loop().call_later(
            max_seconds, self._profile_deadline
        )
        return web.json_response({
            "profiling": True, "dir": out_dir, "max_seconds": max_seconds,
            # This process's clock just before ``start_trace`` was
            # called: no event of the trace is earlier.
            "perf_counter_ns": before_ns,
        })

    def _profile_deadline(self) -> None:
        """Auto-stop timer fired: end the trace (event-loop thread, same
        thread every profile handler runs on — no race with an explicit
        stop)."""
        self._profile_deadline_handle = None
        if not self._profiling or self._profile_stopping:
            return
        logger.warning("profiler auto-stop: max_seconds deadline reached")
        self._profile_stop_task = asyncio.ensure_future(
            self._stop_profile()
        )

    async def _stop_profile(self) -> dict | None:
        """``stop_trace`` on a thread: it writes the whole trace (14-35 s
        for 4 s of a 7B decode; PERF.md, PR 25), and streams must keep
        flowing meanwhile. ``_profiling`` clears only when it has returned. The
        reply's fields, or None where ``stop_trace`` raised (logged).

        The device tracer stops some ms after the call, so the thread
        then reads the trace once for the end of its last device event
        (``traced_device_end_ns``): with a device that never idles, the
        clock at the call would leave device events outside the span the
        two replies give."""
        from parallax_tpu.obs.trace import clock_sync, traced_device_end_ns

        def stop():
            import jax

            now_ns = clock_sync()
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            seconds = time.perf_counter() - t0
            xplane = _newest_xplane(self._profile_dir)
            end_ns = traced_device_end_ns(xplane) if xplane else None
            return max(now_ns, end_ns or 0), seconds, xplane

        self._profile_stopping = True
        try:
            end_ns, seconds, xplane = await asyncio.to_thread(stop)
        except Exception:
            logger.exception("profiler stop failed")
            return None
        finally:
            self._profiling = self._profile_stopping = False
            if self._profile_request_spans:
                self._profile_request_spans = False
                self.request_spans_fn(None)
        return {
            "profiling": False,
            # This process's clock at the end of the trace's last device
            # event (at the call of ``stop_trace`` where it holds none),
            # the seconds the write took, and the trace it wrote.
            "perf_counter_ns": end_ns,
            "stop_seconds": seconds,
            "xplane": xplane,
        }

    async def _profile_cluster(self, action, pipeline, out_dir,
                               max_seconds):
        """Fan a profiler action to a pipeline's stages; reply is the
        per-node manifest ({node_id, profiling, dir} or {error} rows)."""
        if self.profile_cluster_fn is None:
            return web.json_response(
                {"error": "cluster-scope profiling unavailable in this "
                          "mode (no swarm scheduler on this frontend)"},
                status=501,
            )
        try:
            manifest = await asyncio.to_thread(
                self.profile_cluster_fn, action, pipeline, out_dir,
                max_seconds,
            )
        except ValueError as e:
            return self._error(400, str(e))
        except Exception as e:
            logger.exception("cluster profile %s failed", action)
            return self._error(500, f"cluster profile failed: {e}")
        return web.json_response({
            "profiling": action == "start",
            "pipeline": pipeline,
            "nodes": manifest,
        })

    async def profile_stop(self, request):
        try:
            body = await request.json()
        except Exception:
            body = {}
        if body.get("pipeline") is not None:
            return await self._profile_cluster(
                "stop", body["pipeline"], None, 0.0
            )
        if not self._profiling:
            return self._error(409, "profiler not running")
        if self._profile_stopping:
            return self._error(409, "profiler is stopping")
        if self._profile_deadline_handle is not None:
            self._profile_deadline_handle.cancel()
            self._profile_deadline_handle = None
        reply = await self._stop_profile()
        if reply is None:
            return self._error(500, "profiler stop failed")
        return web.json_response(reply)

    async def weight_refit(self, request):
        if self.refit_fn is None:
            return web.json_response({"error": "refit unavailable"}, status=501)
        body = await request.json()
        version = self.refit_fn(body.get("index_map") or {})
        return web.json_response({"version": version})

    async def chat_completions(self, request):
        try:
            body = await request.json()
        except Exception:
            return self._error(400, "invalid JSON body")
        messages = body.get("messages") or []
        try:
            prompt_text = self.tokenizer.apply_chat_template(messages)
        except Exception:
            prompt_text = "\n".join(m.get("content", "") for m in messages)
        return await self._generate(request, body, prompt_text, chat=True)

    async def completions(self, request):
        try:
            body = await request.json()
        except Exception:
            return self._error(400, "invalid JSON body")
        return await self._generate(
            request, body, body.get("prompt", ""), chat=False
        )

    # -- core generation ---------------------------------------------------

    async def _generate(self, http_request, body: dict,
                        prompt: str | list[int], chat: bool):
        rid = f"chatcmpl-{uuid.uuid4().hex[:16]}"
        if isinstance(prompt, list):
            # OpenAI's token-array prompt, the input twin of the
            # ``token_ids`` a choice carries: the only way to continue
            # a stream whose ids the tokenizer has no text for.
            if not all(type(t) is int and t >= 0 for t in prompt):
                return self._error(
                    400, "a token-array prompt holds non-negative integers"
                )
            prompt_ids = prompt
        else:
            prompt_ids = self.tokenizer.encode(prompt)
        if not prompt_ids:
            return self._error(400, "empty prompt")
        try:
            # In a thread: schema validation compiles a DFA (pure-Python,
            # potentially hundreds of ms for big schemas) and must not
            # stall the event loop for in-flight streams.
            sampling_params = await asyncio.to_thread(
                _sampling_from_body, body
            )
        except (TypeError, ValueError) as e:
            return self._error(400, f"invalid sampling parameter: {e}")

        try:
            raw_n = body.get("n")
            n_choices = 1 if raw_n is None else int(raw_n)
        except (TypeError, ValueError):
            return self._error(400, "n must be an integer")
        if not 1 <= n_choices <= 8:
            return self._error(400, "n must be between 1 and 8")
        if n_choices > 1 and body.get("stream"):
            return self._error(400, "streaming with n > 1 is not supported")

        # Multi-tenant QoS (docs/qos.md): class / deadline / tenant from
        # headers and body. All None while QoS is off.
        lora_id = self._request_lora(body)
        qos_class = deadline = tenant_id = None
        if self.qos_config is not None:
            from parallax_tpu.qos import qos_from_http

            try:
                qos_class, deadline_ms, tenant_id = qos_from_http(
                    http_request.headers, body, self.qos_config,
                )
            except (TypeError, ValueError) as e:
                return self._error(400, f"invalid QoS parameter: {e}")
            deadline = time.monotonic() + deadline_ms / 1e3
            if tenant_id is None:
                tenant_id = lora_id

        # Routing with retry ladder (reference request_handler.py:100-245:
        # None path -> 503 after retries; engine full -> 429).
        routing_table: list[str] = []
        if self.route_fn is not None:
            if self._route_takes_meta:
                kwargs = {"prompt_ids": list(prompt_ids),
                          "lora_id": lora_id}
                if self._route_takes_tenant:
                    kwargs["tenant_id"] = tenant_id
                    kwargs["qos_class"] = qos_class
                path = await asyncio.to_thread(
                    self.route_fn, rid, **kwargs,
                )
            else:
                path = await asyncio.to_thread(self.route_fn, rid)
            if path is None:
                return self._error(503, "no serviceable pipeline")
            routing_table = path

        if n_choices > 1:
            return await self._generate_n(
                rid, body, prompt_ids, sampling_params, routing_table,
                chat, n_choices,
                qos=(qos_class, deadline, tenant_id),
            )

        req = Request(
            request_id=rid,
            prompt_ids=list(prompt_ids),
            sampling_params=sampling_params,
            routing_table=routing_table,
            eos_token_ids=tuple(self.tokenizer.eos_token_ids),
            # Per-request adapter (reference Req.lora_path): "lora" in
            # the body or the <model>:<adapter> model-name convention.
            lora_id=lora_id,
            qos_class=qos_class,
            deadline=deadline,
            tenant_id=tenant_id,
        )
        # Count at accept time, not in usage formatting: client disconnects
        # mid-stream must still be visible in /metrics.
        self._count_accept(req)
        t_start = time.monotonic()
        try:
            done = await asyncio.to_thread(self.submit_fn, req)
        except ValueError as e:
            return self._error(400, str(e))
        except BackendUnavailable as e:
            return self._error(503, str(e))
        except RuntimeError as e:
            return self._error(429, str(e))
        except asyncio.CancelledError:
            # Disconnect while the submit thread was in flight: the
            # submission may still have landed — stop it best-effort.
            await self._request_stop(req)
            raise

        if body.get("stream"):
            return await self._stream_response(
                http_request, req, done, chat, t_start
            )
        try:
            # finally (not except): client disconnects cancel this handler
            # mid-wait, and generated tokens must still reach /metrics.
            try:
                text, stop_matched = await self._await_completion(req, done)
            except _GenFailed as e:
                return self._error(502, f"generation failed: {e}")
            except asyncio.CancelledError:
                # Client disconnected: stop the engine work (also unblocks
                # the done.wait waiter thread) instead of generating to
                # max_tokens unobserved.
                await self._request_stop(req)
                raise
            return web.json_response(
                self._completion_body(
                    req, text, chat, t_start,
                    finish_override="stop" if stop_matched else None,
                )
            )
        finally:
            self._count_completion(req, t_start)

    async def _generate_n(self, rid, body, prompt_ids, sampling_params,
                          routing_table, chat, n_choices,
                          qos=(None, None, None)):
        """OpenAI ``n`` > 1: n independent generations on one pipeline path,
        merged into one choices array. (The reference's engine protocol has
        no multi-choice support; the vllm-rs frontend expands client-side
        the same way.) Seeded requests get seed+i per choice so the
        choices differ; greedy requests will legitimately all match."""
        import dataclasses as _dc

        async def abandon(started: list) -> None:
            # Stop every already-running sibling and account its tokens —
            # stopping finishes the request, so the parked done.wait
            # threads (if any) unblock too.
            for r in started:
                await self._request_stop(r)
                self._count_completion(r)

        reqs, dones = [], []
        for i in range(n_choices):
            sp = sampling_params
            if sp.seed is not None:
                sp = _dc.replace(sp, seed=sp.seed + i)
            req = Request(
                request_id=f"{rid}-{i}",
                prompt_ids=list(prompt_ids),
                sampling_params=sp,
                routing_table=list(routing_table),
                eos_token_ids=tuple(self.tokenizer.eos_token_ids),
                lora_id=self._request_lora(body),
                qos_class=qos[0],
                deadline=qos[1],
                tenant_id=qos[2],
            )
            try:
                done = await asyncio.to_thread(self.submit_fn, req)
            except ValueError as e:
                await abandon(reqs)
                return self._error(400, str(e))
            except BackendUnavailable as e:
                await abandon(reqs)
                return self._error(503, str(e))
            except RuntimeError as e:
                await abandon(reqs)
                return self._error(429, str(e))
            except asyncio.CancelledError:
                # Disconnect while still submitting: earlier choices are
                # already running, and the in-flight submission may still
                # have landed in the worker thread — stop and account all
                # of them.
                await abandon(reqs + [req])
                raise
            # Count only actually-submitted choices (at accept time, so a
            # later disconnect is still visible in /metrics).
            self._count_accept(req)
            reqs.append(req)
            dones.append(done)
        t_start = time.monotonic()

        try:
            results = await asyncio.gather(
                *(self._await_completion(r, d) for r, d in zip(reqs, dones)),
                return_exceptions=True,
            )
        except asyncio.CancelledError:
            # Client disconnected: stop the engine work (which also
            # unblocks the waiter threads) instead of letting n choices
            # generate to max_tokens unobserved. abandon() records the
            # tokens generated so far.
            await abandon(reqs)
            raise
        # Tokens generated before a failure must still reach /metrics.
        for req in reqs:
            self._count_completion(req, t_start)
        failures = [r for r in results if isinstance(r, BaseException)]
        if failures:
            for req in reqs:
                await self._request_stop(req)
            return self._error(502, f"generation failed: {failures[0]}")

        choices = []
        bodies = []
        for i, (req, (text, stop_matched)) in enumerate(zip(reqs, results)):
            body_i = self._completion_body(
                req, text, chat, t_start,
                finish_override="stop" if stop_matched else None,
            )
            bodies.append(body_i)
            c = body_i["choices"][0]
            c["index"] = i
            choices.append(c)
        # Compose the merged envelope from the per-choice bodies (one
        # source of truth for the envelope/usage schema) and sum the
        # usage numbers.
        merged = dict(bodies[0], id=rid, choices=choices)
        usage = dict(bodies[0]["usage"])
        for b in bodies[1:]:
            # Prompt tokens count once (OpenAI semantics: one prompt, n
            # choices); completions and throughput sum across choices.
            for key in ("completion_tokens", "tokens_per_second"):
                usage[key] = round(usage[key] + b["usage"][key], 2)
        usage["total_tokens"] = (
            usage["prompt_tokens"] + usage["completion_tokens"]
        )
        merged["usage"] = usage
        return web.json_response(merged)

    async def _await_completion(self, req, done) -> tuple[str, bool]:
        """Wait for one request's generation; returns (text, stop_matched).
        Raises _GenFailed on abort/timeout. Stop strings end generation
        early via the poll loop instead of silently running to
        EOS/max_tokens."""
        stops = req.sampling_params.stop_strings
        stop_idx = None
        dec = IncrementalDecoder(self.tokenizer)
        scanner = _StopScanner(stops)
        if stops:
            deadline = time.monotonic() + 600.0
            checked = 0
            while not req.status.is_finished:
                if time.monotonic() > deadline:
                    req.abort("deadline exceeded")
                    break
                n = len(req.output_ids)
                if n > checked:
                    checked = n
                    text = dec.update(list(req.output_ids[:n]))
                    stop_idx = scanner.find(text)
                    if stop_idx is not None:
                        await self._request_stop(req)
                        break
                await asyncio.sleep(self.stream_poll_s)
            ok = req.status.is_finished or stop_idx is not None
        else:
            ok = await asyncio.to_thread(done.wait, 600.0)
        if not ok or req.status.value == "finished_abort":
            raise _GenFailed(req.abort_reason or "timeout")
        text = dec.finalize(list(req.output_ids))
        if stop_idx is None and stops:
            stop_idx = scanner.find(text)
        stop_matched = stop_idx is not None
        if stop_idx is not None:
            text = text[:stop_idx]
        return text, stop_matched

    async def _stream_response(self, http_request, req, done, chat, t_start):
        resp = web.StreamResponse(headers={
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
        })
        resp.enable_chunked_encoding()
        await resp.prepare(http_request)
        try:
            return await self._stream_body(resp, req, chat, t_start)
        except asyncio.CancelledError:
            # Client went away mid-stream (handler_cancellation=True):
            # stop the engine work instead of generating to max_tokens
            # with nobody reading.
            await self._request_stop(req)
            raise
        finally:
            self._count_completion(req, t_start)

    async def _request_stop(self, req) -> None:
        """Ask the backend to finish ``req`` early (stop-string match)."""
        if self.stop_fn is not None:
            try:
                await asyncio.to_thread(self.stop_fn, req.request_id)
            except Exception as e:
                logger.warning("stop_fn failed for %s: %s", req.request_id, e)

    async def _stream_body(self, resp, req, chat, t_start):
        # BPE detokenization is context-dependent: per-token-span decodes
        # break leading spaces and multi-token UTF-8 sequences, so deltas
        # come from an incremental decoder (bounded per-poll work) and stop
        # strings are scanned over appended text only.
        stops = req.sampling_params.stop_strings
        dec = IncrementalDecoder(self.tokenizer)
        scanner = _StopScanner(stops)
        seen_tokens = 0
        emitted = ""
        lp_sent = 0
        ttft_ms = None
        stop_matched = False
        deadline = time.monotonic() + 600.0
        while True:
            n = len(req.output_ids)
            if n > seen_tokens:
                if ttft_ms is None:
                    ttft_ms = (time.monotonic() - t_start) * 1e3
                    self._m_http_ttft.observe(ttft_ms)
                seen_tokens = n
                full = dec.update(list(req.output_ids[:n]))
                idx = scanner.find(full) if stops else None
                lp_entries, lp_sent = self._stream_logprob_entries(
                    req, lp_sent
                )
                if idx is not None:
                    final = full[:idx]
                    if len(final) > len(emitted) or lp_entries:
                        await resp.write(self._sse_chunk(
                            req, final[len(emitted):], chat,
                            lp_entries=lp_entries,
                        ))
                        emitted = final
                    stop_matched = True
                    await self._request_stop(req)
                    break
                # Hold back any suffix that could become a stop match.
                safe = len(full) - (_stop_holdback(full, stops) if stops else 0)
                if safe > len(emitted) or lp_entries:
                    await resp.write(self._sse_chunk(
                        req, full[len(emitted):safe], chat,
                        lp_entries=lp_entries,
                    ))
                    emitted = full[:safe]
            if req.status.is_finished:
                break
            if time.monotonic() > deadline:
                req.abort("stream deadline exceeded")
                break
            await asyncio.sleep(self.stream_poll_s)
        if not stop_matched:
            # Flush whatever was held back / arrived after the last poll.
            full = dec.finalize(list(req.output_ids))
            idx = scanner.find(full) if stops else None
            if idx is not None:
                full = full[:idx]
                stop_matched = True
            lp_entries, lp_sent = self._stream_logprob_entries(req, lp_sent)
            if len(full) > len(emitted) or lp_entries:
                await resp.write(self._sse_chunk(
                    req, full[len(emitted):], chat, lp_entries=lp_entries,
                ))
        usage = self._usage(req, t_start, ttft_ms)
        await resp.write(self._sse_chunk(
            req, "", chat, finish=True, usage=usage,
            finish_override="stop" if stop_matched else None,
        ))
        await resp.write(b"data: [DONE]\n\n")
        return resp

    def _sse_chunk(self, req, delta_text, chat, finish=False, usage=None,
                   finish_override=None, lp_entries=None) -> bytes:
        reason = (
            (finish_override or self._finish_reason(req)) if finish else None
        )
        if chat:
            delta = {} if finish else {"content": delta_text}
            choice = {
                "index": 0,
                "delta": delta,
                "finish_reason": reason,
            }
            obj = "chat.completion.chunk"
            if lp_entries:
                choice["logprobs"] = {"content": [
                    {"token": t, "logprob": lp} for t, lp in lp_entries
                ]}
        else:
            choice = {
                "index": 0,
                "text": delta_text,
                "finish_reason": reason,
            }
            obj = "text_completion"
            if lp_entries:
                choice["logprobs"] = {
                    "tokens": [t for t, _ in lp_entries],
                    "token_logprobs": [lp for _, lp in lp_entries],
                }
        payload = {
            "id": req.request_id,
            "object": obj,
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [choice],
        }
        if usage:
            payload["usage"] = usage
        return f"data: {json.dumps(payload)}\n\n".encode()

    def _stream_logprob_entries(self, req, lp_sent):
        """New (token_text, logprob) pairs since the last chunk."""
        if not req.sampling_params.logprobs:
            return None, lp_sent
        n = min(len(req.output_ids), len(req.output_logprobs))
        if n <= lp_sent:
            return None, lp_sent
        entries = [
            (self.tokenizer.decode([req.output_ids[i]]),
             req.output_logprobs[i])
            for i in range(lp_sent, n)
        ]
        return entries, n

    def _logprobs_payload(self, req, chat):
        """OpenAI-format logprobs for the committed tokens (chat: content
        entries; completions: parallel token/logprob arrays)."""
        if not req.sampling_params.logprobs or not req.output_logprobs:
            return None
        n = min(len(req.output_ids), len(req.output_logprobs))
        toks = [self.tokenizer.decode([t]) for t in req.output_ids[:n]]
        if chat:
            return {"content": [
                {"token": tok, "logprob": lp}
                for tok, lp in zip(toks, req.output_logprobs[:n])
            ]}
        return {"tokens": toks,
                "token_logprobs": list(req.output_logprobs[:n])}

    def _completion_body(self, req, text, chat, t_start, finish_override=None):
        reason = finish_override or self._finish_reason(req)
        lp = self._logprobs_payload(req, chat)
        if chat:
            choice = {
                "index": 0,
                "message": {"role": "assistant", "content": text},
                "finish_reason": reason,
            }
            obj = "chat.completion"
        else:
            choice = {
                "index": 0,
                "text": text,
                "finish_reason": reason,
            }
            obj = "text_completion"
        # The committed ids themselves: text cannot show them where the
        # tokenizer has no text for an id (byte fallback past 255).
        choice["token_ids"] = list(req.output_ids)
        if lp is not None:
            choice["logprobs"] = lp
        return {
            "id": req.request_id,
            "object": obj,
            "created": int(time.time()),
            "model": self.model_name,
            "choices": [choice],
            "usage": self._usage(req, t_start, None),
        }

    def _usage(self, req, t_start, ttft_ms):
        elapsed = max(1e-6, time.monotonic() - t_start)
        usage = {
            "prompt_tokens": req.num_prompt_tokens,
            "completion_tokens": req.num_output_tokens,
            "total_tokens": req.total_len,
            "tokens_per_second": round(req.num_output_tokens / elapsed, 2),
            # Prompt tokens served from the prefix cache (OpenAI's field).
            "prompt_tokens_details": {
                "cached_tokens": req.num_cached_tokens,
            },
        }
        if ttft_ms is not None:
            usage["ttft_ms"] = round(ttft_ms, 1)
        return usage

    @staticmethod
    def _finish_reason(req) -> str:
        return {
            "finished_eos": "stop",
            "finished_stop": "stop",
            "finished_length": "length",
            "finished_abort": "abort",
        }.get(req.status.value, "stop")

    @staticmethod
    def _error(status: int, message: str):
        return web.json_response(
            {"error": {"message": message, "type": "invalid_request_error"}},
            status=status,
        )

    # -- run ---------------------------------------------------------------

    def run(self, host: str = "0.0.0.0", port: int = 8000) -> None:
        import threading

        kwargs = {}
        if threading.current_thread() is not threading.main_thread():
            # Signal handlers only install on the main thread.
            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            kwargs = {"handle_signals": False, "loop": loop}
        # Cancel handlers when the client goes away (off by default since
        # aiohttp 3.9) so a disconnect stops the engine work via the
        # CancelledError cleanup paths instead of generating to
        # max_tokens unobserved.
        web.run_app(self.app, host=host, port=port, print=None,
                    handler_cancellation=True, **kwargs)


_CHAT_HTML = """<!doctype html><html><head><meta charset="utf-8">
<title>parallax-tpu chat</title><style>
body{font-family:system-ui;margin:0;display:flex;flex-direction:column;
height:100vh;background:#111;color:#eee}
#log{flex:1;overflow-y:auto;padding:16px;max-width:760px;margin:0 auto;width:100%}
.msg{margin:8px 0;padding:10px 14px;border-radius:10px;white-space:pre-wrap}
.user{background:#2a4365}.bot{background:#222}
#bar{display:flex;padding:12px;gap:8px;max-width:760px;margin:0 auto;width:100%}
#inp{flex:1;padding:10px;border-radius:8px;border:1px solid #444;
background:#1a1a1a;color:#eee}button{padding:10px 18px;border-radius:8px;
border:none;background:#3182ce;color:#fff;cursor:pointer}
</style></head><body><div id="log"></div><div id="bar">
<input id="inp" placeholder="message..." autofocus><button id="go">send</button>
</div><script>
const log=document.getElementById('log'),inp=document.getElementById('inp');
const btn=document.getElementById('go');
const history=[];let busy=false;
async function send(){
 if(busy)return;
 const text=inp.value.trim(); if(!text)return; inp.value='';
 busy=true;btn.disabled=true;
 history.push({role:'user',content:text});
 add('user',text); const el=add('bot','');
 try{
  const r=await fetch('/v1/chat/completions',{method:'POST',
   headers:{'Content-Type':'application/json'},
   body:JSON.stringify({model:'parallax-tpu',messages:history,
    stream:true,max_tokens:512})});
  if(!r.ok){const err=await r.text();
   el.textContent='[error '+r.status+': '+err.slice(0,200)+']';
   history.pop();return;}
  const rd=r.body.getReader(),dec=new TextDecoder();let acc='',buf='';
  for(;;){const{done,value}=await rd.read();if(done)break;
   buf+=dec.decode(value,{stream:true});
   const lines=buf.split('\\n');buf=lines.pop();
   for(const line of lines){if(!line.startsWith('data: '))continue;
    const d=line.slice(6);if(d==='[DONE]')continue;
    try{const c=JSON.parse(d).choices[0].delta?.content;
     if(c){acc+=c;el.textContent=acc;log.scrollTop=log.scrollHeight}}catch(e){}}}
  history.push({role:'assistant',content:acc});
 }catch(e){el.textContent='[network error: '+e+']';history.pop();}
 finally{busy=false;btn.disabled=false;inp.focus();}}
function add(cls,text){const d=document.createElement('div');
 d.className='msg '+cls;d.textContent=text;log.appendChild(d);
 log.scrollTop=log.scrollHeight;return d}
btn.onclick=send;
inp.addEventListener('keydown',e=>{if(e.key==='Enter')send()});
</script></body></html>"""
