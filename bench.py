"""Offline serving benchmark: output tokens/sec/chip on the north-star config.

North-star (BASELINE.md): output tokens/sec/chip + p50 TTFT, Qwen2.5-7B,
2-stage pipeline parallel. One real chip is available, so we run one
chip's workload of the 2-stage setup — half the model's decoder layers,
plus embed + lm_head + sampling (a real stage carries one of the two
ends; we carry both, which over-counts slightly and is therefore
conservative) — with continuous batching, and report

    tokens/sec/chip = decode_batch / (2 * stage_decode_step_time)

— the steady-state 2-chip pipeline emits one decode batch per stage step
(stages overlap on different token waves). ``ttft_p50_ms`` is the median
time from request submission to its first sampled token across the full
measured batch (all requests submitted at t=0; the number includes queue
+ chunked prefill, the honest offline-batch definition).

The bench decodes with the engine's fused multi-step path
(``decode_lookahead=32``: k forward+sample steps in one ``lax.scan``
dispatch) chained through the pipelined decode (``decode_pipeline=7``:
each window is dispatched from the previous window's device-resident
carry before its tokens are read back). Knobs: ``BENCH_LOOKAHEAD`` /
``BENCH_PIPELINE`` / ``BENCH_BATCH`` (``BENCH_LOOKAHEAD=1`` measures the
unfused path) / ``BENCH_TEMP`` (sampled decode).

Entry contract: ``python bench.py`` runs the measurement once, in a
child process of its own, on the backend JAX gives it, prints the
child's one JSON line and exits with the child's code — a failed run is
a non-zero exit, never a substitute number. The parent never imports
JAX (a chip belongs to one process). ``BENCH_CPU=1`` is the tests' CPU
fixture: a tiny model on the CPU backend whose record says
``"platform": "cpu"``; its number is a smoke value, not a device metric.
The persistent compilation cache is placed by
``utils/compile_cache.enable_compilation_cache``.

``BENCH_MODEL=dsa`` switches to the sparse-attention benchmark:
DeepSeek-V3.2 attention geometry (MLA latent cache + lightning indexer,
``index_topk=2048``) at ``BENCH_CTX`` context (default 8192), reduced to
a 4-layer dense-FFN stage so one chip holds it. Its ``vs_baseline``
compares achieved HBM bandwidth against the 40%-of-roofline efficiency
the main number's baseline assumes (1.0 == SGLang-class efficiency).

``BENCH_MODEL=hybrid`` benchmarks the hybrid linear-attention path:
Qwen3-Next per-layer geometry (GatedDeltaNet 3:1 + gated full attention,
MoE FFN) on a reduced-depth stage, decoding through the FUSED multistep
window (the recurrence advances inside the scan). Same
bandwidth-efficiency ``vs_baseline`` convention as the DSA mode.

``vs_baseline`` (default mode) compares against a roofline-derived
estimate of the reference's CUDA backend on 2xA100-80G (the repo
publishes no numbers — BASELINE.json ``published: {}``): decode at batch
64 is HBM-bound; each stage streams ~7.6 GB of bf16 params per step =>
2039 GB/s / 7.6 GB ~= 268 steps/s theoretical, ~40% achieved for
SGLang-class engines => ~107 steps/s => 64 tokens / (2 chips * step)
~= 3400 theoretical, ~1360 achieved tok/s/chip. We use 1360.

Prints ONE JSON line.
"""

import dataclasses
import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_TOKENS_PER_SEC_PER_CHIP = 1360.0


def main():
    if os.environ.get("BENCH_CHILD"):
        return _bench()
    # The parent stays off JAX: the chip belongs to the one child.
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__)],
        env=dict(os.environ, BENCH_CHILD="1"),
    )
    sys.exit(out.returncode)


def _transport_probe(cfg, stage_params_fn, kv_dtype, page_size):
    """Two-stage loopback swarm, clean vs slow-peer links (see the call
    site). Returns the probe record for ``detail.transport``."""
    import statistics
    import time as _time

    import numpy as np

    from parallax_tpu.p2p.node import WorkerNode
    from parallax_tpu.p2p.transport import LoopbackTransport
    from parallax_tpu.runtime.engine import EngineConfig
    from parallax_tpu.runtime.request import Request, SamplingParams

    delay_s = float(os.environ.get("BENCH_TRANSPORT_DELAY_S", "0.05"))
    n_req, prompt_len, gen_len = 4, 16, 16
    split = max(1, cfg.num_hidden_layers // 2)
    max_model_len = prompt_len + gen_len + 2 * page_size

    def run(delay: float) -> dict:
        registry: dict = {}
        transports = [
            LoopbackTransport("tw0", registry),
            LoopbackTransport("tw1", registry),
        ]
        if delay:
            # Slow peer: every data-plane send pays the delay (gossip
            # rides call(), which stays fast — only the activation path
            # is stalled, exactly what a congested WAN link does).
            for t in transports:
                real = t.send

                def slow(peer, method, payload, _real=real):
                    _time.sleep(delay)
                    _real(peer, method, payload)

                t.send = slow
        ecfg = EngineConfig(
            page_size=page_size,
            num_pages=n_req * (max_model_len // page_size + 2) + 8,
            max_batch_size=n_req, max_model_len=max_model_len,
            kv_dtype=kv_dtype, enable_prefix_cache=False,
        )
        workers = [
            WorkerNode(
                transport=transports[i],
                scheduler_peer=None,
                model_config=cfg,
                engine_config=ecfg,
                load_params=stage_params_fn,
                heartbeat_interval_s=0.1,
                static_peers=[transports[1 - i].peer_id],
                layers=(
                    (0, split) if i == 0
                    else (split, cfg.num_hidden_layers)
                ),
            )
            for i in range(2)
        ]
        try:
            for w in workers:
                w.start()
            head = workers[0]
            deadline = _time.time() + 120
            while _time.time() < deadline:
                if head.engine is not None and head.local_route():
                    break
                _time.sleep(0.02)
            # Record the head's per-step HOST-BLOCKING ms (the dispatch
            # cadence the sender pipeline must protect).
            host_ms: list[float] = []
            agg = head.engine.step_timing
            orig_update = agg.update

            def record(h, d, o, tokens=1):
                host_ms.append(h)
                orig_update(h, d, o, tokens=tokens)

            agg.update = record
            rng = np.random.default_rng(3)
            reqs, events = [], []
            t0 = time.perf_counter()
            for i in range(n_req):
                req = Request(
                    request_id=f"tp{i}",
                    prompt_ids=[int(x) for x in rng.integers(
                        1, cfg.vocab_size - 1, size=prompt_len
                    )],
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=gen_len,
                        ignore_eos=True,
                    ),
                )
                reqs.append(req)
                events.append(head.submit(req))
            ok = all(ev.wait(120.0) for ev in events)
            wall = time.perf_counter() - t0
            return {
                "requests": n_req,
                "completed": sum(
                    1 for r in reqs
                    if r.status.is_finished
                    and r.status.value != "finished_abort"
                ),
                "finished_in_time": ok,
                "decode_dispatch_ms_median": round(
                    statistics.median(host_ms), 3
                ) if host_ms else 0.0,
                "steps": len(host_ms),
                "wall_s": round(wall, 2),
                "links": head.transport_stats() or {},
            }
        finally:
            for w in workers:
                w.stop()

    return {
        "slow_peer_delay_ms": round(delay_s * 1000, 1),
        "baseline": run(0.0),
        "delayed": run(delay_s),
    }


def _routing_probe(cfg, stage_params_fn, kv_dtype, page_size):
    """Two-replica loopback swarm under a shared-prefix (multi-turn chat)
    workload, once per routing strategy: round-robin routes blind, so a
    follow-up turn usually lands on the replica that has NEVER seen the
    conversation and pays full prefill; cache-aware routing hashes the
    prompt's block chain against the heartbeat-published radix digests
    and sends it back to the warm replica. Returns ``detail.routing``:
    per-strategy prefix hit rate + TTFT p50 over the follow-up turns,
    plus the cache-aware decision counters and predicted-vs-actual hit
    telemetry."""
    import statistics
    import time as _time

    import numpy as np

    from parallax_tpu.backend.scheduler_service import SchedulerService
    from parallax_tpu.p2p.node import WorkerNode
    from parallax_tpu.p2p.transport import LoopbackTransport
    from parallax_tpu.runtime.engine import EngineConfig
    from parallax_tpu.runtime.request import Request, SamplingParams
    from parallax_tpu.scheduling.scheduler import GlobalScheduler

    n_sessions, n_turns = 3, 3
    base_pages, turn_pages = 3, 1
    gen_len = max(4, page_size // 2)
    # Worst-case context: base + per-turn extension + generations.
    max_model_len = (
        (base_pages + n_turns * (turn_pages + 1)) * page_size
        + (n_turns + 1) * gen_len
    )

    rng = np.random.default_rng(11)
    bases = [
        [int(x) for x in rng.integers(
            1, cfg.vocab_size - 1, size=base_pages * page_size
        )]
        for _ in range(n_sessions)
    ]
    chunks = [
        [
            [int(x) for x in rng.integers(
                1, cfg.vocab_size - 1, size=turn_pages * page_size
            )]
            for _ in range(n_turns)
        ]
        for _ in range(n_sessions)
    ]

    def run(routing: str) -> dict:
        registry: dict = {}
        sched = GlobalScheduler(cfg, min_nodes_bootstrapping=2,
                                routing=routing)
        service = SchedulerService(
            sched, LoopbackTransport("sched", registry), join_timeout_s=60.0
        )
        service.start()
        ecfg = EngineConfig(
            page_size=page_size,
            num_pages=n_sessions * (max_model_len // page_size + 2) + 16,
            max_batch_size=n_sessions,
            max_model_len=max_model_len,
            kv_dtype=kv_dtype,
            enable_prefix_cache=True,
        )
        workers = [
            WorkerNode(
                transport=LoopbackTransport(f"rt{i}", registry),
                scheduler_peer="sched",
                model_config=cfg,
                engine_config=ecfg,
                load_params=stage_params_fn,
                heartbeat_interval_s=0.1,
            )
            for i in range(2)
        ]
        try:
            import threading

            starters = [threading.Thread(target=w.start) for w in workers]
            for s in starters:
                s.start()
            for s in starters:
                s.join(timeout=120.0)
            by_id = {w.node_id: w for w in workers}
            deadline = _time.time() + 120
            while _time.time() < deadline:
                st = sched.cluster_status()
                if st["num_pipelines"] >= 2 and all(
                    n["ready"] for p in st["pipelines"] for n in p["nodes"]
                ):
                    break
                _time.sleep(0.02)

            def digests_synced() -> bool:
                # Scheduler mirrors caught up with every live tree.
                for w in workers:
                    eng, node = w.engine, sched.manager.get(w.node_id)
                    if eng is None or node is None:
                        return False
                    tree = getattr(eng.cache, "prefix_cache", None)
                    n = getattr(tree, "num_cached_pages", 0) + getattr(
                        tree, "num_host_pages", 0
                    )
                    if len(node.cache_index) != n:
                        return False
                return True

            contexts: list[list[int]] = [list(b) for b in bases]
            ttfts: list[float] = []
            cached = prompt_total = 0
            completed = requests = 0
            for turn in range(n_turns):
                for s in range(n_sessions):
                    prompt = (
                        contexts[s] if turn == 0
                        else contexts[s] + chunks[s][turn]
                    )
                    rid = f"{routing}-s{s}-t{turn}"
                    path = service.route_request(
                        rid, timeout_s=30.0, prompt_ids=list(prompt)
                    )
                    if path is None:
                        continue
                    requests += 1
                    req = Request(
                        request_id=rid,
                        prompt_ids=list(prompt),
                        sampling_params=SamplingParams(
                            temperature=0.0, max_new_tokens=gen_len,
                            ignore_eos=True,
                        ),
                        routing_table=list(path),
                    )
                    head = by_id[path[0]]
                    t0 = _time.perf_counter()
                    ev = head.submit(req)
                    first_deadline = t0 + 60.0
                    while (
                        not req.output_ids
                        and not req.status.is_finished
                        and _time.perf_counter() < first_deadline
                    ):
                        _time.sleep(0.0005)
                    ttft_ms = (_time.perf_counter() - t0) * 1e3
                    ok = ev.wait(60.0)
                    if (
                        ok and req.status.is_finished
                        and req.status.value != "finished_abort"
                    ):
                        completed += 1
                    contexts[s] = list(req.all_token_ids)
                    if turn > 0:
                        ttfts.append(ttft_ms)
                        cached += req.num_cached_tokens
                        prompt_total += len(prompt)
                # Follow-up turns route against the digests the finished
                # turn donated: wait for the heartbeat mirrors to catch
                # up (cache-aware only; RR reads nothing).
                if routing == "cache_aware":
                    sync_deadline = _time.time() + 10.0
                    while (
                        not digests_synced()
                        and _time.time() < sync_deadline
                    ):
                        _time.sleep(0.02)
                else:
                    _time.sleep(0.25)
            # request_complete actuals ride the async sender: give the
            # predicted-vs-actual aggregate a moment to drain.
            acc_deadline = _time.time() + 3.0
            while (
                sched.routing_accuracy["requests"] < requests
                and _time.time() < acc_deadline
            ):
                _time.sleep(0.02)
            rec = {
                "requests": requests,
                "completed": completed,
                "prefix_hit_rate": round(
                    cached / prompt_total, 4
                ) if prompt_total else 0.0,
                "ttft_p50_ms": round(
                    statistics.median(ttfts), 2
                ) if ttfts else 0.0,
                "pipeline_dispatches": {
                    str(k): v
                    for k, v in sched.router.pipeline_dispatches.items()
                },
            }
            if sched.router.decision_counters:
                rec["decisions"] = dict(sched.router.decision_counters)
            if sched.routing_accuracy["requests"]:
                rec["predicted_vs_actual"] = dict(sched.routing_accuracy)
            return rec
        finally:
            for w in workers:
                w.stop()
            service.stop()

    return {
        "workload": {
            "sessions": n_sessions, "turns": n_turns,
            "base_pages": base_pages, "page_size": page_size,
        },
        "round_robin": run("rr"),
        "cache_aware": run("cache_aware"),
    }


def _churn_probe(cfg, stage_params_fn, kv_dtype, page_size):
    """Node-churn robustness probe (docs/resilience.md): a 4-worker
    loopback swarm forming two 2-stage pipelines behind a cache-aware
    scheduler, serving the same greedy+seeded request set twice — once
    clean, once with a chaos-injected kill of a pipeline's TAIL stage
    mid-decode. The live-migration flow must absorb the kill: every
    affected request is checkpointed off the surviving head, restored on
    the other pipeline, and finishes with 0 aborts and streams
    bit-identical to the clean run. Returns ``detail.churn`` with the
    park->resume migration latency p50/p95 (the CI chaos smoke asserts
    this whole contract)."""
    import dataclasses as _dc
    import threading
    import time as _time

    import numpy as np

    from parallax_tpu.backend.run import SwarmClient
    from parallax_tpu.backend.scheduler_service import SchedulerService
    from parallax_tpu.obs.registry import get_registry, summarize_snapshots
    from parallax_tpu.p2p.node import WorkerNode
    from parallax_tpu.p2p.transport import LoopbackTransport
    from parallax_tpu.runtime.engine import EngineConfig
    from parallax_tpu.runtime.request import Request, SamplingParams
    from parallax_tpu.scheduling import node as sched_node
    from parallax_tpu.scheduling.scheduler import GlobalScheduler
    from parallax_tpu.testing.chaos import ChaosController

    n_req, prompt_len, gen_len = 4, 2 * page_size, 24
    max_model_len = prompt_len + gen_len + 2 * page_size
    split = max(1, cfg.num_hidden_layers // 2)

    rng = np.random.default_rng(17)
    requests = []
    for i in range(n_req):
        sp = (
            SamplingParams(temperature=0.0, max_new_tokens=gen_len,
                           ignore_eos=True)
            if i % 2 == 0 else
            SamplingParams(temperature=0.8, top_k=8, seed=97 + i,
                           max_new_tokens=gen_len, ignore_eos=True)
        )
        prompt = [int(x) for x in rng.integers(
            1, cfg.vocab_size - 1, size=prompt_len
        )]
        requests.append((prompt, sp))

    # lock_sanitizer=False / conformance=False: the probe measures
    # migration latency; instrumented locks would tax every acquisition
    # and conformance hooks every transition/frame in this process.
    chaos = ChaosController(seed=17, lock_sanitizer=False,
                            conformance=False)
    registry: dict = {}
    # Two 2-stage pipelines: cap what one node may hold at half the
    # model so the allocator splits each pipeline across two workers.
    orig_cap = sched_node.RooflinePerformanceModel.max_layers_in_memory
    sched_node.RooflinePerformanceModel.max_layers_in_memory = (
        lambda self, kv_fraction=0.35: split
    )
    # Health plane ON for the churn probe (docs/observability.md): the
    # goodput ledger, watchdog, timeline and SLO tracker must observe
    # the churn episode without changing a single stream bit (the
    # bit_identical verdict below is exactly that assertion — the clean
    # pass ran under the same instrumentation).
    from parallax_tpu.obs.slo import parse_slo_spec

    sched = GlobalScheduler(cfg, min_nodes_bootstrapping=2,
                            heartbeat_timeout_s=3.0,
                            routing="cache_aware",
                            slo=parse_slo_spec(
                                "ttft_p95_ms=60000,tpot_p95_ms=60000,"
                                "availability=0.5",
                                window_s=30.0,
                            ))
    service = SchedulerService(
        sched, chaos.wrap(LoopbackTransport("sched", registry)),
        join_timeout_s=60.0,
    )
    service.start()
    ecfg = EngineConfig(
        page_size=page_size,
        num_pages=n_req * (max_model_len // page_size + 2) + 16,
        max_batch_size=n_req, max_model_len=max_model_len,
        kv_dtype=kv_dtype, enable_prefix_cache=True,
    )
    workers = [
        WorkerNode(
            transport=chaos.wrap(LoopbackTransport(f"ch{i}", registry)),
            scheduler_peer="sched",
            model_config=cfg,
            engine_config=_dc.replace(ecfg),
            load_params=stage_params_fn,
            heartbeat_interval_s=0.1,
            watchdog=True,
            watchdog_degraded_s=1.0,
            watchdog_stalled_s=3.0,
        )
        for i in range(4)
    ]
    by_id = {w.node_id: w for w in workers}

    def serve(tag: str, on_tokens=None) -> list:
        reqs, evs = [], []
        for i, (prompt, sp) in enumerate(requests):
            rid = f"{tag}-{i}"
            path = client.route(rid, prompt_ids=list(prompt))
            if not path:
                continue
            req = Request(
                request_id=rid, prompt_ids=list(prompt),
                sampling_params=_dc.replace(sp),
                routing_table=list(path),
            )
            evs.append(client.submit(req))
            reqs.append(req)
        if on_tokens is not None:
            fired = set()
            deadline = _time.monotonic() + 60.0
            while len(fired) < len(reqs) and _time.monotonic() < deadline:
                for i, req in enumerate(reqs):
                    if i not in fired and (
                        len(req.output_ids) >= 2
                        or req.status.is_finished
                    ):
                        fired.add(i)
                        on_tokens(req)
                _time.sleep(0.002)
        for ev in evs:
            ev.wait(120.0)
        return reqs

    def summarize(reqs: list) -> dict:
        return {
            "requests": len(reqs),
            "completed": sum(
                1 for r in reqs
                if r.status.is_finished
                and r.status.value != "finished_abort"
            ),
            "aborted": sum(
                1 for r in reqs if r.status.value == "finished_abort"
            ),
        }

    def migrations_total() -> int:
        try:
            return int(get_registry().counter(
                "parallax_migrations_total",
                "Requests restored on this head after a live migration "
                "or client resume",
                labelnames=("mode",),
            ).total)
        except Exception:
            return 0

    try:
        starters = [threading.Thread(target=w.start) for w in workers]
        for s in starters:
            s.start()
        for s in starters:
            s.join(timeout=120.0)
        deadline = _time.time() + 120
        while _time.time() < deadline:
            st = sched.cluster_status()
            if st["num_pipelines"] >= 2 and all(
                n["ready"] for p in st["pipelines"] for n in p["nodes"]
            ):
                break
            _time.sleep(0.02)
        client = SwarmClient(
            chaos.wrap(LoopbackTransport("client", registry)), service,
            poll_interval_s=0.002,
        )

        baseline = serve("base")
        base_streams = {
            r.request_id.split("-", 1)[1]: list(r.output_ids)
            for r in baseline
        }

        from parallax_tpu.obs.goodput import get_goodput

        goodput_before = get_goodput().snapshot()
        migrations_before = migrations_total()
        victim: dict = {}
        lock = threading.Lock()

        def kill_tail(req):
            with lock:
                if victim or len(req.routing_table) < 2:
                    return
                tail = req.routing_table[-1]
                victim["tail"] = tail
                t0 = _time.perf_counter()
                chaos.kill(by_id[tail])
                victim["kill_s"] = _time.perf_counter() - t0

        churn = serve("churn", on_tokens=kill_tail)
        migrated = migrations_total() - migrations_before
        bit_identical = bool(churn) and all(
            list(r.output_ids)
            == base_streams.get(r.request_id.split("-", 1)[1])
            for r in churn
        )
        mig_ms = (
            summarize_snapshots(get_registry().histogram_snapshots())
            .get("parallax_migration_ms") or {}
        ).get("", {})
        # Health-plane verdicts over the churn pass (the CI health smoke
        # asserts these):
        # (1) Goodput ledger exactness — every device-step token of the
        #     churn pass landed in exactly one bucket. The oracle is
        #     INDEPENDENT of the ledger: the committed bucket must equal
        #     the token count the client actually streamed (each output
        #     token commits exactly once — on the source head before the
        #     kill or on the target after; the teacher-forced re-commits
        #     land in `replayed`, the re-prefill in `preempted_rework`).
        gp_after = get_goodput().snapshot()
        churn_tokens = {
            k: gp_after["tokens"][k] - goodput_before["tokens"][k]
            for k in gp_after["tokens"]
        }
        churn_total = sum(churn_tokens.values())
        churn_useful = churn_tokens.get("committed", 0)
        client_tokens = sum(len(r.output_ids) for r in churn)
        goodput_payload = get_goodput().payload()
        # (2) The kill must read as a causally-ordered stall->migration
        #     story in the merged timeline: the scheduler's peer_down/
        #     node_leave verdicts on the victim, then the head's
        #     migrate_park/migrate_out, then migration_done on the
        #     survivor.
        tl = sched.timeline.snapshot(limit=None)
        killed = victim.get("tail")
        order = [
            e["kind"] for e in tl["events"]
            if e["kind"] in ("peer_down", "node_leave", "migrate_park",
                             "migrate_out", "migration_done")
            and (e.get("node") == killed
                 or e["kind"] in ("migrate_park", "migrate_out",
                                  "migration_done"))
        ]
        # The stall verdict on the victim (a peer_down report from a
        # surviving sender, or the sweep's node_leave — whichever lands
        # first) must precede the migration completing on the survivor:
        # that is the causally-ordered stall -> migration story.
        stall_idx = min(
            (order.index(k) for k in ("peer_down", "node_leave")
             if k in order),
            default=None,
        )
        stall_then_migration = (
            stall_idx is not None
            and "migrate_out" in order
            and "migration_done" in order
            and stall_idx < (
                len(order) - 1 - order[::-1].index("migration_done")
            )
        )
        status = sched.cluster_status()
        node_health = {
            n["node_id"]: (n.get("health") or {}).get("status")
            for p in status.get("pipelines", ())
            for n in p.get("nodes", ())
        }
        return {
            "workload": {
                "requests": n_req, "prompt_len": prompt_len,
                "gen_len": gen_len, "pipelines": 2, "stages": 2,
            },
            "baseline": summarize(baseline),
            "churn": {
                **summarize(churn),
                "killed_node": victim.get("tail"),
                "migrations": migrated,
                "bit_identical": bit_identical,
                "migration_ms": {
                    k: mig_ms.get(k) for k in ("count", "p50", "p95")
                } if mig_ms else {},
            },
            "health_plane": {
                # Churn-pass goodput deltas: useful + wasted == total by
                # ledger construction; waste > 0 proves the migration
                # replay/rework showed up as lost goodput, not hidden
                # inside latency.
                "goodput": {
                    "tokens": churn_tokens,
                    "tokens_total": churn_total,
                    "tokens_useful": churn_useful,
                    "tokens_wasted": churn_total - churn_useful,
                    # Independent oracle: the useful bucket must equal
                    # the client-observed stream length, token for
                    # token — double counts or drops in the engine's
                    # classification hooks fail here.
                    "client_tokens": client_tokens,
                    "exact": churn_useful == client_tokens,
                    "goodput_fraction": (
                        round(churn_useful / churn_total, 6)
                        if churn_total else 0.0
                    ),
                    "tokens_useful_per_chip_second": round(
                        goodput_payload["tokens_useful"]
                        / max(goodput_payload["elapsed_s"], 1e-9), 3,
                    ),
                },
                "timeline": {
                    "ingested": tl["ingested"],
                    "gaps": tl["gaps"],
                    "killed_node_events": order,
                    "stall_then_migration": stall_then_migration,
                },
                "slo": status.get("slo"),
                "node_health": node_health,
                "cluster_health": status.get("health"),
            },
        }
    finally:
        sched_node.RooflinePerformanceModel.max_layers_in_memory = orig_cap
        for w in workers:
            if not chaos.is_dead(w.node_id):
                w.stop()
        service.stop()


def _disagg_probe(cfg, stage_params_fn, kv_dtype, page_size):
    """Disaggregated prefill/decode probe (docs/disaggregation.md): two
    single-stage full-model replicas behind a cache-aware scheduler
    serve the SAME long-prefill + chatty-decode + interactive workload
    twice — once as a mixed pool (both replicas serve both phases,
    round-robin interference) and once disaggregated (a prefill
    specialist handing finished prompts to a decode specialist over the
    layer-chunked KV-transfer lane). Reports interactive TTFT p50/p95
    and chatty TPOT per mode, kv_transfer telemetry (frames/bytes/ms +
    fallbacks + handoffs by mode), and the bit-identity verdict across
    modes (the CI disaggregation smoke asserts the contract)."""
    import dataclasses as _dc
    import threading
    import time as _time

    import numpy as np

    from parallax_tpu.backend.run import SwarmClient
    from parallax_tpu.backend.scheduler_service import SchedulerService
    from parallax_tpu.obs.registry import get_registry, summarize_snapshots
    from parallax_tpu.p2p.node import WorkerNode
    from parallax_tpu.p2p.transport import LoopbackTransport
    from parallax_tpu.runtime.engine import EngineConfig
    from parallax_tpu.runtime.request import Request, SamplingParams
    from parallax_tpu.scheduling.scheduler import GlobalScheduler

    n_chatty, n_long, n_inter = 3, 2, 6
    chatty_gen, long_gen, inter_gen = 64, 4, 8
    chatty_pages, long_pages, inter_pages = 1, 16, 2
    max_model_len = (long_pages + 2) * page_size + chatty_gen
    rng = np.random.default_rng(23)

    def prompt(pages, salt):
        p = [int(x) for x in rng.integers(
            1, cfg.vocab_size - 1, size=pages * page_size
        )]
        p[-1] = salt % (cfg.vocab_size - 2) + 1
        return p

    # (key, prompt, sampling, class) — same set both modes; greedy and
    # seeded rows so the bit-identity verdict covers both samplers.
    workload = []
    for i in range(n_chatty):
        sp = (
            SamplingParams(temperature=0.0, max_new_tokens=chatty_gen,
                           ignore_eos=True)
            if i % 2 == 0 else
            SamplingParams(temperature=0.8, top_k=8, seed=31 + i,
                           max_new_tokens=chatty_gen, ignore_eos=True)
        )
        workload.append((f"chat{i}", prompt(chatty_pages, i), sp, "chatty"))
    for i in range(n_long):
        workload.append((
            f"long{i}", prompt(long_pages, 100 + i),
            SamplingParams(temperature=0.0, max_new_tokens=long_gen,
                           ignore_eos=True),
            "long",
        ))
    for i in range(n_inter):
        sp = (
            SamplingParams(temperature=0.0, max_new_tokens=inter_gen,
                           ignore_eos=True)
            if i % 2 == 0 else
            SamplingParams(temperature=0.7, top_k=8, seed=61 + i,
                           max_new_tokens=inter_gen, ignore_eos=True)
        )
        workload.append((
            f"inter{i}", prompt(inter_pages, 200 + i), sp, "interactive",
        ))

    def counter_total(name, labelnames):
        try:
            return int(get_registry().counter(
                name, "", labelnames=labelnames
            ).total)
        except Exception:
            return 0

    def run(tag: str, roles: list) -> dict:
        registry: dict = {}
        sched = GlobalScheduler(cfg, min_nodes_bootstrapping=len(roles),
                                heartbeat_timeout_s=5.0,
                                routing="cache_aware")
        service = SchedulerService(
            sched, LoopbackTransport("sched", registry),
            join_timeout_s=60.0,
        )
        service.start()
        ecfg = EngineConfig(
            page_size=page_size,
            num_pages=(
                n_chatty * (chatty_pages + chatty_gen // page_size + 2)
                + n_long * (long_pages + 2)
                + n_inter * (inter_pages + 2) + 24
            ),
            max_batch_size=n_chatty + n_long + n_inter,
            max_model_len=max_model_len,
            kv_dtype=kv_dtype,
            enable_prefix_cache=True,
            # The handoff ships the PR 2 pinned host image; both modes
            # run the tier so the bit-identity comparison is
            # apples-to-apples.
            host_cache_bytes=1 << 26,
        )
        workers = [
            WorkerNode(
                transport=LoopbackTransport(f"{tag}{i}", registry),
                scheduler_peer="sched",
                model_config=cfg,
                engine_config=_dc.replace(ecfg),
                load_params=stage_params_fn,
                heartbeat_interval_s=0.1,
                role=role,
            )
            for i, role in enumerate(roles)
        ]
        try:
            starters = [threading.Thread(target=w.start) for w in workers]
            for s in starters:
                s.start()
            for s in starters:
                s.join(timeout=120.0)
            deadline = _time.time() + 120
            while _time.time() < deadline:
                st = sched.cluster_status()
                if st["num_pipelines"] >= len(roles) and all(
                    n["ready"] for p in st["pipelines"] for n in p["nodes"]
                ):
                    break
                _time.sleep(0.02)
            client = SwarmClient(
                LoopbackTransport("client", registry), service,
                poll_interval_s=0.002,
            )

            reqs: dict[str, Request] = {}
            evs: dict[str, threading.Event] = {}
            t_submit: dict[str, float] = {}
            t_first: dict[str, float] = {}
            t_last: dict[str, float] = {}
            watch_stop = threading.Event()

            def watcher():
                while not watch_stop.is_set():
                    now = _time.perf_counter()
                    for key, r in list(reqs.items()):
                        if r.output_ids and key not in t_first:
                            t_first[key] = now
                        if r.output_ids:
                            t_last[key] = now
                    _time.sleep(0.001)

            wt = threading.Thread(target=watcher, daemon=True)
            wt.start()

            def submit(key, p, sp):
                rid = f"{tag}-{key}"
                path = client.route(rid, prompt_ids=list(p))
                if not path:
                    return
                req = Request(
                    request_id=rid, prompt_ids=list(p),
                    sampling_params=_dc.replace(sp),
                    routing_table=list(path),
                )
                t_submit[key] = _time.perf_counter()
                evs[key] = client.submit(req)
                reqs[key] = req

            by_class = {}
            for key, p, sp, cls in workload:
                by_class.setdefault(cls, []).append((key, p, sp))
            # Phase 1: chatty sessions first; wait until they are deep
            # in decode (the interference the decode pool exists to
            # shield).
            for key, p, sp in by_class["chatty"]:
                submit(key, p, sp)
            deadline = _time.monotonic() + 60.0
            while _time.monotonic() < deadline and not all(
                len(r.output_ids) >= 4
                for k, r in reqs.items() if k.startswith("chat")
            ):
                _time.sleep(0.002)
            # Phase 2: long prefills land, then interactive prompts
            # trickle in while the longs are still being computed.
            for key, p, sp in by_class["long"]:
                submit(key, p, sp)
            _time.sleep(0.02)
            for key, p, sp in by_class["interactive"]:
                submit(key, p, sp)
                _time.sleep(0.015)
            for key, ev in evs.items():
                ev.wait(120.0)
            watch_stop.set()
            wt.join(timeout=2.0)

            def pct(vals, q):
                if not vals:
                    return 0.0
                vals = sorted(vals)
                idx = min(len(vals) - 1, int(round(q * (len(vals) - 1))))
                return round(vals[idx], 2)

            inter_ttfts = [
                (t_first[k] - t_submit[k]) * 1e3
                for k in t_first if k.startswith("inter")
            ]
            chatty_tpots = [
                (t_last[k] - t_first[k]) * 1e3
                / max(1, len(reqs[k].output_ids) - 1)
                for k in t_first
                if k.startswith("chat") and k in t_last
            ]
            return {
                "requests": len(reqs),
                "completed": sum(
                    1 for r in reqs.values()
                    if r.status.is_finished
                    and r.status.value != "finished_abort"
                ),
                "aborted": sum(
                    1 for r in reqs.values()
                    if r.status.value == "finished_abort"
                ),
                "interactive": {
                    "ttft_p50_ms": pct(inter_ttfts, 0.5),
                    "ttft_p95_ms": pct(inter_ttfts, 0.95),
                },
                "chatty": {
                    "tpot_p50_ms": pct(chatty_tpots, 0.5),
                },
                "streams": {
                    k: list(r.output_ids) for k, r in reqs.items()
                },
            }
        finally:
            for w in workers:
                w.stop()
            service.stop()

    mixed = run("mx", [None, None])

    kv_before = {
        "frames": counter_total(
            "parallax_kv_transfer_frames_total", ("direction",)
        ),
        "bytes": counter_total(
            "parallax_kv_transfer_bytes_total", ("direction",)
        ),
        "fallbacks": counter_total(
            "parallax_kv_transfer_fallbacks_total", ("reason",)
        ),
        "handoffs": counter_total(
            "parallax_kv_handoffs_total", ("mode",)
        ),
    }
    disagg = run("dg", ["prefill", "decode"])
    kv_ms = (
        summarize_snapshots(get_registry().histogram_snapshots())
        .get("parallax_kv_transfer_ms") or {}
    ).get("", {})
    kv_transfer = {
        "frames": counter_total(
            "parallax_kv_transfer_frames_total", ("direction",)
        ) - kv_before["frames"],
        "bytes": counter_total(
            "parallax_kv_transfer_bytes_total", ("direction",)
        ) - kv_before["bytes"],
        "fallbacks": counter_total(
            "parallax_kv_transfer_fallbacks_total", ("reason",)
        ) - kv_before["fallbacks"],
        "kv_transfer_ms": {
            k: kv_ms.get(k) for k in ("count", "p50", "p95")
        } if kv_ms else {},
    }
    handoffs = counter_total(
        "parallax_kv_handoffs_total", ("mode",)
    ) - kv_before["handoffs"]

    mixed_streams = mixed.pop("streams")
    disagg_streams = disagg.pop("streams")
    bit_identical = (
        set(mixed_streams) == set(disagg_streams)
        and all(
            mixed_streams[k] == disagg_streams[k] for k in mixed_streams
        )
    )
    return {
        "workload": {
            "chatty": n_chatty, "long_prefill": n_long,
            "interactive": n_inter, "long_pages": long_pages,
            "page_size": page_size, "chatty_gen": chatty_gen,
        },
        "mixed": mixed,
        "disagg": {**disagg, "handoffs": handoffs,
                   "kv_transfer": kv_transfer},
        "bit_identical": bit_identical,
        "interactive_ttft_p95_improved": (
            disagg["interactive"]["ttft_p95_ms"]
            < mixed["interactive"]["ttft_p95_ms"]
        ),
    }


def _qos_probe(cfg, dtype, kv_dtype, page_size) -> dict:
    """Multi-tenant QoS probe (detail.qos, docs/qos.md): the SAME
    mixed workload — a batch-class flood saturating the engine, then
    interactive arrivals — served three ways on one tiny engine:

    - ``unloaded``: interactive requests alone (the TTFT baseline);
    - ``off``: flood + interactive with QoS off (arrival order: the
      interactive rows wait the flood out);
    - ``on``: same workload with QoS on — queue pressure sheds the
      flood, parks its running decodes to the host tier, admits the
      interactive rows, then releases and resumes the flood.

    Contract (asserted by test_bench_contract + the CI qos smoke):
    QoS on keeps interactive p99 TTFT within 2x of unloaded (with a
    250 ms absolute floor against CI jitter) while batch still commits
    every token (parked, never aborted); streams are BIT-IDENTICAL
    between the off and on runs (greedy + seeded rows) — QoS moves
    work in time, it never changes what is computed."""
    import dataclasses as _dc

    import jax
    import numpy as np

    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.engine import (
        EngineConfig,
        StageEngine,
        drive_step,
    )
    from parallax_tpu.runtime.request import Request, SamplingParams

    model = create_stage_model(cfg, 0, cfg.num_hidden_layers)
    params = model.init_params(jax.random.key(5), dtype=dtype)
    rng = np.random.default_rng(29)
    n_flood, flood_gen = 6, 96
    n_inter, inter_gen = 4, 8
    p_pages = 2

    def prompt(salt):
        p = [int(x) for x in rng.integers(
            1, cfg.vocab_size - 1, size=p_pages * page_size
        )]
        p[-1] = salt % (cfg.vocab_size - 2) + 1
        return p

    flood_w = []
    for i in range(n_flood):
        sp = (
            SamplingParams(temperature=0.0, max_new_tokens=flood_gen,
                           ignore_eos=True)
            if i % 2 == 0 else
            SamplingParams(temperature=0.8, top_k=8, seed=131 + i,
                           max_new_tokens=flood_gen, ignore_eos=True)
        )
        flood_w.append((f"batch{i}", prompt(i), sp))
    inter_w = []
    for i in range(n_inter):
        sp = (
            SamplingParams(temperature=0.0, max_new_tokens=inter_gen,
                           ignore_eos=True)
            if i % 2 == 0 else
            SamplingParams(temperature=0.7, top_k=8, seed=171 + i,
                           max_new_tokens=inter_gen, ignore_eos=True)
        )
        inter_w.append((f"inter{i}", prompt(60 + i), sp))

    qos_spec = (
        "interactive_ms=60,tick_interval_s=0.005,min_shed_s=0.02,"
        "burn_window_s=0.5,starvation_s=60"
    )
    pages_per = (p_pages * page_size + flood_gen) // page_size + 2
    max_model_len = (p_pages + 1) * page_size + flood_gen + page_size

    def run(tag, qos, with_flood=True):
        eng = StageEngine(model, params, EngineConfig(
            page_size=page_size,
            num_pages=n_flood * pages_per + 2 * p_pages + 4,
            max_batch_size=4,
            max_model_len=max_model_len,
            kv_dtype=kv_dtype,
            enable_prefix_cache=True,
            host_cache_bytes=1 << 26,
            # K=1: the capacity the interactive rows need must come
            # from QOS park enforcement, not from the adaptive
            # multi-step window's own page-pressure preemption (which
            # would mask the subsystem this probe exists to prove).
            decode_lookahead=1,
            qos=qos,
        ))
        reqs = {}
        pending = None

        def submit(rid, p, sp, cls):
            r = Request(rid, prompt_ids=list(p),
                        sampling_params=_dc.replace(sp), qos_class=cls)
            reqs[rid] = r
            assert eng.submit(r)

        # Warm-up: compile the prefill/decode graphs — greedy AND the
        # seeded-sampler variant — before anything is timed: the
        # unloaded TTFT baseline must measure scheduling, not the
        # first-trace XLA compile of whichever path runs first.
        # A full-width warm batch (max_batch_size rows, half greedy /
        # half seeded) so the measured runs hit the same prefill and
        # decode bucket shapes the warmup already compiled.
        for wi in range(4):
            wsp = (
                SamplingParams(temperature=0.0, max_new_tokens=4,
                               ignore_eos=True)
                if wi % 2 == 0 else
                SamplingParams(temperature=0.7, top_k=8, seed=1 + wi,
                               max_new_tokens=4, ignore_eos=True)
            )
            assert eng.submit(Request(
                f"warm{wi}", prompt_ids=prompt(90 + wi),
                sampling_params=wsp,
                # Batch-class: warm-up TTFTs carry the compile time and
                # must not feed the interactive burn signal.
                qos_class="batch",
            ))
        guard = 0
        while guard < 20000 and (eng.has_work() or pending is not None):
            guard += 1
            _outs, pending = drive_step(eng, pending)

        if with_flood:
            for rid, p, sp in flood_w:
                submit(rid, p, sp, "batch")
            guard = 0
            while guard < 20000 and not any(
                r.output_ids for r in reqs.values()
            ):
                guard += 1
                _outs, pending = drive_step(eng, pending)
        for rid, p, sp in inter_w:
            submit(rid, p, sp, "interactive")
        deadline = time.time() + 120.0
        while (eng.has_work() or pending is not None) and (
            time.time() < deadline
        ):
            _outs, pending = drive_step(eng, pending)

        def pct(vals, q):
            if not vals:
                return 0.0
            vals = sorted(vals)
            return round(
                vals[min(len(vals) - 1, int(round(q * (len(vals) - 1))))],
                2,
            )

        inter_ttfts = [
            (r.first_token_time - r.arrival_time) * 1e3
            for rid, r in reqs.items()
            if rid.startswith("inter") and r.first_token_time is not None
        ]
        pol = eng.scheduler.qos
        out = {
            "requests": len(reqs),
            "completed": sum(
                1 for r in reqs.values()
                if r.status.is_finished
                and r.status.value != "finished_abort"
            ),
            "aborted": sum(
                1 for r in reqs.values()
                if r.status.value == "finished_abort"
            ),
            "interactive": {
                "ttft_p50_ms": pct(inter_ttfts, 0.5),
                "ttft_p99_ms": pct(inter_ttfts, 0.99),
            },
            "batch": {
                "tokens": sum(
                    len(r.output_ids) for rid, r in reqs.items()
                    if rid.startswith("batch")
                ),
            },
            "streams": {
                rid: list(r.output_ids) for rid, r in reqs.items()
            },
        }
        if pol is not None:
            out["sheds"] = sum(pol.counters["shed_held"].values())
            out["parks"] = sum(pol.counters["parked"].values())
            out["shed_transitions"] = dict(pol.controller.transitions)
        return out

    unloaded = run("unloaded", qos_spec, with_flood=False)
    off = run("off", None)
    # The shed trigger is a race the probe engineers (interactive wait
    # crossing half its budget while the flood decodes): on a heavily
    # loaded CI machine one attempt can miss the window — retry a
    # bounded number of times until enforcement demonstrably engaged
    # (streams are asserted bit-identical for whichever attempt wins).
    on = None
    for _attempt in range(3):
        on = run("on", qos_spec)
        if (
            on.get("parks", 0) > 0 and on.get("sheds", 0) > 0
            and on["shed_transitions"].get("releases", 0) >= 1
        ):
            break
    off_streams = off.pop("streams")
    on_streams = on.pop("streams")
    unloaded.pop("streams")
    bit_identical = set(off_streams) == set(on_streams) and all(
        off_streams[k] == on_streams[k] for k in off_streams
    )
    # 2x-of-unloaded with a 250 ms absolute floor: tiny-model TTFTs are
    # a few ms, where scheduler noise would dominate a bare 2x.
    budget = max(2.0 * unloaded["interactive"]["ttft_p99_ms"], 250.0)
    return {
        "workload": {
            "flood": n_flood, "flood_gen": flood_gen,
            "interactive": n_inter, "interactive_gen": inter_gen,
            "max_batch_size": 4, "qos_spec": qos_spec,
        },
        "unloaded": unloaded,
        "off": off,
        "on": on,
        "bit_identical": bit_identical,
        "interactive_p99_within_2x": (
            on["interactive"]["ttft_p99_ms"] <= budget
        ),
        "interactive_p99_budget_ms": round(budget, 2),
    }


def _spec_probe(model, params, kv_dtype: str) -> dict:
    """Speculative-decoding probe (detail.spec, docs/decode_loop.md):
    the acceptance-rate x speedup matrix — spec on/off x K=1/K=8 x
    repetitive/random prompts on one single-stage engine geometry —
    plus the goodput accepted-vs-rejected split per round.

    Workloads: "repetitive" selects, from a batch of constant-token
    candidate prompts, the one whose greedy continuation is the most
    periodic (the candidates round doubles as the K=8 spec-off warmup),
    then serves 8 copies of it — the regime prompt-lookup proposals are
    built for. "random" serves seeded uniform prompts — the adversarial
    regime where acceptance collapses and speculation is expected to
    COST (reported honestly; the goodput ledger charges the discarded
    verify positions to ``speculative_rejected``).

    Timing is decode-phase wall clock amortized per committed token,
    with every engine warmed by a full identical round first (the spec
    window's proposal buffer rides a fixed per-config length, so warm
    and measured rounds share every compile). The CI spec smoke asserts
    spec-on strictly below spec-off at K=8 on the repetitive workload
    and bit-identical greedy+seeded streams; the structural keys are
    pinned by test_bench_contract.
    """
    import numpy as np

    from parallax_tpu.obs.goodput import get_goodput
    from parallax_tpu.runtime.engine import (
        EngineConfig,
        StageEngine,
        drive_step,
    )
    from parallax_tpu.runtime.request import Request, SamplingParams

    vocab = int(model.config.vocab_size)
    batch, prompt_len, gen_len = 8, 16, 128
    page_size = 16
    max_len = prompt_len + gen_len + 3 * page_size
    spec_width, ngram = 4, 2
    lookahead_hi = 8

    def make_engine(spec: int, k: int) -> StageEngine:
        return StageEngine(model, params, EngineConfig(
            page_size=page_size,
            num_pages=batch * ((max_len + page_size - 1) // page_size + 1),
            max_batch_size=batch,
            max_model_len=max_len,
            kv_dtype=kv_dtype,
            enable_prefix_cache=False,
            speculative_tokens=spec,
            speculative_ngram=ngram,
            decode_lookahead=k,
        ))

    def run_round(eng, tag, prompts, temp=0.0, seed=None, overlap=True):
        """One full batch to completion through the drive loop;
        returns decode-phase per-token wall ms, the streams, and the
        round's goodput-ledger delta. The K=8 rounds run the serving
        default (overlap); the K=1 rounds run SYNC — under overlap a
        K=1 decode row is device-fed (its token never reaches the
        host), so the host-synchronous verify fallback those rounds
        exist to measure could never engage."""
        eng.cfg.overlap_steps = overlap
        gp0 = get_goodput().snapshot()["tokens"]
        reqs = []
        for i, prompt in enumerate(prompts):
            req = Request(
                f"spec-{tag}-{i}", prompt_ids=list(prompt),
                sampling_params=SamplingParams(
                    temperature=temp, seed=seed,
                    max_new_tokens=gen_len, ignore_eos=True,
                ),
            )
            reqs.append(req)
            eng.submit(req)
        total = 0
        decode_t0 = None
        tokens_at_decode = 0
        t0 = time.perf_counter()
        pending = None
        while eng.has_work() or pending is not None:
            outs, pending = drive_step(eng, pending)
            for out in outs:
                total += out.num_tokens
                if decode_t0 is None:
                    running = eng.scheduler.running
                    if (
                        not eng.scheduler.wait_queue
                        and running
                        and all(r.output_ids for r in running.values())
                    ):
                        decode_t0 = time.perf_counter()
                        tokens_at_decode = total
        wall_s = time.perf_counter() - (decode_t0 or t0)
        gp1 = get_goodput().snapshot()["tokens"]
        return {
            "per_token_ms": round(
                wall_s * 1000.0 / max(1, total - tokens_at_decode), 4
            ),
            "decode_tokens": total - tokens_at_decode,
            "outputs": [list(r.output_ids) for r in reqs],
            "goodput": {
                k: gp1[k] - gp0[k]
                for k in ("committed", "speculative_rejected")
            },
        }

    def stability(out: list) -> float:
        """Fraction of positions continuing a period<=4 pattern."""
        return max(
            sum(out[i] == out[i - p] for i in range(p, len(out)))
            / max(1, len(out) - p)
            for p in range(1, 5)
        )

    engines = {
        (0, lookahead_hi): make_engine(0, lookahead_hi),
        (spec_width, lookahead_hi): make_engine(spec_width, lookahead_hi),
        (0, 1): make_engine(0, 1),
        (spec_width, 1): make_engine(spec_width, 1),
    }
    # Candidate selection: constant-token prompts, scored on how
    # periodic their greedy continuation stays (this IS the spec-off
    # K=8 warm round). Deterministic given the weights.
    prng = np.random.default_rng(11)
    cand_tokens = [int(x) for x in prng.integers(1, vocab - 1, size=8)]
    cands = [[t] * prompt_len for t in cand_tokens]
    sel = run_round(engines[(0, lookahead_hi)], "sel", cands)
    best = max(range(len(cands)), key=lambda i: stability(sel["outputs"][i]))
    workloads = {
        "repetitive": [list(cands[best]) for _ in range(batch)],
        "random": [
            [int(x) for x in prng.integers(1, vocab - 1, size=prompt_len)]
            for _ in range(batch)
        ],
    }

    result: dict = {
        "speculative_tokens": spec_width,
        "speculative_ngram": ngram,
        "k": lookahead_hi,
        "repetitive_stability": round(stability(sel["outputs"][best]), 3),
    }
    warmed: set = set()
    for wl, prompts in workloads.items():
        rounds = {}
        for label, (spec, k) in (
            ("off_k8", (0, lookahead_hi)),
            ("on_k8", (spec_width, lookahead_hi)),
            ("off_k1", (0, 1)),
            ("on_k1", (spec_width, 1)),
        ):
            eng = engines[(spec, k)]
            overlap = k > 1
            if (spec, k) not in warmed:
                # Full-shape warm round: identical gen/batch so every
                # compile (window program, K=1 path, deferred sampler)
                # lands before the measured rounds.
                run_round(eng, f"warm-{label}", prompts, overlap=overlap)
                warmed.add((spec, k))
            # Per-ROUND spec ledger deltas (spec_summary is engine-
            # cumulative; the warm + other-workload rounds must not
            # leak into this cell's acceptance rate).
            s0 = eng.spec_summary() or {}
            r = run_round(eng, f"{wl}-{label}", prompts, overlap=overlap)
            s1 = eng.spec_summary() or {}
            acc = s1.get("accepted", 0) - s0.get("accepted", 0)
            rej = s1.get("rejected", 0) - s0.get("rejected", 0)
            rounds[label] = {
                "per_token_ms": r["per_token_ms"],
                "decode_tokens": r["decode_tokens"],
                "goodput": r["goodput"],
                **(
                    {
                        "acceptance_rate": (
                            round(acc / (acc + rej), 4)
                            if acc + rej else 0.0
                        ),
                        "accepted": acc,
                        "rejected": rej,
                        "proposals": (
                            s1.get("proposals", 0)
                            - s0.get("proposals", 0)
                        ),
                    }
                    if spec else {}
                ),
                "outputs": r["outputs"],
            }
        bit = (
            rounds["off_k8"]["outputs"] == rounds["on_k8"]["outputs"]
            == rounds["off_k1"]["outputs"] == rounds["on_k1"]["outputs"]
        )
        entry = {
            k2: {kk: vv for kk, vv in v.items() if kk != "outputs"}
            for k2, v in rounds.items()
        }
        entry["bit_identical"] = bit
        entry["speedup_k8"] = round(
            rounds["off_k8"]["per_token_ms"]
            / max(1e-9, rounds["on_k8"]["per_token_ms"]), 3,
        )
        entry["speedup_k1"] = round(
            rounds["off_k1"]["per_token_ms"]
            / max(1e-9, rounds["on_k1"]["per_token_ms"]), 3,
        )
        result[wl] = entry
    # Seeded pair (K=8, repetitive): the lockstep verifier must leave a
    # seeded sampled stream bitwise unchanged.
    rep = workloads["repetitive"]
    s_off = run_round(engines[(0, lookahead_hi)], "seed-off", rep,
                      temp=0.8, seed=1234)
    run_round(engines[(spec_width, lookahead_hi)], "seed-warm", rep,
              temp=0.8, seed=1234)
    s_on = run_round(engines[(spec_width, lookahead_hi)], "seed-on", rep,
                     temp=0.8, seed=1234)
    result["repetitive"]["seeded_bit_identical"] = (
        s_off["outputs"] == s_on["outputs"]
    )
    return result


def _constrained_probe(model, params, kv_dtype: str) -> dict:
    """Constrained-decoding probe (detail.constrained,
    docs/decode_loop.md): JSON-schema-constrained vs unconstrained
    decode on one K=8 engine geometry. The grammar mask runs INSIDE the
    fused decode window (dense device transition table + packed bitsets,
    DFA state in the scan carry), so constrained rows must hold >=80%
    of the unconstrained tokens/s — and the committed streams must be
    bit-identical to the K=1 host-synchronous sampler, valid under the
    schema, with ZERO host-sync fallbacks on the window engine. The CI
    constrained-decode smoke asserts exactly those verdicts; the
    structural keys are pinned by test_bench_contract.
    """
    import json as _json

    from parallax_tpu.runtime.engine import (
        EngineConfig,
        StageEngine,
        drive_step,
    )
    from parallax_tpu.runtime.request import Request, SamplingParams

    vocab = int(model.config.vocab_size)
    eos = vocab - 1
    n_bytes = min(256, vocab - 1)
    grammar_vocab = (
        [bytes([i]) for i in range(n_bytes)]
        + [b""] * (vocab - n_bytes)
    )
    schema = _json.dumps({
        "type": "object",
        "properties": {"v": {"enum": ["x", "y"]}},
        "required": ["v"],
    })
    batch, prompt_len, gen_len = 8, 16, 96
    page_size = 16
    max_len = prompt_len + gen_len + 3 * page_size
    lookahead_hi = 8

    def make_engine(k: int) -> StageEngine:
        eng = StageEngine(model, params, EngineConfig(
            page_size=page_size,
            num_pages=batch * ((max_len + page_size - 1) // page_size + 1),
            max_batch_size=batch,
            max_model_len=max_len,
            kv_dtype=kv_dtype,
            enable_prefix_cache=False,
            decode_lookahead=k,
        ))
        eng.set_grammar_vocab(grammar_vocab, eos)
        return eng

    def run_round(eng, tag, constrained, overlap=True):
        """One full batch to completion; decode-phase wall ms per
        committed token (same amortization as the spec probe). The K=8
        rounds run the serving default (overlap); the K=1 oracle round
        runs SYNC so every token goes through the host sampler."""
        eng.cfg.overlap_steps = overlap
        reqs = []
        for i in range(batch):
            prompt = [1 + (7 * i + j) % (vocab - 2)
                      for j in range(prompt_len)]
            # ignore_eos on BOTH rounds: every row decodes the full
            # budget, so the per-token timing compares identical batch
            # shapes (constrained rows park in the grammar's EOS-only
            # failsafe after the object closes; the validity check
            # strips those trailing ids).
            reqs.append(Request(
                f"con-{tag}-{i}", prompt_ids=prompt,
                sampling_params=SamplingParams(
                    temperature=0.0, max_new_tokens=gen_len,
                    json_schema=schema if constrained else None,
                    ignore_eos=True,
                ),
            ))
            eng.submit(reqs[-1])
        total = 0
        decode_t0 = None
        tokens_at_decode = 0
        t0 = time.perf_counter()
        pending = None
        while eng.has_work() or pending is not None:
            outs, pending = drive_step(eng, pending)
            for out in outs:
                total += out.num_tokens
                if decode_t0 is None:
                    running = eng.scheduler.running
                    if (
                        not eng.scheduler.wait_queue
                        and running
                        and all(r.output_ids for r in running.values())
                    ):
                        decode_t0 = time.perf_counter()
                        tokens_at_decode = total
        wall_s = time.perf_counter() - (decode_t0 or t0)
        return {
            "per_token_ms": round(
                wall_s * 1000.0 / max(1, total - tokens_at_decode), 4
            ),
            "decode_tokens": total - tokens_at_decode,
            "outputs": [list(r.output_ids) for r in reqs],
        }

    eng_win = make_engine(lookahead_hi)
    eng_sync = make_engine(1)
    # Full-shape warm rounds: every compile (plain window, gram window
    # variant, device-table build, K=1 sampler) lands before timing.
    run_round(eng_win, "warm-u", constrained=False)
    run_round(eng_win, "warm-c", constrained=True)
    run_round(eng_sync, "warm-s", constrained=True, overlap=False)

    uncon = run_round(eng_win, "uncon", constrained=False)
    con = run_round(eng_win, "con", constrained=True)
    oracle = run_round(eng_sync, "sync", constrained=True, overlap=False)

    def _valid(out):
        try:
            body = bytes(t for t in out if t < n_bytes)
            return _json.loads(body)["v"] in ("x", "y")
        except (ValueError, KeyError, TypeError):
            return False

    s = eng_win.constrained_summary() or {}
    ratio = round(
        uncon["per_token_ms"] / max(1e-9, con["per_token_ms"]), 3
    )
    return {
        "k": lookahead_hi,
        "batch": batch,
        "gen_len": gen_len,
        "unconstrained": {
            k2: v for k2, v in uncon.items() if k2 != "outputs"
        },
        "constrained": {
            k2: v for k2, v in con.items() if k2 != "outputs"
        },
        "throughput_ratio": ratio,
        "throughput_within_80pct": ratio >= 0.8,
        "bit_identical": con["outputs"] == oracle["outputs"],
        "all_valid_json": all(_valid(o) for o in con["outputs"]),
        "summary": {
            k2: s.get(k2) for k2 in (
                "window_rows", "mask_steps", "table_builds",
                "table_cache_hits", "fallbacks",
            )
        },
        "zero_fallbacks": s.get("fallbacks", 1) == 0,
    }


def _kernel_probe(page_size: int) -> dict:
    """Decode-kernel microbench (detail.kernel): per-token device ms and
    tokens/s/chip for the three decode attention implementations on ONE
    identical ragged batch — ``pallas-fused`` (KV append inside the
    attention kernel + sort-free fused sampling, one program chain),
    ``pallas-split`` (the legacy page-grid attention kernel + separate
    XLA scatter + sort-based sampler) and ``xla`` (the reference path).

    Off-TPU the Pallas impls run in interpret mode — the CI contract
    asserts fused stays strictly below split there (the fused kernels
    stream only each row's valid pages and skip the full-vocab sort,
    the split grid visits every page slot of every row), and that the
    fused and XLA token streams agree bit-for-bit (greedy + seeded).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.ops.attention import _ragged_paged_attention_xla
    from parallax_tpu.ops.attention_pallas import gqa_decode_attention_pallas
    from parallax_tpu.ops.decode_fused_pallas import (
        fused_sample_topk_pallas,
        gqa_fused_decode_pallas,
    )
    from parallax_tpu.ops.kernel_select import fused_interpret
    from parallax_tpu.ops.kv_cache_ops import reshape_and_cache
    from parallax_tpu.ops.sampling import row_gumbel, sample_tokens

    interp = fused_interpret()
    rng = np.random.default_rng(42)
    s, hq, hkv, d, v, layers = 8, 4, 2, 32, 512, 2
    page = max(8, page_size)
    # Ragged context lengths straddling page boundaries; the page table
    # is what a production decode batch looks like mid-stream.
    lens = np.array(
        [17, 4 * page, 33, 5 * page - 1, 9, 6 * page, 50, 70], np.int32
    )[:s]
    pps = int(max(lens) // page + 2)
    num_pages = s * pps + 1
    pages = np.zeros((s, pps), np.int32)
    used = 1
    for i, n in enumerate(lens):
        npg = (int(n) + page - 1) // page
        pages[i, :npg] = np.arange(used, used + npg)
        used += npg
    slot = np.array(
        [pages[i, (int(n) - 1) // page] * page + (int(n) - 1) % page
         for i, n in enumerate(lens)], np.int32,
    )
    q = jnp.asarray(rng.normal(size=(s, hq, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(s, hkv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(s, hkv, d)), jnp.float32)
    cache0 = jnp.asarray(
        rng.normal(size=(num_pages, page, 2 * hkv, d)), jnp.float32
    )
    logits = jnp.asarray(rng.normal(size=(s, v)) * 3.0, jnp.float32)
    lens_j, pages_j, slot_j = (
        jnp.asarray(lens), jnp.asarray(pages), jnp.asarray(slot)
    )
    cu = jnp.arange(s + 1, dtype=jnp.int32)
    ns = jnp.asarray([s], jnp.int32)
    temp = jnp.asarray([0.0, 0.8, 0.0, 1.1, 0.7, 0.0, 0.9, 1.0], jnp.float32)
    top_k = jnp.asarray([0, 8, 0, 16, 4, 0, 8, 0], jnp.int32)
    ones, zeros = jnp.ones((s,), jnp.float32), jnp.zeros((s,), jnp.float32)
    seeds = jnp.asarray([11, 12, 13, 14, 15, 16, 17, 18], jnp.int32)
    steps = jnp.zeros((s,), jnp.int32)
    key = jax.random.key(9)
    sm = d ** -0.5

    @jax.jit
    def chain_fused(cache):
        out = None
        for _ in range(layers):
            out, cache = gqa_fused_decode_pallas(
                q, k_new, v_new, cache, lens_j, pages_j, slot_j, None,
                sm_scale=sm, interpret=interp,
            )
        gumbel = row_gumbel(key, s, v, seeds, steps)
        toks = fused_sample_topk_pallas(
            logits, gumbel, temp, top_k, interpret=interp
        )
        return out, toks, cache

    @jax.jit
    def chain_split(cache):
        out = None
        for _ in range(layers):
            cache = reshape_and_cache(cache, k_new, v_new, slot_j)
            out = gqa_decode_attention_pallas(
                q, cache, lens_j, pages_j, None, sm_scale=sm,
                interpret=interp,
            )
        toks = sample_tokens(
            logits, key, temp, top_k, ones, zeros,
            seeds=seeds, out_steps=steps,
        )
        return out, toks, cache

    @jax.jit
    def chain_xla(cache):
        out = None
        for _ in range(layers):
            cache = reshape_and_cache(cache, k_new, v_new, slot_j)
            out = _ragged_paged_attention_xla(
                q, cache, lens_j, pages_j, cu, ns,
                sm_scale=sm, sliding_window=None, soft_cap=None,
                sinks=None,
            )
        toks = sample_tokens(
            logits, key, temp, top_k, ones, zeros,
            seeds=seeds, out_steps=steps,
        )
        return out, toks, cache

    def measure(fn):
        outs = toks = None
        for _ in range(3):   # warmup: compile + caches hot
            outs, toks, _ = fn(cache0)
            jax.block_until_ready(outs)
        walls = []
        for _ in range(9):
            t0 = time.perf_counter()
            outs, toks, cend = fn(cache0)
            jax.block_until_ready((outs, toks, cend))
            walls.append((time.perf_counter() - t0) * 1000.0)
        med = statistics.median(walls)
        return {
            "device_ms_median": round(med, 3),
            "per_token_device_ms": round(med / s, 4),
            "tokens_per_sec_per_chip": round(s / (med / 1000.0), 1),
        }, np.asarray(outs), np.asarray(toks)

    impls = {}
    impls["pallas-fused"], out_f, toks_f = measure(chain_fused)
    impls["pallas-split"], out_s, toks_s = measure(chain_split)
    impls["xla"], out_x, toks_x = measure(chain_xla)
    greedy_rows = np.asarray(temp) <= 0.0
    return {
        "batch": s,
        "layers": layers,
        "page_size": page,
        "context_lens": [int(x) for x in lens],
        "interpret_mode": interp,
        "impls": impls,
        # The acceptance contract: one fused program chain beats the
        # split dispatch chain on the same batch, and the fused draws
        # match the XLA reference bit-for-bit.
        "fused_below_split": (
            impls["pallas-fused"]["per_token_device_ms"]
            < impls["pallas-split"]["per_token_device_ms"]
        ),
        "tokens_fused_vs_xla_identical": bool(
            np.array_equal(toks_f, toks_x)
        ),
        "greedy_rows_identical_all_impls": bool(
            np.array_equal(toks_f[greedy_rows], toks_s[greedy_rows])
            and np.array_equal(toks_f[greedy_rows], toks_x[greedy_rows])
        ),
        "attn_out_close_fused_vs_xla": bool(
            np.allclose(out_f, out_x, atol=5e-5, rtol=5e-5)
        ),
    }


def _prefill_probe(page_size: int) -> dict:
    """Prefill-roofline probe (detail.prefill, docs/kernels.md): three
    sub-measurements on deterministic workloads.

    ``kernel`` — the fused ragged chunked-prefill kernel vs the XLA
    reference on ONE identical ragged chunk batch, with page CAPACITY
    far above the valid span: the XLA reference scans full capacity
    while the fused kernel streams only each row's valid pages, so the
    per-token gap shows even in CPU interpret mode. The CI
    fused-prefill smoke asserts fused strictly below XLA per token plus
    the bit-identity verdicts.

    ``warm_prefix`` — warm-prefix re-prefill with chunk skipping on vs
    off: a donor prompt releases into the radix tree while a sharer
    (admitted earlier, budget-starved) waits; with
    ``prefill_chunk_skip`` on, the sharer's chunk planning re-consults
    the tree and recomputes ZERO covered chunks. Streams must be
    bit-identical either way.

    ``interactive_under_long_prefill`` — interactive TTFT p50/p95 while
    a long prompt chunk-prefills on the same engine (the mixed-pool
    number; detail.disagg reports the disaggregated-pool counterpart
    and the mixed-vs-disagg improvement verdict).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.ops.attention import _ragged_paged_attention_xla
    from parallax_tpu.ops.kernel_select import fused_interpret
    from parallax_tpu.ops.kv_cache_ops import reshape_and_cache
    from parallax_tpu.ops.prefill_fused_pallas import (
        gqa_fused_prefill_pallas,
    )
    from parallax_tpu.runtime.engine import (
        EngineConfig,
        StageEngine,
        drive_step,
    )
    from parallax_tpu.runtime.request import Request, SamplingParams

    interp = fused_interpret()
    rng = np.random.default_rng(21)

    # -- fused vs XLA prefill chain on one ragged chunk batch ----------
    hq, hkv, d, layers = 4, 2, 32, 2
    page = max(8, page_size)
    q_lens = [17, 2 * page, 33]          # ragged, one page-exact
    cached = [0, 2 * page, 5]            # warm prefixes mid-stream
    s = len(q_lens)
    kv_lens = np.array([c + n for c, n in zip(cached, q_lens)], np.int32)
    cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
    t = int(cu[-1])
    tp = max(64, 1 << (t - 1).bit_length())
    pps = 48                             # capacity >> valid pages
    valid_pages = int(sum((int(n) + page - 1) // page for n in kv_lens))
    num_pages = s * pps + 1
    pages = (
        np.arange(s * pps, dtype=np.int32).reshape(s, pps) + 1
    )
    slots = np.full((tp,), -1, np.int32)
    for i in range(s):
        for j in range(q_lens[i]):
            pos = cached[i] + j
            slots[cu[i] + j] = pages[i, pos // page] * page + pos % page
    q = jnp.asarray(rng.normal(size=(tp, hq, d)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(tp, hkv, d)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(tp, hkv, d)), jnp.float32)
    cache0 = jnp.asarray(
        rng.normal(size=(num_pages, page, 2 * hkv, d)), jnp.float32
    )
    sinks = jnp.asarray(rng.normal(size=(hq,)), jnp.float32)
    kv_lens_j, pages_j, cu_j, slots_j = (
        jnp.asarray(kv_lens), jnp.asarray(pages), jnp.asarray(cu),
        jnp.asarray(slots),
    )
    ns = jnp.asarray([s], jnp.int32)
    sm = d ** -0.5

    @jax.jit
    def chain_fused(cache):
        out = None
        for _ in range(layers):
            out, cache = gqa_fused_prefill_pallas(
                q, k_new, v_new, cache, kv_lens_j, pages_j, cu_j, ns,
                slots_j, sinks, sm_scale=sm, use_sinks=True,
                q_block=32, interpret=interp,
            )
        return out, cache

    @jax.jit
    def chain_xla(cache):
        out = None
        for _ in range(layers):
            cache = reshape_and_cache(cache, k_new, v_new, slots_j)
            out = _ragged_paged_attention_xla(
                q, cache, kv_lens_j, pages_j, cu_j, ns,
                sm_scale=sm, sliding_window=None, soft_cap=None,
                sinks=sinks,
            )
        return out, cache

    def measure(fn):
        outs = cend = None
        for _ in range(3):   # warmup: compile + caches hot
            outs, cend = fn(cache0)
            jax.block_until_ready(outs)
        walls = []
        for _ in range(9):
            t0 = time.perf_counter()
            outs, cend = fn(cache0)
            jax.block_until_ready((outs, cend))
            walls.append((time.perf_counter() - t0) * 1000.0)
        med = statistics.median(walls)
        return {
            "device_ms_median": round(med, 3),
            "per_token_device_ms": round(med / t, 4),
            "tokens_per_sec_per_chip": round(t / (med / 1000.0), 1),
        }, np.asarray(outs), np.asarray(cend)

    impls = {}
    impls["pallas-fused"], out_f, cache_f = measure(chain_fused)
    impls["xla"], out_x, cache_x = measure(chain_xla)
    kernel = {
        "batch_tokens": t,
        "layers": layers,
        "page_size": page,
        "valid_pages": valid_pages,
        "capacity_pages": s * pps,
        "interpret_mode": interp,
        "impls": impls,
        "fused_below_xla": (
            impls["pallas-fused"]["per_token_device_ms"]
            < impls["xla"]["per_token_device_ms"]
        ),
        "cache_fused_vs_xla_identical": bool(
            np.array_equal(cache_f, cache_x)
        ),
        "attn_out_close_fused_vs_xla": bool(
            np.allclose(out_f[:t], out_x[:t], atol=5e-5, rtol=5e-5)
        ),
    }

    # -- engine workloads: a tiny GQA stage, mode-independent ----------
    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=199,
        max_position_embeddings=1024, tie_word_embeddings=False,
    ))
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)

    def drive(eng, reqs, first_token_wall=None):
        t0 = time.perf_counter()
        pending = None
        while eng.has_work() or pending is not None:
            _outs, pending = drive_step(eng, pending)
            if first_token_wall is not None:
                now = time.perf_counter()
                for req in reqs:
                    if req.request_id not in first_token_wall and (
                            req.output_ids):
                        first_token_wall[req.request_id] = (
                            now - t0
                        ) * 1000.0
        return time.perf_counter() - t0

    # Warm-prefix chunk skipping: donor a (16 exact pages) prefills in
    # one 256-token step and releases immediately (max_new=1); sharer b
    # (admitted the same step, zero budget left) plans its first chunk
    # AFTER the release — the radix re-consult covers the whole donor
    # prefix.
    pg = 16
    covered = 16 * pg
    a_ids = [int(x) for x in rng.integers(1, 198, covered)]
    b_ids = a_ids + [int(x) for x in rng.integers(1, 198, 64)]

    def warm_run(chunk_skip: bool):
        eng = StageEngine(model, params, EngineConfig(
            page_size=pg, num_pages=96, max_model_len=512,
            kv_dtype="float32", max_num_tokens_per_batch=covered,
            overlap_steps=False, enable_prefix_cache=True,
            prefill_chunk_skip=chunk_skip,
        ))
        a = Request("warm-a", prompt_ids=list(a_ids),
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=1,
                        ignore_eos=True))
        b = Request("warm-b", prompt_ids=list(b_ids),
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=4,
                        ignore_eos=True))
        eng.submit(a)
        eng.submit(b)
        wall = drive(eng, [a, b])
        return eng, (a.output_ids, b.output_ids), wall

    eng_on, streams_on, wall_on = warm_run(True)
    eng_off, streams_off, wall_off = warm_run(False)
    skipped_on = int(eng_on.cache.stats.tokens_chunk_skipped)
    warm_prefix = {
        "covered_tokens": covered,
        "tokens_chunk_skipped_on": skipped_on,
        "tokens_chunk_skipped_off": int(
            eng_off.cache.stats.tokens_chunk_skipped
        ),
        "covered_tokens_recomputed_on": covered - skipped_on,
        "wall_s_on": round(wall_on, 3),
        "wall_s_off": round(wall_off, 3),
        "re_prefill_speedup_wall": round(
            wall_off / max(wall_on, 1e-9), 3
        ),
        "streams_bit_identical": streams_on == streams_off,
    }

    # Interactive TTFT while a 512-token prompt chunk-prefills (64
    # tokens/step) on the same engine: the mixed-pool head-of-line
    # number (detail.disagg carries the disaggregated counterpart).
    long_ids = [int(x) for x in rng.integers(1, 198, 512)]
    eng = StageEngine(model, params, EngineConfig(
        page_size=pg, num_pages=128, max_model_len=768,
        kv_dtype="float32", max_num_tokens_per_batch=64,
        max_batch_size=8, enable_prefix_cache=False,
    ))
    long_req = Request("long", prompt_ids=list(long_ids),
                       sampling_params=SamplingParams(
                           temperature=0.0, max_new_tokens=4,
                           ignore_eos=True))
    inter = [
        Request(f"inter-{i}",
                prompt_ids=[int(x) for x in rng.integers(1, 198, 16)],
                sampling_params=SamplingParams(
                    temperature=0.0, max_new_tokens=4, ignore_eos=True))
        for i in range(6)
    ]
    eng.submit(long_req)
    for req in inter:
        eng.submit(req)
    ttfts: dict[str, float] = {}
    drive(eng, inter + [long_req], first_token_wall=ttfts)
    inter_ttfts = sorted(
        v for k, v in ttfts.items() if k.startswith("inter-")
    )

    def pct(xs, p):
        if not xs:
            return 0.0
        return round(xs[min(len(xs) - 1, int(p * len(xs)))], 2)

    interactive = {
        "long_prompt_tokens": len(long_ids),
        "chunk_tokens": 64,
        "requests": len(inter),
        "completed": sum(
            1 for r in inter if r.status.is_finished
        ),
        "ttft_p50_ms": pct(inter_ttfts, 0.5),
        "ttft_p95_ms": pct(inter_ttfts, 0.95),
        "long_ttft_ms": round(ttfts.get("long", 0.0), 2),
    }

    return {
        "kernel": kernel,
        "warm_prefix": warm_prefix,
        "interactive_under_long_prefill": interactive,
    }


def _goodput_payload() -> dict:
    """The process goodput ledger's payload (tokens by usefulness
    bucket, time split, goodput fraction) for bench JSON."""
    try:
        import jax as _jax

        from parallax_tpu.obs.goodput import get_goodput

        return get_goodput().payload(chips=_jax.local_device_count())
    except Exception:
        return {}


def _device_payload() -> dict:
    """The device attribution plane's payload (obs/device.py): the HBM
    ledger with its invariant verdict, the compile observatory's
    per-program-family cause split, and per-program device-time shares.
    The CI device-attribution smoke asserts the ledger invariant holds
    and that steady-state decode explains every compile."""
    try:
        from parallax_tpu.obs.device import get_device_plane

        return get_device_plane().payload()
    except Exception:
        return {}


def _obs_metrics() -> dict:
    """p50/p95/p99 summary of the process metrics registry (the series
    the engine's TTFT/TPOT/step histograms accumulated this run)."""
    try:
        from parallax_tpu.obs.registry import (
            get_registry,
            summarize_snapshots,
        )

        return summarize_snapshots(get_registry().histogram_snapshots())
    except Exception:  # pragma: no cover - metrics never break the bench
        return {}


def _bench():
    import jax

    if os.environ.get("BENCH_CPU"):
        jax.config.update("jax_platforms", "cpu")
    # Shared compile-time-hygiene path (utils/compile_cache): same
    # persistent cache serve/join enable, plus the
    # parallax_xla_compiles_total counter registration.
    from parallax_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache()

    import jax.numpy as jnp
    import numpy as np

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.presets import get_preset
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.engine import (
        EngineConfig,
        StageEngine,
        drive_step,
    )
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams
    from parallax_tpu.utils.hw import detect_hardware, device_free_memory_bytes

    on_tpu = jax.default_backend() == "tpu"
    hw = detect_hardware()
    mode = os.environ.get("BENCH_MODEL", "").lower()
    temp = float(os.environ.get("BENCH_TEMP", "0"))

    if mode == "dsa":
        # Sparse-attention benchmark: DeepSeek-V3.2 attention geometry
        # (index_topk=2048 over the MLA latent cache) with the FFN kept
        # dense and the depth cut to 4 layers so one 16 GB chip holds
        # params + caches. Decode cost per token is dominated by the
        # indexer's full-context score pass + the top-k latent gather —
        # exactly the per-layer work a 61-layer production stage repeats.
        if on_tpu:
            raw = dict(
                architectures=["DeepseekV32ForCausalLM"], hidden_size=7168,
                num_hidden_layers=4, num_attention_heads=128,
                num_key_value_heads=128, kv_lora_rank=512, q_lora_rank=1536,
                qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                index_n_heads=64, index_head_dim=128, index_topk=2048,
                intermediate_size=18432, first_k_dense_replace=4,
                # MoE config is structurally required by the V32 model
                # class but no layer < first_k_dense_replace uses it.
                moe_intermediate_size=2048, n_routed_experts=8,
                num_experts_per_tok=2, n_shared_experts=1, n_group=2,
                topk_group=1, scoring_func="sigmoid",
                vocab_size=129280, max_position_embeddings=163840,
                rope_interleave=True, tie_word_embeddings=False,
            )
            cfg = normalize_config(raw, model_name="dsa-bench")
            batch = int(os.environ.get("BENCH_BATCH", "32"))
            prompt_len = int(os.environ.get("BENCH_CTX", "8192"))
            dtype, kv_dtype, page_size = jnp.bfloat16, "bfloat16", 64
            lookahead = int(os.environ.get("BENCH_LOOKAHEAD", "8"))
            pipeline = int(os.environ.get("BENCH_PIPELINE", "2"))
            gen_len = max(65, 1 + max(1, pipeline) * max(1, lookahead))
        else:
            raw = dict(
                architectures=["DeepseekV32ForCausalLM"], hidden_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, kv_lora_rank=32, q_lora_rank=48,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                index_n_heads=4, index_head_dim=32, index_topk=64,
                intermediate_size=128, first_k_dense_replace=2,
                moe_intermediate_size=32, n_routed_experts=4,
                num_experts_per_tok=2, n_shared_experts=1, n_group=2,
                topk_group=1, scoring_func="sigmoid",
                vocab_size=512, max_position_embeddings=2048,
                rope_interleave=True, tie_word_embeddings=False,
            )
            cfg = normalize_config(raw, model_name="dsa-bench")
            batch, prompt_len, gen_len = 4, 128, 8
            dtype, kv_dtype, page_size = jnp.float32, "float32", 16
            lookahead = int(os.environ.get("BENCH_LOOKAHEAD", "1"))
            pipeline = int(os.environ.get("BENCH_PIPELINE", "1"))
    elif mode == "hybrid":
        # Hybrid (linear-attention) benchmark: Qwen3-Next per-layer
        # geometry (GatedDeltaNet 3:1 with gated full attention, dense
        # FFN) cut to a reduced-depth stage one chip holds. Decode runs
        # the FUSED multistep window — the recurrence advances inside the
        # scan — so the number reflects the production hybrid path.
        if on_tpu:
            raw = dict(
                architectures=["Qwen3NextForCausalLM"], hidden_size=2048,
                num_hidden_layers=8, num_attention_heads=16,
                num_key_value_heads=2, head_dim=256,
                intermediate_size=5120,
                moe_intermediate_size=1024, num_experts=8,
                num_experts_per_tok=2,
                shared_expert_intermediate_size=1024,
                decoder_sparse_step=1, mlp_only_layers=[],
                norm_topk_prob=True,
                layer_types=["linear_attention", "linear_attention",
                             "linear_attention", "full_attention"] * 2,
                linear_conv_kernel_dim=4, linear_num_key_heads=16,
                linear_num_value_heads=32, linear_key_head_dim=128,
                linear_value_head_dim=128, partial_rotary_factor=0.25,
                vocab_size=151936, max_position_embeddings=32768,
                rope_theta=10000000.0, tie_word_embeddings=False,
                attention_bias=False,
            )
            cfg = normalize_config(raw, model_name="hybrid-bench")
            batch = int(os.environ.get("BENCH_BATCH", "64"))
            prompt_len = int(os.environ.get("BENCH_CTX", "512"))
            dtype, kv_dtype, page_size = jnp.bfloat16, "bfloat16", 64
            lookahead = int(os.environ.get("BENCH_LOOKAHEAD", "16"))
            pipeline = int(os.environ.get("BENCH_PIPELINE", "4"))
            gen_len = max(129, 1 + max(1, pipeline) * max(1, lookahead))
        else:
            raw = dict(
                architectures=["Qwen3NextForCausalLM"], hidden_size=64,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, intermediate_size=128,
                moe_intermediate_size=32, num_experts=4,
                num_experts_per_tok=2, shared_expert_intermediate_size=32,
                decoder_sparse_step=1, mlp_only_layers=[],
                norm_topk_prob=True,
                layer_types=["linear_attention", "full_attention"] * 2,
                linear_conv_kernel_dim=4, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=16,
                linear_value_head_dim=16, partial_rotary_factor=0.25,
                vocab_size=512, max_position_embeddings=2048,
                rope_theta=10000.0, tie_word_embeddings=False,
                attention_bias=False,
            )
            cfg = normalize_config(raw, model_name="hybrid-bench")
            batch, prompt_len, gen_len = 4, 64, 16
            dtype, kv_dtype, page_size = jnp.float32, "float32", 16
            lookahead = int(os.environ.get("BENCH_LOOKAHEAD", "4"))
            pipeline = int(os.environ.get("BENCH_PIPELINE", "1"))
    elif on_tpu:
        full = get_preset("qwen2.5-7b")
        # One chip's workload of 2-stage PP: half the layers (+ both ends).
        cfg = dataclasses.replace(
            full,
            num_hidden_layers=full.num_hidden_layers // 2,
            layer_types=full.layer_types[: full.num_hidden_layers // 2],
        )
        batch = int(os.environ.get("BENCH_BATCH", "128"))
        prompt_len = 128
        dtype, kv_dtype, page_size = jnp.bfloat16, "bfloat16", 64
        lookahead = int(os.environ.get("BENCH_LOOKAHEAD", "32"))
        pipeline = int(os.environ.get("BENCH_PIPELINE", "7"))
        # Generation ends exactly on a chain boundary (1 prefill token +
        # pipeline*k chained decode tokens) so no window compute is
        # discarded by mid-chain finishes. Floor of 193 keeps the unfused
        # measurement (BENCH_LOOKAHEAD=1) at ~192 decode samples instead
        # of collapsing to pipeline*1 tokens.
        gen_len = max(193, 1 + max(1, pipeline) * max(1, lookahead))
    else:
        # CPU smoke mode (BENCH_CPU=1): tiny shapes, same code path.
        # Sized HOST-bound (per-step host work > device exec) so the
        # overlapped decode loop's recovered idle time is visible in the
        # sync-vs-overlap comparison — the regime the TPU hot path lives
        # in (r05: decode_dispatch 3.51 ms, mostly host).
        cfg = dataclasses.replace(
            get_preset("qwen2.5-0.5b"),
            hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=256,
            vocab_size=512, layer_types=("attention",) * 4,
            tie_word_embeddings=False, attention_bias=False,
        )
        # gen_len sized for a stable decode phase: the K=1 probe rounds
        # get ~127 dispatch samples (the r05 window of 15 was too small
        # for a trustworthy median) and the K-window rounds still see
        # ~16 host visits. Lookahead matches the engine's adaptive
        # default (ADAPTIVE_DECODE_LOOKAHEAD) so the smoke measures the
        # production configuration.
        batch, prompt_len, gen_len = 16, 32, 128
        dtype, kv_dtype, page_size = jnp.float32, "float32", 16
        lookahead = int(os.environ.get("BENCH_LOOKAHEAD", "8"))
        pipeline = int(os.environ.get("BENCH_PIPELINE", "1"))

    model = create_stage_model(cfg, 0, cfg.num_hidden_layers)
    params = model.init_params(jax.random.key(0), dtype=dtype)
    quant = os.environ.get("BENCH_QUANT", "")   # "int8" / "int4" opt-in
    if quant:
        from parallax_tpu.ops.quant import quantize_tree

        params = quantize_tree(params, bits=int(quant.removeprefix("int")))
    params = jax.tree.map(lambda x: x.block_until_ready(), params)
    params_bytes = sum(
        x.nbytes for x in jax.tree.leaves(params) if hasattr(x, "nbytes")
    )

    max_model_len = prompt_len + gen_len + page_size
    pages_needed = ((max_model_len + page_size - 1) // page_size + 1) * batch
    if on_tpu:
        from parallax_tpu.runtime.cache_manager import derive_num_pages

        free = device_free_memory_bytes(fraction=0.85)
        num_pages = min(
            derive_num_pages(free, cfg, cfg.num_hidden_layers, page_size),
            pages_needed,
        )
    else:
        num_pages = pages_needed

    # A memory-tight chip may cap num_pages below full-batch demand; shrink
    # the batch so every request admits up front — otherwise the decode
    # phase (all requests admitted + first token sampled) never starts and
    # the measurement below would be meaningless.
    pages_per_req = (max_model_len + page_size - 1) // page_size + 1
    batch = min(batch, max(1, num_pages // pages_per_req))

    engine = StageEngine(
        model,
        params,
        EngineConfig(
            page_size=page_size,
            num_pages=num_pages,
            max_batch_size=batch,
            max_num_tokens_per_batch=max(2048, prompt_len),
            prefill_chunk_size=max(1024, min(prompt_len, 8192)),
            max_model_len=max_model_len,
            kv_dtype=kv_dtype,
            enable_prefix_cache=False,   # measure raw compute, not cache hits
            decode_lookahead=lookahead,
            decode_pipeline=pipeline,
        ),
    )
    pipe = InProcessPipeline([engine])
    rng = np.random.default_rng(0)

    def run_round(tag: str, n_gen: int, overlap: bool,
                  lookahead_k: int | None = None,
                  rng_seed: int | None = None):
        """Submit a full batch and run it to completion through the
        two-phase dispatch/resolve loop (one step in flight when
        ``overlap``; fully synchronous otherwise). ``lookahead_k`` pins
        the engine's decode_lookahead for this round only (the probe
        rounds compare K-on vs K-off on one engine); ``rng_seed`` draws
        the prompts from a dedicated generator so two probe rounds see
        identical prompts (bit-identity checks).

        Returns a dict of decode-phase measurements. Phase detection is
        by scheduler state, not token counts (with lookahead a decode
        dispatch commits k*batch tokens, which a size heuristic would
        misread as prefill): decode starts once every request is admitted
        and has sampled its first token. TTFT per request = first sampled
        token's wall time minus the round start (all requests submitted
        up front). ``dispatch_times`` is the HOST-BLOCKING ms per decode
        HOST VISIT (StepOutputs.host_ms) — in sync mode that is the whole
        step wall, in overlap mode the portion the device could not hide;
        with K-step windows one visit commits up to k*batch tokens.
        """
        engine.cfg.overlap_steps = overlap
        prev_k = engine.cfg.decode_lookahead
        if lookahead_k is not None:
            engine.cfg.decode_lookahead = lookahead_k
        try:
            return _run_round_body(tag, n_gen, rng_seed)
        finally:
            # A raising round must not leak its pinned K into later
            # rounds (the probe/sync rounds share this engine).
            engine.cfg.decode_lookahead = prev_k

    def _run_round_body(tag: str, n_gen: int, rng_seed: int | None):
        rng_round = (
            np.random.default_rng(rng_seed) if rng_seed is not None else rng
        )
        submitted: list[Request] = []
        for i in range(batch):
            prompt = rng_round.integers(1, cfg.vocab_size - 1, size=prompt_len)
            req = Request(
                request_id=f"{tag}{i}",
                prompt_ids=[int(x) for x in prompt],
                sampling_params=SamplingParams(
                    temperature=temp, max_new_tokens=n_gen, ignore_eos=True,
                ),
            )
            submitted.append(req)
            pipe.submit(req)
        dispatch_times: list[float] = []
        readback_wait_times: list[float] = []
        wall_times: list[float] = []
        overlapped_steps = 0
        ttft_ms: dict[str, float] = {}
        total_tokens = 0
        decode_t0 = None
        tokens_at_decode_start = 0
        t_start = time.perf_counter()
        pending = None
        while engine.has_work() or pending is not None:
            outs, pending = drive_step(engine, pending)
            now = time.perf_counter()
            for out in outs:
                total_tokens += out.num_tokens
                for req in submitted:
                    if req.request_id not in ttft_ms and req.output_ids:
                        ttft_ms[req.request_id] = (now - t_start) * 1000.0
                if decode_t0 is not None and out.num_tokens:
                    dispatch_times.append(out.host_ms)
                    readback_wait_times.append(out.readback_wait_ms)
                    wall_times.append(out.step_time_ms)
                    overlapped_steps += int(out.overlapped)
                elif decode_t0 is None:
                    running = engine.scheduler.running
                    if (
                        not engine.scheduler.wait_queue
                        and running
                        and all(r.output_ids for r in running.values())
                    ):
                        decode_t0 = time.perf_counter()
                        tokens_at_decode_start = total_tokens
        decode_wall_s = time.perf_counter() - (decode_t0 or t_start)
        return dict(
            decode_tokens=total_tokens - tokens_at_decode_start,
            decode_wall_s=decode_wall_s,
            dispatch_times=dispatch_times,
            readback_wait_times=readback_wait_times,
            wall_times=wall_times,
            overlapped_steps=overlapped_steps,
            phase_ok=decode_t0 is not None,
            ttfts=sorted(ttft_ms.values()),
            # Host visits during the decode phase + the streams, for the
            # multi-step probe's amortization and bit-identity contract.
            decode_host_visits=len(dispatch_times),
            outputs=[list(req.output_ids) for req in submitted],
        )

    overlap_on = os.environ.get("BENCH_OVERLAP", "1") != "0"
    # Warmup rounds: populate every jit cache the measured rounds will
    # hit (prefill bucket, fused multi-step decode window, the K=1
    # single-step decode path + deferred sampler for the probe/sync
    # rounds), so the measured decode phases contain zero compiles.
    t_start = time.perf_counter()
    run_round("warm", lookahead + 1, overlap_on)
    if lookahead > 1:
        run_round("warmoff", 3, overlap_on, lookahead_k=1)
    r = run_round("bench", gen_len, overlap_on)
    decode_tokens, decode_wall_s, dispatch_times, phase_ok, ttfts = (
        r["decode_tokens"], r["decode_wall_s"], r["dispatch_times"],
        r["phase_ok"], r["ttfts"],
    )

    def _round_summary(rr: dict) -> dict:
        """Per-round decode summary for side-by-side probe reporting."""
        visits = rr["decode_host_visits"]
        med = (
            statistics.median(rr["dispatch_times"])
            if rr["dispatch_times"] else 0.0
        )
        tpv = rr["decode_tokens"] / max(1, visits)
        return {
            "decode_dispatch_ms_median": round(med, 3),
            "decode_host_visits": visits,
            "decode_tokens": rr["decode_tokens"],
            "tokens_per_host_visit": round(tpv, 2),
            # The number TPOT pays: the host-visit median amortized over
            # the tokens one visit commits.
            "per_token_host_ms": round(med / max(1.0, tpv), 4),
            "decode_wall_s": round(rr["decode_wall_s"], 3),
        }

    # Multi-step decode probe: the SAME engine run K-on vs K-off over
    # identical prompts, in the serving-default overlap mode AND in sync
    # mode, with all four greedy streams required bit-identical. The
    # per-token amortization contract (CI multi-step smoke) is pinned on
    # the SYNC pair: there one host visit's cost is paid exactly once
    # per K tokens, so K>1 wins by construction whenever the visit has
    # any host cost at all. The overlap pair is reported side by side —
    # on the host-bound TPU path it shows the same win directly, while
    # on the device-cheap CPU smoke the K=1 overlap loop already hides
    # most device time, making that pair close to a wash. Cheap on CPU
    # (part of the smoke contract); opt-in on TPU (BENCH_MULTISTEP)
    # where the main round already runs K>1.
    multistep_probe = None
    sync_r = None
    if not on_tpu or os.environ.get("BENCH_MULTISTEP"):
        ms_k = lookahead if lookahead > 1 else 4
        mon = run_round("mson", gen_len, overlap_on,
                        lookahead_k=ms_k, rng_seed=1234)
        moff = run_round("msoff", gen_len, overlap_on,
                         lookahead_k=1, rng_seed=1234)
        son = run_round("msonsync", gen_len, False,
                        lookahead_k=ms_k, rng_seed=1234)
        soff = run_round("msoffsync", gen_len, False,
                         lookahead_k=1, rng_seed=1234)
        engine.cfg.overlap_steps = overlap_on
        multistep_probe = {
            "k": ms_k,
            "on": _round_summary(mon),
            "off": _round_summary(moff),
            "sync_on": _round_summary(son),
            "sync_off": _round_summary(soff),
            "bit_identical": (
                mon["outputs"] == moff["outputs"]
                == son["outputs"] == soff["outputs"]
            ),
        }
        # The K=1 sync round doubles as the overlap-loop comparison
        # baseline (sync_decode_dispatch_ms_median) below.
        sync_r = soff
    # Same-invocation sync comparison: how much host-blocking time the
    # overlapped loop recovers, measured at K=1 on BOTH sides (the
    # overlap side is the probe's K-off round) — a K-window visit wall
    # would drown the per-step comparison. Cheap on CPU (the smoke's
    # contract); opt-in on TPU where the fused window already owns the
    # budget.
    if sync_r is None and overlap_on and (
        not on_tpu or os.environ.get("BENCH_SYNC_COMPARE")
    ):
        sync_r = run_round("sync", gen_len, False, lookahead_k=1,
                           rng_seed=1234)
        engine.cfg.overlap_steps = overlap_on

    # Host-KV-tier pressure probe: the same model under a page budget the
    # working set exceeds, run twice — tier OFF (today's behavior: decode
    # OOM aborts) vs tier ON (radix eviction demotes to host DRAM, decode
    # OOM preempts-to-host, prefix hits swap back in). Two waves of
    # identical prompts make the prefix-hit-ratio difference visible:
    # with the tier, wave-2 prefixes survive the pressure in host memory.
    # Cheap on CPU (part of the smoke contract); opt-in on TPU.
    host_cache_probe = None
    if not on_tpu or os.environ.get("BENCH_HOST_CACHE"):
        prng = np.random.default_rng(7)
        n_press, ppages, gpages = 4, 3, 2
        shared_prefix = [
            int(x) for x in prng.integers(
                1, cfg.vocab_size - 1, size=2 * page_size
            )
        ]
        tails = [
            [int(x) for x in prng.integers(
                1, cfg.vocab_size - 1, size=page_size
            )]
            for _ in range(n_press)
        ]

        def pressure_round(host_bytes: int) -> dict:
            p_len = ppages * page_size
            g_len = gpages * page_size
            # A page budget below the wave's working set (but above one
            # request's demand): pressure is guaranteed, forward
            # progress too.
            budget_pages = n_press * (ppages + gpages) - ppages
            eng = StageEngine(model, params, EngineConfig(
                page_size=page_size,
                num_pages=budget_pages + 1,   # +1 reserved null page
                max_batch_size=n_press,
                max_model_len=2 * (p_len + g_len) + 2 * page_size,
                kv_dtype=kv_dtype,
                enable_prefix_cache=True,
                host_cache_bytes=host_bytes,
            ))

            def wave(tag, prompts):
                reqs = []
                for i, prompt in enumerate(prompts):
                    req = Request(
                        request_id=f"{tag}-{i}",
                        prompt_ids=list(prompt),
                        sampling_params=SamplingParams(
                            temperature=0.0, max_new_tokens=g_len,
                            ignore_eos=True,
                        ),
                    )
                    reqs.append(req)
                    eng.submit(req)
                pending, guard = None, 0
                while (eng.has_work() or pending is not None
                       ) and guard < 20000:
                    guard += 1
                    _outs, pending = drive_step(eng, pending)
                return reqs

            w1 = wave("pw1", [shared_prefix + t for t in tails])
            # Wave 2: follow-up turns over wave 1's full conversations.
            # The deep context pages were evicted under wave-1/2 pressure
            # — with the tier they demoted to host and swap back in on
            # the re-match; without it they are gone and recompute.
            w2 = wave("pw2", [
                r.all_token_ids + t[: page_size]
                for r, t in zip(w1, reversed(tails))
            ])
            done = w1 + w2
            stats = dict(eng.cache_stats() or {})
            stats["requests"] = len(done)
            # Only genuinely finished, non-aborted requests count — a
            # request stuck PENDING/PREEMPTED when the guard tripped is
            # a failure, not a completion (the CI contract asserts
            # completed == requests for the tier-on run).
            stats["completed"] = sum(
                1 for r in done
                if r.status.is_finished
                and r.status.value != "finished_abort"
            )
            return stats

        host_cache_probe = {
            "enabled": pressure_round(1 << 28),
            "disabled": pressure_round(0),
        }

    # Activation-transport probe: a two-stage LOOPBACK swarm (real
    # WorkerNodes, real wire serialization, in-process transport) run
    # twice — clean links vs an injected slow peer (every inter-stage
    # send sleeps ``delay``). The async sender pipeline moves serialize +
    # send off the step thread, so the head's decode DISPATCH cadence
    # (host-blocking ms per step) must stay at the no-delay level while
    # the per-peer queue absorbs the stall; a synchronous sender would
    # push it past the injected delay. Cheap on CPU (part of the smoke
    # contract); opt-in on TPU.
    transport_probe = None
    if not on_tpu or os.environ.get("BENCH_TRANSPORT"):
        transport_probe = _transport_probe(
            cfg, stage_params_fn=lambda m: m.init_params(
                jax.random.key(m.start_layer * 1000 + m.end_layer),
                dtype=dtype,
            ),
            kv_dtype=kv_dtype, page_size=page_size,
        )

    # Prefix-cache-aware routing probe: a two-replica loopback swarm
    # serving a shared-prefix multi-turn workload, once with blind
    # round-robin and once with cache-aware routing. The cache-aware run
    # must win on BOTH prefix hit rate and follow-up-turn TTFT (the CI
    # routing smoke asserts the hit-rate half of that contract). Cheap on
    # CPU (part of the smoke contract); opt-in on TPU.
    routing_probe = None
    if not on_tpu or os.environ.get("BENCH_ROUTING"):
        routing_probe = _routing_probe(
            cfg, stage_params_fn=lambda m: m.init_params(
                jax.random.key(m.start_layer * 1000 + m.end_layer),
                dtype=dtype,
            ),
            kv_dtype=kv_dtype, page_size=page_size,
        )

    # Node-churn robustness probe: a two-replica two-stage loopback
    # swarm, served clean and then with a chaos-killed tail stage
    # mid-decode. The live-migration flow must deliver 0 aborts and
    # bit-identical streams, with park->resume latency reported as
    # p50/p95 (the CI chaos smoke asserts the contract). Cheap on CPU
    # (part of the smoke contract); opt-in on TPU.
    churn_probe = None
    if not on_tpu or os.environ.get("BENCH_CHURN"):
        churn_probe = _churn_probe(
            cfg, stage_params_fn=lambda m: m.init_params(
                jax.random.key(m.start_layer * 1000 + m.end_layer),
                dtype=dtype,
            ),
            kv_dtype=kv_dtype, page_size=page_size,
        )

    # Multi-tenant QoS probe: the same batch-flood + interactive
    # workload served unloaded / QoS-off / QoS-on on one engine. QoS on
    # must hold interactive p99 TTFT near its unloaded value (shed +
    # park through the host tier) while batch still commits every token,
    # with off-vs-on streams bit-identical (the off-inertness /
    # enforcement-not-abort acceptance contract; docs/qos.md). Cheap on
    # CPU (part of the smoke contract); opt-in on TPU (BENCH_QOS).
    qos_probe = None
    if not on_tpu or os.environ.get("BENCH_QOS"):
        qos_probe = _qos_probe(cfg, dtype, kv_dtype, page_size)

    # Speculative-decoding probe: the acceptance-rate x speedup matrix
    # (spec on/off x K=1/K=8 x repetitive/random prompts) with the
    # goodput accepted-vs-rejected split, greedy + seeded bit-identity.
    # The CI spec smoke asserts spec-on strictly below spec-off at K=8
    # on the repetitive workload. Cheap on CPU (part of the smoke
    # contract); opt-in on TPU (BENCH_SPEC).
    spec_probe = None
    if not on_tpu or os.environ.get("BENCH_SPEC"):
        spec_probe = _spec_probe(model, params, kv_dtype)

    # Constrained-decoding probe: JSON-schema-constrained vs
    # unconstrained decode on one K=8 engine — grammar masking inside
    # the fused window must hold >=80% of unconstrained tokens/s with
    # streams bit-identical to the K=1 host-sync sampler and zero
    # fallbacks. Cheap on CPU (part of the smoke contract); opt-in on
    # TPU (BENCH_CONSTRAINED).
    constrained_probe = None
    if not on_tpu or os.environ.get("BENCH_CONSTRAINED"):
        constrained_probe = _constrained_probe(model, params, kv_dtype)

    # Decode-kernel microbench: fused vs split vs XLA attention(+append
    # +sampling) chains on one identical ragged batch — per-token device
    # ms and tokens/s/chip per impl, plus the fused-below-split and
    # fused-vs-XLA bit-identity verdicts the CI fused-decode smoke
    # asserts. Cheap on CPU (interpret mode, part of the smoke
    # contract); opt-in on TPU (BENCH_KERNEL) where it compiles the
    # real kernels.
    kernel_probe = None
    if not on_tpu or os.environ.get("BENCH_KERNEL"):
        kernel_probe = _kernel_probe(page_size)

    # Prefill-roofline probe: fused vs XLA prefill chains on one ragged
    # chunk batch (capacity >> valid pages), warm-prefix re-prefill with
    # chunk skipping on/off, and interactive TTFT under a long chunked
    # prefill — the CI fused-prefill smoke asserts fused strictly below
    # XLA per token, zero covered chunks recomputed, and stream
    # bit-identity. Cheap on CPU (interpret mode, part of the smoke
    # contract); opt-in on TPU (BENCH_PREFILL).
    prefill_probe = None
    if not on_tpu or os.environ.get("BENCH_PREFILL"):
        prefill_probe = _prefill_probe(page_size)

    # Disaggregated prefill/decode probe: the same long-prefill +
    # chatty-decode workload served by a mixed pool and by a prefill
    # specialist handing requests to a decode specialist over the
    # KV-transfer lane. Mixed and disaggregated streams must be
    # bit-identical, with zero aborts and kv_transfer telemetry
    # populated (the CI disaggregation smoke asserts the contract).
    # Cheap on CPU (part of the smoke contract); opt-in on TPU.
    disagg_probe = None
    if not on_tpu or os.environ.get("BENCH_DISAGG"):
        disagg_probe = _disagg_probe(
            cfg, stage_params_fn=lambda m: m.init_params(
                jax.random.key(m.start_layer * 1000 + m.end_layer),
                dtype=dtype,
            ),
            kv_dtype=kv_dtype, page_size=page_size,
        )
    total_s = time.perf_counter() - t_start

    # Decode throughput over the whole decode phase (wall-clock, includes
    # all host overhead between dispatches). 2-stage PP accounting: the
    # pipeline emits one batch per *stage* step and we measured one
    # stage's workload, so per-chip rate is half the measured rate.
    step_ms = statistics.median(dispatch_times) if dispatch_times else 0.0
    pp_div = 1.0 if mode in ("dsa", "hybrid") else 2.0
    tokens_per_sec_per_chip = decode_tokens / max(decode_wall_s, 1e-9) / pp_div
    if not phase_ok:
        # Never report prefill tokens as decode throughput.
        tokens_per_sec_per_chip = 0.0
    ttft_p50 = statistics.median(ttfts) if ttfts else 0.0

    if mode == "dsa":
        # vs_baseline for the sparse bench: achieved HBM bandwidth over
        # the 40%-of-roofline efficiency the main baseline assumes.
        # Decode-step bytes ~= params + per-layer sparse traffic: the
        # indexer's full-context score pass reads the paged index keys
        # [ctx, idx_dim] and the sparse attention gathers [topk,
        # latent+rope] per request per layer (bf16 = 2 B).
        elem = 2 if on_tpu else 4
        d = cfg.dsa
        sparse_bytes = (
            batch * cfg.num_hidden_layers * (
                prompt_len * (d.index_head_dim if d else 0) * elem
                + (d.index_topk if d else 0)
                * (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim) * elem
            )
        )
        step_bytes = params_bytes + sparse_bytes
        bw = hw.hbm_gbps * 1e9 if on_tpu else 50e9
        roofline_steps = bw / max(step_bytes, 1)
        roofline_tps = roofline_steps * batch
        vs_baseline = tokens_per_sec_per_chip / max(0.4 * roofline_tps, 1e-9)
        metric = (
            f"output tokens/sec/chip (DSA sparse decode, V3.2 geometry, "
            f"ctx={prompt_len}, topk={d.index_topk if d else 0})"
        )
    elif mode == "hybrid":
        # vs_baseline: achieved HBM bandwidth over the same
        # 40%-of-roofline efficiency bar. Decode-step bytes ~= params +
        # per-request linear-state traffic (conv + recurrent rows read
        # AND written per linear layer) + the full-attention layers'
        # context KV reads.
        elem = 2 if on_tpu else 4
        la = cfg.linear_attn
        n_linear = sum(
            1 for i in range(cfg.num_hidden_layers)
            if cfg.layer_type(i) == "linear_attention"
        )
        n_full = cfg.num_hidden_layers - n_linear
        conv_dim = (2 * la.num_k_heads * la.head_k_dim
                    + la.num_v_heads * la.head_v_dim)
        state_bytes = 2 * batch * n_linear * (
            conv_dim * (la.conv_kernel_size - 1)
            + la.num_v_heads * la.head_k_dim * la.head_v_dim
        ) * 4   # state arrays are f32
        kv_bytes = (
            batch * n_full * prompt_len
            * 2 * cfg.num_key_value_heads * cfg.head_dim * elem
        )
        step_bytes = params_bytes + state_bytes + kv_bytes
        bw = hw.hbm_gbps * 1e9 if on_tpu else 50e9
        roofline_tps = bw / max(step_bytes, 1) * batch
        vs_baseline = tokens_per_sec_per_chip / max(0.4 * roofline_tps, 1e-9)
        metric = (
            f"output tokens/sec/chip (hybrid GatedDeltaNet decode, "
            f"Qwen3-Next geometry, fused window, ctx={prompt_len})"
        )
    else:
        vs_baseline = (
            tokens_per_sec_per_chip / BASELINE_TOKENS_PER_SEC_PER_CHIP
        )
        metric = (
            "output tokens/sec/chip (Qwen2.5-7B, 2-stage PP accounting)"
            if on_tpu
            else "output tokens/sec/chip (CPU smoke, tiny model)"
        )

    # One consistent snapshot feeds both kernel fields below.
    kernel_summary = engine.kernel_dispatch_summary()
    result = {
        "metric": metric,
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 3),
        "detail": {
            # What JAX ran this on ("device" below is the attribution
            # plane's payload).
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "stage_layers": cfg.num_hidden_layers,
            "batch": batch,
            "prompt_len": prompt_len,
            "temperature": temp,
            "decode_lookahead": lookahead,
            "decode_pipeline": pipeline,
            "decode_phase_detected": phase_ok,
            **({"quantization": quant} if quant else {}),
            **({"bench_model": mode} if mode else {}),
            "params_gb": round(params_bytes / 1e9, 2),
            "ttft_p50_ms": round(ttft_p50, 1),
            "decode_dispatch_ms_median": round(step_ms, 2),
            "decode_dispatches": len(dispatch_times),
            # Multi-step decode accounting: one host visit commits up to
            # decode_lookahead * batch tokens, so the per-visit median
            # above amortizes over tokens_per_host_visit (the probe
            # below compares K-on vs K-off side by side).
            "decode_host_visits": len(dispatch_times),
            "tokens_per_host_visit": round(
                decode_tokens / max(1, len(dispatch_times)), 2
            ),
            "decode_tokens": decode_tokens,
            "decode_wall_s": round(decode_wall_s, 2),
            "total_wall_s": round(total_s, 1),
            # Two-phase step telemetry (overlapped decode loop).
            # decode_dispatch_ms_median now measures the HOST-BLOCKING
            # portion per decode step (host_ms_median is its explicit
            # name); the old full-wall meaning (r05 baseline 3.51 ms)
            # lives on as decode_step_wall_ms_median. Note an overlapped
            # ticket's wall spans its interleaved next dispatch too.
            "overlap_steps": overlap_on,
            # Which attention-kernel impl produced the main metric line
            # (pallas-fused / pallas-split / xla) + the engine's
            # dispatch counts by (impl, path) — docs/kernels.md.
            "attn_impl": kernel_summary["impl"],
            "kernel_dispatches": kernel_summary["dispatch_total"],
            "host_ms_median": round(step_ms, 2),
            "decode_step_wall_ms_median": round(
                statistics.median(r["wall_times"])
                if r["wall_times"] else 0.0, 2,
            ),
            "readback_wait_ms_median": round(
                statistics.median(r["readback_wait_times"])
                if r["readback_wait_times"] else 0.0, 3,
            ),
            "overlapped_steps": r["overlapped_steps"],
            # Prefix-cache / memory-tier counters from the measured
            # engine (prefix cache off there, so mainly occupancy + OOM
            # accounting) and the host-tier pressure probe (tier on/off
            # under a page budget the working set exceeds: kv_oom_aborts,
            # preemptions, prefix_hit_rate per run).
            "cache_stats": engine.cache_stats(),
            # Observability registry percentiles (p50/p95/p99 per
            # histogram: TTFT/TPOT/e2e + step host/device ms + batch
            # tokens) — the same series /metrics exposes, proving the
            # bench run populated the unified registry.
            "metrics": _obs_metrics(),
            # Goodput ledger (obs/goodput.py): the whole run's device-
            # step tokens by usefulness bucket plus the serve/compile/
            # swap/migrate/idle time split — useful + wasted == total by
            # construction.
            "goodput": _goodput_payload(),
            # Device attribution plane (obs/device.py): HBM ledger
            # classes + invariant, per-family compiles by recompile
            # cause, per-program device-time split. The device smoke
            # asserts invariant_ok and zero unexplained steady-state
            # compiles.
            "device": _device_payload(),
            # Multi-step decode probe (same engine, identical prompts,
            # K-on vs K-off): host visits, tokens/visit, per-visit and
            # amortized per-token dispatch medians side by side, plus
            # the greedy bit-identity verdict.
            **(
                {"multistep": multistep_probe}
                if multistep_probe is not None else {}
            ),
            **(
                {"host_cache": host_cache_probe}
                if host_cache_probe is not None else {}
            ),
            # Activation-transport probe (two-stage loopback swarm,
            # clean vs injected-slow-peer links): dispatch cadence must
            # hold while the sender queue absorbs the stall; links carry
            # per-peer bytes/serialize/send/queue/compression telemetry.
            **(
                {"transport": transport_probe}
                if transport_probe is not None else {}
            ),
            # Prefix-cache-aware routing probe (two-replica loopback
            # swarm, shared-prefix multi-turn workload): per-strategy
            # prefix hit rate + follow-up TTFT p50, cache-aware decision
            # counters and predicted-vs-actual hit accuracy.
            **(
                {"routing": routing_probe}
                if routing_probe is not None else {}
            ),
            # Node-churn probe (chaos-killed tail stage mid-decode vs
            # clean run): 0 aborts, bit-identical migrated streams,
            # park->resume migration latency p50/p95.
            **(
                {"churn": churn_probe}
                if churn_probe is not None else {}
            ),
            # Disaggregated prefill/decode probe (mixed pool vs prefill
            # specialist + decode specialist on the same long-prefill +
            # chatty-decode workload): interactive TTFT p50/p95 and
            # chatty TPOT per mode, kv_transfer frames/bytes/ms +
            # handoffs, bit-identity across modes.
            **(
                {"disagg": disagg_probe}
                if disagg_probe is not None else {}
            ),
            # Multi-tenant QoS probe (unloaded / off / on mixed
            # workload): interactive TTFT held near unloaded under a
            # batch flood via shed/park, batch never starved or
            # aborted, off-vs-on streams bit-identical (docs/qos.md).
            **(
                {"qos": qos_probe}
                if qos_probe is not None else {}
            ),
            # Speculative-decoding probe (acceptance-rate x speedup
            # matrix, goodput accepted-vs-rejected split, greedy +
            # seeded bit-identity — docs/decode_loop.md).
            **(
                {"spec": spec_probe}
                if spec_probe is not None else {}
            ),
            # Constrained-decoding probe (schema-constrained vs
            # unconstrained tokens/s ratio, K=1 bit-identity, schema
            # validity, zero-fallback verdict — docs/decode_loop.md).
            **(
                {"constrained": constrained_probe}
                if constrained_probe is not None else {}
            ),
            # Decode-kernel microbench (fused vs split vs XLA per-token
            # device ms + bit-identity verdicts on one ragged batch).
            **(
                {"kernel": kernel_probe}
                if kernel_probe is not None else {}
            ),
            # Prefill roofline (fused vs XLA per-token device ms,
            # warm-prefix chunk-skip recompute, interactive TTFT under
            # a long chunked prefill).
            **(
                {"prefill": prefill_probe}
                if prefill_probe is not None else {}
            ),
            **(
                {
                    "sync_decode_dispatch_ms_median": round(
                        statistics.median(sync_r["dispatch_times"])
                        if sync_r["dispatch_times"] else 0.0, 2,
                    ),
                    "sync_decode_wall_s": round(
                        sync_r["decode_wall_s"], 2
                    ),
                }
                if sync_r is not None else {}
            ),
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
