"""parallax-tpu command line interface.

Capability parity target: reference ``src/parallax/cli.py:26-473``
(``parallax run/join/serve/chat``). Subcommands grow with the framework:

- ``serve``  — single-host OpenAI-compatible server (model + layer range)
- ``run``    — launch the global scheduler + HTTP frontend
- ``join``   — join a swarm as a worker node
"""

from __future__ import annotations

import argparse
import os
import sys
import threading


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="parallax-tpu",
        description="TPU-native decentralized LLM serving",
    )
    sub = p.add_subparsers(dest="command")

    serve = sub.add_parser("serve", help="serve a model on this host")
    serve.add_argument("--model-path", required=True)
    serve.add_argument("--start-layer", type=int, default=None)
    serve.add_argument("--end-layer", type=int, default=None)
    serve.add_argument("--port", type=int, default=8000)
    serve.add_argument("--host", default="0.0.0.0")
    serve.add_argument("--page-size", type=int, default=64)
    serve.add_argument("--max-batch-size", type=int, default=64)
    serve.add_argument("--max-model-len", type=int, default=8192)
    serve.add_argument("--kv-utilization", type=float, default=0.9)
    serve.add_argument("--max-num-tokens-per-batch", type=int, default=2048)
    serve.add_argument("--prefill-chunk-size", type=int, default=1024)
    serve.add_argument("--kv-dtype", choices=["bfloat16", "float32"],
                       default="bfloat16")
    serve.add_argument("--no-prefix-cache", action="store_true")
    serve.add_argument(
        "--host-cache-bytes", type=int, default=None,
        help="host-DRAM KV tier budget: radix eviction demotes pages "
             "here and decode OOM preempts requests here instead of "
             "aborting (default: half of available DRAM on TPU, off on "
             "CPU; 0 disables)",
    )
    serve.add_argument(
        "--linear-prefix-slots", type=int, default=32,
        help="hybrid models: device slots for linear-state prefix "
             "snapshots (~2x expected concurrent requests; 0 disables "
             "hybrid prefix caching)",
    )
    serve.add_argument("--quantization", choices=["int8", "int4"],
                       default=None,
                       help="weight-only quantize an fp checkpoint on load")
    serve.add_argument("--lora-path", default=None,
                       help="PEFT LoRA adapter directory to merge at load")
    serve.add_argument("--lora-adapters", default=None,
                       help="per-request adapters: name=peft_dir[,name=dir] "
                            "— requests select one via the 'lora' body "
                            "field (unmerged; batch-grouped at serving)")
    serve.add_argument("--decode-lookahead", type=int, default=None,
                       help="decode tokens per host visit (single-stage "
                            "serving; fused forward+sample window with "
                            "on-device stop-check). Default: adaptive — "
                            "up to 8 whenever the batch qualifies, "
                            "single-step while any sync-forcing feature "
                            "is active; 1 = off")
    serve.add_argument("--decode-pipeline", type=int, default=1,
                       help="chained k-token decode windows per host "
                            "round (hides dispatch latency; 1 = off)")
    serve.add_argument("--decode-fused", action=argparse.BooleanOptionalAction,
                       default=None,
                       help="fused Pallas decode kernels: KV append + "
                            "attention in one program per layer + "
                            "sort-free greedy/top-k sampling "
                            "(docs/kernels.md). Default: auto — on on "
                            "TPU, XLA reference path elsewhere; "
                            "--decode-fused off-TPU runs interpret mode "
                            "(parity testing only)")
    serve.add_argument("--prefill-fused",
                       action=argparse.BooleanOptionalAction,
                       default=None,
                       help="fused ragged chunked-prefill Pallas kernel: "
                            "KV append + flash attention over the paged "
                            "context in one program per layer "
                            "(docs/kernels.md). Default: auto — on on "
                            "TPU, split/XLA path elsewhere; "
                            "--prefill-fused off-TPU runs interpret mode "
                            "(parity testing only)")
    serve.add_argument("--prefill-chunk-skip",
                       action=argparse.BooleanOptionalAction,
                       default=True,
                       help="prefix-aware chunk skipping: re-consult the "
                            "radix tree at chunk-planning time so a warm "
                            "prefix that landed after admission skips its "
                            "covered chunks (docs/kernels.md). "
                            "--no-prefill-chunk-skip turns admission "
                            "reuse off too (A-B digest comparison)")
    serve.add_argument("--prefill-seq-parallel", action="store_true",
                       help="shard one long prompt's prefill across this "
                            "stage's chips over the mesh seq axis "
                            "(one-knob alternative to --sp-size: claims "
                            "all local devices when tp is off; "
                            "docs/kernels.md)")
    serve.add_argument("--speculative-tokens", type=int, default=0,
                       help="speculative decoding: verify up to N "
                            "proposed continuation tokens per decode "
                            "step (0 = off). With decode-lookahead > 1 "
                            "the draft-verify loop runs on device inside "
                            "the K-step window; K=1 falls back to one "
                            "host-synchronous verify round per visit")
    serve.add_argument("--speculative-ngram", type=int, default=3,
                       help="prompt-lookup proposal n-gram length: match "
                            "the trailing N tokens against earlier "
                            "context and propose what followed (used "
                            "when no draft model is configured)")
    serve.add_argument("--draft-model-path", default=None,
                       help="small draft checkpoint for speculative "
                            "decoding (proposals verified by the main "
                            "model; implies --speculative-tokens 4)")
    serve.add_argument("--sp-size", type=int, default=0,
                       help="ring-attention sequence parallelism over this "
                            "many devices for long-prompt prefill")
    serve.add_argument("--sp-threshold", type=int, default=2048,
                       help="prompts at least this long prefill via SP")
    serve.add_argument("--tp-size", type=int, default=0,
                       help="0 = all local chips")
    serve.add_argument(
        "--wire-dtype", default=None,
        choices=["bfloat16", "bf16", "fp8", "float8_e4m3fn"],
        help="inter-stage activation wire format (default: the model's "
             "native precision — bit-identical streams); fp8 compresses "
             "hidden frames with per-token scales, negotiated per link",
    )
    serve.add_argument(
        "--trace-sample-rate", type=float, default=0.0,
        help="fraction of requests sampled for lifecycle tracing "
             "(GET /debug/trace/<rid>, Chrome trace JSON); 0 disables "
             "with zero per-step overhead",
    )
    serve.add_argument(
        "--slow-request-ms", type=float, default=30000.0,
        help="flight-recorder slow threshold: requests slower end-to-end "
             "than this are captured with their span breakdown "
             "(GET /debug/flight); <= 0 disables slow capture",
    )
    serve.add_argument(
        "--compilation-cache-dir", default=None,
        help="persistent XLA compilation cache directory; 'off' "
             "disables. $JAX_COMPILATION_CACHE_DIR, where set, wins over "
             "this flag; with neither the cache is <checkout>/.jax_cache "
             "— restarts reload compiled programs instead of paying a "
             "recompilation storm",
    )
    serve.add_argument(
        "--watchdog", action="store_true",
        help="run the stall watchdog over the serving loop and the "
             "admission queues: pending work whose progress counter "
             "stops moving walks ok -> degraded -> stalled and flips "
             "the deep GET /healthz (default: off, zero overhead)",
    )
    serve.add_argument(
        "--slo", default=None,
        help="declarative SLO objectives, e.g. "
             "'ttft_p95_ms=500,tpot_p95_ms=50,availability=0.999' — "
             "windowed attainment and multi-window burn rates appear "
             "in /status and as parallax_slo_* gauges",
    )
    serve.add_argument(
        "--slo-window-s", type=float, default=300.0,
        help="short SLO window seconds (the long window is 12x)",
    )
    serve.add_argument(
        "--qos", default=None,
        help="multi-tenant QoS (docs/qos.md): 'on' or a key=value spec "
             "(e.g. 'interactive_ms=500,batch_ms=60000,shed_burn=2') "
             "enables request classes, deadline-aware EDF scheduling "
             "and shed/park admission control; default off is provably "
             "inert (zero per-step cost, bit-identical streams)",
    )
    serve.add_argument(
        "--lora-max-adapters", type=int, default=0,
        help="LoRA hot-load LRU cap: registering past it evicts the "
             "least-recently-batched adapter (never one in flight); "
             "0 = unbounded",
    )

    run = sub.add_parser("run", help="launch the scheduler + web frontend")
    run.add_argument("--model-name", required=True)
    run.add_argument("--min-nodes", type=int, default=1)
    run.add_argument("--port", type=int, default=3001)
    run.add_argument(
        "--routing", default="rr",
        choices=["rr", "dp", "random", "cache_aware"],
        help="request routing strategy: rr round-robins registered "
             "pipelines; dp shortest-latency over announced layer "
             "ranges; random latency-weighted; cache_aware scores "
             "pipelines by predicted prefix-cache hit (workers publish "
             "radix-tree digests through heartbeats) plus load "
             "(see docs/scheduling.md)",
    )
    run.add_argument(
        "--routing-alpha", type=float, default=1.0,
        help="cache_aware: cost per predicted UNCACHED prompt token",
    )
    run.add_argument(
        "--routing-beta", type=float, default=256.0,
        help="cache_aware: cost per in-flight request on the head "
             "(default prices one queued request like 256 uncached "
             "tokens)",
    )
    run.add_argument(
        "--routing-imbalance", type=int, default=8,
        help="cache_aware: when the in-flight spread across eligible "
             "pipelines exceeds this, fall back to least-loaded so a "
             "hot prefix cannot starve a replica",
    )
    run.add_argument(
        "--routing-gamma", type=float, default=0.0,
        help="cache_aware: per-tenant fairness — cost per unit of the "
             "tenant's own recent-dispatch share on a pipeline "
             "(docs/qos.md); 0 disables the term",
    )
    run.add_argument(
        "--relay-token", default=None,
        help="shared secret NAT'd workers must present to register a "
             "relay route (default: registration is identity-bound only)",
    )
    run.add_argument(
        "--slo", default=None,
        help="declarative cluster SLO objectives, e.g. "
             "'ttft_p95_ms=500,tpot_p95_ms=50,availability=0.999' — "
             "evaluated over the cluster-merged histograms; attainment "
             "and burn rates appear in /cluster/status 'slo' and as "
             "parallax_slo_* gauges (the admission-control hook point "
             "for SLO-aware scheduling)",
    )
    run.add_argument(
        "--slo-window-s", type=float, default=300.0,
        help="short SLO window seconds (the long window is 12x)",
    )
    run.add_argument(
        "--qos", default=None,
        help="multi-tenant QoS control plane (docs/qos.md): 'on' or a "
             "key=value spec. Adds request classes + deadlines at the "
             "HTTP frontend, a cluster admission controller relaying "
             "shed verdicts through heartbeats, and (with "
             "'autoscale=1') the goodput-driven pool autoscaler that "
             "re-roles pipelines between the prefill/decode pools",
    )
    run.add_argument(
        "--scheduler-standby", default=None,
        help="scheduler HA (docs/ha.md): comma-separated warm-standby "
             "scheduler RPC addresses. The primary streams its state "
             "journal to them and advertises the list to every worker "
             "and client, so scheduler RPCs fail over to a promoted "
             "standby; omit to run without HA (a scheduler crash "
             "stalls routing until restart)",
    )
    run.add_argument(
        "--standby-of", default=None,
        help="scheduler HA (docs/ha.md): run THIS process as a warm "
             "standby mirroring the given primary scheduler RPC "
             "address; it serves read-only lookups, tails the "
             "snapshot+journal stream, and promotes itself (bumping "
             "the scheduler epoch) when the primary's lease expires",
    )
    run.add_argument(
        "--ha-lease-s", type=float, default=6.0,
        help="scheduler HA: seconds without journal progress from the "
             "primary before a standby promotes itself (docs/ha.md)",
    )

    join = sub.add_parser("join", help="join a swarm as a worker")
    join.add_argument("--scheduler-addr", default=None,
                      help="scheduler RPC address; omit for scheduler-less "
                           "mode (requires --peers + --start-layer/"
                           "--end-layer)")
    join.add_argument("--peers", default=None,
                      help="scheduler-less mode: comma-separated worker "
                           "addresses to gossip block announcements with")
    join.add_argument(
        "--scheduler-standby", default=None,
        help="scheduler HA (docs/ha.md): comma-separated warm-standby "
             "scheduler addresses to fail over to when the primary "
             "dies (the primary also advertises its list through "
             "allocations/heartbeat replies, so this seed is optional "
             "when workers join before any failover)",
    )
    join.add_argument("--start-layer", type=int, default=None,
                      help="scheduler-less mode: this worker's first layer. "
                           "Blocks chain only at EXACT boundaries (a stage "
                           "is jit-compiled for its whole slice, so a "
                           "route cannot enter a block mid-way): every "
                           "worker's end layer must equal the next "
                           "worker's start layer")
    join.add_argument("--end-layer", type=int, default=None,
                      help="scheduler-less mode: one past the last layer "
                           "(must match the next block's --start-layer; "
                           "see --start-layer)")
    join.add_argument("--model-path", default=None)
    join.add_argument("--port", type=int, default=0)
    join.add_argument("--refit-cache-dir", default=None,
                      help="persist fetched refit weight versions here "
                           "(newest 3 kept; reloaded on restart)")
    join.add_argument(
        "--advertise-addr", default=None,
        help="externally reachable host/IP peers dial for pp-forwards",
    )
    join.add_argument(
        "--relay", action="store_true",
        help="NAT'd worker: no inbound dials — keep a reverse connection "
             "at the scheduler and receive pp-forwards relayed through it",
    )
    join.add_argument(
        "--relay-token", default=None,
        help="shared secret presented when registering the relay route "
             "(must match the scheduler's --relay-token)",
    )
    join.add_argument(
        "--role", default=None, choices=["prefill", "decode", "mixed"],
        help="phase specialization for disaggregated serving "
             "(docs/disaggregation.md): 'prefill' computes prompts and "
             "hands finished requests to the decode pool over the "
             "KV-transfer lane; 'decode' runs deep continuous batches "
             "prompts never interrupt; default 'mixed' serves both "
             "phases (no handoffs). Pipelines stay role-homogeneous "
             "and /cluster/status breaks out per-pool saturation",
    )
    join.add_argument(
        "--kv-transfer-chunk-bytes", type=int, default=None,
        help="target payload bytes per layer-chunked KV_TRANSFER frame "
             "on the handoff lane (default 4 MiB): smaller frames "
             "overlap the transfer more, larger frames amortize framing",
    )
    join.add_argument(
        "--lora-adapters", default=None,
        help="per-request adapters this worker serves: "
             "name=peft_dir[,name=dir]",
    )
    join.add_argument(
        "--sp-size", type=int, default=0,
        help="ring-attention sp mesh axis for long-prompt prefill: the "
             "host's chips form an (sp, tp) mesh with tp = chips / "
             "sp-size (must divide evenly)",
    )
    join.add_argument(
        "--host-cache-bytes", type=int, default=None,
        help="host-DRAM KV tier budget for this worker (default: half "
             "of available DRAM on TPU, off on CPU; 0 disables)",
    )
    join.add_argument("--sp-threshold", type=int, default=2048,
                      help="prompts at least this long prefill via SP")
    join.add_argument(
        "--wire-dtype", default=None,
        choices=["bfloat16", "bf16", "fp8", "float8_e4m3fn"],
        help="inter-stage activation wire format for this worker's "
             "outbound links (default: native precision — bit-identical "
             "streams); negotiated per link via wire_caps",
    )
    join.add_argument(
        "--trace-sample-rate", type=float, default=0.0,
        help="head-stage lifecycle-trace sampling rate; the sampled flag "
             "rides FORWARD frames so downstream stages join the trace",
    )
    join.add_argument(
        "--slow-request-ms", type=float, default=30000.0,
        help="flight-recorder slow threshold for this worker's head "
             "stage (<= 0 disables slow capture)",
    )
    join.add_argument(
        "--decode-lookahead", type=int, default=None,
        help="decode tokens per host visit when this worker serves a "
             "full single stage (default: adaptive up to 8; 1 = off)",
    )
    join.add_argument(
        "--speculative-tokens", type=int, default=0,
        help="speculative decoding on this worker's single-stage decode "
             "windows: verify up to N prompt-lookup proposal tokens per "
             "step inside the K-step window (0 = off; the decode pool's "
             "TPOT lever — docs/decode_loop.md)",
    )
    join.add_argument(
        "--speculative-ngram", type=int, default=3,
        help="prompt-lookup proposal n-gram length for this worker",
    )
    join.add_argument(
        "--decode-pipeline", type=int, default=1,
        help="chained k-token decode windows per host visit (1 = off)",
    )
    join.add_argument(
        "--decode-fused", action=argparse.BooleanOptionalAction,
        default=None,
        help="fused Pallas decode kernels (KV append + attention + "
             "fused sampling; default auto-on-TPU — see docs/kernels.md)",
    )
    join.add_argument(
        "--prefill-fused", action=argparse.BooleanOptionalAction,
        default=None,
        help="fused ragged chunked-prefill Pallas kernel (KV append + "
             "flash attention over the paged context in one program; "
             "default auto-on-TPU — see docs/kernels.md)",
    )
    join.add_argument(
        "--prefill-chunk-skip", action=argparse.BooleanOptionalAction,
        default=True,
        help="prefix-aware chunk skipping at chunk-planning time "
             "(docs/kernels.md); --no-prefill-chunk-skip turns "
             "admission reuse off too",
    )
    join.add_argument(
        "--compilation-cache-dir", default=None,
        help="persistent XLA compilation cache directory; 'off' "
             "disables. $JAX_COMPILATION_CACHE_DIR, where set, wins over "
             "this flag; with neither the cache is <checkout>/.jax_cache",
    )
    join.add_argument(
        "--watchdog", action="store_true",
        help="run the stall watchdog over this worker's step loop, "
             "sender queues, migration parks and admission queue; "
             "health states ride heartbeats into /cluster/status "
             "(default: off, zero overhead)",
    )
    join.add_argument(
        "--watchdog-degraded-s", type=float, default=5.0,
        help="seconds without progress (with pending work) before a "
             "component reports degraded",
    )
    join.add_argument(
        "--watchdog-stalled-s", type=float, default=15.0,
        help="seconds without progress before a component reports "
             "stalled (flips deep /healthz to 503)",
    )
    join.add_argument(
        "--qos", default=None,
        help="multi-tenant QoS on this worker's local scheduler "
             "(docs/qos.md): 'on' or a key=value spec — deadline EDF "
             "scheduling + shed/park enforcement; the scheduler's "
             "cluster shed verdict (relayed in heartbeat replies) ORs "
             "with the local controller. Default off = inert",
    )
    join.add_argument(
        "--lora-max-adapters", type=int, default=0,
        help="LoRA hot-load LRU cap (0 = unbounded)",
    )

    gen = sub.add_parser(
        "generate",
        help="offline one-shot generation, no server (reference "
             "scripts/generate.py)",
    )
    gen.add_argument("--model-path", required=True)
    gen.add_argument("--prompt", default="Hi")
    gen.add_argument("--max-tokens", type=int, default=256)
    gen.add_argument("--temperature", type=float, default=0.0)
    gen.add_argument("--top-k", type=int, default=-1)
    gen.add_argument("--top-p", type=float, default=1.0)
    gen.add_argument("--tp-size", type=int, default=0)
    gen.add_argument("--kv-dtype", choices=["bfloat16", "float32"],
                     default="bfloat16")
    gen.add_argument("--decode-lookahead", type=int, default=None,
                     help="decode tokens per host visit (default: "
                          "adaptive up to 8; 1 = off)")
    gen.add_argument("--decode-fused", action=argparse.BooleanOptionalAction,
                     default=None,
                     help="fused Pallas decode kernels (default "
                          "auto-on-TPU — see docs/kernels.md)")
    gen.add_argument("--prefill-fused",
                     action=argparse.BooleanOptionalAction, default=None,
                     help="fused ragged chunked-prefill Pallas kernel "
                          "(default auto-on-TPU — see docs/kernels.md)")
    gen.add_argument(
        "--compilation-cache-dir", default=None,
        help="persistent XLA compilation cache directory; 'off' "
             "disables. $JAX_COMPILATION_CACHE_DIR, where set, wins over "
             "this flag; with neither the cache is <checkout>/.jax_cache",
    )
    gen.add_argument("--quantization", choices=["int8", "int4"],
                     default=None)
    gen.add_argument("--lora-path", default=None)

    chat = sub.add_parser("chat", help="interactive chat against a server")
    chat.add_argument("--base-url", default="http://127.0.0.1:8000")
    chat.add_argument("--max-tokens", type=int, default=512)
    chat.add_argument("--temperature", type=float, default=0.7)

    chost = sub.add_parser(
        "chat-host",
        help="standalone chat UI + OpenAI API host on a non-scheduler "
             "machine, proxying to a swarm head worker over RPC",
    )
    chost.add_argument("--head", required=True,
                       help="head worker transport address (host:port)")
    chost.add_argument("--port", type=int, default=8000)
    chost.add_argument("--model-path", default=None,
                       help="checkpoint dir for the tokenizer")
    chost.add_argument("--model-name", default=None)

    merge = sub.add_parser(
        "lora-merge",
        help="fuse a PEFT LoRA adapter into a checkpoint "
             "(reference prepare_adapter)",
    )
    merge.add_argument("--model-path", required=True)
    merge.add_argument("--adapter-path", required=True)
    merge.add_argument("--out-dir", required=True)
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.command is None:
        build_parser().print_help()
        return 1
    if args.command == "serve":
        from parallax_tpu.backend.serve import serve_main
        from parallax_tpu.utils.banner import print_banner
        from parallax_tpu.utils.version_check import check_latest_release

        print_banner()
        # Purely informational network probe: never let it delay boot
        # (air-gapped deployments), and allow opting out entirely.
        if not os.environ.get("PARALLAX_TPU_NO_VERSION_CHECK"):
            def _version_hint():
                hint = check_latest_release()
                if hint:
                    print(hint)

            threading.Thread(target=_version_hint, daemon=True).start()
        return serve_main(args)
    if args.command == "run":
        from parallax_tpu.backend.run import run_main
        from parallax_tpu.utils.banner import print_banner

        print_banner()
        return run_main(args)
    if args.command == "lora-merge":
        from parallax_tpu.utils.adapter import merge_adapter

        n = merge_adapter(args.model_path, args.adapter_path, args.out_dir)
        print(f"merged {n} adapter modules -> {args.out_dir}")
        return 0
    if args.command == "join":
        from parallax_tpu.p2p.join import join_main

        return join_main(args)
    if args.command == "chat":
        return chat_main(args)
    if args.command == "chat-host":
        from parallax_tpu.backend.run import chat_host_main

        return chat_host_main(args)
    if args.command == "generate":
        from parallax_tpu.backend.generate import generate_main

        return generate_main(args)
    return 1


def chat_main(args) -> int:
    """Interactive streaming chat REPL (reference ``parallax chat``)."""
    import json
    import urllib.request

    history: list[dict] = []
    print(f"chatting with {args.base_url} — /quit to exit, /clear to reset")
    while True:
        try:
            user = input("you> ").strip()
        except (EOFError, KeyboardInterrupt):
            print()
            return 0
        if not user:
            continue
        if user == "/quit":
            return 0
        if user == "/clear":
            history.clear()
            continue
        history.append({"role": "user", "content": user})
        payload = json.dumps({
            "model": "parallax-tpu",
            "messages": history,
            "max_tokens": args.max_tokens,
            "temperature": args.temperature,
            "stream": True,
        }).encode()
        req = urllib.request.Request(
            f"{args.base_url}/v1/chat/completions", data=payload,
            headers={"Content-Type": "application/json"},
        )
        reply = []
        try:
            import time as _time

            from parallax_tpu.utils.request_metrics import request_metrics

            t0 = _time.monotonic()
            t_first = t_last = None
            final_chunk = None
            with urllib.request.urlopen(req, timeout=600) as resp:
                for raw in resp:
                    line = raw.decode().strip()
                    if not line.startswith("data: ") or line == "data: [DONE]":
                        continue
                    chunk = json.loads(line[6:])
                    if chunk.get("usage"):
                        final_chunk = chunk
                    delta = chunk["choices"][0].get("delta", {}).get("content")
                    if delta:
                        t_last = _time.monotonic()
                        if t_first is None:
                            t_first = t_last
                        reply.append(delta)
                        print(delta, end="", flush=True)
            print()
            tps, ttft_ms, _, out_toks = request_metrics(
                final_chunk, t0, t_first, t_last
            )
            if out_toks is not None:
                rate = f" · {tps:.1f} tok/s" if tps is not None else ""
                print(f"[{out_toks} tokens{rate} · ttft {ttft_ms} ms]")
        except KeyboardInterrupt:
            # Cancel the turn, keep the REPL alive.
            print("\n[interrupted]")
            history.pop()
            continue
        except Exception as e:
            print(f"\n[error: {e}]")
            history.pop()
            continue
        history.append({"role": "assistant", "content": "".join(reply)})


if __name__ == "__main__":
    sys.exit(main())
