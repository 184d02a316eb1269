"""``parallax-tpu join`` entry: run a worker node until interrupted.

Capability parity: reference ``parallax join`` -> ``launch.py:89-331``
(minus rank subprocesses — TP is the engine's mesh).
"""

from __future__ import annotations

import signal
import threading

from parallax_tpu.p2p.transport import TcpTransport
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)


def _default_route_ip() -> str:
    """Best-effort externally reachable IP (the UDP-connect trick)."""
    import socket

    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        s.connect(("10.255.255.255", 1))
        ip = s.getsockname()[0]
        s.close()
        return ip
    except Exception:
        return "127.0.0.1"


def join_main(args) -> int:
    import os

    import jax

    # Compile-time hygiene: a rejoining (or autoscaled) worker reloads
    # its compiled stage programs from disk instead of paying a
    # recompilation storm before serving its first token.
    from parallax_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache(getattr(args, "compilation_cache_dir", None))

    from parallax_tpu.config import (
        load_config,
        resolve_speculative_tokens,
    )
    from parallax_tpu.models.loader import load_stage_params
    from parallax_tpu.p2p.node import WorkerNode
    from parallax_tpu.parallel import make_mesh
    from parallax_tpu.runtime.engine import EngineConfig
    from parallax_tpu.utils.hw import (
        default_host_cache_bytes as _default_host_cache_bytes,
    )

    # Scheduler RPC rides one port above its HTTP port by convention.
    scheduler_peer = args.scheduler_addr
    standalone = scheduler_peer is None
    if standalone:
        if getattr(args, "relay", False):
            raise SystemExit("--relay requires a scheduler as the relay")
        if (
            getattr(args, "start_layer", None) is None
            or getattr(args, "end_layer", None) is None
        ):
            raise SystemExit(
                "scheduler-less mode needs --start-layer/--end-layer "
                "(and --peers unless one host serves every layer)"
            )
    transport = TcpTransport(
        "", "0.0.0.0", args.port,
        relay_token=getattr(args, "relay_token", None),
    )
    transport.start()
    if getattr(args, "relay", False):
        # NAT'd worker: no inbound dials — keep a reverse connection at
        # the scheduler's transport and advertise a relay address
        # (reference: libp2p relay + DCUtR, p2p/server.py build_lattica).
        import uuid

        transport.peer_id = (
            f"relay:{uuid.uuid4().hex[:12]}@{scheduler_peer}"
        )
        transport.register_at_relay(scheduler_peer)
    else:
        # The node id doubles as the dial address peers use for
        # pp-forwards: it must be externally reachable, never the
        # 0.0.0.0 bind address.
        advertise_host = (
            getattr(args, "advertise_addr", None) or _default_route_ip()
        )
        transport.peer_id = f"{advertise_host}:{transport.port}"

    model_config = None
    load_params = None
    if args.model_path:
        model_config = load_config(args.model_path)
        load_params = lambda model: load_stage_params(model, args.model_path)
    else:
        raise SystemExit("--model-path is required (checkpoint directory)")

    def resolve_model(name: str):
        """Live model switch (/scheduler/init): a directory this worker can
        read loads real weights; a known preset serves random weights
        (synthetic/benchmark swarms); anything else refuses the switch."""
        import os

        if os.path.isdir(name):
            return load_config(name), (
                lambda model: load_stage_params(model, name)
            )
        from parallax_tpu.models.presets import get_preset

        try:
            return get_preset(name), None
        except KeyError:
            raise RuntimeError(
                f"model {name!r} is neither a local checkpoint nor a "
                "known preset on this worker"
            )

    n_devices = len(jax.local_devices())
    # --sp-size N: the mesh becomes ("sp"=N, "tp"=n/N) — every chip sits
    # on both axes. Long prompts ring-prefill over sp (inside the TP
    # shard_map when tp > 1, over a dedicated sp mesh when tp == 1).
    # Eligibility is pre-checked on the INITIAL model; a later
    # /scheduler/init switch to an ineligible model falls back to the
    # engine's own refusal (warning + replicated sp chips).
    sp_size = max(1, getattr(args, "sp_size", 0) or 0)
    if sp_size > 1:
        from parallax_tpu.parallel.sp import sp_eligible

        if n_devices % sp_size:
            raise SystemExit(
                f"--sp-size {sp_size} does not divide {n_devices} "
                "local chips"
            )
        if model_config is not None and not sp_eligible(model_config):
            logger.warning(
                "--sp-size %d ignored: %s does not support ring-attention "
                "prefill (MLA/sparse/hybrid/window/sink attention)",
                sp_size, model_config.architecture,
            )
            sp_size = 1
    tp_size = n_devices // sp_size
    mesh = None
    sp_mesh = None
    if tp_size > 1:
        mesh = make_mesh(tp_size=tp_size, sp_size=sp_size)
    elif sp_size > 1:
        # SP-only worker (sp spans every chip): the ring opens its own
        # shard_map over a dedicated sp mesh.
        sp_mesh = make_mesh(sp_size=sp_size, tp_size=1)

    from parallax_tpu.ops.lora import parse_adapter_spec

    node = WorkerNode(
        transport=transport,
        scheduler_peer=scheduler_peer,
        model_config=model_config,
        engine_config=EngineConfig(
            # None/0 = adaptive multi-step decode (engine default); the
            # worker's drive loop (node.py) resolves the K-step window
            # tickets like any other overlapped step.
            decode_lookahead=getattr(args, "decode_lookahead", None) or None,
            decode_fused=getattr(args, "decode_fused", None),
            # Fused ragged-prefill kernel + prefix-aware chunk skipping
            # (docs/kernels.md); the seq-parallel knob stays flag-driven
            # through --sp-size on workers (the mesh is carved above).
            prefill_fused=getattr(args, "prefill_fused", None),
            prefill_chunk_skip=getattr(args, "prefill_chunk_skip", True),
            decode_pipeline=getattr(args, "decode_pipeline", 1) or 1,
            # On-device speculative decoding inside the K-step window
            # (prompt-lookup proposals; docs/decode_loop.md). A decode-
            # pool worker is where this pays: TPOT is the whole game
            # there and the window keeps speculation off the host.
            speculative_tokens=resolve_speculative_tokens(
                getattr(args, "speculative_tokens", 0)
            ),
            speculative_ngram=getattr(args, "speculative_ngram", 3) or 3,
            sp_threshold=(
                getattr(args, "sp_threshold", 2048)
                if sp_size > 1 else None
            ),
            # Host-DRAM KV tier, sized from worker RAM on accelerators
            # (off on CPU); see docs/memory.md.
            host_cache_bytes=_default_host_cache_bytes(
                override=getattr(args, "host_cache_bytes", None)
            ),
            # Inter-stage activation wire format; per-link negotiation
            # and alias resolution happen in the worker's sender
            # pipeline (docs/networking.md).
            wire_dtype=getattr(args, "wire_dtype", None),
            # Observability: trace sampling + slow-request threshold
            # (docs/observability.md).
            trace_sample_rate=getattr(args, "trace_sample_rate", 0.0) or 0.0,
            slow_request_ms=getattr(args, "slow_request_ms", 30_000.0),
            # Multi-tenant QoS on this worker's local scheduler
            # (docs/qos.md): deadline EDF + shed/park enforcement;
            # the cluster controller's shed verdict arrives via
            # heartbeat replies and ORs with the local one.
            qos=getattr(args, "qos", None),
            lora_max_adapters=getattr(args, "lora_max_adapters", 0) or 0,
        ),
        load_params=load_params,
        mesh=mesh,
        sp_mesh=sp_mesh,
        tp_size=tp_size,
        refit_cache_dir=getattr(args, "refit_cache_dir", None),
        resolve_model=resolve_model,
        tokenizer_path=args.model_path,
        lora_adapters=parse_adapter_spec(
            getattr(args, "lora_adapters", None)
        ),
        static_peers=[
            p.strip() for p in (getattr(args, "peers", None) or "").split(",")
            if p.strip()
        ],
        layers=(
            (args.start_layer, args.end_layer) if standalone else None
        ),
        # Stall watchdog (docs/observability.md): off by default — no
        # monitor thread, no per-step work.
        watchdog=bool(getattr(args, "watchdog", False)),
        watchdog_degraded_s=getattr(args, "watchdog_degraded_s", 5.0),
        watchdog_stalled_s=getattr(args, "watchdog_stalled_s", 15.0),
        # Disaggregated serving (docs/disaggregation.md): phase role +
        # the KV-transfer lane's frame-chunking target.
        role=getattr(args, "role", None),
        kv_transfer_chunk_bytes=getattr(
            args, "kv_transfer_chunk_bytes", None
        ),
        # Scheduler HA (docs/ha.md): seed standby addresses for the
        # failover rotation; the primary's replies extend the list.
        scheduler_standby=[
            p.strip()
            for p in (
                getattr(args, "scheduler_standby", None) or ""
            ).split(",")
            if p.strip()
        ],
    )
    node.start()
    logger.info("worker %s joined %s", node.node_id, scheduler_peer)

    stop = threading.Event()
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    stop.wait()
    node.stop()
    return 0
