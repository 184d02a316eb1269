"""Offline one-shot generation — no server, no scheduler.

Capability parity: reference ``scripts/generate.py`` (simple offline
inference: load a model, apply the chat template, stream tokens to
stdout, report TTFT and decode throughput). The BASELINE progression's
first config is exactly this path.
"""

from __future__ import annotations

import sys
import time

from parallax_tpu.utils import get_logger

logger = get_logger(__name__)


def generate_main(args) -> int:
    from parallax_tpu.utils.compile_cache import enable_compilation_cache

    enable_compilation_cache(getattr(args, "compilation_cache_dir", None))

    import jax

    from parallax_tpu.backend.http_server import IncrementalDecoder
    from parallax_tpu.config import load_config
    from parallax_tpu.models.loader import load_stage_params
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.engine import (
        EngineConfig,
        StageEngine,
        drive_step,
    )
    from parallax_tpu.runtime.request import Request, SamplingParams
    from parallax_tpu.utils.tokenizer import load_tokenizer

    config = load_config(args.model_path)
    tokenizer = load_tokenizer(args.model_path)

    # Same semantics as serve: 0 = all local chips, 1 = unsharded.
    tp_size = getattr(args, "tp_size", 0)
    if not tp_size:
        tp_size = len(jax.local_devices())
    mesh = None
    if tp_size > 1:
        from parallax_tpu.parallel import make_mesh

        mesh = make_mesh(tp_size=tp_size)
    model = create_stage_model(
        config, 0, config.num_hidden_layers, tp_size=tp_size
    )
    params = load_stage_params(
        model, args.model_path,
        quantize=getattr(args, "quantization", None),
        lora_path=getattr(args, "lora_path", None),
    )

    messages = [{"role": "user", "content": args.prompt}]
    try:
        prompt_ids = tokenizer.encode(
            tokenizer.apply_chat_template(messages)
        )
    except Exception:
        prompt_ids = tokenizer.encode(args.prompt)

    max_model_len = len(prompt_ids) + args.max_tokens + 64
    page_size = 64
    engine = StageEngine(
        model, params,
        EngineConfig(
            page_size=page_size,
            num_pages=(max_model_len + page_size - 1) // page_size + 2,
            max_batch_size=1,
            max_model_len=max_model_len,
            max_num_tokens_per_batch=max(2048, len(prompt_ids)),
            kv_dtype=getattr(args, "kv_dtype", "bfloat16"),
            # None/0 = adaptive multi-step decode (engine default).
            decode_lookahead=getattr(args, "decode_lookahead", None) or None,
            decode_fused=getattr(args, "decode_fused", None),
            prefill_fused=getattr(args, "prefill_fused", None),
        ),
        mesh=mesh,
    )
    req = Request(
        "generate",
        prompt_ids=[int(t) for t in prompt_ids],
        sampling_params=SamplingParams(
            temperature=args.temperature,
            top_k=getattr(args, "top_k", -1) or -1,
            top_p=getattr(args, "top_p", 1.0),
            max_new_tokens=args.max_tokens,
        ),
        eos_token_ids=tuple(tokenizer.eos_token_ids),
    )
    # Single-stage engine: tokens commit locally inside step(); no
    # pipeline ring needed.
    engine.submit(req)

    decoder = IncrementalDecoder(tokenizer)
    t0 = time.perf_counter()
    ttft = None
    sent = 0
    # Overlapped two-phase loop, one step in flight: the host assembles
    # step N+1 while the device computes step N (EngineConfig
    # .overlap_steps); detokenization runs one step behind off the
    # committed ids.
    pending = None
    while engine.has_work() or pending is not None:
        _, pending = drive_step(engine, pending)
        if req.output_ids and ttft is None:
            ttft = time.perf_counter() - t0
        stable = decoder.update(req.output_ids)   # cumulative stable text
        if len(stable) > sent:
            sys.stdout.write(stable[sent:])
            sys.stdout.flush()
            sent = len(stable)
    final = decoder.finalize(req.output_ids)
    sys.stdout.write(final[sent:])
    sys.stdout.write("\n")
    total = time.perf_counter() - t0

    n_out = len(req.output_ids)
    decode_s = max(total - (ttft or 0.0), 1e-9)
    logger.info(
        "%d prompt + %d generated tokens | ttft %.2fs | decode %.1f tok/s "
        "| %s",
        len(prompt_ids), n_out, ttft or 0.0,
        (n_out - 1) / decode_s if n_out > 1 else 0.0,
        req.status.value,
    )
    return 0
