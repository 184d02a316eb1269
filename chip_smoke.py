#!/usr/bin/env python3
"""Chip smoke: the serving path, once, on the chip, at Qwen2.5-7B's widths.

    python3 chip_smoke.py            # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4  # TP=1 against TP=4, and nothing else
    JAX_PLATFORMS=cpu python3 chip_smoke.py --rehearse   # tiny, on the CPU

What it does, one process on the chip at a time (this parent never
imports JAX):

1. Child 1 runs each main-path Pallas kernel once, compiled by Mosaic at
   the real head shapes (28/4 heads and the 7/1 TP=4 shard), against its
   XLA reference — interpret-mode parity says nothing about Mosaic.
2. Writes a checkpoint directory from ``--seed``: ``config.json`` with
   the published Qwen2.5-7B widths and depth cut to ``LAYERS``, plus
   sharded bf16 safetensors streamed tensor by tensor. No tokenizer
   files: the byte tokenizer is the documented path for such a
   checkpoint.
3. Child 2 is ``python -m parallax_tpu.cli serve --model-path <dir>
   --port <free port>`` and no other flag, so the engine is the one a
   user gets. The parent drives it over HTTP and holds the answers and
   the server's own ``/cluster/status_json`` to what a TPU-auto engine
   must show.

Every failure is a non-zero exit with no result line. On success the
LAST stdout line is ``{"ok": true, "device": {...}}`` with the device
as the serving process reported it; earlier lines carry the phases.

``--rehearse`` is the CPU dress rehearsal of this script's control flow
(tiny widths, fused kernels forced into the Pallas interpreter); its
result line says ``"platform": "cpu"`` and is not a chip run.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# https://huggingface.co/Qwen/Qwen2.5-7B-Instruct/blob/main/config.json
# (``attention_bias`` spelled out: Qwen2 has q/k/v biases by definition).
SOURCE = "Qwen/Qwen2.5-7B-Instruct config.json"
QWEN25_7B = {
    "architectures": ["Qwen2ForCausalLM"],
    "attention_dropout": 0.0,
    "bos_token_id": 151643,
    "eos_token_id": 151645,
    "hidden_act": "silu",
    "hidden_size": 3584,
    "initializer_range": 0.02,
    "intermediate_size": 18944,
    "max_position_embeddings": 32768,
    "max_window_layers": 28,
    "model_type": "qwen2",
    "num_attention_heads": 28,
    "num_hidden_layers": 28,
    "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06,
    "rope_theta": 1000000.0,
    "sliding_window": 131072,
    "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "use_sliding_window": False,
    "vocab_size": 152064,
    "attention_bias": True,
}
# 28 layers are 15.2 GB of bf16 and leave one 16 GB chip no KV pool.
# Depth is cut to what leaves the server's own sizing (0.81 of free HBM)
# at least 2 GB of KV and the remaining fifth of free HBM room for the
# compiled programs (~0.04 GB of code per shape bucket, ~0.11 GB of
# temporaries at the 2048-token prefill — memory_analysis(), v5e).
# A constant, not an option: a result line from a shallower model would
# read the same as this one.
LAYERS = 24
# The dress-rehearsal model: same architecture, toy widths.
TINY = dict(
    QWEN25_7B, hidden_size=128, intermediate_size=256, num_attention_heads=8,
    num_key_value_heads=4, num_hidden_layers=4, vocab_size=512,
    max_position_embeddings=4096, max_window_layers=4,
)

SERVE_DEFAULTS = {"max_model_len": 8192, "prefill_chunk_size": 1024}
REHEARSE_FLAGS = {"max_model_len": 2048, "prefill_chunk_size": 256}


def say(**record) -> None:
    """One JSON line on stdout (never the last one: that is ``main``'s)."""
    print(json.dumps(record), flush=True)


class SmokeFailed(Exception):
    pass


def check(cond: bool, what: str, **ctx) -> None:
    if not cond:
        raise SmokeFailed(f"{what} {json.dumps(ctx, default=str)[:2000]}")


# --------------------------------------------------------------------------
# Checkpoint writer (parent, numpy only).
# --------------------------------------------------------------------------


def _tensor_plan(cfg: dict) -> list[tuple[str, list[tuple]]]:
    """[(file name, [(tensor name, shape, kind, scale), ...]), ...]."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    v = cfg["vocab_size"]
    files = [("model-embed.safetensors", [
        ("model.embed_tokens.weight", (v, h), "normal", 0.02),
    ])]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        files.append((f"model-layer-{i:02d}.safetensors", [
            (p + "input_layernorm.weight", (h,), "ones", 1.0),
            (p + "post_attention_layernorm.weight", (h,), "ones", 1.0),
            (p + "self_attn.q_proj.weight", (q, h), "normal", h ** -0.5),
            (p + "self_attn.q_proj.bias", (q,), "normal", 0.02),
            (p + "self_attn.k_proj.weight", (kv, h), "normal", h ** -0.5),
            (p + "self_attn.k_proj.bias", (kv,), "normal", 0.02),
            (p + "self_attn.v_proj.weight", (kv, h), "normal", h ** -0.5),
            (p + "self_attn.v_proj.bias", (kv,), "normal", 0.02),
            (p + "self_attn.o_proj.weight", (h, q), "normal", q ** -0.5),
            (p + "mlp.gate_proj.weight", (inter, h), "normal", h ** -0.5),
            (p + "mlp.up_proj.weight", (inter, h), "normal", h ** -0.5),
            (p + "mlp.down_proj.weight", (h, inter), "normal",
             inter ** -0.5),
        ]))
    files.append(("model-head.safetensors", [
        ("model.norm.weight", (h,), "ones", 1.0),
        ("lm_head.weight", (v, h), "normal", 0.02),
    ]))
    return files


def _bf16_bits(x: np.ndarray) -> np.ndarray:
    """float32 -> bfloat16 bit patterns (round half up, in place)."""
    u = x.view(np.uint32)
    u += 0x8000
    u >>= 16
    return u.astype(np.uint16)


def _write_file(path: str, tensors: list[tuple], seed: int,
                file_idx: int) -> int:
    header, offset = {}, 0
    for name, shape, _, _ in tensors:
        nbytes = 2 * int(np.prod(shape))
        header[name] = {"dtype": "BF16", "shape": list(shape),
                        "data_offsets": [offset, offset + nbytes]}
        offset += nbytes
    blob = json.dumps(header, separators=(",", ":")).encode()
    blob += b" " * (-len(blob) % 8)
    with open(path, "wb") as f:
        f.write(struct.pack("<Q", len(blob)))
        f.write(blob)
        for t_idx, (_, shape, kind, scale) in enumerate(tensors):
            n = int(np.prod(shape))
            if kind == "ones":
                bits = np.full((n,), 0x3F80, np.uint16)
            else:
                rng = np.random.default_rng([seed, file_idx, t_idx])
                x = rng.standard_normal(n, dtype=np.float32)
                x *= np.float32(scale)
                bits = _bf16_bits(x)
            f.write(bits.data)
    return offset


def write_checkpoint(out_dir: str, cfg: dict, seed: int) -> dict:
    """Write the checkpoint, one worker per core and file."""
    t0 = time.monotonic()
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(cfg, f, indent=1)
    files = _tensor_plan(cfg)
    workers = max(1, min(len(files), (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(workers) as pool:
        sizes = list(pool.map(
            lambda item: _write_file(
                os.path.join(out_dir, item[1][0]), item[1][1], seed, item[0]
            ),
            enumerate(files),
        ))
    weight_map = {
        name: fname for fname, tensors in files for name, *_ in tensors
    }
    with open(os.path.join(out_dir, "model.safetensors.index.json"),
              "w") as f:
        json.dump({"metadata": {"total_size": sum(sizes)},
                   "weight_map": weight_map}, f)
    return {"bytes": sum(sizes), "files": len(files),
            "seconds": round(time.monotonic() - t0, 1)}


# --------------------------------------------------------------------------
# Children.
# --------------------------------------------------------------------------

_children: list[subprocess.Popen] = []


def spawn(cmd: list[str], log_path: str, env: dict | None = None):
    """Start a child in its own process group, output to ``log_path``."""
    log = open(log_path, "wb")
    proc = subprocess.Popen(
        cmd, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
        env=env, start_new_session=True,
    )
    log.close()
    _children.append(proc)
    return proc


def stop(proc: subprocess.Popen, grace_s: float = 20.0) -> int | None:
    """SIGTERM the child's group, SIGKILL what is left; returns its code."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass
    return proc.returncode


def tail(path: str, n: int = 6000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")
    except OSError as e:
        return f"<{e}>"


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # The release-freshness probe is the one thing that wants a network.
    env["PARALLAX_TPU_NO_VERSION_CHECK"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def run_kernel_child(work: str, rehearse: bool) -> dict:
    """Child 1, to completion; returns its result record."""
    out = os.path.join(work, "kernels.json")
    log = os.path.join(work, "kernels.log")
    cmd = [sys.executable, os.path.abspath(__file__), "--child-kernels", out]
    if rehearse:
        cmd.append("--rehearse")
    t0 = time.monotonic()
    proc = spawn(cmd, log, child_env())
    try:
        code = proc.wait(timeout=600)
    except subprocess.TimeoutExpired:
        stop(proc)
        raise SmokeFailed(f"kernel child timed out\n{tail(log)}")
    check(code == 0, f"kernel child exited {code}\n{tail(log)}")
    with open(out) as f:
        rec = json.load(f)
    rec["seconds"] = round(time.monotonic() - t0, 1)
    return rec


class Server:
    """Child 2: ``cli serve`` on a free port, driven over HTTP."""

    def __init__(self, work: str, ckpt: str, name: str,
                 extra_flags: list[str]):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.base = f"http://127.0.0.1:{self.port}"
        self.log = os.path.join(work, f"serve-{name}.log")
        cmd = [sys.executable, "-m", "parallax_tpu.cli", "serve",
               "--model-path", ckpt, "--port", str(self.port), *extra_flags]
        self.cmd = cmd
        self.t_spawn = time.monotonic()
        self.proc = spawn(cmd, self.log, child_env())

    def fail(self, what: str):
        raise SmokeFailed(f"{what}\n--- {self.log} ---\n{tail(self.log)}")

    def get(self, path: str, timeout: float = 30.0):
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())

    def wait_ready(self, deadline_s: float) -> float:
        while time.monotonic() - self.t_spawn < deadline_s:
            if self.proc.poll() is not None:
                self.fail(f"serve exited {self.proc.returncode} before "
                          "/healthz answered")
            try:
                status, body = self.get("/healthz", timeout=5)
                if status == 200 and body.get("status") == "ok":
                    return time.monotonic() - self.t_spawn
            except (urllib.error.URLError, OSError, ValueError):
                pass
            time.sleep(0.5)
        self.fail(f"/healthz not ready after {deadline_s:.0f}s")

    def post(self, path: str, body: dict, timeout: float = 600.0) -> dict:
        """One non-streamed completion; 200 or fail."""
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
        )
        t0 = time.monotonic()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                status, payload = r.status, r.read()
        except urllib.error.HTTPError as e:
            self.fail(f"POST {path} -> {e.code}: {e.read()[:500]!r}")
        except (urllib.error.URLError, OSError) as e:
            self.fail(f"POST {path} failed: {e!r}")
        check(status == 200, f"POST {path} -> {status}")
        out = json.loads(payload)
        out["_seconds"] = time.monotonic() - t0
        return out

    def post_stream(self, path: str, body: dict,
                    timeout: float = 600.0) -> dict:
        """One streamed completion: the final usage chunk + chunk count."""
        req = urllib.request.Request(
            self.base + path,
            data=json.dumps(dict(body, stream=True)).encode(),
            headers={"Content-Type": "application/json"},
        )
        t0 = time.monotonic()
        chunks, usage, finish, done = 0, None, None, False
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                check(r.status == 200, f"stream {path} -> {r.status}")
                for raw in r:
                    line = raw.decode().strip()
                    if line == "data: [DONE]":
                        done = True
                    elif line.startswith("data: "):
                        chunk = json.loads(line[6:])
                        chunks += 1
                        usage = chunk.get("usage") or usage
                        finish = (
                            chunk["choices"][0].get("finish_reason")
                            or finish
                        )
        except urllib.error.HTTPError as e:
            self.fail(f"stream {path} -> {e.code}: {e.read()[:500]!r}")
        except (urllib.error.URLError, OSError) as e:
            self.fail(f"stream {path} failed: {e!r}")
        check(done and usage is not None, "stream ended without usage",
              chunks=chunks)
        return {"usage": usage, "chunks": chunks, "finish_reason": finish,
                "_seconds": time.monotonic() - t0}

    def status(self) -> dict:
        code, body = self.get("/cluster/status_json")
        check(code == 200, f"/cluster/status_json -> {code}")
        return body

    def close(self) -> None:
        """Stop the server (never raises: it runs in ``finally``)."""
        code = stop(self.proc)
        print(f"serve ({self.log}) exited {code}", file=sys.stderr,
              flush=True)


def chat(content: str, **kw) -> dict:
    return dict({"model": "smoke",
                 "messages": [{"role": "user", "content": content}]}, **kw)


def filler(n_chars: int, salt: str) -> str:
    """``n_chars`` of prompt text, distinct per ``salt`` (no accidental
    prefix sharing between the requests of one wave)."""
    unit = f"[{salt}] the quick brown fox jumps over the lazy dog. "
    return (unit * (n_chars // len(unit) + 1))[:n_chars]


GREEDY = dict(temperature=0.0, ignore_eos=True)


def usage_of(resp: dict, want_tokens: int, what: str) -> dict:
    usage = resp["usage"]
    check(usage["completion_tokens"] == want_tokens,
          f"{what}: completion_tokens", usage=usage, want=want_tokens)
    return usage


# --------------------------------------------------------------------------
# One chip: the serving path as a user gets it.
# --------------------------------------------------------------------------


def drive_one_chip(srv: Server, sizes: dict, rehearse: bool) -> dict:
    chunk, max_len = sizes["prefill_chunk_size"], sizes["max_model_len"]
    phases = {}

    # First request, alone and streamed: time to first token is the
    # first prefill (compile included), the rest is the first K=8 window.
    first = srv.post_stream(
        "/v1/chat/completions",
        chat(filler(90, "first"), max_tokens=9, **GREEDY),
    )
    usage_of(first, 9, "first request")
    ttft_s = first["usage"]["ttft_ms"] / 1e3
    phases["first_prefill_s"] = round(ttft_s, 2)
    phases["first_window_s"] = round(first["_seconds"] - ttft_s, 2)

    t_steady = time.monotonic()
    # A wave of concurrent chats of different lengths, one past the
    # prefill chunk size (chunked prefill), one streamed.
    lengths = [40, chunk // 5, chunk // 2, chunk + chunk // 2]
    results: list = [None] * len(lengths)

    def one(i: int, n: int) -> None:
        body = chat(filler(n, f"wave{i}"), max_tokens=12, **GREEDY)
        try:
            results[i] = (
                srv.post_stream("/v1/chat/completions", body) if i == 1
                else srv.post("/v1/chat/completions", body)
            )
        except BaseException as e:   # re-raised on the main thread
            results[i] = e

    threads = [threading.Thread(target=one, args=(i, n))
               for i, n in enumerate(lengths)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
    for i, r in enumerate(results):
        if isinstance(r, BaseException):
            raise r
        check(r is not None, f"wave request {i} never returned")
        u = usage_of(r, 12, f"wave request {i}")
        check(u["prompt_tokens"] > lengths[i], "prompt_tokens", usage=u)
    check(results[-1]["usage"]["prompt_tokens"] > chunk,
          "the long wave prompt must exceed --prefill-chunk-size")

    # Sampled and seeded: temperature + top_k is the fused sampler's row.
    sampled_body = chat(filler(70, "sampled"), max_tokens=12,
                        temperature=0.8, top_k=20, seed=1234,
                        ignore_eos=True)
    sampled = srv.post("/v1/chat/completions", sampled_body)
    usage_of(sampled, 12, "sampled request")
    again = srv.post("/v1/chat/completions", sampled_body)
    check(sampled["choices"][0]["token_ids"]
          == again["choices"][0]["token_ids"],
          "a seeded request must reproduce its tokens",
          a=sampled["choices"][0]["token_ids"],
          b=again["choices"][0]["token_ids"])

    # The same greedy prompt twice: the repeat is served from the prefix
    # cache and must produce the same tokens.
    repeat_body = chat(filler(200, "repeat"), max_tokens=12, **GREEDY)
    cold = srv.post("/v1/chat/completions", repeat_body)
    warm = srv.post("/v1/chat/completions", repeat_body)
    usage_of(cold, 12, "repeat (cold)")
    usage_of(warm, 12, "repeat (warm)")
    cached = warm["usage"]["prompt_tokens_details"]["cached_tokens"]
    check(cached > 0, "the repeated prompt reported no cached tokens",
          usage=warm["usage"])
    check(cold["choices"][0]["token_ids"] == warm["choices"][0]["token_ids"],
          "prefix-cache hit changed the tokens",
          cold=cold["choices"][0]["token_ids"],
          warm=warm["choices"][0]["token_ids"])

    # A prompt that ends 7 tokens short of --max-model-len: no K=8
    # window fits, so its tokens come from single-step decode.
    edge = srv.post("/v1/completions", dict(
        model="smoke", prompt=filler(max_len - 8, "edge"), max_tokens=4,
        **GREEDY,
    ))
    usage_of(edge, 4, "max-model-len edge request")
    phases["steady_requests_s"] = round(time.monotonic() - t_steady, 2)

    status = srv.status()
    hw = status["hardware"]
    stage = status["stages"][0]
    kernel = stage["kernel"]
    dispatch = kernel["dispatch_total"]
    want_impl = "pallas-fused"
    check(rehearse or hw["platform"] == "tpu",
          "the serving process is not on a TPU", hardware=hw)
    check(kernel["impl"] == want_impl and kernel["prefill_impl"] == want_impl,
          "TPU-auto did not select the fused kernels", kernel=kernel)
    check(rehearse or kernel["interpret"] is False,
          "fused kernels ran in the Pallas interpreter", kernel=kernel)
    # The decode kernel's page stream: 8 pages a block at this page
    # shape ([64, 2 * 4, 128] bf16), derived, never set.
    check(kernel["decode_pages_per_block"] == 8,
          "the decode kernel's page stream is not at 8 pages a block",
          kernel=kernel)
    for path in ("prefill", "decode", "multistep"):
        check(dispatch.get(f"{want_impl}/{path}", 0) > 0,
              f"no {path} dispatch on the fused kernels", dispatch=dispatch)
    # The windows' sampler, chosen apart from the attention family: the
    # seeded temperature + top_k requests drew through the sort-free
    # kernel, and no window sorted.
    windows = kernel["window_sampler_dispatch_total"]
    check(kernel["window_sampler"] == want_impl
          and windows.get(want_impl, 0) > 0 and "sort" not in windows,
          "a decode window's sampler was not the sort-free kernel",
          kernel=kernel)
    check(not any(k.startswith("xla/") for k in dispatch),
          "an XLA-attention dispatch ran", dispatch=dispatch)
    requests = status["goodput"]["requests"]
    check(requests["aborted"] == 0 and requests["finished"] >= 10,
          "requests were aborted or lost", requests=requests)
    cache = stage["cache_stats"]
    check(cache["kv_oom_aborts"] == 0, "kv_oom aborts", cache=cache)

    compile_ = status["device"]["compile"]
    dev0 = hw["devices"][0]
    say(phase="serve", **phases,
        kernel=kernel, sampled_tokens=sampled["choices"][0]["token_ids"],
        cached_tokens=cached, requests=requests,
        compiles=compile_["compiles_total"],
        compile_cache_hits=compile_["cache_hits_total"],
        compile_seconds=round(compile_["compile_ms_total"] / 1e3, 1),
        compile_programs=compile_["programs"],
        bytes_in_use=dev0["bytes_in_use"],
        peak_bytes_in_use=dev0["peak_bytes_in_use"],
        bytes_limit=dev0["bytes_limit"],
        param_bytes=dev0["param_bytes"],
        kv_pages=stage["num_pages"], free_pages=stage["free_pages"],
        host_capacity_pages=cache.get("host_capacity_pages"),
        wave_seconds=[round(r["_seconds"], 2) for r in results])
    return {"platform": hw["platform"], "kind": hw["kind"],
            "count": hw["count"]}


# --------------------------------------------------------------------------
# Four chips: TP=1 against the default TP=4 on the same checkpoint.
# --------------------------------------------------------------------------

# How far one token's logit may sit from another's, or from itself in the
# other layout, and still count as the same answer. Row-parallel
# projections round each shard's partial sum to bf16 before the psum, so
# TP=4 moves a logit by a few 1e-2 (0.037 was the worst of 53 positions
# on the chip) and flips an argmax now and then.
TP_LOGPROB_TOL = 0.08
TP_PROMPT_CHARS = (40, 150, 400, 900)
TP_TOKENS = 16


def tp_prompts() -> list[list[int]]:
    """The comparison's prompts, as token ids: what a stream generates
    has to go back in, and the byte tokenizer has text for 256 ids."""
    return [list(filler(n, f"tp{i}").encode())
            for i, n in enumerate(TP_PROMPT_CHARS)]


def greedy(srv, prompt_ids: list[int], n: int, **extra):
    """``n`` greedy tokens after ``prompt_ids``: (ids, their logprobs)."""
    r = srv.post("/v1/completions", dict(
        model="smoke", prompt=prompt_ids, max_tokens=n, logprobs=True,
        **GREEDY, **extra,
    ))
    usage_of(r, n, "tp prompt")
    choice = r["choices"][0]
    return choice["token_ids"], choice["logprobs"]["token_logprobs"]


def tied_logprob(srv, context: list[int], token: int) -> float:
    """``token``'s logprob after ``context`` if it ties the server's own
    maximum there, else fail. The server answers for itself: with
    ``TP_LOGPROB_TOL`` added to that one logit (``logit_bias``) a greedy
    step picks ``token`` exactly when it was within the tolerance of the
    maximum. The logprob comes back with the bias in it
    (``q = p e^B / (1 - p + p e^B)``), and is returned with it taken out."""
    bias = TP_LOGPROB_TOL
    ids, (biased,) = greedy(srv, context, 1, logit_bias={str(token): bias})
    check(ids == [token],
          "a divergence that is no tie: the reference's token is not "
          f"within {bias} of this layout's maximum",
          position=len(context), reference=token, chosen_even_so=ids)
    q = math.exp(biased)
    return biased - math.log(math.exp(bias) * (1.0 - q) + q)


def replay_reference(srv, prompts: list[list[int]], reference) -> dict:
    """The tie-tolerant rule of the correctness oracle, over HTTP, with
    TP=1 as the reference. ``srv`` (TP=4) generates greedily and at every
    position must give the reference's token the reference's logprob,
    and either choose it or hold it tied with what it chose
    (:func:`tied_logprob`). After a tie its stream has left the
    reference's context, so it starts again from there: every position
    of every stream is compared, in the reference's context."""
    agreed = ties = 0
    worst = 0.0
    for i, (prompt, (ref_ids, ref_lps)) in enumerate(zip(prompts, reference)):
        done = 0
        while done < len(ref_ids):
            ids, lps = greedy(srv, prompt + ref_ids[:done],
                              len(ref_ids) - done)
            for tok, lp in zip(ids, lps):
                want = ref_ids[done]
                if tok != want:
                    lp = tied_logprob(srv, prompt + ref_ids[:done], want)
                gap = abs(lp - ref_lps[done])
                worst = max(worst, gap)
                check(gap <= TP_LOGPROB_TOL,
                      f"prompt {i} position {done}: the layouts give token "
                      f"{want} different logprobs",
                      tp1=ref_lps[done], tp4=lp)
                done += 1
                if tok != want:
                    ties += 1
                    break        # resume from the reference's context
                agreed += 1
    check(agreed >= 3 * ties, "the layouts tie too often to call it "
          "agreement", agreed=agreed, ties=ties)
    return {"positions_agreed": agreed, "ties": ties,
            "max_logprob_gap": round(worst, 4)}


def drive_four_chips(work: str, ckpt: str, flags: list[str]) -> dict:
    prompts = tp_prompts()
    reference, hardware = None, {}
    for name, tp_flags in (("tp1", ["--tp-size", "1"]), ("tp4", [])):
        srv = Server(work, ckpt, name, flags + tp_flags)
        try:
            load_s = srv.wait_ready(700)
            hardware[name] = srv.status()["hardware"]
            if reference is None:
                reference = [greedy(srv, p, TP_TOKENS) for p in prompts]
                compared = {}
            else:
                compared = replay_reference(srv, prompts, reference)
            status = srv.status()
            stage = status["stages"][0]
            check(status["goodput"]["requests"]["aborted"] == 0,
                  f"{name}: aborted requests")
            check(stage["kernel"]["impl"] == "pallas-fused",
                  f"{name}: fused kernels not selected",
                  kernel=stage["kernel"])
            say(phase=f"serve-{name}", load_s=round(load_s, 1),
                hardware_at_ready=hardware[name], kernel=stage["kernel"],
                kv_pages=stage["num_pages"],
                compiles=status["device"]["compile"]["compiles_total"],
                **compared)
        finally:
            srv.close()

    hw = hardware["tp4"]
    devs = hw["devices"]
    check(hw["count"] == 4 and len(devs) == 4, "want four devices", hw=hw)
    total = hardware["tp1"]["devices"][0]["param_bytes"]
    shares = [d["param_bytes"] / total for d in devs]
    # A quarter of the sharded weights plus the replicated embedding
    # (8% of this model): each device holds about 0.31 of the bytes.
    check(max(shares) <= 0.36 and max(shares) - min(shares) <= 0.02,
          "weights are not spread over the four devices", shares=shares)
    if devs[0]["bytes_in_use"] is not None:
        check(devs[0]["peak_bytes_in_use"] <= 1.5 * devs[0]["bytes_in_use"],
              "device 0 staged more than 1.5x its steady share while "
              "loading", device0=devs[0])
    say(phase="tp-layout", param_share_per_device=[round(s, 3)
                                                   for s in shares],
        device0=devs[0])
    return {"platform": hw["platform"], "kind": hw["kind"],
            "count": hw["count"]}


# --------------------------------------------------------------------------
# Child 1: main-path kernels on the chip against their XLA references.
# --------------------------------------------------------------------------


def child_kernels(out_path: str, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp

    from parallax_tpu.ops.attention import (
        _ragged_paged_attention_xla,
        ragged_paged_attention,
    )
    from parallax_tpu.ops.decode_fused_pallas import (
        fused_sample_topk_pallas,
        gqa_fused_decode_pallas,
    )
    from parallax_tpu.ops.kernel_select import fused_interpret, tpu_available
    from parallax_tpu.ops.kv_cache_ops import reshape_and_cache
    from parallax_tpu.ops.prefill_fused_pallas import gqa_fused_prefill_pallas
    from parallax_tpu.ops.sampling import row_gumbel, sample_tokens

    dev = jax.devices()[0]
    if not rehearse and not tpu_available():
        raise SystemExit(f"no TPU: JAX runs on {dev.platform}")
    interpret = fused_interpret()
    dt = jnp.bfloat16
    d, page, pps, vocab = 128, 64, 129, 152064
    if rehearse:
        d, page, pps, vocab = 32, 8, 48, 1000
    rows = 8                          # the smallest sequence bucket
    num_pages = rows * pps + 1        # every table slot its own page
    scale = d ** -0.5
    rng = np.random.default_rng(0)
    results = {}

    def close(name, got, want, atol):
        err = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32)
        )))
        results[name] = {"max_abs_err": round(err, 5), "atol": atol}
        if not (err <= atol) or not bool(jnp.all(jnp.isfinite(
                got.astype(jnp.float32)))):
            raise SystemExit(f"{name}: max abs err {err} > {atol}")

    def case(hq, hkv, q_lens, cached):
        """A ragged batch over a randomly filled cache."""
        s_real = len(q_lens)
        s = rows
        kv_lens = np.zeros((s,), np.int32)
        kv_lens[:s_real] = np.add(q_lens, cached)
        cu = np.zeros((s + 1,), np.int32)
        cu[1:s_real + 1] = np.cumsum(q_lens)
        cu[s_real + 1:] = cu[s_real]
        t_real = int(cu[s_real])
        t = max(8, 1 << (t_real - 1).bit_length())
        pages = (rng.permutation(num_pages - 1) + 1).astype(
            np.int32
        ).reshape(s, pps)
        slots = np.full((t,), -1, np.int32)
        for i in range(s_real):
            for j in range(q_lens[i]):
                pos = cached[i] + j
                slots[cu[i] + j] = pages[i, pos // page] * page + pos % page

        def arr(*shape):
            return jnp.asarray(
                rng.standard_normal(shape, dtype=np.float32), dt
            )

        return dict(
            q=arr(t, hq, d), k=arr(t, hkv, d), v=arr(t, hkv, d),
            cache=arr(num_pages, page, 2 * hkv, d),
            kv_lens=jnp.asarray(kv_lens), pages=jnp.asarray(pages),
            cu=jnp.asarray(cu), nseq=jnp.asarray([s_real], jnp.int32),
            slots=jnp.asarray(slots), t_real=t_real,
        )

    room = page * pps
    # Decode: one token per row, contexts from 1 token to 12 pages: a
    # page-exact boundary, the fused kernel's block boundary (it streams
    # 8 pages a block at the Qwen shapes, 2 at 32 KV heads) crossed by
    # one token — that row's append lands in the first page of its last
    # block — and a third block. The benchmark's four head shapes and
    # one TP=4 shard.
    for hq, hkv in ((28, 4), (7, 1), (16, 2), (32, 32), (20, 1)):
        tag = f"{hq}q{hkv}kv"
        lens = [1, page, page + 1, 3 * page + 17, min(room, 5 * page), 9,
                8 * page + 1, 12 * page + 5]
        c = case(hq, hkv, [1] * len(lens), [n - 1 for n in lens])
        cache_x = reshape_and_cache(c["cache"], c["k"], c["v"], c["slots"])
        want = _ragged_paged_attention_xla(
            c["q"], cache_x, c["kv_lens"], c["pages"], c["cu"], c["nseq"],
            sm_scale=scale, sliding_window=None, soft_cap=None, sinks=None,
        )
        got, cache_f = gqa_fused_decode_pallas(
            c["q"], c["k"], c["v"], c["cache"], c["kv_lens"], c["pages"],
            c["slots"], None, sm_scale=scale, interpret=interpret,
        )
        if not bool(jnp.array_equal(cache_f, cache_x)):
            raise SystemExit(f"decode {tag}: in-kernel append != scatter")
        n = c["t_real"]
        close(f"decode_{tag}", got[:n], want[:n], 3e-2)

    for hq, hkv in ((28, 4), (7, 1)):
        tag = f"{hq}q{hkv}kv"
        # Prefill: ragged chunk lengths over cached prefixes, crossing
        # page and query-block boundaries.
        q_lens = [70, 33, 1, 100, 17]
        cached = [0, 2 * page, 45, page + 3, 0]
        c = case(hq, hkv, q_lens, cached)
        cache_x = reshape_and_cache(c["cache"], c["k"], c["v"], c["slots"])
        want = _ragged_paged_attention_xla(
            c["q"], cache_x, c["kv_lens"], c["pages"], c["cu"], c["nseq"],
            sm_scale=scale, sliding_window=None, soft_cap=None, sinks=None,
        )
        got, cache_f = gqa_fused_prefill_pallas(
            c["q"], c["k"], c["v"], c["cache"], c["kv_lens"], c["pages"],
            c["cu"], c["nseq"], c["slots"], None,
            sm_scale=scale, interpret=interpret,
        )
        if not bool(jnp.array_equal(cache_f, cache_x)):
            raise SystemExit(f"prefill {tag}: in-kernel append != scatter")
        n = c["t_real"]
        close(f"prefill_{tag}", got[:n], want[:n], 3e-2)
        if not bool(jnp.all(got[n:] == 0)):
            raise SystemExit(f"prefill {tag}: padding rows not zero")

        if not rehearse:
            # The bundled kernel (what a speculative window's forward
            # calls), with block sizes derived from the shapes.
            got = ragged_paged_attention(
                c["q"], cache_x, c["kv_lens"], c["pages"], c["cu"],
                c["nseq"], sm_scale=scale, use_pallas=True,
            )
            close(f"bundled_rpa_{tag}", got[:n], want[:n], 3e-2)

    # The Mamba decode recurrence (ops/mamba.py) at Jamba2-3B's widths:
    # state in, state out in place by slot index, against the plain
    # jax.numpy step; 8 rows (the probe) and serve's full batch.
    from parallax_tpu.ops.mamba import (
        CONV_ROWS,
        conv_decode_step,
        ssm_conv_update,
        ssm_decode_step,
        ssm_decode_update,
    )

    di, n_state = (256, 16) if rehearse else (5120, 16)
    for s_rows in (8, 64):
        def f32(*shape):
            return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))

        n_slots = 2 * s_rows + 1
        ssm = dict(
            dt_lin=f32(s_rows, di) - 2.0, xs=f32(s_rows, di),
            z=f32(s_rows, di), b=f32(s_rows, n_state),
            c=f32(s_rows, n_state),
            a_t=-jnp.exp(0.5 * f32(n_state, di)), d_skip=f32(di),
            state_all=f32(n_slots, n_state, di),
            slots=jnp.asarray(rng.permutation(n_slots - 1)[:s_rows] + 1,
                              jnp.int32),
            reset=jnp.asarray(rng.integers(0, 2, s_rows), jnp.int32),
        )
        want_y, want_state = ssm_decode_step(**ssm, use_pallas=False)
        got_y, got_state = ssm_decode_update(**ssm, interpret=interpret)
        close(f"ssm_decode_update_y_{s_rows}rows", got_y, want_y, 1e-4)
        close(f"ssm_decode_update_state_{s_rows}rows", got_state,
              want_state, 1e-5)
        conv = dict(
            xs=ssm["xs"], weight=f32(4, di), bias=f32(di),
            window_all=jnp.zeros((n_slots, CONV_ROWS, di)).at[:, :3].set(
                f32(n_slots, 3, di)),
            slots=ssm["slots"], reset=ssm["reset"],
        )
        want_y, want_win = conv_decode_step(
            conv["xs"], conv["window_all"], conv["weight"], conv["bias"],
            conv["slots"], conv["reset"], use_pallas=False)
        got_y, got_win = ssm_conv_update(**conv, interpret=interpret)
        close(f"ssm_conv_update_y_{s_rows}rows", got_y, want_y, 1e-5)
        close(f"ssm_conv_update_window_{s_rows}rows", got_win, want_win,
              0.0)

    # Sampler: greedy, plain temperature, and top-k rows (k = 1, mid,
    # the fused bound), seeded and unseeded — draws must be identical.
    # At the served vocabulary in the smallest bucket, and at the A.X-K1
    # cell's window (128 rows of 20,480 logits: a latent-attention
    # stage's windows take this sampler too).
    row_temp = [0.0, 0.7, 1.0, 0.8, 1.3, 0.0, 0.9, 1.0]
    row_top_k = [0, 0, 1, 20, 64, 5, 2, 0]
    row_seeds = [-1, 7, 8, -1, 9, -1, 10, 11]
    wide = (16, 320) if rehearse else (128, 20480)
    for tag, (s, v) in (("sampler", (8, vocab)), ("sampler_wide", wide)):
        logits = jnp.asarray(rng.standard_normal((s, v), dtype=np.float32))
        temp = jnp.asarray(np.resize(row_temp, s), jnp.float32)
        top_k = jnp.asarray(np.resize(row_top_k, s), jnp.int32)
        seeds = jnp.asarray(np.resize(row_seeds, s), jnp.int32)
        steps = jnp.arange(s, dtype=jnp.int32)
        key = jax.random.key(0)
        want = sample_tokens(
            logits, key, temp, top_k, jnp.ones((s,), jnp.float32),
            jnp.zeros((s,), jnp.float32), seeds=seeds, out_steps=steps,
        )
        got = fused_sample_topk_pallas(
            logits, row_gumbel(key, s, v, seeds, steps), temp, top_k,
            interpret=interpret,
        )
        if not bool(jnp.array_equal(got, want)):
            raise SystemExit(f"{tag}: {got.tolist()} != {want.tolist()}")
        results[tag] = {"rows": s, "vocab": v, "tokens": got.tolist()[:8]}

    stats = dev.memory_stats() or {}
    with open(out_path, "w") as f:
        json.dump({
            "phase": "kernels", "interpret": interpret,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "bytes_limit": stats.get("bytes_limit"),
            "results": results,
        }, f)


# --------------------------------------------------------------------------
# Entry.
# --------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve the checkpoint at --tp-size 1 and at "
                         "the default TP=4 and compare; nothing else")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dress rehearsal at toy widths (not a chip run)")
    ap.add_argument("--child-kernels", metavar="OUT", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_kernels:
        child_kernels(args.child_kernels, args.rehearse)
        return 0

    t_start = time.monotonic()
    work = os.path.join(ROOT, ".chip_smoke")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ckpt = os.path.join(work, "checkpoint")
    cfg = dict(TINY) if args.rehearse else dict(
        QWEN25_7B, num_hidden_layers=LAYERS
    )
    sizes = dict(SERVE_DEFAULTS)
    flags: list[str] = []
    if args.rehearse:
        # Off the chip the fused kernels must be asked for (TPU-auto
        # keeps XLA there), and the toy run keeps the page table short.
        sizes = dict(REHEARSE_FLAGS)
        flags = ["--decode-fused", "--prefill-fused",
                 "--max-model-len", str(sizes["max_model_len"]),
                 "--prefill-chunk-size", str(sizes["prefill_chunk_size"])]
    device = None
    try:
        # Child 1 first: it needs no checkpoint, and without a chip it
        # fails here, before 13 GB are written.
        if args.chips == 1:
            say(**run_kernel_child(work, args.rehearse))
        written = write_checkpoint(ckpt, cfg, args.seed)
        say(phase="write", source=SOURCE,
            reduced={"num_hidden_layers":
                     [QWEN25_7B["num_hidden_layers"],
                      cfg["num_hidden_layers"]]},
            widths={k: cfg[k] for k in (
                "hidden_size", "num_attention_heads", "num_key_value_heads",
                "intermediate_size", "vocab_size")},
            rehearse=args.rehearse, seed=args.seed, **written)

        if args.chips == 4:
            device = drive_four_chips(work, ckpt, flags)
        else:
            srv = Server(work, ckpt, "one", flags)
            try:
                load_s = srv.wait_ready(700)
                say(phase="load", seconds=round(load_s, 1),
                    cmd=" ".join(srv.cmd[1:]))
                device = drive_one_chip(srv, sizes, args.rehearse)
            finally:
                srv.close()
        check(device["count"] == args.chips or args.rehearse,
              "device count", device=device, chips=args.chips)
        check(device["platform"] == "tpu" or args.rehearse,
              "not a TPU", device=device)
    except SmokeFailed as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        for proc in _children:
            stop(proc, grace_s=5.0)
        shutil.rmtree(ckpt, ignore_errors=True)
    say(phase="total", seconds=round(time.monotonic() - t_start, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
