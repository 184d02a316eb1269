"""What the algorithm needs, from shapes: operations and bytes.

Kept with the benchmark so that no PR that claims a gain can change how
a kernel's work is counted. All counts are of the mathematics (useful
work on real tokens), not of what a kernel happens to touch: padding
rows, recomputation and masked-out halves of a causal block do not
count.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> int:
    """Bytes of keys and values one token holds, over all layers."""
    return (2 * cfg["num_key_value_heads"] * head_dim(cfg) * BYTES[dtype]
            * cfg["num_hidden_layers"])


def weight_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Bytes of the stage's weights (all layers, embedding, head)."""
    h, inter, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    layer = h * (q + 2 * kv) + (q + 2 * kv) + q * h + 3 * h * inter + 2 * h
    embed = v * h * (1 if cfg.get("tie_word_embeddings") else 2)
    return (cfg["num_hidden_layers"] * layer + embed + h) * BYTES[dtype]


def attn_decode_work(cfg: dict, context: int, dtype: str = "bfloat16") -> dict:
    """One token attending to ``context`` cached positions, all layers:
    QK^T and PV are 2 * Hq * D multiply-adds per position each; the keys
    and values of the context are read once, the query and the output
    row once, the new token's K/V written once."""
    hq, d, layers = cfg["num_attention_heads"], head_dim(cfg), cfg["num_hidden_layers"]
    flops = 4 * hq * d * context * layers
    io = (2 * hq * d * BYTES[dtype]) * layers
    return {"flops": flops,
            "bytes": (context + 1) * kv_bytes_per_token(cfg, dtype) + io}


def causal_pairs(start: int, end: int) -> int:
    """(query, key) pairs of positions ``[start, end)`` under a causal
    mask: position p attends to p + 1 keys."""
    return (end * (end + 1) - start * (start + 1)) // 2


def attn_prefill_work(cfg: dict, start: int, end: int,
                      dtype: str = "bfloat16") -> dict:
    """Prompt positions ``[start, end)`` under a causal mask, all layers:
    position p attends to p + 1 keys. Keys and values of ``[0, end)`` are
    read once, q in and o out once, the chunk's K/V written once."""
    hq, d, layers = cfg["num_attention_heads"], head_dim(cfg), cfg["num_hidden_layers"]
    n = end - start
    pairs = causal_pairs(start, end)
    flops = 4 * hq * d * pairs * layers
    io = 2 * n * hq * d * BYTES[dtype] * layers
    return {"flops": flops,
            "bytes": (end + n) * kv_bytes_per_token(cfg, dtype) + io}


def add(a: dict, b: dict) -> dict:
    return {"flops": a["flops"] + b["flops"], "bytes": a["bytes"] + b["bytes"]}


def least_seconds(w: dict, peaks: dict) -> float:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s."""
    return max(w["flops"] / peaks["bf16_flops_per_s"],
               w["bytes"] / peaks["hbm_bytes_per_s"])


def bound_by(w: dict, peaks: dict) -> str:
    return ("compute" if w["flops"] / peaks["bf16_flops_per_s"]
            >= w["bytes"] / peaks["hbm_bytes_per_s"] else "memory")


def span_work(results: list, t0: float, t1: float, cfg: dict,
              dtype: str = "bfloat16") -> dict:
    """What the requests made the attention kernels compute between
    client times ``t0`` and ``t1``.

    Decode: every token delivered in the span except a request's first
    (which the prefill step samples) attended to prompt + tokens before
    it. Prefill: a prompt counts whole if its first token arrived in the
    span (chunks of one prompt straddling an end are credited to the
    end where it finished). Rows that decode inside a mixed
    prefill-and-decode step run through the prefill kernel; they are
    counted here as decode work (see PERF.md, Open questions).

    ``attn_decode`` / ``attn_prefill`` are reckoned for grouped-query
    attention (``kv_bytes_per_token``). Three sums are true of any
    architecture, for a reader that brings its own kernel's bytes and
    operations: ``decode_context_sum`` (over the decode tokens delivered
    in the span, the context each attended to), ``prefill_new_tokens``
    (uncached prompt tokens of the prompts finished in the span) and
    ``prefill_pair_sum`` (the (query, key) pairs those attended to under
    the causal mask)."""
    zero = {"flops": 0, "bytes": 0}
    dec, pre = dict(zero), dict(zero)
    decode_tokens = prompt_tokens = prompts = 0
    decode_context_sum = prefill_pair_sum = 0
    for r in results:
        seen = 0
        plen = len(r.req.prompt)
        cached = ((r.usage or {}).get("prompt_tokens_details") or {}).get(
            "cached_tokens", 0)
        for t, n in r.chunks:
            for j in range(seen, seen + n):
                if j == 0:
                    if t0 <= t < t1:
                        pre = add(pre, attn_prefill_work(cfg, cached, plen, dtype))
                        prompt_tokens += plen - cached
                        prefill_pair_sum += causal_pairs(cached, plen)
                        prompts += 1
                elif t0 <= t < t1:
                    dec = add(dec, attn_decode_work(cfg, plen + j, dtype))
                    decode_tokens += 1
                    decode_context_sum += plen + j
            seen += n
    return {"attn_decode": dec, "attn_prefill": pre,
            "decode_tokens": decode_tokens, "prompt_tokens": prompt_tokens,
            "prompt_ktok": prompt_tokens / 1e3, "prompts": prompts,
            "decode_context_sum": decode_context_sum,
            "prefill_new_tokens": prompt_tokens,
            "prefill_pair_sum": prefill_pair_sum}
