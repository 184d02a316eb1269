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


def _layer_elements(cfg: dict) -> int:
    """One layer: q/k/v and output projections, q/k/v biases where the
    configuration has them (``attention_bias``), the gated MLP's three
    matrices, two norms."""
    h, inter, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    bias = q + 2 * kv if cfg.get("attention_bias") else 0
    return h * (q + 2 * kv) + bias + q * h + 3 * h * inter + 2 * h


def weight_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Bytes of the stage's weights (all layers, embedding, head)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    embed = v * h * (1 if cfg.get("tie_word_embeddings") else 2)
    return (cfg["num_hidden_layers"] * _layer_elements(cfg) + embed
            + h) * BYTES[dtype]


def decode_step_weight_elements(cfg: dict) -> int:
    """Elements of the weights one decode step must read: every layer's
    matrices, biases and norms, the final norm and ONE head matrix
    ``vocab x hidden`` (head 0's rows where the head predicts several
    tokens). Tied or not, the embedding table is not read at decode (a
    step looks up a row a token), which is what this leaves out of
    ``weight_bytes``' count of storage."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return cfg["num_hidden_layers"] * _layer_elements(cfg) + h + v * h


def decode_step_weight_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Their bytes: under ``weight_bytes`` by one embedding table where
    the head is untied, equal to it where the head is the embedding."""
    return decode_step_weight_elements(cfg) * BYTES[dtype]


def decode_step_work(cfg: dict, steps: float, tokens: int, attn: dict,
                     dtype: str = "bfloat16") -> dict:
    """``steps`` decode steps that together produce ``tokens`` tokens
    (``tokens / steps`` rows a step): the weights are read once a step
    and multiplied into every row (2 operations an element and row),
    and the steps' attention (``attn``, ``decode_read_work``) is added."""
    elements = decode_step_weight_elements(cfg)
    return add(attn, {"flops": 2 * elements * tokens,
                      "bytes": steps * elements * BYTES[dtype]})


def entry_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Bytes of one cache entry in one layer: K and V of every KV head.
    An entry is what a decode step attends: a cached position or, for
    EVA, the summary of one chunk of a completed window."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BYTES[dtype]


def decode_read_work(cfg: dict, entries: int, steps: int = 0,
                     dtype: str = "bfloat16") -> dict:
    """Decode steps that together attend ``entries`` entries, all layers:
    QK^T and PV are 2 * Hq * D multiply-adds per entry each, and every
    entry is read once. ``steps`` (row-steps, where known) adds each
    step's query and output row and the new token's K/V write. With
    ``entries`` the sum of the steps' contexts this is term for term the
    sum of ``attn_decode_work`` over them."""
    hq, d = cfg["num_attention_heads"], head_dim(cfg)
    layers = cfg["num_hidden_layers"]
    per_step = 2 * hq * d * BYTES[dtype] + entry_bytes(cfg, dtype)
    return {"flops": 4 * hq * d * entries * layers,
            "bytes": (entries * entry_bytes(cfg, dtype)
                      + steps * per_step) * layers}


ENTRIES_SERIES = "parallax_eva_entries_attended"


def span_decode_attention(cfg: dict, sw: dict, scrape_t0, scrape_t1,
                          dtype: str = "bfloat16") -> dict | None:
    """What the traced span's decode steps made the attention kernel
    compute (``decode_read_work`` of the entries they attended), or None
    where nothing honest can be read.

    The entries are the program's own count (``ENTRIES_SERIES`` between
    the span's two scrapes: per step and row the entries attended, once,
    not per layer) where it exports one and the count fits what the
    clients saw: a decode token at context ``c`` attends between
    ``c / chunk_size`` entries (all summaries) and ``c`` (no summary).
    Otherwise the clients' ``decode_context_sum``: a grouped-query row
    attends every cached position. A configuration with a ``chunk_size``
    does not, so without a count inside the bracket it reads nothing."""
    seen = entries = sw["decode_context_sum"]
    if seen <= 0:
        return None
    counted = None
    if scrape_t0 is not None and ENTRIES_SERIES in (scrape_t1 or {}):
        counted = (scrape_t1[ENTRIES_SERIES]
                   - scrape_t0.get(ENTRIES_SERIES, 0.0))
    if (counted is not None
            and seen / cfg.get("chunk_size", 1) <= counted <= seen):
        entries = counted
    elif "chunk_size" in cfg:
        return None
    return decode_read_work(cfg, entries, sw["decode_tokens"], dtype)


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
