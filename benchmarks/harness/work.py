"""What the algorithm needs, from shapes: operations and bytes.

Kept with the benchmark so that no PR that claims a gain can change how
a kernel's work is counted. All counts are of the mathematics (useful
work on real tokens), not of what a kernel happens to touch: padding
rows, recomputation and masked-out halves of a causal block do not
count.
"""

from __future__ import annotations

from benchmarks.harness import spec

BYTES = {"bfloat16": 2, "float32": 4}


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


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


# --------------------------------------------------------------------------
# The stage, layer by layer. A configuration may name its own work file
# (``bench.work`` -> ``benchmarks/works/<stem>.py``, ``spec.work_of``);
# without one it is the dense block.
# --------------------------------------------------------------------------

# What a decode step needs of one layer. ``always``: elements every step
# reads whatever its rows are (mixer or attention projections, biases,
# norms, a router, a shared expert, a dense MLP). ``expert``: elements of
# one routed expert; ``experts_held``: how many of them this chip holds;
# ``experts_per_token``: how many a token is routed to. ``entry_bytes`` /
# ``entry_flops``: one paged cache entry as the decode kernel reads it
# (K and V of every KV head, and QK^T + PV against it, for grouped-query
# attention; a latent row for MLA) and ``row_bytes`` a row-step's query
# in and output row out; all three 0 for a layer that holds no pages.
# ``state_bytes``: recurrent state one row's step reads and writes (a
# convolution window, an SSM or linear-attention state).
LAYER_KEYS = ("always", "expert", "experts_held", "experts_per_token",
              "entry_bytes", "entry_flops", "row_bytes", "state_bytes")

DECODE_KERNEL = "^gqa_fused_decode_pallas"
DECODE_PROGRAM = r"^jit_fn\("

# What only the program can count, each the growth of a series between
# the traced span's two scrapes: the distinct held experts a step's rows
# hit, summed over expert layers and steps, and the token-expert pairs
# that landed on held experts. A program with an expert layer exports
# both (benchmarks/README.md, "A new architecture").
EXPERTS_READ_SERIES = "parallax_moe_experts_read"
PAIRS_HELD_SERIES = "parallax_moe_pairs_held"
# The scrapes reach over the span by the profiler's start, and the
# clients' tokens in a 4 s span come in deliveries of a K-step window a
# row (PERF.md: a grain of ~5%): the ends of the experts' bracket that
# hold the program's counts against those are taken this much wide, and
# a count inside the margin is cut to what the span's own steps and
# tokens allow.
COUNT_MARGIN = 1.1


def dense_layer(cfg: dict, dtype: str = "bfloat16") -> dict:
    """One layer of the dense llama-family block: grouped-query
    attention over pages and a gated MLP, no expert, no state."""
    hq, d = cfg["num_attention_heads"], head_dim(cfg)
    return {"always": _layer_elements(cfg), "expert": 0, "experts_held": 0,
            "experts_per_token": 0, "entry_bytes": entry_bytes(cfg, dtype),
            "entry_flops": 4 * hq * d, "row_bytes": 2 * hq * d * BYTES[dtype],
            "state_bytes": 0}


def dense_layers(cfg: dict) -> list[dict]:
    return [dense_layer(cfg)] * cfg["num_hidden_layers"]


def dense_head_elements(cfg: dict) -> int:
    """The final norm and ONE head matrix ``vocab x hidden`` (head 0's
    rows where the head predicts several tokens). Tied or not, the
    embedding table is not read at decode (a step looks up a row a
    token), which is what a step's weights leave out of
    ``weight_bytes``' count of storage."""
    return cfg["hidden_size"] + cfg["vocab_size"] * cfg["hidden_size"]


def stage(cfg: dict, module=None) -> dict:
    """The stage as a decode step's work is counted: the layer list of
    the configuration's work file (``module``; every entry point but
    ``layers`` optional, with the dense block's in its place) and its
    totals. ``module`` None is the dense block.

    A work file binds ``layers(cfg) -> list[dict]`` (one dict a layer
    run, keys of ``LAYER_KEYS``: ``always`` must be there, the others
    count 0 where left out; ``dense_layer`` is there to start from) and,
    where it differs from the dense block: ``head_elements(cfg)``,
    ``DECODE_KERNEL`` / ``DECODE_PROGRAM`` (name patterns of the decode
    attention kernel and of the K-step program), ``ENTRIES_SERIES`` with
    ``entries_bracket(cfg, context_sum) -> (least, most)`` (the
    program's own count of the entries its decode steps attend, where a
    row does not attend every cached position)."""
    layers = []
    for given in (module.layers(cfg) if module else dense_layers(cfg)):
        unknown = set(given) - set(LAYER_KEYS)
        if unknown or "always" not in given:
            raise ValueError(f"a layer of a work file has the keys "
                             f"{LAYER_KEYS} ('always' at least), not "
                             f"{sorted(given)}")
        layers.append({k: given.get(k, 0) for k in LAYER_KEYS})
    held = [l for l in layers if l["experts_held"] > 0]
    if len({(l["expert"], l["experts_per_token"]) for l in held}) > 1:
        # One count over all expert layers cannot be split between them.
        raise ValueError("expert layers of one stage share one expert "
                         "size and one number of experts a token")
    paged = [l for l in layers if l["entry_bytes"] > 0]
    head = getattr(module, "head_elements", dense_head_elements)(cfg)
    series = getattr(module, "ENTRIES_SERIES", None)
    return {
        "cfg": cfg, "layers": layers,
        "always": sum(l["always"] for l in layers) + head,
        "expert": held[0]["expert"] if held else 0,
        "experts_per_token": held[0]["experts_per_token"] if held else 0,
        "experts_held": sum(l["experts_held"] for l in held),
        "expert_layers": len(held),
        "paged_layers": len(paged),
        "entry_bytes": sum(l["entry_bytes"] for l in paged),
        "entry_flops": sum(l["entry_flops"] for l in paged),
        "row_bytes": sum(l["row_bytes"] for l in paged),
        "state_bytes": sum(l["state_bytes"] for l in layers),
        "kernel": getattr(module, "DECODE_KERNEL", DECODE_KERNEL),
        "program": getattr(module, "DECODE_PROGRAM", DECODE_PROGRAM),
        "entries_series": series,
        "entries_bracket": module.entries_bracket if series else None,
    }


def load_stage(cfg: dict, path: str | None) -> dict:
    """``stage`` of the work file at ``path`` (``spec.work_of``'s; None:
    the dense block). A work file imports nothing but this module."""
    return stage(cfg, spec.import_file("bench_work_", path) if path else None)


def entry_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Bytes of one grouped-query cache entry in one layer: K and V of
    every KV head. An entry is what a decode step attends: a cached
    position or, for EVA, the summary of one chunk of a completed
    window."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BYTES[dtype]


def decode_read_work(stg: dict, entries: int, steps: int = 0) -> dict:
    """Decode steps that together attend ``entries`` entries, in every
    layer that holds pages: the entry's operations (QK^T and PV) per
    entry, and every entry read once. ``steps`` (row-steps, where known)
    adds each step's query and output row and the new token's entry
    written. With ``entries`` the sum of the steps' contexts this is
    term for term the sum of ``attn_decode_work`` over them."""
    return {"flops": entries * stg["entry_flops"],
            "bytes": (entries * stg["entry_bytes"]
                      + steps * (stg["row_bytes"] + stg["entry_bytes"]))}


def span_decode_attention(stg: dict, sw: dict, scrape_t0, scrape_t1
                          ) -> dict | None:
    """What the traced span's decode steps made the attention kernel
    compute (``decode_read_work`` of the entries they attended), or None
    where nothing honest can be read.

    The entries are the clients' ``decode_context_sum`` (a grouped-query
    row attends every cached position) unless the stage's work file
    names a series of the program's own (``ENTRIES_SERIES``: per step
    and row the entries attended, once, not per layer): then its growth
    between the span's two scrapes, and only where it lies inside the
    file's bracket of what the clients saw - for EVA a decode token at
    context ``c`` attends between ``c / chunk_size`` entries (all
    summaries) and ``c`` (no summary). Such a stage without a count
    inside the bracket reads nothing."""
    seen = entries = sw["decode_context_sum"]
    if seen <= 0:
        return None
    series = stg["entries_series"]
    if series is not None:
        if scrape_t0 is None or series not in (scrape_t1 or {}):
            return None
        entries = scrape_t1[series] - scrape_t0.get(series, 0.0)
        least, most = stg["entries_bracket"](stg["cfg"], seen)
        if not least <= entries <= most:
            return None
    return decode_read_work(stg, entries, sw["decode_tokens"])


def span_experts(stg: dict, sw: dict, steps: float, scrape_t0, scrape_t1
                 ) -> dict | None:
    """The held experts the span's ``steps`` decode steps read
    (``experts_read``: distinct experts a step's rows hit, summed over
    expert layers and steps) and the token-expert pairs that landed on
    them (``pairs_held``): both 0 for a stage that holds no expert, the
    growth of the program's two series between the span's scrapes for
    one that does, and None where either is missing or outside its
    bracket. With ``R`` rows a step, ``k`` experts a token, ``E`` held
    over ``L_e`` expert layers: ``pairs_held <= tokens * k * L_e`` and
    ``pairs_held / R <= experts_read <= min(pairs_held, steps * E)``
    (``E`` summed over the layers); every end but ``experts_read <=
    pairs_held`` holds a count of the program's against the clients'
    tokens or the trace's steps and is ``COUNT_MARGIN`` wide. Never a
    guess: between one expert and all of them a step's bytes differ by
    the layer's whole size."""
    if stg["experts_held"] == 0:
        return {"experts_read": 0, "pairs_held": 0}
    tokens = sw["decode_tokens"]
    if scrape_t0 is None or scrape_t1 is None or tokens <= 0 or steps <= 0:
        return None
    got = {}
    for key, series in (("experts_read", EXPERTS_READ_SERIES),
                        ("pairs_held", PAIRS_HELD_SERIES)):
        if series not in scrape_t1:
            return None
        got[key] = scrape_t1[series] - scrape_t0.get(series, 0.0)
    read, pairs = got["experts_read"], got["pairs_held"]
    most_pairs = tokens * stg["experts_per_token"] * stg["expert_layers"]
    most_read = steps * stg["experts_held"]
    if not 0 < pairs <= COUNT_MARGIN * most_pairs:
        return None
    if not (pairs * steps / tokens / COUNT_MARGIN <= read
            <= min(pairs, COUNT_MARGIN * most_read)):
        return None
    return {"experts_read": min(read, most_read),
            "pairs_held": min(pairs, most_pairs)}


def decode_step_work(stg: dict, steps: float, tokens: int, attn: dict,
                     experts: dict, dtype: str = "bfloat16") -> dict:
    """``steps`` decode steps that together produce ``tokens`` tokens
    (``tokens / steps`` rows a step): what every step reads (each
    layer's ``always`` and the head) is read once a step and multiplied
    into every row (2 operations an element and row); each expert a
    step's rows hit is read once (``experts`` of ``span_experts``) and
    multiplied into the rows routed to it; every row-step reads and
    writes its recurrent state; and the steps' attention (``attn``,
    ``decode_read_work``) is added."""
    return add(attn, {
        "flops": (2 * stg["always"] * tokens
                  + 2 * stg["expert"] * experts["pairs_held"]),
        "bytes": (steps * stg["always"] * BYTES[dtype]
                  + experts["experts_read"] * stg["expert"] * BYTES[dtype]
                  + tokens * stg["state_bytes"])})


def attn_decode_work(stg: dict, context: int) -> dict:
    """One token attending to ``context`` cached positions, in every
    layer that holds pages: the entry's operations per position; the
    entries of the context are read once, the query and the output row
    once, the new token's entry written once."""
    return {"flops": context * stg["entry_flops"],
            "bytes": (context + 1) * stg["entry_bytes"] + stg["row_bytes"]}


def causal_pairs(start: int, end: int) -> int:
    """(query, key) pairs of positions ``[start, end)`` under a causal
    mask: position p attends to p + 1 keys."""
    return (end * (end + 1) - start * (start + 1)) // 2


def attn_prefill_work(stg: dict, start: int, end: int) -> dict:
    """Prompt positions ``[start, end)`` under a causal mask, in every
    layer that holds pages: position p attends to p + 1 keys. The
    entries of ``[0, end)`` are read once, q in and o out once, the
    chunk's entries written once."""
    n = end - start
    return {"flops": causal_pairs(start, end) * stg["entry_flops"],
            "bytes": (end + n) * stg["entry_bytes"] + n * stg["row_bytes"]}


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


def span_work(results: list, t0: float, t1: float, stg: dict) -> dict:
    """What the requests made the attention kernels compute between
    client times ``t0`` and ``t1``.

    Decode: every token delivered in the span except a request's first
    (which the prefill step samples) attended to prompt + tokens before
    it. Prefill: a prompt counts whole if its first token arrived in the
    span (chunks of one prompt straddling an end are credited to the
    end where it finished). Rows that decode inside a mixed
    prefill-and-decode step run through the prefill kernel; they are
    counted here as decode work (see PERF.md, Open questions).

    ``attn_decode`` / ``attn_prefill`` are reckoned over the layers of
    the stage (``stage``) that hold pages, every cached position an
    entry. Three sums are true of any architecture, for a reader that
    brings its own kernel's bytes and operations: ``decode_context_sum`` (over the decode tokens delivered
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
                        pre = add(pre, attn_prefill_work(stg, cached, plen))
                        prompt_tokens += plen - cached
                        prefill_pair_sum += causal_pairs(cached, plen)
                        prompts += 1
                elif t0 <= t < t1:
                    dec = add(dec, attn_decode_work(stg, plen + j))
                    decode_tokens += 1
                    decode_context_sum += plen + j
            seen += n
    return {"attn_decode": dec, "attn_prefill": pre,
            "decode_tokens": decode_tokens, "prompt_tokens": prompt_tokens,
            "prompt_ktok": prompt_tokens / 1e3, "prompts": prompts,
            "decode_context_sum": decode_context_sum,
            "prefill_new_tokens": prompt_tokens,
            "prefill_pair_sum": prefill_pair_sum}
