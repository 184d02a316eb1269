"""What EVA attention needs, from shapes: operations and bytes.

Beside ``harness/work.py`` and under its rules: counts are of the
mathematics on real entries, not of what a kernel touches. An *entry* is
one row of the paged cache that a decode step attends — a token of the
row's open window or the summary of one chunk of a completed window;
both hold K and V for every head (``2 * heads * head_dim`` elements).
The program counts the entries its decode steps attend
(``parallax_eva_entries_attended``: per step and row the virtual
``kv_len``, once, not per layer); the functions here turn a count into
bytes and operations over all layers.
"""

from __future__ import annotations

from benchmarks.harness import work


def entry_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Bytes of one entry in one layer (K and V of every head)."""
    return (2 * cfg["num_key_value_heads"] * work.head_dim(cfg)
            * work.BYTES[dtype])


def decode_read_work(cfg: dict, entries: int, steps: int = 0,
                     dtype: str = "bfloat16") -> dict:
    """Decode steps that together attend ``entries`` entries, all layers:
    QK^T and PV are 2 * Hq * D multiply-adds per entry each, and every
    entry is read once. ``steps`` (row-steps, where known) adds each
    step's query and output row and the new token's K/V write."""
    hq, d = cfg["num_attention_heads"], work.head_dim(cfg)
    layers = cfg["num_hidden_layers"]
    per_step = 2 * hq * d * work.BYTES[dtype] + entry_bytes(cfg, dtype)
    return {"flops": 4 * hq * d * entries * layers,
            "bytes": (entries * entry_bytes(cfg, dtype)
                      + steps * per_step) * layers}


def summary_work(cfg: dict, chunks: int, dtype: str = "bfloat16") -> dict:
    """``chunks`` chunk summaries, all layers: the chunk's ``chunk_size``
    entries read and one written; two dot products of length D per key
    and head for the logits, two weighted sums of D per entry and head."""
    c, h, d = cfg["chunk_size"], cfg["num_key_value_heads"], work.head_dim(cfg)
    layers = cfg["num_hidden_layers"]
    return {"flops": chunks * layers * 8 * c * h * d,
            "bytes": chunks * layers * (c + 1) * entry_bytes(cfg, dtype)}
