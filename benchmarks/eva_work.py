"""What EVA attention needs, from shapes: operations and bytes.

Beside ``harness/work.py`` and under its rules: counts are of the
mathematics on real entries, not of what a kernel touches. An *entry* is
one row of the paged cache that a decode step attends — a token of the
row's open window or the summary of one chunk of a completed window;
both hold K and V for every head (``2 * heads * head_dim`` elements).
The program counts the entries its decode steps attend
(``parallax_eva_entries_attended``: per step and row the virtual
``kv_len``, once, not per layer). What a decode step reads of them is
any architecture's ``work.entry_bytes`` / ``work.decode_read_work``;
here is what only EVA has, the summary write.
"""

from __future__ import annotations

from benchmarks.harness import work


def summary_work(cfg: dict, chunks: int, dtype: str = "bfloat16") -> dict:
    """``chunks`` chunk summaries, all layers: the chunk's ``chunk_size``
    entries read and one written; two dot products of length D per key
    and head for the logits, two weighted sums of D per entry and head."""
    c, h, d = cfg["chunk_size"], cfg["num_key_value_heads"], work.head_dim(cfg)
    layers = cfg["num_hidden_layers"]
    return {"flops": chunks * layers * 8 * c * h * d,
            "bytes": chunks * layers * (c + 1) * work.entry_bytes(cfg, dtype)}
