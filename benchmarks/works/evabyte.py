"""EvaByte's work file (``bench.work`` of ``configs/evabyte-6.5b-d16.json``):
what a decode step of EVA attention needs, from shapes.

Beside ``harness/work.py`` and under its rules: counts are of the
mathematics on real entries, not of what a kernel touches. The layers
are the dense block's (q/k/v/o, a gated MLP, two norms; ``lm_head``'s
head 0 is the ``vocab_size`` rows the file states), read by the same
decode kernel. What differs is what a row attends. An *entry* is one row
of the paged cache that a decode step attends - a token of the row's
open window or the summary of one chunk of a completed window; both hold
K and V for every head (``2 * heads * head_dim`` elements). So a row at
context ``c`` attends between ``c / chunk_size`` entries (all summaries)
and ``c`` (no summary), and only the program can say how many: it counts
them (``ENTRIES_SERIES``: per step and row the virtual ``kv_len``, once,
not per layer), and ``work.span_decode_attention`` takes the count's
growth between the traced span's two scrapes only inside
``entries_bracket``, else nothing. Here too is what only EVA has, the
summary write.
"""

from __future__ import annotations

from benchmarks.harness import work

layers = work.dense_layers

ENTRIES_SERIES = "parallax_eva_entries_attended"


def entries_bracket(cfg: dict, context_sum: int) -> tuple:
    """Decode tokens whose contexts sum to ``context_sum`` attended at
    least every ``chunk_size``-th of them and at most all."""
    return context_sum / cfg["chunk_size"], context_sum


def summary_work(cfg: dict, chunks: int, dtype: str = "bfloat16") -> dict:
    """``chunks`` chunk summaries, all layers: the chunk's ``chunk_size``
    entries read and one written; two dot products of length D per key
    and head for the logits, two weighted sums of D per entry and head."""
    c, h, d = cfg["chunk_size"], cfg["num_key_value_heads"], work.head_dim(cfg)
    layers = cfg["num_hidden_layers"]
    return {"flops": chunks * layers * 8 * c * h * d,
            "bytes": chunks * layers * (c + 1) * work.entry_bytes(cfg, dtype)}
