"""Ouro's work file (``bench.work`` of ``configs/ouro-2.6b.json``): what
a decode step of a looped stack needs, from shapes.

Beside ``harness/work.py`` and under its rules: counts are of the
mathematics on real tokens, not of what a kernel touches. The model
applies its ``num_hidden_layers`` blocks ``total_ut_steps`` times a
token with the same weights every pass, and pass ``u`` layer ``l``
attends cache layer ``u * num_hidden_layers + l``. So ``layers`` lists
*applications*, ``total_ut_steps x num_hidden_layers`` of them, not
weight layers:

- the weights of all 48 layers (5 GB) cannot wait on the chip for the
  next pass, so every application streams its layer's elements again:
  ``always`` is the whole layer every time;
- every application holds pages of its own: an entry is K and V of the
  16 KV heads in one cache layer, and a row's step attends its context
  in all 192.

A block is the dense one but for two more norm vectors (the sandwich
norms on the two branches). The model's final norm closes every pass:
the last pass's is ``head_elements``' (with one head matrix, once a
step), the earlier passes' ``hidden_size`` elements each are counted in
their pass's last application. The exit gate (``hidden_size + 1``
elements) is held and never read while ``early_exit_threshold`` is 1:
it is not counted.
"""

from __future__ import annotations

from benchmarks.harness import work

DECODE_KERNEL = "^gqa_fused_decode_pallas"
DECODE_PROGRAM = r"^jit_fn\("


def passes(cfg: dict) -> int:
    return int(cfg.get("total_ut_steps") or 1)


def block_elements(cfg: dict) -> int:
    """One block: q, k, v and o, the gated MLP's three matrices, four
    norm vectors; no bias anywhere."""
    h, d = cfg["hidden_size"], work.head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return (h * (q + 2 * kv) + q * h + 3 * h * cfg["intermediate_size"]
            + 4 * h)


def application(cfg: dict, closes_pass: bool) -> dict:
    """One block applied once to a step's rows; ``closes_pass``: the
    final norm that closes a pass other than the last is read after it."""
    hq, d = cfg["num_attention_heads"], work.head_dim(cfg)
    return {"always": (block_elements(cfg)
                       + (cfg["hidden_size"] if closes_pass else 0)),
            "entry_bytes": work.entry_bytes(cfg),
            "entry_flops": 4 * hq * d,
            "row_bytes": 2 * hq * d * work.BYTES["bfloat16"]}


def layers(cfg: dict) -> list[dict]:
    u_all, l_all = passes(cfg), cfg["num_hidden_layers"]
    return [application(cfg, l == l_all - 1 and u < u_all - 1)
            for u in range(u_all) for l in range(l_all)]


head_elements = work.dense_head_elements


def kv_bytes_per_token(cfg: dict) -> int:
    """What one cached token holds over all cache layers."""
    return passes(cfg) * cfg["num_hidden_layers"] * work.entry_bytes(cfg)
