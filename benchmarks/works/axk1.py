"""A.X-K1's work file (``bench.work`` of ``configs/a.x-k1-ep16-d7.json``):
what a decode step of one chip of an expert-parallel deployment needs,
from shapes.

Beside ``harness/work.py`` and under its rules: counts are of the
mathematics on real tokens, not of what a kernel touches. Every layer
attends through latent attention (MLA): a cached token is one row of
``kv_lora_rank + qk_rope_head_dim`` values, read once for all heads (the
absorbed form: ``W_uk`` folded into the query, ``W_uv`` applied after).
The cache holds each row in whole 128-value lane tiles (640 for 576);
the spare lanes are the chip's and are not counted here. The first
``first_k_dense_replace`` layers' feed-forward is the dense gated MLP;
every other layer has a router over all ``n_routed_experts``, one shared
expert every step reads, and ``experts_held`` routed experts of which a
step reads those its rows hit. ``experts_held`` and ``vocab_size`` are
the chip's share, read from the configuration's own keys.

Here too is what only this architecture has: ``expert_layer_work``, one
expert layer's grouped matmuls as ``gmm`` must do them.
"""

from __future__ import annotations

from benchmarks.harness import work

# The latent decode kernel as a device trace names it (the innermost
# ``jax.jit`` around the ``pallas_call``).
DECODE_KERNEL = "^mla_decode_attention_pallas"
# The grouped matmuls and the one-row latent append, likewise.
GMM_KERNEL = "^gmm"

B = work.BYTES["bfloat16"]


def latent_row(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_elements(cfg: dict) -> int:
    """MLA's five matrices and its two inner norms."""
    h, hq = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return (h * q_rank + q_rank                  # W_qa, its norm
            + q_rank * hq * (nope + rope)        # W_qb
            + h * (rank + rope) + rank           # W_kva, the latent's norm
            + rank * hq * (nope + v)             # W_kvb (W_uk | W_uv)
            + hq * v * h)                        # W_o


def expert_elements(cfg: dict) -> int:
    """One expert, routed or shared: a SwiGLU of ``moe_intermediate_size``."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _attention(cfg: dict) -> dict:
    hq, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    return {
        # One latent row, read once for all heads.
        "entry_bytes": latent_row(cfg) * B,
        # q . [latent ; rope] and P . latent, every head.
        "entry_flops": 2 * hq * (latent_row(cfg) + rank),
        # The absorbed query in and the latent output out.
        "row_bytes": hq * (latent_row(cfg) + rank) * B,
    }


def dense_layer(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"always": (attention_elements(cfg) + 2 * h
                       + 3 * h * cfg["intermediate_size"]),
            **_attention(cfg)}


def routed_layer(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"always": (attention_elements(cfg) + 2 * h
                       + cfg["n_routed_experts"] * h          # the router
                       + cfg["n_shared_experts"] * expert_elements(cfg)),
            "expert": expert_elements(cfg),
            "experts_held": cfg.get("experts_held")
            or cfg["n_routed_experts"],
            "experts_per_token": cfg["num_experts_per_tok"],
            **_attention(cfg)}


def layers(cfg: dict) -> list[dict]:
    return [dense_layer(cfg) if i < cfg["first_k_dense_replace"]
            else routed_layer(cfg)
            for i in range(cfg["num_hidden_layers"])]


# The final norm and the head's rows held here (``vocab_size`` is the
# chip's slice).
head_elements = work.dense_head_elements


def routed_layers(cfg: dict) -> int:
    return sum(1 for l in layers(cfg) if l.get("experts_held"))


def expert_layer_work(cfg: dict, pairs: float, experts_read: float) -> dict:
    """One expert layer's three grouped matmuls over ``pairs``
    token-expert pairs that hit ``experts_read`` distinct held experts:
    each such expert's three matrices read once, a pair's hidden row in
    (twice: gate and up) and out, its intermediate row written and read;
    2 operations an element and pair."""
    h, i = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return {"flops": 2 * expert_elements(cfg) * pairs,
            "bytes": (experts_read * expert_elements(cfg) * B
                      + pairs * (3 * h + 2 * i) * B)}
