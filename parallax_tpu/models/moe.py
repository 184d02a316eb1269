"""Mixture-of-experts FFN: megablox grouped matmul on TPU, with expert
parallelism over the ``tp`` mesh axis.

Capability parity: reference MoE models run experts via mlx-lm SwitchGLU
inside a stage (SURVEY.md section 2.7 marks cross-node EP absent; expert
sharding over ICI is the TPU-native equivalent it prescribes). Params hold
the experts this stage holds *stacked*: ``experts.gate_proj/up_proj:
[E_held, I, H]``, ``experts.down_proj: [E_held, H, I]`` — the loader stacks
per-expert HF weights at load time, and EP shards the leading expert dim.

**The share.** A layer is told which of the ``num_experts`` routed experts
it holds: ``MoEConfig.experts_held`` of them from ``expert_offset`` (all
from 0 by default; on a mesh each shard's offset moves on by its
``axis_index``). The router is ``num_experts`` wide and selects
``num_experts_per_tok`` whatever is held; of a token's selected experts
only those held here are computed and weighted in. What the absent ones
would add is *not computed and not stood in for*: on a mesh the shards'
parts meet in the ``psum``, on one chip of a larger deployment the
partial sum is the layer's output (the plain reference is given the same
share: ``benchmarks/references/axk1.py``). The shared expert is whole on
every chip.

Two compute paths with identical semantics:
- ``megablox``: the pairs on held experts sorted first, by expert; one
  ``gmm`` per projection over those groups; every other pair lies past
  the last group, where ``gmm`` neither reads nor writes; a token's rows
  gathered back and summed (``_combine``). TPU only.
- fallback: static loop over the held experts with masked matmuls — used
  on CPU and for verification.

Counts (``count_rows``): the distinct held experts the counted rows hit
and the token-expert pairs landed on them, for the engine's
``parallax_moe_experts_read`` / ``parallax_moe_pairs_held``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from parallax_tpu.config import MoEConfig
from parallax_tpu.models.layers import linear


def route_topk(
    x: jax.Array,
    router_weight: jax.Array,
    moe: MoEConfig,
    bias: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Router: returns (weights f32[T, K], expert_ids i32[T, K]).

    DeepSeek-V3 extras: ``bias`` (e_score_correction_bias) shifts the
    *selection* scores only — gate weights come from the unbiased scores —
    and ``n_group``/``topk_group`` restrict selection to the best expert
    groups (group score = sum of each group's top-2 biased scores).
    ``topk_method`` "none" (A.X-K1) or "greedy" is the plain top-k over
    every expert, whatever ``n_group`` / ``topk_group`` say.
    """
    logits = jax.lax.dot_general(
        x, router_weight,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if moe.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)

    selection = scores if bias is None else scores + bias.astype(jnp.float32)
    grouped = moe.topk_method not in ("none", "greedy")
    if grouped and moe.n_group > 1 and moe.topk_group > 0:
        t, e = selection.shape
        per_group = selection.reshape(t, moe.n_group, e // moe.n_group)
        if moe.topk_method == "group_limited_greedy":
            # DeepSeek-V2: a group scores as its best expert.
            group_score = jnp.max(per_group, axis=-1)
        else:
            # DeepSeek-V3 noaux_tc: sum of each group's top-2 biased scores.
            group_score = jnp.sum(
                jax.lax.top_k(per_group, min(2, e // moe.n_group))[0], axis=-1
            )
        _, top_groups = jax.lax.top_k(group_score, moe.topk_group)
        group_mask = jnp.zeros((t, moe.n_group), bool).at[
            jnp.arange(t)[:, None], top_groups
        ].set(True)
        mask = jnp.repeat(group_mask, e // moe.n_group, axis=-1)
        selection = jnp.where(mask, selection, -jnp.inf)

    _, ids = jax.lax.top_k(selection, moe.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    if moe.norm_topk_prob:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-20
        )
    weights = weights * moe.routed_scaling_factor
    return weights.astype(jnp.float32), ids.astype(jnp.int32)


def _silu_glu(g, u):
    return jax.nn.silu(g) * u


def _expert_ffn(x, gate_w, up_w, down_w, act_fn=_silu_glu):
    """GLU for one expert's weight slices ([I,H],[I,H],[H,I]); ``act_fn(g,
    u)`` defaults to SwiGLU (MiniMax-M3 passes its clamped swiglu-oai)."""
    g = jnp.einsum("th,ih->ti", x, gate_w, preferred_element_type=jnp.float32)
    u = jnp.einsum("th,ih->ti", x, up_w, preferred_element_type=jnp.float32)
    h = act_fn(g, u).astype(x.dtype)
    return jnp.einsum("ti,hi->th", h, down_w, preferred_element_type=jnp.float32)


def _stacked_expert_weights(experts: dict):
    """Stacked [E, I, H]/[E, H, I] expert tensors, dequantizing quantized
    entries (dicts produced by ops/quant.py) on the fly."""
    def get(name):
        w = experts[name]
        if isinstance(w, dict):
            from parallax_tpu.ops.quant import dequantize_weight

            return dequantize_weight(w)
        return w

    return get("gate_proj"), get("up_proj"), get("down_proj")


def _moe_fallback(x, p, weights, local_ids, num_local, act_fn=_silu_glu):
    """Masked loop over the held experts; correct for any routing, O(E)
    matmuls."""
    t = x.shape[0]
    out = jnp.zeros((t, x.shape[1]), jnp.float32)
    gate_w, up_w, down_w = _stacked_expert_weights(p["experts"])
    for le in range(num_local):
        hit = local_ids == le                     # [T, K]
        w = jnp.sum(jnp.where(hit, weights, 0.0), axis=-1)  # [T]
        y = _expert_ffn(x, gate_w[le], up_w[le], down_w[le], act_fn)
        out = out + y * w[:, None]
    return out


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``gmm``'s (m, k, n) tile: rows in the largest power of two up to
    128 that divides the pairs (8 rows x 8 experts a token are 64), the
    whole contraction up to 8,192 and as many output columns as keep a
    weight tile near 4 MB. The default (128, 128, 128) moves an expert's
    matrix in 32 KB tiles: 4.9 ms for 12 experts' 7168 x 2048 on a v5e
    against 1.07-1.12 ms at these (host clock a call, ~0.5 ms of
    dispatch in each; inside the A.X-K1 cell's step program a layer's
    three calls take 1.40 ms, 92% of the held experts' stream; PERF.md,
    PR 51)."""
    tk = min(k, 8192)
    tn = max(128, min(n, (1 << 21) // tk // 128 * 128))
    return math.gcd(m, 128), tk, tn


def _combine(y, pos, weights, held):
    """Pairs back to tokens: ``out[t]`` = the float32 sum over ``k`` of
    ``y[pos[t, k]] * weights[t, k]`` where ``held[t, k]``. ``y`` has one
    row a token-expert pair in expert order, ``pos`` [T, K] says where a
    token's pairs lie in it. One gather of the rows, laid ``[K, T, H]``
    so that the sum adds K whole ``[T, H]`` slabs: every row's work is
    independent, where a scatter-add applies its T*K updates one after
    another (0.25 us each on a v5e: 258 us an A.X-K1 layer, where the
    gather takes 21 and the sum 8; summed over the middle axis of
    ``[T, K, H]`` it takes 45; PERF.md, PR 53). A pair on an expert not
    held lies past ``gmm``'s last group in a row never written: selected
    out, not scaled."""
    rows = y[pos.T] * weights.T[..., None]            # [K, T, H]
    return jnp.sum(jnp.where(held.T[..., None], rows, 0.0), axis=0)


def _moe_megablox(x, p, weights, local_ids, num_local, act_fn=_silu_glu):
    """Grouped-matmul path. ``local_ids`` [T, K]: a pair's expert as an
    index into the held stack, ``num_local`` for a pair on an expert
    that is not held. Held pairs sort first, by expert, and are the
    groups; the others lie past the last group, outside every ``gmm``."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    t, h = x.shape
    k = local_ids.shape[1]
    with jax.named_scope("moe_dispatch"):
        flat_ids = local_ids.reshape(-1)              # [T*K]
        order = jnp.argsort(flat_ids)
        xs = x[order // k]                            # [T*K, H] gathered rows
        group_sizes = jnp.bincount(
            flat_ids, length=num_local + 1
        )[:num_local].astype(jnp.int32)

    gate_w, up_w, down_w = _stacked_expert_weights(p["experts"])
    inter = gate_w.shape[1]
    with jax.named_scope("moe_experts"):
        # The stacks are [E, out, in]: gmm contracts the last axis.
        up = functools.partial(
            gmm, group_sizes=group_sizes, transpose_rhs=True,
            tiling=_gmm_tiling(t * k, h, inter),
        )
        hme = act_fn(up(xs, gate_w), up(xs, up_w)).astype(x.dtype)
        y = gmm(hme, down_w, group_sizes, transpose_rhs=True,
                tiling=_gmm_tiling(t * k, inter, h))  # [T*K, H]

    with jax.named_scope("moe_combine"):
        # ``order``'s inverse: pair (t, k) lies at row pos[t, k] of y.
        pos = jnp.argsort(order).reshape(t, k)
        return _combine(y, pos, weights, local_ids < num_local)


def held_counts(local_ids, num_local: int, count_rows) -> jax.Array:
    """``i32[2]``: the distinct held experts the rows of ``count_rows``
    (bool[T]) hit, and their pairs that landed on held experts."""
    ids = jnp.where(count_rows[:, None], local_ids, num_local)
    per_expert = jnp.bincount(ids.reshape(-1), length=num_local + 1)
    per_expert = per_expert[:num_local]
    return jnp.stack(
        [jnp.count_nonzero(per_expert), jnp.sum(per_expert)]
    ).astype(jnp.int32)


def moe_ffn(
    x: jax.Array,
    p: dict,
    moe: MoEConfig,
    axis_name: str | None = None,
    use_megablox: bool | None = None,
    act_fn=_silu_glu,
    count_rows: jax.Array | None = None,
):
    """Full MoE block: route over every expert, compute the held experts'
    part (+ optional shared experts), psum over the expert-parallel
    axis. With ``count_rows`` (bool[T]: the rows that are live decode
    rows) returns ``(out, held_counts)``, summed over the axis too."""
    if use_megablox is None:
        use_megablox = jax.default_backend() == "tpu"

    with jax.named_scope("moe_dispatch"):
        bias = p["gate"].get("e_score_correction_bias")
        weights, ids = route_topk(x, p["gate"]["weight"], moe, bias=bias)
        gp = p["experts"]["gate_proj"]
        num_local = (gp["qweight"] if isinstance(gp, dict) else gp).shape[0]
        # One rule: the configured share, moved on by the shard's index.
        expert_offset = moe.expert_offset
        if axis_name is not None:
            expert_offset += jax.lax.axis_index(axis_name) * num_local
        local_ids = ids - expert_offset
        local_ids = jnp.where(
            (local_ids >= 0) & (local_ids < num_local), local_ids, num_local
        )

    impl = _moe_megablox if use_megablox else _moe_fallback
    out = impl(x, p, weights, local_ids, num_local, act_fn)

    if "shared_expert" in p:
        # Shared expert uses the standard column/row TP sharding, so its
        # partial output joins the routed experts' psum.
        from parallax_tpu.models.layers import get_weight

        shared = _expert_ffn(
            x,
            get_weight(p["shared_expert"]["gate_proj"]),
            get_weight(p["shared_expert"]["up_proj"]),
            get_weight(p["shared_expert"]["down_proj"]),
            act_fn,
        )
        if "shared_expert_gate" in p:
            sg = jax.nn.sigmoid(
                linear(x, p["shared_expert_gate"]).astype(jnp.float32)
            )
            shared = shared * sg
        out = out + shared

    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    out = out.astype(x.dtype)
    if count_rows is None:
        return out
    counts = held_counts(local_ids, num_local, count_rows)
    if axis_name is not None:
        counts = jax.lax.psum(counts, axis_name)
    return out, counts
