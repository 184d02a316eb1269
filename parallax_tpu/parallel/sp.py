"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

The reference has no SP/CP (SURVEY.md section 2.7 marks it absent and the
build brief makes it first-class here): long-context prefill shards the
*sequence* across chips — each device holds a Q/K/V chunk, K/V blocks
rotate around the ring via ``jax.lax.ppermute`` (XLA lowers it onto ICI),
and flash-style online-softmax accumulation keeps memory at O(chunk)
regardless of total sequence length.

Causality is handled by absolute positions, so the same kernel covers
full prefill, chunked prefill continuation, and cached-prefix extension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn(q, k, v, q_pos, kv_pos, sm_scale, m, l, o):
    """One flash accumulation step: q attends one K/V block.

    q: [Tq, Hkv, G, D]; k/v: [Tk, Hkv, D]; m/l: [Tq, Hkv, G]; o like q.
    """
    s = jnp.einsum(
        "thgd,khd->thgk", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    mask = kv_pos[None, :] <= q_pos[:, None]          # causal by position
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exp(NEG_INF - NEG_INF) guard: rows with nothing visible yet.
    scale_prev = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    l_new = l * scale_prev + jnp.sum(p, axis=-1)
    o_new = o * scale_prev[..., None] + jnp.einsum(
        "thgk,khd->thgd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, q_pos, kv_pos, *, axis_name, sm_scale, sp):
    """Per-device body under shard_map: rotate K/V around the ring."""
    tq, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(tq, hkv, g, d)

    perm = [(j, (j + 1) % sp) for j in range(sp)]

    m = jnp.full((tq, hkv, g), NEG_INF, jnp.float32)
    l = jnp.zeros((tq, hkv, g), jnp.float32)
    o = jnp.zeros((tq, hkv, g, d), jnp.float32)

    # sp is the static mesh extent: unroll so the final (dead) rotation is
    # skipped — only sp-1 ring hops of K/V traffic.
    k_cur, v_cur, pos_cur = k, v, kv_pos
    for step in range(sp):
        m, l, o = _block_attn(
            qg, k_cur, v_cur, q_pos, pos_cur, sm_scale, m, l, o
        )
        if step < sp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
            pos_cur = jax.lax.ppermute(pos_cur, axis_name, perm)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(tq, hq, d).astype(q.dtype)


# Public alias: the per-device ring body, for callers ALREADY inside a
# shard_map whose mesh carries the "sp" axis.
ring_attention_local = _ring_attention_local


def context_blocks_attention_local(
    q_l, k_full, v_full, q_pos_l, kv_pos_full, *, sm_scale, sp
):
    """Per-device flash attention of a LOCAL query block against FULL
    K/V, iterated over ``sp`` static chunks (SP x TP composition —
    layers.paged_attention_block). Inside the TP stage's shard_map every
    rank already holds the full (sp-replicated) K/V, so rotating blocks
    over ICI like the ring does would be pure communication overhead;
    the same online-softmax accumulation runs over local slices
    instead. Score memory stays O(T/sp * chunk) per rank."""
    tq, hq, d = q_l.shape
    hkv = k_full.shape[1]
    g = hq // hkv
    qg = q_l.reshape(tq, hkv, g, d)
    chunk = k_full.shape[0] // sp

    m = jnp.full((tq, hkv, g), NEG_INF, jnp.float32)
    l = jnp.zeros((tq, hkv, g), jnp.float32)
    o = jnp.zeros((tq, hkv, g, d), jnp.float32)
    for step in range(sp):
        sl = slice(step * chunk, (step + 1) * chunk)
        m, l, o = _block_attn(
            qg, k_full[sl], v_full[sl], q_pos_l, kv_pos_full[sl],
            sm_scale, m, l, o,
        )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(tq, hq, d).astype(q_l.dtype)


def ring_attention(
    mesh: Mesh,
    q: jax.Array,           # [T, Hq, D] global (padded to sp multiple)
    k: jax.Array,           # [T, Hkv, D]
    v: jax.Array,           # [T, Hkv, D]
    positions: jax.Array,   # i32[T] absolute positions (padding -> -1)
    *,
    sm_scale: float,
    axis_name: str = "sp",
) -> jax.Array:
    """Causal self-attention with the sequence sharded over ``axis_name``.

    Padding rows must carry position ``-1``: they mask out as keys
    (``-1 <= q_pos`` is true — so padding keys are excluded by giving them
    position ``2**30`` internally) and produce garbage outputs that the
    caller discards.
    """
    sp = mesh.shape[axis_name]
    t = q.shape[0]
    if t % sp:
        raise ValueError(f"sequence {t} not divisible by sp={sp}")

    # Padding keys must never be visible.
    kv_positions = jnp.where(positions < 0, jnp.int32(2**30), positions)

    fn = jax.shard_map(
        functools.partial(
            _ring_attention_local, axis_name=axis_name, sm_scale=sm_scale,
            sp=sp,
        ),
        mesh=mesh,
        in_specs=(
            P(axis_name), P(axis_name), P(axis_name), P(axis_name),
            P(axis_name),
        ),
        out_specs=P(axis_name),
        check_vma=False,
    )
    return fn(q, k, v, positions, kv_positions)


def sp_eligible(config) -> bool:
    """Can this model take the ring-attention prefill path at all?
    Mirrors ``StageEngine._model_supports_sp`` at config level, including
    the class-level ``_attention`` override check (e.g. MiniMax-M2
    overrides it despite a plain-attention config). Launchers use this to
    avoid carving an sp mesh axis a model can never use."""
    from parallax_tpu.config import LAYER_ATTENTION
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.models.registry import get_model_class

    if config.is_mla or config.use_attention_sinks:
        return False
    if (
        config.linear_attn is not None
        or config.dsa is not None
        or config.msa is not None
        or config.eva is not None
    ):
        return False
    if get_model_class(config.architecture)._attention is not (
        StageModel._attention
    ):
        return False
    return all(
        config.layer_type(i) == LAYER_ATTENTION
        for i in range(config.num_hidden_layers)
    )


def dense_causal_reference(q, k, v, positions, sm_scale):
    """Unsharded reference with identical semantics (tests)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(t, hkv, g, d)
    s = jnp.einsum("thgd,khd->thgk", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    kv_pos = jnp.where(positions < 0, jnp.int32(2**30), positions)
    mask = kv_pos[None, :] <= positions[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    o = jnp.einsum("thgk,khd->thgd", p, v.astype(jnp.float32))
    return o.reshape(t, hq, d).astype(q.dtype)
