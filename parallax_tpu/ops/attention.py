"""Ragged paged attention: the single attention op for prefill, chunked
prefill, decode, and mixed batches.

Semantics match the reference's paged attention suite
(``src/parallax_extensions/ops.py:517-591`` decode kernel +
``src/parallax/utils/prefix_cache_utils.py`` prefix-aware prefill), unified
the TPU way: queries for *all* sequences in the step are flattened into one
``[num_tokens, num_q_heads, head_dim]`` array, keys/values are always read
from the paged cache (so prefix-cache hits and chunked prefill need no
special path — earlier tokens are simply already in the cache).

On TPU this dispatches to the Pallas flash kernel
(`jax.experimental.pallas.ops.tpu.ragged_paged_attention`); elsewhere (CPU
tests, debugging) to a jittable vectorized XLA fallback with identical
semantics, including GQA, sliding windows, logit soft cap and attention
sinks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from parallax_tpu.ops.ragged import page_chunks, ragged_token_positions

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# The bundled kernel double-buffers ``num_kv_pages_per_block`` whole KV
# pages in VMEM. Its own default (128 pages, taken when its tuning table
# has no entry for the geometry) wants 33 MB at Qwen2.5-7B's 64-token
# pages against the 16 MB a v5e kernel may scope, so the block sizes are
# derived here from the shapes: as many pages as fit this budget.
_RPA_KV_BUFFER_BYTES = 4 << 20
_RPA_QUERIES_PER_BLOCK = 32


def _rpa_block_sizes(q: jax.Array, kv_pages: jax.Array,
                     pages_per_seq: int) -> dict:
    _, page_size, combined, head_dim = kv_pages.shape
    itemsize = kv_pages.dtype.itemsize
    # VMEM pads the combined-head dim to a whole sublane tile.
    sublanes = 32 // itemsize
    padded = -(-combined // sublanes) * sublanes
    page_bytes = page_size * padded * head_dim * itemsize
    return {
        "num_kv_pages_per_block": max(
            1, min(pages_per_seq, _RPA_KV_BUFFER_BYTES // (2 * page_bytes))
        ),
        "num_queries_per_block": min(q.shape[0], _RPA_QUERIES_PER_BLOCK),
    }


# Kernel-choice policy (TPU detection, use_pallas resolution, fused-mode
# resolution) lives in ops/kernel_select.py — the single helper the old
# per-file `_tpu_available()` copies collapsed into.


def append_and_attend(
    q: jax.Array,             # [T, num_q_heads, head_dim]
    k: jax.Array,             # [T, num_kv_heads, head_dim] (pre-rope'd)
    v: jax.Array,             # [T, num_kv_heads, head_dim]
    kv_pages: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    slot_mapping: jax.Array,  # i32[T]; < 0 = padding, not written
    *,
    sm_scale: float = 1.0,
    sliding_window: int | None = None,
    soft_cap: float | None = None,
    sinks: jax.Array | None = None,
    use_pallas: bool | None = None,
    decode_only: bool = False,
    decode_fused: bool = False,
    prefill_fused: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Write this step's K/V into the paged cache and attend — the one
    facade every GQA model calls (``models/layers.py`` and the model
    classes with bespoke attention blocks).

    With ``decode_fused`` on a decode-only batch (one query token per
    sequence) this is ONE fused Pallas program per layer: the append is
    a single-row DMA inside the attention kernel
    (``decode_fused_pallas.gqa_fused_decode_pallas``), subsuming the
    separate ``reshape_and_cache`` scatter dispatch. ``prefill_fused``
    does the same for every multi-token ragged shape (prefill, chunked
    prefill, mixed batches, speculative windows) via
    ``prefill_fused_pallas.gqa_fused_prefill_pallas`` — per-row block
    DMAs replace the scatter and the attention streams only valid
    pages. With both off, the split path: scatter, then
    :func:`ragged_paged_attention`. Returns ``(out, kv_pages)``.
    """
    from parallax_tpu.ops.kernel_select import fused_interpret

    if decode_fused and decode_only and q.shape[0] == kv_lens.shape[0]:
        from parallax_tpu.ops.decode_fused_pallas import (
            gqa_fused_decode_pallas,
        )

        return gqa_fused_decode_pallas(
            q, k, v, kv_pages, kv_lens, page_indices, slot_mapping,
            sinks,
            sm_scale=sm_scale, sliding_window=sliding_window,
            soft_cap=soft_cap, use_sinks=sinks is not None,
            interpret=fused_interpret(),
        )
    if prefill_fused:
        from parallax_tpu.ops.prefill_fused_pallas import (
            gqa_fused_prefill_pallas,
        )

        return gqa_fused_prefill_pallas(
            q, k, v, kv_pages, kv_lens, page_indices, cu_q_lens,
            num_seqs, slot_mapping, sinks,
            sm_scale=sm_scale, sliding_window=sliding_window,
            soft_cap=soft_cap, use_sinks=sinks is not None,
            interpret=fused_interpret(),
        )
    from parallax_tpu.ops.kv_cache_ops import reshape_and_cache

    kv_pages = reshape_and_cache(kv_pages, k, v, slot_mapping)
    out = ragged_paged_attention(
        q, kv_pages, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale, sliding_window=sliding_window,
        soft_cap=soft_cap, sinks=sinks, use_pallas=use_pallas,
        decode_only=decode_only,
    )
    return out, kv_pages


def ragged_paged_attention(
    q: jax.Array,
    kv_pages: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    *,
    sm_scale: float = 1.0,
    sliding_window: int | None = None,
    soft_cap: float | None = None,
    sinks: jax.Array | None = None,
    use_pallas: bool | None = None,
    decode_only: bool = False,
) -> jax.Array:
    """Attention over the paged KV cache for a ragged batch of sequences.

    Args:
      q: [T, num_q_heads, head_dim] — all sequences' query tokens, flattened.
      kv_pages: [P, page_size, 2*num_kv_heads, head_dim] paged cache; the
        current step's K/V must already be written (see ``reshape_and_cache``).
      kv_lens: i32[S] total context length per sequence (including this
        step's tokens); entries past ``num_seqs`` ignored.
      page_indices: i32[S, pages_per_seq] page table per sequence.
      cu_q_lens: i32[S+1] cumulative query lengths; seq i owns q rows
        ``[cu_q_lens[i], cu_q_lens[i+1])``.
      num_seqs: i32[1] live sequence count (dynamic — no recompile when the
        batch occupancy changes, only when T/S buckets change).
      sm_scale: softmax scale.
      sliding_window: optional window size (keys older than
        ``pos - window + 1`` are masked).
      soft_cap: optional logit soft cap ``cap * tanh(x / cap)``.
      sinks: optional f32[num_q_heads] attention-sink logits (gpt-oss): one
        extra virtual key per head that joins the softmax but contributes no
        value (reference: ``src/parallax_extensions/ops.py:556-572``).
      use_pallas: force kernel choice; default = TPU availability.

    Returns:
      [T, num_q_heads, head_dim] attention output.
    """
    from parallax_tpu.ops.kernel_select import resolve_use_pallas

    use_pallas = resolve_use_pallas(use_pallas)
    if use_pallas and sinks is not None:
        if decode_only and q.shape[0] == kv_lens.shape[0]:
            # Custom flash decode kernel with sink + window support
            # (the bundled kernel has neither sinks nor our sink-decode
            # contract).
            from parallax_tpu.ops.attention_pallas import (
                gqa_decode_attention_pallas,
            )

            return gqa_decode_attention_pallas(
                q, kv_pages, kv_lens, page_indices, sinks,
                sm_scale=sm_scale, sliding_window=sliding_window,
                use_sinks=True,
            )
        # Prefill with sinks: the fused ragged-prefill kernel handles
        # sinks natively in attend-only mode (the chunk's K/V are
        # already in the cache here), retiring the old warn-once
        # memory-heavy XLA fallback. Off-TPU callers never reach this
        # branch (use_pallas is False) and keep the XLA reference path
        # below — that downgrade is the registered ``prefill_fused``
        # gate (analysis/gates.py).
        from parallax_tpu.ops.kernel_select import fused_interpret
        from parallax_tpu.ops.prefill_fused_pallas import (
            gqa_fused_prefill_pallas,
        )

        out, _ = gqa_fused_prefill_pallas(
            q, None, None, kv_pages, kv_lens, page_indices, cu_q_lens,
            num_seqs,
            jnp.full((q.shape[0],), -1, jnp.int32), sinks,
            sm_scale=sm_scale, sliding_window=sliding_window,
            soft_cap=soft_cap, use_sinks=True,
            interpret=fused_interpret(),
        )
        return out
    if use_pallas and sinks is None:
        from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
            ragged_paged_attention as _pallas_rpa,
        )

        return _pallas_rpa(
            q,
            kv_pages,
            kv_lens,
            page_indices,
            cu_q_lens,
            num_seqs,
            sm_scale=sm_scale,
            sliding_window=sliding_window,
            soft_cap=soft_cap,
            **_rpa_block_sizes(q, kv_pages, page_indices.shape[1]),
        )
    return _ragged_paged_attention_xla(
        q,
        kv_pages,
        kv_lens,
        page_indices,
        cu_q_lens,
        num_seqs,
        sm_scale=sm_scale,
        sliding_window=sliding_window,
        soft_cap=soft_cap,
        sinks=sinks,
    )


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "sliding_window", "soft_cap")
)
def _ragged_paged_attention_xla(
    q: jax.Array,
    kv_pages: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    *,
    sm_scale: float,
    sliding_window: int | None,
    soft_cap: float | None,
    sinks: jax.Array | None,
) -> jax.Array:
    """Jittable pure-XLA path: a ``lax.scan`` over KV page-chunks with
    online-softmax accumulation, so the gather transient is O(T * chunk)
    rather than O(T * context) (long-context safety for the sink/window
    prefill paths that cannot take the bundled Pallas kernel). The sink
    logit joins the softmax at the end — numerically identical to a
    virtual key with no value payload."""
    t, num_q_heads, head_dim = q.shape
    _, page_size, combined, _ = kv_pages.shape
    num_kv_heads = combined // 2
    group = num_q_heads // num_kv_heads
    s, pages_per_seq = page_indices.shape

    # Which sequence does each query token belong to, at what position?
    seq_of_tok, q_pos = ragged_token_positions(kv_lens, cu_q_lens, t, s)
    kv_len_tok = kv_lens[seq_of_tok]

    padded_pages, chunk_pages, lc, num_chunks = page_chunks(
        page_indices, page_size
    )
    qg = q.reshape(t, num_kv_heads, group, head_dim)

    def body(carry, g):
        m, l, o = carry
        pages_g = jax.lax.dynamic_slice_in_dim(
            padded_pages, g * chunk_pages, chunk_pages, axis=1
        )
        rows = kv_pages[pages_g.reshape(-1)].reshape(
            s, lc, combined, head_dim
        )
        k_tok = rows[:, :, 0::2, :][seq_of_tok]      # [T, Lc, Hkv, D]
        v_tok = rows[:, :, 1::2, :][seq_of_tok]
        scores = jnp.einsum(
            "thgd,tlhd->thgl", qg, k_tok,
            preferred_element_type=jnp.float32,
        ) * sm_scale
        if soft_cap is not None:
            scores = soft_cap * jnp.tanh(scores / soft_cap)
        kv_pos = g * lc + jnp.arange(lc, dtype=jnp.int32)
        valid = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, :] < kv_len_tok[:, None]
        )
        if sliding_window is not None:
            valid &= kv_pos[None, :] > q_pos[:, None] - sliding_window
        scores = jnp.where(valid[:, None, None, :], scores, _MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        pz = jnp.exp(scores - m_new[..., None])
        pz = jnp.where(valid[:, None, None, :], pz, 0.0)
        l_new = l * alpha + jnp.sum(pz, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "thgl,tlhd->thgd", pz.astype(v_tok.dtype), v_tok,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, o_new), None

    init = (
        jnp.full((t, num_kv_heads, group), _MASK_VALUE, jnp.float32),
        jnp.zeros((t, num_kv_heads, group), jnp.float32),
        jnp.zeros((t, num_kv_heads, group, head_dim), jnp.float32),
    )
    (m, l, o), _ = jax.lax.scan(
        body, init, jnp.arange(num_chunks, dtype=jnp.int32)
    )
    if sinks is not None:
        sink = sinks.reshape(num_kv_heads, group).astype(jnp.float32)
        l = l + jnp.exp(sink[None] - m)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(t, num_q_heads, head_dim).astype(q.dtype)
