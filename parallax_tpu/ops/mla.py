"""Multi-head latent attention (MLA) over a compressed paged cache.

Capability parity: reference MLA kernels
(``src/parallax_extensions/kernels/mla``, facade ``ops.py:73-121``:
softmax(q_latent . latent^T + q_pe . rope^T) . latent) and the DSA latent
cache (``src/parallax/server/cache/dsa_cache.py``).

The cache stores, per token, only the compressed latent (kv_lora_rank) and
the shared rope key (qk_rope_head_dim) — the "absorbed" decode form: W_UK
folds into the query, W_UV applies after attention, so HBM per token is
~R+Dr instead of 2*H*D.

Cache layout per MLA layer:  [num_pages, page_size, W], a token's row
``[latent (R) | rope key (Dr) | unused]`` with ``W = mla_row_width(R,
Dr)``, the row rounded up to whole 128-value lane tiles (576 -> 640).
Why not ``[.., 1, R + Dr]``, measured on a v5e (PERF.md, PR 51): XLA
stores a bf16 array whose last axis is no multiple of 128 with its
*first* axis minor (pages in the lanes), and a singleton second-last
axis is below Mosaic's tile, so every Pallas call over that array was
handed a re-laid copy of the whole cache (1.34 GB a layer call at 8,192
pages). A dense ``[page, W]`` tile is one layout to XLA, to Mosaic and
to a page's DMA; the 64 spare values a row are what the chip's lanes
cost, and ``ModelConfig.kv_bytes_per_token_per_layer`` counts them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from parallax_tpu.ops.ragged import (
    KV_CHUNK_ROWS,
    page_chunks,
    ragged_token_positions,
)

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128
# What one chunk's per-token gather of cache rows may take.
_GATHER_BYTES = 1 << 28


def mla_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Values one cached token's row holds: latent and rope key, rounded
    up to whole lane tiles."""
    return -(-(kv_lora_rank + rope_dim) // _LANES) * _LANES



def new_mla_pages(
    num_pages: int, page_size: int, kv_lora_rank: int, rope_dim: int,
    dtype=jnp.bfloat16,
) -> jax.Array:
    return jnp.zeros(
        (num_pages, page_size, mla_row_width(kv_lora_rank, rope_dim)), dtype
    )


def store_mla_cache(
    cache: jax.Array,
    latent: jax.Array,      # [T, R]
    k_pe: jax.Array,        # [T, Dr]
    slot_mapping: jax.Array,
) -> jax.Array:
    """Scatter latent+rope rows (reference reshape_and_cache DSA variant,
    ops.py:370-413)."""
    p, page, width = cache.shape
    row = mla_cache_rows(latent, k_pe, width, cache.dtype)
    flat = cache.reshape(p * page, width)
    slots = jnp.where(slot_mapping < 0, p * page, slot_mapping)
    flat = flat.at[slots].set(row, mode="drop")
    return flat.reshape(p, page, width)


def mla_cache_rows(latent, k_pe, width: int, dtype) -> jax.Array:
    """``[T, W]`` cache rows: latent, rope key, zeros up to ``width``."""
    row = jnp.concatenate([latent, k_pe], axis=-1).astype(dtype)
    return jnp.pad(row, ((0, 0), (0, width - row.shape[-1])))


def mla_append_and_attend(
    q_latent: jax.Array,      # [T, Hq, R]
    q_pe: jax.Array,          # [T, Hq, Dr]
    latent: jax.Array,        # [T, R] this step's compressed latent
    k_pe: jax.Array,          # [T, Dr] this step's rope key
    cache: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    slot_mapping: jax.Array,
    *,
    sm_scale: float,
    kv_lora_rank: int,
    decode_only: bool = False,
    use_pallas: bool | None = None,
    decode_fused: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Write this step's latent+rope row into the paged cache and attend
    — the MLA twin of ``ops/attention.append_and_attend``. With
    ``decode_fused`` on a decode-only batch the append happens inside
    the fused Pallas program
    (``decode_fused_pallas.mla_fused_decode_pallas``); otherwise the
    split path scatters (:func:`store_mla_cache`) then dispatches
    :func:`mla_ragged_attention`. Returns ``(out, cache)``."""
    if (
        decode_fused
        and decode_only
        and q_latent.shape[0] == kv_lens.shape[0]
    ):
        from parallax_tpu.ops.decode_fused_pallas import (
            mla_fused_decode_pallas,
        )
        from parallax_tpu.ops.kernel_select import fused_interpret

        return mla_fused_decode_pallas(
            q_latent, q_pe, latent, k_pe, cache, kv_lens, page_indices,
            slot_mapping, sm_scale=sm_scale, kv_lora_rank=kv_lora_rank,
            interpret=fused_interpret(),
        )
    cache = store_mla_cache(cache, latent, k_pe, slot_mapping)
    out = mla_ragged_attention(
        q_latent, q_pe, cache, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale, kv_lora_rank=kv_lora_rank,
        decode_only=decode_only, use_pallas=use_pallas,
    )
    return out, cache


def mla_ragged_attention(
    q_latent: jax.Array,
    q_pe: jax.Array,
    cache: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    *,
    sm_scale: float,
    kv_lora_rank: int,
    decode_only: bool = False,
    use_pallas: bool | None = None,
) -> jax.Array:
    """MLA attention dispatcher: the Pallas flash decode kernel on TPU for
    decode-only batches (one query per sequence — reference kernel contract
    ``kernels/mla/mla.cpp``), the XLA gather path otherwise (prefill /
    CPU / oracle)."""
    from parallax_tpu.ops.kernel_select import resolve_use_pallas

    use_pallas = resolve_use_pallas(use_pallas)
    if (
        decode_only
        and use_pallas
        and q_latent.shape[0] == kv_lens.shape[0]
    ):
        from parallax_tpu.ops.mla_pallas import mla_decode_attention_pallas

        return mla_decode_attention_pallas(
            q_latent, q_pe, cache, kv_lens, page_indices,
            sm_scale=sm_scale, kv_lora_rank=kv_lora_rank,
        )
    return mla_ragged_attention_xla(
        q_latent, q_pe, cache, kv_lens, page_indices, cu_q_lens, num_seqs,
        sm_scale=sm_scale, kv_lora_rank=kv_lora_rank,
    )


@functools.partial(jax.jit, static_argnames=("sm_scale", "kv_lora_rank"))
def mla_ragged_attention_xla(
    q_latent: jax.Array,     # [T, Hq, R]   (q_nope absorbed through W_UK)
    q_pe: jax.Array,         # [T, Hq, Dr]
    cache: jax.Array,        # [P, page, W >= R + Dr]
    kv_lens: jax.Array,      # i32[S]
    page_indices: jax.Array, # i32[S, pages_per_seq]
    cu_q_lens: jax.Array,    # i32[S+1]
    num_seqs: jax.Array,     # i32[1]
    *,
    sm_scale: float,
    kv_lora_rank: int,
) -> jax.Array:
    """Returns attention output in latent space: [T, Hq, R].

    The caller up-projects with W_UV. Jittable XLA path; the Pallas flash
    kernel (``ops/mla_pallas.py``) covers decode on TPU. Long contexts run
    a loop over KV page-chunks with online-softmax accumulation so
    the transient footprint is O(T * chunk), never O(T * context) — the
    HBM-safety requirement of the reference MLA kernel contract
    (``kernels/mla/mla.cpp``).
    """
    t, hq, r = q_latent.shape
    p, page_size, width = cache.shape
    dr = q_pe.shape[-1]
    s, pages_per_seq = page_indices.shape
    kv_cap = pages_per_seq * page_size

    seq_of_tok, q_pos = ragged_token_positions(kv_lens, cu_q_lens, t, s)
    kv_len_tok = kv_lens[seq_of_tok]

    # Chunk over whole pages; fall back to a single pass for short caps.
    # A chunk's rows are gathered once a *token* (``rows_tok`` below):
    # at serve's 2,048-token batches 512 rows of 640 values would be
    # 1.3 GB a layer, beside a pool sized to the memory left, so the
    # chunk shrinks with the batch (whole pages, at least one).
    chunk_rows = max(
        page_size,
        min(KV_CHUNK_ROWS, _GATHER_BYTES // (t * width * cache.dtype.itemsize))
        // page_size * page_size,
    )
    padded_pages, chunk_pages, lc, num_chunks = page_chunks(
        page_indices, page_size, chunk_rows
    )

    def body(carry, g):
        m, l, o = carry
        pages_g = jax.lax.dynamic_slice_in_dim(
            padded_pages, g * chunk_pages, chunk_pages, axis=1
        )
        rows = cache[pages_g.reshape(-1)].reshape(s, lc, width)
        rows_tok = rows[seq_of_tok]                  # [T, Lc, width]
        latent = rows_tok[..., :kv_lora_rank]
        rope = rows_tok[..., kv_lora_rank:kv_lora_rank + dr]
        scores = (
            jnp.einsum("thr,tlr->thl", q_latent, latent,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("thd,tld->thl", q_pe, rope,
                         preferred_element_type=jnp.float32)
        ) * sm_scale
        kv_pos = g * lc + jnp.arange(lc, dtype=jnp.int32)
        valid = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, :] < kv_len_tok[:, None]
        )
        scores = jnp.where(valid[:, None, :], scores, _MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        alpha = jnp.exp(m - m_new)
        pz = jnp.exp(scores - m_new[..., None])
        pz = jnp.where(valid[:, None, :], pz, 0.0)
        l_new = l * alpha + jnp.sum(pz, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "thl,tlr->thr", pz.astype(latent.dtype), latent,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, o_new), None

    init = (
        jnp.full((t, hq), _MASK_VALUE, jnp.float32),
        jnp.zeros((t, hq), jnp.float32),
        jnp.zeros((t, hq, r), jnp.float32),
    )
    # Only the chunks some row's context reaches: a chunk past every
    # ``kv_len`` is masked whole and leaves the accumulators as they
    # are, and a page table is as long as ``max_model_len`` whatever
    # the batch (64 chunks of a page for prompts of 256-512: 1.43 s a
    # 2,048-token step on a v5e against 8 of them; PERF.md, PR 51).
    live_chunks = jnp.minimum(
        num_chunks, (jnp.max(kv_lens) + lc - 1) // lc
    )
    m, l, o = jax.lax.fori_loop(
        0, live_chunks, lambda g, carry: body(carry, g)[0], init
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q_latent.dtype)


def mla_rope_permute(x: jax.Array) -> jax.Array:
    """DeepSeek's rope-dim interleave (HF modeling convention): view the
    last dim as [d/2, 2], transpose, flatten — applied to q_pe/k_pe before
    the standard rotate-half rope."""
    *lead, d = x.shape
    return (
        x.reshape(*lead, d // 2, 2).swapaxes(-1, -2).reshape(*lead, d)
    )
