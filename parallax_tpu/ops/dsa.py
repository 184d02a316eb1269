"""DeepSeek Sparse Attention (DSA) ops: lightning indexer + top-k sparse
MLA attention over the compressed latent cache.

Capability parity: reference DSA kernel stack —
``src/parallax_extensions/ops.py:182-367`` (dsa_paged_attention,
dsa_indexer_scores_with_update, dsa_token_indexer_with_update),
``src/parallax_extensions/kernels/dsa/dsa_indexer.metal`` (score formula
``sum_h max(q_h . k, 0) * w_h``), and ``ops.py:124-179``
(store_indexer_cache).

TPU re-design: instead of the reference's dense-mask prefill path plus a
separate sparse decode kernel, one gather-based attention op serves both —
every query row attends to exactly ``index_topk`` gathered latent rows
(sparse rows use their top-k indices, dense rows — where the context fits
inside the top-k budget — use ``iota``), so shapes stay static under jit
and HBM traffic is O(T * K) rather than O(T * context).

Cache layout per DSA layer: the MLA latent cache (``ops/mla.py``) plus an
index-key cache ``[num_pages, page_size, 1, index_head_dim]`` addressed by
the SAME page table and slot mapping.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from parallax_tpu.ops.ragged import (
    SPARSE_CHUNK,
    SPARSE_CHUNK_THRESHOLD,
    page_chunks,
    ragged_token_positions,
)

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_NEG_INF = float("-inf")



def new_index_pages(
    num_pages: int, page_size: int, index_head_dim: int, dtype=jnp.bfloat16
) -> jax.Array:
    """Paged index-key cache (reference DeepSeekSparseCache.indexer_key_cache,
    dsa_cache.py:57-68; key heads == 1 for DeepSeek-V3.2/GLM)."""
    return jnp.zeros((num_pages, page_size, 1, index_head_dim), dtype)


def store_index_cache(
    cache: jax.Array,       # [P, page, 1, D_idx]
    k: jax.Array,           # [T, D_idx]
    slot_mapping: jax.Array,
) -> jax.Array:
    """Scatter index keys (reference store_indexer_cache, ops.py:124-179)."""
    p, page, _, d = cache.shape
    flat = cache.reshape(p * page, d)
    slots = jnp.where(slot_mapping < 0, p * page, slot_mapping)
    flat = flat.at[slots].set(k.astype(cache.dtype), mode="drop")
    return flat.reshape(p, page, 1, d)


def dsa_store_and_score(
    q: jax.Array,             # [T, Hi, D_idx]
    weights: jax.Array,       # f32[T, Hi]
    k_new: jax.Array,         # [T, D_idx] this step's index key
    index_cache: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    slot_mapping: jax.Array,
    *,
    decode_only: bool = False,
    use_pallas: bool | None = None,
    decode_fused: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Write this step's index key into the paged index cache and score
    the full context — the indexer twin of
    ``ops/attention.append_and_attend``. With ``decode_fused`` on a
    decode-only batch the key append rides inside the fused streaming
    scorer (``decode_fused_pallas.indexer_scores_fused_pallas``);
    otherwise the split path scatters (:func:`store_index_cache`) then
    dispatches :func:`dsa_indexer_scores`. Returns
    ``(scores, index_cache)``."""
    if decode_only and decode_fused and q.shape[0] == kv_lens.shape[0]:
        from parallax_tpu.ops.decode_fused_pallas import (
            indexer_scores_fused_pallas,
        )
        from parallax_tpu.ops.kernel_select import fused_interpret

        return indexer_scores_fused_pallas(
            q, weights, k_new, index_cache, kv_lens, page_indices,
            slot_mapping, reduce_kind="dsa",
            interpret=fused_interpret(),
        )
    index_cache = store_index_cache(index_cache, k_new, slot_mapping)
    scores = dsa_indexer_scores(
        q, weights, index_cache, kv_lens, page_indices, cu_q_lens,
        decode_only=decode_only, use_pallas=use_pallas,
    )
    return scores, index_cache


def dsa_indexer_scores(
    q: jax.Array,
    weights: jax.Array,
    index_cache: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    *,
    decode_only: bool = False,
    use_pallas: bool | None = None,
) -> jax.Array:
    """Indexer-score dispatcher: the Pallas page-streaming kernel on TPU
    for decode-only batches (one query per sequence), the chunked XLA
    path otherwise (prefill / CPU / oracle)."""
    from parallax_tpu.ops.kernel_select import resolve_use_pallas

    use_pallas = resolve_use_pallas(use_pallas)
    if decode_only and use_pallas and q.shape[0] == kv_lens.shape[0]:
        from parallax_tpu.ops.dsa_pallas import (
            dsa_indexer_scores_decode_pallas,
        )

        return dsa_indexer_scores_decode_pallas(
            q, weights, index_cache, kv_lens, page_indices
        )
    return dsa_indexer_scores_xla(
        q, weights, index_cache, kv_lens, page_indices, cu_q_lens
    )


@functools.partial(jax.jit, static_argnames=())
def dsa_indexer_scores_xla(
    q: jax.Array,            # [T, Hi, D_idx] rope-applied index queries
    weights: jax.Array,      # f32[T, Hi] head weights (already scaled)
    index_cache: jax.Array,  # [P, page, 1, D_idx]
    kv_lens: jax.Array,      # i32[S]
    page_indices: jax.Array, # i32[S, pages_per_seq]
    cu_q_lens: jax.Array,    # i32[S+1]
) -> jax.Array:
    """Per-token indexer scores over the cached context: [T, kv_cap] f32.

    score[t, s] = sum_h weights[t, h] * relu(q[t, h] . k[s]); -inf outside
    the causal context (reference dsa_indexer.metal:100-115).
    """
    t, hi, d = q.shape
    p, page_size, _, _ = index_cache.shape
    s, pages_per_seq = page_indices.shape
    kv_cap = pages_per_seq * page_size

    seq_of_tok, q_pos = ragged_token_positions(kv_lens, cu_q_lens, t, s)
    kv_len_tok = kv_lens[seq_of_tok]
    w = weights.astype(jnp.float32)

    # Chunk the per-head [T, Hi, Lc] intermediate over page groups so the
    # transient is O(T * Hi * chunk), never O(T * Hi * context); the full
    # (much smaller) [T, context] score matrix is the output either way.
    padded_pages, chunk_pages, lc, num_chunks = page_chunks(
        page_indices, page_size
    )

    def body(_, g):
        pages_g = jax.lax.dynamic_slice_in_dim(
            padded_pages, g * chunk_pages, chunk_pages, axis=1
        )
        keys = index_cache[pages_g.reshape(-1), :, 0, :].reshape(s, lc, d)
        keys_tok = keys[seq_of_tok]                  # [T, Lc, D]
        dots = jnp.einsum(
            "thd,tld->thl", q, keys_tok, preferred_element_type=jnp.float32
        )
        sc = jnp.einsum("th,thl->tl", w, jnp.maximum(dots, 0.0))
        kv_pos = g * lc + jnp.arange(lc, dtype=jnp.int32)
        valid = (kv_pos[None, :] <= q_pos[:, None]) & (
            kv_pos[None, :] < kv_len_tok[:, None]
        )
        return None, jnp.where(valid, sc, _NEG_INF)

    _, chunks = jax.lax.scan(
        body, None, jnp.arange(num_chunks, dtype=jnp.int32)
    )                                                # [G, T, Lc]
    scores = jnp.transpose(chunks, (1, 0, 2)).reshape(t, num_chunks * lc)
    return scores[:, :kv_cap]


@functools.partial(jax.jit, static_argnames=("index_topk",))
def dsa_topk_indices(
    scores: jax.Array,   # f32[T, kv_cap] (-inf outside context)
    *,
    index_topk: int,
) -> jax.Array:
    """Top-k token positions per query row: i32[T, K].

    Rows whose valid-token count fits within the top-k budget are marked
    dense with all -1 (reference dsa_token_indexer_with_update,
    ops.py:345-367) — the attention op then covers positions 0..K-1, which
    is the whole context for those rows.
    """
    t, kv_cap = scores.shape
    k = min(index_topk, kv_cap)
    _, idx = jax.lax.top_k(scores, k)
    idx = idx.astype(jnp.int32)
    if k < index_topk:
        idx = jnp.concatenate(
            [idx, jnp.full((t, index_topk - k), -1, jnp.int32)], axis=-1
        )
    valid_count = jnp.sum(scores > _NEG_INF, axis=-1)
    dense = valid_count <= index_topk
    return jnp.where(dense[:, None], jnp.int32(-1), idx)




@functools.partial(jax.jit, static_argnames=("sm_scale", "kv_lora_rank"))
def mla_ragged_sparse_attention_xla(
    q_latent: jax.Array,     # [T, Hq, R]
    q_pe: jax.Array,         # [T, Hq, Dr]
    cache: jax.Array,        # [P, page, W >= R + Dr] MLA latent cache
    kv_lens: jax.Array,      # i32[S]
    page_indices: jax.Array, # i32[S, pages_per_seq]
    cu_q_lens: jax.Array,    # i32[S+1]
    topk_indices: jax.Array, # i32[T, K] logical positions; row of -1 = dense
    *,
    sm_scale: float,
    kv_lora_rank: int,
) -> jax.Array:
    """Sparse absorbed-MLA attention: each query row attends to its top-k
    latent positions only. Returns [T, Hq, R].

    Reference contract: dsa_paged_attention (ops.py:182-245,
    kernels/dsa/dsa_paged_attention.metal) — softmax(scale * (q_latent .
    latent^T + q_pe . rope^T)) . latent over ``topk_indices``; a -1-leading
    row attends densely over range(context), which here is covered by
    substituting iota for the indices (dense rows only occur when the
    context fits in K). Large K runs the chunked online-softmax variant
    (O(T * chunk) transients); small K a single pass.
    """
    t, hq, r = q_latent.shape
    p, page_size, width = cache.shape
    dr = q_pe.shape[-1]
    s, pages_per_seq = page_indices.shape
    k = topk_indices.shape[1]

    seq_of_tok, q_pos = ragged_token_positions(kv_lens, cu_q_lens, t, s)

    dense_row = topk_indices[:, 0] < 0
    iota = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int32), (t, k))
    pos = jnp.where(dense_row[:, None], iota, topk_indices)  # [T, K]

    # Validity: inside this row's causal context and a real (>=0) index.
    valid = (pos >= 0) & (pos <= q_pos[:, None]) & (
        pos < kv_lens[seq_of_tok][:, None]
    )
    safe_pos = jnp.where(valid, pos, 0)

    # Logical position -> physical slot via the per-sequence page table.
    page_of = safe_pos // page_size                       # [T, K]
    offset = safe_pos % page_size
    phys_page = jnp.take_along_axis(
        page_indices[seq_of_tok], page_of, axis=1
    )                                                     # [T, K]
    flat_rows = phys_page * page_size + offset            # [T, K]
    flat_cache = cache.reshape(p * page_size, width)

    def score_block(rows_blk, valid_blk):
        """[T, Kc, R+Dr] gathered block -> masked f32 scores [T, Hq, Kc]."""
        latent = rows_blk[..., :kv_lora_rank]
        rope = rows_blk[..., kv_lora_rank:kv_lora_rank + dr]
        sc = (
            jnp.einsum("thr,tkr->thk", q_latent, latent,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("thd,tkd->thk", q_pe, rope,
                         preferred_element_type=jnp.float32)
        ) * sm_scale
        return jnp.where(valid_blk[:, None, :], sc, _MASK_VALUE), latent

    if k <= SPARSE_CHUNK_THRESHOLD:
        rows = flat_cache[flat_rows]                      # [T, K, R+Dr]
        scores, latent = score_block(rows, valid)
        m = jnp.max(scores, axis=-1, keepdims=True)
        unnorm = jnp.exp(scores - m)
        probs = unnorm / jnp.maximum(
            jnp.sum(unnorm, axis=-1, keepdims=True), 1e-30
        )
        out = jnp.einsum("thk,tkr->thr", probs.astype(latent.dtype), latent,
                         preferred_element_type=jnp.float32)
        return out.astype(q_latent.dtype)

    # Chunked online softmax over K (flash-style accumulation).
    chunk = SPARSE_CHUNK
    num_chunks = -(-k // chunk)
    pad = num_chunks * chunk - k
    if pad:
        flat_rows = jnp.pad(flat_rows, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))

    def body(carry, c):
        m_run, l_run, acc = carry
        rows_c = jax.lax.dynamic_slice_in_dim(flat_rows, c * chunk, chunk, 1)
        valid_c = jax.lax.dynamic_slice_in_dim(valid, c * chunk, chunk, 1)
        blk = flat_cache[rows_c]                          # [T, Kc, R+Dr]
        sc, latent = score_block(blk, valid_c)            # [T, Hq, Kc]
        m_new = jnp.maximum(m_run, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p_blk = jnp.exp(sc - m_new)
        l_new = l_run * alpha + jnp.sum(p_blk, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "thk,tkr->thr", p_blk.astype(latent.dtype), latent,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc), None

    init = (
        jnp.full((t, hq, 1), _NEG_INF, jnp.float32),
        jnp.zeros((t, hq, 1), jnp.float32),
        jnp.zeros((t, hq, kv_lora_rank), jnp.float32),
    )
    (m_run, l_run, acc), _ = jax.lax.scan(
        body, init, jnp.arange(num_chunks, dtype=jnp.int32)
    )
    out = acc / jnp.maximum(l_run, 1e-30)
    return out.astype(q_latent.dtype)
