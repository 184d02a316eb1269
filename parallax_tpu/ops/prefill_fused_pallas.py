"""The fused Pallas ragged chunked-prefill kernel — the prefill twin of
``decode_fused_pallas``.

One Pallas program covers one (row, query-chunk) unit of work: the
ragged batch's flattened query tokens are tiled into fixed-size blocks
(a block never spans more rows than the ragged layout dictates — the
per-block sequence span is precomputed host-side and scalar-prefetched),
and each program streams only the *valid* KV pages of the sequences its
block touches via the scalar-prefetched page table. Attention is
flash-style online softmax (the exact :func:`online_softmax_update`
core the decode family uses, with the (row, head) pair flattened into
the accumulator's leading axis), with causal intra-chunk masking, GQA
sinks seeded into the running max/denominator, sliding windows clipping
the page range, and logit soft cap — natively, retiring the warn-once
XLA sink-prefill fallback in ``ops/attention.py``.

Like the decode kernels, the chunk's new K/V rows are appended into the
paged cache *inside the same program* through an input/output-aliased
``ANY``-memory-space cache ref: each program first DMAs its block's
rows into the slots ``slot_mapping`` names, then attends through the
output alias so a token sees itself and every earlier token of its own
block. Later tokens of the same step live in later blocks — sequential
grid order has already appended every position the causal mask can
admit, so no cross-program synchronization is needed. ``slot < 0``
(padding, or chunk-skip replay over cache-resident positions) skips the
append while attention still reads the committed context.

Chunked prefill and prefix-cache chunk skipping need no special path:
``kv_lens`` carries the FULL context per row (cached prefix + this
chunk) while ``cu_q_lens`` carries only this chunk's query tokens, so
each query attends across the whole cached page-table span — exactly
the contract of ``ragged_paged_attention``, whose XLA fallback is the
parity oracle for this kernel in interpret mode (CPU CI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from parallax_tpu.ops.decode_fused_pallas import _NEG, online_softmax_update
from parallax_tpu.ops.ragged import ragged_token_positions

# Query-block sizing: the per-program working set (online-softmax
# accumulators, scores, probabilities — all f32 with ``Hq * Bq`` rows)
# has to stay well inside the 16 MB of scoped VMEM a v5e kernel gets,
# and Mosaic unrolls every row into vector code, so compile time grows
# with it too. The edge is therefore derived from the head count: the
# largest power of two (at most _MAX_Q_BLOCK) that keeps ``Hq * Bq``
# within _MAX_BLOCK_ROWS.
_MAX_Q_BLOCK = 128
_MAX_BLOCK_ROWS = 1024
# Mosaic merges ``[G, Bq, D] -> [G * Bq, D]`` without a relayout only
# when Bq fills whole packed sublane tiles (16 rows for bf16); smaller
# token buckets are padded up to this many rows (padding rows carry
# slot -1 and belong to no sequence).
_MIN_Q_BLOCK = 16


def _pick_q_block(num_tokens: int, num_q_heads: int,
                  q_block: int | None) -> int:
    """Largest block <= the requested edge and the VMEM row budget that
    divides the (bucketed, normally power-of-two) token count; degrades
    to 1 for odd counts."""
    cap = _MAX_Q_BLOCK
    while cap > _MIN_Q_BLOCK and cap * num_q_heads > _MAX_BLOCK_ROWS:
        cap //= 2
    bq = min(q_block or cap, cap, num_tokens)
    while num_tokens % bq:
        bq -= 1
    return bq


@functools.partial(
    jax.jit,
    static_argnames=(
        "sm_scale", "sliding_window", "soft_cap", "use_sinks",
        "q_block", "interpret",
    ),
)
def gqa_fused_prefill_pallas(
    q: jax.Array,             # [T, Hq, D] — flattened ragged query tokens
    k_new: jax.Array | None,  # [T, Hkv, D] this chunk's keys, or None
    v_new: jax.Array | None,  # [T, Hkv, D] (None with k_new: attend only)
    kv_pages: jax.Array,      # [P, page, 2*Hkv, D] (donate for in-place)
    kv_lens: jax.Array,       # i32[S] FULL context length per row
    page_indices: jax.Array,  # i32[S, pages_per_seq]
    cu_q_lens: jax.Array,     # i32[S+1] cumulative query lengths
    num_seqs: jax.Array,      # i32[1] live sequence count (dynamic)
    slot_mapping: jax.Array,  # i32[T]; < 0 = no append for that token
    sinks: jax.Array | None,  # f32[Hq] or None
    *,
    sm_scale: float,
    sliding_window: int | None = None,
    soft_cap: float | None = None,
    use_sinks: bool = False,
    q_block: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused program per query block: KV append + ragged flash
    prefill attention. Returns ``(out [T, Hq, D], kv_pages)``; when
    ``k_new`` is None the cache is returned untouched (attend-only
    mode, e.g. the sink-prefill path whose scatter already ran)."""
    t_in, hq, d = q.shape
    _, page_size, combined, _ = kv_pages.shape
    num_kv_heads = combined // 2
    group = hq // num_kv_heads
    s, pages_per_seq = page_indices.shape
    with_append = k_new is not None
    slot_mapping = slot_mapping.astype(jnp.int32)

    # Token buckets below one packed sublane tile are padded up to it.
    t = max(t_in, _MIN_Q_BLOCK)
    if t != t_in:
        pad = t - t_in
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        slot_mapping = jnp.pad(slot_mapping, (0, pad), constant_values=-1)
        if with_append:
            k_new = jnp.pad(k_new, ((0, pad), (0, 0), (0, 0)))
            v_new = jnp.pad(v_new, ((0, pad), (0, 0), (0, 0)))
    bq = _pick_q_block(t, hq, q_block)
    num_blocks = t // bq

    # Host-side ragged prep: which sequences does each block straddle?
    # (The kernel recovers per-token membership and causal positions
    # from cu_q_lens/kv_lens alone; these bounds just keep the per-seq
    # loop from visiting rows the block cannot touch.)
    seq_of_tok, _ = ragged_token_positions(kv_lens, cu_q_lens, t, s)
    sid = seq_of_tok.reshape(num_blocks, bq)
    block_bounds = jnp.stack([sid[:, 0], sid[:, -1]], axis=1).astype(
        jnp.int32
    )

    # Heads lead inside the kernel: a KV head's query group is then a
    # slice of the untiled leading dim, and merging it with the token
    # dim for the MXU is layout-free. XLA does the two transposes.
    q_t = jnp.swapaxes(q, 0, 1)                           # [Hq, T, D]
    if use_sinks:
        sink_col = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(hq, 1, 1), (hq, bq, 1)
        )

    if with_append:
        from parallax_tpu.ops.kv_cache_ops import interleave_kv

        append = interleave_kv(k_new, v_new).astype(kv_pages.dtype)

    def kernel(pages_ref, lens_ref, cu_ref, nseq_ref, slots_ref,
               bounds_ref, *refs):
        pos = 0
        q_ref = refs[pos]; pos += 1
        if use_sinks:
            sinks_ref = refs[pos]; pos += 1
        if with_append:
            append_ref = refs[pos]; pos += 1
        cache_in_ref = refs[pos]; pos += 1
        out_ref = refs[pos]; pos += 1
        if with_append:
            cache_ref = refs[pos]; pos += 1   # output alias: reads see appends
        else:
            cache_ref = cache_in_ref
        m_ref, l_ref, o_ref, page_scratch, read_sem = refs[pos : pos + 5]
        pos += 5
        if with_append:
            write_sem = refs[pos]

        i = pl.program_id(0)
        tok0 = i * bq

        if with_append:
            def append_row(r, carry):
                slot = slots_ref[tok0 + r]

                @pl.when(slot >= 0)
                def _append():
                    cp = pltpu.make_async_copy(
                        append_ref.at[r],
                        cache_ref.at[slot // page_size, slot % page_size],
                        write_sem,
                    )
                    cp.start()
                    cp.wait()

                return carry

            jax.lax.fori_loop(0, bq, append_row, 0)

        if use_sinks:
            # Seed the sink as a virtual key (same trick as the decode
            # kernel): numerically identical to the XLA oracle's
            # finalize-time `l += exp(sink - m)`.
            m_ref[...] = sinks_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
        o_ref[...] = jnp.zeros_like(o_ref)

        q_blk = q_ref[...]                                # [hq, bq, d]
        tok = tok0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        s_lo = bounds_ref[i, 0]
        s_hi = jnp.minimum(bounds_ref[i, 1], nseq_ref[0] - 1)

        def seq_body(seq, carry):
            n = lens_ref[seq]
            lo = cu_ref[seq]
            hi = cu_ref[seq + 1]
            in_seq = jnp.logical_and(tok >= lo, tok < hi)  # [bq, 1]
            # Query position of each block token within seq's context:
            # the chunk's last token sits at n - 1, so position is
            # n - hi + token_index (garbage outside in_seq; masked).
            qpos = n - hi + tok                           # [bq, 1]
            first = jnp.maximum(lo, tok0)
            last = jnp.minimum(hi, tok0 + bq) - 1
            hi_page = jnp.where(
                first <= last, (n - hi + last) // page_size + 1, 0
            )
            if sliding_window is not None:
                lo_page = (
                    jnp.maximum(n - hi + first - sliding_window + 1, 0)
                    // page_size
                )
            else:
                lo_page = 0

            def page_body(j, inner):
                cp = pltpu.make_async_copy(
                    cache_ref.at[pages_ref[seq, j]], page_scratch, read_sem
                )
                cp.start()
                cp.wait()
                rows = page_scratch[...]                  # [page, 2Hkv, D]
                base = j * page_size
                score_heads = []
                for h in range(num_kv_heads):
                    qh = q_blk[h * group:(h + 1) * group].reshape(
                        group * bq, d
                    )
                    kh = rows[:, 2 * h, :]                # [page, D]
                    score_heads.append(jax.lax.dot_general(
                        qh, kh, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ).reshape(group, bq, page_size))
                scores = jnp.concatenate(score_heads, axis=0) * sm_scale
                if soft_cap is not None:
                    scores = soft_cap * jnp.tanh(scores / soft_cap)

                kv_pos = base + jax.lax.broadcasted_iota(
                    jnp.int32, (1, page_size), 1
                )                                         # [1, page]
                valid = jnp.logical_and(
                    in_seq,
                    jnp.logical_and(kv_pos <= qpos, kv_pos < n),
                )                                         # [bq, page]
                if sliding_window is not None:
                    valid = jnp.logical_and(
                        valid, kv_pos > qpos - sliding_window
                    )

                def weighted(p):                          # [hq, bq, page]
                    out_heads = []
                    for h in range(num_kv_heads):
                        ph = p[h * group:(h + 1) * group].reshape(
                            group * bq, page_size
                        )
                        vh = rows[:, 2 * h + 1, :]        # [page, D]
                        out_heads.append(jax.lax.dot_general(
                            ph.astype(vh.dtype), vh,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                        ).reshape(group, bq, d))
                    return jnp.concatenate(out_heads, axis=0)

                online_softmax_update(
                    m_ref, l_ref, o_ref, scores, valid[None], weighted
                )
                return inner

            jax.lax.fori_loop(lo_page, hi_page, page_body, 0)
            return carry

        jax.lax.fori_loop(s_lo, s_hi + 1, seq_body, 0)

        out_ref[...] = (
            o_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(out_ref.dtype)

    in_specs = [pl.BlockSpec((hq, bq, d), lambda i, *_: (0, i, 0))]
    inputs: list = [q_t]
    if use_sinks:
        in_specs.append(pl.BlockSpec((hq, bq, 1), lambda i, *_: (0, 0, 0)))
        inputs.append(sink_col)
    if with_append:
        in_specs.append(
            pl.BlockSpec((bq, combined, d), lambda i, *_: (i, 0, 0))
        )
        inputs.append(append)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(kv_pages)

    out_specs = [pl.BlockSpec((hq, bq, d), lambda i, *_: (0, i, 0))]
    out_shapes = [jax.ShapeDtypeStruct((hq, t, d), q.dtype)]
    aliases = {}
    if with_append:
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shapes.append(
            jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype)
        )
        # cache operand position: 6 scalar-prefetch + the VMEM operands.
        aliases = {6 + len(inputs) - 1: 1}

    scratch = [
        pltpu.VMEM((hq, bq, 1), jnp.float32),
        pltpu.VMEM((hq, bq, 1), jnp.float32),
        pltpu.VMEM((hq, bq, d), jnp.float32),
        pltpu.VMEM((page_size, combined, d), kv_pages.dtype),
        pltpu.SemaphoreType.DMA,
    ]
    if with_append:
        scratch.append(pltpu.SemaphoreType.DMA)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(num_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        input_output_aliases=aliases,
        interpret=interpret,
    )(
        page_indices.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        cu_q_lens.astype(jnp.int32),
        num_seqs.astype(jnp.int32),
        slot_mapping,
        block_bounds,
        *inputs,
    )
    attn = jnp.swapaxes(out[0], 0, 1)[:t_in]              # [T, Hq, D]
    if with_append:
        return attn, out[1]
    return attn, kv_pages
