"""The fused Pallas ragged decode kernel family + the shared
page-streaming core every decode kernel builds on.

This is the TPU analogue of the reference L1 fused kernel set
(``paged_attention`` v1/v2 + ``reshape_and_cache`` in C++/Metal,
PAPER.md): one Pallas program per attention layer consumes the page
table directly, handles per-row ragged context lengths in one grid,
and *appends the new token's K/V into the paged cache inside the same
kernel* — eliminating the separate scatter dispatch the split path
pays per layer. A sort-free filtered top-k/greedy sampling kernel
(:func:`fused_sample_topk_pallas`) completes the chain, so a K-step
decode window (``engine._dispatch_multistep``) is one device program
whose per-step work is kernel-only.

Fusion boundary: every kernel in THIS module is single-token-per-
sequence by construction (the in-kernel append targets one slot per
row, the fused sampler reads one logits row per row). Multi-token
ragged prefill batches have their own fused twin —
``ops/prefill_fused_pallas.py`` reuses :func:`online_softmax_update`
over a flattened token-block grid and appends whole chunks in-kernel —
so between the two modules every non-speculative batch shape has a
fused path. The SPECULATIVE decode window feeds
``1 + speculative_tokens`` positions per row and verifies them all,
which neither fused form models (the decode append is one slot per
row; the prefill kernel has no fused sampler), so its forward runs
the split-Pallas/XLA ragged multi-token path instead
(``ops/kernel_select.spec_window_impl`` — a registered gate,
``analysis/gates.py``); the fused family resumes the moment the batch
drops back to plain windows or single-step decode.

Two grid disciplines live here:

- **Streamed (fused) kernels** — grid ``(num_seqs,)``; each program
  DMAs only the row's *valid* pages HBM->VMEM (``ceil(kv_len/page)``
  of them, window-clipped when sliding) and folds them into a VMEM
  accumulator. The stream's discipline (:func:`paged_decode_stream`):
  pages move in **blocks of B** consecutive page-table entries, one
  DMA a page started back to back, into one of **two VMEM buffers**;
  block ``b + 1``'s copies are started before block ``b`` is waited
  on and folded, so copies are in flight while the fold computes, and
  ``fold`` is handed the landed buffer, ``B * page`` tokens, as a ref
  (the GQA fold reads each KV head out of it with one strided load,
  :func:`_kv_head_strided`). ``B`` is **derived**
  (:func:`decode_pages_per_block`: the largest power of two, at most
  8, whose pages fit 2 MB as VMEM holds them — 8 at 64/128 KB pages,
  2 at 1 MB pages). The row's append is waited on **before the copies
  of its last block start** (that block holds the slot's page), and
  **rows prefetch across the grid**: a row's last fold runs beside the
  next row's first fetch, except when that fetch would read the page
  the next row's own append is about to write. The split kernels'
  grid ``(S, pages_per_seq)`` visits — and block-copies — every page
  slot of every row, valid or not; on ragged decode batches the
  streamed form does strictly less memory traffic, and the fused
  append (a one-row DMA into the page the table already names)
  replaces a full-cache XLA scatter.
- **Legacy page-grid helpers** — :func:`decode_page_grid_spec` and the
  :func:`online_softmax_update` / :func:`online_softmax_finish` pair
  are the shared scaffold for the split decode kernels
  (``ops/attention_pallas.py``, ``ops/dsa_pallas.py``,
  ``ops/msa_pallas.py``), which previously each carried a private copy
  of the same grid/accumulator logic. The split latent kernel
  (``ops/mla_pallas.py``) left the page grid for the streamed core
  without an append (PR 51).

Everything supports ``interpret=True`` (Pallas interpreter), which is
how the CPU CI proves parity against the XLA reference paths
(``ops/attention.py::_ragged_paged_attention_xla``,
``ops/sampling.py``); ``chip_smoke.py`` repeats the comparison with
the compiled kernels on the chip.

Cache-write safety: the cache rides through the kernel as an
input/output-aliased ``ANY``-memory-space ref; all page reads go
through the *output* alias so the appended row is visible to the same
program's attention (the new token attends to itself). Appends target
each row's private tail slot (``slot_mapping``), never a shared
prefix page, so sequential grid iteration needs no cross-row
synchronization beyond the prefetch rule above. ``slot < 0``
(padding / frozen multi-step rows)
skips the append while attention still runs over the row's committed
context.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
_NEG_INF = float("-inf")
# Keep in sync with ops/sampling.NEG_INF (the sampler-parity contract).
_SAMPLE_NEG_INF = -1e10

# Largest per-row top_k the fused sampler accepts: its k-th-value
# threshold is k-1 sequential masked-max passes over the vocab, so cost
# grows O(top_k * vocab) where the sort-based sampler pays one
# O(vocab log vocab) sort regardless of k. Past this bound the engine
# keeps the split sampler (fused attention stays active).
FUSED_SAMPLE_TOPK_MAX = 64

_LANES = 128


# --------------------------------------------------------------------------
# Shared helpers for the legacy (S, pages_per_seq)-grid split kernels.
# --------------------------------------------------------------------------


def decode_page_grid_spec(
    num_seqs: int,
    pages_per_seq: int,
    in_specs: list,
    out_specs,
    scratch_shapes: list | None = None,
):
    """The split decode kernels' common grid: one program per (row,
    page-slot), with the page table + context lengths scalar-prefetched
    so each block's DMA address is known before the body runs."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(num_seqs, pages_per_seq),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes or [],
    )


def online_softmax_update(
    m_ref, l_ref, o_ref, scores, valid, weighted_values
) -> None:
    """One online-softmax accumulation step over a page of scores.

    ``scores``: f32[..., R, page] masked-input logits; ``valid``: bool
    broadcastable to scores; ``weighted_values(p)`` maps the f32[..., R,
    page] softmax numerators to the [..., R, D] value contribution
    (callers own the GQA/MLA head grouping). Accumulators are VMEM
    scratch ``m/l: f32[..., R, 1]``, ``o: f32[..., R, D]``. Every
    intermediate keeps its trailing unit dim: Mosaic has no cheap
    layout for rank-reduced row vectors.
    """
    scores = jnp.where(valid, scores, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(scores - m_new)
    p = jnp.where(valid, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_ref[...] = o_ref[...] * alpha + weighted_values(p)
    m_ref[...] = m_new


def online_softmax_finish(l_ref, o_ref, out_ref) -> None:
    """Divide the accumulated numerator by the running denominator and
    write the row output (zeros for padding rows, whose l is 0)."""
    out_ref[0] = (
        o_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    ).astype(out_ref.dtype)


# --------------------------------------------------------------------------
# The streamed (fused) core: grid (S,), DMA only the valid pages.
# --------------------------------------------------------------------------


# One buffer of the streamed core's page blocks may take this much VMEM
# (two are allocated). v5e gives a kernel 16 MB of scoped VMEM; 2 x 2 MB
# of block buffers leave the accumulators, the operands' double-buffered
# blocks and the fold's temporaries three quarters of it.
_STREAM_BLOCK_BYTES = 2 << 20
_MAX_PAGES_PER_BLOCK = 8      # static unrolling: one DMA a page


def decode_pages_per_block(
    page_size: int, c: int, w: int, dtype
) -> int:
    """How many pages the streamed core moves and folds at once — derived
    from the page's shape, never set: the largest power of two (at most
    ``_MAX_PAGES_PER_BLOCK``) whose pages fit ``_STREAM_BLOCK_BYTES`` *as
    VMEM holds a block buffer*: Mosaic tiles a ``[N, c, w]`` ref by
    ``c`` rows at ``c`` of 1, 2 or 4 and by 8 rows otherwise, whatever
    the dtype, and ``w`` pads to the 128 lanes — a ``[64, 4, 128]``
    bf16 page takes its 64 KB, 6 float32 rows take 8
    (``tests/test_tpu_compile.py``). Only a *loaded* block pads to the
    dtype's whole sublane tile."""
    rows = c if c in (1, 2, 4) else -(-c // 8) * 8
    lanes = -(-w // _LANES) * _LANES
    page_bytes = page_size * rows * lanes * jnp.dtype(dtype).itemsize
    b = max(1, min(_MAX_PAGES_PER_BLOCK, _STREAM_BLOCK_BYTES // page_bytes))
    return 1 << (b.bit_length() - 1)


def _kv_head_strided(rows_ref, h: int):
    """Keys and values of KV head ``h`` for every token of a block,
    ``[N, D]`` each, by a strided load from ``rows_ref`` (``[N, 2*Hkv,
    D]``, K at even and V at odd combined heads) seen as the dense 2-D
    ``[N * 2*Hkv, D]``. Under bf16's sublane packing one 32-bit word of
    that view is the pair (K_h[d], V_h[d]): K is ``word << 16`` and V
    ``word & 0xFFFF0000`` read as float32 — exact, a bf16 is the top
    half of a float32 — and narrowed back (``strided_load_kv`` of JAX's
    bundled ragged paged attention). Any other dtype takes the plain
    strided pair, rows ``2h`` and ``2h + 1`` of every ``2*Hkv``."""
    n, c, d = rows_ref.shape
    flat = rows_ref.reshape(n * c, d)
    if rows_ref.dtype == jnp.bfloat16:
        word = flat.bitcast(jnp.uint32)[pl.ds(h, n, stride=c // 2), :]
        k = pltpu.bitcast(word << 16, jnp.float32)
        v = pltpu.bitcast(word & jnp.uint32(0xFFFF0000), jnp.float32)
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    return (
        flat[pl.ds(2 * h, n, stride=c), :],
        flat[pl.ds(2 * h + 1, n, stride=c), :],
    )


def paged_decode_stream(
    cache: jax.Array,          # [P, page, C, W] (or [P, page, W])
    kv_lens: jax.Array,        # i32[S] context length INCLUDING new token
    page_indices: jax.Array,   # i32[S, pages_per_seq]
    slot_mapping: jax.Array,   # i32[S] flat append slot; < 0 skips append
    operands: list,            # [(array, row_indexed: bool), ...]
    *,
    out_shapes: list,          # [(per-row block shape sans leading 1, dtype)]
    acc_shapes: list,          # [(shape, dtype)] VMEM accumulators
    init,                      # fn(accs, qs, outs) -> None
    fold,                      # fn(accs, qs, outs, rows_ref, base, kv_len)
    finalize,                  # fn(accs, qs, outs, kv_len) -> None
    append: jax.Array | None = None,   # [S, C, W] ([S, W]) rows, cache dtype
    first_page=None,           # fn(kv_len) -> first page index (window clip)
    interpret: bool = False,
):
    """Build + invoke the streamed decode program.

    One grid step per row, rows in order. The row's valid pages
    (window-clipped from ``first_page``) move HBM->VMEM in blocks of
    ``B = decode_pages_per_block(...)`` consecutive page-table entries,
    one DMA a page started back to back, into one of two VMEM buffers:
    block ``b + 1``'s copies are started before block ``b`` is waited
    on and folded, so copies are in flight while ``fold`` computes.
    ``fold`` receives ``rows_ref``, a VMEM *ref* to the block's
    ``[B * page, C, W]`` tokens from position ``base`` on (it loads it
    whole, ``rows_ref[...]``, or reads it in parts), and masks what
    lies at or past ``kv_len`` itself. A short last block re-reads the
    row's last valid page into the slots it has no page for (their
    positions are >= ``kv_len``, so the mask drops them): every slot of
    a buffer holds real cache rows, at most ``B - 1`` pages a row are moved
    twice, and no page-table entry past the last valid page is ever
    dereferenced.

    Append ordering. The row's new-token DMA (``append``; skipped when
    the slot is < 0) is started first and waited on *before the copies
    of the row's last block are started*: the slot is position
    ``kv_len - 1`` (the new token attends to itself), so its page is
    the row's last valid page and only the last block reads it. A row
    of two or more blocks overlaps the append with its first fetch.

    Rows prefetch across the grid: while a row's last block is folded,
    the next row's first block is already on its way into the other
    buffer (the two SMEM words of ``carry_ref`` hand the buffer parity
    and the "already started" mark to the next grid step) — unless
    that block is the next row's only one and the row appends, for its
    append is started only at its own grid step and its page lies in
    that block.

    ``finalize`` writes the row's output block(s). Returns
    ``(outs..., cache)`` when appending (cache input/output-aliased —
    donate it), else ``outs...``; single-element outputs are unwrapped.
    """
    s, pages_per_seq = page_indices.shape
    # A page's rows: ``[C, W]``, or ``[W]`` for a cache without a head
    # axis (the latent cache: a dense ``[page, W]`` tile).
    page_size, tail = cache.shape[1], tuple(cache.shape[2:])
    n_ops = len(operands)
    with_append = append is not None
    bp = decode_pages_per_block(
        page_size, tail[0] if len(tail) == 2 else 1, tail[-1], cache.dtype
    )

    def kernel(pages_ref, lens_ref, slots_ref, *refs):
        qs = refs[:n_ops]
        pos = n_ops
        if with_append:
            append_ref = refs[pos]
            pos += 1
        cache_in_ref = refs[pos]
        pos += 1
        outs = refs[pos : pos + len(out_shapes)]
        pos += len(out_shapes)
        if with_append:
            cache_ref = refs[pos]       # output alias: reads see appends
            pos += 1
        else:
            cache_ref = cache_in_ref
        n_acc = len(acc_shapes)
        accs = refs[pos : pos + n_acc]
        blocks = refs[pos + n_acc]      # [2, B * page, C, W]
        read_sems = refs[pos + n_acc + 1]
        # Across grid steps: [0] blocks streamed so far (its parity names
        # the buffer a row's first block lands in), [1] whether the row
        # before already started this row's first block.
        carry_ref = refs[pos + n_acc + 2]
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            carry_ref[0] = 0
            carry_ref[1] = 0

        def extent(row):
            n = lens_ref[row]
            n_pages = (n + page_size - 1) // page_size
            start = first_page(n) if first_page is not None else 0
            return n, n_pages, start, (n_pages - start + bp - 1) // bp

        n, n_pages, start, n_blocks = extent(i)
        first_buf = carry_ref[0]
        prefetched = carry_ref[1] == 1
        carry_ref[0] = first_buf + n_blocks
        carry_ref[1] = 0

        def block_copies(row, b, buf, start, n_pages):
            """Block ``b`` of ``row``: one DMA a page into buffer
            ``buf % 2``. A short last block re-reads the row's last
            valid page into the slots it has no page for."""
            return [
                pltpu.make_async_copy(
                    cache_ref.at[pages_ref[
                        row, jnp.minimum(start + b * bp + k, n_pages - 1)
                    ]],
                    blocks.at[buf % 2, pl.ds(k * page_size, page_size)],
                    read_sems.at[buf % 2],
                )
                for k in range(bp)
            ]

        def start_block(b):
            for cp in block_copies(i, b, first_buf + b, start, n_pages):
                cp.start()

        def wait_append():
            pass

        if with_append:
            write_sem = refs[pos + n_acc + 3]
            slot = slots_ref[i]

            def append_copy():
                return pltpu.make_async_copy(
                    append_ref.at[0],
                    cache_ref.at[slot // page_size, slot % page_size],
                    write_sem,
                )

            @pl.when(slot >= 0)
            def _append():
                append_copy().start()

            def wait_append():
                @pl.when(slot >= 0)
                def _():
                    append_copy().wait()

        # The row after this one: its first block is started while this
        # row's last block is folded, unless that block holds the page
        # its own append (started only at its own grid step) writes.
        nxt = jnp.minimum(i + 1, s - 1)
        _, nxt_pages, nxt_start, nxt_blocks = extent(nxt)
        prefetch_next = jnp.logical_and(i + 1 < s, nxt_blocks >= 1)
        if with_append:
            prefetch_next = jnp.logical_and(
                prefetch_next,
                jnp.logical_or(nxt_blocks >= 2, slots_ref[nxt] < 0),
            )

        init(accs, qs, outs)

        # The last block reads the appended page: a row of one block (or
        # none) waits for its append here, a longer one inside the loop.
        @pl.when(n_blocks <= 1)
        def _():
            wait_append()

        @pl.when(jnp.logical_and(n_blocks > 0, jnp.logical_not(prefetched)))
        def _():
            start_block(0)

        def body(b, carry):
            @pl.when(b + 1 < n_blocks)
            def _():
                @pl.when(b + 2 == n_blocks)
                def _():
                    wait_append()

                start_block(b + 1)

            @pl.when(jnp.logical_and(b + 1 == n_blocks, prefetch_next))
            def _():
                for cp in block_copies(
                    nxt, 0, first_buf + n_blocks, nxt_start, nxt_pages
                ):
                    cp.start()
                carry_ref[1] = 1

            for cp in block_copies(i, b, first_buf + b, start, n_pages):
                cp.wait()
            fold(
                accs, qs, outs,
                blocks.at[(first_buf + b) % 2],
                (start + b * bp) * page_size, n,
            )
            return carry

        jax.lax.fori_loop(0, n_blocks, body, 0)
        finalize(accs, qs, outs, n)

    in_specs = []
    inputs = []
    for arr, row_indexed in operands:
        blk = (1, *arr.shape[1:])
        if row_indexed:
            in_specs.append(pl.BlockSpec(
                blk,
                lambda i, pages, lens, slots, nd=len(blk): (
                    (i,) + (0,) * (nd - 1)
                ),
            ))
        else:
            in_specs.append(pl.BlockSpec(
                blk,
                lambda i, pages, lens, slots, nd=len(blk): (0,) * nd,
            ))
        inputs.append(arr)
    if with_append:
        in_specs.append(pl.BlockSpec(
            (1, *tail),
            lambda i, pages, lens, slots: (i,) + (0,) * len(tail),
        ))
        inputs.append(append)
    in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
    inputs.append(cache)

    out_specs = []
    out_shape_structs = []
    for shape, dtype in out_shapes:
        blk = (1, *shape)
        # Per-row output blocks: leading dim is the grid row.
        out_specs.append(pl.BlockSpec(
            blk,
            lambda i, pages, lens, slots, nd=len(blk): (
                (i,) + (0,) * (nd - 1)
            ),
        ))
        out_shape_structs.append(
            jax.ShapeDtypeStruct((s, *shape), dtype)
        )
    aliases = {}
    if with_append:
        out_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        out_shape_structs.append(
            jax.ShapeDtypeStruct(cache.shape, cache.dtype)
        )
        # cache operand position: 3 scalar-prefetch + q operands + append.
        aliases = {3 + n_ops + 1: len(out_shapes)}

    scratch = [pltpu.VMEM(shape, dtype) for shape, dtype in acc_shapes]
    scratch.append(pltpu.VMEM((2, bp * page_size, *tail), cache.dtype))
    scratch.append(pltpu.SemaphoreType.DMA((2,)))
    scratch.append(pltpu.SMEM((2,), jnp.int32))
    if with_append:
        scratch.append(pltpu.SemaphoreType.DMA)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape_structs,
        input_output_aliases=aliases,
        # Rows hand their prefetch to the next grid step: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(page_indices, kv_lens, slot_mapping, *inputs)
    if len(out) == 1:
        return out[0]
    return tuple(out)


# --------------------------------------------------------------------------
# Fused GQA decode: append + flash attention (sinks/window/soft-cap).
# --------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "sm_scale", "sliding_window", "soft_cap", "use_sinks", "interpret",
    ),
)
def gqa_fused_decode_pallas(
    q: jax.Array,             # [S, Hq, D] — ONE query token per sequence
    k_new: jax.Array,         # [S, Hkv, D] this step's keys (pre-rope'd)
    v_new: jax.Array,         # [S, Hkv, D]
    kv_pages: jax.Array,      # [P, page, 2*Hkv, D] (donate for in-place)
    kv_lens: jax.Array,       # i32[S] INCLUDING the new token
    page_indices: jax.Array,  # i32[S, pages_per_seq]
    slot_mapping: jax.Array,  # i32[S]; < 0 = no append (padding/frozen)
    sinks: jax.Array | None,  # f32[Hq] or None
    *,
    sm_scale: float,
    sliding_window: int | None = None,
    soft_cap: float | None = None,
    use_sinks: bool = False,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused program: KV append + GQA flash decode. Returns
    ``(out [S, Hq, D], kv_pages)``."""
    s, hq, d = q.shape
    _, page_size, combined, _ = kv_pages.shape
    num_kv_heads = combined // 2
    group = hq // num_kv_heads
    if sinks is None:
        sinks = jnp.zeros((hq,), jnp.float32)
    sinks = sinks.reshape(1, hq).astype(jnp.float32)

    from parallax_tpu.ops.kv_cache_ops import interleave_kv

    append = interleave_kv(k_new, v_new).astype(kv_pages.dtype)

    def init(accs, qs, outs):
        m_ref, l_ref, o_ref = accs
        if use_sinks:
            # The sink is a virtual key with logit sinks[h]: seeding the
            # running max/denominator with it is numerically identical
            # to appending a key with no value payload.
            m_ref[:] = qs[1][0].reshape(hq, 1)
            l_ref[:] = jnp.ones_like(l_ref)
        else:
            m_ref[:] = jnp.full_like(m_ref, _NEG)
            l_ref[:] = jnp.zeros_like(l_ref)
        o_ref[:] = jnp.zeros_like(o_ref)

    def fold(accs, qs, outs, rows_ref, base, n):
        m_ref, l_ref, o_ref = accs
        qrow = qs[0][0]                               # [Hq, D]
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows_ref.shape[0]), 1
        )
        valid = pos < n
        if sliding_window is not None:
            valid = jnp.logical_and(valid, pos >= n - sliding_window)
        # Each KV head's keys and values come straight out of the
        # landed block, [N, D] each (docs/kernels.md, "The page stream").
        heads = [_kv_head_strided(rows_ref, h) for h in range(num_kv_heads)]
        score_rows = []
        for h in range(num_kv_heads):
            qh = qrow[h * group:(h + 1) * group]
            kh = heads[h][0]                          # [N, D]
            score_rows.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))                                        # [G, N]
        scores = jnp.concatenate(score_rows, axis=0) * sm_scale
        if soft_cap is not None:
            scores = soft_cap * jnp.tanh(scores / soft_cap)

        def weighted(p):
            out_rows = []
            for h in range(num_kv_heads):
                ph = p[h * group:(h + 1) * group]
                vh = heads[h][1]                      # [N, D]
                out_rows.append(jax.lax.dot_general(
                    ph.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ))                                    # [G, D]
            return jnp.concatenate(out_rows, axis=0)

        online_softmax_update(m_ref, l_ref, o_ref, scores, valid, weighted)

    def finalize(accs, qs, outs, n):
        _, l_ref, o_ref = accs
        online_softmax_finish(l_ref, o_ref, outs[0])

    first = None
    if sliding_window is not None:
        def first(n):
            return jnp.maximum(n - sliding_window, 0) // page_size

    out, kv_pages = paged_decode_stream(
        kv_pages, kv_lens, page_indices, slot_mapping,
        [(q, True), (sinks, False)],
        out_shapes=[((hq, d), q.dtype)],
        acc_shapes=[
            ((hq, 1), jnp.float32),
            ((hq, 1), jnp.float32),
            ((hq, d), jnp.float32),
        ],
        init=init, fold=fold, finalize=finalize,
        append=append, first_page=first, interpret=interpret,
    )
    return out, kv_pages


# --------------------------------------------------------------------------
# Fused MLA decode: latent append + flash decode over the latent cache.
# --------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "kv_lora_rank", "interpret")
)
def mla_fused_decode_pallas(
    q_latent: jax.Array,      # [S, Hq, R]
    q_pe: jax.Array,          # [S, Hq, Dr]
    latent_new: jax.Array,    # [S, R] this step's compressed latent
    k_pe_new: jax.Array,      # [S, Dr] this step's rope key
    cache: jax.Array,         # [P, page, W] (donate for in-place)
    kv_lens: jax.Array,       # i32[S]
    page_indices: jax.Array,  # i32[S, pages_per_seq]
    slot_mapping: jax.Array,  # i32[S]
    *,
    sm_scale: float,
    kv_lora_rank: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused program: latent-cache append + MLA flash decode.
    Returns ``(out [S, Hq, R], cache)``."""
    from parallax_tpu.ops.mla import mla_cache_rows

    s, hq, r = q_latent.shape
    rope_dim = q_pe.shape[-1]
    append = mla_cache_rows(
        latent_new, k_pe_new, cache.shape[-1], cache.dtype
    )                                                 # [S, W]

    def init(accs, qs, outs):
        m_ref, l_ref, o_ref = accs
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        o_ref[:] = jnp.zeros_like(o_ref)

    def fold(accs, qs, outs, rows_ref, base, n):
        m_ref, l_ref, o_ref = accs
        block_rows = rows_ref[...]                    # [N, W]
        latent = block_rows[:, :kv_lora_rank]
        rope = block_rows[:, kv_lora_rank:kv_lora_rank + rope_dim]
        ql = qs[0][0]                                 # [Hq, R]
        qp = qs[1][0]                                 # [Hq, Dr]
        scores = (
            jax.lax.dot_general(
                ql, latent, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + jax.lax.dot_general(
                qp, rope, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * sm_scale                                  # [Hq, N]
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows_ref.shape[0]), 1
        )
        valid = pos < n

        def weighted(p):
            return jax.lax.dot_general(
                p.astype(latent.dtype), latent, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        online_softmax_update(m_ref, l_ref, o_ref, scores, valid, weighted)

    def finalize(accs, qs, outs, n):
        _, l_ref, o_ref = accs
        online_softmax_finish(l_ref, o_ref, outs[0])

    out, cache = paged_decode_stream(
        cache, kv_lens, page_indices, slot_mapping,
        [(q_latent, True), (q_pe, True)],
        out_shapes=[((hq, r), q_latent.dtype)],
        acc_shapes=[
            ((hq, 1), jnp.float32),
            ((hq, 1), jnp.float32),
            ((hq, r), jnp.float32),
        ],
        init=init, fold=fold, finalize=finalize,
        append=append, interpret=interpret,
    )
    return out, cache


# --------------------------------------------------------------------------
# Fused sparse-indexer scoring (DSA / MSA): index-key append + full-context
# token scores in one streamed program.
# --------------------------------------------------------------------------


@functools.partial(
    jax.jit, static_argnames=("reduce_kind", "sm_scale", "interpret")
)
def indexer_scores_fused_pallas(
    q: jax.Array,             # [S, Hi, D] — ONE query token per sequence
    weights: jax.Array | None,  # f32[S, Hi] (DSA) or None (MSA)
    k_new: jax.Array,         # [S, D] this step's index key
    index_cache: jax.Array,   # [P, page, 1, D] (donate for in-place)
    kv_lens: jax.Array,       # i32[S]
    page_indices: jax.Array,  # i32[S, pages_per_seq]
    slot_mapping: jax.Array,  # i32[S]
    *,
    reduce_kind: str,         # "dsa" (relu-weighted sum) | "msa" (max)
    sm_scale: float = 1.0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused program: index-key append + per-token indexer scores.
    Returns ``(scores f32[S, pages_per_seq*page], index_cache)`` with
    exact ``-inf`` beyond each row's context (the top-k facades'
    dense-row detection relies on it)."""
    s, hi, d = q.shape
    _, page_size, c, w = index_cache.shape
    _, pages_per_seq = page_indices.shape
    kv_cap = pages_per_seq * page_size
    # The stream folds whole blocks: the score row is padded to a block
    # multiple so that a row's last block has room, and cut back below.
    block = page_size * decode_pages_per_block(
        page_size, c, w, index_cache.dtype
    )
    kv_pad = -(-kv_cap // block) * block
    append = k_new.astype(index_cache.dtype)[:, None, :]   # [S, 1, D]
    operands = [(q, True)]
    if reduce_kind == "dsa":
        operands.append((weights.astype(jnp.float32), True))

    def init(accs, qs, outs):
        outs[0][...] = jnp.full((1, kv_pad), _NEG_INF, jnp.float32)

    def fold(accs, qs, outs, rows_ref, base, n):
        keys = rows_ref[...][:, 0, :]                 # [N, D]
        dots = jax.lax.dot_general(
            qs[0][0], keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                             # [Hi, N]
        if reduce_kind == "dsa":
            w = qs[1][0]                              # [Hi]
            sc = jnp.sum(w[:, None] * jnp.maximum(dots, 0.0), axis=0)
        else:
            # Max over index heads; the (positive) scale commutes.
            sc = jnp.max(dots, axis=0) * sm_scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (block,), 0)
        outs[0][0, pl.ds(base, block)] = jnp.where(pos < n, sc, _NEG_INF)

    def finalize(accs, qs, outs, n):
        pass

    scores, index_cache = paged_decode_stream(
        index_cache, kv_lens, page_indices, slot_mapping,
        operands,
        out_shapes=[((kv_pad,), jnp.float32)],
        acc_shapes=[],
        init=init, fold=fold, finalize=finalize,
        append=append, interpret=interpret,
    )
    return scores[:, :kv_cap], index_cache


# --------------------------------------------------------------------------
# Fused sampling: sort-free greedy / filtered top-k in one kernel.
# --------------------------------------------------------------------------


def _sample_kernel(temp_ref, topk_ref, logits_ref, gumbel_ref, out_ref,
                   *, vocab: int):
    """One row per program. The row's vocabulary is folded to
    ``[V/128, 128]`` so it fills whole vector registers; every reduction
    keeps its unit dims, and argmax is spelled max + lowest matching
    token id (``jnp.argmax``'s first-occurrence rule)."""
    i = pl.program_id(0)
    lg = logits_ref[0]                                # [R, 128] f32
    r = lg.shape[0]
    ids = (
        jax.lax.broadcasted_iota(jnp.int32, (r, _LANES), 0) * _LANES
        + jax.lax.broadcasted_iota(jnp.int32, (r, _LANES), 1)
    )                                                 # token id per cell
    real = ids < vocab                                # lane padding off

    def full_max(x):                                  # -> [1, 1]
        return jnp.max(
            jnp.max(x, axis=0, keepdims=True), axis=1, keepdims=True
        )

    def argmax(x):                                    # -> i32[1, 1]
        hit = jnp.where(x == full_max(x), ids, jnp.int32(r * _LANES))
        return jnp.min(
            jnp.min(hit, axis=0, keepdims=True), axis=1, keepdims=True
        )

    lg = jnp.where(real, lg, _NEG_INF)
    greedy = argmax(lg)
    t = temp_ref[i]
    k = topk_ref[i]
    scaled = lg / jnp.maximum(t, 1e-6)
    # k-th largest by iterative max extraction (k-1 removals): identical
    # to descending-sort[k-1] including duplicate handling, no sort.
    need = jnp.logical_and(k > 0, k < vocab)
    iters = jnp.where(need, jnp.maximum(k - 1, 0), 0)

    def drop_max(_, cur):
        return jnp.where(ids == argmax(cur), _NEG, cur)

    red = jax.lax.fori_loop(0, iters, drop_max, scaled)
    thresh = jnp.where(need, full_max(red), jnp.float32(_NEG))
    # Value-threshold top-k (ties at the k-th value included) — the
    # exact filter ops/sampling.sample_tokens applies, so fused and
    # split draws agree bit-for-bit on the same logits.
    keep = scaled >= thresh
    filtered = jnp.where(keep, scaled, _SAMPLE_NEG_INF)
    choice = argmax(
        jnp.where(real, filtered + gumbel_ref[0], _NEG_INF)
    )
    out_ref[0] = jnp.broadcast_to(
        jnp.where(t <= 0.0, greedy, choice), (1, _LANES)
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_sample_topk_pallas(
    logits: jax.Array,        # [S, V] float
    gumbel: jax.Array,        # f32[S, V] per-token-id gumbel noise
    temperature: jax.Array,   # f32[S]; <= 0 = greedy
    top_k: jax.Array,         # i32[S]; <= 0 disables the filter
    *,
    interpret: bool = False,
) -> jax.Array:
    """Sample one token per row without the full-vocab sort: i32[S].

    Gumbel noise is indexed by token id and generated OUTSIDE the
    kernel (``ops/sampling.row_gumbel``) so the draw is bit-identical
    to the XLA sampler's — the kernel only filters and arg-maxes.
    Rows needing top-p/min-p/penalties take the split sampler instead
    (the engine gates them; see analysis/gates.py).
    """
    s, v = logits.shape
    rows = pl.cdiv(v, _LANES)
    pad = rows * _LANES - v

    def fold(x):
        x = x.astype(jnp.float32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        return x.reshape(s, rows, _LANES)

    row_block = pl.BlockSpec((1, rows, _LANES), lambda i, *_: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_sample_kernel, vocab=v),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s,),
            in_specs=[row_block, row_block],
            out_specs=pl.BlockSpec((1, 1, _LANES), lambda i, *_: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((s, 1, _LANES), jnp.int32),
        interpret=interpret,
    )(
        temperature.astype(jnp.float32), top_k.astype(jnp.int32),
        fold(logits), fold(gumbel),
    )
    return out[:, 0, 0]
