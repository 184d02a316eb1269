"""EVA chunk summaries: 16 cached K/V rows -> one cache entry.

EVA (Zheng et al., "Efficient Attention via Control Variates", ICLR 2023,
as EvaByte instantiates it) lets a query attend exactly to its own
window and, for every completed window, to one *summary* per chunk of
``chunk_size`` tokens. With the learned per-head vectors ``mu`` and
``phi`` and the chunk's post-RoPE keys ``k_j`` and values ``v_j``:

    k~ = sum_j softmax_j(<mu, k_j>) k_j      v~ = sum_j softmax_j(<phi, k_j>) v_j

(both softmaxes in float32, no further scale on the logits). A summary
has a token's shape, so it is written into the same paged cache
(``[P, page, 2H, D]``, K at even and V at odd combined heads) and read by
the attention kernels like any entry: the engine lays a row's visible
summary pages before its open window's pages in the page table
(docs/memory.md "EVA"). This module is the write: after a step's cache
append, every chunk the step completed is gathered from the open
window's page and its summary stored into the window's pending summary
page.

``src[i]`` is the flat slot (``page * page_size + offset``) of chunk
``i``'s first token — a chunk never straddles a page
(``EvaConfig.fit_page_size``) — and ``dst[i]`` the flat slot of its
summary, negative where the step completed no chunk in that lane.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def chunk_summaries(rows: jax.Array, mu: jax.Array, phi: jax.Array):
    """``rows`` [N, C, 2H, D] (cache layout) -> summaries f32[N, 2H, D]."""
    n, c, h2, d = rows.shape
    rows = rows.astype(jnp.float32)
    k, v = rows[:, :, 0::2, :], rows[:, :, 1::2, :]
    wk = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", k, mu.astype(jnp.float32)), axis=1)
    wv = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", k, phi.astype(jnp.float32)), axis=1)
    ks = jnp.einsum("nch,nchd->nhd", wk, k)
    vs = jnp.einsum("nch,nchd->nhd", wv, v)
    return jnp.stack([ks, vs], axis=2).reshape(n, h2, d)


def eva_summary_xla(kv_pages, mu, phi, src, dst, *, chunk_size: int):
    """The XLA form: one gather, the two weighted sums, one scatter with
    the dead lanes dropped. Oracle for the kernel and the off-TPU path."""
    p, page, h2, d = kv_pages.shape
    flat = kv_pages.reshape(p * page, h2, d)
    idx = jnp.maximum(src, 0)[:, None] + jnp.arange(chunk_size)[None, :]
    out = chunk_summaries(flat[idx], mu, phi).astype(kv_pages.dtype)
    slots = jnp.where(dst < 0, p * page, dst)
    return flat.at[slots].set(out, mode="drop").reshape(p, page, h2, d)


@functools.partial(jax.jit, static_argnames=("chunk_size", "interpret"))
def eva_summary_pallas(kv_pages, mu, phi, src, dst, *, chunk_size: int,
                       interpret: bool = False):
    """One grid step per lane: DMA the chunk's rows from its page, the
    two float32 softmaxes per head on the VPU, DMA the summary row into
    its slot. The cache is input/output-aliased (donate it). Nothing
    here is bound by bandwidth or compute (16 rows in, one out): the
    cost is the DMA round trip per live lane, and dead lanes cost a
    scalar compare."""
    _, page, h2, d = kv_pages.shape
    heads = h2 // 2
    n = src.shape[0]

    def kernel(src_ref, dst_ref, mu_ref, phi_ref, _cache_in, cache,
               rows, out_row, read_sem, write_sem):
        i = pl.program_id(0)
        slot = dst_ref[i]

        @pl.when(slot >= 0)
        def _():
            first = src_ref[i]
            read = pltpu.make_async_copy(
                cache.at[first // page, pl.ds(first % page, chunk_size)],
                rows, read_sem,
            )
            read.start()
            read.wait()
            x = rows[...]                               # [C, 2H, D]
            out = []
            for h in range(heads):
                k = x[:, 2 * h, :].astype(jnp.float32)  # [C, D]
                v = x[:, 2 * h + 1, :].astype(jnp.float32)
                for w_ref, val in ((mu_ref, k), (phi_ref, v)):
                    logit = jnp.sum(
                        k * w_ref[pl.ds(h, 1), :], axis=1, keepdims=True)
                    e = jnp.exp(logit - jnp.max(logit, axis=0,
                                                keepdims=True))
                    w = e / jnp.sum(e, axis=0, keepdims=True)
                    out.append(jnp.sum(w * val, axis=0, keepdims=True))
            out_row[...] = jnp.concatenate(out, axis=0).astype(
                out_row.dtype)
            write = pltpu.make_async_copy(
                out_row, cache.at[slot // page, slot % page], write_sem)
            write.start()
            write.wait()

    vec = pl.BlockSpec((heads, d), lambda i, *_: (0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n,),
            in_specs=[vec, vec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((chunk_size, h2, d), kv_pages.dtype),
                pltpu.VMEM((h2, d), kv_pages.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(kv_pages.shape, kv_pages.dtype),
        # cache operand: 2 scalar-prefetch + mu + phi.
        input_output_aliases={4: 0},
        interpret=interpret,
        name="eva_summary",
    )(src, dst, mu.astype(jnp.float32), phi.astype(jnp.float32), kv_pages)


def eva_summarize(kv_pages, mu, phi, src, dst, *, chunk_size: int,
                  use_pallas: bool | None = None):
    """Write the summaries of the chunks a step completed (see module
    doc). The Pallas kernel on TPU, the XLA form elsewhere."""
    from parallax_tpu.ops.kernel_select import resolve_use_pallas

    if resolve_use_pallas(use_pallas):
        return eva_summary_pallas(kv_pages, mu, phi, src, dst,
                                  chunk_size=chunk_size)
    return eva_summary_xla(kv_pages, mu, phi, src, dst,
                           chunk_size=chunk_size)
