"""Pallas TPU kernel: GQA flash decode with attention sinks + sliding
window — the SPLIT-dispatch kernel (attention only; the KV append runs
as a separate XLA scatter and sampling as a separate op).

Completes the coverage the bundled
``jax.experimental.pallas.ops.tpu.ragged_paged_attention`` kernel lacks:
gpt-oss attention sinks (one virtual key per head joining the softmax with
no value payload — reference ``src/parallax_extensions/ops.py:556-572``)
and the alternating sliding windows that go with them. Grid
``(num_seqs, pages_per_seq)`` — one query token per sequence, every page
slot visited — built on the shared page-grid scaffold and online-softmax
core in ``ops/decode_fused_pallas.py``. The sink logit enters the
running max/denominator at init, which is numerically identical to
appending a virtual key.

The fused successor (``decode_fused_pallas.gqa_fused_decode_pallas``)
streams only the valid pages and appends the new token's K/V in the same
program; this kernel remains the split fallback and the microbench
baseline (docs/kernels.md).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from parallax_tpu.ops.decode_fused_pallas import (
    decode_page_grid_spec,
    online_softmax_finish,
    online_softmax_update,
)

_NEG = -1e30


def _gqa_decode_kernel(
    pages_ref,    # i32[S, pages_per_seq]
    lens_ref,     # i32[S]
    q_ref,        # [1, Hq, D]
    kv_ref,       # [1, page, 2*Hkv, D]
    sinks_ref,    # f32[1, Hq] (zeros when disabled; flag is static)
    out_ref,      # [1, Hq, D]
    m_ref,        # f32[Hq, 1]
    l_ref,        # f32[Hq, 1]
    o_ref,        # f32[Hq, D]
    *,
    sm_scale: float,
    num_kv_heads: int,
    sliding_window: int | None,
    use_sinks: bool,
):
    s = pl.program_id(0)
    j = pl.program_id(1)
    page_size = kv_ref.shape[1]
    hq = q_ref.shape[1]
    group = hq // num_kv_heads

    @pl.when(j == 0)
    def _init():
        if use_sinks:
            # The sink is a virtual key with logit sinks[h]: seed the
            # running max and denominator with it (value payload is zero).
            m_ref[:] = sinks_ref[0].reshape(hq, 1)
            l_ref[:] = jnp.ones_like(l_ref)
        else:
            m_ref[:] = jnp.full_like(m_ref, _NEG)
            l_ref[:] = jnp.zeros_like(l_ref)
        o_ref[:] = jnp.zeros_like(o_ref)

    kv_len = lens_ref[s]
    base = j * page_size
    q_pos = kv_len - 1
    window_lo = (
        (q_pos - sliding_window + 1) if sliding_window is not None else None
    )
    page_visible = base < kv_len
    if sliding_window is not None:
        page_visible = jnp.logical_and(
            page_visible, base + page_size - 1 >= window_lo
        )

    @pl.when(page_visible)
    def _accumulate():
        kv = kv_ref[0]                             # [page, 2*Hkv, D]
        q = q_ref[0]                               # [Hq, D]
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        valid = pos < kv_len
        if sliding_window is not None:
            valid = jnp.logical_and(valid, pos >= window_lo)

        # Per-KV-head dots (static unroll: Hkv is small).
        score_rows = []
        for h in range(num_kv_heads):
            qh = q[h * group:(h + 1) * group]
            kh = kv[:, 2 * h, :]                   # [page, D]
            score_rows.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))                                     # [G, page]
        scores = jnp.concatenate(score_rows, axis=0) * sm_scale  # [Hq, page]

        def weighted(p):
            out_rows = []
            for h in range(num_kv_heads):
                ph = p[h * group:(h + 1) * group]
                vh = kv[:, 2 * h + 1, :]           # [page, D]
                out_rows.append(jax.lax.dot_general(
                    ph.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ))                                 # [G, D]
            return jnp.concatenate(out_rows, axis=0)

        online_softmax_update(m_ref, l_ref, o_ref, scores, valid, weighted)

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        online_softmax_finish(l_ref, o_ref, out_ref)


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "sliding_window", "use_sinks", "interpret"),
)
def gqa_decode_attention_pallas(
    q: jax.Array,            # [S, Hq, D] — ONE query token per sequence
    kv_pages: jax.Array,     # [P, page, 2*Hkv, D]
    kv_lens: jax.Array,      # i32[S]
    page_indices: jax.Array, # i32[S, pages_per_seq]
    sinks: jax.Array | None, # f32[Hq] or None
    *,
    sm_scale: float,
    sliding_window: int | None = None,
    use_sinks: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Flash GQA decode with optional sinks + sliding window: [S, Hq, D]."""
    s, hq, d = q.shape
    p, page_size, combined, _ = kv_pages.shape
    num_kv_heads = combined // 2
    _, pages_per_seq = page_indices.shape
    if sinks is None:
        sinks = jnp.zeros((hq,), jnp.float32)
    sinks = sinks.reshape(1, hq).astype(jnp.float32)

    grid_spec = decode_page_grid_spec(
        s, pages_per_seq,
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda i, j, pages, lens: (i, 0, 0)),
            pl.BlockSpec(
                (1, page_size, combined, d),
                lambda i, j, pages, lens: (pages[i, j], 0, 0, 0),
            ),
            pl.BlockSpec((1, hq), lambda i, j, pages, lens: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, hq, d), lambda i, j, pages, lens: (i, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, d), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _gqa_decode_kernel,
        sm_scale=sm_scale,
        num_kv_heads=num_kv_heads,
        sliding_window=sliding_window,
        use_sinks=use_sinks,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hq, d), q.dtype),
        interpret=interpret,
    )(page_indices, kv_lens, q, kv_pages, sinks)
