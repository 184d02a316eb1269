"""Pallas TPU kernel: flash-style MLA decode over the compressed latent
cache — the SPLIT-dispatch kernel (attention only; the latent append
runs as a separate XLA scatter before it).

Capability parity: reference MLA decode kernel
(``src/parallax_extensions/kernels/mla/mla.cpp:1-138``, facade
``ops.py:73-121``): ``softmax(q_latent . latent^T + q_pe . rope^T) .
latent`` per sequence, one query token each. The XLA gather path in
``ops/mla.py`` stays as the oracle (tests compare bit-for-bit semantics)
and the prefill path.

Kernel shape: the streamed core of the fused family
(``ops/decode_fused_pallas.paged_decode_stream``) without an append —
grid ``(num_seqs,)``; a row's *valid* pages, and no others, move
HBM->VMEM in double-buffered blocks of ``decode_pages_per_block`` pages
(8 of the latent cache's 80 KB pages) and fold into one online-softmax
accumulator (``online_softmax_update``). The two matmuls per block
([Hq, R] x [R, N] and [Hq, N] x [N, R]) land on the MXU; the fold masks
what lies past a row's context, so padding sequences (kv_len 0) produce
zeros. Until PR 51 this was a ``(num_seqs, pages_per_seq)`` page grid,
one grid step a page slot of every row, valid or not: at 64 heads, 128
rows and 64 slots a row a v5e took 3.49 ms a call at ~2k of context
against 1.25 ms streamed (1.84 / 0.74 at 0.4k, 5.18 / 1.81 at 3.7k;
host clock around one call, ~0.5 ms of dispatch in each; inside the
A.X-K1 cell's step program the streamed kernel takes 0.44 ms a layer at
~1.6k; PERF.md, PR 51).

The fused successor (``decode_fused_pallas.mla_fused_decode_pallas``)
appends the new latent row in the same program; Mosaic refuses its
one-row DMA (``kernel_select.fused_lowering_gap``), so on a TPU this
kernel serves latent decode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from parallax_tpu.ops.decode_fused_pallas import (
    online_softmax_finish,
    online_softmax_update,
    paged_decode_stream,
)

_NEG = -1e30


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "kv_lora_rank", "interpret"),
)
def mla_decode_attention_pallas(
    q_latent: jax.Array,     # [S, Hq, R] — ONE query token per sequence
    q_pe: jax.Array,         # [S, Hq, Dr]
    cache: jax.Array,        # [P, page, W >= R + Dr]
    kv_lens: jax.Array,      # i32[S]
    page_indices: jax.Array, # i32[S, pages_per_seq]
    *,
    sm_scale: float,
    kv_lora_rank: int,
    interpret: bool = False,
) -> jax.Array:
    """Flash MLA decode: [S, Hq, R] attention output in latent space."""
    s, hq, r = q_latent.shape
    rope_dim = q_pe.shape[-1]

    def init(accs, qs, outs):
        m_ref, l_ref, o_ref = accs
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        o_ref[:] = jnp.zeros_like(o_ref)

    def fold(accs, qs, outs, rows_ref, base, n):
        m_ref, l_ref, o_ref = accs
        rows = rows_ref[...]                          # [N, W]
        latent = rows[:, :kv_lora_rank]
        rope = rows[:, kv_lora_rank:kv_lora_rank + rope_dim]
        scores = (
            jax.lax.dot_general(
                qs[0][0], latent, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            + jax.lax.dot_general(
                qs[1][0], rope, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ) * sm_scale                                  # [Hq, N]
        pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows_ref.shape[0]), 1
        )

        def weighted(p):
            return jax.lax.dot_general(
                p.astype(latent.dtype), latent, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        # decode: the query sits at kv_len - 1 and attends all before it
        online_softmax_update(m_ref, l_ref, o_ref, scores, pos < n, weighted)

    def finalize(accs, qs, outs, n):
        _, l_ref, o_ref = accs
        online_softmax_finish(l_ref, o_ref, outs[0])

    return paged_decode_stream(
        cache, kv_lens, page_indices,
        jnp.full((s,), -1, jnp.int32),                # no append here
        [(q_latent, True), (q_pe, True)],
        out_shapes=[((hq, r), q_latent.dtype)],
        acc_shapes=[
            ((hq, 1), jnp.float32),
            ((hq, 1), jnp.float32),
            ((hq, r), jnp.float32),
        ],
        init=init, fold=fold, finalize=finalize,
        interpret=interpret,
    )
