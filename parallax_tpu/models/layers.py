"""Functional building blocks shared by the model zoo.

All blocks operate on a flattened ragged token batch ``x: [T, hidden]`` —
never [batch, seq]: continuous batching means every step mixes sequences of
different lengths, and a flat layout keeps every matmul dense on the MXU
with zero per-sequence padding. Params are plain dicts of jnp arrays keyed
with HF weight names (so the safetensors loader needs no remapping tables).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from parallax_tpu.config import ModelConfig
from parallax_tpu.ops import apply_rope, reshape_and_cache
from parallax_tpu.ops.attention import append_and_attend


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, offset: float = 0.0
) -> jax.Array:
    """RMSNorm; ``offset=1.0`` gives the Gemma/Qwen3-Next zero-init
    convention ``x_hat * (1 + w)``."""
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (weight.astype(jnp.float32) + offset)).astype(orig_dtype)


def full_proj_rms_norm(
    x: jax.Array,
    weight: jax.Array,
    eps: float,
    axis_name: str | None = None,
    full_dim: int | None = None,
) -> jax.Array:
    """RMSNorm over a FULL projection output whose feature dim may be
    column-sharded over ``axis_name`` (MiniMax-M2 qk norms: the statistic
    spans all heads concatenated, so under TP the sum of squares is
    psummed and every shard normalizes by the global mean while scaling
    with its local slice of the norm weight)."""
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    ss = jnp.sum(x * x, axis=-1, keepdims=True)
    dim = x.shape[-1]
    if axis_name is not None:
        ss = jax.lax.psum(ss, axis_name)
        # The statistic is now global; the divisor must be too. Derive it
        # from the mesh when the caller didn't pass full_dim (local dim
        # alone would mis-scale by sqrt(num_shards)).
        dim = (
            full_dim if full_dim is not None
            else x.shape[-1] * jax.lax.psum(1, axis_name)
        )
    x = x * jax.lax.rsqrt(ss / dim + eps)
    return (x * weight.astype(jnp.float32)).astype(orig_dtype)


def layer_norm(x: jax.Array, p: dict, eps: float) -> jax.Array:
    """Standard LayerNorm (mean-centered, with optional bias) — used by the
    DSA indexer's k_norm; everything else in the zoo is RMSNorm."""
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    out = x * p["weight"].astype(jnp.float32)
    if "bias" in p:
        out = out + p["bias"].astype(jnp.float32)
    return out.astype(orig_dtype)


def get_weight(p: dict) -> jax.Array:
    """The float weight of a param dict, dequantizing on the fly for
    weight-only quantized params (``ops/quant.py``)."""
    w = p.get("weight")
    if w is None and "qweight" in p:
        from parallax_tpu.ops.quant import dequantize_weight

        return dequantize_weight(p)
    return w


def _lora_delta(x: jax.Array, ab: dict) -> jax.Array:
    """Per-request LoRA correction ``(x @ A^T) @ B^T * scale`` in fp32.

    Two forms:
    - batch-uniform (``A [r, in]``, ``B [out, r]``, scalar ``s``): two
      thin MXU matmuls — the whole batch shares one adapter.
    - per-row mixed (``"slots"`` present: ``A [n, r, in]``,
      ``B [n, out, r]``, ``s [n]``, ``slots i32[T]``): compute the thin
      first matmul against EVERY adapter (``[T, n, r]`` — r is tiny, so
      this costs ~n*r/out of the base matmul) and contract the second
      matmul jointly over (n, r) with a scale-folded one-hot selecting
      each row's adapter. A row whose slot is out of range (the null
      slot for base traffic) gets an all-zero one-hot and thus a zero
      delta — masking for free. No ``[T, n, out]`` intermediate ever
      materializes.
    """
    if "slots" in ab:
        a_all = jnp.einsum(
            "ti,nri->tnr", x, ab["A"],
            preferred_element_type=jnp.float32,
        )
        n = ab["A"].shape[0]
        onehot = jax.nn.one_hot(
            ab["slots"], n, dtype=jnp.float32
        ) * ab["s"][None, :]
        return jnp.einsum(
            "tnr,tn,nor->to", a_all, onehot, ab["B"],
            preferred_element_type=jnp.float32,
        )
    a = jax.lax.dot_general(
        x, ab["A"],
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return jax.lax.dot_general(
        a, ab["B"],
        dimension_numbers=(((a.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * ab["s"]


def linear(x: jax.Array, p: dict) -> jax.Array:
    """x @ W^T + b with HF [out, in] weight layout kept as stored.

    Keeping the HF layout (contracting on dim 1) avoids a transpose at load
    time; XLA folds the contraction orientation into the matmul tiling.
    """
    out = jax.lax.dot_general(
        x, get_weight(p),
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    if "lora" in p:
        out = out + _lora_delta(x, p["lora"]).astype(out.dtype)
    if "bias" in p:
        out = out + p["bias"].astype(out.dtype)
    return out


def embed_lookup(embed, token_ids: jax.Array) -> jax.Array:
    """Token embedding rows; for a quantized table only the gathered rows
    are dequantized."""
    if isinstance(embed, dict) and "qweight" in embed:
        from parallax_tpu.ops.quant import dequantize_weight

        rows = {
            "qweight": embed["qweight"][token_ids],
            "scales": embed["scales"][token_ids],
        }
        if "biases" in embed:
            rows["biases"] = embed["biases"][token_ids]
        return dequantize_weight(rows)
    w = embed["weight"] if isinstance(embed, dict) else embed
    return w[token_ids]


def lm_head_logits(x: jax.Array, p: dict) -> jax.Array:
    """Final projection in fp32 for a numerically stable softmax/sampler."""
    return jax.lax.dot_general(
        x, get_weight(p),
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def row_parallel_linear(
    x: jax.Array, p: dict, axis_name: str | None
) -> jax.Array:
    """Row-sharded projection: psum the partial matmuls, add the (replicated)
    bias exactly once *after* the reduction."""
    out = jax.lax.dot_general(
        x, get_weight(p),
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    if "lora" in p:
        # Under TP the delta's A is sliced to this shard's in-dim block
        # (ops/lora.select_slot), so like the base matmul it is a partial
        # sum — applying it BEFORE the psum completes both at once.
        out = out + _lora_delta(x, p["lora"]).astype(out.dtype)
    if axis_name is not None:
        out = jax.lax.psum(out, axis_name)
    if "bias" in p:
        out = out + p["bias"].astype(out.dtype)
    return out


def swiglu_mlp(x: jax.Array, p: dict, axis_name: str | None = None) -> jax.Array:
    """SwiGLU FFN (gate/up/down). Under TP the hidden dim is column-sharded
    and the row-parallel down_proj output is psummed over ``axis_name``."""
    gate = linear(x, p["gate_proj"])
    up = linear(x, p["up_proj"])
    return row_parallel_linear(jax.nn.silu(gate) * up, p["down_proj"], axis_name)


def glu_mlp(x: jax.Array, p: dict, act_fn, axis_name: str | None = None) -> jax.Array:
    """GLU FFN with a custom gating activation ``act_fn(gate, up)``
    (MiniMax-M3's clamped swiglu-oai dense layers)."""
    gate = linear(x, p["gate_proj"]).astype(jnp.float32)
    up = linear(x, p["up_proj"]).astype(jnp.float32)
    return row_parallel_linear(
        act_fn(gate, up).astype(x.dtype), p["down_proj"], axis_name
    )


def paged_attention_block(
    x: jax.Array,
    p: dict,
    kv_pages: jax.Array,
    *,
    config: ModelConfig,
    positions: jax.Array,
    kv_lens: jax.Array,
    page_indices: jax.Array,
    cu_q_lens: jax.Array,
    num_seqs: jax.Array,
    slot_mapping: jax.Array,
    cos_table: jax.Array,
    sin_table: jax.Array,
    sliding_window: int | None = None,
    use_pallas: bool | None = None,
    axis_name: str | None = None,
    rope_fn=apply_rope,
    sp_mesh=None,
    sp_in_mesh: int = 0,
    decode_only: bool = False,
    decode_fused: bool = False,
    prefill_fused: bool = False,
    eva_src: jax.Array | None = None,
    eva_dst: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """GQA attention over the paged cache: project, rope, scatter, attend.

    Semantics of the reference per-model attention
    (``src/parallax/models/qwen3.py:30-143``): new K/V always enter the
    cache first, attention always reads from the cache, so prefix hits and
    chunked prefill need no separate code path.

    Head counts are inferred from the weight shapes, so the same code runs
    unsharded or inside shard_map with column-sharded projections (each chip
    sees its local heads + its slice of the KV pages); the row-parallel
    o_proj output is psummed over ``axis_name``.

    ``sp_mesh`` switches long-context prefill to ring attention over the
    mesh's ``sp`` axis (sequence parallelism): the quadratic attention is
    computed with Q/K/V row-sharded over chips, K/V rotating on ICI, while
    the cache write proceeds as usual. Valid only for a batch of
    prefill-from-zero rows (no cached prefix) whose padding rows carry
    position ``-1`` — the engine's SP dispatch guarantees both.
    """
    t = x.shape[0]
    d = config.head_dim
    q = linear(x, p["q_proj"]).reshape(t, -1, d)
    k = linear(x, p["k_proj"]).reshape(t, -1, d)
    v = linear(x, p["v_proj"]).reshape(t, -1, d)
    hq = q.shape[1]

    if config.use_qk_norm and "q_norm" in p:
        q = rms_norm(q, p["q_norm"]["weight"], config.rms_norm_eps)
        k = rms_norm(k, p["k_norm"]["weight"], config.rms_norm_eps)

    q = rope_fn(q, positions, cos_table, sin_table)
    k = rope_fn(k, positions, cos_table, sin_table)

    if sp_in_mesh > 1 or sp_mesh is not None:
        kv_pages = reshape_and_cache(kv_pages, k, v, slot_mapping)
    if sp_in_mesh > 1:
        # SP x TP composition: we are ALREADY inside the TP stage's
        # shard_map (mesh axes ("sp", "tp"); everything here replicated
        # over sp, heads sharded over tp). The cache scatter above ran on
        # the full token batch — identical on every sp rank, keeping the
        # (sp-replicated) cache consistent — and only the quadratic
        # attention shards: each rank slices its query block and flashes
        # it against the full K/V it already holds (no ring rotation —
        # ppermuting replicated blocks would be pure ICI overhead).
        from parallax_tpu.parallel.sp import context_blocks_attention_local

        rank = jax.lax.axis_index("sp")
        tshard = t // sp_in_mesh   # engine lattice pads T to sp multiples
        kv_positions = jnp.where(positions < 0, jnp.int32(2**30), positions)

        def _sl(a):
            return jax.lax.dynamic_slice_in_dim(a, rank * tshard, tshard, 0)

        out_l = context_blocks_attention_local(
            _sl(q), k, v, _sl(positions), kv_positions,
            sm_scale=d**-0.5, sp=sp_in_mesh,
        )
        out = jax.lax.all_gather(out_l, "sp", axis=0, tiled=True)
    elif sp_mesh is not None:
        from parallax_tpu.parallel.sp import ring_attention

        out = ring_attention(
            sp_mesh, q, k, v, positions, sm_scale=d**-0.5,
        )
    else:
        # The common path: cache write + attention through the single
        # append_and_attend facade — one fused Pallas program per layer
        # when ``decode_fused`` is active on a decode batch, the split
        # scatter-then-attend dispatch chain otherwise.
        out, kv_pages = append_and_attend(
            q, k, v, kv_pages,
            kv_lens,
            page_indices,
            cu_q_lens,
            num_seqs,
            slot_mapping,
            sm_scale=d**-0.5,
            sliding_window=sliding_window,
            sinks=p.get("sinks"),
            use_pallas=use_pallas,
            decode_only=decode_only,
            decode_fused=decode_fused,
            prefill_fused=prefill_fused,
        )
        if eva_dst is not None:
            # EVA: the chunks this step completed get their summary
            # entries, read back from the rows the append just wrote.
            from parallax_tpu.ops.eva import eva_summarize

            kv_pages = eva_summarize(
                kv_pages, p["adaptive_mu_k"], p["adaptive_phi"],
                eva_src, eva_dst, chunk_size=config.eva.chunk_size,
                use_pallas=use_pallas,
            )
    out = row_parallel_linear(out.reshape(t, hq * d), p["o_proj"], axis_name)
    return out, kv_pages
