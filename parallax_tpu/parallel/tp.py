"""Tensor parallelism: megatron-style column/row sharding via shard_map.

The stage function runs SPMD over the ``tp`` mesh axis: q/k/v/gate/up
projections are column-sharded (each chip owns a head/FFN slice), o/down
projections are row-sharded with a ``psum`` over ``tp`` restoring the full
residual (the scaling-book recipe; reference counterpart: per-layer
``shard()`` + all-to-sharded linears, ``src/parallax/models/qwen3.py:181-195``).

KV pages are sharded on the combined-head axis, so each chip holds its own
heads' cache and the paged-attention kernel runs purely locally — zero
collectives in attention itself.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

# param paths (last two key segments) -> PartitionSpec
_COLUMN = {
    "q_proj", "k_proj", "v_proj", "gate_proj", "up_proj",
    # MLA head-sharded projections (DeepSeek): outputs are per-head.
    "q_b_proj", "kv_b_proj",
    # Step-3.5 head-wise attention gate: one output per (local) head.
    "g_proj",
    # Qwen3-Next GatedDeltaNet: rows are k-head-grouped blocks.
    "in_proj_qkvz", "in_proj_ba",
}
_ROW = {"o_proj", "down_proj", "out_proj"}

# Shared empty default for the col_vecs parameters (a call in a default
# argument — even an immutable one — trips the B008 ratchet).
_NO_COL_VECS: frozenset = frozenset()


def _spec_for(
    path: tuple[str, ...],
    leaf_value=None,
    tp: int | None = None,
    col_vecs: frozenset = _NO_COL_VECS,
) -> P:
    if len(path) >= 2:
        parent, leaf = path[-2], path[-1]
        if parent in col_vecs and leaf == "weight":
            # Model-declared column-sharded 1-D params (e.g. MiniMax-M2's
            # full-projection qk norm weights, which follow their
            # projection's head sharding).
            return P("tp")
        if parent == "experts":
            # Stacked MoE experts [E, ...] (weights rank 3, biases rank 2):
            # shard the expert dim (EP rides the tp axis).
            rank = getattr(leaf_value, "ndim", 3)
            return P("tp", *([None] * (rank - 1)))
        if parent in _COLUMN and leaf == "weight":
            return P("tp", None)
        if parent in _COLUMN and leaf == "bias":
            return P("tp")
        if parent in _ROW and leaf == "weight":
            return P(None, "tp")
    if path[-1] == "sinks":
        return P("tp")
    if (
        len(path) >= 2 and path[-2] == "lm_head" and path[-1] == "weight"
        and tp is not None
        and getattr(leaf_value, "ndim", 0) == 2
        and leaf_value.shape[0] % tp == 0
    ):
        # Vocab-sharded head: each chip computes a [S, V/tp] logits slice,
        # all-gathered on ICI inside the stage fn (base.py __call__) — the
        # full-vocab matmul FLOPs and the [V, H] weight split tp ways.
        # Guarded: tied-embedding models have no "lm_head" entry, quantized
        # heads have no "weight" leaf, and indivisible vocabs stay
        # replicated — ``lm_head_vocab_sharded`` is the single predicate
        # the model's all_gather must agree with.
        return P("tp", None)
    return P()  # replicated (norms, embed, router, row biases)


def _tree_map_with_path(fn, tree, path=()):
    if isinstance(tree, dict):
        return {k: _tree_map_with_path(fn, v, path + (k,)) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        out = [_tree_map_with_path(fn, v, path) for v in tree]
        return type(tree)(out) if isinstance(tree, tuple) else out
    return fn(path, tree)


def stage_param_specs(
    params: dict, tp: int | None = None, col_vecs: frozenset = _NO_COL_VECS
) -> dict:
    """PartitionSpec pytree matching a stage param tree."""
    return _tree_map_with_path(
        lambda path, leaf: _spec_for(path, leaf, tp, col_vecs), params
    )


def lm_head_vocab_sharded(params: dict, tp: int) -> bool:
    """Whether ``stage_param_specs`` vocab-shards this tree's lm_head (the
    model's logits all_gather must fire exactly when this holds)."""
    head = params.get("lm_head")
    return (
        isinstance(head, dict)
        and "weight" in head
        and getattr(head["weight"], "ndim", 0) == 2
        and head["weight"].shape[0] % tp == 0
    )


KV_SPEC = P(None, None, "tp", None)  # [pages, page, 2*Hkv, D]


def kv_partition_specs(model) -> list:
    """Per-layer KV cache specs, structure-matching the model's cache
    pytree: GQA pages shard on the combined-head axis; MLA latent pages and
    DSA/MSA index-key pages are head-independent and stay replicated.
    Sparse layers carry ``(kv_pages, index_pages)`` tuples, so their spec is
    a tuple too (a bare spec would be applied as a pytree prefix and try to
    shard the index cache's singleton head axis)."""
    from parallax_tpu.config import LAYER_LINEAR, LAYER_MLA

    cfg = model.config
    specs = []
    for li in range(model.num_local_layers):
        gi = model.start_layer + li
        if cfg.layer_type(gi) == LAYER_LINEAR:
            # (conv_state [slots, conv_dim, K], rec_state [slots, Hv, Dk,
            # Dv]): both shard on their channel/head axis — each shard's
            # slice matches its local [q|k|v] mixed layout and v-heads.
            specs.append((P(None, "tp", None), P(None, "tp", None, None)))
        elif cfg.layer_type(gi) == LAYER_MLA:
            if cfg.dsa is not None:
                full = cfg.dsa.indexer_types[gi] == "full"
                specs.append((P(), P()) if full else (P(), None))
            else:
                specs.append(P())
        elif cfg.msa is not None and (
            gi < len(cfg.msa.sparse_layer_mask)
            and cfg.msa.sparse_layer_mask[gi]
        ):
            specs.append((KV_SPEC, P()))
        else:
            specs.append(KV_SPEC)
    return specs


def shard_params(
    params: dict, mesh: Mesh, col_vecs: frozenset = _NO_COL_VECS
) -> dict:
    """Place a (host/global) param tree onto the mesh with TP sharding."""
    specs = stage_param_specs(params, tp=mesh.shape["tp"], col_vecs=col_vecs)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )


def param_sharding(
    mesh: Mesh, path: tuple[str, ...], leaf,
    col_vecs: frozenset = _NO_COL_VECS,
) -> NamedSharding:
    """Where ONE stage param lives on the mesh — the per-leaf form of
    :func:`shard_params`, for the loader, which places each tensor as it
    streams in so that no device ever stages the unsharded stage."""
    return NamedSharding(
        mesh, _spec_for(path, leaf, mesh.shape["tp"], col_vecs)
    )


def shard_kv_caches(kv: list, mesh: Mesh) -> list:
    return [jax.device_put(k, NamedSharding(mesh, KV_SPEC)) for k in kv]


def tp_stage_fn(model, params_template: dict, mesh: Mesh):
    """Wrap ``model.__call__`` for SPMD execution over the tp axis.

    Returns ``fn(params, kv_caches, inputs) -> (out, kv_caches)`` suitable
    for jit with KV donation. The model must have been constructed with
    ``tp_size = mesh.shape['tp']`` so its per-shard head counts match.
    """
    tp = mesh.shape["tp"]
    param_specs = stage_param_specs(
        params_template, tp=tp,
        col_vecs=getattr(model, "tp_column_vector_params", frozenset()),
    )
    model._lm_head_sharded = lm_head_vocab_sharded(params_template, tp)

    def fn(params, kv_caches, inputs):
        return model(params, kv_caches, inputs)

    kv_specs = kv_partition_specs(model)
    in_specs = (
        param_specs,
        kv_specs,
        P(),   # BatchInputs: replicated on every chip
    )
    out_specs = (P(), kv_specs)
    if tp == 1:
        return fn
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
