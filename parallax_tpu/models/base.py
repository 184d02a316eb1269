"""StageModel: one pipeline stage as a pure jit-compiled function.

Capability parity: reference ``src/parallax/server/model.py:17-189``
(ShardedModel: embed iff first shard, norm+lm_head iff last, block
iteration threading cache state). The TPU design makes the stage a pure
function ``(params, kv_caches, BatchInputs) -> (output, kv_caches)`` so the
executor can jit it once per shape bucket with the KV pytree donated.
"""

from __future__ import annotations

import dataclasses
import functools
from collections.abc import Hashable
from typing import NamedTuple

import jax
import jax.numpy as jnp

from parallax_tpu.config import LAYER_LINEAR, LAYER_SLIDING, ModelConfig
from parallax_tpu.models import jamba
from parallax_tpu.models import layers as L
from parallax_tpu.obs.trace import note_block_trace
from parallax_tpu.ops import new_kv_pages
from parallax_tpu.ops.rope import rope_frequencies, rope_table


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BatchInputs:
    """Device inputs for one engine step (all fixed-shape per bucket).

    ``token_ids`` is used by the first stage, ``hidden_states`` by later
    stages; exactly one is non-None.
    """

    token_ids: jax.Array | None      # i32[T]
    hidden_states: jax.Array | None  # [T, hidden]
    positions: jax.Array             # i32[T] absolute positions
    kv_lens: jax.Array               # i32[S]
    page_indices: jax.Array          # i32[S, pages_per_seq]
    cu_q_lens: jax.Array             # i32[S+1]
    num_seqs: jax.Array              # i32[1]
    slot_mapping: jax.Array          # i32[T]
    logits_indices: jax.Array        # i32[S] last-token row per sequence
    # Hybrid (linear-attention) models only; None otherwise.
    state_slots: jax.Array | None = None  # i32[S] per-seq state slot
    dense_map: jax.Array | None = None    # i32[S, maxq] row index per step
    q_lens: jax.Array | None = None       # i32[S] valid steps per row
    # 1 on a request's first chunk: its (possibly reused) slot must be
    # zeroed before use.
    reset_state: jax.Array | None = None  # i32[S]
    # Per-request LoRA: {"slot": i32[], "layers": stacked adapter pytree}
    # for a batch the scheduler grouped under one adapter; None for base
    # traffic (which keeps its adapter-free graph). See ops/lora.py.
    lora: dict | None = None
    # STATIC: every segment is a single decode token (row i == sequence i).
    # Part of the jit cache key — decode steps compile their own variant so
    # decode-specialized kernels (Pallas MLA) can dispatch on it.
    decode_only: bool = dataclasses.field(
        default=False, metadata=dict(static=True)
    )
    # STATIC: fused decode program (EngineConfig.decode_fused): attention
    # layers append this step's K/V inside the Pallas decode kernel
    # (ops/decode_fused_pallas.py) instead of a separate scatter dispatch.
    # Only meaningful with decode_only; part of the jit cache key.
    decode_fused: bool = dataclasses.field(
        default=False, metadata=dict(static=True)
    )
    # STATIC: fused prefill program (EngineConfig.prefill_fused): GQA
    # attention layers append the chunk's K/V inside the ragged Pallas
    # prefill kernel (ops/prefill_fused_pallas.py) instead of a separate
    # scatter dispatch. Covers every multi-token ragged shape (prefill,
    # chunked prefill, mixed batches); mutually exclusive with
    # decode_fused per batch. Part of the jit cache key.
    prefill_fused: bool = dataclasses.field(
        default=False, metadata=dict(static=True)
    )
    # EVA models only (``ModelConfig.eva``; ops/eva.py): per lane, the
    # flat slot of the first token of a chunk this step completes and
    # the flat slot its summary is written to (< 0 = no chunk). With
    # them ``kv_lens`` / ``page_indices`` / ``slot_mapping`` describe
    # the row's *virtual* sequence (visible summaries, then the open
    # window) while ``positions`` stay absolute for RoPE.
    eva_src: jax.Array | None = None      # i32[NC]
    eva_dst: jax.Array | None = None      # i32[NC]
    # Decode windows only: what the K-step scan needs to cross a window
    # boundary on the device (runtime/engine.py ``_eva_step``): the page
    # table and pending summary pages after the rollover, and the window
    # the primary table belongs to.
    eva_window: dict | None = None


class BlockKey(NamedTuple):
    """What a decoder block reads from Python while it is traced, beyond
    the shapes and structure of its array arguments (which say the
    layer's kind: a mixer or attention, dense or expert MLP, the step's
    static flags). Two layers with one key and like arguments are one
    kind of block and share one trace (``StageModel._block``)."""

    window: int | None       # the layer's sliding window, None = full
    use_pallas: bool | None
    axis_name: str | None
    # (sp_mesh, sp_in_mesh) while the engine traces its SP step, else
    # None: the SP trace must not be handed the plain step's jaxpr.
    sp: tuple | None
    # A family's own per-layer facts (``StageModel._block_key``).
    extra: Hashable = None


class StageModel:
    """A contiguous range ``[start_layer, end_layer)`` of decoder blocks."""

    # NeoX-halves rope by default; models using the GPT-J interleaved
    # convention (GLM4) override this class attribute.
    rope_fn = staticmethod(L.apply_rope)
    # 0.0 = llama convention (ones-init weights); 1.0 = Gemma/Qwen3-Next
    # zero-init ``x_hat * (1 + w)`` for all layer/final/qk norms.
    norm_offset = 0.0
    def _rms(self, x, weight):
        return L.rms_norm(x, weight, self.config.rms_norm_eps,
                          offset=self.norm_offset)

    def __init__(
        self,
        config: ModelConfig,
        start_layer: int,
        end_layer: int,
        use_pallas: bool | None = None,
        tp_size: int = 1,
        axis_name: str = "tp",
    ):
        self.config = config
        self.start_layer = start_layer
        self.end_layer = end_layer
        self.is_first = start_layer == 0
        self.is_last = end_layer == config.num_hidden_layers
        self.use_pallas = use_pallas
        self.tp_size = tp_size
        # psum axis inside shard_map; None when running unsharded.
        self.axis_name = axis_name if tp_size > 1 else None
        if config.norm_offset:
            self.norm_offset = config.norm_offset
        if not config.use_rope:
            self.rope_fn = jamba.no_rope
        if config.mamba is not None:
            if config.moe is not None:
                raise ValueError(
                    "Mamba hybrids with num_experts > 1 are not supported"
                )
            if tp_size > 1:
                raise ValueError(
                    "Mamba mixers (per-channel state, d_inner unsharded) "
                    "run at tp-size 1"
                )
        if config.eva is not None and tp_size > 1:
            raise ValueError(
                "EVA attention (per-head summary vectors) runs at tp-size 1"
            )
        if config.loop_passes > 1 and (start_layer, end_layer) != (
            0, config.num_hidden_layers
        ):
            raise ValueError(
                f"a looped stack ({config.loop_passes} passes over "
                f"{config.num_hidden_layers} layers) runs whole on one "
                f"stage, not layers [{start_layer}, {end_layer}): the "
                "stream would go round the ring of stages once a pass, "
                "the final norm on the last stage feeding the first"
            )
        if tp_size > 1:
            for dim, name in (
                (config.num_attention_heads, "num_attention_heads"),
                (config.num_key_value_heads, "num_key_value_heads"),
                (config.intermediate_size, "intermediate_size"),
            ):
                if dim % tp_size:
                    raise ValueError(f"{name}={dim} not divisible by tp={tp_size}")
        inv = rope_frequencies(
            config.head_dim,
            config.rope_theta,
            config.rope_scaling,
            config.partial_rotary_factor,
        )
        scaling = 1.0
        if config.rope_scaling:
            rs = config.rope_scaling
            if "attention_factor" in rs:
                scaling = float(rs["attention_factor"])
            elif rs.get("rope_type", rs.get("type")) == "yarn":
                # HF default YaRN magnitude correction on cos/sin.
                from parallax_tpu.ops.rope import yarn_mscale

                scaling = yarn_mscale(float(rs.get("factor", 1.0)))
        self.cos_table, self.sin_table = rope_table(
            inv, config.max_position_embeddings, scaling
        )
        # The jaxpr of each kind of block a program has met
        # (``_block_fn``).
        self._block_jaxprs: dict = {}

    # -- structure --------------------------------------------------------

    @property
    def num_local_layers(self) -> int:
        return self.end_layer - self.start_layer

    def local_layer_types(self) -> list[str]:
        return [
            self.config.layer_type(i)
            for i in range(self.start_layer, self.end_layer)
        ]

    @property
    def has_linear_layers(self) -> bool:
        """Some local layer carries recurrent state in slots (the engine
        then hands ``new_kv_caches`` its ``num_state_slots``)."""
        return LAYER_LINEAR in self.local_layer_types()

    @property
    def state_dense_rows(self) -> bool:
        """Whether the recurrent layers want the rows densified to
        ``[rows, longest]`` (``BatchInputs.dense_map``); the Mamba scan
        runs over the flat stream."""
        return self.config.mamba is None

    def new_kv_caches(
        self, num_pages: int, page_size: int, dtype=jnp.bfloat16,
        num_state_slots: int = 0,
    ) -> list[jax.Array]:
        """One paged cache per local layer (state slots for a Mamba
        layer). A looped stack's layer holds every pass's pages in one
        array, ``loop_passes x num_pages`` of them (pass ``u`` in the
        ``u``-th ``num_pages``: ``_looped_passes``), so a page id
        addresses a page in every pass of every layer."""
        if self.config.mamba is not None:
            return jamba.new_caches(self, num_pages, page_size, dtype,
                              num_state_slots)
        return [
            new_kv_pages(
                self.config.loop_passes * num_pages,
                page_size,
                self.config.num_key_value_heads,
                self.config.head_dim,
                dtype,
            )
            for _ in range(self.num_local_layers)
        ]

    # -- parameters -------------------------------------------------------

    def finalize_params(self, tree: dict) -> dict:
        """Loader hook: reshape/stack checkpoint weights into this model's
        param layout (e.g. stacking MoE experts). Default: identity,
        but for EVA's per-head summary vectors, which checkpoints hold
        with broadcast axes (``[1, H, 1, D]``)."""
        if self.config.eva is not None:
            for layer in tree.get("layers", []):
                attn = layer["self_attn"]
                for name in ("adaptive_mu_k", "adaptive_phi"):
                    attn[name] = attn[name].reshape(
                        -1, self.config.head_dim
                    )
        if self.config.mamba is not None:
            tree = jamba.finalize_tree(tree)
        return tree

    def init_params(self, rng: jax.Array, dtype=jnp.bfloat16) -> dict:
        """Random init (tests / benchmarks with synthetic weights)."""
        cfg = self.config
        keys = jax.random.split(rng, self.num_local_layers + 2)

        def dense(key, out_dim, in_dim, bias=False):
            p = {
                "weight": (
                    jax.random.normal(key, (out_dim, in_dim), jnp.float32)
                    * (in_dim**-0.5)
                ).astype(dtype)
            }
            if bias:
                p["bias"] = jnp.zeros((out_dim,), dtype)
            return p

        def norm_weight(key, n):
            if not cfg.norm_offset:
                return jnp.ones((n,), dtype)
            # ``x_hat * (1 + w)``: drawn around zero, so that a dropped
            # offset changes every activation.
            return (0.1 * jax.random.normal(key, (n,), jnp.float32)
                    ).astype(dtype)

        params: dict = {"layers": []}
        for li in range(self.num_local_layers):
            k = jax.random.split(keys[li], 8)
            h, d = cfg.hidden_size, cfg.head_dim
            layer = {
                "input_layernorm": {
                    "weight": norm_weight(jax.random.fold_in(keys[li], 8), h)
                },
                "post_attention_layernorm": {
                    "weight": norm_weight(jax.random.fold_in(keys[li], 9), h)
                },
                "self_attn": {
                    "q_proj": dense(k[0], cfg.num_attention_heads * d, h,
                                    cfg.attention_bias),
                    "k_proj": dense(k[1], cfg.num_key_value_heads * d, h,
                                    cfg.attention_bias),
                    "v_proj": dense(k[2], cfg.num_key_value_heads * d, h,
                                    cfg.attention_bias),
                    "o_proj": dense(k[3], h, cfg.num_attention_heads * d),
                },
                "mlp": {
                    "gate_proj": dense(k[4], cfg.intermediate_size, h),
                    "up_proj": dense(k[5], cfg.intermediate_size, h),
                    "down_proj": dense(k[6], h, cfg.intermediate_size),
                },
            }
            if cfg.sandwich_norm:
                # The branch norms' weights are the residual branches'
                # scale: (2 x layers)^-0.5, GPT-2's depth scaling of its
                # residual projections, put where a sandwich norm puts a
                # branch's scale. At ones every block of a pass rewrites
                # the stream, and a stack of random blocks applied 192
                # times amplifies a rounding ~100x: not even a float32
                # program over a bf16 cache stays within the benchmark's
                # logprob limit of its float32 reference (PERF.md, PR 46).
                branch = (2 * cfg.num_hidden_layers) ** -0.5
                for name in ("input_layernorm_2",
                             "post_attention_layernorm_2"):
                    layer[name] = {"weight": jnp.full((h,), branch, dtype)}
            if cfg.use_qk_norm:
                layer["self_attn"]["q_norm"] = {"weight": jnp.ones((d,), dtype)}
                layer["self_attn"]["k_norm"] = {"weight": jnp.ones((d,), dtype)}
            if cfg.eva is not None:
                # The learned, input-independent proposals of the chunk
                # summaries: clip(normal, +-1) * d^-1/2 per head.
                for name, kk in (
                    ("adaptive_mu_k", jax.random.fold_in(keys[li], 10)),
                    ("adaptive_phi", jax.random.fold_in(keys[li], 11)),
                ):
                    layer["self_attn"][name] = (
                        jnp.clip(jax.random.normal(
                            kk, (cfg.num_key_value_heads, d), jnp.float32
                        ), -1.0, 1.0) * d**-0.5
                    ).astype(dtype)
            params["layers"].append(layer)

        # The last stage of a tied-embedding model also needs the embedding
        # matrix (it IS the lm_head), even when it is not the first stage.
        if self.is_first or (self.is_last and cfg.tie_word_embeddings):
            params["embed_tokens"] = {
                "weight": (
                    jax.random.normal(
                        keys[-2], (cfg.vocab_size, cfg.hidden_size), jnp.float32
                    )
                    * 0.02
                ).astype(dtype)
            }
        if self.is_last:
            params["norm"] = {
                "weight": norm_weight(jax.random.fold_in(keys[-1], 1),
                                      cfg.hidden_size)
            }
            if not cfg.tie_word_embeddings:
                # EVA models hold every prediction head's rows; head 0
                # (the first vocab_size rows) is the one sampled.
                heads = cfg.eva.num_pred_heads if cfg.eva else 1
                params["lm_head"] = {
                    "weight": (
                        jax.random.normal(
                            keys[-1],
                            (cfg.vocab_size * heads, cfg.hidden_size),
                            jnp.float32,
                        )
                        * 0.02
                    ).astype(dtype)
                }
            if cfg.sandwich_norm:
                # Ouro's exit gate (hidden -> 1): held, never evaluated
                # (``normalize_config`` refuses a threshold under 1).
                params["early_exit_gate"] = dense(
                    jax.random.fold_in(keys[-1], 2), 1, cfg.hidden_size,
                    bias=True,
                )
        if cfg.mamba is not None:
            params = jamba.init_mixers(self, params, rng, dtype)
        return params

    # -- forward ----------------------------------------------------------

    def __call__(
        self,
        params: dict,
        kv_caches: list[jax.Array],
        inputs: BatchInputs,
    ) -> tuple[jax.Array, list[jax.Array]]:
        """Run the stage.

        Returns ``(hidden [T, hidden], kv)`` for intermediate stages, or
        ``(logits [S, vocab], kv)`` on the last stage (gathered at each
        sequence's final token — reference ``logits_to_tokens``,
        model.py:88-124).
        """
        out, new_kv, _ = self.forward(params, kv_caches, inputs)
        return out, new_kv

    def forward(self, params: dict, kv_caches, inputs: BatchInputs):
        """The stage, and what its blocks handed on: ``(out, kv, carry)``
        with the last block's ``carry`` (``_block``: None unless the
        family hands something from layer to layer, else a dict — a
        decode step of a stage told its share of the routed experts
        leaves what its expert layers counted under ``"held"``)."""
        cfg = self.config
        if self.is_first:
            x = L.embed_lookup(params["embed_tokens"], inputs.token_ids)
        else:
            x = inputs.hidden_states
        if cfg.fp32_residual:
            # The residual stream in float32 (EvaByte fp32_skip_add);
            # ``_decoder_layer`` feeds the matmuls in the weights' dtype.
            x = x.astype(jnp.float32)

        lora_sel = None
        if inputs.lora is not None:
            from parallax_tpu.ops.lora import select_slot

            lora_sel = select_slot(
                inputs.lora, axis_name=self.axis_name, tp=self.tp_size
            )

        def walk(x, caches, inputs):
            """The local layers once: ``(x, caches, carry)`` out."""
            carry, out = None, []
            for li in range(self.num_local_layers):
                lp = params["layers"][li]
                if lora_sel is not None and str(li) in lora_sel:
                    from parallax_tpu.ops.lora import merge_layer_lora

                    lp = merge_layer_lora(lp, lora_sel[str(li)])
                x, kv_l, carry = self._block_fn(
                    self._block_key(li), lp, x, caches[li], inputs, carry
                )
                out.append(kv_l)
            return x, out, carry

        carry = None
        if cfg.loop_passes == 1:
            x, new_kv, carry = walk(x, kv_caches, inputs)
        else:
            # (A looped stack hands no carry out of its passes' loop.)
            x, new_kv = self._looped_passes(
                lambda *a: walk(*a)[:2], params, x, kv_caches, inputs
            )

        if not self.is_last:
            return x, new_kv, carry

        if cfg.loop_passes == 1:
            # (A looped stack's final norm closed its last pass.)
            x = self._rms(x, params["norm"]["weight"])
        x = x[inputs.logits_indices]
        head = params.get("lm_head") or params["embed_tokens"]
        if cfg.fp32_residual:
            x = x.astype(params["norm"]["weight"].dtype)
        logits = L.lm_head_logits(x, head)
        if cfg.eva is not None and cfg.eva.num_pred_heads > 1:
            # Head 0 of the multi-byte output matrix: the next byte.
            logits = logits[:, : cfg.vocab_size]
        if self.axis_name is not None and self._lm_head_sharded:
            # Vocab-sharded head (tp.lm_head_vocab_sharded — set by
            # tp_stage_fn): gather the [S, V/tp] slices on ICI.
            logits = jax.lax.all_gather(
                logits, self.axis_name, axis=1, tiled=True
            )
        return logits, new_kv, carry

    def _looped_passes(self, one_pass, params, x, kv_caches, inputs):
        """A looped stack: the layer walk ``loop_passes`` times under one
        ``lax.fori_loop``, so a program holds the walk once whatever the
        passes (the unrolled walk's programs were four times the size:
        57-67 s of compile each on a v5e's host against ~15,
        PERF.md, PR 46). A layer's cache array holds every pass's pages,
        ``passes x num_pages`` of them, pass ``u`` in the ``u``-th
        ``num_pages``: the pass reaches a block only as the page table
        and slots it is handed, shifted by ``u * num_pages``, so all
        passes and layers replay one block jaxpr. The final norm closes
        every pass: its output is pass ``u``'s result and pass
        ``u + 1``'s input (the last one's is what the head reads)."""
        passes = self.config.loop_passes
        total, page_size = kv_caches[0].shape[:2]
        num_pages = total // passes

        def body(u, state):
            x, caches = state
            slots = inputs.slot_mapping
            shifted = dataclasses.replace(
                inputs,
                page_indices=inputs.page_indices + u * num_pages,
                # (< 0 is padding: nothing is written.)
                slot_mapping=jnp.where(
                    slots >= 0, slots + u * (num_pages * page_size), slots
                ),
            )
            x, caches = one_pass(x, caches, shifted)
            return self._rms(x, params["norm"]["weight"]), caches

        with jax.named_scope("loop_pass"):
            return jax.lax.fori_loop(0, passes, body, (x, list(kv_caches)))

    # Sequence-parallel mode: set by the engine's SP dispatch wrapper while
    # tracing its long-prefill step function (ring attention over the
    # ``sp`` mesh axis instead of the paged-cache read).
    sp_mesh = None
    # SP x TP composition: when > 1, the stage is traced INSIDE a TP
    # shard_map over a combined ("sp", "tp") mesh and the attention block
    # slices its sp rank's token block for the ring body in place of
    # opening its own shard_map.
    sp_in_mesh = 0
    _sp_active = False
    # Set by tp.tp_stage_fn when the lm_head weight is vocab-sharded.
    _lm_head_sharded = False

    def _block_key(self, li: int) -> BlockKey:
        """Local layer ``li``'s key. The rule a family keeps: a block
        reads its arguments and its key, nothing else — an attribute
        that changes after ``__init__`` and is read while a block is
        traced belongs here (a family's own go into ``extra``), or two
        layers that differ share a jaxpr. A family that cannot say what
        its block reads keys it by ``li``: every layer its own kind,
        traced one by one."""
        cfg = self.config
        sliding = cfg.layer_type(self.start_layer + li) == LAYER_SLIDING
        return BlockKey(
            window=cfg.sliding_window if sliding else None,
            use_pallas=self.use_pallas,
            axis_name=self.axis_name,
            sp=(self.sp_mesh, self.sp_in_mesh) if self._sp_active else None,
        )

    def _block_fn(self, key: BlockKey, *args):
        """The layer loop's one way to call a block: ``_block(key,
        *args)`` traced to a jaxpr once a kind (the key and the
        structure, shapes and dtypes of the arguments) and replayed
        into the caller's trace for every layer of the kind, so the
        block's Python runs once a kind and program whatever the depth.

        The replay binds the block's equations one by one on the
        arguments as they are, so the caller's jaxpr is the one the
        plain loop gave, down to the order in which a decode window's
        scan first meets each weight, and XLA is handed the same module
        (a dense stage's letter for letter). A ``jax.jit`` of the block,
        inlined or lowered as one function called once a layer, takes
        every leaf of the layer in at the call, in the pytree's order:
        that reorders the scan's operands, and cost the 3B's decode
        step 2.2% (PERF.md, PR 45)."""
        flat, in_tree = jax.tree.flatten(args)
        kind = (key, in_tree, tuple(jax.typeof(a) for a in flat))
        hit = self._block_jaxprs.get(kind)
        if hit is None:
            closed, out = jax.make_jaxpr(
                functools.partial(self._block, key), return_shape=True
            )(*args)
            hit = self._block_jaxprs[kind] = (
                closed, jax.tree.structure(out)
            )
        closed, out_tree = hit
        return jax.tree.unflatten(
            out_tree, jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *flat)
        )

    def _block(self, key: BlockKey, lp: dict, x: jax.Array, kv,
               inputs: BatchInputs, carry):
        """One decoder block: ``(x, kv, carry)`` out. ``carry`` is what
        a family hands from one layer to the next as a value (None
        here). The body runs only under a trace, once a kind of block
        and program."""
        note_block_trace()
        x, kv = self._decoder_layer(lp, x, kv, inputs, key.window)
        return x, kv, carry

    def _attention(self, lp: dict, h: jax.Array, kv: jax.Array,
                   inputs: BatchInputs, window: int | None):
        cfg = self.config
        if "mamba" in lp:
            return jamba.mamba_mixer(self, lp["mamba"], h, kv, inputs)
        return L.paged_attention_block(
            h,
            lp["self_attn"],
            kv,
            config=cfg,
            positions=inputs.positions,
            kv_lens=inputs.kv_lens,
            page_indices=inputs.page_indices,
            cu_q_lens=inputs.cu_q_lens,
            num_seqs=inputs.num_seqs,
            slot_mapping=inputs.slot_mapping,
            cos_table=self.cos_table,
            sin_table=self.sin_table,
            sliding_window=window,
            use_pallas=self.use_pallas,
            axis_name=self.axis_name,
            rope_fn=self.rope_fn,
            sp_mesh=self.sp_mesh if self._sp_active else None,
            sp_in_mesh=self.sp_in_mesh if self._sp_active else 0,
            decode_only=inputs.decode_only,
            decode_fused=inputs.decode_fused,
            prefill_fused=inputs.prefill_fused,
            eva_src=inputs.eva_src,
            eva_dst=inputs.eva_dst,
        )

    def _decoder_layer(
        self,
        lp: dict,
        x: jax.Array,
        kv: jax.Array,
        inputs: BatchInputs,
        window: int | None,
    ) -> tuple[jax.Array, jax.Array]:
        cfg = self.config
        # With a float32 residual stream the norm reads it whole and
        # hands the block its input in the weights' dtype; the adds
        # promote back to float32.
        act = (lp["input_layernorm"]["weight"].dtype
               if cfg.fp32_residual else x.dtype)
        # ``precise_stream``: a Mamba mixer and the MLP take the norm's
        # float32 output and round it where their matmuls say
        # (``layers.linear_f32``); attention keeps the weights' dtype.
        precise = cfg.precise_stream
        h = self._rms(x, lp["input_layernorm"]["weight"])
        if not (precise and "mamba" in lp):
            h = h.astype(act)
        attn_out, kv = self._attention(lp, h, kv, inputs, window)
        if cfg.sandwich_norm:
            # Each branch is normed again before its add, in the
            # stream's dtype.
            attn_out = self._rms(attn_out.astype(x.dtype),
                                 lp["input_layernorm_2"]["weight"])
        x = x + attn_out
        h = self._rms(x, lp["post_attention_layernorm"]["weight"])
        mlp_out = self._mlp(lp, h if precise else h.astype(act))
        if cfg.sandwich_norm:
            mlp_out = self._rms(mlp_out.astype(x.dtype),
                                lp["post_attention_layernorm_2"]["weight"])
        x = x + mlp_out
        return x, kv

    def _mlp(self, lp: dict, h: jax.Array) -> jax.Array:
        return L.swiglu_mlp(h, lp["mlp"], axis_name=self.axis_name,
                            precise=self.config.precise_stream)
