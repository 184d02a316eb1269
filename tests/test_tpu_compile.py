"""The kernels TPU-auto selects for a dense GQA model, compiled by the
installed TPU compiler for a *described* v5e (no chip attached) at
Qwen2.5-7B widths and ``serve``'s defaults: 28/4 heads unsharded and the
7/1 heads of one TP=4 shard, head_dim 128, bf16, page 64,
``--max-model-len 8192`` (129 pages per sequence), vocab 152064.

Interpret-mode parity (tests/test_decode_fused.py,
tests/test_prefill_fused.py) says a kernel computes the right thing; it
says nothing about whether Mosaic accepts it — block shapes below the
(8, 128) tile, unaligned slices and scoped-VMEM limits are refused only
here. Nothing runs, so this is not a chip run and measures nothing.
The whole-step compiles (an unrolled N-layer program per shape bucket)
live in ``chip_smoke.py``'s real run, not here. The kernels TPU-auto
does NOT select because they do not lower are compiled too, expecting
the compiler's refusal, so the gate cannot outlive its reason.

The ops modules pick interpret mode from ``kernel_select.tpu_available``,
which sees the CPU in this process; the tests pass ``interpret=False``
to the kernels themselves.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.ops import kernel_select
from parallax_tpu.ops.attention import _rpa_block_sizes
from parallax_tpu.ops.attention_pallas import gqa_decode_attention_pallas
from parallax_tpu.ops.decode_fused_pallas import (
    decode_pages_per_block,
    fused_sample_topk_pallas,
    gqa_fused_decode_pallas,
)
from parallax_tpu.ops.prefill_fused_pallas import gqa_fused_prefill_pallas

HEAD_DIM, PAGE, PAGES_PER_SEQ, NUM_PAGES, VOCAB = 128, 64, 129, 1024, 152064
HEADS = [(28, 4), (7, 1)]          # unsharded; one TP=4 shard
HEAD_IDS = ["28q4kv", "tp4-7q1kv"]
# The decode kernel also at Qwen2.5-3B's 16/2 heads (the second cell)
# at Jamba2-3B's multi-query 20/1 (the fourth) and at Ouro-2.6B's
# multi-head 16/16 (the fifth: 192 launches a step, PR 46).
DECODE_HEADS = HEADS + [(16, 2), (20, 1), (16, 16)]
DECODE_HEAD_IDS = HEAD_IDS + ["16q2kv", "mqa-20q1kv", "mha-16q16kv"]
PREFILL_HEADS = HEADS + [(20, 1), (16, 16)]
PREFILL_HEAD_IDS = HEAD_IDS + ["mqa-20q1kv", "mha-16q16kv"]
# A page table long enough for 16k tokens (``--max-model-len 16384``).
PAGES_16K = 16384 // PAGE


@pytest.fixture(scope="module")
def v5e():
    """One described v5e device; the persistent compile cache is off
    while this module runs (an entry compiled for a described device is
    written but can never be read back without the chip)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"v5e:2x2 topology cannot be described: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Lower + compile for the described device; returns the HLO text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _batch(dev, hq, hkv, t, s, pages_per_seq=PAGES_PER_SEQ,
           dtype=jnp.bfloat16):
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    return dict(
        q=a((t, hq, HEAD_DIM), dtype),
        k=a((t, hkv, HEAD_DIM), dtype),
        v=a((t, hkv, HEAD_DIM), dtype),
        cache=a((NUM_PAGES, PAGE, 2 * hkv, HEAD_DIM), dtype),
        kv_lens=a((s,), jnp.int32),
        pages=a((s, pages_per_seq), jnp.int32),
        cu=a((s + 1,), jnp.int32),
        nseq=a((1,), jnp.int32),
        slots=a((t,), jnp.int32),
    )


def _decode(q, k, v, cache, lens, pages, slots):
    return gqa_fused_decode_pallas(
        q, k, v, cache, lens, pages, slots, None,
        sm_scale=HEAD_DIM ** -0.5, interpret=False,
    )


@pytest.mark.parametrize("pages_per_seq", [PAGES_PER_SEQ, PAGES_16K],
                         ids=["8k", "16k"])
@pytest.mark.parametrize("s", [8, 64])
@pytest.mark.parametrize("hq,hkv", DECODE_HEADS, ids=DECODE_HEAD_IDS)
def test_fused_decode_compiles_for_v5e(v5e, hq, hkv, s, pages_per_seq):
    """Both block buffers of the page stream (``decode_pages_per_block``
    pages each), the accumulators and the fold's temporaries inside
    scoped VMEM, at the shapes the benchmark's Qwen cells run."""
    b = _batch(v5e, hq, hkv, s, s, pages_per_seq)
    _compile(
        _decode,
        b["q"], b["k"], b["v"], b["cache"], b["kv_lens"], b["pages"],
        b["slots"],
    )


@pytest.mark.parametrize("hq,hkv", DECODE_HEADS, ids=DECODE_HEAD_IDS)
def test_fused_decode_compiles_for_v5e_with_a_float32_cache(v5e, hq, hkv):
    """``kv_dtype`` float32: the fold's plain strided pair of loads a
    head (no packed words to split) lowers too."""
    b = _batch(v5e, hq, hkv, 8, 8, dtype=jnp.float32)
    _compile(
        _decode, b["q"], b["k"], b["v"], b["cache"], b["kv_lens"],
        b["pages"], b["slots"],
    )


@pytest.mark.parametrize(
    "hq,hkv,dtype,fits,refused",
    [(7, 1, jnp.bfloat16, 192, 288), (16, 2, jnp.bfloat16, 96, 144),
     (12, 12, jnp.bfloat16, 16, 24), (9, 3, jnp.float32, 24, 36)],
    ids=["c2-bf16", "c4-bf16", "c24-bf16", "c6-f32-pads-to-8"],
)
def test_stream_block_buffers_take_the_vmem_the_block_size_counts(
    v5e, monkeypatch, hq, hkv, dtype, fits, refused
):
    """What ``decode_pages_per_block`` counts a page at, held from both
    sides against the 16 MB of scoped VMEM: two buffers of ``fits``
    pages are 12 MB by its rule and compile, two of ``refused`` pages
    18 MB and do not. A ``[N, 4, 128]`` bf16 buffer takes its own bytes
    (padded to bf16's 16-sublane tile it would take four times as
    many), 24 bf16 rows stay 24 (not 32), 6 float32 rows take 8."""
    from parallax_tpu.ops import decode_fused_pallas

    b = _batch(v5e, hq, hkv, 8, 8, dtype=dtype)

    def compile_at(pages_per_block):
        monkeypatch.setattr(
            decode_fused_pallas, "decode_pages_per_block",
            lambda *_: pages_per_block,
        )
        # Unjitted and a new function each time: the block size is read
        # while the kernel is traced, and jit would hand back the trace
        # of another block size.
        _compile(
            lambda *args: gqa_fused_decode_pallas.__wrapped__(
                *args, None, sm_scale=HEAD_DIM ** -0.5, interpret=False,
            ),
            b["q"], b["k"], b["v"], b["cache"], b["kv_lens"], b["pages"],
            b["slots"],
        )

    compile_at(fits)
    with pytest.raises(Exception, match="exceeded scoped vmem limit"):
        compile_at(refused)


@pytest.mark.parametrize("t,s", [(256, 8), (2048, 64)])
@pytest.mark.parametrize("hq,hkv", PREFILL_HEADS, ids=PREFILL_HEAD_IDS)
def test_fused_prefill_compiles_for_v5e(v5e, hq, hkv, t, s):
    b = _batch(v5e, hq, hkv, t, s)
    _compile(
        lambda q, k, v, cache, lens, pages, cu, nseq, slots:
        gqa_fused_prefill_pallas(
            q, k, v, cache, lens, pages, cu, nseq, slots, None,
            sm_scale=HEAD_DIM ** -0.5, interpret=False,
        ),
        b["q"], b["k"], b["v"], b["cache"], b["kv_lens"], b["pages"],
        b["cu"], b["nseq"], b["slots"],
    )


# The A.X-K1 cell's window: 128 rows a step at a vocabulary of 20,480 (a
# latent-attention stage, whose sampler is fused though its attention is
# not: ``kernel_select.resolve_window_sampler_fused``).
@pytest.mark.parametrize("s,vocab", [(8, VOCAB), (64, VOCAB), (128, 20480)])
def test_fused_sampler_compiles_for_v5e(v5e, s, vocab):
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    _compile(
        functools.partial(fused_sample_topk_pallas, interpret=False),
        a((s, vocab), jnp.float32), a((s, vocab), jnp.float32),
        a((s,), jnp.float32), a((s,), jnp.int32),
    )


@pytest.mark.parametrize("hq,hkv", HEADS, ids=HEAD_IDS)
def test_bundled_ragged_attention_compiles_for_v5e(v5e, hq, hkv):
    """What a speculative window's multi-token forward calls (and the
    split path): the bundled kernel, with the block sizes
    ``ops/attention`` derives from the shapes — its own default wants
    33 MB of scoped VMEM at this geometry."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention,
    )

    b = _batch(v5e, hq, hkv, 320, 64)      # 64 rows x (1 + 4 proposals)
    blocks = _rpa_block_sizes(b["q"], b["cache"], PAGES_PER_SEQ)
    _compile(
        functools.partial(
            ragged_paged_attention, sm_scale=HEAD_DIM ** -0.5, **blocks
        ),
        b["q"], b["cache"], b["kv_lens"], b["pages"], b["cu"], b["nseq"],
    )


# EvaByte's geometry (PR 28): 32 KV heads with one query head each, a
# vocabulary of 320 (not a multiple of 128 lanes), chunk summaries of 16
# rows, 256 pages per sequence (--max-model-len 16384).
def _evabyte_batch(dev, t, s):
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    h = 32
    return dict(
        q=a((t, h, HEAD_DIM), jnp.bfloat16),
        cache=a((530, PAGE, 2 * h, HEAD_DIM), jnp.bfloat16),
        kv_lens=a((s,), jnp.int32), pages=a((s, 256), jnp.int32),
        cu=a((s + 1,), jnp.int32), nseq=a((1,), jnp.int32),
        slots=a((t,), jnp.int32),
    )


@pytest.mark.parametrize("s", [8, 64])
def test_fused_decode_compiles_for_v5e_at_32_kv_heads(v5e, s):
    b = _evabyte_batch(v5e, s, s)
    _compile(
        _decode,
        b["q"], b["q"], b["q"], b["cache"], b["kv_lens"], b["pages"],
        b["slots"],
    )


@pytest.mark.parametrize(
    "page,hkv,dtype,want",
    [(PAGE, 2, jnp.bfloat16, 8), (PAGE, 4, jnp.bfloat16, 8),
     (PAGE, 32, jnp.bfloat16, 2), (80, 12, jnp.bfloat16, 4),
     (100, 5, jnp.float32, 2)],
    ids=["3b", "7b", "evabyte", "c24-bf16-stays-24", "c10-f32-pads-to-16"],
)
def test_decode_pages_per_block_at_the_cells_page_shapes(
    page, hkv, dtype, want
):
    """The page stream's block, derived from the bytes VMEM holds a
    page in (the test of the block buffers above): 8 pages, the cap, at
    the 3B's 64 KB and the 7B's 128 KB pages, 2 at EvaByte's 1 MB —
    what the compiles above and at 32 KV heads prove inside scoped
    VMEM. Off the cells' shapes: 24 bf16 rows count as 24 (a page of 80
    tokens is 480 KB, 4 a block; at bf16's tile of 16 it would be 640
    KB and 2), 10 float32 rows as 16 (100 tokens are 800 KB, 2 a block;
    dense they would be 500 KB and 4)."""
    assert decode_pages_per_block(page, 2 * hkv, HEAD_DIM, dtype) == want


@pytest.mark.parametrize("t", [256, 2048])
def test_fused_prefill_compiles_for_v5e_at_32_kv_heads(v5e, t):
    b = _evabyte_batch(v5e, t, 8)
    _compile(
        lambda q, k, v, cache, lens, pages, cu, nseq, slots:
        gqa_fused_prefill_pallas(
            q, k, v, cache, lens, pages, cu, nseq, slots, None,
            sm_scale=HEAD_DIM ** -0.5, interpret=False,
        ),
        b["q"], b["q"], b["q"], b["cache"], b["kv_lens"], b["pages"],
        b["cu"], b["nseq"], b["slots"],
    )


def test_fused_sampler_compiles_for_v5e_at_vocab_49152(v5e):
    """Ouro-2.6B's vocabulary, at the 8-row bucket its two rows run in."""
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    _compile(
        functools.partial(fused_sample_topk_pallas, interpret=False),
        a((8, 49152), jnp.float32), a((8, 49152), jnp.float32),
        a((8,), jnp.float32), a((8,), jnp.int32),
    )


def test_fused_sampler_compiles_for_v5e_at_vocab_320(v5e):
    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    _compile(
        functools.partial(fused_sample_topk_pallas, interpret=False),
        a((8, 320), jnp.float32), a((8, 320), jnp.float32),
        a((8,), jnp.float32), a((8,), jnp.int32),
    )


@pytest.mark.parametrize("lanes", [8, 136])     # a decode step; T=2048
def test_eva_summary_compiles_for_v5e(v5e, lanes):
    from parallax_tpu.ops.eva import eva_summary_pallas

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    _compile(
        functools.partial(eva_summary_pallas, chunk_size=16,
                          interpret=False),
        a((530, PAGE, 64, HEAD_DIM), jnp.bfloat16),
        a((32, HEAD_DIM), jnp.bfloat16), a((32, HEAD_DIM), jnp.bfloat16),
        a((lanes,), jnp.int32), a((lanes,), jnp.int32),
    )


@pytest.mark.parametrize("s", [8, 64])
def test_ssm_decode_update_compiles_for_v5e(v5e, s):
    """The Mamba decode recurrence at Jamba2-3B's widths (d_inner 5120
    on the lanes, d_state 16, float32): a row's whole state is one
    320 KiB block, fetched and written back by slot index, with the
    ``[d_state, 1]`` columns of B and C broadcast along the lanes."""
    from parallax_tpu.ops.mamba import ssm_decode_update

    def a(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    di, n, slots = 5120, 16, 2 * 64 + 32 + 1
    _compile(
        functools.partial(ssm_decode_update, interpret=False),
        a((s, di)), a((s, di)), a((s, di)), a((s, n)), a((s, n)),
        a((n, di)), a((di,)), a((slots, n, di)), a((s,), jnp.int32),
        a((s,), jnp.int32),
    )


@pytest.mark.parametrize("s", [8, 64])
def test_ssm_conv_update_compiles_for_v5e(v5e, s):
    """The Mamba decode convolution at Jamba2-3B's widths: a slot's
    window is one (8, 5120) tile, shifted in place."""
    from parallax_tpu.ops.mamba import CONV_ROWS, ssm_conv_update

    def a(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    di, k, slots = 5120, 4, 2 * 64 + 32 + 1
    _compile(
        functools.partial(ssm_conv_update, interpret=False),
        a((s, di)), a((k, di)), a((di,)), a((slots, CONV_ROWS, di)),
        a((s,), jnp.int32), a((s,), jnp.int32),
    )


def test_split_sink_decode_compiles_for_v5e(v5e):
    """The split GQA decode kernel (sinks + sliding window) at head_dim
    128; gpt-oss's own head_dim 64 is a recorded gap (docs/kernels.md)."""
    b = _batch(v5e, 64, 8, 64, 64)
    sinks = jax.ShapeDtypeStruct((64,), jnp.float32, sharding=v5e)
    _compile(
        functools.partial(
            gqa_decode_attention_pallas, sm_scale=HEAD_DIM ** -0.5,
            sliding_window=128, use_sinks=True,
        ),
        b["q"], b["cache"], b["kv_lens"], b["pages"], sinks,
    )


def _refused_fused_gqa_head_dim_64(dev):
    """gpt-oss geometry: 64 Q / 8 KV heads of 64 lanes."""
    def a(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    s, hq, hkv, d = 64, 64, 8, 64
    return functools.partial(
        gqa_fused_decode_pallas, sm_scale=d ** -0.5, interpret=False,
    ), (
        a((s, hq, d)), a((s, hkv, d)), a((s, hkv, d)),
        a((NUM_PAGES, PAGE, 2 * hkv, d)), a((s,), jnp.int32),
        a((s, PAGES_PER_SEQ), jnp.int32), a((s,), jnp.int32), None,
    )


def test_latent_decode_compiles_for_v5e(v5e):
    """The streamed latent decode kernel at A.X-K1's shapes (PR 51): 64
    heads, latent rank 512 + 64 rope in rows of 640, 128 rows, 64 pages a
    row (``--max-model-len 4096``), and the pool's ~9,600 pages; the
    cache goes to Mosaic as XLA lays it out, with no re-laid copy."""
    from parallax_tpu.ops.mla import mla_row_width
    from parallax_tpu.ops.mla_pallas import mla_decode_attention_pallas

    def a(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    s, hq, rank, rope, pages = 128, 64, 512, 64, 9600
    width = mla_row_width(rank, rope)
    assert width == 640
    fn = functools.partial(
        mla_decode_attention_pallas, sm_scale=192 ** -0.5,
        kv_lora_rank=rank, interpret=False,
    )
    compiled = jax.jit(fn).lower(
        a((s, hq, rank)), a((s, hq, rope)), a((pages, PAGE, width)),
        a((s,), jnp.int32), a((s, 64), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_expert_gmm_compiles_for_v5e(v5e):
    """The routed experts' grouped matmuls at A.X-K1's widths and the
    tiles ``moe._gmm_tiling`` picks: 128 rows x 8 pairs, 12 held experts
    of 7168 x 2048, the stacks contracted on their last axis."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from parallax_tpu.models.moe import _gmm_tiling

    def a(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    h, inter, held, pairs = 7168, 2048, 12, 1024
    assert _gmm_tiling(pairs, h, inter) == (128, 7168, 256)
    assert _gmm_tiling(pairs, inter, h) == (128, 2048, 1024)
    # 8 rows of the reference's replay: 64 pairs in one tile of 64.
    assert _gmm_tiling(64, h, inter)[0] == 64
    for m in (pairs, 64):
        for k, n in ((h, inter), (inter, h)):
            _compile(
                functools.partial(gmm, transpose_rhs=True,
                                  tiling=_gmm_tiling(m, k, n)),
                a((m, k)), a((held, n, k)), a((held,), jnp.int32),
            )


def test_expert_layer_compiles_for_v5e_with_no_scatter_over_its_rows(v5e):
    """An expert layer's decode-sized call at the A.X-K1 cell's shape (128
    rows x 8 pairs, hidden 7168, 12 of 192 experts held): sort, gather,
    three ``gmm`` calls and the combine of ``[128 x 8, 7168]`` float32
    rows (``moe._combine``) compile, and nothing in the program is a
    scatter that writes ``[rows, hidden]``: a scatter-add applies its
    1,024 updates one after another (PERF.md, PR 53)."""
    from parallax_tpu.config import MoEConfig
    from parallax_tpu.models.moe import moe_ffn

    def a(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    rows, h, inter, experts, held, k = 128, 7168, 2048, 192, 12, 8
    moe = MoEConfig(num_experts=experts, num_experts_per_tok=k,
                    moe_intermediate_size=inter, scoring_func="sigmoid",
                    topk_method="none", routed_scaling_factor=2.5,
                    experts_held=held)
    p = {"gate": {"weight": a((experts, h))},
         "experts": {"gate_proj": a((held, inter, h)),
                     "up_proj": a((held, inter, h)),
                     "down_proj": a((held, h, inter))}}
    text = _compile(
        lambda x, p: moe_ffn(x, p, moe, use_megablox=True), a((rows, h)), p)
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= 3
    scatters = re.findall(r"= \w+\[([\d,]*)\]\S* scatter\(", text)
    assert scatters, "the pattern no longer finds gmm's own small scatters"
    assert f"{rows},{h}" not in scatters


def _refused_fused_mla(dev):
    """DeepSeek-V2-Lite geometry: 16 heads, latent rank 512 + 64 rope."""
    from parallax_tpu.ops.decode_fused_pallas import mla_fused_decode_pallas

    def a(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    s, hq, rank, rope = 64, 16, 512, 64
    return functools.partial(
        mla_fused_decode_pallas, sm_scale=0.1, kv_lora_rank=rank,
        interpret=False,
    ), (
        a((s, hq, rank)), a((s, hq, rope)), a((s, rank)), a((s, rope)),
        a((NUM_PAGES, PAGE, 640)), a((s,), jnp.int32),
        a((s, PAGES_PER_SEQ), jnp.int32), a((s,), jnp.int32),
    )


def _refused_indexer(dev, kind, fused):
    """The DSA / MSA sparse-attention indexers: 64 index heads of 128."""
    from parallax_tpu.ops.decode_fused_pallas import (
        indexer_scores_fused_pallas,
    )
    from parallax_tpu.ops.dsa_pallas import dsa_indexer_scores_decode_pallas
    from parallax_tpu.ops.msa_pallas import msa_token_scores_decode_pallas

    def a(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    s, hi = 64, 64
    q = a((s, hi, HEAD_DIM))
    weights = a((s, hi), jnp.float32) if kind == "dsa" else None
    cache = a((NUM_PAGES, PAGE, 1, HEAD_DIM))
    lens, pages = a((s,), jnp.int32), a((s, PAGES_PER_SEQ), jnp.int32)
    if fused:
        fn = functools.partial(
            indexer_scores_fused_pallas, reduce_kind=kind, interpret=False,
            **({"sm_scale": 0.1} if kind == "msa" else {}),
        )
        return fn, (q, weights, a((s, HEAD_DIM)), cache, lens, pages,
                    a((s,), jnp.int32))
    if kind == "dsa":
        return dsa_indexer_scores_decode_pallas, (
            q, weights, cache, lens, pages)
    return functools.partial(msa_token_scores_decode_pallas, sm_scale=0.1), (
        q, cache, lens, pages)


_TILE_8_128 = "last two dimensions of your block shape are divisible by 8"
REFUSED = {
    # id: (kernel and shapes, what the compiler says)
    # The decode fold's strided load wants 128 lanes (the prefill
    # kernel at this head_dim is refused for its 64-lane slice).
    "fused-gqa-head-dim-64": (
        _refused_fused_gqa_head_dim_64,
        "The last dim size is not 128 in original base memref",
    ),
    # The one-row append: a (1, 640) block of the [S, 640] rows (and
    # behind it a one-row DMA into a [page, 640] tile of 16 rows).
    "fused-mla": (_refused_fused_mla, _TILE_8_128),
    "split-dsa-indexer": (
        functools.partial(_refused_indexer, kind="dsa", fused=False),
        _TILE_8_128,
    ),
    "split-msa-indexer": (
        functools.partial(_refused_indexer, kind="msa", fused=False),
        _TILE_8_128,
    ),
    "fused-dsa-indexer": (
        functools.partial(_refused_indexer, kind="dsa", fused=True),
        _TILE_8_128,
    ),
    "fused-msa-indexer": (
        functools.partial(_refused_indexer, kind="msa", fused=True),
        _TILE_8_128,
    ),
}


@pytest.mark.parametrize("case", list(REFUSED))
def test_recorded_lowering_gap_is_still_refused(v5e, case):
    """The refusals ``kernel_select.fused_lowering_gap`` is written
    around and docs/kernels.md "Compiles for v5e" records as NO, asked
    of the installed compiler again. When one of these starts to
    compile (a kernel was repaired, or the compiler moved), this test
    fails: then lift the gate, the docs row and the ROADMAP entry."""
    build, message = REFUSED[case]
    fn, shapes = build(v5e)
    with pytest.raises(Exception, match=message):
        jax.jit(fn).lower(*shapes).compile()


def _cfg(**kw):
    return normalize_config(dict(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=3584,
        num_hidden_layers=2, num_attention_heads=28, num_key_value_heads=4,
        intermediate_size=18944, vocab_size=152064,
    ), **kw))


def test_tpu_auto_selects_only_kernels_that_lower(monkeypatch):
    """TPU-auto decides from the model config: fused for the dense GQA
    geometry the tests above compile; split for the families whose fused
    kernels Mosaic refuses. Explicit flags are not second-guessed."""
    monkeypatch.setattr(kernel_select, "tpu_available", lambda: True)
    dense = _cfg()
    assert kernel_select.fused_lowering_gap(dense) is None
    assert kernel_select.resolve_decode_fused(None, dense) is True
    assert kernel_select.resolve_prefill_fused(None, dense) is True
    narrow = _cfg(architectures=["GptOssForCausalLM"], head_dim=64,
                  num_attention_heads=64, num_key_value_heads=8,
                  hidden_size=2880)
    assert "head_dim 64" in kernel_select.fused_lowering_gap(narrow)
    assert kernel_select.resolve_decode_fused(None, narrow) is False
    assert kernel_select.resolve_prefill_fused(None, narrow) is False
    assert kernel_select.resolve_decode_fused(True, narrow) is True
    assert kernel_select.resolve_decode_fused(False, dense) is False


def test_tpu_available_does_not_hide_a_broken_backend(monkeypatch):
    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="initialize backend"):
        kernel_select.tpu_available()


def test_a_looped_step_compiles_for_v5e_with_few_prefetches(
        v5e, monkeypatch):
    """The options a looped stack's step programs are compiled with
    (``engine.LOOPED_STEP_XLA_OPTIONS``) are the installed compiler's,
    and do what they are there for: a decode step of two of Ouro-2.6B's
    layers, four passes, at the published widths brings no weight matrix
    to VMEM in slices and holds 5 asynchronous copies, where the
    defaults hold 28 ``slice-start`` and 13 ``copy-start`` (every matrix
    in four slices, every norm vector a copy). The block is the same
    whatever the depth."""
    from parallax_tpu.models.base import BatchInputs
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.engine import LOOPED_STEP_XLA_OPTIONS

    monkeypatch.setattr(kernel_select, "tpu_available", lambda: True)
    cfg = normalize_config(dict(
        architectures=["OuroForCausalLM"], model_type="ouro",
        hidden_size=2048, num_hidden_layers=2, num_attention_heads=16,
        num_key_value_heads=16, head_dim=128, intermediate_size=5632,
        vocab_size=49152, total_ut_steps=4, early_exit_threshold=1,
        rope_theta=1000000, rms_norm_eps=1e-6, tie_word_embeddings=False,
        max_position_embeddings=65536, layer_types=["full_attention"] * 2,
    ))
    model = create_stage_model(cfg, 0, 2, use_pallas=True)

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    def described(tree):
        return jax.tree.map(lambda x: a(x.shape, x.dtype), tree)

    params = described(jax.eval_shape(
        lambda k: model.init_params(k, dtype=jnp.bfloat16),
        jax.random.key(0)))
    kv = described(jax.eval_shape(lambda: model.new_kv_caches(93, PAGE)))
    rows = 8
    inputs = BatchInputs(
        token_ids=a((rows,), jnp.int32), hidden_states=None,
        positions=a((rows,), jnp.int32), kv_lens=a((rows,), jnp.int32),
        page_indices=a((rows, 65), jnp.int32),
        cu_q_lens=a((rows + 1,), jnp.int32), num_seqs=a((1,), jnp.int32),
        slot_mapping=a((rows,), jnp.int32),
        logits_indices=a((rows,), jnp.int32), decode_only=True,
        decode_fused=True, prefill_fused=False)
    lowered = jax.jit(lambda p, k, i: model(p, k, i),
                      donate_argnums=(1,)).lower(params, kv, inputs)
    text = lowered.compile(
        compiler_options=dict(LOOPED_STEP_XLA_OPTIONS)).as_text()
    assert "gqa_fused_decode_pallas" in text
    assert "slice-start" not in text
    assert 1 <= len(re.findall(r" copy-start\(", text)) <= 8
