"""Fused Pallas ragged decode (ops/decode_fused_pallas.py) — interpret-mode
parity against the XLA reference paths, KV-append fusion equality against
the kv_cache_ops scatter, sort-free fused-sampler exactness against
ops/sampling.sample_tokens, and engine-level bit-identity of fused-on vs
fused-off token streams (greedy + seeded, sync + overlap, K=1 and K>1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.models.registry import create_stage_model
from parallax_tpu.ops.attention import _ragged_paged_attention_xla
from parallax_tpu.ops import decode_fused_pallas
from parallax_tpu.ops.decode_fused_pallas import (
    decode_pages_per_block,
    fused_sample_topk_pallas,
    gqa_fused_decode_pallas,
    indexer_scores_fused_pallas,
    mla_fused_decode_pallas,
)
from parallax_tpu.ops.dsa import dsa_indexer_scores_xla, store_index_cache
from parallax_tpu.ops.kv_cache_ops import reshape_and_cache
from parallax_tpu.ops.mla import (
    mla_ragged_attention_xla,
    mla_row_width,
    store_mla_cache,
)
from parallax_tpu.ops.sampling import row_gumbel, sample_tokens
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

# ---------------------------------------------------------------------------
# Shared ragged decode geometry: lens straddling page boundaries, one
# padding row (len 0), one frozen row (live context, slot -1 = no append).
# ---------------------------------------------------------------------------

PAGE = 8
S = 6
LENS = np.array([5, 17, 48, 0, 9, 16], np.int32)   # 48, 16: page-exact
FROZEN_ROW = 4


# The cache dtypes the engine allocates (``kv_dtype``): the GQA fold reads
# a float32 block by a plain strided pair of loads and a bfloat16 one
# through its packed 32-bit words; the float32 cases hold the kernel to
# the oracle tightly, the bfloat16 ones to bf16's grain.
CACHE_DTYPES = [jnp.float32, jnp.bfloat16]
CACHE_DTYPE_IDS = ["f32", "bf16"]
TOL = {
    jnp.float32: dict(atol=2e-5, rtol=2e-5),
    jnp.bfloat16: dict(atol=2e-2, rtol=2e-2),
}


def _f32(x) -> np.ndarray:
    return np.asarray(x, np.float32)


def _geometry(num_extra_pages: int = 0):
    pps = 6
    pages = np.zeros((S, pps), np.int32)
    used = 1
    for i, n in enumerate(LENS):
        npg = (int(n) + PAGE - 1) // PAGE
        pages[i, :npg] = np.arange(used, used + npg)
        used += npg
    slot = np.full((S,), -1, np.int32)
    for i, n in enumerate(LENS):
        if n > 0 and i != FROZEN_ROW:
            slot[i] = pages[i, (int(n) - 1) // PAGE] * PAGE + (
                int(n) - 1
            ) % PAGE
    return (
        used + num_extra_pages,
        jnp.asarray(LENS),
        jnp.asarray(pages),
        jnp.asarray(slot),
    )


@pytest.mark.parametrize(
    "window,sinks_on,cap",
    [(None, False, None), (16, False, None), (None, True, None),
     (None, False, 30.0), (16, True, None)],
)
@pytest.mark.parametrize("dtype", CACHE_DTYPES, ids=CACHE_DTYPE_IDS)
def test_gqa_fused_parity_and_append(window, sinks_on, cap, dtype):
    rng = np.random.default_rng(0)
    hq, hkv, d = 4, 2, 16
    num_pages, lens, pages, slot = _geometry()
    q = jnp.asarray(rng.normal(size=(S, hq, d)), dtype)
    k_new = jnp.asarray(rng.normal(size=(S, hkv, d)), dtype)
    v_new = jnp.asarray(rng.normal(size=(S, hkv, d)), dtype)
    cache = jnp.asarray(
        rng.normal(size=(num_pages, PAGE, 2 * hkv, d)), dtype
    )
    sinks = (
        jnp.asarray(rng.normal(size=(hq,)), jnp.float32)
        if sinks_on else None
    )
    out, cache_f = gqa_fused_decode_pallas(
        q, k_new, v_new, cache, lens, pages, slot, sinks,
        sm_scale=d ** -0.5, sliding_window=window, soft_cap=cap,
        use_sinks=sinks_on, interpret=True,
    )
    # Reference: separate scatter dispatch, then the XLA oracle.
    cache_ref = reshape_and_cache(cache, k_new, v_new, slot)
    ref = _ragged_paged_attention_xla(
        q, cache_ref, lens, pages,
        jnp.arange(S + 1, dtype=jnp.int32), jnp.asarray([S], jnp.int32),
        sm_scale=d ** -0.5, sliding_window=window, soft_cap=cap,
        sinks=sinks,
    )
    # KV-append fusion == the kv_cache_ops scatter, bit for bit
    # (including the skipped frozen/padding rows).
    assert np.array_equal(_f32(cache_f), _f32(cache_ref))
    np.testing.assert_allclose(_f32(out), _f32(ref), **TOL[dtype])
    # Padding row outputs exact zeros.
    assert np.all(_f32(out)[3] == 0.0)


def test_mla_fused_parity_and_append():
    rng = np.random.default_rng(1)
    hq, r, dr = 4, 32, 8
    num_pages, lens, pages, slot = _geometry()
    ql = jnp.asarray(rng.normal(size=(S, hq, r)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(S, hq, dr)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(S, r)), jnp.float32)
    kpe = jnp.asarray(rng.normal(size=(S, dr)), jnp.float32)
    cache = jnp.asarray(
        rng.normal(size=(num_pages, PAGE, mla_row_width(r, dr))), jnp.float32
    )
    out, cache_f = mla_fused_decode_pallas(
        ql, qp, lat, kpe, cache, lens, pages, slot,
        sm_scale=0.17, kv_lora_rank=r, interpret=True,
    )
    cache_ref = store_mla_cache(cache, lat, kpe, slot)
    ref = mla_ragged_attention_xla(
        ql, qp, cache_ref, lens, pages,
        jnp.arange(S + 1, dtype=jnp.int32), jnp.asarray([S], jnp.int32),
        sm_scale=0.17, kv_lora_rank=r,
    )
    assert np.array_equal(np.asarray(cache_f), np.asarray(cache_ref))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("kind", ["dsa", "msa"])
def test_indexer_fused_parity_and_append(kind):
    rng = np.random.default_rng(2)
    hi, di = 4, 16
    num_pages, lens, pages, slot = _geometry()
    q = jnp.asarray(rng.normal(size=(S, hi, di)), jnp.float32)
    w = jnp.asarray(np.abs(rng.normal(size=(S, hi))), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(S, di)), jnp.float32)
    cache = jnp.asarray(
        rng.normal(size=(num_pages, PAGE, 1, di)), jnp.float32
    )
    sc, cache_f = indexer_scores_fused_pallas(
        q, w if kind == "dsa" else None, k_new, cache, lens, pages, slot,
        reduce_kind=kind, sm_scale=0.25, interpret=True,
    )
    cache_ref = store_index_cache(cache, k_new, slot)
    assert np.array_equal(np.asarray(cache_f), np.asarray(cache_ref))
    sc = np.asarray(sc)
    if kind == "dsa":
        ref = np.asarray(dsa_indexer_scores_xla(
            q, w, cache_ref, lens, pages,
            jnp.arange(S + 1, dtype=jnp.int32),
        ))
    else:
        from parallax_tpu.ops.msa_pallas import (
            msa_token_scores_decode_pallas,
        )

        # Oracle: the split page-grid scorer (itself tested against the
        # XLA path in test_msa.py) on the post-scatter cache.
        ref = np.asarray(msa_token_scores_decode_pallas(
            q, cache_ref, lens, pages, sm_scale=0.25, interpret=True,
        ))
    # Beyond-context slots must be EXACT -inf on both (the top-k
    # facades' dense-row detection depends on it).
    assert np.array_equal(np.isfinite(sc), np.isfinite(ref))
    mask = np.isfinite(ref)
    np.testing.assert_allclose(sc[mask], ref[mask], atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# The blocked stream's edges. The core moves B pages a block into one of
# two buffers (B derived from the page's shape: 8 at every toy shape
# here, 2 at EvaByte's real page); these rows sit on every edge of that
# discipline. Page-table entries past a row's valid pages name a page of
# NaNs (a dereference would poison the row through 0 * NaN) or lie
# outside the pool altogether.
# ---------------------------------------------------------------------------

B = 8                       # decode_pages_per_block at the toy shapes
BLOCK = B * PAGE
#   context            what it is an edge of
EDGE_LENS = [
    1,                    # one token: the append is the whole context
    PAGE,                 # exactly one page; append in the row's only page
    BLOCK,                # exactly B pages; append in the block's last page
    0,                    # padding row between live rows
    BLOCK + 1,            # B pages + 1: append in the first page of the last block
    2 * BLOCK - PAGE,     # 2B - 1 pages, page-exact
    2 * BLOCK - PAGE - 3,  # 2B - 1 pages, ragged
    37,                   # frozen row (slot -1) between live rows
    2 * BLOCK,            # two full blocks; append in the last page of the last
    2 * BLOCK + 5,        # a third block of one page
    BLOCK - 1,            # one block, last page one short
    3,
]
EDGE_FROZEN = 7
POISON = 1                  # page of NaNs; page 0 is the null page


def _edge_geometry(lens=EDGE_LENS, frozen=EDGE_FROZEN, page=PAGE):
    """(num_pages, kv_lens, table for the kernel, table for the XLA
    oracle, slots): every row its own pages from 2 on; the entries past
    a row's valid pages alternate between the NaN page and an index far
    outside the pool (the oracle's copy names the null page there: it
    gathers every entry and masks)."""
    pps = max(-(-n // page) for n in lens) + 3
    pages = np.zeros((len(lens), pps), np.int32)
    safe = np.zeros_like(pages)
    used = 2
    slot = np.full((len(lens),), -1, np.int32)
    for i, n in enumerate(lens):
        npg = -(-n // page)
        pages[i, :npg] = safe[i, :npg] = np.arange(used, used + npg)
        pages[i, npg:] = [POISON if j % 2 == 0 else 2 ** 30
                          for j in range(pps - npg)]
        used += npg
        if n > 0 and i != frozen:
            slot[i] = pages[i, (n - 1) // page] * page + (n - 1) % page
    return (used, jnp.asarray(np.asarray(lens, np.int32)),
            jnp.asarray(pages), jnp.asarray(safe), jnp.asarray(slot))


def _poisoned(rng, shape, dtype=jnp.float32):
    cache = rng.normal(size=shape).astype(np.float32)
    cache[POISON] = np.nan
    return jnp.asarray(cache, dtype)


EDGE_HEADS = [(16, 2), (28, 4), (32, 32), (7, 1)]
EDGE_HEAD_IDS = ["16q2kv", "28q4kv", "32q32kv", "tp4-7q1kv"]


def _edge_inputs(rng, hq, hkv, num_pages, dtype, d=16):
    """(q, k_new, v_new, poisoned cache) for the EDGE_LENS rows."""
    s = len(EDGE_LENS)
    q = jnp.asarray(rng.normal(size=(s, hq, d)), dtype)
    k_new = jnp.asarray(rng.normal(size=(s, hkv, d)), dtype)
    v_new = jnp.asarray(rng.normal(size=(s, hkv, d)), dtype)
    cache = _poisoned(rng, (num_pages, PAGE, 2 * hkv, d), dtype)
    return q, k_new, v_new, cache


def test_edge_rows_sit_on_the_block_edges_they_name():
    """The geometry above is only worth its name while B is what the
    core derives at these shapes."""
    for c, w in ((4, 16), (8, 16), (64, 16), (1, 40)):
        assert decode_pages_per_block(PAGE, c, w, jnp.float32) == B


@pytest.mark.parametrize(
    "window,sinks_on,cap",
    [(None, False, None),
     (44, False, None),            # window starts mid-block, mid-page
     (None, True, 30.0),
     (BLOCK + 3, True, 30.0)],     # window longer than one block
    ids=["plain", "window-mid-block", "sinks-cap", "window-sinks-cap"],
)
@pytest.mark.parametrize("hq,hkv", EDGE_HEADS, ids=EDGE_HEAD_IDS)
@pytest.mark.parametrize("dtype", CACHE_DTYPES, ids=CACHE_DTYPE_IDS)
def test_gqa_fused_block_edges(dtype, hq, hkv, window, sinks_on, cap):
    rng = np.random.default_rng(7)
    d = 16
    num_pages, lens, pages, safe, slot = _edge_geometry()
    s = len(EDGE_LENS)
    q, k_new, v_new, cache = _edge_inputs(rng, hq, hkv, num_pages, dtype)
    sinks = (
        jnp.asarray(rng.normal(size=(hq,)), jnp.float32)
        if sinks_on else None
    )
    out, cache_f = gqa_fused_decode_pallas(
        q, k_new, v_new, cache, lens, pages, slot, sinks,
        sm_scale=d ** -0.5, sliding_window=window, soft_cap=cap,
        use_sinks=sinks_on, interpret=True,
    )
    cache_ref = reshape_and_cache(cache, k_new, v_new, slot)
    # The oracle gathers every table entry: hand it the pool without
    # the NaN page's payload, which no valid entry names.
    ref = _ragged_paged_attention_xla(
        q, cache_ref.at[POISON].set(0.0), lens, safe,
        jnp.arange(s + 1, dtype=jnp.int32), jnp.asarray([s], jnp.int32),
        sm_scale=d ** -0.5, sliding_window=window, soft_cap=cap,
        sinks=sinks,
    )
    assert np.array_equal(_f32(cache_f), _f32(cache_ref), equal_nan=True)
    out = _f32(out)
    assert np.all(np.isfinite(out)), "a page past the valid ones was read"
    np.testing.assert_allclose(out, _f32(ref), **TOL[dtype])
    assert np.all(out[EDGE_LENS.index(0)] == 0.0)


def _kv_head_transposed(rows_ref, h):
    """The read the strided loads replaced (PR 29's), kept here as their
    witness: the block loaded whole, its heads brought to the front."""
    by_head = jnp.swapaxes(rows_ref[...], 0, 1)       # [2*Hkv, N, D]
    return by_head[2 * h], by_head[2 * h + 1]


@pytest.mark.parametrize("hq,hkv", EDGE_HEADS, ids=EDGE_HEAD_IDS)
@pytest.mark.parametrize("dtype", CACHE_DTYPES, ids=CACHE_DTYPE_IDS)
def test_gqa_strided_head_read_is_the_transposed_read(
    monkeypatch, dtype, hq, hkv
):
    """The strided loads hand the two dots a head the very bits the
    block's transposition did: same output, same cache, bit for bit,
    over the block-edge rows with a window, sinks and a soft cap."""
    rng = np.random.default_rng(11)
    d = 16
    num_pages, lens, pages, _, slot = _edge_geometry()
    q, k_new, v_new, cache = _edge_inputs(rng, hq, hkv, num_pages, dtype)
    sinks = jnp.asarray(rng.normal(size=(hq,)), jnp.float32)

    def run():
        # Unjitted: the fold looks its read up while it is traced.
        return gqa_fused_decode_pallas.__wrapped__(
            q, k_new, v_new, cache, lens, pages, slot, sinks,
            sm_scale=d ** -0.5, sliding_window=BLOCK + 3, soft_cap=30.0,
            use_sinks=True, interpret=True,
        )

    out_s, cache_s = run()
    monkeypatch.setattr(
        decode_fused_pallas, "_kv_head_strided", _kv_head_transposed
    )
    out_t, cache_t = run()
    assert np.array_equal(_f32(out_s), _f32(out_t))
    assert np.array_equal(_f32(cache_s), _f32(cache_t), equal_nan=True)
    assert np.all(np.isfinite(_f32(out_s)))


def test_gqa_fused_block_edges_at_two_pages_a_block():
    """EvaByte's real page ([64, 64, 128] bf16 = 1 MB) derives B = 2:
    contexts of one page, B pages, B pages + 1, 2B pages - 1 and a
    third block, at 32 KV heads with one query head each."""
    rng = np.random.default_rng(8)
    h, d, page = 32, 128, 64
    assert decode_pages_per_block(page, 2 * h, d, jnp.bfloat16) == 2
    lens = [page, 2 * page, 2 * page + 1, 0, 3 * page - 5, 4 * page + 9, 1]
    num_pages, kv_lens, pages, safe, slot = _edge_geometry(
        lens, frozen=4, page=page
    )
    s = len(lens)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    q, k_new, v_new = arr(s, h, d), arr(s, h, d), arr(s, h, d)
    cache = _poisoned(rng, (num_pages, page, 2 * h, d), jnp.bfloat16)
    out, cache_f = gqa_fused_decode_pallas(
        q, k_new, v_new, cache, kv_lens, pages, slot, None,
        sm_scale=d ** -0.5, interpret=True,
    )
    cache_ref = reshape_and_cache(cache, k_new, v_new, slot)
    ref = _ragged_paged_attention_xla(
        q, cache_ref.at[POISON].set(0.0), kv_lens, safe,
        jnp.arange(s + 1, dtype=jnp.int32), jnp.asarray([s], jnp.int32),
        sm_scale=d ** -0.5, sliding_window=None, soft_cap=None, sinks=None,
    )
    assert np.array_equal(
        np.asarray(cache_f, np.float32), np.asarray(cache_ref, np.float32),
        equal_nan=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_mla_fused_block_edges():
    rng = np.random.default_rng(9)
    hq, r, dr = 4, 32, 8
    num_pages, lens, pages, safe, slot = _edge_geometry()
    s = len(EDGE_LENS)
    ql = jnp.asarray(rng.normal(size=(s, hq, r)), jnp.float32)
    qp = jnp.asarray(rng.normal(size=(s, hq, dr)), jnp.float32)
    lat = jnp.asarray(rng.normal(size=(s, r)), jnp.float32)
    kpe = jnp.asarray(rng.normal(size=(s, dr)), jnp.float32)
    cache = _poisoned(rng, (num_pages, PAGE, mla_row_width(r, dr)))
    out, cache_f = mla_fused_decode_pallas(
        ql, qp, lat, kpe, cache, lens, pages, slot,
        sm_scale=0.17, kv_lora_rank=r, interpret=True,
    )
    cache_ref = store_mla_cache(cache, lat, kpe, slot)
    ref = mla_ragged_attention_xla(
        ql, qp, cache_ref.at[POISON].set(0.0), lens, safe,
        jnp.arange(s + 1, dtype=jnp.int32), jnp.asarray([s], jnp.int32),
        sm_scale=0.17, kv_lora_rank=r,
    )
    assert np.array_equal(
        np.asarray(cache_f), np.asarray(cache_ref), equal_nan=True
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("kind", ["dsa", "msa"])
def test_indexer_fused_block_edges(kind):
    """The score row is as long as the page table (19 pages here: not a
    multiple of B) though the stream writes whole blocks."""
    rng = np.random.default_rng(10)
    hi, di = 4, 16
    num_pages, lens, pages, safe, slot = _edge_geometry()
    s = len(EDGE_LENS)
    assert pages.shape[1] % B
    q = jnp.asarray(rng.normal(size=(s, hi, di)), jnp.float32)
    w = jnp.asarray(np.abs(rng.normal(size=(s, hi))), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(s, di)), jnp.float32)
    cache = _poisoned(rng, (num_pages, PAGE, 1, di))
    sc, cache_f = indexer_scores_fused_pallas(
        q, w if kind == "dsa" else None, k_new, cache, lens, pages, slot,
        reduce_kind=kind, sm_scale=0.25, interpret=True,
    )
    cache_ref = store_index_cache(cache, k_new, slot)
    assert np.array_equal(
        np.asarray(cache_f), np.asarray(cache_ref), equal_nan=True
    )
    clean = cache_ref.at[POISON].set(0.0)
    if kind == "dsa":
        ref = np.asarray(dsa_indexer_scores_xla(
            q, w, clean, lens, safe, jnp.arange(s + 1, dtype=jnp.int32),
        ))
    else:
        from parallax_tpu.ops.msa_pallas import (
            msa_token_scores_decode_pallas,
        )

        ref = np.asarray(msa_token_scores_decode_pallas(
            q, clean, lens, safe, sm_scale=0.25, interpret=True,
        ))
    sc = np.asarray(sc)
    assert sc.shape == ref.shape == (s, pages.shape[1] * PAGE)
    assert not np.any(np.isnan(sc)), "a page past the valid ones was read"
    assert np.array_equal(np.isfinite(sc), np.isfinite(ref))
    mask = np.isfinite(ref)
    np.testing.assert_allclose(sc[mask], ref[mask], atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# Fused sampler: exact draw equality with the XLA sampler.
# ---------------------------------------------------------------------------


def test_fused_sampler_exact_vs_xla():
    rng = np.random.default_rng(3)
    b, v = 8, 257
    logits = jnp.asarray(rng.normal(size=(b, v)) * 3.0, jnp.float32)
    temp = jnp.asarray([0.0, 0.7, 1.0, 1.3, 0.0, 0.5, 2.0, 1.0],
                       jnp.float32)
    top_k = jnp.asarray([0, 5, 1, 50, 0, 0, 400, 7], jnp.int32)
    ones, zeros = jnp.ones((b,), jnp.float32), jnp.zeros((b,), jnp.float32)
    key = jax.random.key(42)
    for seeds, steps in [
        (None, None),
        (jnp.asarray([3, 7, -1, 11, -1, 5, -1, 9], jnp.int32),
         jnp.asarray(np.arange(b), jnp.int32)),
    ]:
        kwargs = {} if seeds is None else dict(seeds=seeds, out_steps=steps)
        ref = sample_tokens(logits, key, temp, top_k, ones, zeros, **kwargs)
        g = row_gumbel(key, b, v, seeds, steps)
        fused = fused_sample_topk_pallas(
            logits, g, temp, top_k, interpret=True
        )
        assert np.array_equal(np.asarray(ref), np.asarray(fused))


def test_fused_sampler_topk_tie_semantics():
    """Value-threshold top-k keeps ties at the k-th value in BOTH the
    fused kernel and the XLA sampler — the exactness contract holds on
    adversarial tied logits too."""
    v = 64
    row = np.full((v,), -5.0, np.float32)
    row[[4, 9, 23]] = 2.0          # three-way tie at the top
    row[30] = 1.0
    logits = jnp.asarray(np.stack([row, row]), jnp.float32)
    temp = jnp.asarray([1.0, 1.0], jnp.float32)
    top_k = jnp.asarray([2, 1], jnp.int32)   # k-th value tied both ways
    key = jax.random.key(5)
    ref = sample_tokens(
        logits, key, temp, top_k,
        jnp.ones((2,), jnp.float32), jnp.zeros((2,), jnp.float32),
    )
    g = row_gumbel(key, 2, v)
    fused = fused_sample_topk_pallas(logits, g, temp, top_k, interpret=True)
    assert np.array_equal(np.asarray(ref), np.asarray(fused))
    # All tied tokens are candidates (threshold semantics): the choice
    # always lands on one of them.
    assert int(np.asarray(fused)[0]) in (4, 9, 23)
    assert int(np.asarray(fused)[1]) in (4, 9, 23)


# ---------------------------------------------------------------------------
# Engine-level: fused-on vs fused-off streams bit-identical.
# ---------------------------------------------------------------------------

GQA_CFG = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"], hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, vocab_size=199, max_position_embeddings=512,
    tie_word_embeddings=False,
))

PROMPTS = [[3, 14, 15, 92, 65], [7, 21, 108], [42] * 9]


def _run_engine(model, params, *, fused, lookahead, overlap=True,
                temp=0.0, seed=None, top_p=1.0, max_new=11):
    eng = StageEngine(model, params, EngineConfig(
        page_size=8, num_pages=128, max_model_len=256, kv_dtype="float32",
        decode_lookahead=lookahead, decode_fused=fused,
        overlap_steps=overlap,
    ))
    pipe = InProcessPipeline([eng])
    reqs = []
    for i, pr in enumerate(PROMPTS):
        req = Request(
            f"r{i}", prompt_ids=list(pr),
            sampling_params=SamplingParams(
                temperature=temp, max_new_tokens=max_new, seed=seed,
                top_k=5 if temp else 0, top_p=top_p,
            ),
        )
        reqs.append(req)
        pipe.submit(req)
    pipe.run_until_complete()
    return [r.output_ids for r in reqs], eng


@pytest.fixture(scope="module")
def gqa_model():
    model = StageModel(GQA_CFG, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    return model, params


@pytest.mark.parametrize("lookahead", [1, 8])
@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("temp,seed", [(0.0, None), (0.8, 77)])
def test_engine_streams_bit_identical(gqa_model, lookahead, overlap,
                                      temp, seed):
    model, params = gqa_model
    off, _ = _run_engine(model, params, fused=False, lookahead=lookahead,
                         overlap=overlap, temp=temp, seed=seed)
    on, eng = _run_engine(model, params, fused=True, lookahead=lookahead,
                          overlap=overlap, temp=temp, seed=seed)
    assert on == off
    assert eng.kernel_dispatch_summary()["impl"] == "pallas-fused"
    if lookahead > 1:
        # The fused-sampler multistep variant (or argmax variant for
        # greedy) actually compiled and ran.
        assert (8, temp > 0.0, temp > 0.0, ()) in eng._jit_multistep
        assert any(
            path == "multistep" and impl == "pallas-fused"
            for impl, path in eng._kernel_counts
        )


def test_engine_top_p_rows_force_split_sampler(gqa_model):
    """A top-p row keeps the split (sort-based) sampler — registered
    gate — while fused attention stays active; streams remain identical
    to the fused-off engine."""
    model, params = gqa_model
    on, eng = _run_engine(
        model, params, fused=True, lookahead=8, temp=0.9, seed=123,
        top_p=0.8,
    )
    off, _ = _run_engine(model, params, fused=False, lookahead=8,
                         temp=0.9, seed=123, top_p=0.8)
    assert on == off
    # Split-sampler multistep variant (fused_sample=False) compiled,
    # and the warn-once gate site fired.
    assert (8, True, False, ()) in eng._jit_multistep
    assert eng._warned_split_sampling


def test_engine_large_top_k_rows_force_split_sampler(gqa_model):
    """top_k beyond FUSED_SAMPLE_TOPK_MAX keeps the split sampler (the
    fused threshold extraction is O(top_k * vocab)); streams stay
    identical to the fused-off engine."""
    from parallax_tpu.ops.decode_fused_pallas import FUSED_SAMPLE_TOPK_MAX

    model, params = gqa_model

    def run(fused):
        eng = StageEngine(model, params, EngineConfig(
            page_size=8, num_pages=128, max_model_len=256,
            kv_dtype="float32", decode_lookahead=8, decode_fused=fused,
        ))
        pipe = InProcessPipeline([eng])
        reqs = []
        for i, pr in enumerate(PROMPTS):
            req = Request(
                f"r{i}", prompt_ids=list(pr),
                sampling_params=SamplingParams(
                    temperature=0.9, max_new_tokens=9, seed=31,
                    top_k=FUSED_SAMPLE_TOPK_MAX + 100,
                ),
            )
            reqs.append(req)
            pipe.submit(req)
        pipe.run_until_complete()
        return [r.output_ids for r in reqs], eng

    on, eng = run(True)
    off, _ = run(False)
    assert on == off
    assert (8, True, False, ()) in eng._jit_multistep   # split-sampler variant
    assert eng._warned_split_sampling


def test_engine_mla_fused_stream_identical():
    """Model plumbing beyond plain GQA: the MLA fused kernel family
    (deepseek_v3) produces bit-identical greedy streams."""
    cfg = normalize_config(dict(
        architectures=["DeepseekV3ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=1, n_group=2, topk_group=1,
        routed_scaling_factor=1.0, norm_topk_prob=True,
        scoring_func="sigmoid", first_k_dense_replace=1, moe_layer_freq=1,
        vocab_size=199, max_position_embeddings=512, rms_norm_eps=1e-6,
        rope_theta=10000.0, rope_interleave=True,
        tie_word_embeddings=False, attention_bias=False,
    ))
    model = create_stage_model(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(1), dtype=jnp.float32)
    off, _ = _run_engine(model, params, fused=False, lookahead=4,
                         max_new=7)
    on, eng = _run_engine(model, params, fused=True, lookahead=4,
                          max_new=7)
    assert on == off
    assert eng.kernel_dispatch_summary()["decode_fused"] is True


def test_kernel_dispatch_summary_and_counter(gqa_model):
    from parallax_tpu.obs.registry import get_registry

    model, params = gqa_model
    _, eng = _run_engine(model, params, fused=True, lookahead=8)
    summary = eng.kernel_dispatch_summary()
    assert summary["impl"] == "pallas-fused"
    assert summary["decode_fused"] is True
    # What the page stream ran at, derived from this stage's page
    # ([8, 2 * 2, 16] float32): nothing set it.
    assert summary["decode_pages_per_block"] == decode_pages_per_block(
        8, 4, 16, jnp.float32
    ) == 8
    off = _run_engine(
        model, params, fused=False, lookahead=8
    )[1].kernel_dispatch_summary()
    assert off["decode_pages_per_block"] is None
    assert any(k.startswith("pallas-fused/") for k in
               summary["dispatch_total"])
    # The registry counter carries the same series for /metrics.
    text = get_registry().render()
    assert "parallax_attn_kernel_dispatch_total" in text
    assert 'impl="pallas-fused"' in text
