"""EvaByte (EVA chunked linearized attention) through the engine, at toy
widths on the CPU: window 32, chunk 4, page 8, vocabulary 320.

The oracle is the benchmark's plain reference
(``benchmarks/references/evabyte.py``: the equations computed directly
from every key, no cache, no paging, nothing of the program). Everything
is compared at the level of log-probabilities, never of sampled tokens.
"""

import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.references import evabyte as ref  # noqa: E402
from parallax_tpu.config import EvaConfig, normalize_config  # noqa: E402
from parallax_tpu.models.registry import (  # noqa: E402
    MODEL_REGISTRY,
    create_stage_model,
)
from parallax_tpu.models.base import StageModel  # noqa: E402
from parallax_tpu.runtime.cache_manager import (  # noqa: E402
    EvaCacheManager,
    derive_num_pages,
    make_cache_manager,
)
from parallax_tpu.runtime.engine import EngineConfig, StageEngine  # noqa: E402
from parallax_tpu.runtime.pipeline import InProcessPipeline  # noqa: E402
from parallax_tpu.runtime.request import Request, SamplingParams  # noqa: E402

W, C, PAGE = 32, 4, 8
HF = dict(
    model_type="evabyte", attention_class="eva", hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=128, vocab_size=320, window_size=W, chunk_size=C,
    num_pred_heads=8, rope_theta=100000, rms_norm_eps=1e-5,
    norm_add_unit_offset=True, fp32_skip_add=True, fp32_logits=True,
    max_position_embeddings=512, tie_word_embeddings=False,
)
# float32 weights and cache on the CPU: the program and the reference
# differ by reduction order only (measured 1e-6; a dropped summary, a
# swapped mu/phi or a missing norm offset moves a logprob by > 1e-2).
TOL_F32 = 2e-5


@functools.lru_cache(maxsize=None)
def stage(dtype="float32", **over):
    cfg = normalize_config(dict(HF, **over))
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers,
                               use_pallas=False)
    return model, model.init_params(jax.random.key(0),
                                    dtype=jnp.dtype(dtype))


def engine_for(model, params, k=1, chunk=16, pages=64, **kw):
    return StageEngine(model, params, EngineConfig(
        page_size=PAGE, num_pages=pages, max_model_len=256,
        kv_dtype="float32", prefill_chunk_size=chunk,
        max_num_tokens_per_batch=64, decode_lookahead=k, **kw))


def generate(engine, prompts, n_new, **sampling):
    pipe = InProcessPipeline([engine])
    reqs = [Request(f"r{i}", prompt_ids=list(p), sampling_params=SamplingParams(
        temperature=0.0, max_new_tokens=n_new, ignore_eos=True,
        logprobs=True, **sampling)) for i, p in enumerate(prompts)]
    for r in reqs:
        pipe.submit(r)
    pipe.run_until_complete()
    return reqs


def reference_logprobs(params, hf, prompt, tokens, **kw):
    """The reference's logprob of each of ``tokens`` in the context of
    the prompt and the tokens before it (teacher-forced)."""
    ids = np.asarray([list(prompt) + list(tokens)], np.int32)
    out = []
    for step, tok in enumerate(tokens):
        logits = ref.logits_at(params, hf, ids,
                               np.asarray([len(prompt) + step - 1]), **kw)
        out.append(float(jax.nn.log_softmax(logits, -1)[0, tok]))
    return out


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, 320, n).tolist()


# -- (a) the system against the reference ---------------------------------

CASES = {
    "shorter-than-a-window": (20, 5, 16, 1),
    "prompt-ends-on-a-boundary": (32, 6, 16, 1),
    "one-boundary-chunk-divides": (50, 4, 16, 1),
    "one-boundary-chunk-does-not-divide": (50, 4, 12, 1),
    "three-boundaries-chunk-divides": (100, 4, 16, 1),
    "three-boundaries-chunk-does-not-divide": (100, 4, 12, 8),
    "whole-window-chunks": (70, 3, 64, 1),
    "decode-crosses-chunk-and-window-k1": (27, 14, 16, 1),
    "decode-crosses-chunk-and-window-k8": (27, 14, 16, 8),
    "decode-rolls-over-twice-k8": (60, 40, 16, 8),
    "decode-starts-on-a-boundary-k8": (63, 12, 16, 8),
}


@pytest.mark.parametrize("case", list(CASES))
def test_engine_matches_the_reference(case):
    plen, n_new, chunk, k = CASES[case]
    model, params = stage()
    engine = engine_for(model, params, k=k, chunk=chunk)
    prompt = prompt_of(plen)
    (req,) = generate(engine, [prompt], n_new)
    want = reference_logprobs(params, HF, prompt, req.output_ids)
    np.testing.assert_allclose(req.output_logprobs, want, atol=TOL_F32)
    # The host rolls a window over when the next plan starts past it; a
    # row that ends inside the K-step window that crossed (on the
    # device's second table) is released without that last count.
    crossed = (plen + n_new - 2) // W
    assert crossed - (k > 1) <= engine.cache.rollovers <= crossed
    assert engine.cache.num_free_pages == 63          # all but the null page


def test_concurrent_rows_roll_over_at_different_steps():
    """Four rows of different lengths through K=8 windows one window
    ahead of the host: each crosses its boundaries at its own step."""
    model, params = stage()
    engine = engine_for(model, params, k=8, pages=128)
    prompts = [prompt_of(n, seed=n) for n in (9, 30, 45, 61)]
    reqs = generate(engine, prompts, 40)
    for prompt, req in zip(prompts, reqs):
        want = reference_logprobs(params, HF, prompt, req.output_ids)
        np.testing.assert_allclose(req.output_logprobs, want, atol=TOL_F32)
    assert engine.cache.num_free_pages == 127


@pytest.mark.parametrize("wrong", ["no-summaries", "mu-phi-swapped"])
def test_a_wrong_reference_fails_the_tolerance(wrong):
    """The comparison is tight enough to notice the mathematics: the
    reference without its summaries, or with mu and phi exchanged, is
    off by far more than ``TOL_F32`` once a summary is visible."""
    model, params = stage()
    prompt = prompt_of(50)
    (req,) = generate(engine_for(model, params), [prompt], 4)
    if wrong == "no-summaries":
        got = reference_logprobs(params, HF, prompt, req.output_ids,
                                 with_summaries=False)
    else:
        swapped = jax.tree.map(lambda x: x, params)
        for layer in swapped["layers"]:
            a = layer["self_attn"]
            a["adaptive_mu_k"], a["adaptive_phi"] = (
                a["adaptive_phi"], a["adaptive_mu_k"])
        got = reference_logprobs(swapped, HF, prompt, req.output_ids)
    gap = np.max(np.abs(np.asarray(got) - np.asarray(req.output_logprobs)))
    assert gap > 100 * TOL_F32


def updates_after_four_layers(fp32_residual, prompt, scale=2048.0):
    """What four blocks add to the embedding, by the program (bfloat16
    weights, one prefill step through ``StageModel``) and by the
    reference, for a stream ``scale`` times its usual size."""
    from parallax_tpu.runtime.batch import BucketSpec, assemble
    from parallax_tpu.runtime.scheduler import BatchPlan, ScheduledSeq

    hf = dict(HF, num_hidden_layers=5, fp32_skip_add=fp32_residual)
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, 4, use_pallas=False)   # not last
    params = model.init_params(jax.random.key(0), dtype=jnp.bfloat16)
    embed = params["embed_tokens"]["weight"].astype(jnp.float32) * scale
    params["embed_tokens"]["weight"] = embed.astype(jnp.bfloat16)
    cm = EvaCacheManager(PAGE, 64, cfg.eva)
    req = Request("r", prompt_ids=prompt,
                  sampling_params=SamplingParams(max_new_tokens=1))
    assert cm.allocate_for_prompt(req)
    n = len(prompt)
    inputs = assemble(
        BatchPlan([ScheduledSeq(req, n, prompt, n)]),
        BucketSpec.build(64, 8, 256, PAGE), PAGE, eva=cfg.eva)
    x, _ = model(params, model.new_kv_caches(64, PAGE, jnp.float32), inputs)
    x0 = params["embed_tokens"]["weight"][jnp.asarray(prompt)].astype(
        jnp.float32)
    want = x0
    for lp in params["layers"]:
        want = ref.layer_forward(
            lp, want, heads=4, theta=1e5, eps=1e-5, window=W, chunk=C)
    return np.asarray(x[:n], np.float32) - x0, np.asarray(want - x0)


def test_a_bf16_residual_fails_where_the_float32_one_passes():
    """``fp32_skip_add``: with the stream carried in float32 what the
    blocks add up to is the reference's within bfloat16 matmul noise
    (a few 1e-3 of its size); carried in bfloat16 every add rounds to 8
    bits of the *stream*, and a stream some 100 times its updates (as a deep
    model's becomes) loses them. At toy widths the chosen token's
    logprob does not show this (measured 0.006 against 0.011 at 8
    layers: both inside matmul noise), so the test reads the stream."""
    prompt = prompt_of(24)
    err = {}
    for fp32 in (True, False):
        got, want = updates_after_four_layers(fp32, prompt)
        err[fp32] = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    tol = 0.02
    assert err[True] < tol < err[False], err


# -- (b) below one window EVA is causal softmax attention -------------------


def test_reference_below_a_window_is_causal_softmax():
    rng = np.random.default_rng(1)
    l, h, d = W - 3, 4, 16
    q, k, v = (jnp.asarray(rng.normal(size=(l, h, d)), jnp.float32)
               for _ in range(3))
    mu, phi = (jnp.asarray(rng.normal(size=(h, d)), jnp.float32)
               for _ in range(2))
    got = ref.eva_attention(q, k, v, mu, phi, window=W, chunk=C)
    s = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool))[None], s, -jnp.inf)
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_engine_below_a_window_equals_a_model_that_never_rolls_over():
    """Prompt plus output shorter than the window: the same logprobs as
    the same weights under a window no sequence reaches."""
    prompt = prompt_of(18)
    out = []
    for window in (W, 256):
        model, params = stage(window_size=window)
        (req,) = generate(engine_for(model, params, k=8), [prompt], 12)
        out.append(req.output_logprobs)
    np.testing.assert_array_equal(out[0], out[1])


# -- (c) the cache manager's accounting -------------------------------------


def eva_manager(pages=64):
    return EvaCacheManager(PAGE, pages, EvaConfig(W, C, 8), max_model_len=512)


def held_by_formula(c):
    pp = W // C // PAGE
    return pp * (c // W) + math.ceil((c % W) / PAGE) + pp


def new_request(n_prompt, rid="r"):
    return Request(rid, prompt_ids=prompt_of(n_prompt),
                   sampling_params=SamplingParams(max_new_tokens=4))


@pytest.mark.parametrize("step", [1, 3, 8, 32])
def test_pages_held_follow_the_formula_at_every_context(step):
    """Walk one row from 1 to ~200 tokens ``step`` at a time, as decode
    steps and K-step windows do: a plan that starts at context ``c``
    rolls the window over first if ``c`` lies past it, then reserves up
    to ``c + step``. After the rollover the row holds exactly
    ``pp * (c // W) + ceil((c mod W) / page) + pp`` pages."""
    cm = eva_manager()
    req = new_request(1)
    assert cm.allocate_for_prompt(req)
    total = cm.num_free_pages + cm.pages_held(req)
    c = 1
    while c < 200:
        released = cm.pages_released
        cm.roll_window(req, c)
        assert cm.pages_released - released in (0, W // PAGE)
        assert cm.pages_held(req) == held_by_formula(c)
        want, free = cm.extra_pages(req, c + step), cm.num_free_pages
        assert cm.ensure_capacity(req, c + step)
        assert free - cm.num_free_pages == want
        assert cm.num_free_pages + cm.pages_held(req) == total
        c += step
    cm.roll_window(req, c)
    assert cm.rollovers == c // W
    assert cm.pages_released == (W // PAGE) * (c // W)
    cm.release(req)
    assert cm.num_free_pages == total == 63
    assert cm.pages_held(req) == 0


def test_the_virtual_page_table_is_summaries_then_the_open_window():
    cm = eva_manager()
    req = new_request(1)
    assert cm.allocate_for_prompt(req)
    for c in range(1, 3 * W + 5):
        cm.roll_window(req, c)
        assert cm.ensure_capacity(req, c + 1)
        w = c // W
        assert req.eva_window == w
        assert len(req.page_ids) == w * (W // C // PAGE) + c % W // PAGE + 1
        assert len(req.eva_pending) == W // C // PAGE
        assert cm.eva.virtual_len(c + 1) == w * (W // C) + c % W + 1
    assert cm.pages_needed(c) == held_by_formula(c)


def test_a_window_crossing_reserves_both_tables_and_rolls_over_once():
    cm = eva_manager()
    req = new_request(W - 3)
    assert cm.allocate_for_prompt(req)
    assert cm.extra_pages(req, W + 5) == 1 + 1      # next pending + 1 page
    assert cm.ensure_capacity(req, W + 5)
    assert len(req.eva_next_pending) == 1 and len(req.eva_next_open) == 1
    old_open = list(req.page_ids)
    pending = list(req.eva_pending)
    cm.roll_window(req, W - 1)                       # still the old window
    assert req.eva_window == 0 and req.page_ids == old_open
    cm.roll_window(req, W)
    assert req.eva_window == 1 and cm.rollovers == 1
    assert req.page_ids[:1] == pending               # summaries now visible
    assert cm.pages_released == W // PAGE
    assert not req.eva_next_pending and not req.eva_next_open
    cm.release(req)
    assert cm.num_free_pages == 63


def test_admission_and_growth_fail_cleanly_when_the_pool_is_short():
    cm = eva_manager(pages=8)                        # 7 usable
    a, b = new_request(W, "a"), new_request(W, "b")
    assert cm.allocate_for_prompt(a)                 # 1 pending + 4
    assert not cm.allocate_for_prompt(b)             # needs 5, 2 free
    assert cm.num_free_pages == 2 and not b.page_ids
    assert not cm.ensure_capacity(a, W + 17)         # next pending + 3 > 2
    assert cm.num_free_pages == 2
    cm.release(a)
    assert cm.num_free_pages == 7


def test_the_factory_gives_eva_the_python_manager_and_no_prefix_reuse():
    cm = make_cache_manager(PAGE, 64, eva=EvaConfig(W, C, 8),
                            enable_prefix_cache=True)
    assert isinstance(cm, EvaCacheManager) and not cm.enable_prefix_cache
    with pytest.raises(ValueError):
        make_cache_manager(16, 64, eva=EvaConfig(W, C, 8))


def test_an_aborted_row_and_kv_pressure_return_the_pool_to_full():
    """No host tier and no prefix tree for EVA: under pressure a row is
    aborted with ``kv_oom`` (as for every architecture without the
    tier), and whatever ends a row gives all its pages back."""
    model, params = stage()
    engine = engine_for(model, params, k=8, pages=16)   # 15 usable
    reqs = generate(engine, [prompt_of(40, seed=s) for s in range(4)], 60)
    assert any(r.status.value == "finished_length" for r in reqs)
    assert engine.cache.num_free_pages == 15
    assert engine.host_tier is None


# -- (d) the scheduler ------------------------------------------------------


@pytest.mark.parametrize("chunk", [12, 16, 40, 64])
def test_no_chunk_is_planned_across_a_window_boundary(chunk):
    model, params = stage()
    engine = engine_for(model, params, k=8, chunk=chunk, pages=128)
    seen = []
    form = engine.scheduler.form_batch

    def spy():
        plan = form()
        for seg in plan.seqs:
            first = seg.context_len - seg.num_new_tokens
            seen.append((first, seg.context_len))
            assert first // W == (seg.context_len - 1) // W
        return plan

    engine.scheduler.form_batch = spy
    generate(engine, [prompt_of(n, seed=n) for n in (100, 70, 33)], 3)
    assert any(end % W == 0 for _, end in seen)
    if W % chunk:
        # a chunk cut short at the boundary
        assert any(end % W == 0 and end - first < chunk
                   for first, end in seen)


# -- (e) head and sampler at a vocabulary of 320 ----------------------------


def test_head_holds_every_prediction_head_and_samples_head_zero():
    model, params = stage()
    assert params["lm_head"]["weight"].shape == (320 * 8, 64)
    assert MODEL_REGISTRY["EvaByteForCausalLM"] is StageModel
    (req,) = generate(engine_for(model, params), [prompt_of(5)], 20)
    assert max(req.output_ids) < 320


@pytest.mark.parametrize("top_k", [0, 20])
def test_fused_sampler_at_vocab_320_draws_what_the_xla_sampler_draws(top_k):
    from parallax_tpu.ops.decode_fused_pallas import fused_sample_topk_pallas
    from parallax_tpu.ops.sampling import row_gumbel, sample_tokens

    s, v = 8, 320
    logits = jax.random.normal(jax.random.key(1), (s, v), jnp.float32) * 3
    temp = jnp.asarray([0.0, 0.7, 0.7, 1.0, 0.7, 0.0, 1.3, 0.7], jnp.float32)
    tk = jnp.full((s,), top_k, jnp.int32)
    seeds = jnp.arange(s, dtype=jnp.int32) + 5
    steps = jnp.arange(s, dtype=jnp.int32)
    key = jax.random.key(9)
    want = sample_tokens(logits, key, temp, tk, jnp.ones((s,)),
                         jnp.zeros((s,)), seeds=seeds, out_steps=steps)
    got = fused_sample_topk_pallas(
        logits, row_gumbel(key, s, v, seeds, steps), temp, tk,
        interpret=True)
    np.testing.assert_array_equal(got, want)
    assert int(jnp.max(got)) < v


def test_sampled_rows_through_the_window_stay_inside_the_vocabulary():
    model, params = stage()
    engine = engine_for(model, params, k=8)
    pipe = InProcessPipeline([engine])
    reqs = [Request(f"s{i}", prompt_ids=prompt_of(30, seed=i),
                    sampling_params=SamplingParams(
                        temperature=0.7, top_k=20, seed=i,
                        max_new_tokens=40, ignore_eos=True))
            for i in range(3)]
    for r in reqs:
        pipe.submit(r)
    pipe.run_until_complete()
    assert all(len(r.output_ids) == 40 and max(r.output_ids) < 320
               for r in reqs)


# -- small things the change touches ----------------------------------------


def test_kv_sizing_is_right_for_32_kv_heads():
    cfg = normalize_config(dict(
        HF, hidden_size=4096, num_attention_heads=32,
        num_key_value_heads=32, intermediate_size=11008,
        window_size=2048, chunk_size=16, num_hidden_layers=16))
    assert cfg.head_dim == 128
    assert cfg.kv_bytes_per_token_per_layer() == 2 * 32 * 128 * 2 == 16384
    # 8.9 GB at 16 layers and pages of 64: 16.8 MB a page.
    assert derive_num_pages(int(8.9e9 / 0.9), cfg, 16, 64) == 530
    assert cfg.eva.fit_page_size(64) == 64
    assert normalize_config(HF).eva.fit_page_size(16) == 8


def test_eva_summary_kernel_equals_its_xla_form():
    from parallax_tpu.ops.eva import eva_summary_pallas, eva_summary_xla

    p, h, d = 16, 4, 128
    kv = jax.random.normal(jax.random.key(0), (p, PAGE, 2 * h, d),
                           jnp.float32).astype(jnp.bfloat16)
    mu, phi = (0.1 * jax.random.normal(jax.random.key(i), (h, d))
               for i in (1, 2))
    src = jnp.asarray([8, 12, 40, 0], jnp.int32)
    dst = jnp.asarray([100, -1, 101, 3], jnp.int32)
    want = eva_summary_xla(kv, mu, phi, src, dst, chunk_size=C)
    got = eva_summary_pallas(kv, mu, phi, src, dst, chunk_size=C,
                             interpret=True)
    np.testing.assert_array_equal(got, want)
    changed = np.flatnonzero(np.any(
        np.asarray(want != kv).reshape(p * PAGE, -1), axis=1))
    assert changed.tolist() == [3, 100, 101]
