"""Multi-step decode (k tokens per jit dispatch) — exact parity with the
single-step engine (the SURVEY §7 "multi-step decode inside one jit" hard
part)."""

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

CFG = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"], hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, vocab_size=199, max_position_embeddings=512,
    tie_word_embeddings=False,
))


def _run(lookahead, prompts, max_new=11, eos=None, params=None,
         page_size=8, pipeline=1):
    model = StageModel(CFG, 0, 2, use_pallas=False)
    p = params if params is not None else model.init_params(
        jax.random.key(0), dtype=jnp.float32
    )
    eng = StageEngine(model, p, EngineConfig(
        page_size=page_size, num_pages=128, max_model_len=256,
        kv_dtype="float32", decode_lookahead=lookahead,
        decode_pipeline=pipeline,
    ))
    pipe = InProcessPipeline([eng])
    reqs = []
    for i, prompt in enumerate(prompts):
        req = Request(
            f"r{i}", prompt_ids=list(prompt),
            sampling_params=SamplingParams(temperature=0.0,
                                           max_new_tokens=max_new),
        )
        if eos is not None:
            req.eos_token_ids = eos
        reqs.append(req)
        pipe.submit(req)
    pipe.run_until_complete()
    return reqs, eng


def test_multistep_matches_single_step_exactly():
    prompts = [[3, 14, 15, 92, 65], [7, 21, 108], [42] * 9]
    base, _ = _run(1, prompts)
    multi, eng = _run(4, prompts)
    for b, m in zip(base, multi):
        assert m.output_ids == b.output_ids, (b.output_ids, m.output_ids)
        assert m.status == b.status
    assert (4, False, False, ()) in eng._jit_multistep  # the path actually ran


def test_multistep_respects_max_tokens_and_eos():
    # max_new not a multiple of k: surplus window tokens must be discarded.
    prompts = [[5, 6, 7, 8]]
    base, _ = _run(1, prompts, max_new=7)
    multi, _ = _run(4, prompts, max_new=7)
    assert multi[0].output_ids == base[0].output_ids
    assert len(multi[0].output_ids) == 7
    # EOS mid-window: find what greedy produces, set its 3rd token as EOS.
    probe, _ = _run(1, prompts, max_new=7)
    eos = (probe[0].output_ids[2],)
    base2, _ = _run(1, prompts, max_new=7, eos=eos)
    multi2, _ = _run(4, prompts, max_new=7, eos=eos)
    assert multi2[0].output_ids == base2[0].output_ids
    assert multi2[0].status == base2[0].status


def test_multistep_prefix_cache_donation_consistent():
    """After a multistep run, the donated prefix pages must reflect only
    computed KV (the invariant release() relies on)."""
    prompts = [[9, 8, 7, 6, 5, 4, 3]]  # 7 tokens + outputs
    reqs, eng = _run(4, prompts, max_new=9)
    req = reqs[0]
    # invariant held throughout: computed == len(all) - 1 at finish
    assert req.num_computed_tokens == req.total_len - 1
    # A second request sharing the donated page (prompt + first generated
    # token completes the first full page) gets cache hits.
    follow = Request(
        "f",
        prompt_ids=list(prompts[0]) + req.output_ids[:2] + [100],
        sampling_params=SamplingParams(temperature=0.0, max_new_tokens=4),
    )
    pipe = InProcessPipeline([eng])
    pipe.submit(follow)
    pipe.run_until_complete()
    assert follow.num_cached_tokens > 0
    assert len(follow.output_ids) == 4


def _run_sampled(lookahead, specs, max_new=9, pipeline=1):
    """specs: list of (prompt, temperature, seed)."""
    model = StageModel(CFG, 0, 2, use_pallas=False)
    p = model.init_params(jax.random.key(0), dtype=jnp.float32)
    eng = StageEngine(model, p, EngineConfig(
        page_size=8, num_pages=128, max_model_len=256,
        kv_dtype="float32", decode_lookahead=lookahead,
        decode_pipeline=pipeline,
    ))
    pipe = InProcessPipeline([eng])
    reqs = []
    for i, (prompt, temp, seed) in enumerate(specs):
        req = Request(
            f"r{i}", prompt_ids=list(prompt),
            sampling_params=SamplingParams(
                temperature=temp, max_new_tokens=max_new, seed=seed,
                ignore_eos=True,
            ),
        )
        reqs.append(req)
        pipe.submit(req)
    pipe.run_until_complete()
    return reqs, eng


def test_multistep_sampled_seeded_matches_single_step_exactly():
    """Seeded sampled rows draw from fold_in(key(seed), output_step) on
    BOTH paths, so the fused window must reproduce per-step sampling
    token-for-token (VERDICT r2 #2)."""
    specs = [([3, 14, 15, 92], 0.9, 7), ([7, 21, 108], 1.3, 11)]
    base, beng = _run_sampled(1, specs)
    multi, meng = _run_sampled(4, specs)
    assert (4, True, False, ()) in meng._jit_multistep  # fused-sampler variant ran
    assert not beng._jit_multistep
    for b, m in zip(base, multi):
        assert m.output_ids == b.output_ids, (b.output_ids, m.output_ids)


def test_multistep_sampled_mixed_greedy_rows_stay_greedy():
    """A mixed batch (greedy + sampled rows) takes the fused-sampler
    variant; the greedy rows' outputs must equal the pure-greedy run."""
    specs = [([5, 6, 7, 8], 0.0, None), ([9, 10, 11], 1.0, 3)]
    mixed, meng = _run_sampled(4, specs)
    assert (4, True, False, ()) in meng._jit_multistep
    greedy_only, _ = _run_sampled(1, [([5, 6, 7, 8], 0.0, None)])
    assert mixed[0].output_ids == greedy_only[0].output_ids
    # seeded row reproducible vs its single-step stream too
    seeded_only, _ = _run_sampled(1, [([9, 10, 11], 1.0, 3)])
    assert mixed[1].output_ids == seeded_only[0].output_ids


def test_multistep_sampled_pipelined_windows_match():
    specs = [([42, 43, 44, 45], 1.1, 123)]
    base, _ = _run_sampled(1, specs, max_new=13)
    multi, _ = _run_sampled(3, specs, max_new=13, pipeline=3)
    assert multi[0].output_ids == base[0].output_ids


def test_multistep_runs_penalized_requests_in_window():
    """Penalties are scan-carry state now: penalized rows ride the fused
    window (the "pen" feature variant compiles) and the stream is
    bit-identical to the K=1 host-synchronous sampler."""
    def run(lookahead):
        model = StageModel(CFG, 0, 2, use_pallas=False)
        p = model.init_params(jax.random.key(0), dtype=jnp.float32)
        eng = StageEngine(model, p, EngineConfig(
            page_size=8, num_pages=128, max_model_len=256,
            kv_dtype="float32", decode_lookahead=lookahead,
        ))
        pipe = InProcessPipeline([eng])
        req = Request("s", prompt_ids=[1, 2, 3],
                      sampling_params=SamplingParams(
                          temperature=1.0, max_new_tokens=8, seed=3,
                          repetition_penalty=1.3,
                          presence_penalty=0.4,
                          frequency_penalty=0.2))
        pipe.submit(req)
        pipe.run_until_complete()
        return req, eng

    base, beng = run(1)
    multi, meng = run(4)
    assert len(base.output_ids) == 8
    assert not beng._jit_multistep
    assert (4, True, False, ("pen",)) in meng._jit_multistep
    assert multi.output_ids == base.output_ids


def test_multistep_mixed_arrivals():
    """A prefill arriving mid-stream forces normal steps, then decode
    windows resume; outputs still match the single-step engine."""
    model = StageModel(CFG, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)

    def run(lookahead):
        eng = StageEngine(model, params, EngineConfig(
            page_size=8, num_pages=128, max_model_len=256,
            kv_dtype="float32", decode_lookahead=lookahead,
        ))
        pipe = InProcessPipeline([eng])
        r1 = Request("a", prompt_ids=[3, 14, 15],
                     sampling_params=SamplingParams(temperature=0.0,
                                                    max_new_tokens=10))
        pipe.submit(r1)
        for _ in range(3):
            pipe.step_round()
        r2 = Request("b", prompt_ids=[99, 98, 97, 96],
                     sampling_params=SamplingParams(temperature=0.0,
                                                    max_new_tokens=6))
        pipe.submit(r2)
        pipe.run_until_complete()
        return r1.output_ids, r2.output_ids

    a1, b1 = run(1)
    a4, b4 = run(4)
    assert a4 == a1 and b4 == b1


def test_pipelined_windows_match_single_step_exactly():
    """decode_pipeline chains windows off the device-resident carry; the
    token stream must be bit-identical to the unfused engine."""
    prompts = [[3, 14, 15, 92, 65], [7, 21, 108], [42] * 9]
    base, _ = _run(1, prompts, max_new=25)
    piped, eng = _run(4, prompts, max_new=25, pipeline=3)
    for b, m in zip(base, piped):
        assert m.output_ids == b.output_ids, (b.output_ids, m.output_ids)
        assert m.status == b.status
    assert (4, False, False, ()) in eng._jit_multistep
    assert eng._last_fused_steps == 12  # 3 windows x k=4 actually chained


def test_pipelined_windows_mid_chain_finishes():
    """max_new_tokens ending mid-window and mid-chain: surplus tokens from
    the remaining chained windows must be discarded, not committed."""
    prompts = [[5, 6, 7, 8], [9, 10, 11]]
    base, _ = _run(1, prompts, max_new=6)       # ends mid-window (6 = 4+2)
    piped, _ = _run(4, prompts, max_new=6, pipeline=4)
    for b, m in zip(base, piped):
        assert m.output_ids == b.output_ids
        assert len(m.output_ids) == 6
    # EOS inside the FIRST window of a chain: later windows' tokens for
    # that row are discarded while other rows keep decoding.
    probe, _ = _run(1, prompts, max_new=12)
    eos = (probe[0].output_ids[1],)
    base2, _ = _run(1, prompts, max_new=12, eos=eos)
    piped2, _ = _run(4, prompts, max_new=12, eos=eos, pipeline=3)
    for b, m in zip(base2, piped2):
        assert m.output_ids == b.output_ids
        assert m.status == b.status


def test_pipelined_windows_clamp_to_context_room():
    """Near max_model_len the chain shortens to the windows that fit; the
    request still finishes correctly via the fallback paths."""
    model = StageModel(CFG, 0, 2, use_pallas=False)
    p = model.init_params(jax.random.key(0), dtype=jnp.float32)
    eng = StageEngine(model, p, EngineConfig(
        page_size=8, num_pages=64, max_model_len=64,
        kv_dtype="float32", decode_lookahead=4, decode_pipeline=8,
    ))
    pipe = InProcessPipeline([eng])
    req = Request("clamp", prompt_ids=list(range(1, 41)),  # 40 tokens
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=100))
    pipe.submit(req)
    pipe.run_until_complete()
    assert req.status.value == "finished_length"
    assert req.total_len <= 64


def test_multistep_near_context_limit_falls_back():
    """total_len + k past max_model_len must fall back to single-step
    (never overrun the per-seq page table) and still finish correctly."""
    model = StageModel(CFG, 0, 2, use_pallas=False)
    p = model.init_params(jax.random.key(0), dtype=jnp.float32)
    eng = StageEngine(model, p, EngineConfig(
        page_size=8, num_pages=64, max_model_len=32,
        kv_dtype="float32", decode_lookahead=8,
    ))
    pipe = InProcessPipeline([eng])
    req = Request("edge", prompt_ids=list(range(1, 25)),  # 24 tokens
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=100))
    pipe.submit(req)
    pipe.run_until_complete()
    # clamped by the engine to the context budget, finished at length
    assert req.status.value == "finished_length"
    assert req.total_len <= 32


# -- hybrid (linear-state) models in the fused window ------------------------


def _hybrid_run(lookahead, prompts, max_new=10, pipeline=1, seed=None,
                temperature=0.0):
    from tests.test_linear_prefix_cache import CONFIG as HYBRID_CFG
    from parallax_tpu.models.registry import create_stage_model

    m = create_stage_model(HYBRID_CFG, 0, 4, use_pallas=False)
    eng = StageEngine(
        m, m.init_params(jax.random.key(0), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=128, max_model_len=256,
                     kv_dtype="float32", decode_lookahead=lookahead,
                     decode_pipeline=pipeline),
    )
    windows = []
    orig = eng._dispatch_multistep
    eng._dispatch_multistep = (
        lambda plan, t0, *a: windows.append(1) or orig(plan, t0, *a)
    )
    pipe = InProcessPipeline([eng])
    reqs = []
    for i, p in enumerate(prompts):
        r = Request(f"h{i}", prompt_ids=list(p),
                    sampling_params=SamplingParams(
                        temperature=temperature, max_new_tokens=max_new,
                        ignore_eos=True, seed=seed))
        reqs.append(r)
        pipe.submit(r)
    pipe.run_until_complete()
    return reqs, orig


def test_hybrid_multistep_matches_single_step_exactly():
    """Linear-state models now fuse the decode window: the recurrence
    advances inside the scan (constant slots/dense map per window) and
    must match per-step decode token-for-token."""
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7, 6, 5]]
    base, _ = _hybrid_run(1, prompts)
    fused, orig = _hybrid_run(4, prompts)
    for b, f in zip(base, fused):
        assert f.output_ids == b.output_ids
        assert len(f.output_ids) == 10


def test_hybrid_multistep_sampled_seeded_matches():
    prompts = [[1, 2, 3, 4, 5, 6, 7]]
    base, _ = _hybrid_run(1, prompts, seed=42, temperature=0.8)
    fused, _ = _hybrid_run(4, prompts, seed=42, temperature=0.8)
    assert fused[0].output_ids == base[0].output_ids


def test_hybrid_pipelined_windows_match():
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6]]
    base, _ = _hybrid_run(1, prompts, max_new=16)
    fused, _ = _hybrid_run(4, prompts, max_new=16, pipeline=3)
    assert fused[0].output_ids == base[0].output_ids


def test_one_token_prompt_stays_on_normal_path():
    """A 1-token prompt's first forward has num_new == 1 but is a
    PREFILL; it must not enter the fused window (hybrids would re-zero
    their state every scan step; prefill bookkeeping differs)."""
    base, _ = _hybrid_run(1, [[7]], max_new=8)
    fused, _ = _hybrid_run(4, [[7]], max_new=8)
    assert fused[0].output_ids == base[0].output_ids
    # Dense model too.
    (b,), _ = _run(1, [[7]], max_new=8)
    (f,), _ = _run(4, [[7]], max_new=8)
    assert f.output_ids == b.output_ids


def test_hybrid_mid_window_finish_never_snapshots_overrun_state():
    """A row finishing mid-window has device state PAST its committed
    context; that state must never be donated as a prefix snapshot. A
    follow-up sharing the conversation must emit oracle tokens (resuming
    from a shallower, valid snapshot instead)."""
    from tests.test_linear_prefix_cache import CONFIG as HYBRID_CFG
    from parallax_tpu.models.registry import create_stage_model

    def build(lookahead, prefix):
        m = create_stage_model(HYBRID_CFG, 0, 4, use_pallas=False)
        return StageEngine(
            m, m.init_params(jax.random.key(0), dtype=jnp.float32),
            EngineConfig(page_size=8, num_pages=128, max_model_len=256,
                         kv_dtype="float32", decode_lookahead=lookahead,
                         enable_prefix_cache=prefix,
                         linear_decode_snapshot_stride=1),
        )

    def run(eng, rid, ids, n):
        r = Request(rid, prompt_ids=list(ids),
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=n, ignore_eos=True))
        p = InProcessPipeline([eng])
        p.submit(r)
        p.run_until_complete()
        return r

    # prompt 11 + 5 generated = 16 = page-aligned finish, mid-window for
    # k=4 (window 2 stops after 1 commit; device ran 4 more scan steps).
    prompt = list(range(1, 12))
    oracle = build(1, prefix=False)
    o1 = run(oracle, "o1", prompt, 5)
    convo = prompt + o1.output_ids
    o2 = run(oracle, "o2", convo + [40, 41], 6)

    eng = build(4, prefix=True)
    r1 = run(eng, "r1", prompt, 5)
    assert r1.output_ids == o1.output_ids
    r2 = run(eng, "r2", convo + [40, 41], 6)
    assert r2.output_ids == o2.output_ids   # over-advanced state never used


# -- async window on the overlapped drive loop -------------------------------


def _drive(eng, max_iters=2000):
    """The one-in-flight loop every production driver runs."""
    from parallax_tpu.runtime.engine import drive_step

    outs_all = []
    pending = None
    iters = 0
    while (eng.has_work() or pending is not None) and iters < max_iters:
        iters += 1
        outs, pending = drive_step(eng, pending)
        outs_all.extend(outs)
    assert pending is None and not eng._inflight
    return outs_all


def _build_engine(lookahead, overlap=True, **cfg_kw):
    model = StageModel(CFG, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    defaults = dict(page_size=8, num_pages=128, max_model_len=256,
                    kv_dtype="float32")
    defaults.update(cfg_kw)
    return StageEngine(model, params, EngineConfig(
        decode_lookahead=lookahead, overlap_steps=overlap, **defaults,
    ))


def _drive_requests(eng, specs, max_new=11, ignore_eos=True, eos=None):
    reqs = []
    for i, (prompt, temp, seed) in enumerate(specs):
        req = Request(f"r{i}", prompt_ids=list(prompt),
                      sampling_params=SamplingParams(
                          temperature=temp, seed=seed,
                          max_new_tokens=max_new, ignore_eos=ignore_eos))
        if eos is not None:
            req.eos_token_ids = eos
        reqs.append(req)
        eng.submit(req)
    outs = _drive(eng)
    return reqs, outs


def test_window_rides_overlap_loop_bit_identical():
    """The K-step window is now DISPATCHED (resolve reads the tokens +
    stop mask back in one D2H pass), so it must ride the one-in-flight
    drive loop and still match the fully synchronous K=1 engine
    bit-for-bit — greedy and seeded rows alike."""
    specs = [([3, 14, 15, 92], 0.0, None), ([7, 21, 108], 0.9, 7),
             ([42] * 5, 1.3, 11)]
    base, _ = _drive_requests(
        _build_engine(1, overlap=False), specs, max_new=13)
    over, outs = _drive_requests(_build_engine(4), specs, max_new=13)
    for b, m in zip(base, over):
        assert m.output_ids == b.output_ids, (b.output_ids, m.output_ids)
        assert m.status == b.status
    # Window visits actually happened (one resolve committing a full
    # k * batch block) and the window flew asynchronously: it resolved
    # only after a later dispatch had already been enqueued.
    window_outs = [o for o in outs if o.num_tokens >= 4 * len(specs)]
    assert window_outs, [o.num_tokens for o in outs]
    assert any(o.overlapped for o in window_outs)
    # Sync-mode window engine agrees too (K=4, overlap off).
    sync4, _ = _drive_requests(
        _build_engine(4, overlap=False), specs, max_new=13)
    for b, m in zip(base, sync4):
        assert m.output_ids == b.output_ids


def test_two_stage_pipeline_window_inert_and_identical():
    """Multi-step windows need a local ring (single full stage); on a
    two-stage pipeline the path must stay inert — never compiled — and
    streams must equal the K=1 run exactly."""
    def run(lookahead):
        m0 = StageModel(CFG, 0, 1, use_pallas=False)
        m1 = StageModel(CFG, 1, 2, use_pallas=False)
        p0 = m0.init_params(jax.random.key(0), dtype=jnp.float32)
        p1 = m1.init_params(jax.random.key(1), dtype=jnp.float32)
        ecfg = dict(page_size=8, num_pages=128, max_model_len=256,
                    kv_dtype="float32", decode_lookahead=lookahead)
        engines = [StageEngine(m0, p0, EngineConfig(**ecfg)),
                   StageEngine(m1, p1, EngineConfig(**ecfg))]
        pipe = InProcessPipeline(engines)
        reqs = []
        for i, prompt in enumerate([[3, 14, 15], [9, 8, 7, 6]]):
            r = Request(f"p{i}", prompt_ids=prompt,
                        sampling_params=SamplingParams(
                            temperature=0.0, max_new_tokens=8,
                            ignore_eos=True))
            reqs.append(r)
            pipe.submit(r)
        pipe.run_until_complete()
        return reqs, engines

    base, _ = run(1)
    multi, engines = run(4)
    for b, m in zip(base, multi):
        assert m.output_ids == b.output_ids
    for eng in engines:
        assert not eng._jit_multistep   # never compiled on either stage


def test_stop_token_mid_window_no_phantom_commits():
    """A stop token landing mid-window freezes the row on device; the
    host rolls back the frozen tail before commit. Nothing past the stop
    point may reach the request, the computed-KV count, or the radix
    digest plane (prefix donation)."""
    prompts = [[5, 6, 7, 8, 9, 10, 11, 12]]

    probe = _build_engine(1, overlap=False)
    (p,), _ = _drive_requests(probe, [(prompts[0], 0.0, None)], max_new=9)
    # A token whose FIRST occurrence lies mid-window (index >= 2), so
    # the stop genuinely interrupts a k=4 window partway through.
    stop_idx = next(
        i for i in range(2, 7)
        if p.output_ids[i] not in p.output_ids[:i]
    )
    stop = (p.output_ids[stop_idx],)

    def run(lookahead):
        eng = _build_engine(lookahead, overlap=True, cache_digests=True,
                            enable_prefix_cache=True)
        req = Request("s", prompt_ids=list(prompts[0]),
                      sampling_params=SamplingParams(
                          temperature=0.0, max_new_tokens=9,
                          stop_token_ids=stop))
        eng.submit(req)
        _drive(eng)
        return req, eng

    base, beng = run(1)
    multi, meng = run(4)
    assert multi.output_ids == base.output_ids
    assert multi.status.value == "finished_stop"
    assert len(multi.output_ids) == stop_idx + 1
    # KV bookkeeping: the stop token itself was never fed, so computed
    # sits exactly one short of the committed stream.
    assert multi.num_computed_tokens == multi.total_len - 1
    # Digest plane: the donated prefix chains must be identical to the
    # K=1 run's — a phantom commit would mint extra block digests.
    bp = beng.cache_digest_payload(full=True)
    mp = meng.cache_digest_payload(full=True)
    assert bp is not None and mp is not None
    assert sorted(bp["full"]) == sorted(mp["full"])


def test_window_fallback_under_page_pressure():
    """When the allocator cannot guarantee K steps of KV for every row,
    the scheduler's window planning returns 0 and decode falls back to
    single-step — streams stay bit-identical and every request finishes
    (with the host tier absorbing the pressure, not kv_oom)."""
    def run(lookahead, num_pages):
        eng = _build_engine(
            lookahead, overlap=True, num_pages=num_pages,
            max_model_len=128, enable_prefix_cache=True,
            host_cache_bytes=1 << 26,
        )
        specs = [(list(range(1 + 7 * i, 9 + 7 * i)), 0.0, None)
                 for i in range(4)]
        reqs, _ = _drive_requests(eng, specs, max_new=17)
        return reqs, eng

    base, _ = run(1, num_pages=128)
    # 4 requests x (1 prompt page + ~3 decode pages): 14 pages starves
    # the 8-step window pre-allocation for the full batch.
    tight, teng = run(4, num_pages=14)
    for b, t in zip(base, tight):
        assert t.status.value != "finished_abort", t.abort_reason
        assert t.output_ids == b.output_ids, (b.output_ids, t.output_ids)
    stats = teng.cache.stats
    assert stats.kv_oom_aborts == 0


def test_adaptive_lookahead_default_and_feature_windows():
    """decode_lookahead=None (the default) runs the adaptive window; a
    penalized request joining the batch no longer downshifts it — the
    window recompiles with the "pen" scan-carry variant and keeps
    fusing. Streams match the pinned K=1 engine throughout."""
    from parallax_tpu.runtime.engine import ADAPTIVE_DECODE_LOOKAHEAD

    def run(lookahead):
        eng = _build_engine(lookahead)
        tickets = []
        orig = eng._dispatch_multistep
        eng._dispatch_multistep = (
            lambda plan, t0, *a: tickets.append(
                (orig(plan, t0, *a),
                 [s.request.request_id for s in plan.seqs])
            ) or tickets[-1][0]
        )
        clean = Request("c", prompt_ids=[3, 14, 15],
                        sampling_params=SamplingParams(
                            temperature=0.0, max_new_tokens=24,
                            ignore_eos=True))
        eng.submit(clean)
        pen = Request("p", prompt_ids=[9, 8, 7],
                      sampling_params=SamplingParams(
                          temperature=0.0, max_new_tokens=4,
                          ignore_eos=True, repetition_penalty=1.3))
        from parallax_tpu.runtime.engine import drive_step

        pending = None
        iters = 0
        submitted = False
        while (eng.has_work() or pending is not None) and iters < 500:
            iters += 1
            if not submitted and len(clean.output_ids) >= 9:
                eng.submit(pen)
                submitted = True
            _, pending = drive_step(eng, pending)
        assert submitted
        return clean, pen, eng, tickets

    clean_a, pen_a, eng, tickets = run(None)
    clean_b, pen_b, _, _ = run(1)
    assert clean_a.output_ids == clean_b.output_ids
    assert pen_a.output_ids == pen_b.output_ids
    # Adaptive K compiled at the default cap.
    assert (ADAPTIVE_DECODE_LOOKAHEAD, False, False, ()) in eng._jit_multistep
    # Batches sharing the penalized request still got windows — the
    # "pen" feature variant compiled instead of a downshift refusal.
    # (Its FIRST batch is the prefill step, which never fuses.)
    with_pen = [t for t, rids in tickets if "p" in rids]
    assert with_pen and any(t is not None for t in with_pen)
    assert (
        ADAPTIVE_DECODE_LOOKAHEAD, False, False, ("pen",)
    ) in eng._jit_multistep
    solo = [t for t, rids in tickets if rids == ["c"]]
    assert any(t is not None for t in solo)


def test_window_respects_min_new_tokens():
    """min_new_tokens suppresses EOS inside the device stop mask exactly
    as commit_token does on the host."""
    prompts = [(list([5, 6, 7, 8]), 0.0, None)]
    probe, _ = _drive_requests(_build_engine(1, overlap=False), prompts,
                               max_new=10)
    eos = (probe[0].output_ids[1],)   # 2nd greedy token is EOS

    def run(lookahead, min_new):
        eng = _build_engine(lookahead)
        req = Request("m", prompt_ids=[5, 6, 7, 8],
                      sampling_params=SamplingParams(
                          temperature=0.0, max_new_tokens=10,
                          min_new_tokens=min_new))
        req.eos_token_ids = eos
        eng.submit(req)
        _drive(eng)
        return req

    for min_new in (0, 5):
        base = run(1, min_new)
        multi = run(4, min_new)
        assert multi.output_ids == base.output_ids, min_new
        assert multi.status == base.status


def test_step_timing_splits_per_visit_and_per_token():
    """The K>1 world must report honest TPOT: per-host-visit and
    per-token series are separate, and a window run shows multiple
    tokens per visit."""
    eng = _build_engine(4)
    specs = [([3, 14, 15, 92], 0.0, None), ([7, 21, 108], 0.0, None)]
    _drive_requests(eng, specs, max_new=9)
    s = eng.step_timing.summary()
    assert s["host_visits"] == s["steps"]
    assert s["tokens"] >= 2 * 9
    assert s["tokens_per_visit"] > 1.0
    assert 0.0 < s["per_token_host_ms_ewma"] < s["host_ms_ewma"]
