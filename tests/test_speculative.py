"""Speculative decoding — exact parity, windowed and host-sync.

Two execution paths share the proposers and the acceptance rule:

- the ON-DEVICE speculative window (``decode_lookahead`` K > 1): the
  draft-verify loop fused into the K-step scan — proposals staged at
  dispatch, every iteration verifies 1+P positions in one ragged
  multi-token forward, accepts the longest agreeing prefix + bonus on
  device, and rewinds the context pointer past rejections;
- the host-synchronous verify fallback (K = 1): one proposal round per
  host visit, logits read back and accepted at resolve.

Everything committed must equal what single-step decoding produces,
token for token — greedy AND seeded sampled, sync AND overlapped,
whatever garbage the proposers emit.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.runtime.engine import EngineConfig, StageEngine, drive_step
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

CFG = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"], hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, vocab_size=199, max_position_embeddings=512,
    tie_word_embeddings=False,
))

_MODEL = StageModel(CFG, 0, 2, use_pallas=False)
_PARAMS = _MODEL.init_params(jax.random.key(0), dtype=jnp.float32)


def _engine(spec_tokens, params=None, draft=None, lookahead=None,
            **cfg_kw):
    defaults = dict(
        page_size=8, num_pages=256, max_model_len=256,
        kv_dtype="float32",
    )
    defaults.update(cfg_kw)
    cfg = EngineConfig(
        speculative_tokens=spec_tokens, decode_lookahead=lookahead,
        **defaults,
    )
    return StageEngine(
        _MODEL, params if params is not None else _PARAMS, cfg,
        draft=draft,
    )


def _adversarialize(eng, fallback):
    """Wrap the engine's proposer: when n-gram lookup finds nothing,
    propose ``fallback`` garbage — exactness must hold for ARBITRARY
    proposals (bad ones cost acceptance, never tokens)."""
    orig = eng._ngram_proposal

    def _adversarial(tokens, n, k):
        prop = orig(tokens, n, k)
        return prop or list(fallback)[:k]

    eng._ngram_proposal = _adversarial


def _run(spec_tokens, prompts, max_new=12, params=None, draft=None,
         lookahead=None, sp_kw=None, overlap=False, adversarial=None,
         **cfg_kw):
    """Run prompts to completion. ``overlap`` drives the two-phase
    one-in-flight loop (the serving default); otherwise the synchronous
    InProcessPipeline. Returns (requests, engine)."""
    eng = _engine(spec_tokens, params=params, draft=draft,
                  lookahead=lookahead, **cfg_kw)
    if adversarial is not None:
        _adversarialize(eng, adversarial)
    kws = sp_kw or [dict(temperature=0.0)] * len(prompts)
    reqs = []
    for i, (prompt, kw) in enumerate(zip(prompts, kws)):
        req = Request(f"r{i}", prompt_ids=list(prompt),
                      sampling_params=SamplingParams(
                          max_new_tokens=max_new, ignore_eos=True, **kw))
        reqs.append(req)
        eng.submit(req)
    if overlap:
        eng.cfg.overlap_steps = True
        pending = None
        guard = 0
        while (eng.has_work() or pending is not None) and guard < 20000:
            _, pending = drive_step(eng, pending)
            guard += 1
    else:
        pipe = InProcessPipeline([eng])
        pipe.run_until_complete()
    return reqs, eng


def _spec_engaged(eng) -> bool:
    s = eng.spec_summary()
    return bool(s and s["proposals"] > 0)


# -- proposer units ----------------------------------------------------------


def test_ngram_proposal_finds_repeats():
    prop = StageEngine._ngram_proposal(
        [1, 2, 3, 9, 9, 1, 2, 3], n=3, k=4
    )
    assert prop == [9, 9, 1, 2]   # continuation of the earlier [1,2,3]
    assert StageEngine._ngram_proposal([1, 2, 3, 4], n=3, k=4) == []
    assert StageEngine._ngram_proposal([5, 5], n=3, k=4) == []


def test_ngram_proposal_cycles_periodic_tails():
    """A match whose continuation runs to the sequence end means the
    stream is periodic: the proposal cycles to fill k instead of
    stopping after one period."""
    assert StageEngine._ngram_proposal(
        [9, 1, 2, 1, 2, 1, 2], n=2, k=6
    ) == [1, 2, 1, 2, 1, 2]
    assert StageEngine._ngram_proposal([4] * 6, n=3, k=5) == [4] * 5
    # A terminal match means the whole visible tail is periodic — the
    # continuation cycles with the match distance as its period.
    assert StageEngine._ngram_proposal(
        [1, 2, 3, 7, 8, 1, 2, 3], n=3, k=8
    ) == [7, 8, 1, 2, 3, 7, 8, 1]
    # Non-terminal matches never cycle (the real continuation is known
    # and might not repeat).
    assert StageEngine._ngram_proposal(
        [1, 2, 3, 7, 8, 4, 4, 1, 2, 3], n=3, k=3
    ) == [7, 8, 4]


def test_ngram_proposal_respects_budget_and_lookback():
    """Property-style sweep: proposals never exceed the budget, never
    contain tokens from outside the lookback window, and k<=0 / short
    contexts propose nothing."""
    rng = np.random.default_rng(7)
    for _ in range(50):
        n = int(rng.integers(1, 5))
        k = int(rng.integers(0, 12))
        toks = [int(x) for x in rng.integers(0, 6, size=rng.integers(0, 900))]
        prop = StageEngine._ngram_proposal(toks, n, k)
        assert len(prop) <= max(0, k)
        window = set(toks[-StageEngine._SPEC_LOOKBACK:])
        assert all(t in window for t in prop)
    assert StageEngine._ngram_proposal([1, 2, 3, 1, 2, 3], 3, 0) == []
    # The lookback bound: a match older than _SPEC_LOOKBACK is invisible.
    far = [7, 7, 7, 9] + [1, 2] * (StageEngine._SPEC_LOOKBACK // 2 + 8)
    prop = StageEngine._ngram_proposal(far + [7, 7, 7], n=3, k=4)
    assert prop == []


# -- windowed speculation (the K-step scan) ----------------------------------


def test_window_speculation_matches_plain_greedy():
    prompts = [
        [7, 8, 9, 10, 7, 8, 9, 10, 7, 8, 9],
        [3, 14, 15, 3, 14, 15, 3, 14],
    ]
    base, _ = _run(0, prompts, max_new=24, lookahead=1)
    spec, eng = _run(6, prompts, max_new=24, lookahead=8,
                     adversarial=[1, 2, 3])
    assert eng._jit_spec_multistep, "spec window never compiled"
    assert _spec_engaged(eng)
    for b, s in zip(base, spec):
        assert s.output_ids == b.output_ids, (b.output_ids, s.output_ids)
        assert s.status == b.status
        assert s.num_computed_tokens == s.total_len - 1


def test_window_bit_identity_matrix():
    """The acceptance contract's matrix: greedy + seeded x sync/overlap
    x K=1/K=8 — every speculative stream must be bitwise the spec-off
    stream, with the spec path verifiably engaged."""
    prompts = [[5, 6, 5, 6, 5, 6], [7, 8, 9, 10, 7, 8, 9, 10, 7, 8]]
    kinds = {
        "greedy": [dict(temperature=0.0)] * 2,
        "seeded": [dict(temperature=0.7, seed=123),
                   dict(temperature=0.4, seed=7)],
    }
    for kind, kws in kinds.items():
        base, _ = _run(0, prompts, max_new=20, lookahead=1, sp_kw=kws)
        for overlap in (False, True):
            for k in (1, 8):
                spec, eng = _run(4, prompts, max_new=20, lookahead=k,
                                 sp_kw=kws, overlap=overlap,
                                 adversarial=[1, 2, 3])
                label = (kind, "overlap" if overlap else "sync", k)
                if not (overlap and k == 1):
                    # Overlapped K=1 rows are device-fed — the host
                    # cannot propose their continuation, by design.
                    assert _spec_engaged(eng), label
                if k > 1:
                    assert eng._jit_spec_multistep, label
                for b, s in zip(base, spec):
                    assert s.output_ids == b.output_ids, (
                        label, b.output_ids, s.output_ids,
                    )
                    assert s.status == b.status, label


def test_window_mid_stream_stop_token_rolls_back_exactly():
    """A stop token landing mid-window freezes the row on device; the
    frozen tail and every rejected proposal roll back before commit —
    nothing phantom reaches the request, the computed-KV count, or the
    radix digest plane (prefix donation)."""
    prompt = [5, 6, 7, 8, 9, 10, 11, 12]
    (probe,), _ = _run(0, [prompt], max_new=9, lookahead=1)
    stop_idx = next(
        i for i in range(2, 7)
        if probe.output_ids[i] not in probe.output_ids[:i]
    )
    stop = (probe.output_ids[stop_idx],)

    def run(spec, lookahead):
        eng = _engine(spec, lookahead=lookahead, cache_digests=True,
                      enable_prefix_cache=True)
        if spec:
            _adversarialize(eng, [1, 2, 3])
        req = Request("s", prompt_ids=list(prompt),
                      sampling_params=SamplingParams(
                          temperature=0.0, max_new_tokens=9,
                          stop_token_ids=stop))
        eng.submit(req)
        pipe = InProcessPipeline([eng])
        pipe.run_until_complete()
        return req, eng

    base, beng = run(0, 1)
    multi, meng = run(4, 8)
    assert multi.output_ids == base.output_ids
    assert multi.status.value == "finished_stop"
    assert len(multi.output_ids) == stop_idx + 1
    assert multi.num_computed_tokens == multi.total_len - 1
    bp = beng.cache_digest_payload(full=True)
    mp = meng.cache_digest_payload(full=True)
    assert bp is not None and mp is not None
    assert sorted(bp["full"]) == sorted(mp["full"])


def test_window_respects_max_tokens_and_min_new():
    prompts = [[9, 9, 9, 9, 9, 9, 9, 9]]
    base, _ = _run(0, prompts, max_new=5, lookahead=1)
    spec, _ = _run(8, prompts, max_new=5, lookahead=8,
                   adversarial=[9, 9, 9])
    assert spec[0].output_ids == base[0].output_ids
    assert len(spec[0].output_ids) == 5
    assert spec[0].status == base[0].status
    assert spec[0].num_computed_tokens == spec[0].total_len - 1
    # min_new_tokens gates EOS mid-window exactly as single-step.
    kws = [dict(temperature=0.0)]

    def run_eos(spec_tokens, lookahead):
        eng = _engine(spec_tokens, lookahead=lookahead)
        if spec_tokens:
            _adversarialize(eng, [1, 2, 3])
        req = Request("e", prompt_ids=[9, 9, 9, 9, 9, 9, 9, 9],
                      sampling_params=SamplingParams(
                          temperature=0.0, max_new_tokens=12,
                          min_new_tokens=6))
        req.eos_token_ids = (base[0].output_ids[1],)
        eng.submit(req)
        InProcessPipeline([eng]).run_until_complete()
        return req

    b = run_eos(0, 1)
    s = run_eos(4, 8)
    assert s.output_ids == b.output_ids
    assert s.status == b.status


def test_window_goodput_exactness_with_rejections():
    """Goodput: a spec window classifies every computed position exactly
    once — useful + wasted == total — with ``speculative_rejected`` > 0
    when proposals lose."""
    from parallax_tpu.obs.goodput import get_goodput

    gp0 = get_goodput().snapshot()["tokens"]
    prompts = [[int(x) for x in np.random.default_rng(5).integers(
        1, 198, size=14)]]
    spec, eng = _run(4, prompts, max_new=16, lookahead=8,
                     adversarial=[1, 2, 3])
    gp1 = get_goodput().snapshot()["tokens"]
    delta = {k: gp1[k] - gp0[k] for k in gp1}
    assert _spec_engaged(eng)
    assert delta["speculative_rejected"] > 0, delta
    assert delta["committed"] >= len(spec[0].output_ids), delta
    # Exactness: every classified token is in exactly one bucket by
    # construction; the buckets must account for the whole run
    # (nothing negative, nothing uncounted).
    assert all(v >= 0 for v in delta.values()), delta
    s = eng.spec_summary()
    assert s["rejected"] > 0 and s["proposals"] > 0
    assert 0.0 <= s["acceptance_rate"] <= 1.0


def test_window_page_budget_downshifts_gracefully():
    """A speculative window the planner cannot page retries plain, then
    K=1 — never an abort, streams unchanged."""
    prompts = [[3, 14, 15, 92, 65], [7, 21, 108]]
    base, _ = _run(0, prompts, max_new=12, lookahead=1)
    # num_pages barely covers the contexts: the K*(1+P) reservation
    # cannot be guaranteed, so windows downshift.
    spec, eng = _run(4, prompts, max_new=12, lookahead=8,
                     num_pages=14, adversarial=[1, 2, 3])
    for b, s in zip(base, spec):
        assert s.output_ids == b.output_ids
        assert s.status.value != "finished_abort"


def test_kill_mid_spec_window_ships_committed_only_checkpoints():
    """Live-migration composition: a request extracted mid-flight from
    a speculating engine refuses while its window is in device flight,
    ships a checkpoint holding COMMITTED tokens only (draft state is
    discardable), and the replay-restored stream on a fresh engine is
    bit-identical to the uninterrupted run."""
    from parallax_tpu.runtime.checkpoint import (
        build_resumed_request,
        checkpoint_from_request,
        checkpoint_from_wire,
        checkpoint_to_wire,
    )

    prompt = [5, 6, 5, 6, 5, 6]
    (full,), _ = _run(0, [prompt], max_new=20, lookahead=1)

    eng = _engine(4, lookahead=8)
    _adversarialize(eng, [1, 2, 3])
    req = Request("m", prompt_ids=list(prompt),
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=20,
                      ignore_eos=True))
    eng.submit(req)
    # Drive overlapped until a speculative window is in flight, then
    # "kill": extraction must refuse while the window writes KV.
    eng.cfg.overlap_steps = True
    pending = None
    guard = 0
    while guard < 200:
        guard += 1
        if eng._inflight and req.output_ids:
            break
        _, pending = drive_step(eng, pending)
    assert eng._inflight, "no window ever in flight"
    assert eng.extract("m") is None, "extracted mid-window"
    # Resolve the in-flight window, then park.
    if pending is not None:
        eng.resolve(pending)
    committed_at_kill = list(req.output_ids)
    assert 0 < len(committed_at_kill) < 20
    taken = eng.extract("m")
    assert taken is req
    ck = checkpoint_from_wire(checkpoint_to_wire(
        checkpoint_from_request(req)
    ))
    # Committed-only: the checkpoint carries exactly the committed
    # stream — no proposal/draft state travels.
    assert ck.output_ids == committed_at_kill
    assert ck.kv is None
    eng.cache.release(req)

    target = _engine(4, lookahead=8)
    _adversarialize(target, [1, 2, 3])
    resumed = build_resumed_request(ck, replay=True)
    target.submit(resumed)
    InProcessPipeline([target]).run_until_complete()
    assert resumed.full_output_ids == full.output_ids, (
        resumed.full_output_ids, full.output_ids,
    )


# -- host-sync verify fallback (K=1) -----------------------------------------


def test_sync_fallback_matches_plain_greedy():
    prompts = [
        [7, 8, 9, 10, 7, 8, 9, 10, 7, 8, 9],
        [3, 14, 15, 3, 14, 15, 3, 14],
    ]
    base, _ = _run(0, prompts, lookahead=1)
    spec, eng = _run(6, prompts, lookahead=1)
    assert _spec_engaged(eng)
    assert not eng._jit_spec_multistep      # K=1: no window compiled
    for b, s in zip(base, spec):
        assert s.output_ids == b.output_ids, (b.output_ids, s.output_ids)
        assert s.status == b.status


def test_sync_fallback_random_prompts_exact():
    rng = np.random.default_rng(5)
    prompts = [[int(x) for x in rng.integers(1, 198, size=18)]
               for _ in range(3)]
    base, _ = _run(0, prompts, lookahead=1)
    spec, _ = _run(6, prompts, lookahead=1, adversarial=[4, 4, 4])
    for b, s in zip(base, spec):
        assert s.output_ids == b.output_ids


def test_speculative_windows_compress_host_rounds():
    """With the adaptive default, a speculating engine commits many
    tokens per host round (spec windows where proposals hit, plain
    windows otherwise) — far fewer rounds than tokens. Whether that is
    faster on the chip is not measured: no benchmark cell speculates
    (ROADMAP D16)."""
    eng = _engine(6)                       # adaptive K
    _adversarialize(eng, [1, 2, 3])
    pipe = InProcessPipeline([eng])
    req = Request("r", prompt_ids=[5, 6, 5, 6, 5, 6],
                  sampling_params=SamplingParams(temperature=0.0,
                                                 max_new_tokens=24))
    pipe.submit(req)
    steps = 0
    while pipe.has_work() and steps < 200:
        pipe.step_round()
        steps += 1
    assert len(req.output_ids) == 24
    base, _ = _run(0, [[5, 6, 5, 6, 5, 6]], max_new=24, lookahead=1)
    assert base[0].output_ids == req.output_ids
    assert _spec_engaged(eng)
    assert steps < 12, steps


def test_sampled_seeded_sync_fallback_is_exact():
    """A seeded sampled stream must be IDENTICAL with and without
    speculation (lockstep verification draws each position from the
    target distribution under the same fold_in(key(seed), output_step)
    keys as sequential decode), even against adversarial proposals."""
    prompts = [
        [7, 8, 9, 10, 7, 8, 9, 10, 7, 8, 9],
        [3, 14, 15, 3, 14, 15, 3, 14],
    ]
    kws = [dict(temperature=0.7, seed=123), dict(temperature=0.4, seed=7)]
    base, _ = _run(0, prompts, max_new=14, lookahead=1, sp_kw=kws)
    spec, eng = _run(6, prompts, max_new=14, lookahead=1, sp_kw=kws,
                     adversarial=[1, 2, 3])
    assert _spec_engaged(eng)
    for b, g in zip(base, spec):
        assert g.output_ids == b.output_ids
        assert g.status == b.status


def test_mixed_greedy_and_seeded_batch_speculates_exactly():
    prompts = [
        [7, 8, 9, 10, 7, 8, 9, 10, 7, 8],
        [5, 6, 5, 6, 5, 6, 5],
    ]
    kws = [dict(temperature=0.0), dict(temperature=0.6, seed=5)]
    base, _ = _run(0, prompts, max_new=14, lookahead=1, sp_kw=kws)
    for k in (1, 8):
        spec, eng = _run(6, prompts, max_new=14, lookahead=k,
                         adversarial=[4, 4, 4], sp_kw=kws)
        assert _spec_engaged(eng), k
        for b, g in zip(base, spec):
            assert g.output_ids == b.output_ids, k


def test_unseeded_sampled_speculation_smoke():
    """Unseeded sampled rows have no cross-path reproducibility
    contract; the spec paths must still engage and produce well-formed
    streams."""
    prompts = [[7, 8, 9, 10, 7, 8, 9, 10, 7, 8]]
    kws = [dict(temperature=0.8)]
    for k in (1, 8):
        got, eng = _run(6, prompts, max_new=14, lookahead=k, sp_kw=kws,
                        adversarial=[9, 10, 7])
        assert _spec_engaged(eng), k
        assert len(got[0].output_ids) == 14


# -- draft-model proposals ---------------------------------------------------


def _draft_engine(params=None, key=0):
    from parallax_tpu.runtime.engine import DraftProposer

    model = StageModel(CFG, 0, 2, use_pallas=False)
    p = params if params is not None else model.init_params(
        jax.random.key(key), dtype=jnp.float32
    )
    eng = StageEngine(model, p, EngineConfig(
        page_size=8, num_pages=256, max_model_len=256,
        kv_dtype="float32", decode_lookahead=4,
    ))
    return DraftProposer(eng), p


def test_draft_model_same_weights_accepts_everything():
    """Draft == main: every proposal verifies, outputs match single-step
    greedy exactly (windowed AND sync paths)."""
    prompts = [[3, 14, 15, 92, 65], [7, 21, 108]]
    base, _ = _run(0, prompts, max_new=12, lookahead=1)
    for k in (1, 8):
        draft, _ = _draft_engine(params=_PARAMS)
        got, eng = _run(4, prompts, max_new=12, lookahead=k,
                        draft=draft)
        assert _spec_engaged(eng), k
        assert eng.spec_summary()["by_source"].keys() == {"draft"}
        for b, g in zip(base, got):
            assert g.output_ids == b.output_ids, k
            assert g.status == b.status


def test_draft_model_different_weights_is_still_exact():
    """A bad draft must never change outputs — only acceptance rate."""
    prompts = [[5, 6, 7, 8], [42] * 6]
    base, _ = _run(0, prompts, max_new=10, lookahead=1)
    for k in (1, 8):
        draft, _ = _draft_engine(key=99)    # different random weights
        got, _ = _run(4, prompts, max_new=10, lookahead=k, draft=draft)
        for b, g in zip(base, got):
            assert g.output_ids == b.output_ids, k
            assert g.status == b.status


def test_sampled_seeded_speculation_is_exact_draft_model():
    prompts = [[7, 8, 9, 10, 7, 8], [42] * 6]
    kws = [dict(temperature=0.5, seed=11), dict(temperature=0.9, seed=99)]
    base, _ = _run(0, prompts, max_new=14, lookahead=1, sp_kw=kws,
                   params=_PARAMS)
    draft, _ = _draft_engine(params=_PARAMS)
    spec, eng = _run(4, prompts, max_new=14, lookahead=1, sp_kw=kws,
                     params=_PARAMS, draft=draft)
    assert _spec_engaged(eng)
    for b, g in zip(base, spec):
        assert g.output_ids == b.output_ids
        assert g.status == b.status


def test_draft_proposer_budget_properties():
    """Property-style: proposals never exceed the requested budget, the
    draft's context limit, or the page budget — and aborted/finished
    drafts never leak into later rounds."""
    draft, _ = _draft_engine()
    rng = np.random.default_rng(13)
    for trial in range(6):
        n_rows = int(rng.integers(1, 5))
        contexts = [
            [int(x) for x in rng.integers(1, 198,
                                          size=rng.integers(2, 40))]
            for _ in range(n_rows)
        ]
        budgets = [int(b) for b in rng.integers(0, 9, size=n_rows)]
        props = draft.propose_batch(contexts, budgets)
        assert len(props) == n_rows
        for prop, budget, ctx in zip(props, budgets, contexts):
            assert len(prop) <= max(0, budget)
            assert len(ctx) + len(prop) < 256   # draft max_model_len
        # Nothing queued between rounds (leaked drafts would be
        # re-stepped by every later proposal round).
        assert draft.engine.scheduler.num_requests() == 0
    # Context at/over the draft's model length proposes nothing.
    props = draft.propose_batch([[1] * 300, [1, 2, 3]], [4, 4])
    assert props[0] == []
    assert len(props[1]) <= 4
    assert draft.engine.scheduler.num_requests() == 0


def test_slow_draft_cannot_stall_the_batch():
    """Proposal wall time is bounded and a deadline-stopped round aborts
    (releases) its unfinished drafts — nothing queues up to be
    re-stepped by later rounds."""
    import time as _time

    draft, _ = _draft_engine()
    draft.propose_batch([[1, 2, 3, 4, 5]] * 4, [6] * 4)   # warm jits
    draft.max_propose_ms = 1.0
    real_step = draft.engine.step

    def slow_step():
        _time.sleep(0.05)
        return real_step()

    draft.engine.step = slow_step
    t0 = _time.perf_counter()
    props = draft.propose_batch([[1, 2, 3, 4, 5]] * 4, [6] * 4)
    elapsed_ms = (_time.perf_counter() - t0) * 1000.0
    assert elapsed_ms < 1000.0, elapsed_ms
    assert len(props) == 4
    assert draft.engine.scheduler.num_requests() == 0

    draft.engine.step = real_step
    prompts = [[5, 6, 7, 8]]
    base, _ = _run(0, prompts, max_new=8, lookahead=1)
    got, _ = _run(4, prompts, max_new=8, lookahead=1, draft=draft)
    assert got[0].output_ids == base[0].output_ids


def test_draft_proposer_reuses_active_compile_cache(tmp_path):
    """Enabling speculation must not pay a second compile storm: the
    proposer records (and never re-points) the process's persistent
    compile cache — whatever directory the serving entrypoint already
    activated."""
    from parallax_tpu.utils import compile_cache

    active = compile_cache.active_cache_dir()
    draft, _ = _draft_engine()
    assert draft.compile_cache_dir == active
    assert compile_cache.active_cache_dir() == active


# -- adaptive-K interplay ----------------------------------------------------


def test_spec_rows_no_longer_downshift_adaptive_windows():
    """PR 6's adaptive rule dropped spec batches to K=1; windowed
    speculation removes it — with the ADAPTIVE default and speculation
    on, decode batches compile and run the speculative window."""
    prompts = [[5, 6, 5, 6, 5, 6]]
    base, _ = _run(0, prompts, max_new=20, lookahead=1)
    spec, eng = _run(4, prompts, max_new=20, lookahead=None,
                     adversarial=[1, 2, 3])     # adaptive default
    assert eng._jit_spec_multistep, "adaptive K did not run spec windows"
    assert spec[0].output_ids == base[0].output_ids


def test_host_state_rows_ride_the_spec_window():
    """Penalized rows are scan-carry state now: they speculate inside
    the window (the "pen" spec variant compiles) and streams still
    match the non-spec engine token-for-token."""
    prompts = [[7, 8, 9, 10, 7, 8, 9, 10, 7, 8, 9]]
    kws = [dict(temperature=1.0, seed=3, repetition_penalty=1.3)]
    base, _ = _run(0, prompts, max_new=12, lookahead=1, sp_kw=kws)
    spec, eng = _run(4, prompts, max_new=12, lookahead=8, sp_kw=kws,
                     adversarial=[1, 2, 3])
    assert any(key[4] == ("pen",) for key in eng._jit_spec_multistep), (
        eng._jit_spec_multistep.keys()
    )
    assert spec[0].output_ids == base[0].output_ids
    # The host-sync verify fallback (K=1) still has no feature state:
    # those batches decode one token per step, streams unchanged.
    sync, seng = _run(4, prompts, max_new=12, lookahead=1, sp_kw=kws)
    assert not seng._jit_spec_multistep
    assert not _spec_engaged(seng)
    assert sync[0].output_ids == base[0].output_ids
