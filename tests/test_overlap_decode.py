"""Overlapped two-phase decode (dispatch/resolve, one step in flight) —
exact parity with the synchronous engine, plus the safety invariants the
overlap loop relies on (one-in-flight enforcement, mid-stream abort,
dispatch-failure consistency)."""

import json

import jax
import jax.numpy as jnp
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.models.base import StageModel
from parallax_tpu.runtime.engine import (
    EngineConfig,
    StageEngine,
    drive_step,
)
from parallax_tpu.runtime.request import Request, SamplingParams

CFG = normalize_config(dict(
    architectures=["Qwen2ForCausalLM"], hidden_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=128, vocab_size=258, max_position_embeddings=512,
    tie_word_embeddings=False,
))

# Byte-level grammar vocabulary (tokens 0..255 are raw bytes, 257 = EOS)
# so json_schema enforcement runs without a real tokenizer.
BYTE_VOCAB = [bytes([i]) for i in range(256)] + [b"", b""]
EOS = 257
SCHEMA = json.dumps({
    "type": "object",
    "properties": {"v": {"enum": ["x", "y"]}},
    "required": ["v"],
})

PROMPTS = [[3, 14, 15, 92, 65], [7, 21, 108], [42] * 9]


@pytest.fixture(scope="module")
def model_and_params():
    model = StageModel(CFG, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    return model, params


def _engine(model_and_params, overlap, grammar=False):
    model, params = model_and_params
    eng = StageEngine(model, params, EngineConfig(
        page_size=8, num_pages=128, max_model_len=256,
        kv_dtype="float32", overlap_steps=overlap,
    ))
    if grammar:
        eng.set_grammar_vocab(BYTE_VOCAB, EOS)
    return eng


def _drive(eng, max_iters=500):
    """The one-in-flight loop every driver runs. Returns the StepOutputs
    stream."""
    outs_all = []
    pending = None
    iters = 0
    while (eng.has_work() or pending is not None) and iters < max_iters:
        iters += 1
        outs, pending = drive_step(eng, pending)
        outs_all.extend(outs)
    assert pending is None and not eng._inflight
    return outs_all


def _run(model_and_params, overlap, make_params, grammar=False,
         prompts=PROMPTS):
    eng = _engine(model_and_params, overlap, grammar=grammar)
    reqs = []
    for i, prompt in enumerate(prompts):
        req = Request(f"r{i}", prompt_ids=list(prompt),
                      sampling_params=make_params(i))
        reqs.append(req)
        eng.submit(req)
    outs = _drive(eng)
    return reqs, eng, outs


def _assert_equal_streams(base, over):
    for b, m in zip(base, over):
        assert m.output_ids == b.output_ids, (b.output_ids, m.output_ids)
        assert m.status == b.status, (b.status, m.status)


# -- sync-vs-overlap bit-exactness -------------------------------------


def test_overlap_matches_sync_greedy(model_and_params):
    mk = lambda i: SamplingParams(temperature=0.0, max_new_tokens=11,
                                  ignore_eos=True)
    base, _, _ = _run(model_and_params, False, mk)
    over, eng, outs = _run(model_and_params, True, mk)
    _assert_equal_streams(base, over)
    # The overlap actually engaged (steps resolved after a later
    # dispatch) and cleaned up after itself.
    assert any(o.overlapped for o in outs)
    assert len(eng._free_token_slots) == eng.cfg.max_batch_size


def test_overlap_matches_sync_seeded_sampling(model_and_params):
    mk = lambda i: SamplingParams(temperature=0.8, seed=1000 + i,
                                  max_new_tokens=9, ignore_eos=True)
    base, _, _ = _run(model_and_params, False, mk)
    over, _, outs = _run(model_and_params, True, mk)
    _assert_equal_streams(base, over)
    assert any(o.overlapped for o in outs)


def test_overlap_matches_sync_penalties(model_and_params):
    # Penalty rows force a sync resolve; a penalty-free greedy row rides
    # in the same batch to exercise the mixed path.
    def mk(i):
        if i == 1:
            return SamplingParams(temperature=0.0, max_new_tokens=9,
                                  ignore_eos=True)
        return SamplingParams(
            temperature=0.0, max_new_tokens=9, ignore_eos=True,
            presence_penalty=0.4, frequency_penalty=0.3,
            repetition_penalty=1.2,
        )
    base, _, _ = _run(model_and_params, False, mk)
    over, _, _ = _run(model_and_params, True, mk)
    _assert_equal_streams(base, over)


def test_overlap_matches_sync_logit_bias(model_and_params):
    mk = lambda i: SamplingParams(
        temperature=0.0, max_new_tokens=8, ignore_eos=True,
        logit_bias={17: 4.0, 29: -6.0},
    )
    base, _, _ = _run(model_and_params, False, mk)
    over, _, _ = _run(model_and_params, True, mk)
    _assert_equal_streams(base, over)


def test_overlap_matches_sync_grammar(model_and_params):
    mk = lambda i: SamplingParams(temperature=0.0, max_new_tokens=40,
                                  json_schema=SCHEMA)
    base, _, _ = _run(model_and_params, False, mk, grammar=True,
                      prompts=[[1, 2, 3], [5, 6]])
    over, _, _ = _run(model_and_params, True, mk, grammar=True,
                      prompts=[[1, 2, 3], [5, 6]])
    _assert_equal_streams(base, over)
    out = bytes(t for t in base[0].output_ids if t < 256)
    assert json.loads(out)["v"] in ("x", "y")


def test_overlap_matches_sync_host_sync_join_mid_stream(model_and_params):
    """A host-synchronous request (logit_bias) joining mid-stream forces
    the running seeded row's next step onto the sync resolve path while
    its previous token is device-fed: the seeded per-output-index keys
    must not shift (regression: resolve-time packing double-counted the
    already-committed fed token)."""
    def run(overlap):
        eng = _engine(model_and_params, overlap)
        seeded = Request("s", prompt_ids=[3, 14, 15],
                         sampling_params=SamplingParams(
                             temperature=0.8, seed=1234, max_new_tokens=12,
                             ignore_eos=True))
        eng.submit(seeded)
        late = None
        pending = None
        iters = 0
        while (eng.has_work() or pending is not None) and iters < 200:
            iters += 1
            _, pending = drive_step(eng, pending)
            if late is None and len(seeded.output_ids) >= 3:
                late = Request("b", prompt_ids=[7, 8],
                               sampling_params=SamplingParams(
                                   temperature=0.0, max_new_tokens=6,
                                   ignore_eos=True,
                                   logit_bias={17: 4.0}))
                eng.submit(late)
        return seeded, late
    sb, lb = run(False)
    so, lo = run(True)
    assert so.output_ids == sb.output_ids, (sb.output_ids, so.output_ids)
    assert lo.output_ids == lb.output_ids


def test_overlap_matches_sync_eos_mid_stream(model_and_params):
    """A row finishing on EOS mid-overlap: the surplus in-flight step's
    token must be discarded, never committed."""
    greedy = lambda i: SamplingParams(temperature=0.0, max_new_tokens=9,
                                      ignore_eos=True)
    probe, _, _ = _run(model_and_params, False, greedy)
    eos = (probe[0].output_ids[3],)

    def mk(i):
        return SamplingParams(temperature=0.0, max_new_tokens=9)
    def with_eos(overlap):
        eng = _engine(model_and_params, overlap)
        reqs = []
        for i, prompt in enumerate(PROMPTS):
            req = Request(f"r{i}", prompt_ids=list(prompt),
                          sampling_params=mk(i), eos_token_ids=eos)
            reqs.append(req)
            eng.submit(req)
        _drive(eng)
        return reqs, eng
    base, _ = with_eos(False)
    over, eng = with_eos(True)
    _assert_equal_streams(base, over)
    assert len(eng._free_token_slots) == eng.cfg.max_batch_size


# -- overlap-loop safety invariants ------------------------------------


def test_one_in_flight_enforced(model_and_params):
    eng = _engine(model_and_params, True)
    req = Request("r", prompt_ids=[5, 6, 7],
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=8, ignore_eos=True))
    eng.submit(req)
    t1 = eng.dispatch()          # prefill + deferred sample
    t2 = eng.dispatch()          # device-fed decode, one in flight
    with pytest.raises(RuntimeError, match="in flight"):
        eng.dispatch()
    eng.resolve(t1)
    eng.resolve(t2)
    _drive(eng)
    assert req.status.is_finished
    assert len(req.output_ids) == 8


def test_overlap_survives_mid_stream_abort(model_and_params):
    eng = _engine(model_and_params, True)
    reqs = []
    for i, prompt in enumerate(PROMPTS):
        req = Request(f"r{i}", prompt_ids=list(prompt),
                      sampling_params=SamplingParams(
                          temperature=0.0, max_new_tokens=20,
                          ignore_eos=True))
        reqs.append(req)
        eng.submit(req)
    pending = None
    iters = 0
    while (eng.has_work() or pending is not None) and iters < 200:
        iters += 1
        _, pending = drive_step(eng, pending)
        if iters == 4:
            # Abort one request while its step is in flight.
            eng.release("r1", abort=True)
    assert reqs[1].status.value == "finished_abort"
    for r in (reqs[0], reqs[2]):
        assert len(r.output_ids) == 20
    # Slots and in-flight state fully reclaimed; the engine still serves.
    assert len(eng._free_token_slots) == eng.cfg.max_batch_size
    follow = Request("f", prompt_ids=[9, 8, 7],
                     sampling_params=SamplingParams(
                         temperature=0.0, max_new_tokens=4,
                         ignore_eos=True))
    eng.submit(follow)
    _drive(eng)
    assert len(follow.output_ids) == 4


def test_dispatch_exception_leaves_scheduler_consistent(model_and_params):
    eng = _engine(model_and_params, True)
    req = Request("r", prompt_ids=[5, 6, 7],
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=6, ignore_eos=True))
    eng.submit(req)
    real = eng._jit_step
    calls = {"n": 0}

    def boom(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("injected dispatch failure")
        return real(*a, **kw)

    eng._jit_step = boom
    with pytest.raises(RuntimeError, match="injected"):
        eng.dispatch()
    # No bookkeeping advanced, nothing in flight: the same work is
    # re-schedulable and the run completes normally.
    assert not eng._inflight
    assert req.num_computed_tokens == 0
    _drive(eng)
    assert req.status.is_finished
    assert len(req.output_ids) == 6
    # Matches a clean engine's stream.
    base, _, _ = _run(
        model_and_params, False,
        lambda i: SamplingParams(temperature=0.0, max_new_tokens=6,
                                 ignore_eos=True),
        prompts=[[5, 6, 7]],
    )
    assert req.output_ids == base[0].output_ids


def test_resolve_failure_does_not_wedge_dispatch(model_and_params):
    """A resolve() failure mid-loop must not orphan the just-dispatched
    ticket in the in-flight list — that would wedge every later dispatch
    on the one-in-flight invariant."""
    eng = _engine(model_and_params, True)
    req = Request("r", prompt_ids=[5, 6, 7],
                  sampling_params=SamplingParams(
                      temperature=0.0, max_new_tokens=12,
                      ignore_eos=True))
    eng.submit(req)
    pending = None
    _, pending = drive_step(eng, pending)
    assert pending is not None
    real = eng._emit_tokens

    def boom(*a, **kw):
        eng._emit_tokens = real
        raise RuntimeError("injected resolve failure")

    eng._emit_tokens = boom
    with pytest.raises(RuntimeError, match="injected"):
        drive_step(eng, pending)
    # Both tickets are out of flight; the failed step's rows were
    # aborted, and the engine serves fresh work.
    assert not eng._inflight
    assert req.status.value == "finished_abort"
    follow = Request("f2", prompt_ids=[9, 8],
                     sampling_params=SamplingParams(
                         temperature=0.0, max_new_tokens=5,
                         ignore_eos=True))
    eng.submit(follow)
    _drive(eng)
    assert len(follow.output_ids) == 5


def test_step_outputs_timing_fields(model_and_params):
    _, eng, outs = _run(
        model_and_params, True,
        lambda i: SamplingParams(temperature=0.0, max_new_tokens=6,
                                 ignore_eos=True),
    )
    real = [o for o in outs if o.num_tokens]
    assert real and all(o.host_ms > 0.0 for o in real)
    assert all(o.readback_wait_ms >= 0.0 for o in real)
    summary = eng.step_timing.summary()
    assert summary is not None
    assert summary["steps"] == len(real)
    assert 0.0 <= summary["overlap_fraction"] <= 1.0
