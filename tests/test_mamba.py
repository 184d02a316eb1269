"""Mamba-1 mixers on state slots (ops/mamba.py, models/jamba.py): the
chunked scan against the sequential recurrence, the decode kernel
against its ``jax.numpy`` oracle, state reset and carried through the
engine, a snapshot restored at a page boundary, and the page budget of
a hybrid. CPU, float32, toy widths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.models.registry import create_stage_model
from parallax_tpu.ops import mamba as M
from parallax_tpu.runtime.cache_manager import (
    derive_num_pages,
    kv_bytes_per_page,
)
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

# d_inner spans two of the kernels' lane chunks.
DI, N, K = 2048, 16, 4

JAMBA_TOY = dict(
    architectures=["JambaForCausalLM"], model_type="jamba", hidden_size=64,
    intermediate_size=96, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=1, vocab_size=211, attn_layer_period=2,
    attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
    num_experts=1, num_experts_per_tok=1, tie_word_embeddings=True,
    rms_norm_eps=1e-6, max_position_embeddings=512)


def ragged(rng, lens, t_len):
    """Scan inputs for rows of ``lens`` tokens in a stream of ``t_len``."""
    cu = np.zeros(len(lens) + 1, np.int32)
    cu[1:] = np.cumsum(lens)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return dict(
        dt=jax.nn.softplus(f(t_len, DI) - 2.0), xs=f(t_len, DI),
        b=f(t_len, N), c=f(t_len, N),
        a_t=-jnp.exp(f(N, DI) * 0.5), state=f(len(lens), N, DI),
        cu=jnp.asarray(cu))


def sequential(x, lens):
    """One dependent step a token, a row at a time."""
    ys = np.zeros((x["xs"].shape[0], DI), np.float32)
    finals = []
    cu = np.asarray(x["cu"])
    for i, n in enumerate(lens):
        h = x["state"][i]
        for t in range(cu[i], cu[i] + n):
            # dt through its inverse softplus, an open gate, no D skip:
            # the oracle's step is then the scan's.
            y, h = M.ssm_step_reference(
                jnp.log(jnp.expm1(x["dt"][t]))[None], x["xs"][t][None],
                jnp.full((1, DI), 40.0), x["b"][t][None],
                x["c"][t][None], x["a_t"], jnp.zeros((DI,)), h[None])
            y = y / jax.nn.silu(40.0)
            ys[t], h = np.asarray(y[0]), h[0]
        finals.append(np.asarray(h))
    return ys, np.stack(finals)


@pytest.mark.parametrize("chunk", [1, 7, 64])
@pytest.mark.parametrize("lens", [[448], [100, 1, 37, 0, 200, 64]],
                         ids=["one-row", "ragged"])
def test_chunked_scan_is_the_sequential_recurrence(chunk, lens):
    x = ragged(np.random.default_rng(1), lens, 448)
    want_y, want_h = sequential(x, lens)
    y, h = jax.jit(M.ssm_prefill_scan, static_argnames="chunk")(
        x["dt"], x["xs"], x["b"], x["c"], x["a_t"], x["state"], x["cu"],
        chunk=chunk)
    real = int(np.sum(lens))
    np.testing.assert_allclose(np.asarray(y)[:real], want_y[:real],
                               rtol=2e-5, atol=2e-5)
    # A row without tokens keeps its state; the others end at their last.
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=2e-5, atol=2e-5)


def test_scan_refuses_a_stream_that_is_no_multiple_of_its_chunk():
    x = ragged(np.random.default_rng(1), [100], 100)
    with pytest.raises(ValueError, match="no multiple"):
        M.ssm_prefill_scan(x["dt"], x["xs"], x["b"], x["c"], x["a_t"],
                           x["state"], x["cu"], chunk=64)


@pytest.mark.parametrize("lens", [[64], [5, 1, 0, 2, 30]],
                         ids=["one-row", "ragged"])
def test_conv_over_the_stream_carries_each_rows_window(lens):
    rng = np.random.default_rng(2)
    t_len = 64
    cu = np.zeros(len(lens) + 1, np.int32)
    cu[1:] = np.cumsum(lens)
    xs = rng.normal(size=(t_len, DI)).astype(np.float32)
    window = rng.normal(size=(len(lens), K - 1, DI)).astype(np.float32)
    w = rng.normal(size=(K, DI)).astype(np.float32)
    bias = rng.normal(size=(DI,)).astype(np.float32)
    y, new = M.conv_prefill(jnp.asarray(xs), jnp.asarray(window),
                            jnp.asarray(w), jnp.asarray(bias),
                            jnp.asarray(cu))
    for i, n in enumerate(lens):
        full = np.concatenate([window[i], xs[cu[i]: cu[i] + n]])
        want = sum(full[j: j + n] * w[j] for j in range(K)) + bias
        np.testing.assert_allclose(np.asarray(y)[cu[i]: cu[i] + n],
                                   np.asarray(jax.nn.silu(want)),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(new)[i], full[n:])
    # The decode step is the same convolution, one token a row.
    # (its windows in rows 0..K-2 of a slot's tile of 8), in plain
    # jax.numpy and as the Pallas kernel in interpret mode.
    slots = jnp.asarray([3, 1, 2], jnp.int32)
    reset = jnp.asarray([0, 1, 0], jnp.int32)
    all_w = jnp.zeros((5, M.CONV_ROWS, DI), jnp.float32).at[:, : K - 1].set(
        jnp.asarray(rng.normal(size=(5, K - 1, DI)), jnp.float32))
    y2, new2 = M.conv_prefill(
        jnp.asarray(xs[:3]),
        all_w[slots, : K - 1] * jnp.asarray([1.0, 0.0, 1.0])[:, None, None],
        jnp.asarray(w), jnp.asarray(bias), jnp.arange(4, dtype=jnp.int32))
    for use_pallas in (False, True):
        if use_pallas:
            y1, all_new = M.ssm_conv_update(
                jnp.asarray(xs[:3]), jnp.asarray(w), jnp.asarray(bias),
                all_w, slots, reset, interpret=True)
        else:
            y1, all_new = M.conv_decode_step(
                jnp.asarray(xs[:3]), all_w, jnp.asarray(w),
                jnp.asarray(bias), slots, reset, use_pallas=False)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(all_new)[np.asarray(slots), : K - 1],
            np.asarray(new2))
        assert not np.asarray(all_new)[:, K - 1:].any()
        untouched = np.asarray([0, 4])
        np.testing.assert_array_equal(np.asarray(all_new)[untouched],
                                      np.asarray(all_w)[untouched])


@pytest.mark.parametrize("di", [256, 2048])
def test_decode_kernel_in_interpret_mode_is_the_plain_step(di):
    """State in, state out in place by slot index, ``dt``'s softplus and
    the gate inside; a row with ``reset`` starts from zeros; slots no
    row names are untouched."""
    rng = np.random.default_rng(3)
    s, slots_total = 8, 21
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt, xs, z = f(s, di) - 2.0, f(s, di), f(s, di)
    b, c, a_t, d = f(s, N), f(s, N), -jnp.exp(f(N, di) * 0.5), f(di)
    state = f(slots_total, N, di)
    slots = jnp.asarray([5, 1, 20, 7, 2, 9, 11, 3], jnp.int32)
    reset = jnp.asarray([0, 0, 1, 0, 0, 0, 1, 0], jnp.int32)
    y, new = M.ssm_decode_update(dt, xs, z, b, c, a_t, d, state, slots,
                                 reset, interpret=True)
    want_y, want = M.ssm_decode_step(dt, xs, z, b, c, a_t, d, state, slots,
                                     reset, use_pallas=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    untouched = sorted(set(range(slots_total)) - set(np.asarray(slots)))
    np.testing.assert_array_equal(np.asarray(new)[untouched],
                                  np.asarray(state)[untouched])


# -- through the engine ----------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    cfg = normalize_config(JAMBA_TOY)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers, tp_size=1)
    return model, model.init_params(jax.random.key(3), dtype=jnp.float32)


def serve(toy, prompts, new_tokens=20, **engine):
    """Greedy rows through an engine, one after the other (so that a
    later row takes the slot an earlier one gave back)."""
    model, params = toy
    kw = dict(page_size=16, num_pages=64, max_model_len=160,
              kv_dtype="float32", enable_prefix_cache=False)
    kw.update(engine)
    eng = StageEngine(model, params, EngineConfig(**kw))
    pipe = InProcessPipeline([eng])
    out = []
    for i, p in enumerate(prompts):
        r = Request(f"r{i}", prompt_ids=list(p),
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=new_tokens,
                        ignore_eos=True, logprobs=True))
        pipe.submit(r)
        pipe.run_until_complete()
        out.append(r)
    return eng, out


def same(a, b):
    assert list(a.output_ids) == list(b.output_ids)
    np.testing.assert_allclose(a.output_logprobs, b.output_logprobs,
                               atol=2e-4)


def test_the_config_maps_jamba_onto_the_block(toy):
    model, params = toy
    cfg = model.config
    assert cfg.layer_types == ("linear_attention", "attention") * 2
    assert cfg.moe is None and not cfg.use_rope and cfg.is_hybrid
    assert cfg.mamba.d_inner == 128 and cfg.mamba.dt_rank == 8
    assert model.has_linear_layers and not model.state_dense_rows
    # A Mamba hybrid carries its stream in float32; no other model's
    # block changes.
    assert cfg.fp32_residual and cfg.precise_stream
    dense = normalize_config(dict(JAMBA_TOY, architectures=["Qwen2ForCausalLM"],
                                  model_type="qwen2"))
    assert dense.mamba is None and not dense.precise_stream
    assert not dense.fp32_residual and dense.use_rope
    assert cfg.num_paged_layers() == 2 and cfg.num_paged_layers(0, 1) == 0
    assert cfg.state_bytes_per_slot() == 2 * 4 * 128 * (16 + 8)
    assert ["mamba" in l for l in params["layers"]] == [True, False] * 2
    # Per kind: a Mamba layer is no attention layer's size.
    m = 64 * 256 + 128 * 5 + 128 * 40 + 40 + 8 * 128 + 128 + 128 * 17 + 128 * 64
    assert cfg.decoder_layer_params(0) == m + 3 * 64 * 96 + 2 * 64
    assert cfg.decoder_layer_params(1) == (
        64 * 64 + 2 * 64 * 16 + 64 * 64 + 3 * 64 * 96 + 2 * 64)
    kv = model.new_kv_caches(8, 16, jnp.float32, num_state_slots=5)
    assert [c[0].shape for c in kv[::2]] == [(6, 8, 128)] * 2
    assert [c[1].shape for c in kv[::2]] == [(6, 16, 128)] * 2
    assert all(c[1].dtype == jnp.float32 for c in kv[::2])
    assert kv[1].shape == (8, 16, 2, 16)
    with pytest.raises(ValueError, match="tp-size 1"):
        create_stage_model(cfg, 0, 4, tp_size=2)


@pytest.mark.parametrize("rows", [8, 136], ids=["a-decode-step", "a-chunk"])
def test_float32_rows_reach_the_matmuls_as_two_halves(rows):
    """``linear_f32``: a float32 activation against bfloat16 weights is
    multiplied as ``hi + lo``, nearer the float32 product than one
    rounding, whatever the number of rows (one path: a token is computed
    the same in a decode step and in a prefill chunk); an activation in
    the weights' dtype is multiplied as it is."""
    from parallax_tpu.models import layers as L

    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=(96, 64)), jnp.bfloat16)
    p = {"weight": w}
    x = jnp.asarray(rng.normal(size=(rows, 64)), jnp.float32)
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64).T
    err = lambda x: np.abs(np.asarray(L.linear_f32(x, p)) - exact).max()
    assert L.linear_f32(x, p).dtype == jnp.float32
    assert err(x) < err(x.astype(jnp.bfloat16)) / 20


def test_state_is_carried_across_chunks_and_a_k8_window(toy):
    """A 70-token prompt in chunks of 24 and decode in K=8 windows gives
    what one chunk and one step at a time give."""
    rng = np.random.default_rng(5)
    prompts = rng.integers(0, 211, (2, 70)).tolist()
    _, whole = serve(toy, prompts, prefill_chunk_size=128,
                     decode_lookahead=1)
    _, cut = serve(toy, prompts, prefill_chunk_size=24, decode_lookahead=8)
    for a, b in zip(whole, cut):
        same(a, b)


def test_a_mixed_step_is_right_for_the_decodes_and_the_chunk(toy):
    """A 90-token prompt arrives while two rows decode: its chunks of 24
    run in steps beside their decode tokens (one flat stream, three
    rows), and all three rows read as they do alone."""
    model, params = toy
    rng = np.random.default_rng(8)
    short = rng.integers(0, 211, (2, 30)).tolist()
    late = rng.integers(0, 211, 90).tolist()
    eng = StageEngine(model, params, EngineConfig(
        page_size=16, num_pages=64, max_model_len=160, kv_dtype="float32",
        enable_prefix_cache=False, prefill_chunk_size=24,
        decode_lookahead=1))
    pipe = InProcessPipeline([eng])

    def request(name, prompt, n):
        return Request(name, prompt_ids=list(prompt),
                       sampling_params=SamplingParams(
                           temperature=0.0, max_new_tokens=n,
                           ignore_eos=True, logprobs=True))

    rows = [request(f"s{i}", p, 24) for i, p in enumerate(short)]
    for r in rows:
        pipe.submit(r)
    while min(len(r.output_ids) for r in rows) < 3:
        pipe.step_round()
    rows.append(request("late", late, 10))
    pipe.submit(rows[-1])
    pipe.run_until_complete()
    # The late prompt's prefill overlapped the others' decode.
    assert all(len(r.output_ids) == r.sampling_params.max_new_tokens
               for r in rows)
    for r, prompt in zip(rows, short + [late]):
        _, (alone,) = serve(toy, [prompt],
                            new_tokens=r.sampling_params.max_new_tokens)
        same(r, alone)


def test_state_is_reset_on_a_rows_first_chunk(toy):
    """A row that takes over a slot a finished row gave back starts from
    zeros: it decodes as it does alone on a new engine."""
    rng = np.random.default_rng(6)
    first, second = rng.integers(0, 211, (2, 40)).tolist()
    eng, (_, reused) = serve(toy, [first, second], prefill_chunk_size=24)
    _, (alone,) = serve(toy, [second], prefill_chunk_size=24)
    same(reused, alone)
    assert eng._slot_alloc.num_free == eng._slot_alloc.num_slots


def test_a_snapshot_restored_at_a_page_boundary_continues_the_row(toy):
    """The prefix cache of a hybrid: the first row's state is snapshot
    at the deepest page boundary inside its 43-token prompt (32); the
    second shares 40 tokens with it, resumes from that snapshot and
    continues as the uninterrupted row does."""
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 211, 40).tolist()
    tails = rng.integers(0, 211, (2, 3)).tolist()
    prompts = [shared + t for t in tails]
    eng, (_, resumed) = serve(toy, prompts, enable_prefix_cache=True,
                              prefill_chunk_size=24)
    assert resumed.num_cached_tokens == 32
    _, (alone,) = serve(toy, prompts[1:])
    same(resumed, alone)
    # A snapshot and a restore at least, each under its span; the rows
    # are done, so what is in use is what the prefix cache keeps.
    assert eng._h_state_snapshot.count >= 2
    eng._collect_obs()
    assert eng._g_state_total.value == 2 * eng.cfg.max_batch_size + 32
    assert 1 <= eng._g_state_in_use.value <= 32


def test_a_decoding_row_is_snapshot_at_its_stride_with_every_slot_attached(
        toy, monkeypatch):
    """Two finished rows leave both snapshot slots attached to the tree
    (any server after its first requests). A third row, whose K=8
    windows end on page boundaries (prompt 24, pages of 16), still gets
    its decode snapshots — at 32, and then every stride of 2 pages, at
    64 and 96 — the first in the tree's least recently used slot, the
    others over it; a follow-up that sends the whole conversation
    resumes at 96."""
    copies = []
    copy_state = StageEngine._copy_state
    monkeypatch.setattr(
        StageEngine, "_copy_state",
        lambda self, src, dst: (copies.append(dst),
                                copy_state(self, src, dst))[1])
    rng = np.random.default_rng(11)
    fill = rng.integers(0, 211, (2, 41)).tolist()
    row = rng.integers(0, 211, 24).tolist()
    eng, (*_, first) = serve(
        toy, fill + [row], new_tokens=90, enable_prefix_cache=True,
        linear_prefix_slots=2, linear_decode_snapshot_stride=2,
        decode_lookahead=8)
    # A prefill snapshot a row (at 32, 32 and 16: the third takes the
    # first's slot), then the third row's three decode snapshots.
    a, b = eng._prefix_slot_base, eng._prefix_slot_base + 1
    assert copies == [a, b, a, b, b, b]
    assert eng._prefix_slot_alloc.num_free == 0
    turn = Request("again", prompt_ids=list(first.all_token_ids[:100]),
                   sampling_params=SamplingParams(
                       temperature=0.0, max_new_tokens=4, ignore_eos=True))
    pipe = InProcessPipeline([eng])
    pipe.submit(turn)
    pipe.run_until_complete()
    assert turn.num_cached_tokens == 96


# -- the page budget ---------------------------------------------------------

GIB = 1 << 30


@pytest.mark.parametrize("hf, layers, per_page", [
    # Qwen2.5-7B cut to 24 layers, Qwen2.5-3B, EvaByte cut to 16: every
    # layer attends, and a page costs what it cost before.
    (dict(architectures=["Qwen2ForCausalLM"], hidden_size=3584,
          num_hidden_layers=24, num_attention_heads=28,
          num_key_value_heads=4, intermediate_size=18944,
          vocab_size=152064), 24, 2 * 4 * 128 * 2 * 64 * 24),
    (dict(architectures=["Qwen2ForCausalLM"], hidden_size=2048,
          num_hidden_layers=36, num_attention_heads=16,
          num_key_value_heads=2, intermediate_size=11008,
          vocab_size=151936), 36, 2 * 2 * 128 * 2 * 64 * 36),
    (dict(architectures=["EvaByteForCausalLM"], model_type="evabyte",
          hidden_size=4096, num_hidden_layers=16, num_attention_heads=32,
          num_key_value_heads=32, intermediate_size=11008, vocab_size=320,
          window_size=2048, chunk_size=16), 16, 2 * 32 * 128 * 2 * 64 * 16),
], ids=["qwen2.5-7b-d24", "qwen2.5-3b", "evabyte-6.5b-d16"])
def test_the_accepted_configurations_pages_cost_what_they_did(
        hf, layers, per_page):
    cfg = normalize_config(hf)
    assert cfg.num_paged_layers() == layers == cfg.num_hidden_layers
    assert cfg.state_bytes_per_slot() == 0
    assert kv_bytes_per_page(cfg, cfg.num_paged_layers(), 64) == per_page
    for free in (3 * GIB, 9 * GIB + 12345):
        assert derive_num_pages(free, cfg, cfg.num_paged_layers(), 64) == (
            int(free * 0.9) // per_page)


def test_a_hybrid_pays_pages_only_where_it_attends_and_state_first():
    hf = dict(JAMBA_TOY, hidden_size=2560, intermediate_size=8192,
              num_hidden_layers=28, num_attention_heads=20,
              attn_layer_period=14, attn_layer_offset=7, mamba_dt_rank=160,
              vocab_size=65536)
    cfg = normalize_config(hf)
    assert cfg.num_paged_layers() == 2
    per_page = kv_bytes_per_page(cfg, cfg.num_paged_layers(), 64)
    assert per_page == 65536
    assert 14 * per_page == kv_bytes_per_page(cfg, 28, 64) == 917504
    # 26 layers of (5120 x 16 + 5120 x 8) float32 (the convolution's 3
    # rows lie in a tile of 8): 12.8 MB a slot; the slots serve's
    # defaults hold (2 x 64 + 32 + 1) come off first.
    slot = cfg.state_bytes_per_slot()
    assert slot == 26 * 4 * 5120 * 24 == 12779520
    state = 161 * slot
    free = 9 * GIB
    assert derive_num_pages(free, cfg, 2, 64, state_bytes=state) == (
        (int(free * 0.9) - state) // per_page)
    # More state than budget leaves the least pool, not a negative one.
    assert derive_num_pages(GIB, cfg, 2, 64, state_bytes=2 * GIB) == 8
