"""Ouro's looped stack through serve's path (PR 46).

The stage's layers are applied ``total_ut_steps`` times a token with the
same weights, pass ``u`` layer ``l`` on cache layer ``u * L + l`` (layer
``l``'s array holds every pass's pages, pass ``u`` in its ``u``-th
``num_pages``), each branch of a block normed again before its add, and
the final norm closes every pass. Held here, on the CPU at toy widths:

(a) serve's path — prefill, chunked prefill across a chunk boundary, the
    K-step decode window through the cache, a prefix-cache hit — against
    the benchmark's plain reference (``benchmarks/references/ouro.py``:
    the full forward over the whole sequence, no cache) at 2 and 4
    passes, and three controls that must fail;
(b) pages and pool bytes counted from cache layers, not weight layers;
(c) what is refused, each with its reason (a partial range, a threshold
    under 1, the host tier and a KV image: no page-granular image of a
    page that lies in four places a layer); what works beside it
    (eviction and recompute, speculation, TP, SP prefill);
(d) the loader on a state dict with the published key names.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.references import ouro as reference  # noqa: E402
from parallax_tpu.config import normalize_config  # noqa: E402
from parallax_tpu.models.base import StageModel  # noqa: E402
from parallax_tpu.models.registry import create_stage_model  # noqa: E402
from parallax_tpu.runtime.cache_manager import (  # noqa: E402
    derive_num_pages,
    kv_bytes_per_page,
)
from parallax_tpu.runtime.engine import EngineConfig, StageEngine  # noqa: E402
from parallax_tpu.runtime.pipeline import InProcessPipeline  # noqa: E402
from parallax_tpu.runtime.request import Request, SamplingParams  # noqa: E402

TOY = dict(
    architectures=["OuroForCausalLM"], model_type="ouro", hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, intermediate_size=128, vocab_size=211, total_ut_steps=2,
    early_exit_threshold=1, rope_theta=1000000, rms_norm_eps=1e-6,
    tie_word_embeddings=False, max_position_embeddings=512,
    layer_types=["full_attention"] * 3, sliding_window=None,
    use_sliding_window=False, max_window_layers=3, hidden_act="silu",
    rope_scaling=None,
)
PUBLISHED = dict(
    TOY, hidden_size=2048, num_hidden_layers=48, num_attention_heads=16,
    num_key_value_heads=16, head_dim=128, intermediate_size=5632,
    vocab_size=49152, total_ut_steps=4, max_position_embeddings=65536,
    layer_types=["full_attention"] * 48, max_window_layers=48,
)
# Float32 weights, stream and cache on both sides: what is left between
# serve's path and the reference is the order of float32 sums (a cache
# read in pages, a ragged batch, XLA's fusions against "highest"
# matmuls), a few 1e-6 a block at these widths; a wrong cache layer, a
# dropped norm or a missing pass moves a logit by 1e-1 and more.
TOL = 2e-4
GIB = 1 << 30


def build(passes=2, **over):
    hf = dict(TOY, total_ut_steps=passes, **over)
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers,
                               use_pallas=False)
    params = model.init_params(jax.random.key(3), dtype=jnp.float32)
    # ``init_params`` draws every norm as ones: perturb them, so that a
    # norm read under another's name, or not at all, shows.
    rng = np.random.default_rng(17)

    def draw(path, leaf):
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        if any("norm" in n for n in names):
            return leaf * jnp.asarray(
                1.0 + 0.3 * rng.standard_normal(leaf.shape), leaf.dtype)
        return leaf

    return hf, model, jax.tree_util.tree_map_with_path(draw, params)


@pytest.fixture(scope="module", params=[2, 4], ids=["2-passes", "4-passes"])
def toy(request):
    return build(request.param)


def serve(model, params, prompts, new_tokens=12, together=False, **engine):
    kw = dict(page_size=8, num_pages=96, max_model_len=160,
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
        if not together:
            pipe.run_until_complete()
        out.append(r)
    pipe.run_until_complete()
    return eng, out


def held_to_reference(hf, params, rows, prompts, leave_out=frozenset()):
    """Every served row's tokens and logprobs beside the reference's
    own greedy continuation of its prompt."""
    for r, prompt in zip(rows, prompts):
        n = len(r.output_ids)
        (want,) = reference.greedy_continuations(
            params, hf, [list(prompt)], n, leave_out=leave_out)
        assert list(r.output_ids) == want["tokens"]
        np.testing.assert_allclose(r.output_logprobs, want["logprobs"],
                                   atol=TOL)


# -- (a) serve's path against the plain reference ----------------------------


def test_prefill_and_the_decode_window_are_the_reference(toy):
    """A prompt in one chunk, then K=8 windows through the cache: every
    pass reads the keys its own earlier steps wrote."""
    hf, model, params = toy
    prompts = np.random.default_rng(1).integers(0, 211, (2, 21)).tolist()
    eng, rows = serve(model, params, prompts, new_tokens=20, together=True,
                      decode_lookahead=8)
    assert any(k[0] == 8 for k in eng._jit_multistep)
    # One array a layer, every pass's pages in it.
    assert [a.shape[0] for a in eng.kv] == [hf["total_ut_steps"] * 96] * 3
    held_to_reference(hf, params, rows, prompts)


def test_a_prompt_cut_into_chunks_is_the_reference(toy):
    """61 tokens in chunks of 16 over 7 page boundaries: a later
    chunk's passes each attend what the same pass of the earlier chunks
    wrote."""
    hf, model, params = toy
    prompts = [np.random.default_rng(2).integers(0, 211, 61).tolist()]
    _, rows = serve(model, params, prompts, new_tokens=6,
                    prefill_chunk_size=16, max_num_tokens_per_batch=16)
    held_to_reference(hf, params, rows, prompts)


def test_a_prefix_hit_shares_a_page_in_every_cache_layer(toy):
    """The second prompt shares 40 tokens with the first: its 5 whole
    pages are reused in all ``passes x layers`` cache layers, and it
    continues as the reference does from its first token."""
    hf, model, params = toy
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 211, 40).tolist()
    prompts = [shared + t for t in rng.integers(0, 211, (2, 5)).tolist()]
    _, rows = serve(model, params, prompts, new_tokens=8,
                    enable_prefix_cache=True)
    assert rows[1].num_cached_tokens == 40
    held_to_reference(hf, params, rows, prompts)


class PassZerosPages(StageModel):
    """The stage with no pass shifted: every pass reads and writes pass
    0's pages (the first ``num_pages`` of each layer's array)."""

    def _looped_passes(self, one_pass, params, x, kv_caches, inputs):
        for _ in range(self.config.loop_passes):
            x, kv_caches = one_pass(x, kv_caches, inputs)
            x = self._rms(x, params["norm"]["weight"])
        return x, kv_caches


def test_the_control_fails_when_a_pass_is_handed_pass_zeros_cache():
    """Serve's own forward with the cache index ``u * L + l`` collapsed
    to ``l`` (no pass shifts its page table): every pass overwrites and
    reads the same pages."""
    hf, model, params = build(2)
    prompt = np.random.default_rng(4).integers(0, 211, 21).tolist()
    eng, (row,) = serve(model, params, [prompt], new_tokens=1)
    (want,) = reference.greedy_continuations(params, hf, [prompt], 1)
    assert abs(row.output_logprobs[0] - want["logprobs"][0]) < TOL

    from parallax_tpu.models.base import BatchInputs

    t = len(prompt)
    inputs = BatchInputs(
        token_ids=jnp.asarray(prompt, jnp.int32), hidden_states=None,
        positions=jnp.arange(t, dtype=jnp.int32),
        kv_lens=jnp.asarray([t], jnp.int32),
        page_indices=jnp.arange(8, dtype=jnp.int32)[None],
        cu_q_lens=jnp.asarray([0, t], jnp.int32),
        num_seqs=jnp.asarray([1], jnp.int32),
        slot_mapping=jnp.arange(t, dtype=jnp.int32),
        logits_indices=jnp.asarray([t - 1], jnp.int32))
    kv = model.new_kv_caches(8, 8, jnp.float32)
    logits, _ = model(params, kv, inputs)
    lp = jax.nn.log_softmax(logits[0])
    assert abs(float(lp[want["tokens"][0]]) - want["logprobs"][0]) < TOL
    # One chunk cannot tell the passes' pages apart (a pass writes
    # before it reads); a second chunk can: it must find pass u's keys
    # of the first chunk, not the last pass's.
    half = 16
    first = dataclasses.replace(
        inputs, token_ids=inputs.token_ids[:half],
        positions=inputs.positions[:half],
        kv_lens=jnp.asarray([half], jnp.int32),
        cu_q_lens=jnp.asarray([0, half], jnp.int32),
        slot_mapping=inputs.slot_mapping[:half],
        logits_indices=jnp.asarray([half - 1], jnp.int32))
    second = dataclasses.replace(
        inputs, token_ids=inputs.token_ids[half:],
        positions=inputs.positions[half:],
        cu_q_lens=jnp.asarray([0, t - half], jnp.int32),
        slot_mapping=inputs.slot_mapping[half:],
        logits_indices=jnp.asarray([t - half - 1], jnp.int32))

    def two_chunks(call):
        _, kv1 = call(params, model.new_kv_caches(8, 8, jnp.float32), first)
        logits, _ = call(params, kv1, second)
        return float(jax.nn.log_softmax(logits[0])[want["tokens"][0]])

    assert abs(two_chunks(model) - want["logprobs"][0]) < TOL
    shared = PassZerosPages(model.config, 0, 3, use_pallas=False)
    assert abs(two_chunks(shared) - want["logprobs"][0]) > 100 * TOL


@pytest.mark.parametrize("part", ["pass_norm", "attn_branch_norm",
                                  "mlp_branch_norm", "last_pass"])
def test_the_control_fails_when_the_reference_leaves_a_part_out(part):
    """The norm closing a pass, a branch norm or a whole pass left out of
    the reference: serve's path is no longer within the tolerance of it
    (it is of the whole reference: the cases above)."""
    hf, model, params = build(2)
    prompt = np.random.default_rng(5).integers(0, 211, 21).tolist()
    _, (row,) = serve(model, params, [prompt], new_tokens=4)
    (whole,) = reference.greedy_continuations(params, hf, [prompt], 4)
    assert list(row.output_ids) == whole["tokens"]
    np.testing.assert_allclose(row.output_logprobs, whole["logprobs"],
                               atol=TOL)
    ids = np.asarray([prompt], np.int32)
    at = np.asarray([len(prompt) - 1], np.int32)
    full = jax.nn.log_softmax(reference.logits_at(params, hf, ids, at))
    cut = jax.nn.log_softmax(reference.logits_at(
        params, hf, ids, at, leave_out={part}))
    assert float(jnp.abs(full - cut).max()) > 100 * TOL
    # On the one token the served row chose, still far outside what the
    # whole reference is held to.
    assert abs(float(cut[0, row.output_ids[0]])
               - row.output_logprobs[0]) > 20 * TOL


def prefill_logprobs(model, params, prompt):
    """Log-softmax at the prompt's last token, by one call of the stage."""
    from parallax_tpu.models.base import BatchInputs

    t = len(prompt)
    inputs = BatchInputs(
        token_ids=jnp.asarray(prompt, jnp.int32), hidden_states=None,
        positions=jnp.arange(t, dtype=jnp.int32),
        kv_lens=jnp.asarray([t], jnp.int32),
        page_indices=jnp.arange(8, dtype=jnp.int32)[None],
        cu_q_lens=jnp.asarray([0, t], jnp.int32),
        num_seqs=jnp.asarray([1], jnp.int32),
        slot_mapping=jnp.arange(t, dtype=jnp.int32),
        logits_indices=jnp.asarray([t - 1], jnp.int32))
    logits, _ = model(params, model.new_kv_caches(8, 8, jnp.float32), inputs)
    return jax.nn.log_softmax(logits[0])


def test_the_program_drops_nothing_the_controls_name():
    """The faults of the wrong references made in the program instead (a
    twin of the model with the last pass and the norm before it, or with
    the branch norms, taken out): each parts from the whole reference."""
    hf, model, params = build(2)
    prompt = np.random.default_rng(6).integers(0, 211, 21).tolist()
    want = jax.nn.log_softmax(reference.logits_at(
        params, hf, np.asarray([prompt], np.int32),
        np.asarray([len(prompt) - 1], np.int32))[0])

    def off(twin_cfg):
        twin = create_stage_model(twin_cfg, 0, 3, use_pallas=False)
        return float(jnp.abs(prefill_logprobs(twin, params, prompt)
                             - want).max())

    assert off(model.config) < TOL
    assert off(dataclasses.replace(model.config, loop_passes=1)) > 100 * TOL
    assert off(dataclasses.replace(model.config,
                                   sandwich_norm=False)) > 100 * TOL


def test_one_pass_without_branch_norms_is_the_dense_block_bit_for_bit():
    """``passes == 1``: the layer loop is the dense stage's — the same
    logits and cache, bit for bit, as a Llama stage on the same weights
    (which knows nothing of passes or branch norms)."""
    hf, model, params = build(1)
    cfg = dataclasses.replace(model.config, sandwich_norm=False)
    assert cfg.loop_passes == 1
    looped = create_stage_model(cfg, 0, 3, use_pallas=False)
    dense_hf = {k: v for k, v in hf.items()
                if k not in ("total_ut_steps", "early_exit_threshold",
                             "model_type")}
    dense_hf["architectures"] = ["LlamaForCausalLM"]
    dense = create_stage_model(normalize_config(dense_hf), 0, 3,
                               use_pallas=False)
    assert dense.config.loop_passes == 1 and not dense.config.sandwich_norm
    prompts = np.random.default_rng(7).integers(0, 211, (2, 21)).tolist()
    a_eng, a = serve(looped, params, prompts, together=True)
    b_eng, b = serve(dense, params, prompts, together=True)
    for x, y in zip(a, b):
        assert list(x.output_ids) == list(y.output_ids)
        assert list(x.output_logprobs) == list(y.output_logprobs)
    for x, y in zip(a_eng.kv, b_eng.kv):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


# -- (b) pages and pool bytes from cache layers ------------------------------


def test_the_published_model_counts_what_the_issue_counted():
    cfg = normalize_config(PUBLISHED)
    assert cfg.architecture == "OuroForCausalLM"
    assert (cfg.loop_passes, cfg.sandwich_norm) == (4, True)
    assert cfg.fp32_residual            # measured on the chip: PERF.md
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert cfg.decoder_layer_params(0) == layer == 51_388_416
    total = 48 * layer + 2 * cfg.embedding_params() + 2048 + 2049
    assert total == 2_667_974_657
    assert cfg.num_paged_layers() == 48
    assert cfg.num_cache_layers() == 192
    assert cfg.kv_bytes_per_token() == 1_572_864
    per_page = kv_bytes_per_page(cfg, cfg.num_cache_layers(), 64)
    assert per_page == 100_663_296
    # Sized from 48 weight layers the pool would be 4 x over.
    assert kv_bytes_per_page(cfg, cfg.num_paged_layers(), 64) * 4 == per_page
    free = int(0.9 * (16.909e9 - 5.336e9))
    pages = derive_num_pages(free, cfg, cfg.num_cache_layers(), 64)
    assert pages == int(free * 0.9) // per_page == 93
    # The work of a decode step, as the global scheduler's roofline
    # counts it: four times a layer's.
    once = dataclasses.replace(cfg, loop_passes=1)
    assert cfg.decoder_layer_flops(8, 1024) == 4 * once.decoder_layer_flops(
        8, 1024)


def test_the_scheduler_estimates_from_cache_layers():
    from parallax_tpu.scheduling.node import (
        HBM_UTILIZATION,
        KV_RESERVE_FRACTION,
        Node,
        RooflinePerformanceModel,
    )
    from parallax_tpu.utils.hw import HardwareInfo

    cfg = normalize_config(PUBLISHED)
    once = dataclasses.replace(cfg, loop_passes=1)
    hw = HardwareInfo(device_kind="v5e", num_chips=1, tflops_bf16=197.0,
                      hbm_gib=16.0, hbm_gbps=819.0, ici_gbps=200.0)

    def node(model):
        n = Node(node_id="n", hardware=hw, model=model)
        n.set_layers(0, 48)
        return n

    looped, plain = node(cfg), node(once)
    budget = hw.total_hbm_bytes * HBM_UTILIZATION * KV_RESERVE_FRACTION
    assert looped.max_concurrent_requests(2048) == max(
        1, int(budget // (1_572_864 * 2048)))
    assert plain.max_concurrent_requests(2048) == int(
        budget // (393_216 * 2048))
    # A decode step streams the layer and its rows' K/V once a pass.
    assert RooflinePerformanceModel(hw, cfg).layer_latency_ms(
        8, 1024) == pytest.approx(
        4 * RooflinePerformanceModel(hw, once).layer_latency_ms(8, 1024))
    # All of the stack or none of it: no range to cut.
    assert looped.layer_capacity() == 48
    small = dataclasses.replace(hw, hbm_gib=4.0)
    assert Node(node_id="s", hardware=small, model=cfg).layer_capacity() == 0
    assert 0 < Node(node_id="s", hardware=small,
                    model=once).layer_capacity() < 48


def test_the_engine_says_what_a_page_id_addresses(toy):
    from parallax_tpu.obs import names as mnames
    from parallax_tpu.obs.registry import get_registry

    hf, model, params = toy
    eng, _ = serve(model, params, [[1, 2, 3]], new_tokens=2)
    passes = hf["total_ut_steps"]
    per_token = passes * 3 * 2 * 4 * 16 * 4       # float32 cache
    assert eng.kv_layout() == {"loop_passes": passes,
                               "kv_cache_layers": passes * 3,
                               "kv_bytes_per_token": per_token}
    text = get_registry().render()
    stage = 'stage="0-3"'
    for name, value in ((mnames.LOOP_PASSES, passes),
                        (mnames.KV_CACHE_LAYERS, passes * 3),
                        (mnames.KV_BYTES_PER_TOKEN, per_token)):
        assert f"{name}{{{stage}}} {value}" in text.replace(".0\n", "\n")


def test_only_a_looped_stack_on_the_tpu_is_compiled_with_options(
        monkeypatch):
    """A stack walked once a token keeps the compiler's defaults (the
    parent's modules and cache keys); a looped one, on the TPU alone
    (the CPU's compiler refuses the TPU's options), is compiled with at
    most two VMEM prefetches in flight - and the engine hands that to
    its step programs."""
    from parallax_tpu.runtime import engine as eng_mod

    looped = normalize_config(TOY)
    once = normalize_config(dict(TOY, total_ut_steps=1))
    assert eng_mod.step_compiler_options(looped) is None      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng_mod.step_compiler_options(once) is None
    got = eng_mod.step_compiler_options(looped)
    assert got == eng_mod.LOOPED_STEP_XLA_OPTIONS
    assert got is not eng_mod.LOOPED_STEP_XLA_OPTIONS
    monkeypatch.undo()
    _, model, params = build(2)
    eng, _ = serve(model, params, [[1, 2, 3]], new_tokens=2)
    assert eng._xla_options is None


# -- (c) refusals, and what works beside them --------------------------------


def test_a_partial_layer_range_is_refused_with_its_reason():
    cfg = normalize_config(TOY)
    for span in ((0, 2), (1, 3)):
        with pytest.raises(ValueError, match="runs whole on one stage"):
            create_stage_model(cfg, *span)
    # One pass is an ordinary stack: any range.
    create_stage_model(normalize_config(dict(TOY, total_ut_steps=1)), 1, 3)


def test_an_exit_threshold_under_one_is_refused_with_its_reason():
    with pytest.raises(ValueError, match="early_exit_threshold < 1"):
        normalize_config(dict(TOY, early_exit_threshold=0.9))
    assert normalize_config(dict(TOY, early_exit_threshold=1.0)).loop_passes == 2


def test_the_gate_is_drawn_and_never_read():
    hf, model, params = build(2)
    gate = params["early_exit_gate"]
    assert gate["weight"].shape == (1, 64) and gate["bias"].shape == (1,)
    prompt = list(range(5, 26))
    _, (a,) = serve(model, params, [prompt], new_tokens=4)
    other = dict(params, early_exit_gate=jax.tree.map(
        lambda x: x + 100.0, gate))
    _, (b,) = serve(model, other, [prompt], new_tokens=4)
    assert list(a.output_logprobs) == list(b.output_logprobs)


def test_pressure_evicts_and_recomputes_and_the_host_tier_is_refused(caplog):
    """A pool too small for the rows' prefixes: evicted pages are
    dropped and recomputed, and every stream is the unpressured
    engine's. The host tier is refused at start with one logged reason:
    a page id addresses ``passes`` places in a layer's array, of which
    the tier's page images know one."""
    import logging

    hf, model, params = build(2)
    rng = np.random.default_rng(8)
    first = rng.integers(0, 211, (3, 40)).tolist()

    def turns(**engine):
        eng, a = serve(model, params, first, new_tokens=8,
                       enable_prefix_cache=True, **engine)
        pipe = InProcessPipeline([eng])
        again = [Request(f"t{i}", prompt_ids=list(p) + list(r.output_ids),
                         sampling_params=SamplingParams(
                             temperature=0.0, max_new_tokens=6,
                             ignore_eos=True, logprobs=True))
                 for i, (p, r) in enumerate(zip(first, a))]
        for r in again:
            pipe.submit(r)
            pipe.run_until_complete()
        return eng, a + again

    seen = []

    class Grab(logging.Handler):
        def emit(self, record):
            seen.append(record.getMessage())

    logger = logging.getLogger("parallax_tpu.runtime.host_cache")
    handler = Grab()
    logger.addHandler(handler)
    try:
        tight, got = turns(num_pages=14, host_cache_bytes=1 << 24)
    finally:
        logger.removeHandler(handler)
    assert tight.host_tier is None
    assert sum("host KV tier disabled: a looped stack" in m
               for m in seen) == 1
    _, want = turns(num_pages=96)
    stats = tight.cache_stats()
    # The six rows' prefixes are 30 whole pages; the tree kept what 13
    # hold and dropped the rest, none to the host.
    assert stats["cached_pages"] <= 13 and not stats.get("pages_demoted")
    for a, b in zip(got, want):
        assert list(a.output_ids) == list(b.output_ids)
        np.testing.assert_allclose(a.output_logprobs, b.output_logprobs,
                                   atol=TOL)


def test_a_kv_image_is_refused_and_the_row_is_prefilled_again():
    """No page-granular image of a looped stack's pages: the signature
    two engines must share is None, so a hand-off falls back to
    re-prefill (``adopt_checkpoint_kv`` False), which is always right."""
    hf, model, params = build(2)
    eng = StageEngine(model, params, EngineConfig(
        page_size=8, num_pages=64, max_model_len=160, kv_dtype="float32"))
    assert eng.kv_page_signature() is None
    r = Request("m0", prompt_ids=list(range(5, 35)),
                sampling_params=SamplingParams(
                    temperature=0.0, max_new_tokens=4, ignore_eos=True))
    assert eng.harvest_kv_image(r) is None


def test_speculation_verifies_through_every_pass():
    hf, model, params = build(2)
    prompts = [[5, 6, 5, 6, 5, 6, 5, 6, 5], [9, 8, 7, 9, 8, 7, 9, 8, 7]]
    _, plain = serve(model, params, prompts, new_tokens=16,
                     decode_lookahead=1)
    eng, spec = serve(model, params, prompts, new_tokens=16,
                      speculative_tokens=4, decode_lookahead=8)
    assert eng.spec_summary() is not None
    for a, b in zip(spec, plain):
        assert list(a.output_ids) == list(b.output_ids)


def test_tensor_parallel_shards_every_passes_pages_by_head():
    if len(jax.devices()) < 2:
        pytest.skip("not enough virtual devices")
    from parallax_tpu.parallel import make_mesh
    from parallax_tpu.parallel.tp import kv_partition_specs, shard_params

    hf = dict(TOY)
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, 3, use_pallas=False, tp_size=2)
    assert len(kv_partition_specs(model)) == 3
    one = create_stage_model(cfg, 0, 3, use_pallas=False)
    params = one.init_params(jax.random.key(3), dtype=jnp.float32)
    mesh = make_mesh(tp_size=2)
    kw = dict(page_size=8, num_pages=64, max_model_len=160,
              kv_dtype="float32", enable_prefix_cache=False)
    eng = StageEngine(model, shard_params(params, mesh), EngineConfig(**kw),
                      mesh=mesh)
    pipe = InProcessPipeline([eng])
    prompt = np.random.default_rng(10).integers(0, 211, 21).tolist()
    r = Request("tp", prompt_ids=prompt, sampling_params=SamplingParams(
        temperature=0.0, max_new_tokens=10, ignore_eos=True, logprobs=True))
    pipe.submit(r)
    pipe.run_until_complete()
    _, (alone,) = serve(one, params, [prompt], new_tokens=10)
    assert list(r.output_ids) == list(alone.output_ids)
    np.testing.assert_allclose(r.output_logprobs, alone.output_logprobs,
                               atol=TOL)


def test_sequence_parallel_prefill_writes_every_passes_pages():
    if len(jax.devices()) < 8:
        pytest.skip("not enough virtual devices")
    from parallax_tpu.parallel import make_mesh

    hf, model, params = build(2)
    prompt = np.random.default_rng(11).integers(0, 211, 300).tolist()
    kw = dict(page_size=8, num_pages=128, max_model_len=512,
              max_num_tokens_per_batch=512, sp_threshold=256)
    eng, (ring,) = serve(model, params, [prompt], new_tokens=6, **kw)
    assert not eng._sp_enabled
    sp_eng = StageEngine(
        model, params, EngineConfig(
            kv_dtype="float32", enable_prefix_cache=False, **kw),
        sp_mesh=make_mesh(sp_size=8, tp_size=1))
    pipe = InProcessPipeline([sp_eng])
    r = Request("sp", prompt_ids=prompt, sampling_params=SamplingParams(
        temperature=0.0, max_new_tokens=6, ignore_eos=True, logprobs=True))
    pipe.submit(r)
    pipe.run_until_complete()
    assert sp_eng._sp_enabled
    assert list(r.output_ids) == list(ring.output_ids)
    np.testing.assert_allclose(r.output_logprobs, ring.output_logprobs,
                               atol=TOL)


# -- (d) the loader -----------------------------------------------------------


def test_the_loader_reads_the_published_key_names():
    torch = pytest.importorskip("torch")
    from parallax_tpu.models.loader import params_from_torch_state_dict

    hf, model, params = build(2)
    sd = {}

    def put(key, leaf):
        sd[key] = torch.tensor(np.asarray(leaf, np.float32))

    for i, lp in enumerate(params["layers"]):
        for name in ("input_layernorm", "input_layernorm_2",
                     "post_attention_layernorm",
                     "post_attention_layernorm_2"):
            put(f"model.layers.{i}.{name}.weight", lp[name]["weight"])
        for group in ("self_attn", "mlp"):
            for proj, leaves in lp[group].items():
                put(f"model.layers.{i}.{group}.{proj}.weight",
                    leaves["weight"])
    put("model.embed_tokens.weight", params["embed_tokens"]["weight"])
    put("model.norm.weight", params["norm"]["weight"])
    put("model.early_exit_gate.weight", params["early_exit_gate"]["weight"])
    put("model.early_exit_gate.bias", params["early_exit_gate"]["bias"])
    put("lm_head.weight", params["lm_head"]["weight"])
    loaded = params_from_torch_state_dict(model, sd, dtype=jnp.float32)
    assert (jax.tree.structure(loaded) == jax.tree.structure(params))
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
