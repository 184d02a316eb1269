"""One trace a kind of decoder block (PR 45).

``StageModel.__call__`` calls every layer through ``_block_fn``, which
traces the block to a jaxpr once a kind, keyed by what the block reads
from Python while it is traced (``BlockKey``) and its arguments' shapes,
and replays it, so a program runs the block's Python once a *kind*
whatever the depth. Held here:

(a) parity, bit for bit, against the plain Python loop (the witness
    lives in this file, not in the package): logits and every KV and
    state leaf, on the step calls a real engine made;
(b) the counter: ``block_traces`` of a program reads the kinds of block
    it has, and a second program of the same shapes adds 0;
(c) the rule a family keeps — a block reads its arguments and its key,
    nothing else — as a check of the package's source.
"""

import ast
import copy
import inspect
import os
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import parallax_tpu.models  # noqa: E402,F401  (registers every family)
from parallax_tpu.config import normalize_config  # noqa: E402
from parallax_tpu.models import jamba as jamba_mod  # noqa: E402
from parallax_tpu.models.base import StageModel  # noqa: E402
from parallax_tpu.models.registry import (  # noqa: E402
    MODEL_REGISTRY,
    create_stage_model,
)
from parallax_tpu.obs import trace as obs_trace  # noqa: E402
from parallax_tpu.parallel import make_mesh  # noqa: E402
from parallax_tpu.parallel import tp as tp_mod  # noqa: E402
from parallax_tpu.runtime.engine import EngineConfig, StageEngine  # noqa: E402
from parallax_tpu.runtime.pipeline import InProcessPipeline  # noqa: E402
from parallax_tpu.runtime.request import Request, SamplingParams  # noqa: E402

DENSE = dict(
    architectures=["Qwen2ForCausalLM"], hidden_size=64, num_hidden_layers=4,
    num_attention_heads=8, num_key_value_heads=4, intermediate_size=128,
    vocab_size=151, max_position_embeddings=2048, attention_bias=True,
    tie_word_embeddings=False,
)
SLIDING = dict(DENSE, sliding_window=8, layer_types=[
    "sliding_attention", "full_attention", "sliding_attention",
    "full_attention"])
EVABYTE = dict(
    model_type="evabyte", attention_class="eva", hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=128, vocab_size=320, window_size=32, chunk_size=4,
    num_pred_heads=8, rope_theta=100000, rms_norm_eps=1e-5,
    norm_add_unit_offset=True, fp32_skip_add=True, fp32_logits=True,
    max_position_embeddings=512, tie_word_embeddings=False,
)
JAMBA = dict(
    architectures=["JambaForCausalLM"], model_type="jamba", hidden_size=64,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=1,
    intermediate_size=96, vocab_size=211, attn_layer_period=2,
    attn_layer_offset=1, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=8, mamba_conv_bias=True, mamba_proj_bias=False,
    num_experts=1, num_experts_per_tok=1, rms_norm_eps=1e-6,
    tie_word_embeddings=True, max_position_embeddings=512,
)
V32 = dict(
    architectures=["DeepseekV32ForCausalLM"], hidden_size=64,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4, index_head_dim=32,
    index_topk=8, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
    n_group=2, topk_group=1, scoring_func="sigmoid",
    first_k_dense_replace=1, vocab_size=199, max_position_embeddings=512,
    rms_norm_eps=1e-6, rope_theta=10000.0, rope_interleave=True,
    tie_word_embeddings=False,
    # full, shared, shared, full, shared: top-k handed on as a value.
    index_topk_freq=3, index_skip_topk_offset=0,
)
OURO = dict(
    architectures=["OuroForCausalLM"], model_type="ouro", hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, intermediate_size=128, vocab_size=211, total_ut_steps=2,
    early_exit_threshold=1, rope_theta=1000000, rms_norm_eps=1e-6,
    tie_word_embeddings=False, max_position_embeddings=512,
)
ENGINE = dict(page_size=8, num_pages=128, max_model_len=512,
              kv_dtype="float32", max_num_tokens_per_batch=512,
              enable_prefix_cache=False)


def build(hf, tp_size=1, **kw):
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers,
                               use_pallas=False, tp_size=tp_size, **kw)
    return model, model.init_params(jax.random.key(5), dtype=jnp.float32)


def plain_loop(model):
    """The witness: the stage with its blocks called one by one in
    Python, every layer's body run inside the caller's trace, as
    ``StageModel.__call__`` did before it called a cached block."""
    twin = copy.copy(model)
    twin._block_fn = twin._block    # no cache: every layer's body runs
    return twin


def parents_call(self, params, kv_caches, inputs):
    """``StageModel.__call__`` as PR 45 left it, letter for letter (the
    witness lives in this file): one walk over the layers, no pass, no
    scope. PR 46 put a loop over a looped stack's passes around the walk;
    a model of one pass must still be handed to XLA as this."""
    from parallax_tpu.models import layers as L

    cfg = self.config
    if self.is_first:
        x = L.embed_lookup(params["embed_tokens"], inputs.token_ids)
    else:
        x = inputs.hidden_states
    if cfg.fp32_residual:
        x = x.astype(jnp.float32)
    assert inputs.lora is None
    new_kv = []
    carry = None
    for li in range(self.num_local_layers):
        lp = params["layers"][li]
        x, kv_l, carry = self._block_fn(
            self._block_key(li), lp, x, kv_caches[li], inputs, carry
        )
        new_kv.append(kv_l)
    if not self.is_last:
        return x, new_kv
    x = self._rms(x, params["norm"]["weight"])
    x = x[inputs.logits_indices]
    head = params.get("lm_head") or params["embed_tokens"]
    if cfg.fp32_residual:
        x = x.astype(params["norm"]["weight"].dtype)
    logits = L.lm_head_logits(x, head)
    if cfg.eva is not None and cfg.eva.num_pred_heads > 1:
        logits = logits[:, : cfg.vocab_size]
    return logits, new_kv


def parents_loop(model):
    """``model`` with the parent's ``__call__`` (same class name, so the
    jitted function is named alike)."""
    twin = copy.copy(model)
    twin.__class__ = type(type(model).__name__, (type(model),),
                          {"__call__": parents_call})
    return twin


def recorded_steps(eng):
    """Every call the engine makes of its step programs (prefill, mixed,
    the SP step) and of its decode windows, as made."""
    calls = []
    for name in ("_jit_step", "_jit_sp_step"):
        fn = getattr(eng, name, None)
        if fn is None:
            continue

        def rec(params, kv, inputs, _fn=fn, _name=name):
            calls.append((_name, params, kv, inputs))
            return _fn(params, kv, inputs)

        setattr(eng, name, rec)
    build = eng._build_multistep

    def rec_build(*key):
        fn = build(*key)

        def rec(params, kv, inputs, ms):
            calls.append((key, params, kv, inputs, ms))
            return fn(params, kv, inputs, ms)

        return rec

    eng._build_multistep = rec_build
    return calls, build


def window_fn(eng, build, model, key):
    """The K-step decode window the engine jits around ``model``."""
    mine, eng.model = eng.model, model
    try:
        return build(*key)
    finally:
        eng.model = mine


def stage_fn(eng, model, sp):
    """The function the engine jits for ``model``: the stage itself,
    under ``shard_map`` with TP, with the SP switch up for the SP step."""
    fn = model
    if eng.mesh is not None and model.tp_size > 1:
        fn = tp_mod.tp_stage_fn(model, eng.params, eng.mesh)
    if not sp:
        return fn

    def sp_fn(params, kv, inputs):
        model._sp_active = True
        try:
            return fn(params, kv, inputs)
        finally:
            model._sp_active = False

    return sp_fn


def generate(eng, prompts, n_new=6, lora_id=None):
    pipe = InProcessPipeline([eng])
    reqs = [Request(f"r{i}", prompt_ids=list(p), lora_id=lora_id,
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=n_new,
                        ignore_eos=True))
            for i, p in enumerate(prompts)]
    for r in reqs:
        pipe.submit(r)
    pipe.run_until_complete()
    assert all(len(r.output_ids) == n_new for r in reqs)


def assert_same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()


def lora_tree(cfg, layers):
    rng = np.random.default_rng(2)
    h, inter, r = cfg.hidden_size, cfg.intermediate_size, 4
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32) * 0.1
    return {li: {"self_attn.q_proj": (f(r, h), f(h, r), 0.5),
                 "mlp.gate_proj": (f(r, h), f(inter, r), 0.5)}
            for li in layers}


CASES = {
    "dense-gqa-bias": dict(hf=DENSE, kinds=1),
    "sliding-mixed-with-full": dict(hf=SLIDING, kinds=2),
    "evabyte-toy": dict(hf=EVABYTE, kinds=1, engine=dict(
        max_model_len=256, max_num_tokens_per_batch=64,
        prefill_chunk_size=16), prompt_len=41),
    "jamba-toy": dict(hf=JAMBA, kinds=2),
    # Layer 0 dense MLP and no top-k yet, layers 1-2 shared, layer 3
    # full with a top-k handed in, layer 4 shared: four kinds.
    "deepseek-v32-shared-topk": dict(hf=V32, kinds=4),
    "lora-on-some-layers": dict(hf=DENSE, kinds=2, lora=(1, 3)),
    # Two passes over three layers under one ``fori_loop``: the walk is
    # in the program once, one kind — the pass reaches a block only as
    # the page table it is handed.
    "ouro-toy-2-passes": dict(hf=OURO, kinds=1),
    "tp2-under-shard-map": dict(hf=DENSE, kinds=1, tp=2),
    "sp-step-after-the-plain-step": dict(hf=DENSE, kinds=1, sp=8,
                                         prompt_len=300),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_the_cached_block_is_the_plain_loop_bit_for_bit(case):
    c = CASES[case]
    tp, sp = c.get("tp", 1), c.get("sp", 0)
    if len(jax.devices()) < max(tp, sp):
        pytest.skip("not enough virtual devices")
    model, params = build(c["hf"], tp_size=tp)
    cfg = model.config
    kw = dict(ENGINE, **c.get("engine", {}))
    eng_kw = {}
    if tp > 1:
        eng_kw["mesh"] = make_mesh(tp_size=tp)
    if sp:
        kw["sp_threshold"] = 256
        eng_kw["sp_mesh"] = make_mesh(sp_size=sp, tp_size=1)
    eng = StageEngine(model, params, EngineConfig(**kw), **eng_kw)
    lora_id = None
    if "lora" in c:
        eng.load_adapter("ad", lora_tree(cfg, c["lora"]))
        lora_id = "ad"
    calls, build_window = recorded_steps(eng)
    rng = np.random.default_rng(11)
    n = c.get("prompt_len", 21)
    generate(eng, rng.integers(1, cfg.vocab_size - 1, (2, n)).tolist(),
             lora_id=lora_id)
    generate(eng, [rng.integers(1, cfg.vocab_size - 1, 9).tolist()],
             lora_id=lora_id)
    # One call of each program the engine built: its prefill steps (the
    # SP step too) and its decode windows.
    picked, seen = [], set()
    for call in calls:
        shape = (call[0], call[3].positions.shape, call[3].kv_lens.shape)
        if shape not in seen:
            seen.add(shape)
            picked.append(call)
    names = {call[0] for call in picked}
    assert "_jit_step" in names and any(isinstance(k, tuple) for k in names)
    if sp:
        assert "_jit_sp_step" in names

    twin = plain_loop(model)
    for name, *args in picked:
        if isinstance(name, tuple):
            cached_fn = window_fn(eng, build_window, model, name)
            plain_fn = window_fn(eng, build_window, twin, name)
        else:
            is_sp = name == "_jit_sp_step"
            cached_fn = jax.jit(stage_fn(eng, model, is_sp))
            plain_fn = jax.jit(stage_fn(eng, twin, is_sp))
        before = obs_trace.block_traces()
        got = cached_fn(*args)
        cached = obs_trace.block_traces() - before
        want = plain_fn(*args)
        plain = obs_trace.block_traces() - before - cached
        assert_same_bits(got, want)
        # The witness ran every layer's body; the stage a body a kind
        # (0 where the engine's own call left the kind's jaxpr in JAX's
        # tracing cache).
        assert plain == model.num_local_layers
        assert cached <= c["kinds"]


def test_the_sp_trace_is_not_handed_the_plain_steps_jaxpr():
    """One model object, one set of arguments: the plain step, then the
    step with the engine's SP switch up. The block's key differs, so
    the block is traced again, and gives what the plain loop gives
    under the switch (which is not what the plain step gives)."""
    if len(jax.devices()) < 8:
        pytest.skip("not enough virtual devices")
    model, params = build(DENSE)
    eng = StageEngine(
        model, params, EngineConfig(**ENGINE, sp_threshold=256),
        sp_mesh=make_mesh(sp_size=8, tp_size=1))
    calls, _ = recorded_steps(eng)
    prompt = np.random.default_rng(0).integers(1, 150, 300).tolist()
    generate(eng, [prompt], n_new=2)
    (call,) = [c for c in calls if c[0] == "_jit_sp_step"]
    _, p, kv, inputs = call
    assert model._sp_active is False
    assert model._block_key(0).sp is None
    n0 = obs_trace.block_traces()
    plain = jax.jit(stage_fn(eng, model, False))(p, kv, inputs)
    n1 = obs_trace.block_traces()
    ring = jax.jit(stage_fn(eng, model, True))(p, kv, inputs)
    n2 = obs_trace.block_traces()
    assert n1 - n0 == 1 and n2 - n1 <= 1
    want = jax.jit(stage_fn(eng, plain_loop(model), True))(p, kv, inputs)
    assert_same_bits(ring, want)
    # The ring step does not write the page pool the way the plain step
    # does: had the SP trace been handed the plain jaxpr, these agreed.
    differ = [np.asarray(a).tobytes() != np.asarray(b).tobytes()
              for a, b in zip(jax.tree.leaves(ring), jax.tree.leaves(plain))]
    assert any(differ)


# -- (b) the counter ---------------------------------------------------------


class LayerIndexed(StageModel):
    """A family that cannot say what its block reads: keyed by layer."""

    def _block_key(self, li):
        return super()._block_key(li)._replace(extra=li)


def lowered(model, params, tokens=16):
    """One prefill program of ``tokens`` tokens, traced and lowered
    (nothing is compiled or run)."""
    from parallax_tpu.models.base import BatchInputs

    s, pages = 2, 8
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    state = model.has_linear_layers
    inputs = BatchInputs(
        token_ids=i32(tokens), hidden_states=None, positions=i32(tokens),
        kv_lens=i32(s), page_indices=i32(s, pages), cu_q_lens=i32(s + 1),
        num_seqs=i32(1), slot_mapping=i32(tokens), logits_indices=i32(s),
        state_slots=i32(s) if state else None,
        reset_state=i32(s) if state else None,
    )
    kv = jax.eval_shape(lambda: model.new_kv_caches(
        16, 8, jnp.float32, **({"num_state_slots": 4} if state else {})))
    return jax.jit(model).lower(params, kv, inputs)


def lowered_block_traces(model, params, tokens=16):
    """Block bodies run while that program is traced."""
    before = obs_trace.block_traces()
    lowered(model, params, tokens)
    return obs_trace.block_traces() - before


def test_xla_is_handed_the_module_the_plain_loop_gave():
    """The block's jaxpr is replayed into the program's, equation by
    equation on the arguments as they are, so a dense stage lowers to the unrolled loop's module letter for
    letter: the compiled step, and the persistent cache's key, are what
    they were (PERF.md, PR 45)."""
    model, params = build(dict(DENSE, num_hidden_layers=6))
    twin = plain_loop(model)
    assert (lowered(model, params).as_text()
            == lowered(twin, params).as_text())
    # The decode window too: its scan takes the weights in as operands
    # in the order the body first meets them, and XLA's prefetch of the
    # 3B's weights follows that order (handing a block every leaf of its
    # layer at once, as a jit call does, cost the 3B's step 2.2%).
    eng = StageEngine(model, params, EngineConfig(**ENGINE))
    calls, build_window = recorded_steps(eng)
    generate(eng, [[5, 6, 7, 8, 9]], n_new=10)
    (key, *args), = [c for c in calls if isinstance(c[0], tuple)][:1]
    assert (window_fn(eng, build_window, model, key).lower(*args).as_text()
            == window_fn(eng, build_window, twin, key).lower(*args).as_text())
    # PR 46: the walk over the layers now sits inside a loop over a
    # looped stack's passes. A model of one pass — every accepted cell's
    # — is handed to XLA as the parent's single walk was, prefill
    # program and decode window, dense block and hybrid: no scope, no
    # branch, no reordered operand.
    for hf in (dict(DENSE, num_hidden_layers=6), EVABYTE, JAMBA):
        model, params = build(hf)
        assert model.config.loop_passes == 1
        old = parents_loop(model)
        assert (lowered(model, params).as_text()
                == lowered(old, params).as_text())
    model, params = build(dict(DENSE, num_hidden_layers=6))
    assert (window_fn(eng, build_window, model, key).lower(*args).as_text()
            == window_fn(eng, build_window, parents_loop(model),
                         key).lower(*args).as_text())
    # And a looped stack is not: its walk sits in a loop over the
    # passes, under a scope of its own.
    looped, lparams = build(OURO)
    text = lowered(looped, lparams).as_text(debug_info=True)
    assert "loop_pass" in text
    assert "loop_pass" not in lowered(model, params).as_text(debug_info=True)


def build_layer_indexed(layers):
    cfg = normalize_config(dict(DENSE, num_hidden_layers=layers))
    model = LayerIndexed(cfg, 0, layers, use_pallas=False)
    return model, model.init_params(jax.random.key(0), dtype=jnp.float32)


@pytest.mark.parametrize("name,make,kinds", [
    ("dense-12-layers", lambda: build(dict(DENSE, num_hidden_layers=12)), 1),
    ("jamba-toy-8-layers", lambda: build(dict(JAMBA, num_hidden_layers=8)), 2),
    ("ouro-toy-4-passes-of-3", lambda: build(dict(OURO, total_ut_steps=4)), 1),
    ("layer-indexed-6-layers", lambda: build_layer_indexed(6), 6),
], ids=lambda v: v if isinstance(v, str) else None)
def test_a_program_traces_a_kind_of_block_once(name, make, kinds):
    model, params = make()
    assert lowered_block_traces(model, params) == kinds
    # A second program of the same shapes: every kind's jaxpr is in
    # JAX's tracing cache.
    assert lowered_block_traces(model, params) == 0
    # Another token bucket is another program: its kinds once more.
    assert lowered_block_traces(model, params, tokens=32) == kinds


def test_a_builds_record_says_how_many_blocks_it_traced():
    """``device.compile.recent``: ``block_traces`` beside ``trace_ms``,
    the blocks traced on this thread since the build's note; and the
    series the benchmark reads."""
    from parallax_tpu.obs import names as mnames
    from parallax_tpu.obs.device import CompileObservatory
    from parallax_tpu.obs.registry import MetricsRegistry
    from parallax_tpu.obs.trace import SlowVisits

    obs = CompileObservatory(registry=MetricsRegistry())
    model, params = build(dict(DENSE, num_hidden_layers=12))
    lowered_block_traces(model, params)       # before any note: nobody's
    obs.note_program("prefill", {"tokens": 64, "seq": 2})
    assert lowered_block_traces(model, params, tokens=64) == 1
    obs.on_cache_hit(0.01, "jit(_stage_fn)")
    obs.note_program("prefill", {"tokens": 64, "seq": 2})
    obs.on_compile(0.01, "jit(_stage_fn)")
    first, second = obs.snapshot()["recent"]
    assert first["block_traces"] == 1 and first["cache_hit"] is True
    assert second["block_traces"] == 0
    # The counter: bumped where the body runs, labelled by nothing.
    registry = MetricsRegistry()
    ledger = SlowVisits()
    ledger.bind_registry(registry)
    ledger.count_block_trace()
    ledger.count_block_trace()
    assert f"{mnames.BLOCK_TRACES_TOTAL} 2" in registry.render()


# -- (c) the rule: a block reads its arguments and its key -------------------

# Methods of a stage model that never run while a block is traced.
OUTSIDE_THE_BLOCK = {
    "__init__", "__call__", "_block_key", "init_params", "finalize_params",
    "new_kv_caches", "local_layer_types", "num_local_layers",
    "has_linear_layers", "state_dense_rows",
}


def _self_attrs(fn_node, self_name, ctx):
    return {n.attr for n in ast.walk(fn_node)
            if isinstance(n, ast.Attribute) and isinstance(n.ctx, ctx)
            and isinstance(n.value, ast.Name) and n.value.id == self_name}


def _functions(source):
    return {n.name: n for n in ast.walk(ast.parse(textwrap.dedent(source)))
            if isinstance(n, ast.FunctionDef)}


def _assigned_from_outside():
    """Attributes the rest of the package assigns on a model object
    (``model.sp_mesh = ...``, ``self.model._sp_active = True``)."""
    found = set()
    pkg = os.path.join(ROOT, "parallax_tpu")
    for dirpath, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(dirpath, f)) as fh:
                tree = ast.parse(fh.read())
            for n in ast.walk(tree):
                if (isinstance(n, ast.Attribute)
                        and isinstance(n.ctx, ast.Store)
                        and "model" in ast.unparse(n.value).split(".")[-1]
                        and ast.unparse(n.value) != "self"):
                    found.add(n.attr)
    return found


def block_rule_violations(cls, outside_assigned=frozenset()):
    """What breaks the rule in ``cls``: (function, attribute, why).
    Every version of a method along the MRO counts (a ``super()`` call
    reaches the base's), and the module functions a block hands itself
    to (``jamba.mamba_mixer(self, ...)``)."""
    bodies = {}     # name -> [(function node, the name it calls itself)]
    for klass in cls.__mro__[:-1]:
        for name, fn in _functions(inspect.getsource(klass)).items():
            bodies.setdefault(name, []).append((fn, "self"))
    methods = set(bodies)
    for name, fn in _functions(inspect.getsource(jamba_mod)).items():
        if fn.args.args and fn.args.args[0].arg == "model":
            bodies.setdefault(name, []).append((fn, "model"))

    todo, block = ["_block"], []
    while todo:
        name = todo.pop()
        if name in block:
            continue
        block.append(name)
        for fn, me in bodies[name]:
            todo += [a for a in _self_attrs(fn, me, ast.Load)
                     if a in methods and a not in OUTSIDE_THE_BLOCK]
            todo += [n.func.attr for n in ast.walk(fn)
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Attribute)
                     and n.func.attr in bodies and n.func.attr not in methods]
    # Assigned after __init__: by any method of the family, or from
    # outside on a model object.
    mutable = set(outside_assigned)
    for name in methods - {"__init__"}:
        for fn, me in bodies[name]:
            mutable |= _self_attrs(fn, me, ast.Store)
    in_key = set()
    for fn, me in bodies["_block_key"]:
        in_key |= _self_attrs(fn, me, ast.Load)
    out = []
    for name in block:
        for fn, me in bodies[name]:
            for attr in sorted(_self_attrs(fn, me, ast.Store)):
                out.append((name, attr, "assigned while a block is traced"))
            for attr in sorted(_self_attrs(fn, me, ast.Load)
                               & mutable - in_key):
                out.append((name, attr, "changes after __init__ and is not "
                                        "read by _block_key"))
    return out


def stage_model_classes():
    return sorted({StageModel, *MODEL_REGISTRY.values()},
                  key=lambda c: c.__name__)


@pytest.mark.parametrize("cls", stage_model_classes(),
                         ids=lambda c: c.__name__)
def test_a_familys_block_reads_its_arguments_and_its_key(cls):
    outside = _assigned_from_outside()
    # What the engine and the TP wrapper set on a model today.
    assert {"_sp_active", "sp_mesh", "sp_in_mesh",
            "_lm_head_sharded"} <= outside
    assert block_rule_violations(cls, outside) == []


def test_the_guard_fails_a_family_that_counts_its_layers_in_python():
    """What ``deepseek_v32`` and ``minimax_m3`` did before PR 45: with a
    cached block the counter would stand still and every layer read the
    first layer's fact."""

    class Counting(StageModel):
        def __call__(self, params, kv_caches, inputs):
            self._local_li = 0
            return super().__call__(params, kv_caches, inputs)

        def _decoder_layer(self, lp, x, kv, inputs, window):
            self._gi = self.start_layer + self._local_li
            self._local_li += 1
            return super()._decoder_layer(lp, x, kv, inputs, window)

        def _mlp(self, lp, h):
            return super()._mlp(lp, h) * (1 + self._gi)

    found = {(m, a) for m, a, _ in block_rule_violations(Counting)}
    assert ("_decoder_layer", "_local_li") in found
    assert ("_decoder_layer", "_gi") in found
    assert ("_mlp", "_gi") in found

    class Switched(StageModel):
        """An attribute somebody flips after construction, read by the
        block and left out of the key."""

        def _mlp(self, lp, h):
            return super()._mlp(lp, h) * (2.0 if self.loud else 1.0)

    assert block_rule_violations(Switched, {"loud"}) == [
        ("_mlp", "loud", "changes after __init__ and is not read by "
                         "_block_key")]

    class Keyed(Switched):
        def _block_key(self, li):
            return super()._block_key(li)._replace(extra=self.loud)

    assert block_rule_violations(Keyed, {"loud"}) == []
