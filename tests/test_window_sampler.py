"""Which sampler a K-step decode window compiles is its own decision
(``kernel_select.resolve_window_sampler_fused``), apart from the fused
attention family: the sort-free sampler kernel reads logits and sampling
parameters and needs nothing of the attention kernels, so on a TPU a
model whose fused attention does not lower (latent attention, MSA,
head_dim 64) still draws through it. The resolver's table, and an engine
on a tiny latent-attention stage whose attention stays on the split/XLA
path while its windows run the fused sampler (interpret mode here),
token for token what the same stage draws through the sort.
"""

import itertools
import logging

import jax
import jax.numpy as jnp
import pytest

from parallax_tpu.config import normalize_config
from parallax_tpu.models.registry import create_stage_model
from parallax_tpu.obs import names
from parallax_tpu.obs.registry import get_registry
from parallax_tpu.ops import kernel_select
from parallax_tpu.runtime.engine import EngineConfig, StageEngine
from parallax_tpu.runtime.pipeline import InProcessPipeline
from parallax_tpu.runtime.request import Request, SamplingParams

K = 8


def _dense():
    return normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=256,
        num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=1,
        head_dim=128, intermediate_size=128, vocab_size=199,
    ))


def _mla(vocab=199):
    return normalize_config(dict(
        architectures=["DeepseekV3ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=1, n_group=2, topk_group=1,
        routed_scaling_factor=1.0, norm_topk_prob=True,
        scoring_func="sigmoid", first_k_dense_replace=1, moe_layer_freq=1,
        vocab_size=vocab, max_position_embeddings=512, rms_norm_eps=1e-6,
        rope_theta=10000.0, rope_interleave=True,
        tie_word_embeddings=False, attention_bias=False,
    ))


MODELS = {"dense": _dense, "latent": _mla}


@pytest.mark.parametrize(
    "flag,tpu,use_pallas,family",
    list(itertools.product(
        (None, True, False), (True, False), (None, False), MODELS,
    )),
)
def test_window_sampler_is_resolved_apart_from_the_attention_family(
        monkeypatch, flag, tpu, use_pallas, family):
    monkeypatch.setattr(kernel_select, "tpu_available", lambda: tpu)
    config = MODELS[family]()
    gap = kernel_select.fused_lowering_gap(config)
    assert (gap is None) == (family == "dense")
    # The sampler: forced, pinned, or (auto) a TPU with Pallas not
    # pinned off. The model is never asked.
    want_sampler = (
        flag if flag is not None else (tpu and use_pallas is None)
    )
    assert kernel_select.resolve_window_sampler_fused(
        flag, use_pallas
    ) is want_sampler
    assert kernel_select.window_sampler_impl(want_sampler) == (
        "pallas-fused" if want_sampler else "sort"
    )
    # The attention family keeps the decision it had.
    want_attention = (
        flag if flag is not None else (tpu and gap is None)
    )
    assert kernel_select.resolve_decode_fused(flag, config) is want_attention


PROMPTS = [[3, 14, 15, 92, 65], [7, 21, 108], [42] * 9, [5, 4, 3, 2]]
# Greedy, seeded plain temperature, seeded top_k, and a seeded top_k at
# another temperature: what the fused sampler serves, mixed in a batch.
MIXED = [
    dict(temperature=0.0),
    dict(temperature=0.8, seed=77),
    dict(temperature=0.7, seed=5, top_k=20),
    dict(temperature=1.1, seed=9, top_k=3),
]


def _engine(monkeypatch, model, params, *, decode_fused, tpu):
    """An engine constructed as on a TPU (or not): the decision is taken
    once, at construction, from what the engine can observe. The
    programs are traced afterwards, on this CPU: attention takes the XLA
    path and a fused sampler runs in the Pallas interpreter."""
    with monkeypatch.context() as m:
        m.setattr(kernel_select, "tpu_available", lambda: tpu)
        return StageEngine(model, params, EngineConfig(
            page_size=8, num_pages=128, max_model_len=256,
            kv_dtype="float32", decode_lookahead=K,
            decode_fused=decode_fused,
        ))


def _serve(eng, tag, sampling, max_new=11):
    pipe = InProcessPipeline([eng])
    reqs = []
    for i, (prompt, sp) in enumerate(zip(PROMPTS, sampling)):
        req = Request(
            f"{tag}{i}", prompt_ids=list(prompt),
            sampling_params=SamplingParams(max_new_tokens=max_new, **sp),
        )
        reqs.append(req)
        pipe.submit(req)
    pipe.run_until_complete()
    return [r.output_ids for r in reqs]


@pytest.mark.parametrize("vocab", [256, 199], ids=["v256", "v199"])
def test_latent_stage_windows_draw_through_the_fused_sampler(
        monkeypatch, vocab):
    """A vocabulary that is and one that is not a multiple of 128."""
    model = create_stage_model(_mla(vocab), 0, 2)
    params = model.init_params(jax.random.key(1), dtype=jnp.float32)
    eng = _engine(monkeypatch, model, params, decode_fused=None, tpu=True)
    # Auto on a TPU: the latent append does not lower, so attention
    # keeps the split chain, and the window's sampler is fused all the
    # same. Off a TPU auto keeps the XLA sampler.
    assert eng._decode_fused is False
    assert eng._window_sampler_fused is True
    cpu = _engine(monkeypatch, model, params, decode_fused=None, tpu=False)
    assert (cpu._decode_fused, cpu._window_sampler_fused) == (False, False)
    assert cpu.kernel_dispatch_summary()["window_sampler"] == "sort"
    assert cpu.kernel_dispatch_summary()["interpret"] is False
    sort = _engine(monkeypatch, model, params, decode_fused=False, tpu=True)
    assert sort._window_sampler_fused is False

    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    lg = logging.getLogger("parallax_tpu.runtime.engine")
    lg.addHandler(handler)
    try:
        got = _serve(eng, "f", MIXED)
        want = _serve(sort, "s", MIXED)
        assert got == want
        assert all(len(ids) == 11 for ids in got)
        assert (K, True, True, ()) in eng._jit_multistep
        assert (K, True, False, ()) not in eng._jit_multistep
        assert list(sort._jit_multistep) == [(K, True, False, ())]
        assert not records

        kernel = eng.kernel_dispatch_summary()
        assert kernel["decode_fused"] is False
        assert kernel["impl"] != "pallas-fused"
        assert kernel["window_sampler"] == "pallas-fused"
        assert kernel["interpret"] is True          # the sampler counts
        windows = kernel["window_sampler_dispatch_total"]
        assert windows["pallas-fused"] > 0 and "sort" not in windows
        # The series a scrape reads carries the same count.
        series = [
            ln for ln in get_registry().render().splitlines()
            if ln.startswith(names.WINDOW_SAMPLER_DISPATCH_TOTAL + "{")
            and 'impl="pallas-fused"' in ln
        ]
        assert series and float(series[0].rsplit(" ", 1)[1]) > 0
        assert sort.kernel_dispatch_summary()[
            "window_sampler_dispatch_total"
        ].keys() == {"sort"}

        # A top-p row anywhere drops its whole batch to the sort, twice
        # over, and the marker is logged once.
        with_top_p = MIXED[:3] + [dict(temperature=0.9, seed=3, top_p=0.8)]
        for tag in ("p", "q"):
            assert _serve(eng, tag, with_top_p) == _serve(
                sort, tag, with_top_p
            )
        assert (K, True, False, ()) in eng._jit_multistep
        assert eng.kernel_dispatch_summary()[
            "window_sampler_dispatch_total"
        ]["sort"] > 0
    finally:
        lg.removeHandler(handler)
    marks = [m for m in records if "fused window sampler disabled" in m]
    assert len(marks) == 1 and "top-p" in marks[0]

    # All greedy: neither sampler, counted as what it is.
    _serve(eng, "g", [dict(temperature=0.0)] * 4)
    assert eng.kernel_dispatch_summary()[
        "window_sampler_dispatch_total"
    ]["argmax"] > 0
