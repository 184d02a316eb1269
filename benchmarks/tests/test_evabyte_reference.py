"""EvaByte's plain reference (``references/evabyte.py``) against the
program's model at the configuration's rehearsal widths (CPU, float32
weights, XLA attention: window 32, chunk 4): prefill across window
boundaries and decode through the paged cache, through K-step windows
that roll a window over, must give the reference's full-forward logits;
and the control: a reference with the summaries left out ends a
rehearsal with no result line."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import server, spec
from benchmarks.references import evabyte

CONFIG = "evabyte-6.5b-d16"
CELL = "evabyte-6.5b-d16.decode-probe8-8k"


def toy():
    c = spec.load_config(CONFIG)
    return dict(c["hf"], **c["bench"]["rehearse"])


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog of published configurations here")
    with open(CATALOG) as f:
        published = next(json.loads(line) for line in f
                         if json.loads(line)["name"] == "EvaByte")
    c = spec.load_config(CONFIG)
    changed = {k for k, v in published["config"].items()
               if c["hf"].get(k, "absent") != v}
    assert changed == {"num_hidden_layers"} == set(c["bench"]["reduced"])
    assert c["bench"]["source"] == published["source_url"]
    assert c["reference"]["import"] == "benchmarks.references.evabyte"
    assert [r["prompt_tokens"] for r in c["reference"]["rows"]] == [
        48, 4090, 6200]


@pytest.mark.parametrize("prompt_tokens, new_tokens, k", [
    (20, 6, 1), (48, 16, 8), (70, 30, 8)])
def test_reference_matches_the_stage_model(prompt_tokens, new_tokens, k):
    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.registry import create_stage_model
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    hf = toy()
    cfg = normalize_config(hf)
    model = create_stage_model(cfg, 0, cfg.num_hidden_layers, tp_size=1)
    params = model.init_params(jax.random.key(3), dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, hf["vocab_size"], (2, prompt_tokens)).tolist()
    rows = evabyte.greedy_continuations(params, hf, prompts, new_tokens)

    engine = StageEngine(model, params, EngineConfig(
        page_size=cfg.eva.fit_page_size(16), num_pages=64, max_model_len=128,
        kv_dtype="float32", prefill_chunk_size=24, decode_lookahead=k))
    pipe = InProcessPipeline([engine])
    reqs = [Request(f"r{i}", prompt_ids=list(p), sampling_params=SamplingParams(
        temperature=0.0, max_new_tokens=new_tokens, ignore_eos=True,
        logprobs=True)) for i, p in enumerate(prompts)]
    for r in reqs:
        pipe.submit(r)
    pipe.run_until_complete()
    for r, row in zip(reqs, rows):
        assert list(r.output_ids) == row["tokens"]
        np.testing.assert_allclose(r.output_logprobs, row["logprobs"],
                                   atol=2e-4)
    if prompt_tokens + new_tokens > hf["window_size"]:
        assert engine.cache.rollovers > 0
        # With the summaries left out the reference is another model.
        wrong = evabyte.greedy_continuations(
            params, hf, prompts, new_tokens, with_summaries=False)
        assert max(abs(a - b) for r, w in zip(rows, wrong)
                   for a, b in zip(r["logprobs"], w["logprobs"])) > 1e-2


WRONG = '''"""Written by test_evabyte_reference.py: EvaByte's reference with the
chunk summaries left out."""
import functools

from benchmarks.references import evabyte

greedy_continuations = functools.partial(
    evabyte.greedy_continuations, with_summaries=False)
'''


def test_a_reference_without_summaries_fails_the_rehearsal(tmp_path):
    """The comparison's own control: the child runs a reference that
    leaves the summaries out, the server keeps them, and the replay of
    the 48 + 16 row (window 32: summaries visible from position 32 on)
    ends the run before any window, ``correct`` false, the number that
    failed beside its limit in the line's ``compared``."""
    mod_path = os.path.join(spec.BENCH_DIR, "references",
                            "evabyte_nosummary_for_test.py")
    cfg_path = spec.config_path("evabyte-nosummary-for-test")
    with open(spec.config_path(CONFIG)) as f:
        cfg = json.load(f)
    cfg["bench"]["reference"]["module"] = "evabyte_nosummary_for_test"
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # EvaByte's two entries by name: a later configuration's go at the
    # end of their lists.
    (config,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    bench["configs"].append(dict(
        config, name="evabyte-nosummary-for-test",
        file="benchmarks/configs/evabyte-nosummary-for-test.json"))
    bench["workloads"].append(dict(
        cell, name="evabyte-nosummary-for-test.probe",
        config="evabyte-nosummary-for-test"))
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("evabyte-nosummary-for-test.probe")
    bench_path = tmp_path / "BENCHMARK.json"
    bench_path.write_text(json.dumps(bench))
    try:
        with open(cfg_path, "w") as f:
            json.dump(cfg, f)
        with open(mod_path, "w") as f:
            f.write(WRONG)
        proc = subprocess.run(
            [sys.executable, os.path.join(spec.ROOT, "benchmarks", "run.py"),
             "--workload", "evabyte-nosummary-for-test.probe",
             "--seed", "3000000019", "--seconds", "3", "--trace", "0",
             "--rehearse", "--benchmark-json", str(bench_path)],
            cwd=spec.ROOT, capture_output=True, text=True, timeout=600)
    finally:
        for path in (cfg_path, mod_path):
            if os.path.exists(path):
                os.remove(path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["metrics"] == {}
    numbers = line["compared"]
    assert list(line)[-1] == "compared" and not server.holds(numbers)
    assert (numbers["logprob_gap_max"]["value"] > server.LOGPROB_TOL
            or numbers["tie_top2_gap_max"]["value"] > 2 * server.LOGPROB_TOL
            or numbers["divergences_untied"]["value"] == 1)
    assert numbers["requests_attempted"]["value"] is None   # no window
