"""A routed-expert configuration through a ``--rehearse`` run, as a later
``model_config`` PR would bring one: a toy Qwen2-MoE stage (a class the
registry has: the dense block's attention in both layers, a dense MLP in
the first and 16 routed experts, 2 a token, softmax scores renormalised,
in the last) and its float32 reference, which returns ``choice_margin``.
Files written and removed by the test, nothing listed in
``BENCHMARK.json``.

The routed layer is the last on purpose. This toy holds every expert,
and in such a stage a token that went to another expert carries other
keys and values into every later layer, where they reach every later
position through attention: no margin of the position's own forward
pass sees that (PERF.md section 2: a chip's share of the experts is
what the rule is for). After the last layer nothing attends.

``correct`` on three seeds, the bf16 program against the float32
reference although the two route a few of 160 positions otherwise; and
three references that must not pass: the routed part of a layer left
out, one expert fewer a token, every margin nought."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import server, spec

RUN = os.path.join(spec.ROOT, "benchmarks", "run.py")
NAME, STEM = "toy-routed-for-test", "toy_routed_for_test"
POSITIONS = 160

CONFIG = {
    "architectures": ["Qwen2MoeForCausalLM"], "model_type": "qwen2_moe",
    "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 8, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "vocab_size": 512,
    "max_position_embeddings": 4096, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000.0, "tie_word_embeddings": False,
    "attention_bias": True,
    "num_experts": 16, "num_experts_per_tok": 2, "moe_intermediate_size": 64,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "bench": {
        "source": "https://example.org/toy-routed/config.json",
        "reduced": {}, "assumed": {}, "deployment": "a toy, whole",
        "chips": 1, "serve_flags": [], "rehearse": {},
        # 10 x 16 = 160 positions, of which a quarter stands within
        # CHOICE_TIE of the routing boundary (32-50 over 16 seeds):
        # rows are listed until SURE_MIN are sure with room.
        "reference": {"module": STEM, "rows": [
            {"prompts": POSITIONS // 16, "prompt_tokens": 48,
             "new_tokens": 16}]},
    },
}

REFERENCE = '''"""Written by test_choice_rehearse.py: the toy routed-expert block in
float32. Attention is the dense block's (``harness/reference.py`` with
its MLP silenced); the experts follow ``Qwen2MoeSparseMoeBlock`` without
its shared expert: softmax over the router's logits, the best K
renormalised."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import reference as dense
from benchmarks.references import choice_margin

DROP_ROUTED_IN = {drop}      # a layer whose routed part is left out
FEWER_EXPERTS = {fewer}      # experts a token under the configuration's
MARGINS_NOUGHT = {nought}    # say that every position is near a boundary


def silent_mlp(hidden):
    zero = jnp.zeros((1, hidden), jnp.float32)
    return {{"gate_proj": {{"weight": zero}}, "up_proj": {{"weight": zero}},
            "down_proj": {{"weight": zero.T}}}}


@functools.partial(jax.jit, static_argnames=("k", "eps", "routed"))
def experts(lp, x, *, k, eps, routed):
    """``x + experts(norm(x))`` on [B, L, hidden], and the router's
    logits [B, L, E]."""
    with jax.default_matmul_precision("highest"):
        h = dense._rms(x, lp["post_attention_layernorm"]["weight"], eps)
        m = lp["mlp"]
        logits = h @ m["gate"]["weight"].astype(jnp.float32).T
        top, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        top = top / top.sum(axis=-1, keepdims=True)
        weight = (jax.nn.one_hot(ids, logits.shape[-1]) * top[..., None]).sum(-2)
        e = {{n: w.astype(jnp.float32) for n, w in m["experts"].items()}}
        act = (jax.nn.silu(jnp.einsum("blh,eih->blei", h, e["gate_proj"]))
               * jnp.einsum("blh,eih->blei", h, e["up_proj"]))
        out = jnp.einsum("blei,ehi,ble->blh", act, e["down_proj"], weight)
        return (x + out if routed else x), logits


def logits_and_margin(params, cfg, ids, at):
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, k = float(cfg["rms_norm_eps"]), cfg["num_experts_per_tok"]
    rows = np.arange(ids.shape[0])
    margin = np.full(ids.shape[0], np.inf)
    x = dense._embed(params["embed_tokens"]["weight"], jnp.asarray(ids))
    for i, lp in enumerate(params["layers"]):
        routed = "experts" in lp["mlp"]
        x = dense.layer_forward(
            dict(lp, mlp=silent_mlp(x.shape[-1])) if routed else lp, x,
            hq=hq, hkv=hkv, theta=float(cfg["rope_theta"]), eps=eps)
        if not routed:
            continue
        x, scores = experts(lp, x, k=k - FEWER_EXPERTS, eps=eps,
                            routed=i != DROP_ROUTED_IN)
        # This stage holds every expert: all candidates count.
        margin = np.minimum(margin, choice_margin(
            np.asarray(scores)[rows, at], k))
    x = dense._final_norm(x[rows, jnp.asarray(at)], params["norm"]["weight"],
                          eps)
    return dense._head_chunk(x, params["lm_head"]["weight"]), margin


def greedy_continuations(params, cfg, prompts, n_new):
    b, plen = len(prompts), len(prompts[0])
    ids = np.zeros((b, plen + n_new), np.int32)
    ids[:, :plen] = np.asarray(prompts, np.int32)
    out = [{{"prompt": list(map(int, p)), "tokens": [], "logprobs": [],
            "top2_gap": [], "choice_margin": []}} for p in prompts]
    for step in range(n_new):
        at = np.full((b,), plen + step - 1, np.int32)
        logits, margin = logits_and_margin(params, cfg, ids, at)
        lps = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        top2 = np.sort(np.asarray(logits), axis=-1)[:, -2:]
        tok = lps.argmax(-1)
        ids[:, plen + step] = tok
        for i in range(b):
            out[i]["tokens"].append(int(tok[i]))
            out[i]["logprobs"].append(float(lps[i, tok[i]]))
            out[i]["top2_gap"].append(float(top2[i, 1] - top2[i, 0]))
            out[i]["choice_margin"].append(
                0.0 if MARGINS_NOUGHT else float(margin[i]))
    return out
'''


@pytest.fixture()
def toy_routed(tmp_path):
    """Three new files (configuration, reference module, a copy of
    ``BENCHMARK.json`` with two more entries) and no edit to a file
    that is there. Yields ``write(**control)`` -> (cell, extra)."""
    cfg_path, mod_path = spec.config_path(NAME), spec.reference_path(STEM)
    assert not os.path.exists(cfg_path) and not os.path.exists(mod_path)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": NAME, "source": CONFIG["bench"]["source"],
        "file": os.path.relpath(cfg_path, spec.ROOT), "reduced": [],
        "why": "a toy that routes tokens to experts"})
    cell = NAME + ".decode-probe8"
    bench["workloads"].append({
        "name": cell, "config": NAME, "traffic": "decode-probe8",
        "chips": 1, "why": "a reference that says how near it came to "
        "choosing other experts"})
    bench_path = tmp_path / "BENCHMARK.json"
    bench_path.write_text(json.dumps(bench))

    def write(drop=None, fewer=0, nought=False):
        with open(cfg_path, "w") as f:
            json.dump(CONFIG, f)
        with open(mod_path, "w") as f:
            f.write(REFERENCE.format(drop=drop, fewer=fewer, nought=nought))
        return cell, ("--benchmark-json", str(bench_path))

    try:
        yield write
    finally:
        for path in (cfg_path, mod_path):
            if os.path.exists(path):
                os.remove(path)


def run(cell, seed, extra):
    proc = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--seed", str(seed),
         "--seconds", "3", "--trace", "0", "--rehearse", *extra],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    numbers = line["compared"]
    assert list(line)[-1] == "compared"
    assert list(numbers) == [
        "positions", "logprob_gap_max", "ties", "tie_top2_gap_max",
        "divergences_untied", "positions_sure", "positions_unsure",
        "unsure_logprob_gap_max", "choice_tie", "repeat_identical",
        "requests_attempted", "requests_failed_or_short"]
    assert numbers["choice_tie"]["value"] == server.CHOICE_TIE
    assert numbers["positions_sure"]["limit"] == server.SURE_MIN
    # Each beside its limit, the last lines of standard error too.
    last = proc.stderr.strip().splitlines()[-len(numbers):]
    assert [x.split(":")[0] for x in last] == [
        f"compared {name}" for name in numbers]
    return line, {k: n["value"] for k, n in numbers.items()}


@pytest.mark.parametrize("seed", [2271560481, 12, 14])
def test_a_routed_expert_stage_is_held_to_its_reference(toy_routed, seed):
    cell, extra = toy_routed()
    line, got = run(cell, seed, extra)
    assert line["correct"] is True and server.holds(line["compared"])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert got["positions_sure"] + got["positions_unsure"] == POSITIONS
    assert got["positions"] == got["positions_sure"] >= server.SURE_MIN
    assert got["logprob_gap_max"] <= server.LOGPROB_TOL
    # The rule had something to do: where the two sides went to other
    # experts the logprobs part by a whole expert (0.11-0.12 on these
    # seeds, over ``LOGPROB_TOL``: without the key the run is not
    # correct), and every such position is one the reference called
    # unsure (the sure ones read 0.006-0.01).
    assert got["positions_unsure"] > 0
    assert got["unsure_logprob_gap_max"] > 3 * got["logprob_gap_max"]


@pytest.mark.parametrize("control", [
    {"drop": 1}, {"fewer": 1}, {"nought": True}],
    ids=["routed_part_of_a_layer_dropped", "one_expert_fewer_a_token",
         "margins_all_nought"])
def test_a_reference_that_is_another_model_or_excuses_itself_fails(
        toy_routed, control):
    """The comparison's own controls at the rehearsal's size: the first
    two fail on a sure position as a dense reference with a layer left
    out does, and open no window; margins of nought leave no sure
    position, which ``correct`` does not take."""
    cell, extra = toy_routed(**control)
    line, got = run(cell, 14, extra)
    assert line["correct"] is False
    assert not server.holds(line["compared"])
    if control.get("nought"):
        assert (got["positions_sure"], got["positions_unsure"]) == (
            0, POSITIONS)
        assert got["logprob_gap_max"] == 0.0 and got["divergences_untied"] == 0
    else:
        assert (line["attempted"], line["metrics"]) == (0, {})  # no window
        assert (got["logprob_gap_max"] > server.LOGPROB_TOL
                or got["tie_top2_gap_max"] > 2 * server.LOGPROB_TOL
                or got["divergences_untied"] == 1)
        assert got["repeat_identical"] is None


def test_the_measurement_behind_the_constant_runs_at_a_toy_size(tmp_path):
    """``choice_flips.py`` (the bf16 program against the same weights in
    float32: where the two routed otherwise, and at which margins) is a
    measurement, made on the CPU and on the chip (PERF.md section 2);
    here it only has to run and count."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(spec.BENCH_DIR, "tests", "choice_flips.py"),
         "--size", "toy", "--layers", "2", "--out", str(tmp_path)],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(tmp_path / "choice_flips_toy_glm4moe_held192.json") as f:
        got = json.load(f)
    assert (got["positions"], got["token_layers"]) == (96, 192)
    assert got["flip_margin_first_layer"]["n"] <= got["flip_margin"]["n"]
    by_tie = got["by_choice_tie"]
    assert (by_tie["0.01"]["unsure_share"] < by_tie["0.05"]["unsure_share"]
            < by_tie["0.1"]["unsure_share"] <= 1.0)
