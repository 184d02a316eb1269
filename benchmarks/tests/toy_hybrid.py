"""A toy hybrid as a later ``model_config`` PR would bring one, for the
tests: a configuration and its work file, written to a directory the
test owns and never listed in ``BENCHMARK.json``. 16 blocks of one
mixer each (``x + mixer(norm(x))``), by the pattern: ``M`` a state-space
mixer with a convolution window and an SSM state and no pages, ``E``
routed experts of two matrices beside a shared expert, half of the
experts held on this chip, ``*`` grouped-query attention over pages.
It has a key called ``chunk_size`` (the scan's), which says nothing
about what a row attends.
"""

import json
import os
import shutil

from benchmarks.harness import spec, work

NAME = "toy-hybrid"

CONFIG = {
    "architectures": ["ToyHybridForCausalLM"],
    "hidden_size": 64, "vocab_size": 1000, "num_hidden_layers": 16,
    "hybrid_override_pattern": "MEMEM*EMEMEM*EME",
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
    "n_groups": 2, "conv_kernel": 4, "chunk_size": 128,
    "n_routed_experts": 8, "experts_held": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "bench": {
        "source": "https://example.org/toy-hybrid/config.json",
        "reduced": {"experts_held": {"published": 8, "run": 4}},
        "assumed": {}, "deployment": "one of two chips that share each layer",
        "chips": 1, "serve_flags": [],
        "work": {"module": "toy_hybrid"},
    },
}

WORK_FILE = '''"""Written by benchmarks/tests/toy_hybrid.py."""


def mamba(c):
    h, heads, n = c["hidden_size"], c["mamba_num_heads"], c["ssm_state_size"]
    inner = heads * c["mamba_head_dim"]
    conv = inner + 2 * c["n_groups"] * n            # x, B and C
    return {
        # in_proj -> [z | xBC | dt], the depthwise convolution with its
        # bias, dt_bias + A_log + D a head, the gated norm, out_proj,
        # the block's norm.
        "always": (h * (inner + conv + heads) + conv * c["conv_kernel"]
                   + conv + 3 * heads + inner + inner * h + h),
        # The convolution's window in bf16 and the SSM state in float32,
        # each read and written by a row's step.
        "state_bytes": 2 * (conv * (c["conv_kernel"] - 1) * 2
                            + inner * n * 4),
    }


def experts(c):
    h = c["hidden_size"]
    return {
        # Router and its correction bias, the shared expert's two
        # matrices, the block's norm.
        "always": (h * c["n_routed_experts"] + c["n_routed_experts"]
                   + 2 * h * c["moe_shared_expert_intermediate_size"] + h),
        "expert": 2 * h * c["moe_intermediate_size"],
        "experts_held": c["experts_held"],
        "experts_per_token": c["num_experts_per_tok"],
    }


def attention(c):
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return {"always": h * (q + 2 * kv) + q * h + h,
            "entry_bytes": 2 * kv * 2, "entry_flops": 4 * q,
            "row_bytes": 2 * q * 2}


KINDS = {"M": mamba, "E": experts, "*": attention}


def layers(cfg):
    return [KINDS[kind](cfg) for kind in cfg["hybrid_override_pattern"]]
'''

# What the work file says of one layer of each kind, by hand.
MAMBA_ALWAYS = (64 * (32 + 96 + 4) + 96 * 4 + 96 + 12 + 32 + 32 * 64 + 64)
MAMBA_STATE = 2 * (96 * 3 * 2 + 32 * 16 * 4)
EXPERTS_ALWAYS = 64 * 8 + 8 + 2 * 64 * 48 + 64
EXPERT = 2 * 64 * 32
ATTENTION_ALWAYS = 64 * (128 + 64) + 128 * 64 + 64
HEAD = 64 + 1000 * 64
ALWAYS = 7 * MAMBA_ALWAYS + 7 * EXPERTS_ALWAYS + 2 * ATTENTION_ALWAYS + HEAD


def write(bench_dir) -> str:
    """A benchmark directory of the test's own at ``bench_dir``: the
    committed data files (configurations, traffic, readers, work files,
    references, peaks) with the toy hybrid's two files added.
    Returns the configuration's name; the caller points
    ``spec.BENCH_DIR`` there."""
    for sub in ("configs", "traffic", "layer_metrics", "works", "references"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, sub),
                        os.path.join(bench_dir, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    os.makedirs(os.path.join(bench_dir, "harness"))
    for name in ("peaks.json", "reference.py"):
        shutil.copy(os.path.join(spec.BENCH_DIR, "harness", name),
                    os.path.join(bench_dir, "harness", name))
    with open(os.path.join(bench_dir, "configs", NAME + ".json"), "w") as f:
        json.dump(CONFIG, f)
    with open(os.path.join(bench_dir, "works", "toy_hybrid.py"), "w") as f:
        f.write(WORK_FILE)
    return NAME


def hand_ctx(stage, sw, t0=None, t1=None):
    """What ``metrics.read_layer_metric`` hands a reader, built by hand:
    0.4 s of the decode kernel in two layers' events beside another
    operation, 12.5 executions (one cut by the span's end) of the K=8
    window program in 2.0 s."""
    return {"trace": {"op_seconds": {"gqa_fused_decode_pallas.3": 0.25,
                                     "gqa_fused_decode_pallas.7": 0.15,
                                     "fusion.12": 9.0},
                      "module_seconds": {"jit_fn(1234)": 2.0,
                                         "jit__stage_fn(99)": 0.5},
                      "module_counts": {"jit_fn(1234)": 12.5,
                                        "jit__stage_fn(99)": 3}},
            "span_work": sw, "scrape_t0": t0, "scrape_t1": t1,
            "model": stage["cfg"], "work": stage,
            "peaks": spec.peaks_for("TPU v5 lite")}


# 100 steps of 8 rows: 800 decode tokens at contexts that sum to 2.4M.
SPAN_WORK = {"decode_tokens": 800, "decode_context_sum": 2_400_000}


def counts(read, pairs):
    """The span's two scrapes, between which the program counted ``read``
    held experts read and ``pairs`` token-expert pairs landed on them."""
    a = {work.EXPERTS_READ_SERIES: 1_000.0, work.PAIRS_HELD_SERIES: 5.0}
    return a, {work.EXPERTS_READ_SERIES: 1_000.0 + read,
               work.PAIRS_HELD_SERIES: 5.0 + pairs}
