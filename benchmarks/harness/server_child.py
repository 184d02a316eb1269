"""The child that holds the chip: ``serve`` with weights made from a seed.

Started by ``benchmarks/run.py`` (which never imports JAX). It

1. refuses to go on unless JAX runs on a TPU with as many chips as the
   cell asks for (``--rehearse``: any platform, and it says so);
2. writes a model directory holding ``config.json`` and hands ``serve``
   an id-to-text map in the tokenizer's place (``IdText``: ``t<id>`` per
   id): with ``serve``'s byte fallback the SSE stream sends no chunk for
   a token past id 255 (http_server ``_stream_body`` writes only when the
   text grew), so no client could see the first token of a
   152k-vocabulary model. Prompts go in as token arrays; nothing here is
   tokenized;
3. replaces ``parallax_tpu.models.loader.load_stage_params`` by a function
   that makes the stage's weights on the device in one jitted call from
   ``--seed`` (``StageModel.init_params`` with every bias-like leaf drawn
   too, in bf16), runs the configuration's plain reference on them
   (``bench.reference``; ``harness/reference.py`` where the key is
   absent) *before* ``serve`` sizes its KV pool, and hands them to
   ``serve`` placed as the loader would;
4. calls ``serve_main`` with the arguments ``cli.build_parser()`` gives
   for ``serve --model-path <dir> --port <p>`` plus the configuration
   file's ``serve_flags``.

Everything but where the weights come from is what a user of ``serve``
gets: its KV sizing, its defaults, its frontend.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import zlib

from benchmarks.harness import spec

# A rehearsal's reference rows are cut to what ``REHEARSE_FLAGS`` holds.
REHEARSE_REF_ROW = {"prompt_tokens": 48, "new_tokens": 16}
# serve's sizes in a rehearsal (toy widths on the CPU); the parent scales
# the traffic to them (``loadgen.REHEARSE``).
REHEARSE_FLAGS = ["--max-model-len", "512", "--max-batch-size", "8",
                  "--prefill-chunk-size", "64",
                  "--max-num-tokens-per-batch", "128", "--page-size", "16"]


def write_model_dir(path: str, hf_cfg: dict) -> None:
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_cfg, f, indent=1)


class IdText:
    """What ``serve`` asks of a tokenizer (``utils/tokenizer.py``), with
    one whitespace-free word per id and nothing to load."""

    eos_token_ids = ()

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def encode(self, text: str) -> list[int]:
        return [int(w[1:]) for w in text.split()]

    def decode(self, ids) -> str:
        return " ".join(f"t{i}" for i in ids)

    def apply_chat_template(self, messages) -> str:
        return " ".join(m["content"] for m in messages)


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's are above 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key((seed >> 24) & 0x7FFFFFFF),
                              seed & 0xFFFFFF)


QKV = ("q_proj", "k_proj", "v_proj")


def path_names(path) -> tuple[str, ...]:
    return tuple(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def is_bias(names: tuple[str, ...]) -> bool:
    return names[-1] == "bias" or names[-1].endswith("_bias")


def make_params(model, seed: int, mesh=None):
    """The stage's weights, on the device, from the seed, in one jitted
    call. ``init_params`` leaves every bias at a constant, which would
    let a dropped bias pass the reference check, so every leaf named
    ``bias`` or ``*_bias`` (a router's ``e_score_correction_bias``
    among them) gets normal(0, 0.02) added, from a key folded from its
    path. Nothing here knows a layer by name but for the q/k/v biases of
    ``self_attn``, whose keys stay ``fold_in(k_bias, 3 * layer + j)``:
    the dense configurations' weights are bit for bit what they were
    before the draw became general (PERF.md, PR 27)."""
    import jax
    import jax.numpy as jnp

    def init(key):
        k_init, k_bias = jax.random.split(key)
        k_rest = jax.random.fold_in(k_bias, 0x7FFFFFFF)
        params = model.init_params(k_init, dtype=jnp.bfloat16)

        def draw(path, leaf):
            names = path_names(path)
            if not (is_bias(names)
                    and jnp.issubdtype(leaf.dtype, jnp.floating)):
                return leaf
            if (len(names) == 5 and names[0] == "layers"
                    and names[2] == "self_attn" and names[3] in QKV):
                kb = jax.random.fold_in(
                    k_bias, 3 * int(names[1]) + QKV.index(names[3]))
            else:
                kb = jax.random.fold_in(
                    k_rest, zlib.crc32("/".join(names).encode()) >> 1)
            noise = 0.02 * jax.random.normal(kb, leaf.shape)
            return (leaf.astype(jnp.float32) + noise).astype(leaf.dtype)

        params = jax.tree_util.tree_map_with_path(draw, params)
        return model.finalize_params(params)

    key = seed_key(seed)
    if mesh is None:
        return jax.jit(init)(key)
    # A TP stage: every tensor goes straight to its shards, as the
    # loader puts it (``parallel/tp.param_sharding``).
    from parallax_tpu.parallel.tp import param_sharding

    shapes = jax.eval_shape(init, key)
    col_vecs = getattr(model, "tp_column_vector_params", frozenset())

    def sharding_of(path, leaf):
        return param_sharding(mesh, path_names(path), leaf,
                              col_vecs=col_vecs)

    shardings = jax.tree_util.tree_map_with_path(sharding_of, shapes)
    return jax.jit(init, out_shardings=shardings)(key)


def check_choice_margins(rows: list[dict]) -> None:
    """A row's ``choice_margin`` (``benchmarks/references``), where it
    has one: a float a generated position, none negative and none NaN
    (either would read as sure or unsure by accident of a comparison)."""
    for i, row in enumerate(rows):
        margin = row.get("choice_margin")
        if margin is None:
            continue
        if len(margin) != len(row["tokens"]):
            raise ValueError(
                f"reference row {i}: {len(margin)} choice margins for "
                f"{len(row['tokens'])} tokens")
        bad = [m for m in margin if not m >= 0]    # a NaN is not >= 0
        if bad:
            raise ValueError(
                f"reference row {i}: a choice margin is a distance, "
                f"not {bad[0]}")


def run_reference(params, hf_cfg: dict, seed: int, out_path: str,
                  ref: dict, rehearse: bool = False) -> None:
    """The configuration's reference (``spec.reference_of``) over its
    row shapes, each drawn from the seed in the order listed."""
    import jax
    import numpy as np

    t0 = time.monotonic()
    module = importlib.import_module(ref["import"])
    rng = np.random.default_rng([int(seed), 0x5EF])
    rows = []
    for shape in ref["rows"]:
        if rehearse:
            shape = {**shape, **{k: min(shape[k], v) for k, v
                                 in REHEARSE_REF_ROW.items()}}
        prompts = rng.integers(
            0, hf_cfg["vocab_size"],
            (shape["prompts"], shape["prompt_tokens"])).tolist()
        rows += module.greedy_continuations(
            params, hf_cfg, prompts, shape["new_tokens"])
    check_choice_margins(rows)
    with open(out_path + ".tmp", "w") as f:
        json.dump({"module": ref["module"], "rows": rows,
                   "seconds": round(time.monotonic() - t0, 3)}, f)
    os.replace(out_path + ".tmp", out_path)
    # The reference's programs and temporaries must not be counted
    # against the KV pool that ``serve`` sizes next.
    jax.clear_caches()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    with open(args.config_file) as f:
        raw = json.load(f)
    meta = raw.pop("bench")
    hf_cfg = raw
    ref = spec.reference_of(meta)
    flags = list(meta["serve_flags"])
    if args.rehearse:
        hf_cfg = dict(hf_cfg, **meta["rehearse"])
        # Off the chip TPU-auto keeps the XLA attention path: the Pallas
        # interpreter takes seconds a step, and the rehearsal is of the
        # harness, not of the kernels (chip_smoke.py --rehearse is).
        # A small pool too: the CPU backend copies the whole KV cache
        # every step (no donation).
        flags += REHEARSE_FLAGS

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and (
        device["platform"] != "tpu" or device["count"] != args.chips
    ):
        print(f"server_child: the cell asks for {args.chips} TPU chip(s), "
              f"JAX found {device}", file=sys.stderr, flush=True)
        return 3
    with open(os.path.join(args.workdir, "device.json"), "w") as f:
        json.dump(device, f)

    model_dir = os.path.join(args.workdir, "model")
    write_model_dir(model_dir, hf_cfg)

    from parallax_tpu.models import loader

    ref_path = os.path.join(args.workdir, "reference.json")

    def load_stage_params(model, model_path, dtype=None, quantize=None,
                          lora_path=None, mesh=None):
        if quantize or lora_path:
            raise ValueError("seeded weights take no quantize / lora_path")
        t0 = time.monotonic()
        params = make_params(model, args.seed, mesh=mesh)
        jax.block_until_ready(params)
        t1 = time.monotonic()
        run_reference(params, hf_cfg, args.seed, ref_path, ref,
                      rehearse=args.rehearse)
        print(f"server_child: weights {t1 - t0:.1f}s, reference "
              f"{time.monotonic() - t1:.1f}s", file=sys.stderr, flush=True)
        return params

    loader.load_stage_params = load_stage_params

    from parallax_tpu.backend import serve
    from parallax_tpu.cli import build_parser

    serve.load_tokenizer = lambda path: IdText(hf_cfg["vocab_size"])

    serve_args = build_parser().parse_args(
        ["serve", "--model-path", model_dir, "--host", "127.0.0.1",
         "--port", str(args.port), *flags]
    )
    return serve.serve_main(serve_args)


if __name__ == "__main__":
    sys.exit(main())
