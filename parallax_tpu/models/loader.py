"""Shard-selective safetensors weight loading.

Capability parity: reference ``src/parallax/server/shard_loader.py:47-653``
(MLXModelLoader: select only the files/keys containing the shard's layers,
remap global layer indices to stage-local ones, tied embeddings). The TPU
loader materializes the stage param pytree directly as jnp arrays in the
target dtype — weights keep the HF [out, in] layout (see
``models/layers.linear``), so no transposition pass is needed.
"""

from __future__ import annotations

import json
import os
import re

import jax.numpy as jnp
import numpy as np

from parallax_tpu.config import ModelConfig
from parallax_tpu.models.base import StageModel
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)

_LAYER_RE = re.compile(r"^model\.layers\.(\d+)\.(.+)$")

_DTYPE_MAP = {
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float32": jnp.float32,
}


def _weight_files(model_path: str, key_needed=None) -> list[str]:
    """Weight files to read. A selectively-downloaded stage dir
    legitimately lacks other stages' shard files, so missing indexed
    files are tolerated ONLY when (per the index's weight_map) they hold
    no key ``key_needed`` accepts — an incomplete copy of a needed shard
    still fails fast with the file names."""
    index = os.path.join(model_path, "model.safetensors.index.json")
    if os.path.exists(index):
        with open(index, encoding="utf-8") as f:
            weight_map = json.load(f)["weight_map"]
        files = sorted(set(weight_map.values()))
        present = [f for f in files
                   if os.path.exists(os.path.join(model_path, f))]
        missing = set(files) - set(present)
        if missing and key_needed is not None:
            needed_missing = sorted({
                fname for key, fname in weight_map.items()
                if fname in missing and key_needed(key)
            })
            if needed_missing:
                raise FileNotFoundError(
                    f"{model_path}: shard files holding this stage's "
                    f"weights are missing: {needed_missing}"
                )
        if not present:
            raise FileNotFoundError(
                f"index lists {len(files)} shard files but none exist "
                f"under {model_path}"
            )
        if missing:
            logger.info(
                "%s: %d/%d indexed shard files present (selective "
                "download)", model_path, len(present), len(files),
            )
        return [os.path.join(model_path, f) for f in present]
    single = os.path.join(model_path, "model.safetensors")
    if os.path.exists(single):
        return [single]
    raise FileNotFoundError(f"no safetensors weights under {model_path}")


def shard_key_filter(
    key: str, start_layer: int, end_layer: int, num_layers: int
) -> str | None:
    """Map a global HF weight key to a stage-local param path, or None if the
    key belongs to another stage (the selective-download filter of reference
    ``model_download.py`` / ``weight_filter_utils.py``)."""
    m = _LAYER_RE.match(key)
    if m:
        gi = int(m.group(1))
        if start_layer <= gi < end_layer:
            return f"layers.{gi - start_layer}.{m.group(2)}"
        return None
    if key.startswith("model.embed_tokens."):
        # embed needed on first stage; also on last for tied lm_head.
        return "embed_tokens." + key.split(".", 2)[2]
    for final in ("model.norm.", "model.final_layernorm."):   # Jamba's name
        if key.startswith(final):
            return ("norm." + key[len(final):]
                    if end_layer == num_layers else None)
    if key.startswith("lm_head."):
        return key if end_layer == num_layers else None
    if key.startswith("model.early_exit_gate."):   # Ouro's exit gate
        return (key[len("model."):] if end_layer == num_layers else None)
    return None


def _assign(tree: dict, path: str, value) -> None:
    parts = path.split(".")
    node = tree
    for i, part in enumerate(parts[:-1]):
        if part == "layers" and i == 0:
            node = node.setdefault("layers", {})
        else:
            node = node.setdefault(part, {})
    node[parts[-1]] = value


def _quant_settings_for(
    raw_cfg: dict, local_path: str, start_layer: int
) -> tuple[int, int] | None:
    """(bits, group_size) for a weight, honoring per-layer overrides.

    Mirrors reference ``shard_loader.py:496-540`` (class_predicate): the
    checkpoint's ``quantization`` dict holds global defaults plus optional
    per-module override dicts keyed by the global (``model.``-prefixed or
    bare) weight path.
    """
    qcfg = raw_cfg.get("quantization") or raw_cfg.get("quantization_config")
    if not isinstance(qcfg, dict) or "bits" not in qcfg:
        return None
    module = local_path.rsplit(".", 1)[0]  # strip trailing .weight/.scales
    candidates = [module, f"model.{module}"]
    if module.startswith("layers."):
        parts = module.split(".")
        if len(parts) > 2 and parts[1].isdigit():
            gi = int(parts[1]) + start_layer
            candidates.append(
                "model.layers." + str(gi) + "." + ".".join(parts[2:])
            )
    for key in candidates:
        override = qcfg.get(key)
        if override is False:
            return None
        if isinstance(override, dict):
            return (
                int(override.get("bits", qcfg["bits"])),
                int(override.get("group_size", qcfg.get("group_size", 64))),
            )
    return int(qcfg["bits"]), int(qcfg.get("group_size", 64))


def _iter_safetensors(path: str, fp8_mode: bool, resolve):
    """Yield ``(local_path, numpy_array, is_fp8)`` for one safetensors
    file, fetching only keys ``resolve`` maps to this stage (partial
    stages must not pay IO for other stages' tensors).

    Plain checkpoints stream through the numpy framework. FP8 checkpoints
    need the torch framework (numpy has no float8 dtype); float8 tensors
    are upcast to float32 on the way out, block scaling applied by the
    caller."""
    from safetensors import safe_open

    if not fp8_mode:
        with safe_open(path, framework="numpy") as f:
            for key in f.keys():
                local = resolve(key)
                if local is not None:
                    yield local, f.get_tensor(key), False
        return
    import torch

    fp8_dtypes = {torch.float8_e4m3fn, torch.float8_e5m2}
    with safe_open(path, framework="pt") as f:
        for key in f.keys():
            local = resolve(key)
            if local is None:
                continue
            t = f.get_tensor(key)
            if t.dtype in fp8_dtypes:
                yield local, t.to(torch.float32).numpy(), True
            elif t.dtype in (torch.bfloat16, torch.float16):
                yield local, t.to(torch.float32).numpy(), False
            else:
                yield local, t.numpy(), False


def load_stage_params(
    model: StageModel, model_path: str, dtype=jnp.bfloat16,
    quantize: str | None = None,
    lora_path: str | None = None,
    mesh=None,
) -> dict:
    """Load this stage's weights from a local HF checkpoint directory.

    With a TP ``mesh`` each full-precision tensor goes from host memory
    straight to its shards (``parallel.tp.param_sharding``); without
    one, to the default device. Staging the whole stage on one device
    first would need that device to hold all of it.

    Quantized checkpoints (MLX affine format: packed-uint32 ``weight`` +
    ``scales``/``biases`` siblings, config ``quantization`` dict with
    per-layer overrides) load into on-the-fly-dequantized params
    (``ops/quant.py``). HF FP8 block-quantized checkpoints
    (``quantization_config.quant_method == "fp8"``: float8_e4m3 weights +
    ``weight_scale_inv`` block scales — the DeepSeek/Qwen "-FP8"
    releases) dequantize to ``dtype`` on load. ``quantize="int8"|"int4"``
    quantizes a full-precision checkpoint at load time instead
    (reference intent: fitting DeepSeek-class MoE into a small-HBM
    stage; reference byte accounting: ``static_config.py:110-131``).
    """
    cfg = model.config
    raw_cfg = {}
    cfg_path = os.path.join(model_path, "config.json")
    if os.path.exists(cfg_path):
        with open(cfg_path, encoding="utf-8") as f:
            raw_cfg = json.load(f)
    qc = raw_cfg.get("quantization_config") or {}
    quant_method = qc.get("quant_method")
    if quant_method not in (None, "fp8", "gptq", "mxfp4"):
        # An unknown packed format (awq, compressed-tensors, ...) would
        # stream raw int tensors into float param slots and serve
        # garbage; refuse loudly instead.
        raise ValueError(
            f"quantization_config.quant_method {quant_method!r} is not "
            "supported (have: fp8, gptq, mxfp4, MLX-format, or on-load "
            "--quantization int8/int4); dequantize the checkpoint "
            "offline to serve it"
        )
    fp8_mode = quant_method == "fp8"
    fp8_block = tuple(qc.get("weight_block_size") or (128, 128))
    gptq_mode = quant_method == "gptq"
    gptq_bits = int(qc.get("bits") or 4)
    mxfp4_mode = quant_method == "mxfp4"
    # v1 storage biases zeros by +1; gptq_v2 (GPTQModel) does not.
    gptq_zero_offset = (
        0 if qc.get("checkpoint_format") == "gptq_v2" else 1
    )

    tree: dict = {}
    want_embed = model.is_first or (model.is_last and cfg.tie_word_embeddings)
    n_loaded = 0
    n_quant = 0
    # Full-precision tensors stream straight to device; only quantized
    # triplets (packed uint32 weight + scales/biases siblings, already the
    # compressed representation) are buffered until all parts arrive, so
    # host peak memory stays far below the stage's fp footprint.
    pending: dict[str, np.ndarray] = {}

    def _to_device(local: str, arr: np.ndarray):
        if mesh is None:
            return jnp.asarray(arr).astype(dtype)
        import jax

        from parallax_tpu.parallel.tp import param_sharding

        sharding = param_sharding(
            mesh, tuple(local.split(".")), arr,
            col_vecs=getattr(model, "tp_column_vector_params", frozenset()),
        )
        return jax.device_put(arr, sharding).astype(dtype)

    def _resolve(key: str) -> str | None:
        """THE stage-ownership filter (shared by file selection and the
        tensor loop): global checkpoint key -> local param path, or None
        when another stage owns it."""
        local = shard_key_filter(
            key, model.start_layer, model.end_layer, cfg.num_hidden_layers
        )
        if local is None or (
            local.split(".")[0] == "embed_tokens" and not want_embed
        ):
            return None
        return local

    weight_files = _weight_files(
        model_path, key_needed=lambda key: _resolve(key) is not None
    )

    def _dequant_fp8(local: str, w: np.ndarray, scale) -> None:
        from parallax_tpu.ops.quant import dequant_fp8_block

        _assign(tree, local,
                jnp.asarray(dequant_fp8_block(w, scale, fp8_block)).astype(
                    dtype))

    # FP8 weight/scale pairs live in the same shard file; dequantize as
    # soon as both halves are seen so host RAM holds at most one file's
    # stragglers, never the whole stage upcast to fp32.
    fp8_weights: dict[str, np.ndarray] = {}
    fp8_scales: dict[str, np.ndarray] = {}
    # GPTQ quartets (qweight/qzeros/scales/g_idx per projection) buffer
    # until complete; they are already the compressed representation.
    gptq_parts: dict[str, dict[str, np.ndarray]] = {}
    _GPTQ_SUFFIXES = (".qweight", ".qzeros", ".scales", ".g_idx")
    # MXFP4 halves (gpt-oss expert tensors: ``<proj>_blocks`` packed fp4
    # + ``<proj>_scales`` e8m0) pair within one shard file.
    mx_blocks: dict[str, np.ndarray] = {}
    mx_scales: dict[str, np.ndarray] = {}

    def _mx_emit(base: str, blocks: np.ndarray, scales: np.ndarray):
        from parallax_tpu.ops.quant import dequant_mxfp4

        w = dequant_mxfp4(blocks, scales)
        if w.ndim == 3:
            # Expert tensors dequantize to [E, out, in]; the serving
            # layout (and the bf16 checkpoints) use [E, in, out].
            w = np.swapaxes(w, 1, 2)
        _assign(tree, base, jnp.asarray(w).astype(dtype))

    for path in weight_files:
        for local, arr, is_fp8 in _iter_safetensors(path, fp8_mode, _resolve):
            if gptq_mode and local.endswith(_GPTQ_SUFFIXES):
                base, _, part = local.rpartition(".")
                gptq_parts.setdefault(base, {})[part] = arr
                continue
            if mxfp4_mode and local.endswith(("_blocks", "_scales")):
                is_blocks = local.endswith("_blocks")
                base = local[: -len("_blocks")]
                other = (mx_scales if is_blocks else mx_blocks).pop(
                    base, None
                )
                if other is not None:
                    blocks, scales = (arr, other) if is_blocks else (
                        other, arr
                    )
                    _mx_emit(base, blocks, scales)
                    n_loaded += 1
                else:
                    (mx_blocks if is_blocks else mx_scales)[base] = arr
                continue
            if local.endswith(".weight_scale_inv"):
                base = local[: -len("_scale_inv")]
                w = fp8_weights.pop(base, None)
                if w is not None:
                    _dequant_fp8(base, w, arr)
                    n_loaded += 1
                else:
                    fp8_scales[base] = arr
                continue
            if is_fp8:
                scale = fp8_scales.pop(local, None)
                if scale is not None:
                    _dequant_fp8(local, arr, scale)
                    n_loaded += 1
                else:
                    fp8_weights[local] = arr
                continue
            if local.endswith((".scales", ".biases")) or (
                local.endswith(".weight") and arr.dtype == np.uint32
            ):
                pending[local] = arr
                continue
            _assign(tree, local, _to_device(local, arr))
            n_loaded += 1

    if fp8_weights:
        raise ValueError(
            f"fp8 weights with no .weight_scale_inv sibling: "
            f"{sorted(fp8_weights)[:5]}"
        )
    if fp8_scales:
        raise ValueError(
            f"orphan fp8 scales without weights: {sorted(fp8_scales)[:5]}"
        )

    if mx_blocks or mx_scales:
        raise ValueError(
            f"unpaired mxfp4 tensors: "
            f"{sorted([*mx_blocks, *mx_scales])[:5]}"
        )

    if gptq_parts:
        from parallax_tpu.ops.quant import convert_gptq_weight

        for base, parts in gptq_parts.items():
            missing = {"qweight", "qzeros", "scales"} - set(parts)
            if missing:
                raise ValueError(
                    f"incomplete GPTQ tensors for {base!r}: missing "
                    f"{sorted(missing)}"
                )
            out = convert_gptq_weight(
                parts["qweight"], parts["qzeros"], parts["scales"],
                parts.get("g_idx"), gptq_bits,
                zero_offset=gptq_zero_offset,
            )
            if "weight" in out:
                # Activation-ordered (desc_act) groups: stored float.
                _assign(tree, base + ".weight",
                        jnp.asarray(out["weight"]).astype(dtype))
            else:
                _assign(tree, base + ".qweight",
                        jnp.asarray(out["qweight"]))
                _assign(tree, base + ".scales",
                        jnp.asarray(out["scales"]).astype(dtype))
                _assign(tree, base + ".biases",
                        jnp.asarray(out["biases"]).astype(dtype))
                n_quant += 1
            n_loaded += 1

    from parallax_tpu.ops.quant import unpack_uint32

    for local in list(pending):
        if not local.endswith(".weight"):
            continue
        base = local[: -len(".weight")]
        arr = pending.pop(local)
        scales = pending.pop(base + ".scales", None)
        if scales is None:
            raise ValueError(
                f"packed uint32 weight {base!r} has no .scales sibling"
            )
        qs = _quant_settings_for(raw_cfg, local, model.start_layer)
        if qs is None:
            raise ValueError(
                f"quantized weight {base!r} but the checkpoint config has "
                "no usable 'quantization' dict (bits/group_size unknown)"
            )
        _assign(tree, base + ".qweight",
                jnp.asarray(unpack_uint32(arr, qs[0])))
        _assign(tree, base + ".scales", jnp.asarray(scales).astype(dtype))
        biases = pending.pop(base + ".biases", None)
        if biases is not None:
            _assign(tree, base + ".biases", jnp.asarray(biases).astype(dtype))
        n_quant += 1
        n_loaded += 1
    if pending:
        raise ValueError(
            f"orphan quantization tensors without a weight: "
            f"{sorted(pending)[:5]}"
        )

    # layers dict {local_idx_str: {...}} -> ordered list
    layer_map = tree.get("layers", {})
    tree["layers"] = [
        layer_map[str(i)] for i in range(model.num_local_layers)
    ]
    logger.info(
        "loaded %d tensors (%d quantized) for layers [%d, %d) from %s",
        n_loaded, n_quant, model.start_layer, model.end_layer, model_path,
    )
    if lora_path:
        # Pre-finalize: fused/per-expert HF module names still exist here.
        apply_lora_adapter(model, tree, lora_path, dtype)
    tree = model.finalize_params(tree)
    if quantize:
        from parallax_tpu.ops.quant import quantize_tree

        bits = {"int8": 8, "int4": 4}[quantize]
        tree = quantize_tree(tree, bits=bits, group_size=64, dtype=dtype)
        logger.info("quantized stage params on load (%s)", quantize)
    return tree


def params_from_torch_state_dict(
    model: StageModel, state_dict, dtype=jnp.bfloat16
) -> dict:
    """Build stage params from an in-memory torch state dict (tests compare
    against HF transformers reference models)."""
    cfg = model.config
    tree: dict = {}
    want_embed = model.is_first or (model.is_last and cfg.tie_word_embeddings)
    for key, tensor in state_dict.items():
        local = shard_key_filter(
            key, model.start_layer, model.end_layer, cfg.num_hidden_layers
        )
        if local is None:
            continue
        if local.startswith("embed_tokens") and not want_embed:
            continue
        arr = np.asarray(tensor.detach().to("cpu").float().numpy())
        _assign(tree, local, jnp.asarray(arr).astype(dtype))
    layer_map = tree.get("layers", {})
    tree["layers"] = [layer_map[str(i)] for i in range(model.num_local_layers)]
    return model.finalize_params(tree)


def _apply_dora_magnitude(module: str, v: "np.ndarray", ab: dict):
    """DoRA closing step: renormalize the updated weight's rows to the
    learned magnitudes. ``v = W + scale * B @ A`` ([out, in]); plain LoRA
    modules (no magnitude) pass through unchanged. Reference semantics:
    ``shard_loader.py:188-225`` (load_lora DoRA branch)."""
    if "M" not in ab:
        return v
    m = np.asarray(ab["M"], np.float32).reshape(-1)   # [out]
    if m.shape[0] != v.shape[0]:
        raise ValueError(
            f"DoRA magnitude length {m.shape[0]} does not match output "
            f"dim {v.shape[0]} for {module}"
        )
    norm = np.linalg.norm(v, axis=1)                  # per output row
    return (m / np.maximum(norm, 1e-12))[:, None] * v


def apply_lora_adapter(
    model: StageModel, params: dict, adapter_path: str, dtype=jnp.bfloat16
) -> int:
    """Merge a PEFT-format LoRA adapter into this stage's weights.

    Reference: ``shard_loader.py:114-227`` (linear_to_lora_layers /
    load_lora) keeps live adapter modules; for TPU inference the adapters
    are merged at load — ``W' = W + (alpha / r) * B @ A`` — which is
    mathematically identical for frozen adapters and keeps the jitted
    stage function unchanged. Returns the number of merged modules.
    DoRA adapters (reference ``shard_loader.py:188-225``) merge too:
    ``W' = m * V / ||V||_row`` with ``V = W + (alpha/r) * B @ A`` and
    ``m`` the learned per-output-row ``lora_magnitude_vector`` — the
    weight-decomposed form collapses to a plain matrix for frozen
    adapters just like LoRA does.

    Call on the PRE-finalize tree (``load_stage_params(lora_path=...)``
    does) so adapters targeting fused (``gate_up_proj``) or per-expert
    modules still find their weights.

    Adapter layout: ``adapter_config.json`` (r, lora_alpha, optional
    use_rslora) + ``adapter_model.safetensors`` with keys
    ``base_model.model.model.layers.N.<module>.lora_{A,B}.weight``.
    """
    from safetensors import safe_open

    cfg_path = os.path.join(adapter_path, "adapter_config.json")
    with open(cfg_path, encoding="utf-8") as f:
        acfg = json.load(f)
    default_alpha = float(acfg.get("lora_alpha", acfg.get("r", 8)))
    alpha_pattern = acfg.get("alpha_pattern") or {}
    use_rslora = bool(acfg.get("use_rslora"))

    def scale_for(module: str, rank: int) -> float:
        # Per-module alpha overrides (PEFT alpha_pattern, matched on module
        # suffix); the rank always comes from the actual lora_A tensor so
        # rank_pattern adapters merge with the right scale.
        alpha = default_alpha
        for pat, a in alpha_pattern.items():
            if module.endswith(pat) or pat in module:
                alpha = float(a)
                break
        return alpha / (rank ** 0.5 if use_rslora else rank)

    weight_file = None
    for name in ("adapter_model.safetensors", "adapter.safetensors"):
        p = os.path.join(adapter_path, name)
        if os.path.exists(p):
            weight_file = p
            break
    if weight_file is None:
        raise FileNotFoundError(f"no adapter safetensors under {adapter_path}")

    cfg = model.config
    pairs: dict[str, dict[str, np.ndarray]] = {}
    with safe_open(weight_file, framework="numpy") as f:
        for key in f.keys():
            k = key
            for prefix in ("base_model.model.", "base_model."):
                if k.startswith(prefix):
                    k = k[len(prefix):]
                    break
            if ".lora_magnitude_vector" in k:
                # DoRA: per-output-row magnitude, applied after the
                # directional update.
                mod = k.split(".lora_magnitude_vector")[0]
                local = shard_key_filter(
                    mod + ".weight", model.start_layer, model.end_layer,
                    cfg.num_hidden_layers,
                )
                if local is not None:
                    pairs.setdefault(local[: -len(".weight")], {})["M"] = (
                        f.get_tensor(key)
                    )
                continue
            if ".lora_A." in k:
                mod, part = k.split(".lora_A."), "A"
            elif ".lora_B." in k:
                mod, part = k.split(".lora_B."), "B"
            else:
                continue
            local = shard_key_filter(
                mod[0] + ".weight", model.start_layer, model.end_layer,
                cfg.num_hidden_layers,
            )
            if local is None:
                continue
            pairs.setdefault(local[: -len(".weight")], {})[part] = (
                f.get_tensor(key)
            )

    merged = 0
    for module, ab in pairs.items():
        if "A" not in ab or "B" not in ab:
            logger.warning("lora adapter incomplete for %s; skipped", module)
            continue
        node = params
        parts = module.split(".")
        try:
            for part in parts:
                node = node[int(part)] if part.isdigit() else node[part]
        except (KeyError, IndexError, TypeError):
            logger.warning("lora target %s not in stage params; skipped",
                           module)
            continue
        if "weight" not in node:
            raise ValueError(
                f"cannot merge LoRA into quantized module {module}; load "
                "the checkpoint in full precision (or quantize AFTER "
                "merging with --quantization)"
            )
        a = np.asarray(ab["A"], np.float32)   # [r, in]
        b = np.asarray(ab["B"], np.float32)   # [out, r]
        delta = scale_for(module, a.shape[0]) * (b @ a)
        w = np.asarray(node["weight"], np.float32)
        if w.shape != delta.shape:
            raise ValueError(
                f"LoRA shape mismatch for {module}: {w.shape} vs "
                f"{delta.shape}"
            )
        new_w = _apply_dora_magnitude(module, w + delta, ab)
        node["weight"] = jnp.asarray(new_w).astype(dtype)
        merged += 1
    logger.info("merged %d LoRA modules from %s", merged, adapter_path)
    return merged
