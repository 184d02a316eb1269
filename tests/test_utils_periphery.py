"""parallax_utils parity: request metrics, version check, banner, and
offline LoRA adapter fusion (reference request_metrics.py /
version_check.py / ascii_anime.py / prepare_adapter.py)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parallax_tpu.utils.request_metrics import parse_usage_chunk, request_metrics


def test_request_metrics_from_sse_chunk():
    chunk = (
        'data: {"choices": [{"delta": {}}], "usage": {"prompt_tokens": 10, '
        '"completion_tokens": 20, "total_tokens": 30}}'
    )
    assert parse_usage_chunk(chunk) == {
        "prompt_tokens": 10, "completion_tokens": 20, "total_tokens": 30,
    }
    tps, ttft, in_t, out_t = request_metrics(chunk, 1.0, 1.5, 3.5)
    assert (in_t, out_t) == (10, 20)
    assert ttft == 500
    assert abs(tps - 10.0) < 1e-9


def test_request_metrics_malformed_is_all_none():
    for bad in (None, "", "data: [DONE]", b"\xff\xfe", '{"no": "usage"}'):
        assert request_metrics(bad, 0.0, 1.0, 2.0) == (
            None, None, None, None
        )
    # Missing first token (no output): also all-None, never a crash.
    ok = 'data: {"usage": {"prompt_tokens": 1, "completion_tokens": 0}}'
    assert request_metrics(ok, 0.0, None, None) == (None, None, None, None)


def test_version_check_offline_graceful(monkeypatch):
    from parallax_tpu.utils import version_check as vc

    assert vc.get_current_version() != ""
    monkeypatch.setattr(vc, "RELEASES_URL", "http://127.0.0.1:1/none")
    assert vc.get_latest_version(timeout=0.2) is None
    assert vc.check_latest_release() is None  # unknown latest -> quiet


def test_banner_contains_version():
    from parallax_tpu.utils.banner import banner
    from parallax_tpu.utils.version_check import get_current_version

    text = banner(device_line="v5e x1")
    assert get_current_version() in text
    assert "v5e x1" in text


def _write_tiny_checkpoint(path, cfg_dict, params):
    """Flatten a stage param tree into an HF-keyed safetensors file."""
    from safetensors.numpy import save_file

    tensors = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}.{k}" if prefix else k, v)
        elif isinstance(node, list):
            for i, v in enumerate(node):
                walk(f"{prefix}.{i}", v)
        else:
            tensors[f"model.{prefix}"] = np.asarray(node)

    walk("", params)
    # lm_head lives outside the "model." prefix in HF checkpoints.
    for k in list(tensors):
        if k.startswith("model.lm_head."):
            tensors[k[len("model."):]] = tensors.pop(k)
    os.makedirs(path, exist_ok=True)
    save_file(tensors, os.path.join(path, "model.safetensors"))
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg_dict, f)


def test_lora_merge_produces_servable_equal_checkpoint(tmp_path):
    """cli lora-merge output == serving base + --lora-path, weight for
    weight."""
    from safetensors.numpy import save_file

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.models.loader import load_stage_params
    from parallax_tpu.utils.adapter import merge_adapter

    cfg_dict = dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=64, vocab_size=97, max_position_embeddings=128,
        tie_word_embeddings=False,
    )
    cfg = normalize_config(cfg_dict)
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    base_dir = str(tmp_path / "base")
    _write_tiny_checkpoint(base_dir, cfg_dict, params)

    # A rank-2 adapter on layer 0's q_proj and layer 1's down_proj.
    rng = np.random.default_rng(0)
    h = cfg.hidden_size
    qdim = cfg.num_attention_heads * cfg.head_dim
    adapter_dir = str(tmp_path / "adapter")
    os.makedirs(adapter_dir)
    adapter = {
        "base_model.model.model.layers.0.self_attn.q_proj.lora_A.weight":
            rng.normal(size=(2, h)).astype(np.float32),
        "base_model.model.model.layers.0.self_attn.q_proj.lora_B.weight":
            rng.normal(size=(qdim, 2)).astype(np.float32),
        "base_model.model.model.layers.1.mlp.down_proj.lora_A.weight":
            rng.normal(size=(2, cfg.intermediate_size)).astype(np.float32),
        "base_model.model.model.layers.1.mlp.down_proj.lora_B.weight":
            rng.normal(size=(h, 2)).astype(np.float32),
    }
    save_file(adapter, os.path.join(adapter_dir, "adapter_model.safetensors"))
    with open(os.path.join(adapter_dir, "adapter_config.json"), "w") as f:
        json.dump({"r": 2, "lora_alpha": 4}, f)

    merged_dir = str(tmp_path / "merged")
    n = merge_adapter(base_dir, adapter_dir, merged_dir)
    assert n == 2
    assert os.path.exists(os.path.join(merged_dir, "config.json"))

    via_tool = load_stage_params(model, merged_dir, dtype=jnp.float32)
    via_load = load_stage_params(
        model, base_dir, dtype=jnp.float32, lora_path=adapter_dir
    )
    flat_a = jax.tree.leaves(via_tool)
    flat_b = jax.tree.leaves(via_load)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6
        )
    # And the delta actually changed the targeted weight.
    base = load_stage_params(model, base_dir, dtype=jnp.float32)
    q0 = np.asarray(base["layers"][0]["self_attn"]["q_proj"]["weight"])
    q0m = np.asarray(via_tool["layers"][0]["self_attn"]["q_proj"]["weight"])
    assert np.abs(q0m - q0).max() > 1e-3


def test_dora_merge_offline_equals_load_time(tmp_path):
    """DoRA offline fusion == load-time merge, and merged row norms equal
    the learned magnitudes."""
    from safetensors.numpy import save_file

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.models.loader import load_stage_params
    from parallax_tpu.utils.adapter import merge_adapter

    cfg_dict = dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=64, vocab_size=97, max_position_embeddings=128,
        tie_word_embeddings=False,
    )
    cfg = normalize_config(cfg_dict)
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(1), dtype=jnp.float32)
    base_dir = str(tmp_path / "base")
    _write_tiny_checkpoint(base_dir, cfg_dict, params)

    rng = np.random.default_rng(7)
    h = cfg.hidden_size
    qdim = cfg.num_attention_heads * cfg.head_dim
    mag = (rng.normal(size=qdim).astype(np.float32) * 0.1 + 1.0)
    adapter_dir = str(tmp_path / "adapter")
    os.makedirs(adapter_dir)
    pre = "base_model.model.model.layers.0.self_attn.q_proj"
    save_file({
        f"{pre}.lora_A.weight": rng.normal(size=(2, h)).astype(np.float32),
        f"{pre}.lora_B.weight": rng.normal(size=(qdim, 2)).astype(np.float32),
        f"{pre}.lora_magnitude_vector.weight": mag,
    }, os.path.join(adapter_dir, "adapter_model.safetensors"))
    with open(os.path.join(adapter_dir, "adapter_config.json"), "w") as f:
        json.dump({"r": 2, "lora_alpha": 4, "use_dora": True}, f)

    merged_dir = str(tmp_path / "merged")
    assert merge_adapter(base_dir, adapter_dir, merged_dir) == 1

    via_tool = load_stage_params(model, merged_dir, dtype=jnp.float32)
    via_load = load_stage_params(
        model, base_dir, dtype=jnp.float32, lora_path=adapter_dir
    )
    qt = np.asarray(via_tool["layers"][0]["self_attn"]["q_proj"]["weight"])
    ql = np.asarray(via_load["layers"][0]["self_attn"]["q_proj"]["weight"])
    np.testing.assert_allclose(qt, ql, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.linalg.norm(qt, axis=1), mag,
                               rtol=2e-5, atol=2e-5)


def test_cli_lora_merge_subcommand(tmp_path, capsys):
    import pytest

    from parallax_tpu.cli import build_parser

    args = build_parser().parse_args([
        "lora-merge", "--model-path", "x", "--adapter-path", "y",
        "--out-dir", "z",
    ])
    assert args.command == "lora-merge"
    from parallax_tpu.cli import main

    with pytest.raises(FileNotFoundError):
        main(["lora-merge", "--model-path", str(tmp_path),
              "--adapter-path", str(tmp_path), "--out-dir",
              str(tmp_path / "o")])


def test_shard_files_for_layers_selects_minimal_set():
    from parallax_tpu.utils.model_download import shard_files_for_layers

    wm = {
        "model.embed_tokens.weight": "s0.safetensors",
        "model.layers.0.self_attn.q_proj.weight": "s0.safetensors",
        "model.layers.1.mlp.down_proj.weight": "s1.safetensors",
        "model.layers.2.self_attn.q_proj.weight": "s1.safetensors",
        "model.layers.3.mlp.down_proj.weight": "s2.safetensors",
        "model.norm.weight": "s3.safetensors",
        "lm_head.weight": "s3.safetensors",
    }
    # First stage: embed + layers 0-1.
    assert shard_files_for_layers(wm, 0, 2, 4) == [
        "s0.safetensors", "s1.safetensors",
    ]
    # Last stage (untied): layers 2-3 + norm/lm_head, no embed file pull
    # beyond what its layers already need.
    assert shard_files_for_layers(wm, 2, 4, 4, tie_word_embeddings=False) == [
        "s1.safetensors", "s2.safetensors", "s3.safetensors",
    ]
    # Middle stage of a tied model: layer 1 only.
    assert shard_files_for_layers(wm, 1, 2, 4) == ["s1.safetensors"]
    # Tied last stage needs the embed file (it IS the lm_head).
    assert "s0.safetensors" in shard_files_for_layers(
        wm, 2, 4, 4, tie_word_embeddings=True
    )


def test_selective_download_with_injected_fetcher(tmp_path):
    """End-to-end against a local 'hub': only the needed shard files are
    fetched, and the result dir serves load_stage_params."""
    import shutil

    from safetensors.numpy import save_file

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.models.loader import load_stage_params
    from parallax_tpu.utils.model_download import selective_download

    cfg_dict = dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=64, vocab_size=97, max_position_embeddings=128,
        tie_word_embeddings=False,
    )
    cfg = normalize_config(cfg_dict)
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    # Build a sharded "remote" repo: one file per layer + one for ends.
    remote = tmp_path / "remote"
    remote.mkdir()
    flat: dict[str, np.ndarray] = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}.{k}" if prefix else k, v)
        elif isinstance(node, list):
            for i, v in enumerate(node):
                walk(f"{prefix}.{i}", v)
        else:
            flat[f"model.{prefix}"] = np.asarray(node)

    walk("", params)
    for k in list(flat):
        if k.startswith("model.lm_head."):
            flat[k[len("model."):]] = flat.pop(k)
    shards = {"a.safetensors": {}, "b.safetensors": {}, "c.safetensors": {}}
    wmap = {}
    for k, v in flat.items():
        if ".layers.0." in k:
            fname = "a.safetensors"
        elif ".layers.1." in k:
            fname = "b.safetensors"
        else:
            fname = "c.safetensors"
        shards[fname][k] = v
        wmap[k] = fname
    for fname, tensors in shards.items():
        save_file(tensors, str(remote / fname))
    json.dump({"weight_map": wmap}, open(remote / "model.safetensors.index.json", "w"))
    json.dump(cfg_dict, open(remote / "config.json", "w"))

    local = tmp_path / "local"
    local.mkdir()
    fetched = []

    def fetch(repo_id, filename):
        src = remote / filename
        if not src.exists():
            raise FileNotFoundError(filename)
        fetched.append(filename)
        dst = local / filename
        shutil.copy2(src, dst)
        return str(dst)

    out = selective_download("fake/repo", 1, 2, fetch=fetch)
    assert out == str(local)
    # Layer-0 shard was never fetched for a [1, 2) stage.
    assert "a.safetensors" not in fetched
    assert "b.safetensors" in fetched

    stage = StageModel(cfg, 1, 2, use_pallas=False)
    loaded = load_stage_params(stage, out, dtype=jnp.float32)
    ref = np.asarray(params["layers"][1]["self_attn"]["q_proj"]["weight"])
    np.testing.assert_allclose(
        np.asarray(loaded["layers"][0]["self_attn"]["q_proj"]["weight"]),
        ref,
    )


def test_loader_fails_fast_on_missing_needed_shard(tmp_path):
    """An incomplete copy (missing a shard this stage NEEDS) must raise
    with the file names, not a cryptic downstream KeyError; missing
    shards of OTHER stages stay tolerated."""
    import shutil

    import pytest

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.models.loader import load_stage_params
    from safetensors.numpy import save_file

    cfg_dict = dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=64, vocab_size=97, max_position_embeddings=128,
        tie_word_embeddings=False,
    )
    cfg = normalize_config(cfg_dict)
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    flat = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(f"{prefix}.{k}" if prefix else k, v)
        elif isinstance(node, list):
            for i, v in enumerate(node):
                walk(f"{prefix}.{i}", v)
        else:
            flat[f"model.{prefix}"] = np.asarray(node)

    walk("", params)
    for k in list(flat):
        if k.startswith("model.lm_head."):
            flat[k[len("model."):]] = flat.pop(k)
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    shards = {"l0.safetensors": {}, "l1.safetensors": {}, "ends.safetensors": {}}
    wmap = {}
    for k, v in flat.items():
        fname = ("l0.safetensors" if ".layers.0." in k
                 else "l1.safetensors" if ".layers.1." in k
                 else "ends.safetensors")
        shards[fname][k] = v
        wmap[k] = fname
    for fname, tensors in shards.items():
        save_file(tensors, str(ckpt / fname))
    json.dump({"weight_map": wmap},
              open(ckpt / "model.safetensors.index.json", "w"))
    json.dump(cfg_dict, open(ckpt / "config.json", "w"))

    # Missing shard needed by a [0, 2) stage -> clear FileNotFoundError.
    os.remove(ckpt / "l1.safetensors")
    with pytest.raises(FileNotFoundError, match="l1.safetensors"):
        load_stage_params(model, str(ckpt), dtype=jnp.float32)
    # But a [0, 1) stage doesn't need it and loads fine.
    s0 = StageModel(cfg, 0, 1, use_pallas=False)
    loaded = load_stage_params(s0, str(ckpt), dtype=jnp.float32)
    assert len(loaded["layers"]) == 1


# -- compile cache placement (utils/compile_cache.py) -----------------------


@pytest.mark.parametrize("env_dir,flag,want_dir,sets_dir", [
    # The environment places the cache: the program sets no directory
    # in code, and the --compilation-cache-dir flag loses to it.
    ("/x", None, "/x", False),
    ("/x", "FLAG", "/x", False),
    # Unplaced: the flag's directory, else <checkout>/.jax_cache — one
    # fixed path (the path is part of the cache key).
    (None, "FLAG", "FLAG", True),
    (None, None, "CHECKOUT", True),
    # "off" disables whatever the environment says.
    ("/x", "off", None, False),
    (None, "off", None, False),
], ids=["env", "env-beats-flag", "flag", "checkout-default", "off-beats-env",
        "off"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir, flag,
                                 want_dir, sets_dir):
    from parallax_tpu.utils import compile_cache

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = {"FLAG": str(tmp_path / "flag"),
             "CHECKOUT": os.path.join(repo, ".jax_cache")}
    flag, want_dir = names.get(flag, flag), names.get(want_dir, want_dir)
    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda key, value: updates.update({key: value})
    )
    monkeypatch.setattr(compile_cache, "_active_path", None)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)

    assert compile_cache.enable_compilation_cache(flag) == want_dir
    assert compile_cache.active_cache_dir() == want_dir
    if sets_dir:
        assert updates["jax_compilation_cache_dir"] == want_dir
        assert os.path.isdir(want_dir)
    else:
        assert "jax_compilation_cache_dir" not in updates
        assert not os.path.exists(names["FLAG"])
    if want_dir is not None:
        # Small programs are cached too, wherever the cache lives.
        assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
        assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_persistent_cache_hits_are_not_counted_as_compiles():
    """JAX fires its backend-compile duration event around the whole
    compile-or-load-from-cache call, so it fires for persistent-cache
    hits too; the hit announces itself first on the same thread. The
    compile series must count real compiles only."""
    from jax import monitoring

    from parallax_tpu.obs.device import get_device_plane
    from parallax_tpu.utils import compile_cache

    compile_cache.register_compile_counter()
    observatory = get_device_plane().compile
    before = observatory.snapshot()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25
    )
    after_hit = observatory.snapshot()
    assert after_hit["cache_hits_total"] == before["cache_hits_total"] + 1
    assert after_hit["compiles_total"] == before["compiles_total"]
    assert after_hit["compile_ms_total"] == before["compile_ms_total"]
    monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25
    )
    after_miss = observatory.snapshot()
    assert after_miss["cache_hits_total"] == after_hit["cache_hits_total"]
    assert after_miss["compiles_total"] == after_hit["compiles_total"] + 1


# -- hardware table (utils/hw.py): no guessed accelerator -------------------


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", "cpu"),
    ("tpu", "TPU v5 lite", "v5e"),
    ("tpu", "TPU v5e", "v5e"),
    ("tpu", "TPU v5p", "v5p"),
    ("tpu", "TPU v6 lite", "v6e"),
    ("tpu", "TPU v4", "v4"),
    # An accelerator the table does not know is an error: not a v5e
    # because its name holds "tpu", not the CPU row by default.
    ("tpu", "TPU v9 mega", None),
    ("gpu", "NVIDIA H100", None),
])
def test_device_kind_is_looked_up_never_guessed(platform, kind, want):
    from parallax_tpu.utils.hw import _device_kind_key

    if want is None:
        with pytest.raises(ValueError, match="unknown accelerator"):
            _device_kind_key(platform, kind)
    else:
        assert _device_kind_key(platform, kind) == want


@pytest.mark.parametrize("platform,stats,want", [
    ("tpu", {"bytes_limit": 1000, "bytes_in_use": 200}, 400),
    # The CPU platform reports no memory: budgeted from its table row.
    ("cpu", None, 4 << 30),
    # A TPU that reports none must not have its KV pool sized from one.
    ("tpu", None, None),
    ("tpu", {}, None),
])
def test_kv_budget_comes_from_the_device_not_the_table(
        monkeypatch, platform, stats, want):
    from parallax_tpu.utils import hw

    class Dev:
        def memory_stats(self):
            return stats

    Dev.platform = platform
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev()])
    if want is None:
        with pytest.raises(RuntimeError, match="reports no memory limit"):
            hw.device_free_memory_bytes(0.5)
    else:
        assert hw.device_free_memory_bytes(0.5) == want
