"""Architecture registry: HF ``architectures[0]`` name -> StageModel class.

Capability parity: reference ``MODEL_CLASS_MAP`` + EntryClass registry
(``src/parallax/server/shard_loader.py:33-44,79-112``).
"""

from __future__ import annotations

from parallax_tpu.models.base import StageModel

MODEL_REGISTRY: dict[str, type[StageModel]] = {}


def register_model(*architectures: str):
    def deco(cls: type[StageModel]):
        for a in architectures:
            MODEL_REGISTRY[a] = cls
        return cls
    return deco


# The dense llama-family architectures share one block (config flags drive
# qk-norm / bias / sliding-window differences). EvaByte is that block too:
# its unit-offset norm, float32 residual, EVA summaries and multi-head
# output matrix all come from the normalized config (``ModelConfig.eva``).
# Jamba likewise (``ModelConfig.mamba``, models/jamba.py): its Mamba layers
# are a mixer in the block's attention slot. Ouro too: its looped stack
# (``ModelConfig.loop_passes``) is the layer loop run several times, its
# sandwich norms two more vectors in the block (``sandwich_norm``).
for _arch in (
    "LlamaForCausalLM",
    "MistralForCausalLM",
    "Qwen2ForCausalLM",
    "Qwen3ForCausalLM",
    "EvaByteForCausalLM",
    "JambaForCausalLM",
    "OuroForCausalLM",
):
    MODEL_REGISTRY[_arch] = StageModel


def get_model_class(architecture: str) -> type[StageModel]:
    try:
        return MODEL_REGISTRY[architecture]
    except KeyError:
        raise ValueError(
            f"unsupported architecture {architecture!r}; known: "
            f"{sorted(MODEL_REGISTRY)}"
        ) from None


def create_stage_model(config, start_layer: int, end_layer: int, **kw) -> StageModel:
    cls = get_model_class(config.architecture)
    return cls(config, start_layer, end_layer, **kw)
