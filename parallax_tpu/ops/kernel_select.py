"""Kernel-selection policy shared by every attention-family op.

One place answers the questions the ops facades
(``ops/attention.py``, ``ops/mla.py``, ``ops/dsa.py``, ``ops/msa.py``)
used to answer each for themselves:

- ``tpu_available()`` — is the default backend a TPU (the only backend
  the non-interpret Pallas kernels compile for)?
- ``resolve_use_pallas(flag)`` — the per-op kernel choice: an explicit
  caller flag wins, ``None`` means "Pallas iff TPU".
- ``resolve_decode_fused(flag)`` — the engine-level fused-decode-program
  choice (``EngineConfig.decode_fused`` / ``--decode-fused``): ``None``
  means auto (on on TPU, off elsewhere), ``True`` forces the fused
  kernels even off-TPU (they then run in Pallas interpret mode — the CI
  parity/microbench path), ``False`` pins the split dispatch chain.
- ``resolve_window_sampler_fused(flag, use_pallas)`` — which sampler a
  K-step decode window compiles: the sort-free Pallas sampler or the
  sort. Its own decision: the sampler kernel reads logits and sampling
  parameters and needs nothing of the attention kernels, so a model
  whose fused attention does not lower still gets it on a TPU.

The impl names returned by :func:`decode_attn_impl` are the canonical
labels for the ``parallax_attn_kernel_dispatch_total{impl,path}``
counter and the ``kernel`` sections of ``/status`` and
``/cluster/status`` — keep them in sync with docs/kernels.md.
"""

from __future__ import annotations

import jax

from parallax_tpu.utils import get_logger

logger = get_logger(__name__)

# Canonical impl labels (docs/kernels.md "Kernel catalog").
IMPL_FUSED = "pallas-fused"
IMPL_SPLIT = "pallas-split"
IMPL_XLA = "xla"
# The decode window's sampler (``window_sampler_impl``): IMPL_FUSED, the
# full-vocabulary sort of ``ops/sampling.sample_tokens``, or neither (a
# window whose rows are all greedy takes the argmax).
IMPL_SORT = "sort"
IMPL_ARGMAX = "argmax"

_warned_non_tpu_fused = False
_warned_auto_off = False
_warned_non_tpu_prefill = False
_warned_prefill_auto_off = False


def tpu_available() -> bool:
    """True when the default JAX backend is a TPU. A backend that fails
    to initialize raises here — "no TPU" is an answer only a working
    backend may give."""
    return jax.default_backend() == "tpu"


def resolve_use_pallas(use_pallas: bool | None) -> bool:
    """Per-op kernel choice: explicit flag wins, None = TPU autodetect."""
    if use_pallas is None:
        return tpu_available()
    return bool(use_pallas)


def fused_interpret() -> bool:
    """Whether fused Pallas kernels must run in interpret mode (any
    non-TPU backend: the CPU CI parity path)."""
    return not tpu_available()


def fused_lowering_gap(config) -> str | None:
    """Why TPU-auto must not select the fused Pallas family for this
    model: the reason Mosaic refuses one of its kernels (v5e, JAX 0.9.0,
    docs/kernels.md "Compiles for v5e"), or None when every fused kernel
    the model would dispatch compiles. Decided from the model config —
    the one thing the engine can observe before the first step."""
    if config.is_mla:
        return (
            "fused MLA decode / DSA indexer kernels do not lower (their "
            "one-row latent append is below the cache's HBM tiling)"
        )
    if config.msa is not None:
        return (
            "fused MSA indexer kernel does not lower ((1, page) score "
            "blocks are below the (8, 128) block minimum)"
        )
    if config.head_dim % 128:
        return (
            f"fused GQA kernels do not lower at head_dim "
            f"{config.head_dim} (page slices must fill 128 lanes)"
        )
    return None


def _auto_fused(config, family: str) -> bool:
    """TPU-auto for one fused family (``decode``/``prefill``): on when
    the backend is a TPU and the model's kernels lower there."""
    if not tpu_available():
        return False
    gap = fused_lowering_gap(config) if config is not None else None
    if gap is not None:
        logger.warning(
            "%s-fused kernels disabled: no TPU lowering for this model "
            "(%s); the split dispatch chain serves it", family, gap,
        )
        return False
    return True


def resolve_decode_fused(decode_fused: bool | None, config=None) -> bool:
    """Engine-level fused-decode choice: None = auto (on on a TPU for a
    model whose fused kernels lower, :func:`fused_lowering_gap`); True
    forces the fused kernels anywhere (interpret mode off-TPU); False =
    split.

    The single warning site for the non-TPU downgrade: auto mode on a
    CPU/GPU backend keeps the XLA reference path and says so once.
    """
    global _warned_non_tpu_fused, _warned_auto_off
    if decode_fused is None:
        on = _auto_fused(config, "decode")
        if not on and not tpu_available() and not _warned_auto_off:
            _warned_auto_off = True
            logger.info(
                "decode-fused kernels disabled: non-TPU backend keeps "
                "the XLA reference attention path (--decode-fused forces "
                "the fused kernels in Pallas interpret mode)",
            )
        return on
    if decode_fused and not tpu_available() and not _warned_non_tpu_fused:
        _warned_non_tpu_fused = True
        logger.info(
            "decode_fused forced on a non-TPU backend: fused Pallas "
            "kernels run in interpret mode (correct but slow — the CI "
            "parity configuration, not a serving one)",
        )
    return bool(decode_fused)


def resolve_window_sampler_fused(
    decode_fused: bool | None, use_pallas: bool | None
) -> bool:
    """Whether a K-step decode window's sampled rows may take the
    sort-free Pallas sampler (``fused_sample_topk_pallas``) in place of
    ``sample_tokens``' sort. The same ``EngineConfig.decode_fused`` flag
    forces it (True: anywhere, interpret mode off a TPU) or pins the
    sort (False); None = auto is decided by what the sampler kernel
    itself needs — a TPU backend with Pallas not pinned off — and not by
    :func:`fused_lowering_gap`, which speaks of the attention kernels
    only. Off a TPU auto keeps the XLA sampler."""
    if decode_fused is None:
        return tpu_available() and resolve_use_pallas(use_pallas)
    return bool(decode_fused)


def resolve_prefill_fused(prefill_fused: bool | None, config=None) -> bool:
    """Engine-level fused-prefill choice, mirroring
    :func:`resolve_decode_fused`: None = auto-on-TPU; True forces the
    fused ragged-prefill kernel anywhere (interpret mode off-TPU — the
    CI parity path); False keeps the split scatter + ragged-attention
    chain.

    The single warning site for the non-TPU downgrade — registered as
    the ``prefill_fused`` gate in analysis/gates.py.
    """
    global _warned_non_tpu_prefill, _warned_prefill_auto_off
    if prefill_fused is None:
        on = _auto_fused(config, "prefill")
        if not on and not tpu_available() and not _warned_prefill_auto_off:
            _warned_prefill_auto_off = True
            logger.info(
                "prefill-fused kernels disabled: non-TPU backend keeps "
                "the split prefill attention path (--prefill-fused "
                "forces the fused kernel in Pallas interpret mode)",
            )
        return on
    if prefill_fused and not tpu_available() and not _warned_non_tpu_prefill:
        _warned_non_tpu_prefill = True
        logger.info(
            "prefill_fused forced on a non-TPU backend: the fused "
            "ragged-prefill Pallas kernel runs in interpret mode "
            "(correct but slow — the CI parity configuration, not a "
            "serving one)",
        )
    return bool(prefill_fused)


def decode_attn_impl(
    decode_fused: bool, use_pallas: bool | None
) -> str:
    """The canonical impl label for a stage's decode attention path."""
    if decode_fused:
        return IMPL_FUSED
    if resolve_use_pallas(use_pallas):
        return IMPL_SPLIT
    return IMPL_XLA


def prefill_attn_impl(
    prefill_fused: bool, use_pallas: bool | None
) -> str:
    """The canonical impl label for a stage's prefill attention path."""
    if prefill_fused:
        return IMPL_FUSED
    if resolve_use_pallas(use_pallas):
        return IMPL_SPLIT
    return IMPL_XLA


def window_sampler_impl(fused: bool) -> str:
    """The impl label of the sampler a stage's decode windows compile
    for rows the fused sampler serves (greedy, plain temperature,
    ``top_k`` up to ``FUSED_SAMPLE_TOPK_MAX``)."""
    return IMPL_FUSED if fused else IMPL_SORT


def spec_window_impl(use_pallas: bool | None) -> str:
    """Impl label for the speculative decode window's verify forward.

    The window feeds 1+P tokens per row and gathers logits at every
    position — a multi-token ragged program the decode-fused kernels
    (single-token by construction: in-kernel append keys one slot per
    sequence, fused sampling reads one logits row per sequence) cannot
    serve. Fused engines therefore drop to the split-Pallas/XLA
    prefill-style path for spec windows; the engine registers the gate
    (analysis/gates.py) and counts the dispatch under ``path="spec"``
    so the fallback is operator-visible.
    """
    return IMPL_SPLIT if resolve_use_pallas(use_pallas) else IMPL_XLA
