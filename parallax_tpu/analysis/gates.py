"""The registered feature-gate table.

ROADMAP item 2 calls out a whole class of production surprises:
"feature X silently off" — one config knob warn-disables another
(host tier vs TP sharding, SP vs unsupported attention) and nothing
but a log line records the loss.
This table makes every such gate an *explicit, reviewed* fact:

- the config-gate checker scans the package for gate-shaped log
  messages ("... disabled: ...", "... ignored ...", "run(s)
  replicated") and fails on any site not covered by a ``marker``
  below — adding a new gate without registering it here is a lint
  error;
- each entry must name a real ``EngineConfig`` field (or a CLI flag,
  spelled ``flag:--name``) — renaming the field orphans the entry and
  fails the pass;
- each entry's ``doc`` file must exist and mention the feature, so the
  operator-facing story can never silently drift from the code.

Adding a gate therefore takes three deliberate steps: the warning in
code, the entry here, and the doc paragraph — exactly the trail a
reviewer needs.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Gate:
    """One registered warn-gate: ``feature`` is the EngineConfig field
    (or ``flag:--cli-name``) whose requested behavior the gate can turn
    off; ``marker`` is a distinctive substring of the log message at
    the gate site; ``doc`` is the operator-facing page that explains
    the tradeoff."""

    feature: str
    marker: str
    doc: str
    reason: str


GATE_TABLE: tuple[Gate, ...] = (
    Gate(
        feature="host_cache_bytes",
        marker="host KV tier disabled: EVA rows hold summary",
        doc="docs/memory.md",
        reason="a page image does not say whether a page holds exact "
               "entries, visible summaries or pending ones",
    ),
    Gate(
        feature="enable_prefix_cache",
        marker="prefix cache disabled: an EVA row releases",
        doc="docs/memory.md",
        reason="a window's exact pages are released at its rollover, so "
               "a finished row has no page list of its prefix to donate",
    ),
    Gate(
        feature="speculative_tokens",
        marker="speculative decoding disabled: EVA rows roll",
        doc="docs/decode_loop.md",
        reason="only the plain K-step window switches page tables at a "
               "window boundary",
    ),
    Gate(
        feature="host_cache_bytes",
        marker="host KV tier disabled: hybrid linear-state KV",
        doc="docs/memory.md",
        reason="recurrent state has no page-granularity host image",
    ),
    Gate(
        feature="host_cache_bytes",
        marker="host KV tier disabled: TP-sharded KV",
        doc="docs/memory.md",
        reason="sharded gather/scatter transfers not implemented yet",
    ),
    Gate(
        feature="host_cache_bytes",
        marker="host KV tier disabled: a looped stack keeps",
        doc="docs/memory.md",
        reason="a page id addresses one place a pass in a layer's array; "
               "the tier's gather and its page images know one a layer",
    ),
    Gate(
        feature="host_cache_bytes",
        marker="host KV tier disabled: unsupported KV layout",
        doc="docs/memory.md",
        reason="non-paged layouts and sub-page budgets cannot tier",
    ),
    Gate(
        feature="sp_threshold",
        marker="SP prefill is disabled for",
        doc="docs/quickstart.md",
        reason="model class/config does not support ring-attention "
               "prefill; sp chips run replicated",
    ),
    Gate(
        feature="flag:--sp-size",
        marker="--sp-size %d ignored",
        doc="docs/quickstart.md",
        reason="MLA/sparse/hybrid/window/sink attention has no SP path",
    ),
    Gate(
        feature="flag:--role",
        marker="kv-image handoff disabled: no host KV tier",
        doc="docs/disaggregation.md",
        reason="page shipping harvests the PR 2 pinned host image; "
               "without a host tier handoffs ship checkpoints only and "
               "the decode pool re-prefills",
    ),
    Gate(
        feature="decode_fused",
        marker="fused window sampler disabled",
        doc="docs/kernels.md",
        reason="top-p/min-p and top_k beyond FUSED_SAMPLE_TOPK_MAX need "
               "the sort-based sampler, for the whole batch of the "
               "window; logits features (penalties, logprobs, grammar, "
               "logit_bias) run in-window as scan-carry state and do "
               "not downshift. The window's sampler is chosen apart "
               "from the attention family "
               "(ops/kernel_select.resolve_window_sampler_fused): fused "
               "attention may or may not be on beside it and is not "
               "touched",
    ),
    Gate(
        feature="decode_fused",
        marker="decode-fused kernels disabled: non-TPU backend",
        doc="docs/kernels.md",
        reason="auto mode keeps the XLA reference attention path off-TPU; "
               "--decode-fused forces the fused kernels in Pallas "
               "interpret mode (CI parity, not a serving configuration)",
    ),
    Gate(
        feature="flag:--role",
        marker="ignored in scheduler-less mode",
        doc="docs/disaggregation.md",
        reason="handoff targets come from the scheduler's decode-pool "
               "chooser; a gossip swarm has nobody to pick them",
    ),
    Gate(
        feature="speculative_tokens",
        marker="speculative decode windows disabled: multi-stage",
        doc="docs/decode_loop.md",
        reason="the on-device draft-verify window needs the whole ring "
               "local; pipelines speculate via pp-spec, whose "
               "last-stage verify forces a synchronous resolve",
    ),
    Gate(
        feature="constrained_window",
        marker="constrained decode windows disabled",
        doc="docs/decode_loop.md",
        reason="grammar masking inside the fused K-step window needs a "
               "dense device transition table; when the knob is off or "
               "the grammar's state-x-vocab product exceeds "
               "DEVICE_TABLE_MAX_CELLS, grammar batches decode on the "
               "host-synchronous sampler",
    ),
    Gate(
        feature="decode_fused",
        marker="decode-fused kernels disabled for speculative windows",
        doc="docs/kernels.md",
        reason="the spec window's verify forward is multi-token ragged; "
               "fused append and fused sampling are single-token by "
               "construction — plain windows keep the fused kernels",
    ),
    Gate(
        feature="prefill_fused",
        marker="prefill-fused kernels disabled: non-TPU backend",
        doc="docs/kernels.md",
        reason="auto mode keeps the split/XLA prefill attention path "
               "off-TPU; --prefill-fused forces the fused ragged-prefill "
               "kernel in Pallas interpret mode (CI parity, not a "
               "serving configuration)",
    ),
    Gate(
        feature="prefill_fused",
        marker="prefill_fused forced on a non-TPU backend",
        doc="docs/kernels.md",
        reason="explicit opt-in runs the fused ragged-prefill kernel in "
               "interpret mode — correct but slow; the CI parity "
               "configuration",
    ),
    Gate(
        feature="prefill_fused",
        marker="prefill-fused kernel unavailable for this model family",
        doc="docs/kernels.md",
        reason="MLA latent-page and MSA sparse-index prefill have their "
               "own dispatch chains; the fused ragged-prefill kernel "
               "covers the GQA page layout only",
    ),
    Gate(
        feature="decode_fused",
        marker="fused kernels disabled: no TPU lowering for this model",
        doc="docs/kernels.md",
        reason="TPU-auto only selects kernels the v5e compiler accepts: "
               "MLA/DSA/MSA fused decode and GQA at head_dim not a "
               "multiple of 128 are refused by Mosaic "
               "(ops/kernel_select.fused_lowering_gap), so those models "
               "keep the split dispatch chain",
    ),
    Gate(
        feature="prefill_fused",
        marker="fused kernels disabled: no TPU lowering for this model",
        doc="docs/kernels.md",
        reason="same lowering gap as decode_fused: the fused ragged "
               "prefill kernel is refused at head_dim not a multiple "
               "of 128",
    ),
    Gate(
        feature="prefill_seq_parallel",
        marker="sequence-parallel prefill disabled: single-chip stage",
        doc="docs/kernels.md",
        reason="sharding one prompt's chunks needs an sp mesh axis with "
               "more than one chip; ordinary chunked prefill proceeds "
               "on the single chip",
    ),
    Gate(
        feature="qos",
        marker="qos park enforcement disabled: no host KV tier",
        doc="docs/qos.md",
        reason="shed enforcement parks running batch decodes through "
               "the PR 2 preempt-to-host path; without the tier, "
               "shedding can only hold NEW admissions",
    ),
    Gate(
        feature="flag:--qos",
        marker="qos autoscaler disabled: single-host serving",
        doc="docs/qos.md",
        reason="the autoscaler re-roles pipelines between the swarm's "
               "prefill/decode pools; a single-host engine has no "
               "pools to rebalance",
    ),
    Gate(
        feature="flag:--scheduler-standby",
        marker="standby disabled: no --scheduler-standby",
        doc="docs/ha.md",
        reason="without a standby address list the scheduler journals "
               "nothing and a primary crash stalls routing until a "
               "manual restart — warm-standby HA is strictly opt-in",
    ),
)
