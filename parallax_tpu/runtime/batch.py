"""Host-side batch assembly: BatchPlan -> bucketed device inputs.

This is the TPU-specific piece the reference never needed (SURVEY.md §7
"Dynamic shapes vs XLA"): continuous batching produces ragged batches every
step; to avoid recompiles the token count and sequence count are padded up
to a small lattice of power-of-two buckets, so the engine runs a handful of
compiled programs regardless of load. Occupancy within a bucket is dynamic
(``num_seqs``), costing no recompile.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from parallax_tpu.models.base import BatchInputs
from parallax_tpu.runtime.scheduler import BatchPlan


def next_bucket(n: int, buckets: list[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} exceeds largest bucket {buckets[-1]}")


def default_buckets(max_value: int, floor: int = 8) -> list[int]:
    out, b = [], floor
    while b < max_value:
        out.append(b)
        b *= 2
    out.append(max_value)
    return out


@dataclasses.dataclass
class BucketSpec:
    """The compile lattice: (token bucket, seq bucket, fixed pages/seq)."""

    token_buckets: list[int]
    seq_buckets: list[int]
    pages_per_seq: int

    @classmethod
    def build(
        cls, max_num_tokens: int, max_batch_size: int, max_model_len: int,
        page_size: int,
    ) -> "BucketSpec":
        # Decode batches bucket their token count on the SEQ lattice
        # (t == s, the decode-kernel dispatch contract). A
        # non-power-of-two max_batch_size adds an exact-size tail bucket
        # that the single-step AND every K-step decode program each
        # compile separately — merge it into the next power of two when
        # the padding is small (<= 25% dead rows at saturation). Past
        # that, the permanent per-step compute on padded rows costs more
        # than the one-time extra compile, so the exact tail stays.
        seq = default_buckets(max_batch_size)
        tail = seq[-1]
        if tail & (tail - 1):
            pow2 = 1 << (tail - 1).bit_length()
            if pow2 <= tail + tail // 4:
                seq[-1] = pow2
        return cls(
            token_buckets=default_buckets(max_num_tokens),
            seq_buckets=seq,
            pages_per_seq=(max_model_len + page_size - 1) // page_size,
        )


def assemble(
    plan: BatchPlan,
    spec: BucketSpec,
    page_size: int,
    hidden_states: np.ndarray | None = None,
    with_dense_map: bool = False,
    pad_position: int = 0,
    decode_only: bool = False,
    gather_all_logits: bool = False,
    decode_fused: bool = False,
    prefill_fused: bool = False,
    eva=None,
    eva_tables: bool = False,
) -> BatchInputs:
    """Build fixed-shape arrays from a ragged plan.

    ``eva`` (the model's ``EvaConfig``): rows are laid out as their
    virtual sequences (:class:`_EvaFields`); ``eva_tables`` adds what a
    decode window needs to cross a window boundary on the device.

    ``hidden_states`` replaces token ids on non-first stages; rows must be
    ordered exactly as the plan's segments (already padded to the token
    bucket by the caller, or padded here). The SP path passes
    ``pad_position=-1`` so ring attention masks padding rows as keys.
    """
    seqs = plan.seqs
    t_real = plan.total_new_tokens
    s_real = len(seqs)
    s = next_bucket(max(s_real, 1), spec.seq_buckets)
    if decode_only:
        # One token per sequence: bucket tokens on the SEQ lattice so
        # t == s always holds (the decode-kernel dispatch contract), even
        # when the two lattices diverge (non-power-of-two max_batch_size).
        t = s
    else:
        t = next_bucket(max(t_real, 1), spec.token_buckets)

    token_ids = np.zeros((t,), np.int32)
    positions = np.full((t,), pad_position, np.int32)
    slot_mapping = np.full((t,), -1, np.int32)
    kv_lens = np.zeros((s,), np.int32)
    page_indices = np.zeros((s, spec.pages_per_seq), np.int32)
    cu_q_lens = np.zeros((s + 1,), np.int32)
    logits_indices = np.zeros((s,), np.int32)

    eva_fields = None
    if eva is not None:
        eva_fields = _EvaFields(eva, page_size, s, t, spec.pages_per_seq,
                                decode_only, eva_tables)

    row = 0
    for i, seg in enumerate(seqs):
        n = seg.num_new_tokens
        start_pos = seg.context_len - n
        req = seg.request
        token_ids[row : row + n] = seg.token_ids
        positions[row : row + n] = np.arange(start_pos, seg.context_len)
        pages = np.asarray(req.page_ids, np.int32)
        pos = np.arange(start_pos, seg.context_len)
        shift = 0
        if eva_fields is not None:
            # The row's virtual sequence: its open window's tokens sit
            # behind the visible summaries of the windows before it.
            shift = eva_fields.row(i, req, pos, pages)
            pos = pos - shift
        slot_mapping[row : row + n] = pages[pos // page_size] * page_size + pos % page_size
        kv_lens[i] = seg.context_len - shift
        page_indices[i, : len(pages)] = pages
        cu_q_lens[i + 1] = cu_q_lens[i] + n
        logits_indices[i] = row + n - 1
        row += n
    cu_q_lens[s_real + 1 :] = cu_q_lens[s_real]
    if gather_all_logits:
        # Speculative verification needs logits at EVERY fed position, not
        # just each sequence's last token; its length defines the logits
        # row count, which nothing ties to the seq bucket.
        logits_indices = np.arange(t, dtype=np.int32)

    state_slots = dense_map = q_lens_arr = None
    if with_dense_map:
        # Hybrid models: densify ragged rows to [S, maxq] per-seq steps; maxq
        # is its own bucket dimension so decode batches compile with maxq=1
        # (the recurrence scan vanishes).
        maxq_real = max((seg.num_new_tokens for seg in seqs), default=1)
        maxq = next_bucket(maxq_real, [1] + spec.token_buckets)
        dense_map = np.full((s, maxq), t, np.int32)  # t = OOB padding row
        q_lens_np = np.zeros((s,), np.int32)
        slots = np.zeros((s,), np.int32)
        reset = np.zeros((s,), np.int32)
        for i, seg in enumerate(seqs):
            n = seg.num_new_tokens
            dense_map[i, :n] = np.arange(cu_q_lens[i], cu_q_lens[i] + n)
            q_lens_np[i] = n
            slots[i] = getattr(seg.request, "state_slot", 0)
            # First chunk of the request: its reused slot holds a previous
            # request's final state and must be zeroed.
            reset[i] = int(seg.context_len - n == 0)
        state_slots = jnp.asarray(slots)
        q_lens_arr = jnp.asarray(q_lens_np)
        dense_map = jnp.asarray(dense_map)
        reset_arr = jnp.asarray(reset)

    return BatchInputs(
        decode_only=decode_only,
        # Fused decode program (static jit-key flag): attention layers
        # append this step's K/V inside the Pallas kernel, reading the
        # page-table/ragged-lens layout assembled above directly.
        decode_fused=decode_fused and decode_only,
        # Fused prefill program: the multi-token twin — attention layers
        # run the ragged Pallas prefill kernel with the in-kernel append.
        # Chunk-skipped prefixes are already encoded in the layout above
        # (query rows offset past cached_len, kv_lens/page_indices
        # spanning the full cached context), so the kernel needs no
        # extra signal.
        prefill_fused=prefill_fused and not decode_only,
        state_slots=state_slots,
        dense_map=dense_map,
        q_lens=q_lens_arr,
        reset_state=None if not with_dense_map else reset_arr,
        token_ids=jnp.asarray(token_ids),
        hidden_states=(
            None if hidden_states is None
            else jnp.asarray(_pad_rows(hidden_states, t))
        ),
        positions=jnp.asarray(positions),
        kv_lens=jnp.asarray(kv_lens),
        page_indices=jnp.asarray(page_indices),
        cu_q_lens=jnp.asarray(cu_q_lens),
        num_seqs=jnp.asarray([s_real], jnp.int32),
        slot_mapping=jnp.asarray(slot_mapping),
        logits_indices=jnp.asarray(logits_indices),
        **(eva_fields.arrays() if eva_fields is not None else {}),
    )


class _EvaFields:
    """The EVA side of one assembled batch (``ModelConfig.eva``,
    ``cache_manager.EvaCacheManager``): per completed chunk the slot of
    its first token and the slot its summary goes to, and for a decode
    batch what a K-step window needs to cross a window boundary on the
    device (the tables after the rollover; the engine's scan switches to
    them at the step whose position enters the next window)."""

    def __init__(self, eva, page_size: int, s: int, t: int,
                 pages_per_seq: int, decode_only: bool, tables: bool):
        self.eva, self.page = eva, page_size
        # A row of n tokens completes at most n // C + 1 chunks.
        lanes = s if decode_only else t // eva.chunk_size + s
        self.src = np.zeros((lanes,), np.int32)
        self.dst = np.full((lanes,), -1, np.int32)
        self.lane = 0
        self.window = None
        if tables:
            pp = eva.summaries_per_window // page_size
            self.window = dict(
                ctx=np.zeros((s,), np.int32),
                w0=np.zeros((s,), np.int32),
                pend=np.zeros((s, pp), np.int32),
                next_pend=np.zeros((s, pp), np.int32),
                next_pages=np.zeros((s, pages_per_seq), np.int32),
            )

    def row(self, i: int, req, pos: np.ndarray, pages: np.ndarray) -> int:
        """Register row ``i`` (absolute positions ``pos``, virtual page
        table ``pages``); returns ``absolute - virtual`` position."""
        eva, page = self.eva, self.page
        w = req.eva_window
        if pos[0] // eva.window_size != w or pos[-1] // eva.window_size != w:
            raise ValueError(
                f"{req.request_id}: positions {pos[0]}..{pos[-1]} leave "
                f"the open EVA window {w}"
            )
        shift = w * (eva.window_size - eva.summaries_per_window)
        pend = np.asarray(req.eva_pending, np.int32)
        ends = pos[(pos + 1) % eva.chunk_size == 0]     # chunks completed
        first = ends - (eva.chunk_size - 1) - shift
        chunk = (ends % eva.window_size) // eva.chunk_size
        lanes = slice(self.lane, self.lane + len(ends))
        self.src[lanes] = pages[first // page] * page + first % page
        self.dst[lanes] = pend[chunk // page] * page + chunk % page
        self.lane += len(ends)
        if self.window is not None:
            from parallax_tpu.runtime.cache_manager import eva_rolled_table

            win = self.window
            nxt = (
                eva_rolled_table(req, len(req.eva_pending))
                if req.eva_next_pending else req.page_ids
            )
            win["ctx"][i] = pos[-1] + 1
            win["w0"][i] = w
            win["pend"][i] = pend
            win["next_pend"][i] = req.eva_next_pending or req.eva_pending
            win["next_pages"][i, : len(nxt)] = nxt
        return shift

    def arrays(self) -> dict:
        return dict(
            eva_src=jnp.asarray(self.src), eva_dst=jnp.asarray(self.dst),
            eva_window=(
                None if self.window is None
                else {k: jnp.asarray(v) for k, v in self.window.items()}
            ),
        )


def _pad_rows(x: np.ndarray, t: int) -> np.ndarray:
    if x.shape[0] == t:
        return x
    pad = np.zeros((t - x.shape[0], x.shape[1]), x.dtype)
    return np.concatenate([x, pad], axis=0)


@jax.jit
def _gather_feed(token_ids, last_tokens, slots):
    fed = last_tokens[jnp.clip(slots, 0, last_tokens.shape[0] - 1)]
    return jnp.where(slots >= 0, fed, token_ids)


def widen_for_spec_window(
    inputs: BatchInputs, width: int, num_real_seqs: int
) -> BatchInputs:
    """Re-shape a decode-only [S]-row template into the speculative
    window's ragged multi-token layout: every bucket row owns ``width``
    contiguous token slots (``t = S * width``), real rows' spans are
    registered in ``cu_q_lens`` exactly as :func:`assemble` would for a
    ``width``-token segment, and logits are gathered at EVERY fed
    position (the window verifies all of them). The per-iteration
    fields — token ids, positions, slot mapping, kv lens — are
    placeholders the jitted window rebuilds from its scan carry each
    step, so the static shapes here are the whole contract.

    The widened batch is a multi-token ragged forward: ``decode_only``
    (and with it the decode-fused Pallas kernels, which are single-token
    by construction) turns off for the window's forward.
    """
    s = int(inputs.kv_lens.shape[0])
    t = s * width
    n = min(num_real_seqs, s)
    cu = np.zeros((s + 1,), np.int32)
    cu[1 : n + 1] = (np.arange(n, dtype=np.int32) + 1) * width
    cu[n + 1 :] = cu[n]
    return dataclasses.replace(
        inputs,
        decode_only=False,
        decode_fused=False,
        prefill_fused=False,
        token_ids=jnp.zeros((t,), jnp.int32),
        positions=jnp.zeros((t,), jnp.int32),
        slot_mapping=jnp.full((t,), -1, jnp.int32),
        cu_q_lens=jnp.asarray(cu),
        logits_indices=jnp.arange(t, dtype=jnp.int32),
    )


def gather_device_feed(host_tokens, last_tokens, feed_slots):
    """Per-ROW twin of :func:`substitute_device_tokens` for the
    speculative window's [S]-shaped feed carry: rows with a
    non-negative slot gather their first window token from the
    device-resident last-token array; host rows keep their committed
    token id. Enqueued between the in-flight step's sampler and the
    window's first forward — no host round trip."""
    return _gather_feed(host_tokens, last_tokens, feed_slots)


def substitute_device_tokens(
    inputs: BatchInputs, last_tokens, feed_slots
) -> BatchInputs:
    """Overlapped decode's on-device token feedback: replace the
    placeholder token ids of device-fed rows with a gather from the
    engine's device-resident last-token array.

    ``feed_slots`` is i32[T] with the row's token slot at its first token
    position and -1 everywhere else (host rows keep their assembled ids).
    The gather is a tiny jitted op enqueued between the sampler that
    produced ``last_tokens`` and the forward that consumes the result, so
    the sampled token never round-trips through the host.
    """
    token_ids = _gather_feed(inputs.token_ids, last_tokens, feed_slots)
    return dataclasses.replace(inputs, token_ids=token_ids)
