"""Local continuous-batching scheduler for one pipeline stage.

Capability parity: reference ``src/parallax/server/scheduler.py:42-392``
(two-phase admit/form_batch, chunked prefill token accounting, finish
checks, timeouts). TPU-specific addition: the formed batch is described by a
:class:`BatchPlan` of ragged segments that the executor pads onto a bucket
lattice — batching decisions remain fully host-side and O(batch).
"""

from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict

from parallax_tpu.analysis import conformance
from parallax_tpu.runtime.cache_manager import CacheManager
from parallax_tpu.runtime.request import Request, RequestStatus
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)


@dataclasses.dataclass
class ScheduledSeq:
    """One ragged segment of the step batch."""

    request: Request
    num_new_tokens: int          # query tokens this step
    token_ids: list[int]         # the new tokens (head node fills these)
    context_len: int             # total KV length after this step
    is_last_prefill_chunk: bool = True
    # Overlapped decode: this row's fed token is the one an in-flight
    # step sampled — it lives only in the engine's device-resident
    # last-token array; ``token_ids`` holds a placeholder the engine
    # replaces with an on-device gather (batch.substitute_device_tokens).
    device_token: bool = False

    @property
    def pending_fed(self) -> int:
        """Tokens of this row that an in-flight step has sampled and the
        host has not committed yet (one for a row fed from the last-token
        array, up to a whole window for a row fed from a window's carry):
        budgets, the min_new_tokens gate and the seeded step origin count
        them. 0 once they are committed (resolve-time packing)."""
        if not self.device_token:
            return 0
        return max(0, self.context_len - self.request.total_len)

    @property
    def budget_left(self) -> int:
        """Tokens the row may still produce from this step on: its
        ``max_new_tokens`` less what it generated, committed or pending.
        <= 0 for a row whose budget the step in flight exhausts."""
        req = self.request
        return (req.sampling_params.max_new_tokens - req.num_generated
                - self.pending_fed)


@dataclasses.dataclass
class BatchPlan:
    """Everything the executor needs to build device inputs for one step."""

    seqs: list[ScheduledSeq]
    # The single LoRA adapter every seq in this batch uses (None = base);
    # one adapter per dispatch keeps the in-graph slot selection scalar.
    lora_id: str | None = None
    # Mixed-adapter DECODE batch: every row selects its own adapter via a
    # per-token slot vector (ops/lora.py mixed form). Lifts the
    # one-adapter-per-step ITL cost under many concurrent tenants — with
    # N active adapters each tenant would otherwise decode on ~1/N of
    # steps.
    mixed_lora: bool = False

    @property
    def total_new_tokens(self) -> int:
        return sum(s.num_new_tokens for s in self.seqs)

    @property
    def is_empty(self) -> bool:
        return not self.seqs

    @property
    def has_prefill(self) -> bool:
        return any(s.num_new_tokens > 1 or not s.request.is_prefill_done
                   for s in self.seqs)


class Scheduler:
    """Continuous batching over a wait queue and a running set."""

    def __init__(
        self,
        cache_manager: CacheManager,
        max_batch_size: int = 64,
        max_num_tokens_per_batch: int = 2048,
        prefill_chunk_size: int = 1024,
        max_queue_size: int = 1024,
        request_timeout_s: float = 600.0,
        is_first_stage: bool = True,
        snapshot_page_align: int | None = None,
        stage_name: str = "stage",
        qos: "QoSPolicy | None" = None,
    ):
        # Observability: the stage label this scheduler's flight-recorder
        # events and trace spans carry (preempt / swap-in / kv_oom).
        self.stage_name = stage_name
        # Conformance ownership token (analysis/conformance.py): unique
        # per scheduler for the sanitizer's one-head-per-rid check —
        # never id(self), which CPython reuses after GC.
        self.conf_token = conformance.new_token()
        self.cache = cache_manager
        # EVA models (``cache_manager.EvaCacheManager``): a prefill chunk
        # ends on a window boundary and a step that starts past one
        # rolls the window over first; None for every other model.
        self._eva_window = getattr(cache_manager, "window", None)
        # Set where ``_settle_window_rows`` ended a hand-over: why.
        self.window_break: str | None = None
        self.max_batch_size = max_batch_size
        self.max_num_tokens_per_batch = max_num_tokens_per_batch
        self.prefill_chunk_size = prefill_chunk_size
        self.max_queue_size = max_queue_size
        self.request_timeout_s = request_timeout_s
        self.is_first_stage = is_first_stage
        # Hybrid prefix snapshots: split the final prefill chunk at the
        # last boundary aligned to this many tokens, so the engine can
        # snapshot linear state covering (almost) the whole prompt.
        self.snapshot_page_align = snapshot_page_align
        self.wait_queue: OrderedDict[str, Request] = OrderedDict()
        self.running: OrderedDict[str, Request] = OrderedDict()
        # Monotonic count of wait-queue departures (admissions, resumes,
        # finished-while-parked routing) — the stall watchdog's progress
        # signal for the admission component: a non-empty queue whose
        # counter stops moving is a wedged admission path.
        self.admitted_total = 0
        # Round-robin cursor over adapter groups (see form_batch).
        self._lora_cursor = 0
        # Rotation cursor for budget-capped mixed decode batches.
        self._decode_cursor = 0
        # Multi-tenant QoS policy (parallax_tpu/qos, docs/qos.md):
        # deadline-aware admission/ordering + shed/park enforcement.
        # None (the default, --qos off) keeps every path below on the
        # pre-QoS arrival-order behavior — each hook is one attribute
        # check, so off-mode per-step cost is zero and streams are
        # bit-identical.
        self.qos = qos

    # -- intake -----------------------------------------------------------

    def enqueue(self, request: Request) -> bool:
        if len(self.wait_queue) >= self.max_queue_size:
            return False
        self.wait_queue[request.request_id] = request
        return True

    def num_requests(self) -> int:
        return len(self.wait_queue) + len(self.running)

    # -- admission (phase 1) ---------------------------------------------

    def admit_requests(self) -> None:
        """Move wait-queue requests into the running set with KV allocated.

        Reference: ``admit_requests`` (scheduler.py:251-312) — FCFS, stops at
        the first request that does not fit to preserve ordering fairness.
        With a QoS policy attached the iteration order becomes
        earliest-deadline-first (with the starvation guard) and the shed
        gate can hold sheddable classes back; the per-request admission
        mechanics (``_admit_one``) are shared so the two modes can never
        drift.
        """
        if self.qos is not None:
            self._admit_requests_qos()
            return
        while self.wait_queue and len(self.running) < self.max_batch_size:
            rid, req = next(iter(self.wait_queue.items()))
            if not self._admit_one(rid, req):
                break

    def _admit_one(self, rid: str, req: Request) -> bool:
        """Try to admit one wait-queue request. Returns False when
        admission must STOP (the request blocks: migrating, or capacity
        ran out) — later queue entries must not leapfrog it, whatever
        ordering discipline chose it."""
        if req.migrating:
            # About to be checkpointed away: admitting (or swapping
            # it back in) would race the extraction. The park lands
            # within a step or two; admission resumes then.
            return False
        if req.status.is_finished:
            # Aborted while parked (timeout / client cancel): route it
            # through the running set so the normal finish collection
            # releases its state.
            del self.wait_queue[rid]
            self.admitted_total += 1
            self.running[rid] = req
            return True
        if req.status is RequestStatus.PREEMPTED:
            # Preempted-to-host: swap the KV image back in instead of
            # re-allocating a prompt. FCFS discipline is unchanged —
            # a resume that does not fit blocks admission like any
            # other head-of-queue request.
            t0 = time.perf_counter()
            if not self.cache.resume_from_host(req):
                return False
            del self.wait_queue[rid]
            self.admitted_total += 1
            # A mid-prefill park resumes into PREFILLING: the chunk loop
            # picks up at num_computed_tokens (the restored KV image
            # covers exactly that span). Completed prefills resume
            # straight into decode as before.
            req.set_status(
                RequestStatus.DECODING if req.is_prefill_done
                else RequestStatus.PREFILLING,
                "swap-in",
            )
            self.running[rid] = req
            self._obs_event("swap_in", req, dur=time.perf_counter() - t0)
            return True
        if not self.cache.allocate_for_prompt(req):
            return False
        del self.wait_queue[rid]
        self.admitted_total += 1
        head_cached = getattr(req, "mirror_head_cached", None)
        if head_cached is not None:
            # Mirror of a head-side prefix hit: the head only forwards
            # hidden rows from ``head_cached`` on. A SHORTER local
            # match means this stage would need rows that never arrive
            # — abort loudly rather than stall or serve garbage
            # (asymmetric eviction between stages; rare). A LONGER
            # local match is clamped down: the overlap rows recompute
            # into the shared pages deterministically (same inputs,
            # same values).
            if req.num_computed_tokens < head_cached:
                logger.warning(
                    "%s: downstream prefix-cache miss (head skipped "
                    "%d, local match %d) — aborting", rid,
                    head_cached, req.num_computed_tokens,
                )
                req.abort("downstream_prefix_cache_miss")
                self.running[rid] = req   # collected + released next step
                return True
            req.num_computed_tokens = head_cached
        req.set_status(RequestStatus.PREFILLING, "admission")
        self.running[rid] = req
        return True

    def _admit_requests_qos(self) -> None:
        """QoS admission: EDF order with the starvation guard, the shed
        gate holding sheddable classes while the admission controller
        sheds, and park enforcement over the running set. Mechanics per
        request are ``_admit_one`` — identical to FCFS mode."""
        pol = self.qos
        now = time.monotonic()
        pol.maybe_tick(now, self)
        self._qos_enforce(pol)
        if pol.controller.active:
            # Shed-held accounting covers EVERY gated request, not just
            # the ones the capacity-bounded loop below happens to
            # visit (count_shed is once-per-request).
            for req in self.wait_queue.values():
                if not req.status.is_finished and pol.blocks_admission(req):
                    pol.count_shed(req)
        for rid, req in pol.admit_order(self.wait_queue, now):
            if len(self.running) >= self.max_batch_size:
                break
            if self.wait_queue.get(rid) is not req:
                continue   # admitted/parked by an earlier iteration
            if not req.status.is_finished and pol.blocks_admission(req):
                # Held, not dropped: the request stays queued (already
                # counted by the full-queue sweep above) and resumes
                # through this same gate when the shed lifts.
                continue
            was_finished = req.status.is_finished
            if not self._admit_one(rid, req):
                break
            if (
                not was_finished
                and not req.status.is_finished
                and rid in self.running
            ):
                pol.on_admit(req, now)

    def _qos_enforce(self, pol) -> None:
        """Shed enforcement over the RUNNING set: park sheddable-class
        decodes to the host tier (the PR 2 PREEMPTED path — they resume
        bit-identically when the shed releases; enforcement never
        aborts). Uses the same safety tests as memory-pressure
        preemption: only committed/device-fed decode rows park, never
        mirrors, in-flight rows, state-slot holders or migrating
        requests."""
        if not pol.controller.active:
            return
        if self.cache.host_tier is None:
            # No tier: enforcement can only hold admissions.
            pol.warn_no_tier_once()
            return
        for req in list(self.running.values()):
            if (
                not pol.parkable(req)
                or req.migrating
                or req.status is not RequestStatus.DECODING
                or not (req.ready_for_step or req.device_feed_ready)
                or getattr(req, "is_mirror", False)
                or getattr(req, "state_slot", None) is not None
            ):
                continue
            if not self.cache.preempt_to_host(req):
                continue   # host tier full: the request keeps running
            self._park(req)
            pol.count_park(req)

    def take_sp_prefill(self, threshold: int) -> BatchPlan | None:
        """Pick one whole long prompt for a sequence-parallel prefill step.

        Eligible: a PREFILLING request with nothing computed yet (ring
        attention covers new-token attention only, so no cached prefix and
        no earlier chunks) and a prompt of at least ``threshold`` tokens.
        The request is scheduled alone, unchunked. (No check_timeouts here:
        the fall-through form_batch covers it, and the SP probe runs every
        engine step — the O(requests) timeout scan must not run twice.)
        """
        self.admit_requests()
        for req in list(self.running.values()):
            if req.status is not RequestStatus.PREFILLING or req.migrating:
                continue
            if req.lora_id is not None:
                # The ring-attention SP step does not carry adapter
                # weights; LoRA prompts take the chunked-prefill path.
                continue
            n = req.num_prompt_tokens
            if req.num_computed_tokens != 0 or n < threshold:
                continue
            if not self._ensure_capacity_or_preempt(req, n):
                continue
            return BatchPlan([
                ScheduledSeq(
                    request=req,
                    num_new_tokens=n,
                    token_ids=list(req.prompt_ids),
                    context_len=n,
                    is_last_prefill_chunk=True,
                )
            ])
        return None

    # -- batch formation (phase 2) ---------------------------------------

    def form_batch(self) -> BatchPlan:
        """Prefill-first batch under token and batch-size budgets.

        Reference: ``form_batch`` (scheduler.py:332-392). Chunked prefill:
        a long prompt contributes at most ``prefill_chunk_size`` tokens per
        step and keeps its place in the running set between chunks.
        """
        self.check_timeouts()
        self.admit_requests()
        self._settle_window_rows()

        # One LoRA adapter per batch (in-graph slot selection is scalar).
        # The batch's adapter rotates round-robin over the DISTINCT
        # adapters with schedulable work — without rotation the first
        # running request's tenant head-of-line-blocks every other tenant
        # until it finishes. A chosen group can still schedule nothing
        # (e.g. its only request OOM-aborts at capacity check), so fall
        # through to the next group rather than idling the step.
        groups: list = []
        for req in self.running.values():
            schedulable = (
                req.status is RequestStatus.PREFILLING
                and req.remaining_prompt_tokens() > 0
            ) or (
                req.status is RequestStatus.DECODING
                and (req.ready_for_step or req.device_feed_ready
                     or req.window_pending)
            )
            if schedulable and req.lora_id not in groups:
                groups.append(req.lora_id)
        if not groups:
            return BatchPlan([])
        if len(groups) > 1 and not any(
            req.status is RequestStatus.PREFILLING
            and req.remaining_prompt_tokens() > 0
            for req in self.running.values()
        ):
            # Pure decode with several tenants active: serve EVERY tenant
            # this step with a mixed-adapter batch (per-row slot vectors)
            # instead of rotating — per-tenant ITL stops scaling with the
            # number of active adapters. Prefill keeps adapter grouping
            # (chunk compute dominates; rotation is fine there).
            seqs = self._fill_decode(batch_lora=None, any_adapter=True)
            if seqs:
                lids = {s.request.lora_id for s in seqs}
                if len(lids) > 1:
                    return BatchPlan(seqs, mixed_lora=True)
                # Capacity aborts collapsed it to one tenant after all.
                return BatchPlan(seqs, lora_id=next(iter(lids)))
        start = self._lora_cursor % len(groups)
        if len(groups) > 1:
            self._lora_cursor += 1
        for off in range(len(groups)):
            batch_lora = groups[(start + off) % len(groups)]
            seqs = self._fill_batch(batch_lora)
            if seqs:
                return BatchPlan(seqs, lora_id=batch_lora)
        return BatchPlan([])

    def _settle_window_rows(self) -> None:
        """Rows of an in-flight decode window (``window_pending``) may be
        planned one window ahead only as that same batch again: every
        running request is such a row and still decoding, and nothing
        waits to be admitted. An arrival, a prefill chunk, a finished,
        aborted or migrating row ends the hand-over — the marks are
        dropped, the rows stay un-schedulable until the window's resolve
        commits them, and the plan is formed as without a window in
        flight."""
        running = self.running.values()
        if not any(req.window_pending for req in running):
            return
        if not self.wait_queue and all(
            req.window_pending
            and req.status is RequestStatus.DECODING
            and not req.migrating
            for req in running
        ):
            return
        # Why the hand-over ends, for whoever counts the window it
        # costs (``StageEngine._window_ahead`` takes it).
        self.window_break = "row_ended" if any(
            req.window_pending
            and (req.migrating or req.status is not RequestStatus.DECODING)
            for req in running
        ) else "row_joined"
        for req in running:
            req.window_pending = 0

    def _fill_batch(self, batch_lora: str | None) -> list[ScheduledSeq]:
        """The prefill-first loops for one adapter group."""
        seqs: list[ScheduledSeq] = []
        token_budget = self.max_num_tokens_per_batch

        # Prefill chunks first (including re-chunked long prompts).
        # Snapshot: preemption-to-host can move a running request to the
        # wait queue mid-iteration. With QoS on, earliest deadline
        # first (guard=False: the starvation guard is a WAIT-QUEUE
        # notion — see QoSPolicy.order_key): under a token-budget
        # squeeze the urgent prompt's chunk ships this step, not the
        # flood's.
        running = list(self.running.values())
        if self.qos is not None:
            now = time.monotonic()
            running.sort(
                key=lambda r: self.qos.order_key(r, now, guard=False)
            )
        for req in running:
            if len(seqs) >= self.max_batch_size or token_budget <= 0:
                break
            if req.status is not RequestStatus.PREFILLING or req.migrating:
                continue
            if req.lora_id != batch_lora:
                continue
            # Prefix-aware chunk skipping: before this request's FIRST
            # chunk ships, re-consult the radix tree — a donor that
            # released after this request was admitted may now cover far
            # more of the prompt than the admission-time match did. Only
            # while nothing has been computed past the cached prefix
            # (num_computed == num_cached): once a chunk dispatched, the
            # covered span is no longer a pure prefix swap. The guard is
            # race-free because on_batch_computed advances
            # num_computed_tokens at dispatch time, not completion.
            if req.num_computed_tokens == req.num_cached_tokens:
                if self.cache.extend_prefix_match(req):
                    # parallax_prefill_tokens_skipped_total is collected
                    # pull-style from CacheStats (same shape as the
                    # preemption counters) — only the flight/trace event
                    # is emitted here.
                    self._obs_event("chunk_skip", req)
            remaining = req.remaining_prompt_tokens()
            if remaining <= 0:
                continue
            n = min(remaining, self.prefill_chunk_size, token_budget)
            if n < remaining and n < self.cache.page_size:
                break  # not worth a degenerate chunk; wait for budget
            start = req.num_computed_tokens
            if self.snapshot_page_align and start + n >= req.num_prompt_tokens:
                # End the penultimate chunk exactly at the last USABLE
                # aligned prompt boundary (the linear-state snapshot
                # point); the ragged remainder becomes one more small
                # chunk. "(prompt_len - 1)": a prefix hit always leaves
                # >= 1 token to recompute, so a snapshot at the full
                # (aligned) prompt length could never be matched.
                a = ((req.num_prompt_tokens - 1) // self.snapshot_page_align
                     ) * self.snapshot_page_align
                if start < a < start + n:
                    n = a - start
            if self._eva_window:
                # The chunk's queries read [visible summaries] ++ [own
                # window so far]: it may not reach into the next window.
                n = min(n, self._eva_window - start % self._eva_window)
                self.cache.roll_window(req, start)
            # Mirror requests grow their prompt incrementally (chunks arrive
            # over the wire), so page capacity may lag the prompt length.
            if not self._ensure_capacity_or_preempt(req, start + n):
                continue
            seqs.append(
                ScheduledSeq(
                    request=req,
                    num_new_tokens=n,
                    token_ids=req.prompt_ids[start : start + n],
                    context_len=start + n,
                    is_last_prefill_chunk=(start + n >= req.num_prompt_tokens),
                )
            )
            token_budget -= n

        # Then ready decodes.
        seqs.extend(self._fill_decode(
            batch_lora,
            max_seqs=self.max_batch_size - len(seqs),
            token_budget=token_budget,
        ))
        return seqs

    def _fill_decode(
        self,
        batch_lora: str | None,
        any_adapter: bool = False,
        max_seqs: int | None = None,
        token_budget: int | None = None,
    ) -> list[ScheduledSeq]:
        """Ready decode rows — one adapter group, or every tenant at once
        (``any_adapter``, mixed-adapter batches)."""
        if max_seqs is None:
            max_seqs = self.max_batch_size
        if token_budget is None:
            token_budget = self.max_num_tokens_per_batch
        candidates = [
            req for req in self.running.values()
            if req.status is RequestStatus.DECODING
            and not req.migrating
            and (req.ready_for_step or req.device_feed_ready
                 or req.window_pending)
            and (any_adapter or req.lora_id == batch_lora)
        ]
        if self.qos is not None and candidates:
            # EDF decode-batch formation: when the batch/token budget
            # caps the step, the rows with the least deadline slack
            # decode first. guard=False — running rows are being
            # served, so the wait-queue starvation guard must not put
            # every old batch row ahead of fresh interactive deadlines;
            # batch rows overtake naturally as their own slack decays.
            # Replaces the rotation fairness below.
            now = time.monotonic()
            candidates.sort(
                key=lambda r: self.qos.order_key(r, now, guard=False)
            )
        elif any_adapter and candidates:
            # The mixed path returns before form_batch's group rotation,
            # so fairness must live here: when the budget caps the batch,
            # a fixed iteration order would serve the same head-of-line
            # rows every step and starve the rest. Rotate the start.
            start = self._decode_cursor % len(candidates)
            candidates = candidates[start:] + candidates[:start]
        seqs: list[ScheduledSeq] = []
        scheduled: set[str] = set()
        for req in candidates:
            if len(seqs) >= max_seqs or token_budget <= 0:
                break
            if req.status is not RequestStatus.DECODING:
                continue   # preempted by an earlier row in this pass
            # A device-fed row's next token was sampled by the in-flight
            # step and lives only on device: it occupies one more context
            # slot than the host-committed total.
            # A row of the window in flight: that window holds its next
            # tokens (``window_pending`` of them, none committed yet).
            ahead = req.window_pending
            fed = ahead > 0 or (
                req.device_feed_ready and not req.ready_for_step
            )
            ctx = req.total_len + (ahead or int(fed))
            if self._eva_window:
                self.cache.roll_window(req, ctx - 1)
            if ahead:
                # The window reserved its pages up to here: nothing to
                # evict or preempt for.
                req.window_pending = 0
                if not self.cache.ensure_capacity(req, ctx):
                    continue
            elif not self._ensure_capacity_or_preempt(
                req, ctx, allow_self=True, exclude_scheduled=scheduled,
            ):
                continue
            scheduled.add(req.request_id)
            seqs.append(
                ScheduledSeq(
                    request=req,
                    num_new_tokens=1,
                    token_ids=[0] if fed else [req.all_token_ids[-1]],
                    context_len=ctx,
                    device_token=fed,
                )
            )
            if fed:
                req.device_feed_ready = False
            token_budget -= 1
        if any_adapter:
            self._decode_cursor += len(seqs)
        return seqs

    # -- multi-step decode planning ---------------------------------------

    def plan_decode_window(
        self, plan: BatchPlan, k: int, max_windows: int,
        max_model_len: int, spec: int = 0,
    ) -> int:
        """``decode_lookahead=K`` planning: pre-allocate KV pages for a
        chain of up to ``max_windows`` k-token decode windows over
        ``plan``'s rows, all-or-nothing.

        Returns the number of windows (>= 1) whose pages are guaranteed
        RIGHT NOW, or 0 when the allocator (or host-tier pressure behind
        it) cannot guarantee even one window — the caller then falls
        back to single-step decode, whose normal path owns preemption
        and kv_oom decisions. Lookahead planning never preempts and the
        chain is sized against pages free right now, so a failed probe
        leaves no speculative allocations or evictions behind; only the
        final single-window ``ensure_capacity`` may evict from the
        prefix tree, exactly as a single-step +1 probe would.

        ``spec > 0`` plans a SPECULATIVE window: every scan iteration
        feeds ``1 + spec`` tokens per row (the current feed plus the
        staged proposals), so the worst case — every proposal accepted
        everywhere — commits ``k * (1 + spec)`` tokens per window and
        the reservation must cover it. The engine downshifts gracefully
        on a 0 here: first to a plain window (``spec=0``), then to
        single-step.

        The chain is clamped to every row's context room below
        ``max_model_len`` and to the largest remaining generation budget
        (windows past every row's ``max_new_tokens`` are pure waste —
        under speculation a window still commits at least ``k`` tokens
        per live row, so the plain-window clamp stays conservative);
        device-fed rows count their pending uncommitted tokens (one,
        or a window's worth for rows of the window in flight).
        """
        k_eff = k * (1 + max(0, spec))
        m = max(1, max_windows)
        want = 0
        live = []
        for seg in plan.seqs:
            left = seg.budget_left
            if left <= 0:
                # The window in flight exhausts this row's budget: it
                # rides this one frozen and needs no room and no pages.
                continue
            room = (max_model_len - seg.context_len) // k_eff
            if self._eva_window:
                # One window boundary per dispatch at most: the scan
                # switches page tables once (engine ``_eva_step``).
                room = min(room, self._eva_window // k_eff)
            if room < 1:
                return 0
            m = min(m, room)
            want = max(want, left)
            live.append(seg)
        m = min(m, max(1, -(-want // k)))

        def _extra_pages(mm: int) -> int:
            return sum(
                self.cache.extra_pages(
                    seg.request, seg.context_len + mm * k_eff
                )
                for seg in live
            )

        while m > 1 and _extra_pages(m) > self.cache.num_free_pages:
            m -= 1
        if not all(
            self.cache.ensure_capacity(
                seg.request, seg.context_len + m * k_eff
            )
            for seg in live
        ):
            return 0
        return m

    # -- step feedback ----------------------------------------------------

    def on_batch_computed(self, plan: BatchPlan) -> None:
        """Advance prefill progress; mark decodes in-flight.

        Decode requests wait for the pipeline ring to deliver the sampled
        token (``ready_for_step`` gating, reference scheduler.py:192-249).
        """
        for s in plan.seqs:
            req = s.request
            if req.status is RequestStatus.PREFILLING:
                req.num_computed_tokens += s.num_new_tokens
                if req.is_prefill_done:
                    req.set_status(RequestStatus.DECODING,
                                   "prefill-complete")
                    req.ready_for_step = False
            elif req.status is RequestStatus.DECODING:
                # The fed token's KV was written this step, so the computed
                # count advances during decode too — release() relies on it
                # to know which pages are fully backed by real KV.
                req.num_computed_tokens += s.num_new_tokens
                req.ready_for_step = False

    def on_token_committed(self, request: Request) -> None:
        """The ring (or the local resolve) delivered a sampled token.

        A token that was already fed from the device-resident array (the
        overlapped step loop ran one dispatch ahead) must NOT re-arm
        ``ready_for_step`` — feeding it again would recompute its
        position and resample its logits, duplicating a token.
        """
        fed_ahead = request.num_computed_tokens >= request.total_len
        request.ready_for_step = not fed_ahead
        if not fed_ahead:
            # The committed token is host-known and unfed: the normal
            # host-fed path takes over (sync tail / overlap off).
            request.device_feed_ready = False

    # -- completion -------------------------------------------------------

    def finished_requests(self) -> list[Request]:
        return [r for r in self.running.values() if r.status.is_finished]

    def release_request(self, request: Request) -> None:
        self.running.pop(request.request_id, None)
        self.wait_queue.pop(request.request_id, None)
        self.cache.release(request)
        conformance.on_disown(request.request_id, self.conf_token)

    def _abort_on_oom(self, req: Request) -> None:
        logger.warning("decode OOM: aborting %s", req.request_id)
        req.abort("kv_oom")
        self.cache.stats.kv_oom_aborts += 1
        self._obs_event("kv_oom", req)

    def _obs_event(self, kind: str, req: Request, dur: float = 0.0) -> None:
        """Flight-recorder event + (for traced requests) a trace span for
        the memory-pressure lifecycle transitions — the "which of the
        five places" answer when a slow request hit swap traffic."""
        from parallax_tpu.obs.flight import get_flight

        get_flight().event(
            kind, request_id=req.request_id, stage=self.stage_name,
            context_tokens=req.total_len,
        )
        if req.traced:
            from parallax_tpu.obs.trace import get_trace_store

            get_trace_store().add(
                req.request_id, self.stage_name, kind,
                t0=time.perf_counter() - dur, dur=dur,
                args={"context_tokens": req.total_len},
            )

    # -- preemption to host -----------------------------------------------

    def _ensure_capacity_or_preempt(
        self,
        req: Request,
        new_total_tokens: int,
        allow_self: bool = False,
        exclude_scheduled: set[str] | None = None,
    ) -> bool:
        """``ensure_capacity`` with preemption-to-host behind it.

        Under memory pressure, swap out the lowest-priority running
        decode (latest arrival first) until ``req`` fits. When nothing
        is left to preempt: park ``req`` itself if eligible
        (``allow_self``, decode path), else abort it — ``kv_oom`` is the
        last resort once the host tier is also exhausted, not the first
        response to pressure. Returns True when ``req`` may be
        scheduled this step.
        """
        if self.cache.ensure_capacity(req, new_total_tokens):
            return True
        preempt = self.cache.preempt_to_host
        if self.cache.host_tier is not None:
            skip: set[str] = set(exclude_scheduled or ())
            while True:
                victim = self._preemption_victim(req, skip)
                if victim is None:
                    break
                if not preempt(victim):
                    # This victim's KV image does not fit the host tier;
                    # a smaller (slightly older) victim still might —
                    # keep walking before declaring the tier full.
                    skip.add(victim.request_id)
                    continue
                self._park(victim)
                if self.cache.ensure_capacity(req, new_total_tokens):
                    return True
            if (
                allow_self
                and req.status is RequestStatus.DECODING
                and (req.ready_for_step or req.device_feed_ready)
                and preempt(req)
            ):
                # req is itself the lowest priority: park it rather than
                # abort — its pages unblock older requests immediately.
                self._park(req)
                return False
        self._abort_on_oom(req)
        return False

    def _preemption_victim(
        self, exclude: Request, exclude_ids: set[str] | None = None
    ) -> Request | None:
        """Latest-arrival running decode that is safe to swap out.

        Safe: a committed row awaiting scheduling (``ready_for_step``),
        or a row whose next token sits in the device last-token array
        (``device_feed_ready``) — an in-flight step's writes to its
        pages are ordered BEFORE the demotion gather on the device
        stream, and its pending commit lands on the parked request
        object directly. Unsafe: a row awaiting a ring/host token with
        nothing device-resident (the late commit would look up the
        running set and drop the token), rows already placed in the
        plan being formed (their segment would reference freed pages),
        mirrors, and hybrid state-slot holders (their swap-out would
        need cross-stage/state coordination this tier does not model).
        """
        best: Request | None = None
        for r in self.running.values():
            if (
                r is exclude
                or r.migrating
                or r.status is not RequestStatus.DECODING
                or not (r.ready_for_step or r.device_feed_ready)
                or (exclude_ids and r.request_id in exclude_ids)
                or getattr(r, "is_mirror", False)
                or getattr(r, "state_slot", None) is not None
            ):
                continue
            if best is None or r.arrival_time > best.arrival_time:
                best = r
        return best

    def _park(self, req: Request) -> None:
        """Move a preempted request to the wait-queue FRONT: preempted
        requests carry the oldest arrivals among waiting work, so FCFS
        resume order falls out of front insertion. Capacity preemption
        only ever parks DECODING rows (see _preemption_victim); node-level
        migration parks can also preempt a mid-prefill request, which
        swap-in later resumes into PREFILLING at its computed-token mark.
        ``ready_for_step`` is preserved: a parked row with a commit still
        in flight is re-armed by ``on_token_committed`` when it lands."""
        self.running.pop(req.request_id, None)
        req.set_status(RequestStatus.PREEMPTED, "preempt")
        req.device_feed_ready = False
        self.wait_queue[req.request_id] = req
        self.wait_queue.move_to_end(req.request_id, last=False)
        self._obs_event("preempt", req)

    def check_timeouts(self) -> list[Request]:
        """Abort requests exceeding the wall-clock budget
        (reference scheduler.py:314-330)."""
        now = time.monotonic()
        timed_out = []
        for req in list(self.running.values()) + list(self.wait_queue.values()):
            # Already-finished rows awaiting collection must not be
            # re-aborted: FINISHED_* is terminal in the declared FSM,
            # and a timeout "abort" here would overwrite the real
            # outcome of a request that finished on time.
            if req.status.is_finished:
                continue
            if now - req.arrival_time > self.request_timeout_s:
                req.abort("timeout")
                timed_out.append(req)
        return timed_out
