"""KV cache orchestration: page ownership, prefix reuse, memory budgeting.

Capability parity: reference ``src/parallax/server/cache_manager.py:25-804``
(CacheManager: allocation w/ prefix match + eviction on pressure, decode
append, prefix insertion on release, HBM budgeting). The device arrays
themselves live in the executor's jit state; this class only does the
host-side bookkeeping — pages never move on device, only ids are shared.

Ownership model: every device page has one owner — an in-flight request or
the radix tree. Prefix-cache hits share tree-owned pages read-only, pinned
via lock refs for the request's lifetime.
"""

from __future__ import annotations

import contextlib
import math
import time

from parallax_tpu.config import LAYER_ATTENTION, LAYER_SLIDING, ModelConfig
from parallax_tpu.runtime.allocator import OutOfPages, PageAllocator
from parallax_tpu.runtime.radix_cache import RadixPageCache
from parallax_tpu.runtime.request import Request
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)


def kv_bytes_per_page(
    config: ModelConfig, num_cache_layers: int, page_size: int, dtype_bytes: int = 2
) -> int:
    """Device bytes one page occupies across this shard's cache layers
    (``ModelConfig.num_cache_layers``: every layer but a hybrid's
    recurrent ones, which carry state slots instead, and once a pass
    for a looped stack, whose passes each write pages of their own).

    Uses the config's per-token accounting, which covers MLA latent+rope
    and the DSA index-key cache (reference DSA/MSA index-cache budgeting,
    cache_manager.py:354-420).
    """
    per_token = config.kv_bytes_per_token_per_layer() * dtype_bytes // 2
    return per_token * page_size * num_cache_layers


def derive_num_pages(
    free_bytes: int,
    config: ModelConfig,
    num_cache_layers: int,
    page_size: int,
    utilization: float = 0.9,
    dtype_bytes: int = 2,
    state_bytes: int = 0,
) -> int:
    """KV page budget from free HBM (reference
    ``cache_manager._calculate_cache_allocation``, cache_manager.py:354-420).
    ``state_bytes``: what a hybrid's recurrent-state slots will hold
    (allocated beside the pool), taken off the budget before pages are
    sized."""
    per_page = kv_bytes_per_page(config, num_cache_layers, page_size, dtype_bytes)
    budget = int(free_bytes * utilization) - state_bytes
    return max(8, budget // max(1, per_page))


def make_cache_manager(
    page_size: int,
    num_pages: int,
    enable_prefix_cache: bool = True,
    max_model_len: int = 32768,
    linear_state: bool = False,
    on_slot_free=None,
    host_tier=None,
    track_digests: bool = False,
    prefill_chunk_skip: bool = True,
    eva=None,
):
    """The stage's page bookkeeper, chosen by the model:
    :class:`EvaCacheManager` where ``eva`` (``ModelConfig.eva``) is set
    — two kinds of entry in one pool, and pages that go back before a
    request ends; the engine has switched prefix reuse and the host
    tier off — and :class:`CacheManager` for every other model."""
    if eva is not None:
        return EvaCacheManager(page_size, num_pages, eva,
                               max_model_len=max_model_len)
    return CacheManager(
        page_size, num_pages, enable_prefix_cache=enable_prefix_cache,
        max_model_len=max_model_len, linear_state=linear_state,
        on_slot_free=on_slot_free, host_tier=host_tier,
        track_digests=track_digests,
        prefill_chunk_skip=prefill_chunk_skip,
    )


_NS_SECRET_NOTED = False


def derive_ns_salt(lora_id: str) -> int:
    """Deterministic 31-bit prefix-cache namespace salt for one
    adapter: ``blake2s(secret + adapter id)``, never 0 (an all-zero
    salt would alias the base namespace).

    Deterministic BY DESIGN (it used to be process-random): every
    replica salts the same adapter identically, so the block-hash
    digests workers publish for adapter-namespaced prefixes are
    reproducible scheduler-side — cache-aware routing and migration
    targeting can score adapter tenants' warm replicas instead of
    skipping the prediction (RequestMeta.chain). Namespaces stay
    pairwise distinct, but without a secret they are COMPUTABLE: a
    caller who can submit raw token ids (library/swarm surfaces — the
    HTTP plane tokenizes text) could craft a stream landing in another
    adapter's namespace. Deployments that need unguessable namespaces
    set ``PARALLAX_NS_SECRET`` (same value cluster-wide — the salt
    must agree across replicas for routing to work); the first
    adapter-salt derivation logs which mode is in effect."""
    import hashlib
    import os

    secret = os.environ.get("PARALLAX_NS_SECRET", "")
    global _NS_SECRET_NOTED
    if not _NS_SECRET_NOTED:
        _NS_SECRET_NOTED = True
        if not secret:
            logger.info(
                "adapter prefix-cache namespaces derived without "
                "PARALLAX_NS_SECRET: deterministic and distinct per "
                "adapter, but computable by anyone who knows the "
                "adapter id (set the secret cluster-wide for "
                "unguessable namespaces; docs/qos.md)"
            )
    digest = hashlib.blake2s(
        f"{secret}:{lora_id}".encode("utf-8", "surrogatepass")
    ).digest()
    return (int.from_bytes(digest[:4], "little") & 0x7FFFFFFF) or 1


def ns_salt(salts: dict[str, int], lora_id: str | None) -> int | None:
    """Memoized per-adapter namespace salt (see ``derive_ns_salt``).

    KV contents depend on the LoRA adapter, so tenants must never
    prefix-hit each other's pages. XOR-salting the token stream keeps
    its length (page alignment intact) and the tokens in 31 bits.
    Cross-tenant collisions require an entire page of positionwise-
    colliding tokens between two distinct adapters' namespaces."""
    if lora_id is None:
        return None
    salt = salts.get(lora_id)
    if salt is None:
        salt = salts[lora_id] = derive_ns_salt(lora_id)
    return salt


def ns_tokens(salts: dict[str, int], token_ids: list[int],
              lora_id: str | None) -> list[int]:
    """Namespace a token stream per LoRA adapter (see ``ns_salt``)."""
    salt = ns_salt(salts, lora_id)
    if salt is None:
        return token_ids
    return [t ^ salt for t in token_ids]


class CacheManager:
    """Host-side paged-KV bookkeeping for one pipeline stage."""

    def __init__(
        self,
        page_size: int,
        num_pages: int,
        enable_prefix_cache: bool = True,
        max_model_len: int = 32768,
        linear_state: bool = False,
        on_slot_free=None,
        host_tier=None,
        track_digests: bool = False,
        prefill_chunk_skip: bool = True,
    ):
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_model_len = max_model_len
        self.enable_prefix_cache = enable_prefix_cache
        # Prefix-aware chunk skipping (EngineConfig.prefill_chunk_skip):
        # False keeps the radix tree populating on release (digest
        # parity, routing) but admission and mid-prefill planning stop
        # REUSING matches — every chunk recomputes. A/B + debug knob.
        self.prefill_chunk_skip = prefill_chunk_skip
        # Hybrid models: prefix hits additionally need a linear-state
        # snapshot at the skip boundary (reference linear prefix slots,
        # cache_manager.py:96-103,422-447); matches truncate to the deepest
        # slot-carrying node and the snapshot's slot id is surfaced on the
        # request as ``restore_state_from``.
        self.linear_state = linear_state
        self.on_slot_free = on_slot_free
        # Host-DRAM second tier (runtime/host_cache.py): radix eviction
        # demotes pages into it, matches can hit host-resident nodes
        # (swap-in before admission), and decode OOM preempts whole
        # requests into it instead of aborting.
        self.host_tier = host_tier
        self.allocator = PageAllocator(num_pages)
        self.prefix_cache = RadixPageCache(
            page_size, on_evict_slot=on_slot_free,
            host_free=(
                (lambda h: host_tier.pool.free(h))
                if host_tier is not None else None
            ),
            track_digests=track_digests and enable_prefix_cache,
        )
        if host_tier is not None:
            host_tier.set_evict_cb(self.prefix_cache.drop_host_page)
        from parallax_tpu.utils.request_metrics import CacheStats

        self.stats = CacheStats()
        # rid -> (locked node path, number of shared tree-owned pages)
        self._locked: dict[str, tuple] = {}
        # Per-adapter radix namespaces: KV depends on the LoRA adapter, so
        # tenants must never prefix-hit each other's pages (see
        # ``ns_tokens``).
        self._ns_salts: dict[str, int] = {}

    def _ns_tokens(self, token_ids: list[int], lora_id: str | None):
        return ns_tokens(self._ns_salts, token_ids, lora_id)

    # -- capacity ---------------------------------------------------------

    @property
    def num_free_pages(self) -> int:
        return self.allocator.num_free

    def pages_needed(self, num_tokens: int) -> int:
        return math.ceil(num_tokens / self.page_size)

    def extra_pages(self, request: Request, new_total_tokens: int) -> int:
        """Pages :meth:`ensure_capacity` would have to find."""
        return max(
            0, self.pages_needed(new_total_tokens) - len(request.page_ids)
        )

    def _reclaim(self, need: int) -> bool:
        """Free pages from the prefix cache until ``need`` are available.

        With a host tier attached, evicted pages demote into it instead
        of losing their KV; prefix reuse then extends past HBM capacity.
        This runs inside the scheduler's plan, so the demotion only
        enqueues its copy (``HostKVTier.demote``); the engine settles
        it after the next read-back."""
        if self.allocator.num_free >= need:
            return True
        deficit = need - self.allocator.num_free
        demoter = None
        if self.host_tier is not None:
            def demoter(ids, _tier=self.host_tier):
                # Partial mode: evict() orders victims coldest-first, so
                # the kept suffix is the warmest, ancestor-closed subset.
                return _tier.demote(ids, partial=True)
        freed = self.prefix_cache.evict(deficit, demoter=demoter)
        self.allocator.free(freed)
        self.stats.pages_evicted += len(freed)
        return self.allocator.num_free >= need

    # -- request lifecycle ------------------------------------------------

    def allocate_for_prompt(self, request: Request) -> bool:
        """Admit a request: prefix-match, pin, allocate the rest.

        Sets ``request.page_ids`` / ``num_cached_tokens`` /
        ``num_computed_tokens``. Returns False (no side effects) when memory
        is insufficient even after eviction.
        Reference: ``allocate_request`` (cache_manager.py:462-564).
        """
        prompt_len = request.num_prompt_tokens
        shared_pages: list[int] = []
        path = []  # empty match path (both impls accept [] for lock/unlock)
        if self.linear_state and hasattr(request, "restore_state_from"):
            del request.restore_state_from  # stale from a failed admit
        if (
            self.enable_prefix_cache
            and self.prefill_chunk_skip
            and prompt_len > 1
        ):
            pages, full_path = self.prefix_cache.match_prefix(
                self._ns_tokens(request.prompt_ids, request.lora_id)
            )
            # Always leave >=1 prompt token to recompute so the stage emits a
            # hidden state for sampling.
            usable = min(len(pages), (prompt_len - 1) // self.page_size)
            if self.linear_state:
                # Mirror stages must skip EXACTLY what the head skipped
                # (rows before that never arrive); cap the walk there so a
                # longer local match cannot put the recurrence state ahead
                # of the rows about to be replayed.
                head_cached = getattr(request, "mirror_head_cached", None)
                if head_cached is not None:
                    usable = min(usable, head_cached // self.page_size)
                usable = self.prefix_cache.deepest_linear_slot(
                    full_path, usable
                )
                if usable:
                    request.restore_state_from = (  # type: ignore[attr-defined]
                        full_path[usable - 1].linear_slot
                    )
            shared_pages = pages[:usable]
            path = self.prefix_cache.slice_path(full_path, usable)

        total_pages = self.pages_needed(prompt_len)
        fresh_needed = total_pages - len(shared_pages)
        # Host-resident nodes in the matched path need a device page each
        # (swap-in) on top of the fresh tail.
        host_nodes = [n for n in path if not n.on_device]
        # Pin the matched prefix BEFORE any eviction: reclaiming first could
        # evict the matched nodes and hand their device pages back out as
        # this very request's fresh pages (double-booked page = corrupted
        # KV). The pin also shields host-resident nodes from the host
        # pool's own watermark eviction while the reclaim below runs.
        self.prefix_cache.lock(path)
        if not self._reclaim(fresh_needed + len(host_nodes)):
            self.prefix_cache.unlock(path)
            return False
        try:
            fresh = self.allocator.alloc(fresh_needed + len(host_nodes))
        except OutOfPages:
            self.prefix_cache.unlock(path)
            return False
        if host_nodes:
            # H2D scatter of the host-tier hits, then the nodes are
            # ordinary device-resident tree pages shared with this
            # request.
            t_swap = time.perf_counter()
            swap_pages = fresh[:len(host_nodes)]
            fresh = fresh[len(host_nodes):]
            handles = [
                self.prefix_cache.promote_node(n, p)
                for n, p in zip(host_nodes, swap_pages)
            ]
            self.host_tier.promote(handles, swap_pages)
            shared_pages = [n.page_id for n in path]
            # Observability: admission-time host-tier swap-in is one of
            # the places a slow request can hide — record it for traced
            # requests and the flight-recorder event ring.
            dur = time.perf_counter() - t_swap
            from parallax_tpu.obs.flight import get_flight
            from parallax_tpu.obs.trace import get_trace_store

            self._goodput_swap(dur)
            get_flight().event(
                "swap_in", request_id=request.request_id,
                pages=len(host_nodes), ms=round(dur * 1e3, 3),
            )
            if request.traced:
                get_trace_store().add(
                    request.request_id, "cache", "swap_in",
                    t0=t_swap, dur=dur, args={"pages": len(host_nodes)},
                )
        request.page_ids = shared_pages + fresh
        request.num_cached_tokens = len(shared_pages) * self.page_size
        request.num_computed_tokens = request.num_cached_tokens
        self._locked[request.request_id] = (path, len(shared_pages))
        self.stats.tokens_admitted += prompt_len
        self.stats.tokens_hit_host += len(host_nodes) * self.page_size
        self.stats.tokens_hit_device += (
            request.num_cached_tokens - len(host_nodes) * self.page_size
        )
        return True

    def extend_prefix_match(self, request: Request) -> int:
        """Mid-prefill chunk skipping: re-consult the radix tree before a
        request's FIRST chunk and grow its shared prefix if a donor
        finished (and inserted) after this request was admitted.

        Radix insertion only happens at :meth:`release`, so a request
        admitted while its prefix donor was still running gets a shallow
        admission match; by the time its first chunk is planned the tree
        may cover far more. The extension stays a pure prefix-growth —
        the request's own fresh pages over the newly covered span are
        freed and replaced by tree-shared (locked) pages, preserving the
        contiguous shared-prefix invariant every preemption/release path
        relies on (``owned = page_ids[num_shared:]``).

        Callers must only invoke this while
        ``num_computed_tokens == num_cached_tokens`` (no chunk computed
        past the admission skip — anything deeper is no longer a prefix
        swap). Returns the number of newly skipped tokens (0 = no
        change). Never allocates; only frees.
        """
        if not (self.enable_prefix_cache and self.prefill_chunk_skip):
            return 0
        if self.linear_state:
            # Linear-state skips need the recurrence snapshot wired at
            # the skip boundary (restore_state_from), which assemble
            # only honors on the request's first chunk dispatch — the
            # admission-time match is the one that set it up; keep it.
            return 0
        if getattr(request, "mirror_head_cached", None) is not None:
            # Mirror stages may only skip what the head skipped: rows
            # before the head's boundary never arrive on the wire.
            return 0
        entry = self._locked.get(request.request_id)
        if entry is None:
            return 0
        old_path, num_shared = entry
        prompt_len = request.num_prompt_tokens
        if prompt_len <= 1:
            return 0
        pages, full_path = self.prefix_cache.match_prefix(
            self._ns_tokens(request.prompt_ids, request.lora_id)
        )
        usable = min(len(pages), (prompt_len - 1) // self.page_size)
        # Host-resident nodes in the extension would need a swap-in
        # allocation; truncate the growth at the first one (the
        # admission path owns swap-in orchestration).
        new_path = self.prefix_cache.slice_path(full_path, usable)
        for i, node in enumerate(new_path[num_shared:], start=num_shared):
            if not node.on_device:
                usable = i
                new_path = self.prefix_cache.slice_path(full_path, usable)
                break
        if usable <= num_shared:
            return 0
        new_shared = pages[:usable]
        if new_shared[:num_shared] != request.page_ids[:num_shared]:
            # The tree's page chain diverged from what this request
            # pinned at admission (should not happen while locked) —
            # refuse rather than corrupt.
            return 0
        # Lock the longer path before unlocking the old one so shared
        # ancestors never drop to zero refs in between.
        self.prefix_cache.lock(new_path)
        self.prefix_cache.unlock(old_path)
        replaced = request.page_ids[num_shared:usable]
        self.allocator.free(replaced)
        request.page_ids = new_shared + request.page_ids[usable:]
        request.num_cached_tokens = usable * self.page_size
        request.num_computed_tokens = usable * self.page_size
        self._locked[request.request_id] = (new_path, usable)
        skipped = (usable - num_shared) * self.page_size
        self.stats.tokens_hit_device += skipped
        self.stats.tokens_chunk_skipped += skipped
        return skipped

    def ensure_capacity(self, request: Request, new_total_tokens: int) -> bool:
        """Grow the page list to cover ``new_total_tokens`` (decode append).

        Reference: ``append_slot`` (cache_manager.py:606-629).
        """
        need = self.pages_needed(new_total_tokens) - len(request.page_ids)
        if need <= 0:
            return True
        if not self._reclaim(need):
            return False
        try:
            request.page_ids.extend(self.allocator.alloc(need))
        except OutOfPages:
            return False
        return True

    def trim_uncomputed_pages(self, request: Request) -> int:
        """Free a mid-prefill request's owned pages past its computed
        span. ``allocate_for_prompt`` allocates the WHOLE prompt's pages
        upfront, so a request parked mid-prefill owns pages holding no
        KV yet; a preemption image that demoted them would ship garbage
        and overrun the checkpoint wire bound (one page of slack past
        the computed tokens). The prefill chunk loop re-grows the list
        through ``ensure_capacity`` after resume. Returns the number of
        pages freed."""
        keep = max(
            self.pages_needed(request.num_computed_tokens),
            self._locked.get(request.request_id, ([], 0))[1],
        )
        tail = request.page_ids[keep:]
        if not tail:
            return 0
        self.allocator.free(tail)
        del request.page_ids[keep:]
        return len(tail)

    # -- preemption (decode OOM -> host tier, not abort) ------------------

    def preempt_to_host(self, request: Request) -> bool:
        """Park a running request's KV in the host tier (pinned — losing
        it would corrupt the resumed stream) and free its device pages.

        The shared prefix stays tree-owned and LOCKED on device (the
        ``_locked`` entry survives preemption), so only the request's own
        pages move. False (no side effects) when the tier is absent or
        cannot hold the image — the caller then falls back to abort.
        """
        if self.host_tier is None:
            return False
        _path, num_shared = self._locked.get(
            request.request_id, ([], 0)
        )
        owned = request.page_ids[num_shared:]
        if not owned:
            return False   # nothing to reclaim; preemption is pointless
        t_swap = time.perf_counter()
        handles = self.host_tier.demote(owned, pinned=True)
        if handles is None:
            return False
        # The one demotion that takes its bytes at once: the image is
        # what the row resumes (or migrates) from, so it is whole on the
        # host before the row's pages go back.
        self.host_tier.settle()
        request.host_page_handles = handles  # type: ignore[attr-defined]
        self.allocator.free(owned)
        del request.page_ids[num_shared:]
        self.stats.preemptions += 1
        self._goodput_swap(time.perf_counter() - t_swap, "swap_gather")
        return True

    def shared_prefix_tokens(self, request_id: str) -> int:
        """Tokens of the request's context covered by LOCKED tree-shared
        pages (the part a preemption image does NOT carry)."""
        _path, num_shared = self._locked.get(request_id, ([], 0))
        return num_shared * self.page_size

    def adopt_migrated(
        self, request: Request, handles: list[int], prefix_tokens: int
    ) -> bool:
        """Register a migrated-in request's host-parked KV image as if
        THIS manager had preempted it locally: lock a radix path
        covering exactly ``prefix_tokens`` (the image starts right after
        them) and attach the pinned handles; the request then resumes
        through the ordinary ``resume_from_host`` admission. False (no
        side effects — the caller frees the handles and falls back to
        re-prefill) when the local radix does not cover the prefix with
        on-device pages."""
        pages_prefix = prefix_tokens // self.page_size
        path: list = []
        shared: list[int] = []
        if pages_prefix:
            if not self.enable_prefix_cache:
                return False
            pages, full_path = self.prefix_cache.match_prefix(
                self._ns_tokens(request.prompt_ids, request.lora_id)
            )
            if len(pages) < pages_prefix:
                return False
            path = self.prefix_cache.slice_path(full_path, pages_prefix)
            if any(not n.on_device for n in path):
                # Host-resident twins would need their own swap-in
                # orchestration; re-prefill is simpler and always right.
                return False
            shared = pages[:pages_prefix]
            self.prefix_cache.lock(path)
        request.page_ids = list(shared)
        request.host_page_handles = (  # type: ignore[attr-defined]
            list(handles)
        )
        self._locked[request.request_id] = (path, len(shared))
        request.num_cached_tokens = prefix_tokens
        self.stats.tokens_hit_device += prefix_tokens
        return True

    def resume_from_host(self, request: Request) -> bool:
        """Swap a preempted request's KV image back into fresh device
        pages. False (request stays parked) when pages are still short."""
        handles = getattr(request, "host_page_handles", None)
        if handles is None:
            return True
        if not self._reclaim(len(handles)):
            return False
        try:
            fresh = self.allocator.alloc(len(handles))
        except OutOfPages:
            return False
        t_swap = time.perf_counter()
        self.host_tier.promote(handles, fresh)
        request.page_ids.extend(fresh)
        del request.host_page_handles
        self.stats.resumes += 1
        self._goodput_swap(time.perf_counter() - t_swap)
        return True

    @staticmethod
    def _goodput_swap(seconds: float, program: str = "swap_scatter") -> None:
        """Accrue host<->device KV transfer time into the goodput time
        split and the per-program device-time split — ``swap_gather``
        is device->host (preemption park), ``swap_scatter`` is
        host->device (resume / admission swap-in). Never raises —
        metrics must not break serving."""
        try:
            from parallax_tpu.obs.device import get_device_plane
            from parallax_tpu.obs.goodput import get_goodput

            get_goodput().add_time("swap", seconds)
            get_device_plane().time.add(program, seconds)
        except Exception:  # pragma: no cover - obs only
            pass

    def release(self, request: Request) -> None:
        """Return a finished/aborted request's pages.

        Full pages of the final context are donated to the prefix cache;
        duplicates and the ragged tail are freed.
        Reference: ``insert_full_blocks_to_cache`` (cache_manager.py:704-791).
        """
        handles = getattr(request, "host_page_handles", None)
        if handles is not None:
            # Released while preempted (timeout/abort): the parked host
            # image dies with the request.
            self.host_tier.free(handles)
            del request.host_page_handles
        path, num_shared = self._locked.pop(request.request_id, ([], 0))
        self.prefix_cache.unlock(path)
        # Hybrid models: the engine snapshotted conv/recurrent state into
        # dedicated slots at page-aligned boundaries (deepest prompt
        # boundary + deepest conversation boundary); attach each to the
        # radix node at exactly its boundary so future prefix hits can
        # resume the recurrence there. Unattachable (aborted request, node
        # missing, boundary already covered) -> the slot goes back to the
        # engine's pool via on_slot_free.
        snapshots = list(getattr(request, "state_snapshots", {}).values())
        if hasattr(request, "state_snapshots"):
            del request.state_snapshots
        owned = request.page_ids[num_shared:]
        if not owned:
            if self.on_slot_free:
                for _length, slot in snapshots:
                    self.on_slot_free(slot)
            request.page_ids = []
            return
        if self.enable_prefix_cache and request.status.value != "finished_abort":
            # Only donate pages fully covered by *computed* KV. The final
            # sampled token never runs a forward step (the request finishes
            # at commit), so its KV slot is stale — when the token count is
            # page-aligned the naive len(all_token_ids) count would donate a
            # page with one corrupt slot that future prefix hits silently
            # read. (Reference insert_full_blocks_to_cache uses context_len,
            # the computed KV length, for the same reason.)
            computed = min(request.num_computed_tokens, len(request.all_token_ids))
            n_full = computed // self.page_size
            tokens = self._ns_tokens(
                request.all_token_ids[: n_full * self.page_size],
                request.lora_id,
            )
            tail = owned[max(0, n_full - num_shared):]
            duplicates = self.prefix_cache.insert(tokens, request.page_ids[:n_full])
            self.allocator.free(duplicates + tail)
            for length, slot in snapshots:
                attached = (
                    length <= n_full * self.page_size
                    and self.prefix_cache.attach_linear_slot(
                        self._ns_tokens(
                            request.all_token_ids[:length], request.lora_id
                        ),
                        slot,
                    )
                )
                if not attached and self.on_slot_free:
                    self.on_slot_free(slot)
        else:
            if self.on_slot_free:
                for _length, slot in snapshots:
                    self.on_slot_free(slot)
            self.allocator.free(owned)
        request.page_ids = []

    def reset_prefix_cache(self) -> None:
        self.allocator.free(self.prefix_cache.reset())

    def digest_payload(self, full: bool = False) -> dict | None:
        """Prefix-digest heartbeat payload for cache-aware routing (see
        :meth:`RadixPageCache.digest_payload`); None when tracking is off
        or the prefix cache is disabled."""
        if not self.enable_prefix_cache:
            return None
        return self.prefix_cache.digest_payload(full=full)


def eva_rolled_table(request: Request, summary_pages: int) -> list[int]:
    """An EVA row's virtual page table once its open window is complete:
    the pending summary pages join the visible ones, and the pages
    prepared for the next window (if any) follow."""
    visible = summary_pages * request.eva_window
    return (request.page_ids[:visible] + request.eva_pending
            + request.eva_next_open)


class EvaCacheManager(CacheManager):
    """Page bookkeeping for EVA attention (``config.EvaConfig``,
    docs/memory.md "EVA"): a row at context ``c`` holds ``c mod W`` exact
    entries of its open window and one summary per chunk of every
    completed window, both in pages of the one pool.

    ``request.page_ids`` is the row's *virtual* page table, what the
    attention kernels read: the visible summary pages of windows
    ``0 .. w-1`` (``W / C / page`` each) and then the open window's
    pages. ``request.eva_pending`` are the pages the open window's
    summaries are written into; they join the visible list when the
    window is complete, and its ``W / page`` exact pages go back to the
    allocator (:meth:`roll_window`). A step that writes on both sides
    of a boundary (a K-step decode window) needs both tables at once:
    :meth:`ensure_capacity` then prepares ``eva_next_open`` /
    ``eva_next_pending`` beside the current ones, and the rollover
    happens when the next plan starts past the boundary, by which time
    every program that reads the old pages is already enqueued ahead of
    whatever is given them next.

    Pages held at context ``c`` after a plan that starts there:
    ``pp * (c // W) + ceil((c mod W) / page) + pp`` with
    ``pp = W / C / page``. No prefix reuse (a donated page list would
    name released pages) and no host tier.
    """

    def __init__(self, page_size: int, num_pages: int, eva,
                 max_model_len: int = 32768):
        super().__init__(page_size, num_pages, enable_prefix_cache=False,
                         max_model_len=max_model_len)
        if eva.fit_page_size(page_size) != page_size:
            raise ValueError(
                f"page size {page_size} does not fit EVA chunks of "
                f"{eva.chunk_size} in windows of {eva.window_size} "
                f"(EvaConfig.fit_page_size)"
            )
        self.eva = eva
        self.window = eva.window_size
        self.summary_pages = eva.summaries_per_window // page_size  # a window's
        # Monotonic totals (engine collectors publish them).
        self.rollovers = 0
        self.pages_released = 0
        # ``with rollover_span():`` around a rollover's host work; the
        # engine installs its ``engine.eva_rollover`` span here.
        self.rollover_span = None

    # -- arithmetic ---------------------------------------------------------

    def pages_needed(self, num_tokens: int) -> int:
        w, r = divmod(num_tokens, self.window)
        return (self.summary_pages * (w + 1)
                + math.ceil(r / self.page_size))

    def pages_held(self, request: Request) -> int:
        return (len(request.page_ids)
                + len(getattr(request, "eva_pending", ()))
                + len(getattr(request, "eva_next_open", ()))
                + len(getattr(request, "eva_next_pending", ())))

    def extra_pages(self, request: Request, new_total_tokens: int) -> int:
        """Pages :meth:`ensure_capacity` would allocate."""
        return sum(self._growth(request, new_total_tokens))

    def _growth(self, request: Request, total: int) -> tuple[int, int, int]:
        """(open pages, next pending pages, next open pages) still to
        allocate so that positions below ``total`` can be written."""
        w = request.eva_window
        lo, hi = w * self.window, (w + 1) * self.window
        have_open = len(request.page_ids) - self.summary_pages * w
        open_need = math.ceil((min(total, hi) - lo) / self.page_size)
        if total <= hi:
            return max(0, open_need - have_open), 0, 0
        if total > hi + self.window:
            raise ValueError("a step spans more than one EVA window")
        return (
            max(0, open_need - have_open),
            self.summary_pages - len(request.eva_next_pending),
            max(0, math.ceil((total - hi) / self.page_size)
                - len(request.eva_next_open)),
        )

    # -- request lifecycle --------------------------------------------------

    def allocate_for_prompt(self, request: Request) -> bool:
        """Admit: the first window's pages (or the prompt's, if shorter)
        and its pending summary pages. Later windows grow chunk by chunk
        through :meth:`ensure_capacity`: a window's rollover frees more
        than the next one's pending pages take."""
        first = min(request.num_prompt_tokens, self.window)
        need = self.summary_pages + math.ceil(first / self.page_size)
        try:
            pages = self.allocator.alloc(need)
        except OutOfPages:
            return False
        request.eva_window = 0
        request.eva_pending = pages[: self.summary_pages]
        request.eva_next_open = []
        request.eva_next_pending = []
        request.page_ids = pages[self.summary_pages:]
        request.num_cached_tokens = 0
        request.num_computed_tokens = 0
        self._locked[request.request_id] = ([], 0)
        self.stats.tokens_admitted += request.num_prompt_tokens
        return True

    def roll_window(self, request: Request, start_pos: int) -> None:
        """The next step's first write is at ``start_pos``: if that lies
        past the open window, the window is complete and every program
        that reads its exact pages is enqueued. Its pending summary
        pages become visible, its exact pages go back, the prepared (or
        a fresh) next window opens. Frees before it allocates, so it
        cannot run out of pages."""
        if start_pos // self.window <= request.eva_window:
            return
        if start_pos // self.window != request.eva_window + 1:
            raise ValueError("EVA rollover skipped a window")
        with (self.rollover_span or contextlib.nullcontext)():
            old = request.page_ids[self.summary_pages * request.eva_window:]
            self.allocator.free(old)
            request.page_ids = eva_rolled_table(request, self.summary_pages)
            request.eva_pending = (
                request.eva_next_pending
                or self.allocator.alloc(self.summary_pages)
            )
            request.eva_next_open = []
            request.eva_next_pending = []
            request.eva_window += 1
            self.rollovers += 1
            self.pages_released += len(old)

    def ensure_capacity(self, request: Request, new_total_tokens: int) -> bool:
        grow_open, grow_pend, grow_next = self._growth(
            request, new_total_tokens
        )
        need = grow_open + grow_pend + grow_next
        if need <= 0:
            return True
        try:
            pages = self.allocator.alloc(need)
        except OutOfPages:
            return False
        request.page_ids.extend(pages[:grow_open])
        request.eva_next_pending.extend(
            pages[grow_open : grow_open + grow_pend]
        )
        request.eva_next_open.extend(pages[grow_open + grow_pend :])
        return True

    def trim_uncomputed_pages(self, request: Request) -> int:
        return 0

    def release(self, request: Request) -> None:
        """Everything the request holds goes back; nothing is donated
        (there is no prefix tree to donate to)."""
        self._locked.pop(request.request_id, None)
        pages = list(request.page_ids)
        for name in ("eva_pending", "eva_next_open", "eva_next_pending"):
            pages += getattr(request, name, [])
            setattr(request, name, [])
        self.allocator.free(pages)
        request.page_ids = []
