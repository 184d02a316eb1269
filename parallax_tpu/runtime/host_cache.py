"""Host-DRAM KV tier: the second level of the memory hierarchy.

HBM holds the working set (PagedAttention pool + radix prefix cache);
this module adds a host-side page pool behind it so memory pressure
degrades to latency instead of failures:

- Radix eviction *demotes* cold prefix pages to host DRAM (batched
  gather-to-staging D2H) instead of discarding their KV; a later prefix
  match on a host-resident node swaps the page back in (H2D scatter)
  before admission.
- Decode-time OOM *preempts* the lowest-priority running request to the
  host tier (its whole KV image parks here, pinned) rather than
  aborting it with ``kv_oom``; it resumes via swap-in when pages free
  up.

The pool is itself LRU with a low watermark: once full it sheds cold
unpinned pages in a batch down to the watermark, so steady-state
demotion never pays a per-page eviction walk or repeated single-slot
reclaims. Pinned pages (preempted
requests' KV) are never shed — preemption data loss would be silent
output corruption, so the only way out of the pool for those is
``free()`` on resume/release.

Device transfers are injected (``gather_fn``/``scatter_fn``) so the
bookkeeping is testable without an accelerator; the engine wires jitted
implementations built on ``ops/kv_cache_ops.py`` (gather_pages /
scatter_pages). A demotion only *enqueues* its gather and starts the
D2H copies; the bytes are taken later (:meth:`HostKVTier.settle`),
where the step loop would not otherwise wait.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Sequence

import numpy as np

from parallax_tpu.obs.trace import host_span
from parallax_tpu.utils import get_logger

logger = get_logger(__name__)

# Payload of a handle whose bytes are still on their way from the
# device (``HostKVTier.demote`` reserved it, ``settle`` fills it).
_IN_FLIGHT = object()


class HostPagePool:
    """LRU pool of host-resident KV pages under a byte budget.

    Entries are opaque per-page payloads of a fixed size
    (``page_nbytes``); the budget is expressed in bytes and enforced as
    a page-count capacity. Eviction consults ``evict_cb(handle)`` — the
    owner (radix tree) drops its reference and returns True, or refuses
    (pinned node) and the walk skips it.
    """

    def __init__(
        self,
        budget_bytes: int,
        page_nbytes: int,
        low_watermark: float = 0.85,
    ):
        self.page_nbytes = max(1, int(page_nbytes))
        self.capacity = max(0, int(budget_bytes) // self.page_nbytes)
        self.low_target = int(self.capacity * low_watermark)
        # handle -> payload, insertion/access-ordered (oldest first).
        self._pages: "OrderedDict[int, object]" = OrderedDict()
        self._pinned: set[int] = set()
        self._next_handle = 0
        self.evict_cb: Callable[[int], bool] | None = None
        self.evictions = 0

    # -- capacity ---------------------------------------------------------

    @property
    def num_pages(self) -> int:
        return len(self._pages)

    @property
    def num_free(self) -> int:
        return self.capacity - len(self._pages)

    def ensure_room(self, n: int) -> bool:
        """Make room for ``n`` new pages, shedding cold unpinned entries
        down to the low watermark in one batch. False when the budget
        cannot hold them even after eviction (everything pinned, or the
        pool is simply too small)."""
        if n > self.capacity:
            return False
        if self.num_free >= n:
            return True
        target = min(self.low_target, self.capacity - n)
        # Snapshot: evict_cb may reentrantly free() descendants of the
        # handle being dropped (host subtrees), mutating the dict.
        for h in list(self._pages.keys()):
            if len(self._pages) <= target:
                break
            if h in self._pinned or h not in self._pages:
                continue
            if self.evict_cb is None or self.evict_cb(h):
                self._pages.pop(h, None)
                self._pinned.discard(h)
                self.evictions += 1
        return self.num_free >= n

    # -- entries ----------------------------------------------------------

    def store(self, data, pinned: bool = False) -> int | None:
        """Insert one page; None when no room can be made."""
        if not self.ensure_room(1):
            return None
        h = self._next_handle
        self._next_handle += 1
        self._pages[h] = data
        if pinned:
            self._pinned.add(h)
        return h

    def fill(self, handle: int, data) -> None:
        """Give a reserved handle its payload, unless the pool shed or
        freed it meanwhile (its place in the LRU order is the
        reservation's)."""
        if handle in self._pages:
            self._pages[handle] = data

    def load(self, handle: int):
        """Read a page's payload (touches LRU recency)."""
        data = self._pages[handle]
        self._pages.move_to_end(handle)
        return data

    def free(self, handle: int) -> None:
        self._pages.pop(handle, None)
        self._pinned.discard(handle)

    def unpin(self, handle: int) -> None:
        """Make a pinned page evictable again (pinning itself happens at
        ``store(pinned=True)`` — a page is pinned for its whole parked
        life or not at all)."""
        self._pinned.discard(handle)


class HostKVTier:
    """Device<->host page movement over a :class:`HostPagePool`.

    ``gather_fn(page_ids) -> [staged array [layers, >= n, ...]]``
    enqueues the read of device pages into staging buffers and *starts*
    their copies to the host; it must not wait for them (materializing
    a staged array is the wait, and :meth:`settle` is the one place
    that does). Each staged array holds some consecutive layers on its
    leading axis; one after the other they are the layers in order.
    ``scatter_fn(page_ids, layers)`` writes host pages back into device
    pages. One handle = one page's KV across every local attention
    layer.
    """

    def __init__(
        self,
        budget_bytes: int,
        page_nbytes: int,
        gather_fn: Callable[[list[int]], list],
        scatter_fn: Callable[[list[int], list[np.ndarray]], None],
        low_watermark: float = 0.85,
    ):
        self.pool = HostPagePool(budget_bytes, page_nbytes, low_watermark)
        self._gather = gather_fn
        self._scatter = scatter_fn
        # Demotions whose bytes have not been taken yet, oldest first:
        # (staged arrays, the handles reserved for their rows).
        self._unsettled: list[tuple[list, list[int]]] = []
        self.pages_demoted = 0
        self.pages_swapped_in = 0

    def set_evict_cb(self, cb: Callable[[int], bool] | None) -> None:
        self.pool.evict_cb = cb

    @property
    def num_host_pages(self) -> int:
        return self.pool.num_pages

    @property
    def capacity_pages(self) -> int:
        return self.pool.capacity

    @property
    def host_evictions(self) -> int:
        return self.pool.evictions

    def demote(
        self,
        page_ids: Sequence[int],
        pinned: bool = False,
        partial: bool = False,
    ) -> list[int] | None:
        """Start copying device pages to host; returns their handles.

        Returns without waiting for the device: the handles are
        reserved in the pool (they hold their room and their place in
        the LRU order from now on), the gather is enqueued and its
        copies started, and the caller may hand the device pages back to
        the allocator at once — whatever overwrites them is enqueued
        after the gather, so the device's own order protects the read.
        :meth:`settle` takes the bytes. A handle the pool sheds before
        that (``evict_cb``, as for any other) is simply never filled.
        ``pages_demoted`` counts here: pages handed to the tier.

        All-or-nothing by default: None (no side effects beyond pool
        eviction) when the tier cannot hold every page — a preempted
        request's KV image is useless in halves. With ``partial``, as
        many pages as fit are taken from the END of the list (None
        entries for the rest): radix eviction passes victims coldest
        first, so the suffix keeps the warmest pages AND is
        ancestor-closed (children precede parents in the victim order,
        so a kept child's kept parent is never dropped under it)."""
        n = len(page_ids)
        if n == 0:
            return []
        want = min(n, self.pool.capacity) if partial else n
        if not self.pool.ensure_room(want) and not partial:
            return None
        # Non-partial: ensure_room(n) succeeded, so fit == n here.
        fit = min(want, self.pool.num_free)
        if fit <= 0:
            return [None] * n if partial else None
        with host_span("cache.demote_enqueue", pages=fit):
            staged = self._gather(list(page_ids[n - fit:]))
            handles = [
                self.pool.store(_IN_FLIGHT, pinned=pinned)
                for _ in range(fit)
            ]
            self._unsettled.append((staged, handles))
        self.pages_demoted += fit
        return [None] * (n - fit) + handles

    def settle(self) -> None:
        """Take the bytes of every demotion still in flight into the
        handles reserved for them (the blocking device-to-host read).
        The engine calls it after the read-back of the step the gathers
        were enqueued behind, when the copies are done or nearly;
        :meth:`promote` and a preemption, which need the bytes now, call
        it themselves."""
        if not self._unsettled:
            return
        batches, self._unsettled = self._unsettled, []
        with host_span("cache.demote_settle",
                       pages=sum(len(h) for _, h in batches)):
            for staged, handles in batches:
                layers = [layer for s in staged for layer in np.asarray(s)]
                for j, h in enumerate(handles):
                    self.pool.fill(h, tuple(layer[j] for layer in layers))

    def promote(
        self, handles: Sequence[int], device_page_ids: Sequence[int]
    ) -> None:
        """Swap host pages back into freshly allocated device pages and
        release their host copies."""
        if not handles:
            return
        datas = [self.pool.load(h) for h in handles]
        if any(d is _IN_FLIGHT for d in datas):
            # Demoted and matched again before any read-back came
            # between: the swap-in needs the bytes now.
            self.settle()
            datas = [self.pool.load(h) for h in handles]
        layers = [
            np.stack([d[i] for d in datas])
            for i in range(len(datas[0]))
        ]
        self._scatter(list(device_page_ids), layers)
        for h in handles:
            self.pool.free(h)
        self.pages_swapped_in += len(handles)

    def store_image(
        self, layers: Sequence[np.ndarray]
    ) -> list[int] | None:
        """Adopt an externally produced page image (live migration): the
        per-layer ``[n_pages, ...]`` arrays a peer's checkpoint carried
        are stored pinned, page by page, with no device gather.
        All-or-nothing; None when the pool cannot hold them."""
        if not layers:
            return []
        n = int(layers[0].shape[0])
        if any(int(a.shape[0]) != n for a in layers):
            return None
        if not self.pool.ensure_room(n):
            return None
        handles: list[int] = []
        for j in range(n):
            h = self.pool.store(
                tuple(np.asarray(a[j]) for a in layers), pinned=True
            )
            if h is None:  # pragma: no cover - ensure_room guarantees room
                for hh in handles:
                    self.pool.free(hh)
                return None
            handles.append(h)
        return handles

    def free(self, handles: Sequence[int]) -> None:
        for h in handles:
            self.pool.free(h)


def tier_from_paged_kv(
    budget_bytes: int,
    get_kv: Callable[[], list],
    set_kv: Callable[[list], None],
    num_pages: int,
    low_watermark: float = 0.85,
) -> HostKVTier | None:
    """Build a tier whose transfers operate on the engine's live list of
    paged per-layer device arrays (leading dim ``num_pages``).

    The KV list is re-read through ``get_kv`` on every transfer — the
    engine's step donates and replaces its arrays each dispatch, so a
    captured reference would go stale after one step — and swap-ins
    write the updated list back through ``set_kv``. Returns None when
    the KV layout is unsupported (hybrid linear-state tuples, sharded
    leaves without ``nbytes``) or the budget is below one page.

    The gather enqueues ONE jitted program (``gather_pages`` of every
    layer, consecutive layers of one shape stacked into one staging
    array: a dense model's 24 layers are one output and one D2H copy,
    0.6 ms of host time a call on a v5e where 24 outputs, 24 copies and
    a device array of ids took 8.7; PERF.md, PR 43), starts the copies
    and returns the staged device arrays. It
    reads the live KV list, which after a dispatch is the in-flight
    step's *output* buffers: on the device the gather runs after that
    step and before whatever is enqueued next, which is all the order
    a demotion needs, so the host does not wait for either
    (:meth:`HostKVTier.settle` does, later). The swap-in is a jitted
    donated scatter (``scatter_pages``).
    """
    import jax
    import jax.numpy as jnp

    from parallax_tpu.ops.kv_cache_ops import gather_pages, scatter_pages

    kv_arrays = get_kv()
    if not kv_arrays or any(
        not hasattr(a, "shape")
        or not hasattr(a, "nbytes")
        or a.shape[0] != num_pages
        for a in kv_arrays
    ):
        return None
    page_nbytes = sum(int(a.nbytes) // num_pages for a in kv_arrays)
    if budget_bytes < page_nbytes:
        return None

    # Runs of consecutive layers with one page shape and dtype.
    runs: list[list[int]] = []
    for i, a in enumerate(kv_arrays):
        last = kv_arrays[runs[-1][-1]] if runs else None
        if last is not None and (a.shape, a.dtype) == (last.shape, last.dtype):
            runs[-1].append(i)
        else:
            runs.append([i])
    _jit_gather = jax.jit(
        lambda kv, ids: [
            jnp.stack([gather_pages(kv[i], ids) for i in run])
            for run in runs
        ]
    )
    _jit_scatter = jax.jit(
        lambda kv, ids, datas: [
            scatter_pages(layer, ids, data)
            for layer, data in zip(kv, datas)
        ],
        donate_argnums=(0,),
    )

    def _bucket_ids(page_ids: list[int]) -> np.ndarray:
        # Power-of-two id buckets bound transfer recompiles; padding
        # repeats the first id — harmless for gather (extra rows sliced
        # off host-side) and for scatter (the same payload rewritten).
        b = 1
        while b < len(page_ids):
            b *= 2
        ids = np.full((b,), page_ids[0], np.int32)
        ids[: len(page_ids)] = page_ids
        return ids

    def gather_fn(page_ids: list[int]) -> list:
        # The ids go in as they are: the jit call places a numpy
        # argument itself, far cheaper than a device array made first.
        staged = _jit_gather(get_kv(), _bucket_ids(page_ids))
        for s in staged:
            s.copy_to_host_async()
        return staged

    def scatter_fn(page_ids: list[int], layers: list[np.ndarray]) -> None:
        n = len(page_ids)
        ids = _bucket_ids(page_ids)
        padded = []
        for data in layers:
            if ids.shape[0] != n:
                pad = np.repeat(data[:1], ids.shape[0] - n, axis=0)
                data = np.concatenate([data, pad], axis=0)
            padded.append(data)
        set_kv(_jit_scatter(get_kv(), ids, padded))

    return HostKVTier(
        budget_bytes, page_nbytes, gather_fn, scatter_fn, low_watermark
    )


def stage_host_tier(
    budget_bytes: int,
    model,
    mesh,
    get_kv: Callable[[], list],
    set_kv: Callable[[list], None],
    num_pages: int,
) -> HostKVTier | None:
    """The host tier of one engine stage (``model``: its ``StageModel``,
    ``mesh``: its TP mesh or None), or None with the one reason logged:
    no budget asked for (silent), a cache whose pages
    :func:`tier_from_paged_kv` cannot image, or a budget below one
    page. Every refusal is a registered gate (``analysis/gates.py``)."""
    if budget_bytes <= 0:
        return None
    if model.config.eva is not None:
        logger.info(
            "host KV tier disabled: EVA rows hold summary and "
            "exact pages that the tier's page images do not "
            "tell apart",
        )
    elif model.has_linear_layers:
        logger.warning(
            "host KV tier disabled: hybrid linear-state KV "
            "cannot be paged to host (recurrent state has no "
            "page-granularity image)",
        )
    elif mesh is not None and model.tp_size > 1:
        logger.warning(
            "host KV tier disabled: TP-sharded KV transfers "
            "are not supported yet",
        )
    elif model.config.loop_passes > 1:
        logger.warning(
            "host KV tier disabled: a looped stack keeps a "
            "page once a pass in every layer's array, and the "
            "tier's page images hold one place a layer",
        )
    else:
        tier = tier_from_paged_kv(budget_bytes, get_kv, set_kv, num_pages)
        if tier is None:
            logger.warning(
                "host KV tier disabled: unsupported KV layout "
                "or budget below one page",
            )
        return tier
    return None
