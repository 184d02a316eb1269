"""The page bookkeeper's invariants under random operation sequences.

``PageAllocator`` + ``RadixPageCache`` and the ``CacheManager`` over them
are driven by seeded random admit / grow / release / evict sequences and
held, after every operation, to what must be true of any page
bookkeeper: no page has two owners, free + cached + held is the pool, a
locked path is never evicted, a match returns only what was inserted,
and (hybrid models) a snapshot slot has one holder and is handed back
once.
"""

import numpy as np
import pytest

from parallax_tpu.runtime.allocator import OutOfPages, PageAllocator
from parallax_tpu.runtime.cache_manager import CacheManager
from parallax_tpu.runtime.radix_cache import RadixPageCache
from parallax_tpu.runtime.request import (
    Request,
    RequestStatus,
    SamplingParams,
)

SEEDS = [0, 1, 2, 3, 4]
PAGE = 4


@pytest.fixture
def alloc():
    return PageAllocator(64)


@pytest.fixture
def tree():
    return RadixPageCache(PAGE)


class TestAllocatorAndRadix:
    def test_alloc_free_cycle(self, alloc):
        pages = alloc.alloc(10)
        assert len(set(pages)) == 10 and 0 not in pages
        assert alloc.num_free == 53
        alloc.free(pages[:5])
        assert alloc.num_free == 58
        with pytest.raises(OutOfPages):
            alloc.alloc(1000)

    def test_match_insert_evict(self, tree):
        tokens = list(range(12))
        assert tree.insert(tokens, [5, 6, 7]) == []
        pages, path = tree.match_prefix(tokens)
        assert pages == [5, 6, 7]
        assert tree.num_cached_pages == 3
        # diverging suffix matches only the shared page
        pages2, _ = tree.match_prefix([0, 1, 2, 3, 99, 99, 99, 99])
        assert pages2 == [5]
        # duplicate insert reports the loser
        assert tree.insert(tokens[:4], [9]) == [9]
        # pinned pages cannot be evicted
        tree.lock(path)
        assert tree.evict(3) == []
        tree.unlock(path)
        assert sorted(tree.evict(3)) == [5, 6, 7]
        assert tree.num_cached_pages == 0

    def test_partial_lock_path(self, tree):
        tokens = list(range(8))
        tree.insert(tokens, [3, 4])
        pages, full = tree.match_prefix(tokens)
        part = tree.slice_path(full, 1)
        tree.lock(part)
        freed = tree.evict(2)
        assert freed == [4]  # leaf evictable, pinned root page is not
        tree.unlock(part)
        assert sorted(tree.evict(2)) == [3]

    def test_reset_returns_all(self, tree):
        tree.insert(list(range(8)), [1, 2])
        tree.insert([9] * 4, [3])
        assert sorted(tree.reset()) == [1, 2, 3]
        assert tree.num_cached_pages == 0


def _nodes(tree):
    """Every node of the tree below its root."""
    out, stack = [], list(tree._root.children.values())
    while stack:
        n = stack.pop()
        out.append(n)
        stack.extend(n.children.values())
    return out


def _tree_pages(tree) -> list[int]:
    return [n.page_id for n in _nodes(tree)]


def _rand_tokens(rng, lo, hi):
    # A small alphabet forces shared prefixes.
    return [int(x) for x in rng.integers(0, 3, size=int(rng.integers(lo, hi)))]


def _check_pool(alloc, tree, held: list[int], step):
    """No page has two owners, and the owners account for the pool."""
    cached = _tree_pages(tree)
    free = list(alloc._free)
    owners = free + cached + held
    assert len(set(owners)) == len(owners), (step, "a page has two owners")
    assert alloc.null_page not in owners, step
    assert len(cached) == tree.num_cached_pages, step
    assert len(owners) == alloc.num_pages - 1, (
        step, "free + cached + held is not the pool",
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_radix_and_allocator_randomized_invariants(seed):
    """Insert / match / lock / unlock / evict against a dict that models
    the tree's content (full-page prefix -> page id)."""
    rng = np.random.default_rng(seed)
    alloc, tree = PageAllocator(40), RadixPageCache(PAGE)
    model: dict[tuple, int] = {}
    locked: list[tuple[list, list[int]]] = []   # (path, its pages)
    held: list[list[int]] = []                  # pages a "request" owns
    evicted_beside_a_lock = False

    def evict(n):
        nonlocal evicted_beside_a_lock
        pinned = {p for _path, pages in locked for p in pages}
        freed = tree.evict(n)
        assert not pinned & set(freed), "a locked path was evicted"
        for prefix in [k for k, p in model.items() if p in set(freed)]:
            del model[prefix]
        alloc.free(freed)       # raises on a page freed twice
        evicted_beside_a_lock |= bool(pinned and freed)

    for step in range(400):
        op = rng.random()
        if op < 0.4:
            toks = _rand_tokens(rng, PAGE, 5 * PAGE + 1)
            n = len(toks) // PAGE
            if not alloc.can_alloc(n):
                evict(n - alloc.num_free)
            if not alloc.can_alloc(n):
                continue
            pages = alloc.alloc(n)
            dup = tree.insert(toks, pages)
            for i, p in enumerate(pages):
                prefix = tuple(toks[:(i + 1) * PAGE])
                # The tree keeps the copy it had; the newcomer is the
                # duplicate, handed back for the caller to free.
                assert (p in dup) == (prefix in model), step
                model.setdefault(prefix, p)
            alloc.free(dup)
        elif op < 0.7:
            toks = _rand_tokens(rng, 1, 6 * PAGE)
            pages, path = tree.match_prefix(toks)
            want = []
            for i in range(len(toks) // PAGE):
                p = model.get(tuple(toks[:(i + 1) * PAGE]))
                if p is None:
                    break
                want.append(p)
            assert pages == want, (step, "matched what was not inserted")
            if pages and rng.random() < 0.5:
                tree.lock(path)
                locked.append((path, pages))
        elif op < 0.8 and locked:
            path, _pages = locked.pop(int(rng.integers(len(locked))))
            tree.unlock(path)
        elif op < 0.9:
            evict(int(rng.integers(1, 6)))
        elif held and rng.random() < 0.5:
            alloc.free(held.pop(int(rng.integers(len(held)))))
        elif alloc.can_alloc(3):
            held.append(alloc.alloc(3))
        _check_pool(alloc, tree, [p for h in held for p in h], step)
        assert set(_tree_pages(tree)) == set(model.values()), step
    assert evicted_beside_a_lock, "the fuzz never evicted beside a lock"
    # Unpinned, everything the tree holds can be evicted.
    for path, _pages in locked:
        tree.unlock(path)
    alloc.free(tree.evict(alloc.num_pages))
    assert tree.num_cached_pages == 0
    assert alloc.num_free == alloc.num_pages - 1 - sum(map(len, held))


def _mk_req(rid, prompt):
    return Request(request_id=rid, prompt_ids=list(prompt),
                   sampling_params=SamplingParams())


def _check_manager(cm, live: list[Request], step):
    """The pool's accounting with requests as owners: a request owns
    its pages past the shared prefix, the tree owns the shared ones and
    keeps them while the request lives."""
    cached = set(_tree_pages(cm.prefix_cache))
    owned: list[int] = []
    for r in live:
        num_shared = cm._locked[r.request_id][1]
        shared = r.page_ids[:num_shared]
        assert set(shared) <= cached, (step, "a locked page was evicted")
        owned += r.page_ids[num_shared:]
        assert len(r.page_ids) >= cm.pages_needed(r.num_computed_tokens), step
    _check_pool(cm.allocator, cm.prefix_cache, owned, step)


def _finish(rng, req) -> bool:
    """End a request, one in five by abort; True where its pages are
    donated to the tree."""
    aborted = rng.random() < 0.2
    req.status = (RequestStatus.FINISHED_ABORT if aborted
                  else RequestStatus.FINISHED_EOS)
    return not aborted


@pytest.mark.parametrize("seed", SEEDS)
def test_cache_manager_randomized_invariants(seed):
    """Admit / grow / release under eviction pressure (prompts of up to
    10 pages in a pool of 63)."""
    rng = np.random.default_rng(seed)
    cm = CacheManager(page_size=PAGE, num_pages=64)
    live: list[Request] = []
    donated: set[tuple] = set()     # full-page prefixes ever inserted
    refused = rematched = 0

    def admit(rid, prompt):
        nonlocal refused
        req = _mk_req(rid, prompt)
        free, cached = cm.num_free_pages, cm.prefix_cache.num_cached_pages
        if not cm.allocate_for_prompt(req):
            # Refused: nothing taken, nothing left locked.
            refused += 1
            assert not req.page_ids and req.request_id not in cm._locked
            assert cm.num_free_pages + cm.prefix_cache.num_cached_pages \
                == free + cached
            return None
        hit = req.num_cached_tokens
        assert hit % PAGE == 0 and hit < len(prompt), step
        assert not hit or tuple(prompt[:hit]) in donated, (
            step, "hit a prefix that was never inserted",
        )
        assert len(req.page_ids) == cm.pages_needed(len(prompt)), step
        req.num_computed_tokens = len(prompt)
        live.append(req)
        return req

    for step in range(400):
        op = rng.random()
        if op < 0.45 or not live:
            admit(f"p{step}", _rand_tokens(rng, 1, 40))
        elif op < 0.7:
            req = live[int(rng.integers(len(live)))]
            # Decode progress: tokens committed, all but the last computed.
            req.output_ids.extend(_rand_tokens(rng, 1, 9))
            if cm.ensure_capacity(req, req.total_len):
                assert len(req.page_ids) == cm.pages_needed(req.total_len)
                req.num_computed_tokens = req.total_len - 1
            else:
                # No room: the row is aborted, as the scheduler would.
                live.remove(req)
                req.status = RequestStatus.FINISHED_ABORT
                cm.release(req)
        else:
            req = live.pop(int(rng.integers(len(live))))
            kept = _finish(rng, req)
            tokens = req.all_token_ids[:req.num_computed_tokens]
            n_full = len(tokens) // PAGE
            cm.release(req)
            assert not req.page_ids and req.request_id not in cm._locked
            if kept:
                donated.update(
                    tuple(tokens[:(i + 1) * PAGE]) for i in range(n_full)
                )
            if kept and n_full and rng.random() < 0.5:
                # The next request with its prompt finds its pages.
                again = admit(f"again{step}", tokens[:n_full * PAGE] + [7])
                if again is not None:
                    assert again.num_cached_tokens == n_full * PAGE, step
                    rematched += 1
        _check_manager(cm, live, step)
    assert cm.stats.pages_evicted and refused and rematched, (
        "the fuzz never reached eviction pressure"
    )
    for req in live:
        req.status = RequestStatus.FINISHED_ABORT
        cm.release(req)
    cm.reset_prefix_cache()
    assert cm.num_free_pages == cm.num_pages - 1


@pytest.mark.parametrize("seed", SEEDS)
def test_linear_state_cache_manager_randomized_invariants(seed):
    """Hybrid models: a match is cut to the deepest node that carries a
    state snapshot and surfaces that slot; a snapshot slot handed over
    at release is attached to one node or handed back, and handed back
    once when its node goes."""
    rng = np.random.default_rng(seed)
    freed: list[int] = []
    cm = CacheManager(page_size=PAGE, num_pages=48, linear_state=True,
                      on_slot_free=freed.append)
    issued: list[int] = []
    live: list[Request] = []
    # Tokens up to a boundary that a release snapshotted, until the
    # next admission (which may evict its node).
    turn: list[int] | None = None
    restored = 0

    def slot():
        issued.append(len(issued) + 1)
        return issued[-1]

    for step in range(400):
        if rng.random() < 0.5 or not live:
            prompt = _rand_tokens(rng, 2, 32)
            if turn is not None:
                # The conversation's next turn: only releases, which
                # evict nothing, lie between it and the snapshot.
                prompt = turn + prompt
            req = _mk_req(f"p{step}", prompt)
            admitted = cm.allocate_for_prompt(req)
            at_least, turn = len(turn or ()), None
            if admitted:
                hit = req.num_cached_tokens
                assert hit >= at_least, step
                if hit:
                    # The hit ends on a node that carries the snapshot
                    # the row's recurrence restarts from.
                    _pages, path = cm.prefix_cache.match_prefix(prompt[:hit])
                    assert path[-1].linear_slot == req.restore_state_from
                    assert path[-1].linear_slot is not None, step
                    restored += 1
                else:
                    assert not hasattr(req, "restore_state_from"), step
                req.num_computed_tokens = len(prompt)
                live.append(req)
        else:
            req = live.pop(int(rng.integers(len(live))))
            # Most finishes carry snapshots at page boundaries.
            aligned = (req.num_computed_tokens // PAGE) * PAGE
            snapped = rng.random() < 0.6 and aligned >= PAGE
            if snapped:
                snaps = {"prefill": (aligned, slot())}
                if aligned >= 2 * PAGE and rng.random() < 0.5:
                    snaps = {"prefill": (aligned - PAGE, snaps["prefill"][1]),
                             "decode": (aligned, slot())}
                req.state_snapshots = snaps
            kept = _finish(rng, req)
            cm.release(req)
            assert not hasattr(req, "state_snapshots"), step
            if kept and snapped and rng.random() < 0.5:
                turn = req.all_token_ids[:aligned]
        _check_manager(cm, live, step)
        in_tree = [n.linear_slot for n in _nodes(cm.prefix_cache)
                   if n.linear_slot is not None]
        assert len(set(in_tree)) == len(in_tree), (
            step, "a slot is held by two nodes",
        )
        assert len(set(freed)) == len(freed), (step, "a slot was freed twice")
        assert not set(in_tree) & set(freed), step
        assert set(in_tree) | set(freed) == set(issued), (
            step, "a slot was lost",
        )
    assert freed and restored, "the fuzz never freed or restored a snapshot"
    # The engine's slot-steal path: an unpinned snapshot leaves its
    # node, which keeps its pages.
    cached = cm.prefix_cache.num_cached_pages
    unpinned = [n.linear_slot for n in _nodes(cm.prefix_cache)
                if n.linear_slot is not None and n.lock_ref <= 0]
    stolen = cm.prefix_cache.detach_lru_linear_slot()
    assert (stolen in unpinned) if unpinned else (stolen is None)
    assert stolen is None or stolen not in [
        n.linear_slot for n in _nodes(cm.prefix_cache)
    ]
    assert cm.prefix_cache.num_cached_pages == cached
