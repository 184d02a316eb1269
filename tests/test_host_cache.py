"""Host-DRAM KV tier: pool LRU/watermarks, radix demotion/promotion,
pin-refcount safety under eviction, preemption-to-host, and end-to-end
bit-exactness of preempted-then-resumed streams.

The cache/pool tests drive the tier with a fake numpy "device" so the
bookkeeping is exercised without an accelerator; the e2e tests run the
real engine under a page budget its working set exceeds.
"""

import numpy as np
import pytest

from parallax_tpu.runtime.allocator import (
    OutOfPages,
    PageAllocator,
    SlotAllocator,
)
from parallax_tpu.runtime.cache_manager import CacheManager
from parallax_tpu.runtime.host_cache import HostKVTier, HostPagePool
from parallax_tpu.runtime.request import Request, RequestStatus, SamplingParams


# -- allocator guards -----------------------------------------------------


class TestAllocatorGuards:
    def test_double_free_raises(self):
        alloc = PageAllocator(16)
        pages = alloc.alloc(3)
        alloc.free(pages)
        with pytest.raises(ValueError, match="double free"):
            alloc.free([pages[0]])

    def test_out_of_range_free_raises(self):
        alloc = PageAllocator(16)
        with pytest.raises(ValueError, match="out-of-range"):
            alloc.free([16])
        with pytest.raises(ValueError, match="out-of-range"):
            alloc.free([-3])

    def test_duplicate_within_batch_raises(self):
        alloc = PageAllocator(16)
        (p,) = alloc.alloc(1)
        with pytest.raises(ValueError, match="double free"):
            alloc.free([p, p])
        # the failed batch must not have freed anything
        assert alloc.num_free == 14

    def test_partial_batch_not_applied_on_error(self):
        alloc = PageAllocator(16)
        pages = alloc.alloc(2)
        before = alloc.num_free
        with pytest.raises(ValueError):
            alloc.free([pages[0], 99])
        assert alloc.num_free == before
        alloc.free(pages)   # still freeable afterwards

    def test_null_page_is_skipped(self):
        alloc = PageAllocator(16)
        alloc.free([alloc.null_page])   # no-op, no raise
        assert alloc.num_free == 15

    def test_alloc_free_cycle_still_works(self):
        alloc = PageAllocator(8)
        for _ in range(5):
            pages = alloc.alloc(7)
            assert alloc.num_free == 0
            alloc.free(pages)
            assert alloc.num_free == 7
        with pytest.raises(OutOfPages):
            alloc.alloc(8)

    def test_slot_allocator_guards(self):
        sa = SlotAllocator(4)
        s = sa.alloc()
        sa.free(s)
        with pytest.raises(ValueError, match="double free"):
            sa.free(s)
        with pytest.raises(ValueError, match="out-of-range"):
            sa.free(4)
        assert sa.num_free == 4


# -- host page pool -------------------------------------------------------


class TestHostPagePool:
    def test_store_load_free(self):
        pool = HostPagePool(budget_bytes=4 * 100, page_nbytes=100)
        h = pool.store("a")
        assert pool.load(h) == "a"
        assert pool.num_pages == 1
        pool.free(h)
        assert pool.num_pages == 0

    def test_capacity_from_budget(self):
        pool = HostPagePool(budget_bytes=350, page_nbytes=100)
        assert pool.capacity == 3
        assert HostPagePool(budget_bytes=50, page_nbytes=100).capacity == 0

    def test_lru_eviction_order_and_watermark(self):
        evicted = []
        pool = HostPagePool(10 * 100, 100, low_watermark=0.5)
        pool.evict_cb = lambda h: evicted.append(h) or True
        handles = [pool.store(i) for i in range(10)]
        pool.load(handles[0])          # refresh h0 -> MRU
        assert pool.store("x") is not None
        # watermark: shed down to 5 in one batch, oldest (but not h0) first
        assert pool.num_pages <= 6
        assert handles[0] not in evicted
        assert evicted == handles[1:1 + len(evicted)]

    def test_pinned_never_evicted(self):
        pool = HostPagePool(3 * 100, 100)
        pool.evict_cb = lambda h: True
        hs = [pool.store(i, pinned=True) for i in range(3)]
        assert pool.store("x") is None          # everything pinned
        pool.unpin(hs[0])
        assert pool.store("x") is not None
        assert hs[0] not in pool._pages

    def test_evict_cb_refusal_skips(self):
        pool = HostPagePool(2 * 100, 100)
        keep = set()
        pool.evict_cb = lambda h: h not in keep
        h0, h1 = pool.store("a"), pool.store("b")
        keep.add(h0)
        assert pool.store("c") is not None      # h1 evicted instead of h0
        assert h0 in pool._pages and h1 not in pool._pages


# -- radix + cache manager with a fake device tier ------------------------


PAGE = 4
PAGES = 16


def partial_demoter(tier):
    return lambda ids: tier.demote(ids, partial=True)


class Staged:
    """A staged 'device' array of the fake tier: it holds what the
    device held when the gather was enqueued (the device's order), and
    ``np.asarray`` of it — the blocking read — is noted in ``reads``."""

    def __init__(self, data, reads):
        self._data, self._reads = data, reads

    def __array__(self, dtype=None, copy=None):
        self._reads.append(self._data.shape[1])
        return self._data


def make_cm(host_pages=8, num_pages=PAGES):
    """CacheManager over a numpy 'device' (one layer, 2 floats/token).
    ``tier.reads``: pages of every staged array materialised so far."""
    dev = np.arange(num_pages * PAGE * 2, dtype=np.float32).reshape(
        num_pages, PAGE * 2
    )
    reads = []

    def gather(ids):
        return [Staged(dev[np.asarray(ids)].copy()[None], reads)]

    def scatter(ids, layers):
        dev[np.asarray(ids)] = layers[0]

    nbytes = dev[0].nbytes
    tier = HostKVTier(host_pages * nbytes, nbytes, gather, scatter)
    tier.reads = reads
    cm = CacheManager(page_size=PAGE, num_pages=num_pages, host_tier=tier)
    return cm, tier, dev


def finish(cm, req, computed=None):
    req.num_computed_tokens = (
        computed if computed is not None else len(req.all_token_ids)
    )
    req.status = RequestStatus.FINISHED_EOS
    cm.release(req)


class TestRadixHostTier:
    def test_evict_demotes_and_match_hits_host(self):
        cm, tier, dev = make_cm()
        orig = dev.copy()
        r1 = Request("r1", prompt_ids=list(range(12)))
        assert cm.allocate_for_prompt(r1)
        p1 = list(r1.page_ids)
        finish(cm, r1)
        # pressure demotes the whole tree
        freed = cm.prefix_cache.evict(3, demoter=partial_demoter(tier))
        assert len(freed) == 3
        cm.allocator.free(freed)
        assert cm.prefix_cache.num_cached_pages == 0
        assert cm.prefix_cache.num_host_pages == 3
        # scribble the freed device pages: swap-in must restore content
        for p in p1:
            dev[p] = -1.0
        r2 = Request("r2", prompt_ids=list(range(12)) + [50, 51, 52])
        assert cm.allocate_for_prompt(r2)
        assert r2.num_cached_tokens == 12
        assert cm.stats.tokens_hit_host == 12
        assert tier.pages_swapped_in == 3
        pages, _path = cm.prefix_cache.match_prefix(list(range(12)))
        assert all(p >= 0 for p in pages)
        for pg, op in zip(pages, p1):
            assert (dev[pg] == orig[op]).all()

    def test_pinned_pages_never_demoted_or_freed(self):
        """The satellite invariant: evict() while a matched prefix is
        pinned must not demote or free the pinned pages."""
        cm, tier, _dev = make_cm()
        r1 = Request("r1", prompt_ids=list(range(12)))
        assert cm.allocate_for_prompt(r1)
        finish(cm, r1)
        pages, path = cm.prefix_cache.match_prefix(list(range(12)))
        cm.prefix_cache.lock(path)
        pinned = set(pages)
        freed = cm.prefix_cache.evict(3, demoter=partial_demoter(tier))
        assert not (set(freed) & pinned)
        assert all(n.on_device for n in path)
        assert cm.prefix_cache.num_cached_pages == 3
        cm.prefix_cache.unlock(path)
        freed = cm.prefix_cache.evict(3, demoter=partial_demoter(tier))
        assert len(freed) == 3    # unpinned -> all demote now

    def test_partial_lock_demotes_only_unpinned_suffix(self):
        cm, tier, _dev = make_cm()
        r1 = Request("r1", prompt_ids=list(range(12)))
        assert cm.allocate_for_prompt(r1)
        finish(cm, r1)
        pages, full = cm.prefix_cache.match_prefix(list(range(12)))
        part = cm.prefix_cache.slice_path(full, 1)
        cm.prefix_cache.lock(part)
        freed = cm.prefix_cache.evict(3, demoter=partial_demoter(tier))
        assert pages[0] not in freed
        assert sorted(freed) == sorted(pages[1:])
        assert full[0].on_device and not full[1].on_device
        cm.prefix_cache.unlock(part)

    def test_host_pool_pressure_recycles_radix_pages(self):
        """A full pool sheds its OLDEST radix-owned host pages (via
        drop_host_page) to admit new demotions; the surviving host nodes
        still form a valid ancestor chain under the root."""
        cm, tier, _dev = make_cm(host_pages=2)
        r1 = Request("r1", prompt_ids=list(range(12)))
        assert cm.allocate_for_prompt(r1)
        finish(cm, r1)
        freed = cm.prefix_cache.evict(3, demoter=partial_demoter(tier))
        assert len(freed) == 3
        assert cm.prefix_cache.num_cached_pages == 0
        # 3 victims through a 2-page pool: partial demotion keeps the
        # warmest suffix (the two shallowest nodes); the coldest leaf is
        # dropped and what survives is a reachable ancestor chain.
        assert cm.prefix_cache.num_host_pages == tier.num_host_pages == 2
        pages, path = cm.prefix_cache.match_prefix(list(range(12)))
        assert len(path) == 2 and all(not n.on_device for n in path)

    def test_demote_refused_when_tier_cannot_hold(self):
        """Zero-capacity tier: demotion is all-or-nothing refused and
        eviction falls back to dropping pages outright."""
        cm, tier, _dev = make_cm(host_pages=0)
        r1 = Request("r1", prompt_ids=list(range(12)))
        assert cm.allocate_for_prompt(r1)
        finish(cm, r1)
        freed = cm.prefix_cache.evict(3, demoter=partial_demoter(tier))
        assert len(freed) == 3
        assert cm.prefix_cache.num_host_pages == 0
        assert cm.prefix_cache.num_cached_pages == 0
        assert tier.num_host_pages == 0

    def test_insert_adopts_host_resident_twin(self):
        cm, tier, dev = make_cm()
        r1 = Request("r1", prompt_ids=list(range(8)))
        assert cm.allocate_for_prompt(r1)
        finish(cm, r1)
        freed = cm.prefix_cache.evict(2, demoter=partial_demoter(tier))
        cm.allocator.free(freed)
        assert cm.prefix_cache.num_host_pages == 2
        # same content recomputed by a cache-missing request
        r2 = Request("r2", prompt_ids=list(range(8)))
        assert cm.allocate_for_prompt(r2)
        assert r2.num_cached_tokens == 4    # only 1 page usable (8-1)//4
        finish(cm, r2)
        # the recomputed full pages upgraded the host nodes to device
        assert cm.prefix_cache.num_host_pages == 0
        assert tier.num_host_pages == 0

    def test_reset_releases_host_pages(self):
        cm, tier, _dev = make_cm()
        r1 = Request("r1", prompt_ids=list(range(12)))
        assert cm.allocate_for_prompt(r1)
        finish(cm, r1)
        cm.allocator.free(cm.prefix_cache.evict(3, demoter=partial_demoter(tier)))
        assert tier.num_host_pages == 3
        cm.reset_prefix_cache()
        assert tier.num_host_pages == 0
        assert cm.prefix_cache.num_host_pages == 0


class TestDemotionEnqueuesAndSettles:
    """An eviction's demotion returns before its bytes are read; they
    are taken by ``settle`` (the engine, after a read-back) or by
    whoever needs them first."""

    def _cached(self, cm, n_tokens=12, rid="r1"):
        req = Request(rid, prompt_ids=list(range(n_tokens)))
        assert cm.allocate_for_prompt(req)
        pages = list(req.page_ids)
        finish(cm, req)
        return pages

    def test_partial_demote_returns_before_anything_is_materialised(self):
        cm, tier, _dev = make_cm()
        self._cached(cm)
        freed = cm.prefix_cache.evict(3, demoter=partial_demoter(tier))
        assert len(freed) == 3 and tier.reads == []
        # Handed to the tier, counted and holding their room already.
        assert tier.pages_demoted == 3 and tier.num_host_pages == 3
        assert cm.prefix_cache.num_host_pages == 3
        tier.settle()
        assert tier.reads == [3]
        tier.settle()                       # nothing left in flight
        assert tier.reads == [3]

    def test_reclaim_inside_a_plan_reads_nothing(self):
        """The path the scheduler's plan takes: ``_reclaim`` under an
        admission frees the pages at once and waits for no copy."""
        cm, tier, _dev = make_cm(num_pages=8)
        self._cached(cm)                    # 3 of 7 pages stay cached
        r = Request("c", prompt_ids=list(range(100, 124)))
        assert cm.allocate_for_prompt(r)    # needs 6: evicts
        assert tier.pages_demoted > 0 and tier.reads == []
        assert cm.stats.pages_evicted == tier.pages_demoted

    def test_promote_of_a_handle_in_flight_settles_first(self):
        cm, tier, dev = make_cm()
        orig = dev.copy()
        p1 = self._cached(cm)
        cm.allocator.free(
            cm.prefix_cache.evict(3, demoter=partial_demoter(tier)))
        for p in p1:
            dev[p] = -1.0                   # the freed pages, overwritten
        assert tier.reads == []
        r2 = Request("r2", prompt_ids=list(range(12)) + [50, 51, 52])
        assert cm.allocate_for_prompt(r2)   # swap-in: promote
        assert tier.reads == [3] and tier.pages_swapped_in == 3
        assert r2.num_cached_tokens == 12
        for pg, op in zip(r2.page_ids[:3], p1):
            assert (dev[pg] == orig[op]).all()
        assert tier.num_host_pages == 0

    def test_a_page_shed_before_its_settle_goes_through_evict_cb(self):
        """A reservation holds its room, so a settle never finds the
        pool without any; what can happen is that the pool sheds a
        reserved page for a newer one before its bytes came. The radix
        node goes through ``evict_cb`` as any other, and the settle
        does not bring the page back."""
        cm, tier, _dev = make_cm(host_pages=2)
        self._cached(cm)
        dropped = []
        real = tier.pool.evict_cb
        tier.set_evict_cb(lambda h: dropped.append(h) or real(h))
        cm.allocator.free(
            cm.prefix_cache.evict(2, demoter=partial_demoter(tier)))
        in_flight = list(tier.pool._pages)
        assert len(in_flight) == 2 and cm.prefix_cache.num_host_pages == 2
        # A parked image (pinned, all-or-nothing) needs both places.
        req = Request("p1", prompt_ids=list(range(100, 108)))
        assert cm.allocate_for_prompt(req)
        req.status = RequestStatus.DECODING
        req.num_computed_tokens = 8
        assert cm.preempt_to_host(req)
        assert sorted(dropped) == sorted(in_flight)
        assert cm.prefix_cache.num_host_pages == 0
        tier.settle()
        assert not set(in_flight) & set(tier.pool._pages)
        assert tier.num_host_pages == 2     # the image alone
        assert cm.resume_from_host(req)
        cm.release(req)

    def test_reset_frees_pages_still_in_flight(self):
        cm, tier, _dev = make_cm()
        self._cached(cm)
        cm.allocator.free(
            cm.prefix_cache.evict(3, demoter=partial_demoter(tier)))
        cm.reset_prefix_cache()
        assert tier.num_host_pages == 0
        tier.settle()                       # fills nothing
        assert tier.num_host_pages == 0 and not tier.pool._pages

    def test_bytes_survive_a_later_jitted_write_over_the_freed_pages(self):
        """The real transfers (CPU jit): the program enqueued after the
        gather overwrites the demoted pages before anything is read
        back, and the tier still holds what they held."""
        import jax
        import jax.numpy as jnp

        from parallax_tpu.runtime.host_cache import tier_from_paged_kv

        # Two layers of one page shape and a third of another: the
        # gather stacks the first two into one staging array.
        n, layers = 8, 3
        state = {"kv": [
            jnp.arange(n * 8, dtype=jnp.float32).reshape(n, 4, 2) + 1000 * i
            for i in range(2)
        ] + [jnp.arange(n * 6, dtype=jnp.float32).reshape(n, 3, 2) - 500]}
        orig = [np.array(a) for a in state["kv"]]
        tier = tier_from_paged_kv(
            1 << 20, lambda: state["kv"],
            lambda kv: state.__setitem__("kv", kv), n,
        )
        victims = [5, 2, 7]                 # 3 ids in a bucket of 4
        handles = tier.demote(victims, partial=True)
        assert all(h is not None for h in handles)
        ids = jnp.asarray(victims)
        state["kv"] = jax.jit(
            lambda kv: [a.at[ids].set(-1.0) for a in kv]
        )(state["kv"])
        assert (np.asarray(state["kv"][0])[victims] == -1.0).all()
        tier.settle()
        for h, p in zip(handles, victims):
            for i, page in enumerate(tier.pool.load(h)):
                assert (page == orig[i][p]).all()
        tier.promote(handles, [0, 1, 3])
        for i in range(layers):
            got = np.asarray(state["kv"][i])
            assert (got[[0, 1, 3]] == orig[i][victims]).all()
        assert tier.num_host_pages == 0 and tier.pages_swapped_in == 3


class TestPreemptionBookkeeping:
    def _decoding_request(self, cm, rid, n_prompt=8):
        req = Request(rid, prompt_ids=list(range(100, 100 + n_prompt)))
        assert cm.allocate_for_prompt(req)
        req.status = RequestStatus.DECODING
        req.num_computed_tokens = n_prompt
        return req

    def test_preempt_and_resume_roundtrip(self):
        cm, tier, dev = make_cm()
        req = self._decoding_request(cm, "p1")
        pages = list(req.page_ids)
        image = dev[np.asarray(pages)].copy()
        assert cm.preempt_to_host(req)
        assert req.page_ids == []
        assert cm.stats.preemptions == 1
        assert tier.num_host_pages == len(pages)
        for p in pages:
            dev[p] = -7.0
        assert cm.resume_from_host(req)
        assert len(req.page_ids) == len(pages)
        assert (dev[np.asarray(req.page_ids)] == image).all()
        assert tier.num_host_pages == 0
        cm.release(req)

    def test_preempted_image_is_pinned_against_pool_pressure(self):
        cm, tier, _dev = make_cm(host_pages=2)
        req = self._decoding_request(cm, "p1")
        assert cm.preempt_to_host(req)
        # radix demotions now cannot displace the parked image
        r2 = Request("r2", prompt_ids=list(range(8)))
        assert cm.allocate_for_prompt(r2)
        finish(cm, r2)
        freed = cm.prefix_cache.evict(2, demoter=partial_demoter(tier))
        assert len(freed) == 2               # dropped outright, pool full
        assert tier.num_host_pages == 2      # the parked image, untouched
        assert cm.resume_from_host(req)
        cm.release(req)

    def test_release_while_preempted_frees_host_image(self):
        cm, tier, _dev = make_cm()
        req = self._decoding_request(cm, "p1")
        assert cm.preempt_to_host(req)
        req.abort("timeout")
        cm.release(req)
        assert tier.num_host_pages == 0

    @pytest.mark.parametrize("host_pages, parked", [(8, True), (1, False)])
    def test_preempt_is_all_or_nothing_and_has_its_bytes(
            self, host_pages, parked):
        """The one demotion that settles at once: a parked image is on
        the host, whole, before the row's pages are freed — or nothing
        moved at all."""
        cm, tier, _dev = make_cm(host_pages=host_pages)
        req = self._decoding_request(cm, "p1")      # 2 pages
        pages = list(req.page_ids)
        free = cm.num_free_pages
        assert cm.preempt_to_host(req) is parked
        if parked:
            assert tier.reads == [2] and req.page_ids == []
            assert all(
                tier.pool.load(h)[0] is not None
                and isinstance(tier.pool.load(h)[0], np.ndarray)
                for h in req.host_page_handles
            )
            assert cm.num_free_pages == free + 2
        else:
            assert tier.reads == [] and req.page_ids == pages
            assert tier.num_host_pages == 0 and tier.pages_demoted == 0
            assert cm.num_free_pages == free
            assert not hasattr(req, "host_page_handles")

    def test_preempt_without_tier_is_refused(self):
        cm = CacheManager(page_size=PAGE, num_pages=PAGES)
        req = self._decoding_request(cm, "p1")
        assert not cm.preempt_to_host(req)
        assert req.page_ids            # untouched


# -- end-to-end: engine under pressure ------------------------------------


@pytest.fixture(scope="module")
def model_and_params():
    import jax
    import jax.numpy as jnp

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel

    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=258, max_position_embeddings=512,
        tie_word_embeddings=False,
    ))
    model = StageModel(cfg, 0, 2, use_pallas=False)
    params = model.init_params(jax.random.key(0), dtype=jnp.float32)
    return model, params


def _run_engine(model_and_params, num_pages, host_bytes, overlap=True,
                temp=0.0, seed=None, n=6, gen=24):
    from parallax_tpu.runtime.engine import (
        EngineConfig,
        StageEngine,
        drive_step,
    )

    model, params = model_and_params
    eng = StageEngine(model, params, EngineConfig(
        page_size=8, num_pages=num_pages, max_model_len=256,
        kv_dtype="float32", host_cache_bytes=host_bytes,
        overlap_steps=overlap,
    ))
    reqs = []
    for i in range(n):
        r = Request(f"r{i}", prompt_ids=[3 + i] * 12,
                    sampling_params=SamplingParams(
                        temperature=temp, seed=seed,
                        max_new_tokens=gen, ignore_eos=True))
        reqs.append(r)
        eng.submit(r)
    pending, guard = None, 0
    while (eng.has_work() or pending is not None) and guard < 5000:
        guard += 1
        _outs, pending = drive_step(eng, pending)
    assert guard < 5000, "engine made no progress"
    return reqs, eng


class TestEngineEndToEnd:
    def test_preempted_stream_bit_identical_greedy(self, model_and_params):
        base, _ = _run_engine(model_and_params, 256, 0)
        on, eng = _run_engine(model_and_params, 22, 1 << 24)
        stats = eng.cache_stats()
        assert stats["kv_oom_aborts"] == 0
        assert stats["preemptions"] > 0 and stats["resumes"] > 0
        for a, b in zip(base, on):
            assert b.status == a.status
            assert b.output_ids == a.output_ids

    def test_preempted_stream_bit_identical_seeded(self, model_and_params):
        base, _ = _run_engine(model_and_params, 256, 0, temp=0.8, seed=42)
        on, eng = _run_engine(model_and_params, 22, 1 << 24,
                              temp=0.8, seed=42)
        assert eng.cache_stats()["preemptions"] > 0
        for a, b in zip(base, on):
            assert b.output_ids == a.output_ids

    def test_preemption_in_sync_mode(self, model_and_params):
        base, _ = _run_engine(model_and_params, 256, 0, overlap=False)
        on, eng = _run_engine(model_and_params, 22, 1 << 24, overlap=False)
        assert eng.cache_stats()["kv_oom_aborts"] == 0
        for a, b in zip(base, on):
            assert b.output_ids == a.output_ids

    def test_tier_disabled_behavior_unchanged(self, model_and_params):
        """host_cache_bytes=0 keeps today's behavior: pressure aborts
        with kv_oom and survivors' streams match the unpressured run."""
        base, _ = _run_engine(model_and_params, 256, 0)
        off, eng = _run_engine(model_and_params, 22, 0)
        stats = eng.cache_stats()
        assert stats["preemptions"] == 0
        assert stats["kv_oom_aborts"] > 0
        assert any(r.abort_reason == "kv_oom" for r in off)
        for a, b in zip(base, off):
            if b.abort_reason is None:
                assert b.output_ids == a.output_ids

    def test_host_tier_prefix_reuse_across_turns(self, model_and_params):
        """Follow-up turns re-hit demoted context pages from the host
        tier (tokens_hit_host > 0) and swap them back in."""
        from parallax_tpu.runtime.engine import (
            EngineConfig,
            StageEngine,
            drive_step,
        )

        model, params = model_and_params
        eng = StageEngine(model, params, EngineConfig(
            page_size=8, num_pages=22, max_model_len=256,
            kv_dtype="float32", host_cache_bytes=1 << 24,
        ))

        def wave(reqs):
            for r in reqs:
                eng.submit(r)
            pending, guard = None, 0
            while (eng.has_work() or pending is not None) and guard < 5000:
                guard += 1
                _outs, pending = drive_step(eng, pending)
            return reqs

        w1 = wave([
            Request(f"a{i}", prompt_ids=[5 + i] * 24,
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=16,
                        ignore_eos=True))
            for i in range(4)
        ])
        w2 = wave([
            Request(f"b{i}", prompt_ids=r.all_token_ids + [9, 9, 9, 9],
                    sampling_params=SamplingParams(
                        temperature=0.0, max_new_tokens=16,
                        ignore_eos=True))
            for i, r in enumerate(w1)
        ])
        stats = eng.cache_stats()
        assert stats["kv_oom_aborts"] == 0
        assert all(r.abort_reason is None for r in w2)
        assert stats["tokens_hit_host"] > 0
        assert stats["pages_demoted"] > 0
        assert stats["pages_swapped_in"] > 0

    def test_an_eviction_inside_a_plan_settles_after_the_read_back(
            self, model_and_params, monkeypatch):
        """A pool the prefix cache fills, one row at a time (so nothing
        is preempted): every admission evicts, inside the scheduler's
        plan. The demotion enqueues there (``cache.demote_enqueue``) and
        its blocking read (``cache.demote_settle``, the tier's one
        ``np.asarray`` of staged arrays) never runs under
        ``sched.form_plan`` or ``engine.pack``: it comes after a
        read-back, outside both. Second turns then hit the demoted pages
        and the streams equal an unpressured engine's."""
        from parallax_tpu.obs import trace as obs_trace
        from parallax_tpu.runtime.engine import (
            EngineConfig,
            StageEngine,
            drive_step,
        )

        log = []

        class Annotation:
            def __init__(self, name, **args):
                self.name = name

            def __enter__(self):
                log.append(("enter", self.name))

            def __exit__(self, *exc):
                log.append(("exit", self.name))

        monkeypatch.setattr(obs_trace, "_annotations",
                            (Annotation, Annotation))
        model, params = model_and_params

        def serve(num_pages, host_bytes):
            eng = StageEngine(model, params, EngineConfig(
                page_size=8, num_pages=num_pages, max_model_len=256,
                kv_dtype="float32", host_cache_bytes=host_bytes,
            ))
            turns = []
            for turn in range(2):
                for i in range(6):
                    prompt = [7 + i] * 24
                    if turn:
                        prompt = turns[i].all_token_ids + [9, 9, 9, 9]
                    r = Request(f"t{turn}-{i}", prompt_ids=prompt,
                                sampling_params=SamplingParams(
                                    temperature=0.0, max_new_tokens=16,
                                    ignore_eos=True))
                    eng.submit(r)
                    pending, guard = None, 0
                    while ((eng.has_work() or pending is not None)
                           and guard < 5000):
                        guard += 1
                        _outs, pending = drive_step(eng, pending)
                    turns.append(r)
            return turns, eng

        base, _ = serve(256, 0)
        del log[:]
        on, eng = serve(20, 1 << 24)
        stats = eng.cache_stats()
        assert stats["preemptions"] == 0 and stats["kv_oom_aborts"] == 0
        assert stats["pages_demoted"] > 0 and stats["tokens_hit_host"] > 0
        assert [r.output_ids for r in on] == [r.output_ids for r in base]
        assert not eng.host_tier._unsettled

        plan = {"parallax.sched.form_plan", "parallax.engine.pack"}
        open_spans, enqueues, settles, last_exit = [], 0, 0, None
        for what, name in log:
            if what == "exit":
                open_spans.remove(name)
                last_exit = name
                continue
            if name == "parallax.cache.demote_enqueue":
                enqueues += 1
                assert plan & set(open_spans), open_spans
            elif name == "parallax.cache.demote_settle":
                settles += 1
                assert not plan & set(open_spans), open_spans
                # resolve's last span has closed: the step is read back.
                assert last_exit == "parallax.engine.commit", last_exit
            open_spans.append(name)
        assert enqueues > 0 and 0 < settles <= enqueues

