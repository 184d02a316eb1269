"""Native (C++) cache structures: behavior parity with the Python oracle.

Runs the behavioral suite on both implementations plus a randomized
differential test, and confirms the engine works end-to-end on the native
structures (tests elsewhere run with PARALLAX_TPU_NO_NATIVE unset, so the
whole suite exercises whichever impl CacheManager picked).
"""

import numpy as np
import pytest

from parallax_tpu.runtime.allocator import OutOfPages, PageAllocator
from parallax_tpu.runtime.radix_cache import RadixPageCache

from parallax_tpu import native


@pytest.fixture(params=["python", "native"])
def impls(request):
    if request.param == "python":
        return PageAllocator(64), RadixPageCache(4)
    return native.NativePageAllocator(64), native.NativeRadixPageCache(4)


class TestBehaviorParity:
    def test_alloc_free_cycle(self, impls):
        alloc, _ = impls
        pages = alloc.alloc(10)
        assert len(set(pages)) == 10 and 0 not in pages
        assert alloc.num_free == 53
        alloc.free(pages[:5])
        assert alloc.num_free == 58
        with pytest.raises(OutOfPages):
            alloc.alloc(1000)

    def test_match_insert_evict(self, impls):
        _, tree = impls
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
        freed = tree.evict(3)
        assert sorted(freed) == [5, 6, 7] or len(freed) == 3
        assert tree.num_cached_pages == 0

    def test_partial_lock_path(self, impls):
        _, tree = impls
        tokens = list(range(8))
        tree.insert(tokens, [3, 4])
        pages, full = tree.match_prefix(tokens)
        part = tree.slice_path(full, 1)
        tree.lock(part)
        freed = tree.evict(2)
        assert freed == [4]  # leaf evictable, pinned root page is not
        tree.unlock(part)
        assert sorted(tree.evict(2)) == [3]

    def test_reset_returns_all(self, impls):
        _, tree = impls
        tree.insert(list(range(8)), [1, 2])
        tree.insert([9] * 4, [3])
        assert sorted(tree.reset()) == [1, 2, 3]
        assert tree.num_cached_pages == 0


def test_randomized_differential():
    """Same random op sequence on both impls => same observable state."""
    rng = np.random.default_rng(0)
    py = RadixPageCache(4)
    nat = native.NativeRadixPageCache(4)
    next_page = [1]

    def rand_tokens():
        n_pages = int(rng.integers(1, 5))
        # small alphabet to force shared prefixes
        return [int(x) for x in rng.integers(0, 3, size=n_pages * 4)]

    for step in range(300):
        op = rng.random()
        if op < 0.5:
            toks = rand_tokens()
            pages = list(range(next_page[0], next_page[0] + len(toks) // 4))
            next_page[0] += len(pages)
            d1 = py.insert(toks, pages)
            d2 = nat.insert(toks, pages)
            assert d1 == d2, (step, d1, d2)
        elif op < 0.85:
            toks = rand_tokens()
            p1, _ = py.match_prefix(toks)
            p2, _ = nat.match_prefix(toks)
            assert p1 == p2, (step, p1, p2)
        else:
            n = int(rng.integers(1, 4))
            f1 = py.evict(n)
            f2 = nat.evict(n)
            # LRU tie-breaking may differ in order; sets must agree given
            # identical access patterns.
            assert sorted(f1) == sorted(f2), (step, f1, f2)
        assert py.num_cached_pages == nat.num_cached_pages, step


def test_engine_runs_on_native_cache():
    import jax
    import jax.numpy as jnp

    from parallax_tpu.config import normalize_config
    from parallax_tpu.models.base import StageModel
    from parallax_tpu.runtime.engine import EngineConfig, StageEngine
    from parallax_tpu.runtime.pipeline import InProcessPipeline
    from parallax_tpu.runtime.request import Request, SamplingParams

    cfg = normalize_config(dict(
        architectures=["Qwen2ForCausalLM"], hidden_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        intermediate_size=128, vocab_size=151,
    ))
    m = StageModel(cfg, 0, 2, use_pallas=False)
    # Native is the default cache manager; nothing to toggle.
    eng = StageEngine(
        m, m.init_params(jax.random.key(0), dtype=jnp.float32),
        EngineConfig(page_size=8, num_pages=64, max_model_len=128,
                     kv_dtype="float32"),
    )
    assert type(eng.cache.prefix_cache).__name__ == "NativeRadixPageCache"
    pipe = InProcessPipeline([eng])
    shared = list(range(1, 20))
    r1 = Request("a", prompt_ids=shared + [40],
                 sampling_params=SamplingParams(temperature=0.0,
                                                max_new_tokens=5))
    pipe.submit(r1)
    pipe.run_until_complete()
    r2 = Request("b", prompt_ids=shared + [50],
                 sampling_params=SamplingParams(temperature=0.0,
                                                max_new_tokens=5))
    pipe.submit(r2)
    pipe.run_until_complete()
    assert len(r1.output_ids) == 5 and len(r2.output_ids) == 5
    assert r2.num_cached_tokens == 16


def _mk_req(rid, prompt):
    from parallax_tpu.runtime.request import Request, SamplingParams

    return Request(request_id=rid, prompt_ids=list(prompt),
                   sampling_params=SamplingParams())


def test_cache_manager_differential():
    """Full-manager differential: identical request lifecycles through the
    Python CacheManager and the batched-ABI NativeCacheManager must leave
    identical observable state (free pages, cached pages, admission
    outcomes, prefix-hit counts)."""
    from parallax_tpu.runtime.cache_manager import CacheManager
    from parallax_tpu.runtime.request import RequestStatus

    rng = np.random.default_rng(1)
    py = CacheManager(page_size=4, num_pages=64)
    nat = native.NativeCacheManager(page_size=4, num_pages=64)
    live: list[tuple] = []

    for step in range(400):
        op = rng.random()
        if op < 0.45 or not live:
            n = int(rng.integers(1, 40))
            prompt = [int(x) for x in rng.integers(0, 3, size=n)]
            r1 = _mk_req(f"p{step}", prompt)
            r2 = _mk_req(f"p{step}", prompt)
            ok1 = py.allocate_for_prompt(r1)
            ok2 = nat.allocate_for_prompt(r2)
            assert ok1 == ok2, step
            if ok1:
                assert r1.num_cached_tokens == r2.num_cached_tokens, step
                r1.num_computed_tokens = r2.num_computed_tokens = n
                live.append((r1, r2))
        elif op < 0.7:
            r1, r2 = live[int(rng.integers(len(live)))]
            grow = r1.total_len + int(rng.integers(1, 9))
            # simulate decode progress: tokens committed + computed
            new = [int(x) for x in
                   rng.integers(0, 3, size=grow - r1.total_len)]
            for t in new:
                r1.output_ids.append(t)
                r2.output_ids.append(t)
            ok1 = py.ensure_capacity(r1, r1.total_len)
            ok2 = nat.ensure_capacity(r2, r2.total_len)
            assert ok1 == ok2, step
            r1.num_computed_tokens = r2.num_computed_tokens = (
                r1.total_len - 1
            )
        else:
            idx = int(rng.integers(len(live)))
            r1, r2 = live.pop(idx)
            status = (RequestStatus.FINISHED_ABORT if rng.random() < 0.2
                      else RequestStatus.FINISHED_EOS)
            r1.status = r2.status = status
            py.release(r1)
            nat.release(r2)
        assert py.num_free_pages == nat.num_free_pages, step
        assert (py.prefix_cache.num_cached_pages
                == nat.prefix_cache.num_cached_pages), step


def test_native_manager_faster_than_python():
    """The batched ABI must beat the Python manager in the production
    regime — a full prefix cache under eviction pressure with real prompt
    lengths (the round-1 per-call variant measured 0.4-1.0x; the do-or-
    delete bar from that review). Measured here: ~3-16x (ratio grows with
    prompt length; only toy sub-256-token workloads with an empty cache
    are comparable)."""
    import time

    from parallax_tpu.runtime.cache_manager import CacheManager
    from parallax_tpu.runtime.request import RequestStatus

    rng = np.random.default_rng(2)
    prompts = [
        [int(x) for x in rng.integers(0, 5, size=1024)] for _ in range(8)
    ]
    kw = dict(page_size=16, num_pages=260)  # < working set: eviction-bound

    def run(cm, n_iter=60):
        t0 = time.perf_counter()
        for i in range(n_iter):
            req = _mk_req(f"r{i}", prompts[i % len(prompts)])
            if not cm.allocate_for_prompt(req):
                continue
            req.num_computed_tokens = req.num_prompt_tokens
            req.output_ids = [1]
            cm.ensure_capacity(req, req.total_len)
            req.status = RequestStatus.FINISHED_EOS
            cm.release(req)
        return time.perf_counter() - t0

    run(native.NativeCacheManager(**kw), 10)  # warmup: lib load
    t_py = run(CacheManager(**kw))
    t_nat = run(native.NativeCacheManager(**kw))
    print(f"python {t_py*1e3:.1f} ms vs native {t_nat*1e3:.1f} ms "
          f"({t_py/t_nat:.2f}x)")
    assert t_nat < t_py, (t_py, t_nat)


def test_linear_state_cache_manager_differential():
    """Hybrid differential: the linear-slot semantics (match truncation
    to snapshot-carrying nodes, restore-slot surfacing, snapshot attach
    on release, orphaned-slot draining on eviction) must be identical
    between the Python CacheManager and the native one."""
    from parallax_tpu.runtime.cache_manager import CacheManager
    from parallax_tpu.runtime.request import RequestStatus

    rng = np.random.default_rng(7)
    freed_py, freed_nat = [], []
    py = CacheManager(page_size=4, num_pages=48, linear_state=True,
                      on_slot_free=freed_py.append)
    nat = native.NativeCacheManager(page_size=4, num_pages=48,
                                    linear_state=True,
                                    on_slot_free=freed_nat.append)
    next_slot = [1]
    live: list[tuple] = []

    for step in range(400):
        op = rng.random()
        if op < 0.5 or not live:
            n = int(rng.integers(2, 32))
            prompt = [int(x) for x in rng.integers(0, 3, size=n)]
            r1 = _mk_req(f"p{step}", prompt)
            r2 = _mk_req(f"p{step}", prompt)
            ok1 = py.allocate_for_prompt(r1)
            ok2 = nat.allocate_for_prompt(r2)
            assert ok1 == ok2, step
            if ok1:
                assert r1.num_cached_tokens == r2.num_cached_tokens, step
                assert (getattr(r1, "restore_state_from", None)
                        == getattr(r2, "restore_state_from", None)), step
                r1.num_computed_tokens = r2.num_computed_tokens = n
                live.append((r1, r2))
        else:
            idx = int(rng.integers(len(live)))
            r1, r2 = live.pop(idx)
            # Half the finishes carry snapshots at aligned boundaries.
            if rng.random() < 0.6:
                snaps = {}
                aligned = (r1.num_computed_tokens // 4) * 4
                if aligned >= 4:
                    slot = next_slot[0]
                    next_slot[0] += 1
                    snaps["prefill"] = (aligned, slot)
                    if aligned >= 8 and rng.random() < 0.5:
                        slot2 = next_slot[0]
                        next_slot[0] += 1
                        snaps = {"prefill": (aligned - 4, slot),
                                 "decode": (aligned, slot2)}
                if snaps:
                    r1.state_snapshots = dict(snaps)
                    r2.state_snapshots = dict(snaps)
            status = (RequestStatus.FINISHED_ABORT if rng.random() < 0.2
                      else RequestStatus.FINISHED_EOS)
            r1.status = r2.status = status
            py.release(r1)
            nat.release(r2)
        assert py.num_free_pages == nat.num_free_pages, step
        assert (py.prefix_cache.num_cached_pages
                == nat.prefix_cache.num_cached_pages), step
        assert sorted(freed_py) == sorted(freed_nat), step
    # Exercised both hit and slot-recycling paths.
    assert freed_py, "fuzz never freed a snapshot slot"

    # LRU slot detach agrees too (engine slot-steal path).
    d1 = py.prefix_cache.detach_lru_linear_slot()
    d2 = nat.prefix_cache.detach_lru_linear_slot()
    assert (d1 is None) == (d2 is None)
