"""Native (C++) host-side cache structures with ctypes bindings.

Exposes :class:`NativeRadixPageCache` and :class:`NativePageAllocator`,
drop-in replacements for the pure-Python versions in
``parallax_tpu/runtime``. The shared library builds on demand with g++.

Two tiers:
- Piecewise structures (``NativeRadixPageCache``/``NativePageAllocator``):
  one crossing per primitive — behavior-verified, but marshalling parity
  makes them only break-even vs Python.
- :class:`NativeCacheManager`: ONE crossing per scheduler operation
  (admit = match+lock+evict+alloc fused; grow; release =
  unlock+insert+free fused). Measured ~3-16x faster than the Python
  manager in the production regime (full prefix cache under eviction
  pressure; the ratio grows with prompt length). This is the default via
  ``runtime.cache_manager.make_cache_manager``; set
  ``PARALLAX_TPU_NO_NATIVE=1`` to force the Python oracle.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

from parallax_tpu.analysis.sanitizer import make_lock

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "radix_cache.cpp")
_LIB_PATH = os.path.join(_HERE, "libradix.so")
_lock = make_lock("native.build")
_lib = None


def _build() -> None:
    """Compile ``radix_cache.cpp`` (the one committed source) next to
    it. A failed build raises: the library is the default cache manager,
    and a process that silently ran the other one would not be the
    system its operator started."""
    # Compile to a process-unique temp path, then atomically rename: two
    # processes may build concurrently but never load a half-written .so.
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"native build failed: {' '.join(cmd)}\n"
            f"{e.stderr.decode(errors='replace')[-2000:]}"
        ) from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load_library():
    """Load (building if needed) the native library; None only when
    ``PARALLAX_TPU_NO_NATIVE`` asks for the Python manager."""
    global _lib
    if os.environ.get("PARALLAX_TPU_NO_NATIVE"):
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_LIB_PATH) or (
            os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC)
        ):
            _build()
        lib = ctypes.CDLL(_LIB_PATH)
        i32p = ctypes.POINTER(ctypes.c_int32)
        sigs = {
            "radix_new": ([ctypes.c_int32], ctypes.c_void_p),
            "radix_free": ([ctypes.c_void_p], None),
            "radix_num_pages": ([ctypes.c_void_p], ctypes.c_int64),
            "radix_match": (
                [ctypes.c_void_p, i32p, ctypes.c_int64, i32p, ctypes.c_int64],
                ctypes.c_int64,
            ),
            "radix_lock": (
                [ctypes.c_void_p, i32p, ctypes.c_int64, ctypes.c_int64,
                 ctypes.c_int32],
                None,
            ),
            "radix_insert": (
                [ctypes.c_void_p, i32p, ctypes.c_int64, i32p, ctypes.c_int64,
                 i32p, ctypes.c_int64],
                ctypes.c_int64,
            ),
            "radix_evict": (
                [ctypes.c_void_p, ctypes.c_int64, i32p], ctypes.c_int64
            ),
            "radix_reset": (
                [ctypes.c_void_p, i32p, ctypes.c_int64], ctypes.c_int64
            ),
            "alloc_new": ([ctypes.c_int32, ctypes.c_int32], ctypes.c_void_p),
            "alloc_free": ([ctypes.c_void_p], None),
            "alloc_num_free": ([ctypes.c_void_p], ctypes.c_int64),
            "alloc_take": (
                [ctypes.c_void_p, ctypes.c_int64, i32p], ctypes.c_int64
            ),
            "alloc_release": (
                [ctypes.c_void_p, i32p, ctypes.c_int64], None
            ),
            "cache_admit": (
                [ctypes.c_void_p, ctypes.c_void_p, i32p, ctypes.c_int64,
                 ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
                 i32p, ctypes.c_int64,
                 ctypes.POINTER(ctypes.c_int64), i32p],
                ctypes.c_int64,
            ),
            "cache_grow": (
                [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, i32p],
                ctypes.c_int64,
            ),
            "cache_release": (
                [ctypes.c_void_p, ctypes.c_void_p, i32p, ctypes.c_int64,
                 ctypes.c_int64, i32p, ctypes.c_int64, ctypes.c_int64,
                 ctypes.c_int32, ctypes.POINTER(ctypes.c_int64), i32p,
                 ctypes.c_int64, i32p],
                ctypes.c_int64,
            ),
            "radix_attach_slot": (
                [ctypes.c_void_p, i32p, ctypes.c_int64, ctypes.c_int32],
                ctypes.c_int32,
            ),
            "radix_detach_lru_slot": ([ctypes.c_void_p], ctypes.c_int32),
            "radix_take_freed_slots": (
                [ctypes.c_void_p, i32p, ctypes.c_int64], ctypes.c_int64
            ),
        }
        for name, (argtypes, restype) in sigs.items():
            fn = getattr(lib, name)
            fn.argtypes = argtypes
            fn.restype = restype
        _lib = lib
        return _lib


def _as_i32(xs) -> np.ndarray:
    return np.ascontiguousarray(xs, dtype=np.int32)


def _ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class NativeRadixPageCache:
    """ctypes facade matching ``runtime.radix_cache.RadixPageCache``.

    Lock paths are tracked by (token prefix, page count) instead of node
    objects; ``match_prefix`` returns that handle as its second element.
    """

    def __init__(self, page_size: int, on_evict=None, on_evict_slot=None):
        self._lib = load_library()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self.page_size = page_size
        self.on_evict = on_evict
        self.on_evict_slot = on_evict_slot
        self._h = self._lib.radix_new(page_size)

    def _drain_slots(self) -> None:
        """Return snapshot slots orphaned by eviction/reset to the
        engine's pool (mirrors the Python radix's on_evict_slot).
        No-op without a slot consumer — slots only exist for hybrid
        managers, and the drain must not cost the non-hybrid hot path
        an ABI crossing."""
        if self.on_evict_slot is None:
            return
        if not hasattr(self, "_slot_buf"):
            self._slot_buf = np.empty(64, np.int32)
        out = self._slot_buf
        while True:
            n = self._lib.radix_take_freed_slots(self._h, _ptr(out), 64)
            for s in out[:n].tolist():
                self.on_evict_slot(int(s))
            if n < 64:
                return

    def attach_linear_slot(self, token_ids, slot: int) -> bool:
        tokens = _as_i32(token_ids)
        return bool(self._lib.radix_attach_slot(
            self._h, _ptr(tokens), len(tokens), slot
        ))

    def detach_lru_linear_slot(self):
        slot = int(self._lib.radix_detach_lru_slot(self._h))
        return None if slot < 0 else slot

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.radix_free(self._h)
                self._h = None
        except Exception:
            pass

    @property
    def num_cached_pages(self) -> int:
        return int(self._lib.radix_num_pages(self._h))

    def match_prefix(self, token_ids):
        tokens = _as_i32(token_ids)
        cap = max(1, len(tokens) // self.page_size)
        out = np.empty(cap, np.int32)
        n = self._lib.radix_match(
            self._h, _ptr(tokens), len(tokens), _ptr(out), cap
        )
        pages = out[:n].tolist()
        return pages, (tokens[: n * self.page_size], n)

    def slice_path(self, path, n: int):
        tokens, _ = path
        return (tokens[: n * self.page_size], n)

    def lock(self, path) -> None:
        if not path:
            return
        tokens, n = path
        if n:
            self._lib.radix_lock(self._h, _ptr(tokens), len(tokens), n, 1)

    def unlock(self, path) -> None:
        if not path:
            return
        tokens, n = path
        if n:
            self._lib.radix_lock(self._h, _ptr(tokens), len(tokens), n, -1)

    def insert(self, token_ids, page_ids) -> list[int]:
        tokens = _as_i32(token_ids)
        pages = _as_i32(page_ids)
        dups = np.empty(max(1, len(pages)), np.int32)
        n = self._lib.radix_insert(
            self._h, _ptr(tokens), len(tokens), _ptr(pages), len(pages),
            _ptr(dups), len(dups),
        )
        return dups[:n].tolist()

    def evict(self, num_pages: int) -> list[int]:
        out = np.empty(max(1, num_pages), np.int32)
        n = self._lib.radix_evict(self._h, num_pages, _ptr(out))
        freed = out[:n].tolist()
        if self.on_evict:
            for p in freed:
                self.on_evict(p)
        self._drain_slots()
        return freed

    def reset(self) -> list[int]:
        cap = self.num_cached_pages or 1
        out = np.empty(cap, np.int32)
        n = self._lib.radix_reset(self._h, _ptr(out), cap)
        self._drain_slots()
        return out[:n].tolist()


class NativePageAllocator:
    """ctypes facade matching ``runtime.allocator.PageAllocator``."""

    def __init__(self, num_pages: int, reserve_null_page: bool = True):
        self._lib = load_library()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self.num_pages = num_pages
        self.null_page = 0 if reserve_null_page else -1
        self._h = self._lib.alloc_new(num_pages, int(reserve_null_page))

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.alloc_free(self._h)
                self._h = None
        except Exception:
            pass

    @property
    def num_free(self) -> int:
        return int(self._lib.alloc_num_free(self._h))

    def alloc(self, n: int) -> list[int]:
        from parallax_tpu.runtime.allocator import OutOfPages

        out = np.empty(max(1, n), np.int32)
        got = self._lib.alloc_take(self._h, n, _ptr(out))
        if got < 0:
            raise OutOfPages(f"need {n} pages, {self.num_free} free")
        return out[:n].tolist()

    def free(self, pages) -> None:
        if not len(pages):
            return
        arr = _as_i32(pages)
        self._lib.alloc_release(self._h, _ptr(arr), len(arr))

    def can_alloc(self, n: int) -> bool:
        return n <= self.num_free


class NativeCacheManager:
    """Fully-native CacheManager: ONE ABI crossing per scheduler operation
    (admit / grow / release), the batching the round-1 per-call variant
    lacked. Drop-in for ``runtime.cache_manager.CacheManager``."""

    def __init__(self, page_size: int, num_pages: int,
                 enable_prefix_cache: bool = True,
                 max_model_len: int = 32768,
                 linear_state: bool = False,
                 on_slot_free=None):
        self._lib = load_library()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self.page_size = page_size
        self.num_pages = num_pages
        self.max_model_len = max_model_len
        self.enable_prefix_cache = enable_prefix_cache
        # Hybrid models: matches truncate to snapshot-carrying nodes and
        # release attaches per-request snapshots (see the Python
        # CacheManager for the semantics; differential-fuzzed).
        self.linear_state = linear_state
        self.on_slot_free = on_slot_free
        self.prefix_cache = NativeRadixPageCache(
            page_size, on_evict_slot=on_slot_free
        )
        self.allocator = NativePageAllocator(num_pages)
        # rid -> number of tree-shared pages (for release's unlock walk).
        self._shared: dict[str, int] = {}
        # Per-adapter prefix-cache namespaces (cache_manager.ns_salt:
        # deterministic per adapter id, so replicas agree and routing
        # digests reproduce scheduler-side).
        self._ns_salts: dict[str, int] = {}
        # Observability counters (utils.request_metrics.cache_stats_summary
        # reads these; the native tier has no host cache, so host/preempt
        # fields stay zero).
        from parallax_tpu.utils.request_metrics import CacheStats

        self.stats = CacheStats()

    def _ns_i32(self, token_ids, lora_id) -> np.ndarray:
        """int32 tokens, XOR-salted at numpy speed for adapter requests
        (the scheduler hot path must stay free of per-token Python)."""
        from parallax_tpu.runtime.cache_manager import ns_salt

        tokens = _as_i32(token_ids)
        salt = ns_salt(self._ns_salts, lora_id)
        if salt is not None:
            tokens = tokens ^ np.int32(salt)
        return tokens

    # -- capacity ---------------------------------------------------------

    @property
    def num_free_pages(self) -> int:
        return self.allocator.num_free

    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    # -- request lifecycle ------------------------------------------------

    def allocate_for_prompt(self, request) -> bool:
        if self.linear_state and hasattr(request, "restore_state_from"):
            del request.restore_state_from  # stale from a failed admit
        tokens = self._ns_i32(
            request.prompt_ids, getattr(request, "lora_id", None)
        )
        cap = self.pages_needed(len(tokens)) + 1
        out = np.empty(cap, np.int32)
        shared = ctypes.c_int64(0)
        restore = np.full(1, -1, np.int32)
        head_cached = getattr(request, "mirror_head_cached", None)
        pages_cap = (
            head_cached // self.page_size
            if self.linear_state and head_cached is not None else -1
        )
        total = self._lib.cache_admit(
            self.prefix_cache._h, self.allocator._h,
            _ptr(tokens), len(tokens), int(self.enable_prefix_cache),
            int(self.linear_state), pages_cap,
            _ptr(out), cap, ctypes.byref(shared), _ptr(restore),
        )
        self.prefix_cache._drain_slots()   # admit may have evicted
        if total < 0:
            return False
        request.page_ids = out[:total].tolist()
        request.num_cached_tokens = int(shared.value) * self.page_size
        request.num_computed_tokens = request.num_cached_tokens
        if int(restore[0]) >= 0:
            request.restore_state_from = int(restore[0])
        self._shared[request.request_id] = int(shared.value)
        self.stats.tokens_admitted += len(tokens)
        self.stats.tokens_hit_device += request.num_cached_tokens
        return True

    def extra_pages(self, request, new_total_tokens: int) -> int:
        return max(
            0, self.pages_needed(new_total_tokens) - len(request.page_ids)
        )

    def ensure_capacity(self, request, new_total_tokens: int) -> bool:
        need = self.pages_needed(new_total_tokens) - len(request.page_ids)
        if need <= 0:
            return True
        out = np.empty(need, np.int32)
        got = self._lib.cache_grow(
            self.prefix_cache._h, self.allocator._h, need, _ptr(out)
        )
        self.prefix_cache._drain_slots()   # grow may have evicted
        if got < 0:
            return False
        request.page_ids.extend(out[:need].tolist())
        return True

    def extend_prefix_match(self, request) -> int:
        """Mid-prefill chunk skipping — semantics mirror
        ``CacheManager.extend_prefix_match`` (the behavioral oracle).
        This is a rare per-request event (a donor released after this
        request was admitted), not the admit/grow/release hot path, so
        per-call ABI crossings are fine here. The native tree has no
        host tier, so there is no host-node truncation case."""
        if not self.enable_prefix_cache:
            return 0
        if self.linear_state:
            # Linear-state skips need the recurrence snapshot wired at
            # the skip boundary, which only the admission match sets up.
            return 0
        if getattr(request, "mirror_head_cached", None) is not None:
            # Mirrors may only skip what the head skipped.
            return 0
        num_shared = self._shared.get(request.request_id)
        if num_shared is None:
            return 0
        prompt_len = request.num_prompt_tokens
        if prompt_len <= 1:
            return 0
        tokens = self._ns_i32(
            request.prompt_ids, getattr(request, "lora_id", None)
        )
        pages, full_path = self.prefix_cache.match_prefix(tokens)
        usable = min(len(pages), (prompt_len - 1) // self.page_size)
        if usable <= num_shared:
            return 0
        new_shared = pages[:usable]
        if new_shared[:num_shared] != request.page_ids[:num_shared]:
            # The tree's page chain diverged from what this request
            # pinned at admission — refuse rather than corrupt.
            return 0
        # Lock the longer path before unlocking the old one so shared
        # ancestors never drop to zero refs in between. The old locked
        # path is the num_shared-prefix of the same token stream.
        self.prefix_cache.lock(
            self.prefix_cache.slice_path(full_path, usable)
        )
        self.prefix_cache.unlock(
            self.prefix_cache.slice_path(full_path, num_shared)
        )
        self.allocator.free(request.page_ids[num_shared:usable])
        request.page_ids = new_shared + request.page_ids[usable:]
        request.num_cached_tokens = usable * self.page_size
        request.num_computed_tokens = usable * self.page_size
        self._shared[request.request_id] = usable
        skipped = (usable - num_shared) * self.page_size
        self.stats.tokens_hit_device += skipped
        self.stats.tokens_chunk_skipped += skipped
        return skipped

    def release(self, request) -> None:
        n_shared = self._shared.pop(request.request_id, 0)
        snapshots = list(getattr(request, "state_snapshots", {}).values())
        if hasattr(request, "state_snapshots"):
            del request.state_snapshots
        pages = _as_i32(request.page_ids)
        if not len(pages):
            if self.on_slot_free:
                for _length, slot in snapshots:
                    self.on_slot_free(slot)
            request.page_ids = []
            return
        tokens = self._ns_i32(
            request.all_token_ids, getattr(request, "lora_id", None)
        )
        computed = min(request.num_computed_tokens, len(tokens))
        insert = int(
            self.enable_prefix_cache
            and request.status.value != "finished_abort"
        )
        if snapshots:
            snap_lens = np.ascontiguousarray(
                [length for length, _ in snapshots], dtype=np.int64
            )
            snap_slots = _as_i32([slot for _, slot in snapshots])
            unattached = np.empty(len(snapshots), np.int32)
            n_un = self._lib.cache_release(
                self.prefix_cache._h, self.allocator._h,
                _ptr(tokens), len(tokens), computed,
                _ptr(pages), len(pages), n_shared, insert,
                snap_lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                _ptr(snap_slots), len(snapshots), _ptr(unattached),
            )
            if self.on_slot_free:
                for slot in unattached[:n_un].tolist():
                    self.on_slot_free(int(slot))
        else:
            # Non-hybrid fast path: zero extra allocations per release.
            self._lib.cache_release(
                self.prefix_cache._h, self.allocator._h,
                _ptr(tokens), len(tokens), computed,
                _ptr(pages), len(pages), n_shared, insert,
                None, None, 0, None,
            )
        request.page_ids = []

    def reset_prefix_cache(self) -> None:
        self.allocator.free(self.prefix_cache.reset())

