"""Host-side runtime control plane: ctypes bindings for the native
continuous-batching scheduler + a pure-Python mirror (SURVEY.md §2 #5).

The C++ library (native/orion_runtime.cc) is compiled on first use with
g++ into ``native/_build/`` and loaded via ctypes — no pybind11
dependency.  ``Scheduler`` prefers the native implementation and falls
back to :class:`PyScheduler` when no toolchain is available; both obey
the identical contract (cross-checked step-for-step in
tests/test_runtime_native.py).

Contract (PR 8 serving rework): ON-DEMAND page allocation with
mid-flight recycling — admission grants pages for the prompt + first
token only, ``extend`` grows a running request segment by segment
(PR 10: plus an optional speculative-verify ``slack`` of draft
positions past the growth target, rolled back in place on rejection,
never freed), and ``preempt`` frees + requeues for restart when the
pool runs dry.
Admission is watermark-gated and policy-ordered (fifo / priority /
deadline-EDF, no overtaking within the order).  Cross-request prefix
caching shares hash-matched full prompt pages read-only (refcounted,
LRU-evictable at refs==0, graduated into the cache by ``finish``).
LIFO page reuse.

PR 12 (multi-tenant serving QoS): every request carries a ``tenant``
id; admission first picks the backlogged tenant with the lowest
integer virtual service (``vserv += admitted_tokens * 4096 //
weight``), filtered by each tenant's ``max_running`` concurrency cap
(reserved capacity), then applies the configured policy within that
tenant — register envelopes via ``set_tenant(tenant, weight,
max_running)``.  One uncapped tenant degrades exactly to the
single-queue order.  ``cancel`` removes a waiting request (the
engine's abort path).

PR 17 (tiered KV cache): every LRU eviction of a refs==0 cached page
is recorded as a (hash, page) event for ``drain_evictions`` — the
engine's hook for spilling the page's KV to a host-RAM tier before the
page is overwritten — and ``insert_cached(hash)`` re-admits a
host-tier hash device-side (``cache_lookup`` probes for it first).
The scheduler never touches KV bytes, so both implementations stay
bit-identical.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "native", "orion_runtime.cc")
_BUILD_DIR = os.path.join(_HERE, "native", "_build")
_SO = os.path.join(_BUILD_DIR, "liborion_runtime.so")
_FAIL = _SO + ".fail"

_lib = None
_lib_lock = threading.Lock()
# Negative-result memo (per source hash): a missing/broken g++ must not
# re-run the 120 s-timeout subprocess attempt on every Scheduler()
# construction — once a hash has failed to build, later constructions
# in this process (and, via the .fail sentinel, later processes) fall
# straight back to PyScheduler until the source changes.
_load_failed_hash: Optional[str] = None
# Why the last build attempt fell back (None = it did not): the
# fallback itself stays quiet, so callers that must not be served by
# PyScheduler unawares (chip_smoke.py) print this.
last_build_error: Optional[str] = None

POLICIES = {"fifo": 0, "priority": 1, "deadline": 2}
NO_DEADLINE = -1


def _src_hash() -> str:
    import hashlib

    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _compile() -> Optional[str]:
    """Build the .so iff missing or the source hash changed.

    Freshness is content-hashed, not mtime-based: checkout mtimes are
    arbitrary after a clone, and the build dir is gitignored (no binary
    is ever committed — ADVICE r1).  A FAILED build is also memoized
    per source hash (the ``.fail`` sentinel), so a toolchain-less box
    pays the compile attempt once, not per construction.
    """
    global last_build_error
    os.makedirs(_BUILD_DIR, exist_ok=True)
    hash_file = _SO + ".sha256"
    want = _src_hash()
    if os.path.exists(_SO) and os.path.exists(hash_file):
        with open(hash_file) as f:
            if f.read().strip() == want:
                return _SO
    try:
        with open(_FAIL) as f:
            if f.read().strip() == want:
                last_build_error = f"memoized failure ({_FAIL})"
                return None
    except OSError:
        pass
    cmd = ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", _SRC, "-o", _SO]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        with open(hash_file, "w") as f:
            f.write(want)
        try:
            os.remove(_FAIL)
        except OSError:
            pass
        return _SO
    except subprocess.TimeoutExpired as e:
        last_build_error = repr(e)
        # Transient (loaded box): fall back for THIS process (the
        # in-process memo still stops repeat attempts) but never write
        # the cross-process sentinel — a one-off slow CI run must not
        # disable the native scheduler for the checkout forever.
        return None
    except (OSError, subprocess.SubprocessError) as e:
        # Deterministic per source/toolchain (g++ missing, compile
        # error): memoize across processes until the source changes.
        stderr = getattr(e, "stderr", None) or b""
        last_build_error = f"{e!r} {stderr[-500:].decode(errors='replace')}"
        try:
            with open(_FAIL, "w") as f:
                f.write(want)
        except OSError:
            pass
        return None


def _load():
    global _lib, _load_failed_hash
    with _lib_lock:
        if _lib is not None:
            return _lib
        want = _src_hash()
        if _load_failed_hash == want:
            return None
        try:
            lib = _bind(_compile())
        except OSError:
            # Incompatible/corrupt binary (e.g. copied from another
            # arch) whose content hash still matches: self-heal by
            # discarding it and rebuilding once; fall back to
            # PyScheduler only if the rebuild also fails to load.
            for p in (_SO, _SO + ".sha256"):
                try:
                    os.remove(p)
                except OSError:
                    pass
            try:
                lib = _bind(_compile())
            except OSError:
                lib = None
        if lib is None:
            _load_failed_hash = want
            return None
        _lib = lib
        return _lib


def _bind(so: Optional[str]):
    if so is None:
        return None
    lib = ctypes.CDLL(so)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.osch_create.restype = ctypes.c_void_p
    lib.osch_create.argtypes = [ctypes.c_int] * 5
    lib.osch_destroy.argtypes = [ctypes.c_void_p]
    lib.osch_add.restype = ctypes.c_int
    lib.osch_add.argtypes = [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int,
                             ctypes.c_int, ctypes.c_int, ctypes.c_int64,
                             i64p, ctypes.c_int, ctypes.c_int64]
    lib.osch_add_group.restype = ctypes.c_int
    lib.osch_add_group.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                   ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                   ctypes.c_int, ctypes.c_int64, i64p,
                                   ctypes.c_int, ctypes.c_int64]
    lib.osch_set_tenant.restype = ctypes.c_int
    lib.osch_set_tenant.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                    ctypes.c_int64, ctypes.c_int64]
    lib.osch_set_watermark.restype = ctypes.c_int
    lib.osch_set_watermark.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.osch_cancel.restype = ctypes.c_int
    lib.osch_cancel.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.osch_admit.restype = ctypes.c_int
    lib.osch_admit.argtypes = [ctypes.c_void_p, i64p, i32p, ctypes.c_int]
    lib.osch_pages.restype = ctypes.c_int
    lib.osch_pages.argtypes = [ctypes.c_void_p, ctypes.c_int64, i32p,
                               ctypes.c_int]
    lib.osch_extend.restype = ctypes.c_int
    lib.osch_extend.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                ctypes.c_int, ctypes.c_int]
    for name in ("osch_cache_lookup", "osch_insert_cached"):
        fn = getattr(lib, name)
        fn.restype = ctypes.c_int
        fn.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.osch_drain_evictions.restype = ctypes.c_int
    lib.osch_drain_evictions.argtypes = [ctypes.c_void_p, i64p, i32p,
                                         ctypes.c_int]
    for name in ("osch_slot", "osch_shared_count", "osch_cached_count",
                 "osch_preempt", "osch_finish"):
        fn = getattr(lib, name)
        fn.restype = ctypes.c_int
        fn.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    for name in ("osch_clear_cache", "osch_free_pages",
                 "osch_available_pages", "osch_cached_total",
                 "osch_waiting", "osch_running"):
        fn = getattr(lib, name)
        fn.restype = ctypes.c_int
        fn.argtypes = [ctypes.c_void_p]
    return lib


def native_available() -> bool:
    return _load() is not None


def _hash_buf(hashes: Sequence[int]):
    n = len(hashes)
    return (ctypes.c_int64 * max(n, 1))(*hashes), n


class _NativeScheduler:
    def __init__(self, num_pages: int, page_size: int, max_slots: int,
                 watermark: int = 0, policy: str = "fifo"):
        lib = _load()
        if lib is None:
            raise RuntimeError("native runtime unavailable (no g++?)")
        self._lib = lib
        self._h = lib.osch_create(num_pages, page_size, max_slots,
                                  watermark, POLICIES[policy])
        if not self._h:
            raise ValueError("bad scheduler parameters")
        self.max_slots = max_slots
        # Reused across pages() calls: a fresh 256 KB ctypes buffer per
        # call showed up at ~4 ms/wave in the serving-loop profile.
        self._pages_buf = (ctypes.c_int32 * (1 << 16))()
        # Reused drain_evictions buffers (same rationale).
        self._evh_buf = (ctypes.c_int64 * 4096)()
        self._evp_buf = (ctypes.c_int32 * 4096)()

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.osch_destroy(self._h)
            self._h = None

    def add(self, req_id: int, prompt_len: int, max_new: int,
            priority: int = 0, deadline: int = NO_DEADLINE,
            prefix_hashes: Sequence[int] = (), tenant: int = 0) -> None:
        buf, n = _hash_buf(prefix_hashes)
        self._lib.osch_add(self._h, req_id, prompt_len, max_new, priority,
                           deadline, buf, n, tenant)

    def add_group(self, first_id: int, prompt_len: int, max_new: int,
                  k: int, priority: int = 0, deadline: int = NO_DEADLINE,
                  prefix_hashes: Sequence[int] = (),
                  tenant: int = 0) -> None:
        buf, n = _hash_buf(prefix_hashes)
        if self._lib.osch_add_group(self._h, first_id, prompt_len, max_new,
                                    k, priority, deadline, buf, n,
                                    tenant) != 0:
            raise ValueError(
                f"group of {k} clones can never be admitted "
                f"(max_slots={self.max_slots})")

    def set_tenant(self, tenant: int, weight: int = 1,
                   max_running: int = 0) -> None:
        """Register a tenant's weighted-fair share (weight >= 1) and
        concurrency cap (max admitted members; 0 = unlimited)."""
        if self._lib.osch_set_tenant(self._h, tenant, weight,
                                     max_running) != 0:
            raise ValueError(
                f"bad tenant params: weight={weight} (>= 1), "
                f"max_running={max_running} (>= 0)")

    def set_watermark(self, watermark: int) -> None:
        """Re-aim the admission-headroom watermark online (the
        autopilot's page-pressure actuator); takes effect at the next
        ``admit``."""
        if self._lib.osch_set_watermark(self._h, int(watermark)) != 0:
            raise ValueError(
                f"watermark must be >= 0, got {watermark}")

    def cancel(self, req_id: int) -> None:
        """Remove a WAITING request (running ones are preempted first
        by the engine, which requeues them as waiting)."""
        if self._lib.osch_cancel(self._h, req_id) < 0:
            raise KeyError(req_id)

    def admit(self, max_out: Optional[int] = None) -> List[Tuple[int, int]]:
        if max_out is None:
            max_out = self.max_slots
        ids = (ctypes.c_int64 * self.max_slots)()
        slots = (ctypes.c_int32 * self.max_slots)()
        n = self._lib.osch_admit(self._h, ids, slots,
                                 min(max_out, self.max_slots))
        return [(int(ids[i]), int(slots[i])) for i in range(n)]

    def pages(self, req_id: int) -> List[int]:
        out = self._pages_buf
        n = self._lib.osch_pages(self._h, req_id, out, 1 << 16)
        if n < 0:
            raise KeyError(req_id)
        return [int(out[i]) for i in range(n)]

    def extend(self, req_id: int, total_tokens: int,
               slack: int = 0) -> int:
        n = self._lib.osch_extend(self._h, req_id, total_tokens, slack)
        if n == -2:
            raise KeyError(req_id)
        return n

    def preempt(self, req_id: int) -> None:
        if self._lib.osch_preempt(self._h, req_id) < 0:
            raise KeyError(req_id)

    def slot(self, req_id: int) -> int:
        s = self._lib.osch_slot(self._h, req_id)
        if s < 0:
            raise KeyError(req_id)
        return s

    def shared_count(self, req_id: int) -> int:
        n = self._lib.osch_shared_count(self._h, req_id)
        if n < 0:
            raise KeyError(req_id)
        return n

    def cached_count(self, req_id: int) -> int:
        n = self._lib.osch_cached_count(self._h, req_id)
        if n < 0:
            raise KeyError(req_id)
        return n

    def finish(self, req_id: int) -> int:
        n = self._lib.osch_finish(self._h, req_id)
        if n < 0:
            raise KeyError(req_id)
        return n

    def clear_cache(self) -> int:
        return self._lib.osch_clear_cache(self._h)

    def cache_lookup(self, h: int) -> int:
        """Device page currently caching chain-hash ``h``, or -1."""
        return self._lib.osch_cache_lookup(self._h, h)

    def insert_cached(self, h: int) -> int:
        """Re-admit host-tier hash ``h`` device-side as a refs==0
        cached page (LRU tail).  Returns the allocated page (upload the
        host KV into it before any other dispatch), -2 when already
        device-cached, -1 when no page is available."""
        return self._lib.osch_insert_cached(self._h, h)

    def drain_evictions(self) -> List[Tuple[int, int]]:
        """Pending (hash, page) LRU-eviction events in occurrence
        order; draining clears them.  Call promptly after any
        allocating operation — the KV is only intact until the engine's
        next pool write."""
        out: List[Tuple[int, int]] = []
        while True:
            n = self._lib.osch_drain_evictions(self._h, self._evh_buf,
                                               self._evp_buf, 4096)
            out.extend((int(self._evh_buf[i]), int(self._evp_buf[i]))
                       for i in range(n))
            if n < 4096:
                return out

    @property
    def free_pages(self) -> int:
        return self._lib.osch_free_pages(self._h)

    @property
    def available_pages(self) -> int:
        return self._lib.osch_available_pages(self._h)

    @property
    def cached_total(self) -> int:
        return self._lib.osch_cached_total(self._h)

    @property
    def waiting(self) -> int:
        return self._lib.osch_waiting(self._h)

    @property
    def running(self) -> int:
        return self._lib.osch_running(self._h)

    def stats(self) -> dict:
        return _sched_stats(self)


class PyScheduler:
    """Pure-Python mirror of the native scheduler (same contract,
    bit-identical decisions — every operation below is a line-for-line
    transliteration of the C++ and is cross-checked by the randomized
    property test in tests/test_runtime_native.py)."""

    def __init__(self, num_pages: int, page_size: int, max_slots: int,
                 watermark: int = 0, policy: str = "fifo"):
        if (num_pages <= 0 or page_size <= 0 or max_slots <= 0
                or watermark < 0 or policy not in POLICIES):
            raise ValueError("bad scheduler parameters")
        self._ps = page_size
        self._policy = POLICIES[policy]
        self._watermark = watermark
        # Reversed so .pop() hands out 0,1,2,... exactly like the native
        # LIFO free list (cross-checked in tests).
        self._free_pages = list(range(num_pages - 1, -1, -1))
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self._seq = 0
        self._waiting: list = []   # dicts, seq order for FIFO
        self._running: dict = {}   # req_id -> request dict
        self._groups: dict = {}    # head_id -> [pages, hashes, refs]
        self._cache_map: dict = {}     # hash -> page
        self._cached_pages: dict = {}  # page -> [hash, refs, orphan]
        self._avail: list = []         # refs==0 cached pages, LRU order
        self._tenants: dict = {}       # tenant -> [weight, vserv]
        self._vclock = 0               # last admission's service level
        self._evictions: list = []     # (hash, page) LRU spill events
        self.max_slots = max_slots

    _VSCALE = 4096  # integer virtual-service scale (mirror of kVScale)

    # -- enqueue --------------------------------------------------------
    def _catch_up(self, tenant) -> None:
        """A tenant (re-)entering the backlog catches its virtual
        clock up to the last admission's level — idle tenants bank no
        credit, new tenants start level with the field.  Judged on the
        PRE-insert queue (mirror of the native CatchUp)."""
        for w in self._waiting:
            if w["tenant"] == tenant:
                return
        t = self._tenants.setdefault(tenant, [1, 0, 0, 0])
        if t[1] < self._vclock:
            t[1] = self._vclock

    def _enqueue(self, req_id, prompt_len, max_new, k, priority, deadline,
                 hashes, tenant):
        cap = (prompt_len - 1) // self._ps if prompt_len > 0 else 0
        self._catch_up(tenant)
        self._waiting.append({
            "id": req_id, "plen": prompt_len, "mnew": max_new, "k": k,
            "prio": priority, "deadline": deadline, "tenant": tenant,
            "hashes": list(hashes)[:cap], "seq": self._seq})
        self._seq += 1

    def add(self, req_id: int, prompt_len: int, max_new: int,
            priority: int = 0, deadline: int = NO_DEADLINE,
            prefix_hashes: Sequence[int] = (), tenant: int = 0) -> None:
        self._enqueue(req_id, prompt_len, max_new, 1, priority, deadline,
                      prefix_hashes, tenant)

    def add_group(self, first_id: int, prompt_len: int, max_new: int,
                  k: int, priority: int = 0, deadline: int = NO_DEADLINE,
                  prefix_hashes: Sequence[int] = (),
                  tenant: int = 0) -> None:
        """Shared-prefix sampling group: k clones (ids first_id ..
        first_id+k-1) of one prompt; the group's freshly-computed full
        prompt pages are allocated once and refcounted.  Admission is
        all-or-nothing so the wave prefill writes them exactly once."""
        if not 1 <= k <= self.max_slots:
            raise ValueError(
                f"group of {k} clones can never be admitted "
                f"(max_slots={self.max_slots})")
        self._enqueue(first_id, prompt_len, max_new, k, priority, deadline,
                      prefix_hashes, tenant)

    def set_tenant(self, tenant: int, weight: int = 1,
                   max_running: int = 0) -> None:
        """Register a tenant's weighted-fair share (weight >= 1) and
        concurrency cap (max admitted members; 0 = unlimited)."""
        if weight < 1 or max_running < 0:
            raise ValueError(
                f"bad tenant params: weight={weight} (>= 1), "
                f"max_running={max_running} (>= 0)")
        t = self._tenants.setdefault(tenant, [1, 0, 0, 0])
        t[0] = weight
        t[2] = max_running

    def set_watermark(self, watermark: int) -> None:
        """Re-aim the admission-headroom watermark online (the
        autopilot's page-pressure actuator); takes effect at the next
        ``admit``."""
        if watermark < 0:
            raise ValueError(
                f"watermark must be >= 0, got {watermark}")
        self._watermark = int(watermark)

    def cancel(self, req_id: int) -> None:
        """Remove a WAITING request (running ones are preempted first
        by the engine, which requeues them as waiting)."""
        for i, w in enumerate(self._waiting):
            if w["id"] == req_id:
                del self._waiting[i]
                return
        raise KeyError(req_id)

    # -- page bookkeeping ----------------------------------------------
    def _available(self) -> int:
        return len(self._free_pages) + len(self._avail)

    def _alloc_page(self) -> int:
        if self._free_pages:
            return self._free_pages.pop()
        page = self._avail.pop(0)  # evict LRU unreferenced cached page
        self._evictions.append((self._cached_pages[page][0], page))
        del self._cache_map[self._cached_pages[page][0]]
        del self._cached_pages[page]
        return page

    def _ref_cached(self, page: int, count: int) -> None:
        ent = self._cached_pages[page]
        if ent[1] == 0:
            self._avail.remove(page)
        ent[1] += count

    def _unref_cached(self, page: int) -> None:
        ent = self._cached_pages[page]
        ent[1] -= 1
        if ent[1] == 0:
            if ent[2]:  # orphaned by clear_cache mid-flight
                del self._cached_pages[page]
                self._free_pages.append(page)
            else:
                self._avail.append(page)

    def _retire_page(self, page: int, has_hash: bool, h: int) -> int:
        if has_hash and h not in self._cache_map:
            self._cache_map[h] = page
            self._cached_pages[page] = [h, 0, False]
            self._avail.append(page)
            return 0
        self._free_pages.append(page)
        return 1

    # -- admission ------------------------------------------------------
    def _policy_better(self, a, b) -> bool:
        if self._policy == POLICIES["fifo"]:
            return a["seq"] < b["seq"]
        if self._policy == POLICIES["priority"]:
            return (a["prio"] > b["prio"]
                    or (a["prio"] == b["prio"] and a["seq"] < b["seq"]))
        # deadline: EDF, no-deadline sorts last
        inf = (1 << 63) - 1
        da = inf if a["deadline"] == NO_DEADLINE else a["deadline"]
        db = inf if b["deadline"] == NO_DEADLINE else b["deadline"]
        return da < db or (da == db and a["seq"] < b["seq"])

    def _select_waiting(self) -> int:
        """Returns -1 when no tenant may admit (all at their caps).
        Pick order: each tenant's POLICY HEAD (no overtaking within a
        tenant), tenants filtered by max_running, then the lowest-
        virtual-service eligible tenant (ties: smaller tenant id).
        With one uncapped tenant this degrades exactly to the pre-PR12
        single-queue order."""
        heads: dict = {}
        for i, w in enumerate(self._waiting):
            hi = heads.get(w["tenant"])
            if hi is None or self._policy_better(w, self._waiting[hi]):
                heads[w["tenant"]] = i
        best, best_t = -1, 0
        for tt, hi in heads.items():
            t = self._tenants[tt]
            if t[2] > 0 and t[3] + self._waiting[hi]["k"] > t[2]:
                continue  # at its concurrency cap: its queue waits
            if best < 0:
                best, best_t = hi, tt
                continue
            va, vb = t[1], self._tenants[best_t][1]
            if va < vb or (va == vb and tt < best_t):
                best, best_t = hi, tt
        return best

    def admit(self, max_out: Optional[int] = None) -> List[Tuple[int, int]]:
        if max_out is None:
            max_out = self.max_slots
        out = []
        while self._waiting and self._free_slots:
            pick = self._select_waiting()
            if pick < 0:
                break  # every backlogged tenant is at its cap
            head = self._waiting[pick]
            k = head["k"]
            full_prompt = head["plen"] // self._ps
            cached = 0
            hashes = head["hashes"]
            while (cached < len(hashes)
                   and hashes[cached] in self._cache_map):
                cached += 1
            shared_new = full_prompt - cached
            need_new = shared_new + k
            headroom = (self._watermark
                        if (self._running or out) else 0)
            # Cached prefix pages this admission will ref (refs 0->k)
            # leave the available pool when claimed — count them in
            # the availability check or a tight pool allocates past
            # empty (latent PR 8 bug; see the native twin).
            refed_avail = 0
            seen_pages = set()
            for h in hashes[:cached]:
                p = self._cache_map[h]
                if p not in seen_pages:
                    seen_pages.add(p)
                    if self._cached_pages[p][1] == 0:
                        refed_avail += 1
            if len(out) + k > max_out:
                break
            if len(self._free_slots) < k:
                break
            if self._available() < need_new + refed_avail + headroom:
                break
            self._waiting.pop(pick)
            # Weighted-fair accounting: the admitted tenant's virtual
            # service advances by its normalized token cost; the
            # global clock is the re-entry floor for idle tenants.
            t = self._tenants[head["tenant"]]
            t[1] += (head["plen"] + head["mnew"]) * k * self._VSCALE \
                // t[0]
            t[3] += k
            self._vclock = t[1]
            cached_list = [self._cache_map[h] for h in hashes[:cached]]
            for p in cached_list:
                self._ref_cached(p, k)
            shared_pages = [self._alloc_page() for _ in range(shared_new)]
            for j in range(k):
                slot = self._free_slots.pop()
                pages = cached_list + shared_pages + [self._alloc_page()]
                self._running[head["id"] + j] = {
                    "slot": slot, "pages": pages, "cached": cached,
                    "shared": shared_new if k > 1 else 0,
                    "group": head["id"] if k > 1 else None,
                    "plen": head["plen"], "mnew": head["mnew"],
                    "prio": head["prio"], "deadline": head["deadline"],
                    "tenant": head["tenant"],
                    "hashes": hashes, "seq": head["seq"]}
                out.append((head["id"] + j, slot))
            if k > 1:
                self._groups[head["id"]] = [shared_pages, hashes[cached:],
                                            k]
        return out

    # -- accessors ------------------------------------------------------
    def pages(self, req_id: int) -> List[int]:
        return list(self._running[req_id]["pages"])

    def slot(self, req_id: int) -> int:
        return self._running[req_id]["slot"]

    def shared_count(self, req_id: int) -> int:
        return self._running[req_id]["shared"]

    def cached_count(self, req_id: int) -> int:
        return self._running[req_id]["cached"]

    # -- growth / retirement -------------------------------------------
    def extend(self, req_id: int, total_tokens: int,
               slack: int = 0) -> int:
        """Grow to cover ``total_tokens`` positions + ``slack`` draft
        positions past them (speculative-verify extents: a verify
        chunk writes up to k rejected-draft positions that are rolled
        back in place, never freed — the reservation only grows).  The
        lifetime cap stretches by the same slack."""
        r = self._running[req_id]
        slack = max(0, slack)
        cap = -(-(r["plen"] + r["mnew"] + slack) // self._ps)
        need = min(-(-(total_tokens + slack) // self._ps), cap)
        cur = len(r["pages"])
        if need <= cur:
            return 0
        delta = need - cur
        if self._available() < delta:
            return -1
        for _ in range(delta):
            r["pages"].append(self._alloc_page())
        return delta

    def finish(self, req_id: int) -> int:
        r = self._running.pop(req_id)
        self._tenants[r["tenant"]][3] -= 1
        freed = 0
        for i in range(r["cached"]):
            self._unref_cached(r["pages"][i])
        priv_start = r["cached"] + r["shared"]
        for i in range(priv_start, len(r["pages"])):
            has_hash = r["group"] is None and i < len(r["hashes"])
            freed += self._retire_page(
                r["pages"][i], has_hash,
                r["hashes"][i] if has_hash else 0)
        self._free_slots.append(r["slot"])
        if r["group"] is not None:
            g = self._groups[r["group"]]
            g[2] -= 1
            if g[2] == 0:
                for i, p in enumerate(g[0]):
                    has_hash = i < len(g[1])
                    freed += self._retire_page(
                        p, has_hash, g[1][i] if has_hash else 0)
                del self._groups[r["group"]]
        return freed

    def preempt(self, req_id: int) -> None:
        """Free everything the request holds (no cache graduation — its
        pages may be only partially prefilled) and requeue it, as a
        SOLO request, at its original arrival position for
        restart-by-recompute."""
        r = self._running.pop(req_id)
        self._tenants[r["tenant"]][3] -= 1
        for i in range(r["cached"]):
            self._unref_cached(r["pages"][i])
        priv_start = r["cached"] + r["shared"]
        for i in range(priv_start, len(r["pages"])):
            self._free_pages.append(r["pages"][i])
        self._free_slots.append(r["slot"])
        if r["group"] is not None:
            g = self._groups[r["group"]]
            g[2] -= 1
            if g[2] == 0:
                for p in g[0]:
                    self._free_pages.append(p)
                del self._groups[r["group"]]
        entry = {"id": req_id, "plen": r["plen"], "mnew": r["mnew"],
                 "k": 1, "prio": r["prio"], "deadline": r["deadline"],
                 "tenant": r["tenant"],
                 "hashes": r["hashes"], "seq": r["seq"]}
        self._catch_up(r["tenant"])
        pos = 0
        while (pos < len(self._waiting)
               and self._waiting[pos]["seq"] < r["seq"]):
            pos += 1
        self._waiting.insert(pos, entry)

    def clear_cache(self) -> int:
        """Drop the prefix cache (stale weights): unreferenced pages go
        back to the free list in LRU order; still-referenced pages lose
        their mapping and free on their last unref."""
        n = 0
        while self._avail:
            p = self._avail.pop(0)
            del self._cache_map[self._cached_pages[p][0]]
            del self._cached_pages[p]
            self._free_pages.append(p)
            n += 1
        for ent in self._cached_pages.values():
            if not ent[2]:
                del self._cache_map[ent[0]]
                ent[2] = True
        return n

    def cache_lookup(self, h: int) -> int:
        """Device page currently caching chain-hash ``h``, or -1."""
        return self._cache_map.get(h, -1)

    def insert_cached(self, h: int) -> int:
        """Re-admit host-tier hash ``h`` device-side as a refs==0
        cached page (LRU tail).  Returns the allocated page (upload the
        host KV into it before any other dispatch), -2 when already
        device-cached, -1 when no page is available."""
        if h in self._cache_map:
            return -2
        if self._available() < 1:
            return -1
        page = self._alloc_page()
        self._cache_map[h] = page
        self._cached_pages[page] = [h, 0, False]
        self._avail.append(page)
        return page

    def drain_evictions(self) -> List[Tuple[int, int]]:
        """Pending (hash, page) LRU-eviction events in occurrence
        order; draining clears them.  Call promptly after any
        allocating operation — the KV is only intact until the engine's
        next pool write."""
        out = self._evictions
        self._evictions = []
        return out

    @property
    def free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def available_pages(self) -> int:
        return self._available()

    @property
    def cached_total(self) -> int:
        return len(self._cached_pages)

    @property
    def waiting(self) -> int:
        return len(self._waiting)

    @property
    def running(self) -> int:
        return len(self._running)

    def stats(self) -> dict:
        return _sched_stats(self)


def _sched_stats(sched) -> dict:
    """Page/queue gauges for telemetry (orion_tpu.obs): one dict read
    per wave, identical shape for both scheduler implementations."""
    return {
        "free_pages": int(sched.free_pages),
        "available_pages": int(sched.available_pages),
        "cached_pages": int(sched.cached_total),
        "waiting": int(sched.waiting),
        "running": int(sched.running),
    }


def Scheduler(num_pages: int, page_size: int, max_slots: int,
              watermark: int = 0, policy: str = "fifo"):
    """Native scheduler when the toolchain allows, PyScheduler otherwise."""
    if native_available():
        return _NativeScheduler(num_pages, page_size, max_slots,
                                watermark, policy)
    return PyScheduler(num_pages, page_size, max_slots, watermark, policy)
