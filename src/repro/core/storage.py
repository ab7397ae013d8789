"""Storage backends: real file I/O + the calibrated N-lane SSD model.

The container has no NVMe array, but SSD count is the x-axis of the paper's
Figures 2-3.  ``SimulatedStorage`` reads real bytes from the local file but
*accounts* time against an N-lane model calibrated to the paper's GDS
observations:

    request_time(lane) = latency + size / lane_bandwidth

so a request's achieved bandwidth is  bw · s/(s + latency·bw)  — small
(~100 KB) requests reach less than half of a lane while MiB-scale requests
saturate it (Insight 2).  Requests stripe across lanes; a batch completes
when its slowest lane drains.  Every benchmark labels which numbers come
from this model vs. real measurement (DESIGN.md §2).

Request **coalescing** (Insight 2's configuration-level fix): adjacent or
near-adjacent byte ranges — e.g. the column chunks of one row group, which
the writer lays out back to back — merge into one large read when the gap
between them is at most ``coalesce_gap`` bytes.  The gap bytes are read and
discarded; with 20 µs request latency at 7 GB/s a request is worth ~140 KB,
so the default 64 KiB gap always pays on the modeled lanes (and costs one
page-cache copy on the real backend).

Both backends read with ``os.pread`` on a shared fd — positionless reads
need no seek lock, so the overlapped reader's I/O thread never serializes
against the decode thread's dictionary fetches.

Defaults: 7 GB/s per lane (PCIe4 NVMe, the paper's class of device), 20 µs
per-request latency on the accelerator DMA path.

**Object store** (``ObjectStoreStorage``): the remote profile next to the
NVMe model — per-request latency in the milliseconds (first-byte on an
S3-class store), a few parallel connections at ~GB/s each, and a much
larger default coalesce gap (at 8 ms × 1.2 GB/s a request is worth
~10 MB, so multi-MiB gap bytes are cheaper than a second round trip).
Unlike the NVMe model it *sleeps* its modeled request time by default:
remote latency is real wall time in production, so overlapping it
(fetch_threads > 1, prefetch, multi-device sharding) must show up in
measured wall, not only in the modeled schedule.

**Prefetch** (``PrefetchingStorage``): wraps a modeled backend with a
small background pool.  ``prefetch(ranges)`` issues reads ahead of
demand; a later ``fetch``/``fetch_batch`` for the same (offset, size)
consumes the buffered bytes and pays only the *residual* wait — the
portion of the modeled request time not yet elapsed — so remote latency
hides behind decode.  Hit/miss/hidden/stall counters land in
``prefetch_stats``; consumed prefetches account into the inner backend's
FetchStats at consumption time, so request counts stay deterministic for
the CI gate regardless of background-thread timing.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections.abc import Sequence

from repro.core import trace
from repro.core.compression import inflate_backend

DEFAULT_COALESCE_GAP = 64 * 1024

# object-store profile defaults: ms-scale first-byte latency, a few
# parallel connections, multi-MiB coalescing (see module docstring)
DEFAULT_OBJECT_LATENCY = 8e-3
DEFAULT_OBJECT_BANDWIDTH = 1.2e9
DEFAULT_OBJECT_CONNECTIONS = 4
DEFAULT_OBJECT_COALESCE_GAP = 4 * 1024 * 1024

#: per-request latency samples kept per FetchStats (bounded so a long
#: scan's observability never grows without bound)
LATENCY_SAMPLE_CAP = 4096


@dataclasses.dataclass
class FetchStats:
    requests: int = 0        # requests actually issued (post-coalescing)
    bytes: int = 0
    seconds: float = 0.0     # simulated (sim backend) or measured (real)
    batches: int = 0         # fetch_batch calls (one per row group in scans)
    last_batch_requests: int = 0
    # informational: which gzip-inflate backend decompresses the fetched
    # chunks downstream (isal / zlib-ng / zlib — core/compression.py)
    inflate_backend: str = inflate_backend()
    # per-request latency samples (modeled on sim/object, measured on
    # real) — the p50/p95 observability columns; bounded reservoir
    latencies: list = dataclasses.field(default_factory=list)

    def add(self, other: "FetchStats") -> None:
        self.requests += other.requests
        self.bytes += other.bytes
        self.seconds += other.seconds
        self.batches += other.batches
        if other.batches:
            self.last_batch_requests = other.last_batch_requests
        if other.latencies:
            room = LATENCY_SAMPLE_CAP - len(self.latencies)
            if room > 0:
                self.latencies.extend(other.latencies[:room])

    @property
    def requests_per_batch(self) -> float:
        return self.requests / max(1, self.batches)

    @property
    def bandwidth(self) -> float:
        return self.bytes / max(1e-12, self.seconds)

    def latency_us(self, q: float) -> float:
        """Per-request latency percentile in microseconds (0 when no
        samples were recorded)."""
        if not self.latencies:
            return 0.0
        import numpy as _np
        return float(_np.percentile(self.latencies, q)) * 1e6


def coalesce_ranges(ranges: Sequence[tuple[int, int]], gap: int
                    ) -> tuple[list[tuple[int, int]],
                               list[tuple[int, int]]]:
    """Merge byte ranges whose gaps are ≤ ``gap`` into large requests.

    Returns ``(merged, index)`` where ``merged`` is the ascending list of
    requests to issue and ``index[i] = (merged_idx, rel_off)`` locates input
    range ``i`` inside its merged request.
    """
    n = len(ranges)
    order = sorted(range(n), key=lambda i: ranges[i][0])
    merged: list[tuple[int, int]] = []
    index: list[tuple[int, int]] = [(0, 0)] * n
    for i in order:
        off, size = ranges[i]
        if merged:
            mo, ms = merged[-1]
            if mo <= off <= mo + ms + gap:
                merged[-1] = (mo, max(ms, off + size - mo))
                index[i] = (len(merged) - 1, off - mo)
                continue
        merged.append((off, size))
        index[i] = (len(merged) - 1, 0)
    return merged, index


def _slice_back(views: list[memoryview], index, ranges
                ) -> list[memoryview]:
    return [views[mi][rel:rel + size]
            for (mi, rel), (_, size) in zip(index, ranges)]


def fetch_coalesced(storage, ranges: Sequence[tuple[int, int]],
                    gap: int = DEFAULT_COALESCE_GAP
                    ) -> tuple[list[memoryview], float]:
    """Fetch ``ranges`` through ``storage`` as coalesced requests.

    Returns per-input-range zero-copy views into the merged buffers plus the
    batch time.  ``gap <= 0`` disables merging (every range is its own
    request) — the pre-coalescing baseline for benchmarks.
    """
    if gap <= 0:
        datas, dt = storage.fetch_batch(list(ranges))
        return [memoryview(d) for d in datas], dt
    merged, index = coalesce_ranges(ranges, gap)
    bufs, dt = storage.fetch_batch(merged)
    return _slice_back([memoryview(b) for b in bufs], index, ranges), dt


def fetch_ranges(fetch, ranges: Sequence[tuple[int, int]],
                 gap: int = DEFAULT_COALESCE_GAP) -> list[memoryview]:
    """Coalesced reads through a plain ``fetch(offset, size)`` callable
    (the reader's storage-agnostic path; no batch timing)."""
    if gap <= 0:
        return [memoryview(fetch(o, s)) for o, s in ranges]
    merged, index = coalesce_ranges(ranges, gap)
    views = [memoryview(fetch(o, s)) for o, s in merged]
    return _slice_back(views, index, ranges)


class RealStorage:
    """Direct file reads with measured wall time.

    Reads use ``os.pread`` so concurrent fetches (the overlapped reader's
    I/O thread alongside the decode thread) don't serialize on a shared
    file-position lock.
    """

    kind = "real"

    def __init__(self, path: str):
        self.path = path
        self._fd = os.open(path, os.O_RDONLY)
        self.stats = FetchStats()
        # the pipeline executor's fetch thread and decode workers may issue
        # concurrent reads; stats mutation is the only shared state
        self._stats_lock = threading.Lock()

    def close(self) -> None:
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _read(self, offset: int, size: int) -> bytes:
        return os.pread(self._fd, size, offset)

    def fetch(self, offset: int, size: int) -> bytes:
        t0 = time.perf_counter()
        data = os.pread(self._fd, size, offset)
        t1 = time.perf_counter()
        dt = t1 - t0
        with self._stats_lock:
            self.stats.add(FetchStats(1, len(data), dt, latencies=[dt]))
        tr = trace.active()
        if tr is not None:
            tr.complete("storage_read", "io", t0, t1, backend=self.kind,
                        offset=offset, bytes=len(data), n=1)
        return data

    def fetch_batch(self, requests: Sequence[tuple[int, int]]
                    ) -> tuple[list[bytes], float]:
        t0 = time.perf_counter()
        out = []
        lats = []
        for o, s in requests:
            t_r = time.perf_counter()
            out.append(os.pread(self._fd, s, o))
            lats.append(time.perf_counter() - t_r)
        t1 = time.perf_counter()
        dt = t1 - t0
        with self._stats_lock:
            self.stats.add(FetchStats(len(requests),
                                      sum(len(d) for d in out), dt,
                                      batches=1,
                                      last_batch_requests=len(requests),
                                      latencies=lats))
        tr = trace.active()
        if tr is not None:
            tr.complete("storage_read", "io", t0, t1, backend=self.kind,
                        bytes=sum(len(d) for d in out), n=len(requests))
        return out, dt


class SimulatedStorage:
    """N-lane SSD model over a real backing file.

    ``batch_seconds`` is the modeled completion time of a batch of requests
    issued together (per-RG in the scan engine): requests go to the
    least-loaded lane; the batch drains when the slowest lane finishes.
    """

    kind = "sim"

    def __init__(self, path: str, n_lanes: int = 1,
                 lane_bandwidth: float = 7e9, latency: float = 20e-6):
        self.path = path
        self.n_lanes = n_lanes
        self.lane_bandwidth = lane_bandwidth
        self.latency = latency
        self._fd = os.open(path, os.O_RDONLY)
        self.stats = FetchStats()
        self._stats_lock = threading.Lock()

    def close(self) -> None:
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _read(self, offset: int, size: int) -> bytes:
        return os.pread(self._fd, size, offset)

    def request_seconds(self, size: int) -> float:
        return self.latency + size / self.lane_bandwidth

    def batch_seconds(self, sizes: Sequence[int]) -> float:
        lanes = [0.0] * self.n_lanes
        for s in sorted(sizes, reverse=True):  # LPT assignment
            i = min(range(self.n_lanes), key=lanes.__getitem__)
            lanes[i] += self.request_seconds(s)
        return max(lanes) if lanes else 0.0

    def fetch(self, offset: int, size: int) -> bytes:
        tr = trace.active()
        t0 = time.perf_counter() if tr is not None else 0.0
        data = self._read(offset, size)
        dt = self.request_seconds(size)
        self._account(dt)
        with self._stats_lock:
            self.stats.add(FetchStats(1, len(data), dt, latencies=[dt]))
        if tr is not None:
            tr.complete("storage_read", "io", t0, time.perf_counter(),
                        backend=self.kind, offset=offset,
                        bytes=len(data), n=1, modeled_dt=dt)
        return data

    def fetch_batch(self, requests: Sequence[tuple[int, int]]
                    ) -> tuple[list[bytes], float]:
        tr = trace.active()
        t0 = time.perf_counter() if tr is not None else 0.0
        out = [self._read(o, s) for o, s in requests]
        dt = self.batch_seconds([s for _, s in requests])
        self._account(dt)
        with self._stats_lock:
            self.stats.add(FetchStats(
                len(requests), sum(len(d) for d in out), dt,
                batches=1, last_batch_requests=len(requests),
                latencies=[self.request_seconds(s) for _, s in requests]))
        if tr is not None:
            tr.complete("storage_read", "io", t0, time.perf_counter(),
                        backend=self.kind, bytes=sum(len(d) for d in out),
                        n=len(requests), modeled_dt=dt)
        return out, dt

    def _account(self, modeled_seconds: float) -> None:
        """Hook: the NVMe model only *accounts* modeled time (wall stays
        real); the object-store profile overrides this to sleep it."""

    def effective_bandwidth(self, size: int) -> float:
        """bw · s/(s + latency·bw): the Insight-2 efficiency curve."""
        return size / self.request_seconds(size)


class ObjectStoreStorage(SimulatedStorage):
    """High-latency object-store profile (S3-class remote reads).

    Same N-lane accounting as ``SimulatedStorage`` — ``connections``
    parallel HTTP-range streams at ``connection_bandwidth`` each, with
    millisecond first-byte ``latency`` — but by default the modeled
    request time is also *slept*, so hiding remote latency (prefetch,
    fetch_threads > 1, multi-device sharding) shows up in measured wall
    time, not only in the modeled schedule.  Pair with the much larger
    ``DEFAULT_OBJECT_COALESCE_GAP``: at 8 ms × 1.2 GB/s a request is
    worth ~10 MB, so multi-MiB gap bytes beat a second round trip.
    """

    kind = "object"

    def __init__(self, path: str,
                 connections: int = DEFAULT_OBJECT_CONNECTIONS,
                 connection_bandwidth: float = DEFAULT_OBJECT_BANDWIDTH,
                 latency: float = DEFAULT_OBJECT_LATENCY,
                 sleep: bool = True):
        super().__init__(path, n_lanes=connections,
                         lane_bandwidth=connection_bandwidth,
                         latency=latency)
        self.sleep = sleep

    @property
    def connections(self) -> int:
        return self.n_lanes

    def _account(self, modeled_seconds: float) -> None:
        if self.sleep and modeled_seconds > 0:
            time.sleep(modeled_seconds)


Storage = object  # duck-typed: RealStorage | SimulatedStorage


# ---------------------------------------------------------------------------
# background prefetch: hide remote latency behind decode
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PrefetchStats:
    hits: int = 0             # demand requests served from the buffer
    misses: int = 0           # demand requests that went to the backend
    hidden_seconds: float = 0.0  # modeled request time already elapsed at hit
    stall_seconds: float = 0.0   # residual wait actually paid at hit


class _PrefetchEntry:
    __slots__ = ("offset", "size", "event", "data", "issue_t",
                 "modeled_dt", "error")

    def __init__(self, offset: int, size: int):
        self.offset = offset
        self.size = size
        self.event = threading.Event()
        self.data: bytes | None = None
        self.issue_t = 0.0
        self.modeled_dt = 0.0
        self.error: BaseException | None = None


class PrefetchingStorage:
    """Background-prefetch wrapper over any storage backend.

    ``prefetch(ranges)`` issues reads ahead of demand on a small daemon
    pool; a later ``fetch``/``fetch_batch`` for the *same* (offset, size)
    consumes the buffered bytes and pays only the residual of the modeled
    request time — the part not yet elapsed since issue — so remote
    latency overlaps with whatever the caller did in between (decode).

    Determinism: background reads go through the raw ``_read`` path and
    account **nothing**; the inner backend's FetchStats are charged at
    consumption time with the same request counts and modeled seconds the
    un-prefetched demand path would have charged.  The CI-gated
    ``io_requests`` counter is therefore independent of background-thread
    timing.  Entries are single-use and keyed by exact (offset, size) —
    the scan path always re-derives the same coalesced ranges, so
    lookahead issued with the same gap always hits.
    """

    def __init__(self, inner, threads: int = 2,
                 max_buffer_bytes: int = 256 * 1024 * 1024):
        self.inner = inner
        self.threads = max(1, threads)
        self.max_buffer_bytes = max_buffer_bytes
        self.prefetch_stats = PrefetchStats()
        self._buf: dict[tuple[int, int], _PrefetchEntry] = {}
        self._buf_bytes = 0
        self._lock = threading.Lock()
        self._queue: list[_PrefetchEntry] = []
        self._queue_cv = threading.Condition(self._lock)
        self._pool: list[threading.Thread] = []
        self._closed = False
        self._sleeps = bool(getattr(inner, "sleep", False))

    # -- wrapper plumbing ---------------------------------------------------

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def close(self) -> None:
        with self._lock:
            self._closed = True
            for e in self._queue:
                e.error = RuntimeError("storage closed")
                e.event.set()
            self._queue.clear()
            self._queue_cv.notify_all()
        self.inner.close()

    # -- background pool ----------------------------------------------------

    def _ensure_pool_locked(self) -> None:
        while len(self._pool) < self.threads:
            t = threading.Thread(target=self._worker_loop, daemon=True,
                                 name=f"prefetch-{len(self._pool)}")
            self._pool.append(t)
            t.start()

    def _worker_loop(self) -> None:
        while True:
            with self._queue_cv:
                while not self._queue and not self._closed:
                    self._queue_cv.wait()
                if self._closed:
                    return
                entry = self._queue.pop(0)
            entry.issue_t = time.perf_counter()
            try:
                data = self.inner._read(entry.offset, entry.size)
                rs = getattr(self.inner, "request_seconds", None)
                entry.modeled_dt = (rs(entry.size) if rs is not None
                                    else time.perf_counter() - entry.issue_t)
                entry.data = data
            except BaseException as e:  # noqa: BLE001 — surfaced at consume
                entry.error = e
            entry.event.set()

    # -- issue --------------------------------------------------------------

    def prefetch(self, requests: Sequence[tuple[int, int]]) -> int:
        """Queue background reads for ``requests``; returns how many were
        accepted (duplicates and over-budget ranges are skipped)."""
        accepted = 0
        with self._queue_cv:
            if self._closed:
                return 0
            for off, size in requests:
                key = (off, size)
                if key in self._buf:
                    continue
                if self._buf_bytes + size > self.max_buffer_bytes:
                    continue
                entry = _PrefetchEntry(off, size)
                self._buf[key] = entry
                self._buf_bytes += size
                self._queue.append(entry)
                accepted += 1
            if accepted:
                self._ensure_pool_locked()
                self._queue_cv.notify_all()
        if accepted:
            tr = trace.active()
            if tr is not None:
                tr.instant("prefetch_issue", "io", n=accepted)
        return accepted

    # -- consume ------------------------------------------------------------

    def _take(self, key: tuple[int, int]) -> _PrefetchEntry | None:
        with self._lock:
            entry = self._buf.pop(key, None)
            if entry is not None:
                self._buf_bytes -= entry.size
            return entry

    def _residual(self, entry: _PrefetchEntry) -> float:
        """Wait for the background read, then return the unexpired part of
        its modeled request time (0 when decode fully hid it)."""
        entry.event.wait()
        if entry.error is not None:
            return -1.0
        return max(0.0, entry.issue_t + entry.modeled_dt
                   - time.perf_counter())

    def _note(self, **deltas) -> None:
        with self._lock:
            for k, v in deltas.items():
                setattr(self.prefetch_stats, k,
                        getattr(self.prefetch_stats, k) + v)

    def fetch(self, offset: int, size: int) -> bytes:
        entry = self._take((offset, size))
        if entry is not None:
            residual = self._residual(entry)
            if residual >= 0.0:
                if self._sleeps and residual > 0:
                    time.sleep(residual)
                self._note(hits=1,
                           hidden_seconds=entry.modeled_dt - residual,
                           stall_seconds=residual)
                with self.inner._stats_lock:
                    self.inner.stats.add(FetchStats(
                        1, len(entry.data), entry.modeled_dt,
                        latencies=[entry.modeled_dt]))
                tr = trace.active()
                if tr is not None:
                    tr.instant("prefetch_hit", "io", offset=offset,
                               hidden=entry.modeled_dt - residual,
                               stall=residual)
                return entry.data
        self._note(misses=1)
        tr = trace.active()
        if tr is not None:
            tr.instant("prefetch_miss", "io", offset=offset)
        return self.inner.fetch(offset, size)

    def fetch_batch(self, requests: Sequence[tuple[int, int]]
                    ) -> tuple[list[bytes], float]:
        requests = list(requests)
        t0 = time.perf_counter()
        out: list[bytes | None] = [None] * len(requests)
        hit_entries: list[_PrefetchEntry] = []
        miss_idx: list[int] = []
        max_residual = 0.0
        for i, (off, size) in enumerate(requests):
            entry = self._take((off, size))
            residual = -1.0 if entry is None else self._residual(entry)
            if residual < 0.0:
                miss_idx.append(i)
                continue
            out[i] = entry.data
            hit_entries.append(entry)
            max_residual = max(max_residual, residual)
            self._note(hits=1,
                       hidden_seconds=entry.modeled_dt - residual,
                       stall_seconds=residual)
        if miss_idx:
            self._note(misses=len(miss_idx))
            datas, _ = self.inner.fetch_batch(
                [requests[i] for i in miss_idx])
            for i, d in zip(miss_idx, datas):
                out[i] = d
        if hit_entries:
            # hit requests ran concurrently in the background → one
            # residual wait covers them all (minus wall already spent on
            # the demand-path misses above)
            if self._sleeps:
                remaining = max_residual - (time.perf_counter() - t0)
                if remaining > 0:
                    time.sleep(remaining)
            bs = getattr(self.inner, "batch_seconds", None)
            sizes = [e.size for e in hit_entries]
            dt_hit = (bs(sizes) if bs is not None
                      else sum(e.modeled_dt for e in hit_entries))
            with self.inner._stats_lock:
                self.inner.stats.add(FetchStats(
                    len(hit_entries), sum(len(e.data) for e in hit_entries),
                    dt_hit,
                    batches=0 if miss_idx else 1,
                    last_batch_requests=0 if miss_idx else len(requests),
                    latencies=[e.modeled_dt for e in hit_entries]))
        tr = trace.active()
        if tr is not None and (hit_entries or miss_idx):
            tr.instant("prefetch_hit" if hit_entries else "prefetch_miss",
                       "io", hits=len(hit_entries), misses=len(miss_idx),
                       stall=max_residual)
        return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# bounded retry with exponential backoff + jitter and per-request timeouts
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """How storage reads recover from transient faults (DESIGN.md §6).

    ``attempts`` is the total try count (1 = no retry).  Backoff is
    exponential from ``base_delay`` capped at ``max_delay``, with
    *deterministic* jitter — a hash of (attempt, offset) — so fault-replay
    tests see identical schedules.  ``timeout`` is a per-request budget:
    ``os.pread`` cannot be interrupted mid-call, so the check is post-hoc
    (a request that came back over budget counts as a timeout and is
    retried/raised) — it bounds how long a latency spike's bytes are
    trusted, which is the recoverable failure this layer owns; whole-scan
    budgets are the scheduler's deadline (core/scheduler.py).

    ``name`` identifies the policy in traces and ScanMetrics
    (``retry_policy`` column) — "nvme" for the local default, "object"
    for the remote profile (``backend_retry_policy``)."""

    attempts: int = 3
    base_delay: float = 0.001
    max_delay: float = 0.050
    jitter: float = 0.5
    timeout: float | None = None
    name: str = "nvme"

    def delay(self, attempt: int, salt: int = 0) -> float:
        import zlib
        import struct as _struct
        base = min(self.max_delay, self.base_delay * (2 ** attempt))
        u = zlib.crc32(_struct.pack("<qq", attempt, salt)) / 2**32
        return base * (1.0 + self.jitter * u)


#: retries on by default: 3 tries heal any single-shot transient fault
DEFAULT_RETRY_POLICY = RetryPolicy()

#: remote profile (PR 8 carried follow-up): an object store's transient
#: window is seconds, not microseconds — more attempts, backoff starting
#: above the 8 ms first-byte latency (a faster retry just queues behind
#: the same congested connection), and a per-request deadline generous
#: enough for a slept multi-MiB coalesced read at 1.2 GB/s + spikes
OBJECT_RETRY_POLICY = RetryPolicy(attempts=5, base_delay=0.025,
                                  max_delay=1.0, timeout=10.0,
                                  name="object")

NO_RETRY = RetryPolicy(attempts=1, name="none")


def backend_retry_policy(backend: str) -> RetryPolicy:
    """Per-backend default RetryPolicy, the recovery sibling of
    ``backend_io_defaults``: the NVMe policy for real/sim, the
    longer-backoff/deadline remote policy for object."""
    if backend == "object":
        return OBJECT_RETRY_POLICY
    return DEFAULT_RETRY_POLICY


@dataclasses.dataclass
class RetryStats:
    retries: int = 0      # extra attempts actually spent
    timeouts: int = 0     # requests that exceeded the per-request budget
    short_reads: int = 0  # truncated reads detected (then retried)


class RetryingStorage:
    """Bounded-retry wrapper over any storage backend.

    ``fetch`` retries retryable failures (core/faults.py taxonomy) and
    validates length — a short read is retried like an I/O error, never
    returned.  ``fetch_batch`` tries the batch once; on any failure it
    degrades to per-request retried fetches, so one bad request costs one
    batch-shaped region its coalescing, not the scan its life.  Counters
    land in ``retry_stats`` (ScanMetrics picks them up); everything else
    delegates to the wrapped backend."""

    def __init__(self, inner, policy: RetryPolicy | None = None):
        self.inner = inner
        self.policy = policy if policy is not None else DEFAULT_RETRY_POLICY
        self.retry_stats = RetryStats()
        self._retry_lock = threading.Lock()

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def close(self) -> None:
        self.inner.close()

    def _note(self, **deltas) -> None:
        with self._retry_lock:
            for k, v in deltas.items():
                setattr(self.retry_stats, k,
                        getattr(self.retry_stats, k) + v)

    def _fetch_once(self, offset: int, size: int) -> bytes:
        from repro.core.faults import FetchTimeout, ShortReadError
        t0 = time.perf_counter()
        data = self.inner.fetch(offset, size)
        elapsed = time.perf_counter() - t0
        if (self.policy.timeout is not None
                and elapsed > self.policy.timeout):
            self._note(timeouts=1)
            tr = trace.active()
            if tr is not None:
                tr.instant("fetch_timeout", "fault", offset=offset,
                           elapsed=elapsed, budget=self.policy.timeout)
            raise FetchTimeout(offset, size, elapsed, self.policy.timeout)
        if len(data) < size:
            self._note(short_reads=1)
            tr = trace.active()
            if tr is not None:
                tr.instant("short_read", "fault", offset=offset,
                           want=size, got=len(data))
            raise ShortReadError(offset, size, len(data))
        return data

    def fetch(self, offset: int, size: int) -> bytes:
        from repro.core.faults import is_retryable
        last: BaseException | None = None
        for attempt in range(max(1, self.policy.attempts)):
            if attempt:
                self._note(retries=1)
                tr = trace.active()
                if tr is not None:
                    tr.instant("retry_attempt", "fault", offset=offset,
                               attempt=attempt, policy=self.policy.name,
                               error=type(last).__name__)
                trace.registry().counter_inc("storage.retries")
                time.sleep(self.policy.delay(attempt - 1, offset))
            try:
                return self._fetch_once(offset, size)
            except BaseException as e:  # noqa: BLE001 — reclassified below
                if not is_retryable(e):
                    raise
                last = e
        raise last

    def fetch_batch(self, requests: Sequence[tuple[int, int]]
                    ) -> tuple[list[bytes], float]:
        from repro.core.faults import is_retryable
        try:
            datas, dt = self.inner.fetch_batch(list(requests))
            if all(len(d) == s for d, (_, s) in zip(datas, requests)):
                return datas, dt
            self._note(short_reads=1)
        except BaseException as e:  # noqa: BLE001 — reclassified below
            if not is_retryable(e):
                raise
        # degraded path: per-request retried fetches (wall-measured — the
        # modeled batch time does not apply to a fault-recovery replay).
        # The replay is itself one retry of the batch-shaped region, even
        # when every per-request fetch then succeeds first try.
        self._note(retries=1)
        tr = trace.active()
        if tr is not None:
            tr.instant("retry_attempt", "fault", n=len(requests),
                       policy=self.policy.name, batch=True)
        trace.registry().counter_inc("storage.retries")
        t0 = time.perf_counter()
        out = [self.fetch(o, s) for o, s in requests]
        return out, time.perf_counter() - t0


def backend_io_defaults(backend: str) -> tuple[float, float, int]:
    """Per-backend ``(lane_bandwidth, latency, coalesce_gap)`` defaults:
    the NVMe profile for real/sim, the remote profile for object."""
    if backend == "object":
        return (DEFAULT_OBJECT_BANDWIDTH, DEFAULT_OBJECT_LATENCY,
                DEFAULT_OBJECT_COALESCE_GAP)
    return 7e9, 20e-6, DEFAULT_COALESCE_GAP


def open_storage(path: str, backend: str = "real", n_lanes: int = 1,
                 lane_bandwidth: float | None = None,
                 latency: float | None = None):
    default_bw, default_lat, _ = backend_io_defaults(backend)
    if lane_bandwidth is None:
        lane_bandwidth = default_bw
    if latency is None:
        latency = default_lat
    if backend == "real":
        return RealStorage(path)
    if backend == "sim":
        return SimulatedStorage(path, n_lanes=n_lanes,
                                lane_bandwidth=lane_bandwidth,
                                latency=latency)
    if backend == "object":
        # n_lanes=1 is the NVMe-profile default, not a deliberate "one
        # connection" ask — the remote profile parallelizes by default
        connections = n_lanes if n_lanes > 1 else DEFAULT_OBJECT_CONNECTIONS
        return ObjectStoreStorage(path, connections=connections,
                                  connection_bandwidth=lane_bandwidth,
                                  latency=latency)
    raise ValueError(backend)
