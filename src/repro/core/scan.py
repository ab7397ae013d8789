"""Device scan engine: storage → (decompress → decode) → device columns.

Effective bandwidth (the paper's headline metric) = logical raw bytes after
decode/decompress ÷ scan time.  The scanner accounts all three byte flows:

  stored_bytes   what moved from storage        (denominator of Insight 2/3)
  logical_bytes  what the query sees            (numerator of effective bw)
  decode work    measured wall time on this host

Decode backends:
  'pallas'  the TPU kernels (interpret mode on CPU) — correctness path
  'host'    vectorized numpy decoders — the *measured* throughput path on
            this CPU-only container (labeled in all benchmark output)

Both backends decode through the row-group DecodePlan by default
(core/decode_plan.py): pages are batched *across columns* per
(encoding, codec, width class), so a multi-column row group costs
O(encoding groups) kernel launches instead of O(columns × stride groups);
``use_plan=False`` selects the per-chunk reference path.  Fetches are
coalesced (core/storage.py): adjacent chunk byte ranges merge into large
reads, which the N-lane model rewards per Insight 2.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from collections.abc import Iterator, Sequence

import numpy as np

from repro.core.compression import ChecksumError, inflate_backend
from repro.core.decode_plan import planner_for
from repro.core.faults import (FaultPlan, InjectedDecodeError, is_retryable,
                               wrap_storage)
from repro.core.metadata import ChunkMeta
from repro.core.reader import TabFileReader, read_footer
from repro.core import trace
from repro.core.storage import (DEFAULT_COALESCE_GAP, PrefetchingStorage,
                                RealStorage, RetryingStorage, RetryPolicy,
                                backend_io_defaults, backend_retry_policy,
                                coalesce_ranges, fetch_coalesced,
                                open_storage)
from repro.kernels import ops
from repro.kernels.common import kernel_launch_count


@dataclasses.dataclass
class ScanMetrics:
    backend: str = "real"
    stored_bytes: int = 0
    logical_bytes: int = 0
    io_seconds: float = 0.0
    decode_seconds: float = 0.0
    n_row_groups: int = 0
    n_pages: int = 0
    io_per_rg: list[float] = dataclasses.field(default_factory=list)
    decode_per_rg: list[float] = dataclasses.field(default_factory=list)
    n_kernel_launches: int = 0   # pallas dispatches during this scan
    n_io_requests: int = 0       # storage requests issued (post-coalescing)
    shared_rgs: int = 0          # RGs delivered from another scan's
                                 # in-flight job (cooperative scans)
    plan_seconds: float = 0.0    # decode-plan build time (0 on cache hits)
    # per-stage wall spans of a pipelined run (overlap.py): elapsed time
    # between each stage's first start and last end — distinct from the
    # summed per-RG stage times above, which ignore thread overlap.
    fetch_wall_seconds: float = 0.0
    decode_wall_seconds: float = 0.0
    consume_seconds: float = 0.0
    # per-chunk decode item times per row group (ScanService dispatch):
    # decode_chunks_per_rg[k] lists RG k's independently scheduled item
    # walls in completion order — open, phase-1 (decompress) items, the
    # phase transition, phase-2 (decode) items, finalize; empty on
    # monolithic decode.  sum(decode_chunks_per_rg[k]) ≈ decode_per_rg[k].
    # decode_p2_start_per_rg[k] indexes RG k's first phase-2 item — the
    # barrier the modeled schedule honors (phase 2 starts only after
    # every phase-1 item drained).
    decode_chunks_per_rg: list[list[float]] = dataclasses.field(
        default_factory=list)
    decode_p2_start_per_rg: list[int] = dataclasses.field(
        default_factory=list)
    # fault-recovery accounting (DESIGN.md §6): extra attempts spent at
    # any layer (storage refetch, decode requeue), CRC failures observed
    # (whether healed by refetch or propagated), and per-request timeouts.
    retries: int = 0
    checksum_failures: int = 0
    timeouts: int = 0
    # informational: the gzip-inflate backend active for this process
    # (isal / zlib-ng / zlib — core/compression.py)
    inflate_backend: str = inflate_backend()
    # per-backend observability (DESIGN.md §8): prefetch economics when a
    # PrefetchingStorage wraps the backend, per-request latency
    # percentiles (modeled on sim/object, measured on real), and the
    # decode-worker pinning in effect (REPRO_DECODE_AFFINITY)
    prefetch_hits: int = 0
    prefetch_misses: int = 0
    prefetch_hidden_seconds: float = 0.0
    prefetch_stall_seconds: float = 0.0
    io_p50_us: float = 0.0
    io_p95_us: float = 0.0
    decode_affinity: str = "off"
    # observability (DESIGN.md §10; informational, never gated): which
    # RetryPolicy recovered this scan's reads (nvme/object/custom), how
    # many flight-recorder events the run recorded (0 when tracing off),
    # and the process metrics-registry snapshot at scan end
    retry_policy: str = ""
    trace_events: int = 0
    registry_snapshot: dict = dataclasses.field(default_factory=dict)

    @property
    def blocking_seconds(self) -> float:
        return self.io_seconds + self.decode_seconds

    @property
    def overlapped_seconds(self) -> float:
        """Two-stage pipeline schedule: storage is the serial resource; the
        compute stage for RG i starts at max(io done(i), compute done(i-1))."""
        io_done = 0.0
        compute_done = 0.0
        for io, dec in zip(self.io_per_rg, self.decode_per_rg):
            io_done += io
            compute_done = max(io_done, compute_done) + dec
        return compute_done

    def effective_bandwidth(self, overlapped: bool = True) -> float:
        t = self.overlapped_seconds if overlapped else self.blocking_seconds
        return self.logical_bytes / max(1e-12, t)

    @property
    def storage_bandwidth(self) -> float:
        return self.stored_bytes / max(1e-12, self.io_seconds)

    @property
    def compression_ratio(self) -> float:
        return self.logical_bytes / max(1, self.stored_bytes)


class DecodeJob:
    """Protocol for a schedulable row-group decode (see Scanner.decode_job).

    Run every callable from ``phase1_tasks()`` (concurrently is fine), then
    — only after phase 1 fully drains — every callable from
    ``phase2_tasks()``, then ``finalize()`` (the join barrier), which
    returns the decoded columns dict.  Serial callers may simply iterate;
    the ScanService fans the items out across its shared decode pool so one
    slow chunk no longer holds its whole row group.
    """

    def phase1_tasks(self) -> list:
        return []

    def phase2_tasks(self) -> list:
        return []

    def phase3_tasks(self) -> list:
        """Late-materialization items (fused stage-B, core/fused.py) —
        valid once phase 2 fully drains; empty on unfused scans."""
        return []

    def finalize(self) -> dict[str, ops.DecodeResult]:
        raise NotImplementedError


class _PlannedDecodeJob(DecodeJob):
    """Staged DecodePlanner execution (the default path)."""

    def __init__(self, scanner: "Scanner", rg_index: int, raws):
        self.planner = scanner.planner
        self.ctx = self.planner.begin_execute(rg_index, raws)

    def phase1_tasks(self):
        return self.planner.decompress_tasks(self.ctx)

    def phase2_tasks(self):
        return self.planner.decode_tasks(self.ctx)

    def phase3_tasks(self):
        return self.planner.fused_tasks(self.ctx)

    def finalize(self):
        out = self.planner.finish_execute(self.ctx)
        tr = trace.active()
        t0 = time.perf_counter() if tr is not None else 0.0
        for res in out.values():
            if res.on_device:
                res.array.block_until_ready()
        if tr is not None:
            tr.complete("device_wait", "device", t0, time.perf_counter(),
                        site="finalize")
        return out


class _PerChunkDecodeJob(DecodeJob):
    """use_plan=False reference path: one item per column chunk."""

    def __init__(self, scanner: "Scanner", rg_index: int, raws):
        self.scanner = scanner
        self.rg_index = rg_index
        self.raws = raws
        self.out: dict[str, ops.DecodeResult] = {}

    def _decode_column(self, name: str) -> None:
        sc = self.scanner
        rg = sc.meta.row_groups[self.rg_index]
        chunk = rg.column(name)
        field = sc.meta.schema.field(name)
        self.out[name] = ops.decode_chunk(
            chunk, field, self.raws[name],
            use_kernels=(sc.decode_backend == "pallas"))

    def phase2_tasks(self):
        return [functools.partial(self._decode_column, name)
                for name in self.scanner.columns]

    def finalize(self):
        for res in self.out.values():
            if res.on_device:
                res.array.block_until_ready()
        return {name: self.out[name] for name in self.scanner.columns}


class Scanner:
    def __init__(self, path: str, columns: list[str] | None = None,
                 storage=None, decode_backend: str = "pallas",
                 use_plan: bool = True,
                 coalesce_gap: int = DEFAULT_COALESCE_GAP,
                 retry: RetryPolicy | None = None,
                 fault_plan: FaultPlan | None = None,
                 fused_spec=None):
        self.path = path
        self.meta = read_footer(path)
        self.columns = columns if columns is not None \
            else self.meta.schema.names
        storage = storage if storage is not None else RealStorage(path)
        # fault-recovery sandwich (DESIGN.md §6): the FaultPlan injects
        # *under* the retry wrapper, so retries heal transient injections
        # exactly as they would heal real storage faults.  Retries are on
        # by default with the storage backend's profile policy — the NVMe
        # policy locally, longer backoff/deadlines on the object store
        # (backend_retry_policy); attempts=1 disables.
        self.fault_plan = fault_plan
        storage = wrap_storage(storage, fault_plan)
        self.retry = retry if retry is not None else backend_retry_policy(
            getattr(storage, "kind", "real"))
        if self.retry.attempts > 1 or self.retry.timeout is not None:
            storage = RetryingStorage(storage, self.retry)
        self.storage = storage
        assert decode_backend in ("pallas", "host")
        self.decode_backend = decode_backend
        self.coalesce_gap = coalesce_gap
        if fused_spec is not None and not use_plan:
            raise ValueError("fused scans require use_plan=True")
        self.fused_spec = fused_spec
        self.planner = planner_for(path, self.meta, self.columns,
                                   decode_backend,
                                   fused_spec=fused_spec) \
            if use_plan else None
        self._reader = TabFileReader(path, fetch=self.storage.fetch)
        # decode-layer fault accounting; storage-layer counts live in the
        # RetryingStorage.  Lock-protected: the ScanService's decode
        # workers increment concurrently.
        self._fault_lock = threading.Lock()
        self._decode_retries = 0
        self._checksum_failures = 0
        self._timeouts = 0

    def enable_fused(self, spec) -> None:
        """Attach a FusedSpec to an already-open scanner, or detach it
        with None (rebinds the planner — fused and unfused scans never
        share stage-A plans)."""
        if self.planner is None:
            raise ValueError("fused scans require use_plan=True")
        self.fused_spec = spec
        self.planner = planner_for(self.path, self.meta, self.columns,
                                   self.decode_backend, fused_spec=spec)

    # -- fault accounting ----------------------------------------------------

    def count_fault(self, *, retries: int = 0, checksum_failures: int = 0,
                    timeouts: int = 0) -> None:
        """Record decode-layer recovery events (scheduler requeues, CRC
        failures, deadline-adjacent timeouts) against this scanner."""
        with self._fault_lock:
            self._decode_retries += retries
            self._checksum_failures += checksum_failures
            self._timeouts += timeouts

    def fault_counters(self) -> dict[str, int]:
        """Merged recovery counters: decode layer + storage retry layer."""
        rs = getattr(self.storage, "retry_stats", None)
        with self._fault_lock:
            return {
                "retries": self._decode_retries
                + (rs.retries if rs else 0),
                "checksum_failures": self._checksum_failures,
                "timeouts": self._timeouts + (rs.timeouts if rs else 0),
            }

    # -- planning -------------------------------------------------------------

    def plan(self, predicate_stats=None,
             row_groups: Sequence[int] | None = None) -> list[int]:
        return self._reader.plan_row_groups(predicate_stats, row_groups)

    def prepare_plans(self, row_groups: Sequence[int] | None = None,
                      predicate_stats=None) -> int:
        """Build (and cache) decode plans for the scan's row groups ahead of
        time — the serving/query loop pattern where planning cost must not
        land on the first request.  Returns the number of groups planned."""
        if self.planner is None:
            return 0
        return sum(self.planner.plan_rg(i).n_groups
                   for i in self.plan(predicate_stats, row_groups))

    def rg_requests(self, rg_index: int) -> list[tuple[str, ChunkMeta,
                                                       tuple[int, int]]]:
        rg = self.meta.row_groups[rg_index]
        out = []
        for name in self.columns:
            chunk = rg.column(name)
            out.append((name, chunk, chunk.byte_range))
        return out

    def prefetch_rgs(self, rg_indices: Sequence[int]) -> int:
        """Issue background reads for the given row groups' coalesced
        ranges (no-op unless the storage stack has a PrefetchingStorage).
        The merged ranges are derived with the scanner's own coalesce gap,
        so the later demand ``fetch_rg`` asks for byte-identical requests
        and always hits the prefetch buffer."""
        pf = getattr(self.storage, "prefetch", None)
        if pf is None:
            return 0
        merged_all: list[tuple[int, int]] = []
        for i in rg_indices:
            ranges = [r for _, _, r in self.rg_requests(i)]
            if self.coalesce_gap <= 0:
                merged_all.extend(ranges)
            else:
                merged, _ = coalesce_ranges(ranges, self.coalesce_gap)
                merged_all.extend(merged)
        return pf(merged_all)

    # -- stages ----------------------------------------------------------------

    def fetch_rg(self, rg_index: int) -> tuple[dict[str, bytes], float]:
        """Fetch every selected chunk of one row group with coalesced
        requests: adjacent/near-adjacent column byte ranges merge into one
        large read (Insight 2); per-column zero-copy views come back."""
        reqs = self.rg_requests(rg_index)
        datas, dt = fetch_coalesced(self.storage, [r for _, _, r in reqs],
                                    self.coalesce_gap)
        return {name: d for (name, _, _), d in zip(reqs, datas)}, dt

    def decode_job(self, rg_index: int, raws: dict[str, bytes]
                   ) -> "DecodeJob":
        """Schedulable decode of one row group (ScanService per-chunk
        dispatch, core/scheduler.py): phase-1 items (decompress), phase-2
        items (one per DecodePlan group / fallback column), then a join
        ``finalize``.  Bit-identical to ``decode_rg`` — both drive the same
        staged planner execution.  An *instance-patched* ``decode_rg``
        (tests, instrumentation) stays authoritative: the job degrades to
        one opaque item that calls it."""
        if "decode_rg" in self.__dict__:
            from repro.core.scheduler import OpaqueDecodeJob
            return OpaqueDecodeJob(self, rg_index, raws)
        if self.fault_plan is not None:
            self.fault_plan.maybe_decode_error(rg_index)
        if self.planner is not None:
            return _PlannedDecodeJob(self, rg_index, raws)
        return _PerChunkDecodeJob(self, rg_index, raws)

    def _decode_rg_once(self, rg_index: int, raws: dict[str, bytes]
                        ) -> dict[str, ops.DecodeResult]:
        if self.fault_plan is not None:
            self.fault_plan.maybe_decode_error(rg_index)
        if self.planner is not None:
            return self.planner.execute(rg_index, raws)
        out = {}
        rg = self.meta.row_groups[rg_index]
        for name in self.columns:
            chunk = rg.column(name)
            field = self.meta.schema.field(name)
            out[name] = ops.decode_chunk(chunk, field, raws[name],
                                         use_kernels=(self.decode_backend
                                                      == "pallas"))
        return out

    def retry_decode(self, rg_index: int, e: BaseException) -> bool:
        """Prepare a decode retry after failure ``e``: count it, evict
        anything the failed attempt may have pushed into the shared
        caches, and say whether the retry budget allows another try
        (callers then refetch the raw bytes and decode again).  Shared by
        the blocking path below and the ScanService requeue path."""
        if isinstance(e, ChecksumError):
            self.count_fault(checksum_failures=1)
            tr = trace.active()
            if tr is not None:
                tr.instant("checksum_failure", "fault", scan=self.path,
                           rg=rg_index)
        if isinstance(e, TimeoutError):
            self.count_fault(timeouts=1)
        if not is_retryable(e):
            return False
        if self.planner is not None:
            self.planner.evict_rg(rg_index)
        return True

    def decode_rg(self, rg_index: int, raws: dict[str, bytes]
                  ) -> tuple[dict[str, ops.DecodeResult], float]:
        t0 = time.perf_counter()
        out = None
        for attempt in range(max(1, self.retry.attempts)):
            try:
                out = self._decode_rg_once(rg_index, raws)
                break
            except (ChecksumError, InjectedDecodeError) as e:
                # a CRC failure here may be transit corruption (torn DMA,
                # injected flip): evict, refetch clean bytes, try again —
                # but never more times than the storage retry budget
                if (not self.retry_decode(rg_index, e)
                        or attempt + 1 >= max(1, self.retry.attempts)):
                    raise
                self.count_fault(retries=1)
                raws, _ = self.fetch_rg(rg_index)
        # flush async dispatch so decode time is honest
        for res in out.values():
            if res.on_device:
                res.array.block_until_ready()
        return out, time.perf_counter() - t0

    # -- full scans --------------------------------------------------------------

    def scan(self, row_groups: Sequence[int] | None = None,
             predicate_stats=None
             ) -> Iterator[tuple[int, dict[str, ops.DecodeResult]]]:
        for i in self.plan(predicate_stats, row_groups):
            raws, _ = self.fetch_rg(i)
            cols, _ = self.decode_rg(i, raws)
            yield i, cols

    def scan_with_metrics(self, row_groups: Sequence[int] | None = None,
                          predicate_stats=None, consume=None
                          ) -> tuple[object | None, ScanMetrics]:
        m = ScanMetrics(backend=getattr(self.storage, "kind", "real"))
        launches0 = kernel_launch_count()
        requests0 = self.storage.stats.requests
        faults0 = self.fault_counters()
        plan_s0 = self.planner.plan_seconds if self.planner else 0.0
        acc = None
        for i in self.plan(predicate_stats, row_groups):
            raws, io_dt = self.fetch_rg(i)
            cols, dec_dt = self.decode_rg(i, raws)
            rg = self.meta.row_groups[i]
            for name in self.columns:
                chunk = rg.column(name)
                m.stored_bytes += chunk.stored_bytes
                m.n_pages += len(chunk.pages)
            m.logical_bytes += sum(r.logical_bytes for r in cols.values())
            m.io_seconds += io_dt
            m.decode_seconds += dec_dt
            m.io_per_rg.append(io_dt)
            m.decode_per_rg.append(dec_dt)
            m.n_row_groups += 1
            if consume is not None:
                acc = consume(acc, i, cols)
        m.n_kernel_launches = kernel_launch_count() - launches0
        m.n_io_requests = self.storage.stats.requests - requests0
        faults = self.fault_counters()
        m.retries = faults["retries"] - faults0["retries"]
        m.checksum_failures = (faults["checksum_failures"]
                               - faults0["checksum_failures"])
        m.timeouts = faults["timeouts"] - faults0["timeouts"]
        if self.planner is not None:
            m.plan_seconds = self.planner.plan_seconds - plan_s0
        m.retry_policy = self.retry.name
        tr = trace.active()
        if tr is not None:
            m.trace_events = tr.event_count()
            m.registry_snapshot = trace.registry().snapshot()
        return acc, m


def open_scanner(path: str, columns=None, backend: str = "real",
                 n_lanes: int = 1, decode_backend: str = "pallas",
                 lane_bandwidth: float | None = None,
                 latency: float | None = None,
                 use_plan: bool = True,
                 coalesce_gap: int | None = None,
                 retry: RetryPolicy | None = None,
                 fault_plan: FaultPlan | None = None,
                 fused_spec=None, prefetch: bool = False,
                 prefetch_threads: int = 2) -> Scanner:
    # None means "the backend's profile default": NVMe numbers and 64 KiB
    # gaps for real/sim, the remote profile (ms latency, multi-MiB gap)
    # for object — callers that pass explicit values still win
    if coalesce_gap is None:
        coalesce_gap = backend_io_defaults(backend)[2]
    storage = open_storage(path, backend, n_lanes, lane_bandwidth, latency)
    if prefetch:
        storage = PrefetchingStorage(storage, threads=prefetch_threads)
    return Scanner(path, columns, storage, decode_backend,
                   use_plan=use_plan, coalesce_gap=coalesce_gap,
                   retry=retry, fault_plan=fault_plan,
                   fused_spec=fused_spec)
