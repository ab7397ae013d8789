"""ScanService: shared-pool multi-scan scheduler (DESIGN.md §2.6).

The serving loop runs *many small scans* concurrently, but the PR-2
executor gave every ``run_overlapped`` call a private fetch thread and a
private decode pool — concurrent scans fought over cores, and decode
dispatched at whole-row-group granularity, so one slow column chunk
stalled its row group.  This module schedules the fetch → decompress →
decode path as one shared resource across scans (the Presto-on-GPU /
Data-Path-Fusion result):

  fetch    a shared fetch pool (``fetch_threads``, default ONE thread)
           issues each scan's coalesced per-RG reads, round-robin across
           active scans, gated by each scan's ``depth`` credits (the
           per-scan in-flight bound / OOM backpressure).  The single-
           thread default is deliberate — the paper's storage model
           treats the NVMe array as one shared channel whose bandwidth
           coalesced large reads already saturate — but high-latency
           *real* backends (network FS) want ``fetch_threads > 1`` so
           concurrent fragment scans overlap their blocking reads; the
           default path is bit-identical either way (pinned in tests);
  decode   ONE shared worker pool runs *per-chunk* work items — each
           DecodePlan group, fallback column, or decompress item of a row
           group is independently schedulable (``Scanner.decode_job``),
           with a join barrier before consume, so one slow gzip chunk no
           longer holds the whole row group, and items from different
           scans interleave fairly (round-robin dispatch);
  consume  each scan's caller thread takes its row groups strictly in
           plan order from a per-scan in-order queue (``ScanHandle``).

**Fairness & priority.**  Both the fetch pool and the decode workers
service scans in round-robin order, so N concurrent scans each make
progress instead of the first-submitted scan monopolizing the pool.
``submit(priority=k)`` groups scans into strict priority classes (lower k
served first; round-robin *within* a class): the dataset executor uses
this to bias the pool toward earliest-submitted fragments so fragment
results complete (and release their window slot) in plan order.  The
default priority 0 for every scan reduces exactly to the flat
round-robin.

**Multi-tenant weighted fair shares (DESIGN.md §11).**  ``submit(
tenant="gold")`` attributes the scan to a registered :class:`Tenant`.
Within a priority class that has any tenanted scan, dispatch switches
from flat rotation to *stride scheduling*: every fetch grant and every
row-group "open" dispatch charges the owning tenant ``1/weight`` of
virtual time, and the tenant with the smallest virtual time is served
first — a weight-4 tenant receives ~4x the decode slots of a weight-1
tenant under saturation, and every tenant's virtual time advances on
each grant, so no tenant starves.  Untenanted scans ride along as a
shared weight-1 virtual tenant; a class with *no* tenanted scans keeps
the legacy rotation bit-for-bit.  Admission control is per tenant:
``max_active`` bounds concurrently admitted scans, with
``on_limit="reject"`` raising :class:`AdmissionRejected` and
``"queue"`` blocking the submitter until a slot frees.  A tenant with
an ``slo_s`` latency target feeds the adaptive sizer: while its recent
mean scan latency misses the target, the policy asks for one extra
decode worker (capped at ``max_workers``).

**Delivered-result window.**  Cooperative in-flight sharing only helps
scans that truly overlap; ``ScanService(window_bytes=N)`` additionally
retains the most recently *delivered* shareable row groups in a
byte-capped LRU keyed by the same share identity, so a late-arriving
identical scan is served decoded columns with **no fetch and no
decode** even after the original scan finished.  Off by default
(``window_bytes=0``) — cold-start measurements and io_request pins stay
exact; the serving front end (serve/engine.py) turns it on.  Cold-scan
ladders clear it via ``clear_delivered_windows()``.

**Error isolation / cancellation.**  A failing work item (or fetch) marks
only its own scan: queued items of that scan are dropped, its handle
re-raises the first error, and every other scan is untouched.
``ScanHandle.cancel()`` does the same without an error.  The pool never
dies with a scan.

**Adaptive worker sizing.**  The pool resizes from observed per-stage
wall ratios over a sliding window of delivered row groups: decode-bound
streams (decode ≫ max(fetch, consume)) grow the pool toward
``cpu_count - 1``; fetch/consume-bound streams shrink it toward one
worker (idle decode threads only add GIL contention).  An explicit
``workers_hint`` (``run_overlapped(decode_workers=N)``) pins the floor at
N while that scan is active.

``run_overlapped`` (core/overlap.py) is a thin client of this service for
``decode_workers >= 1``; the private inline path survives behind
``decode_workers=0``.  The process-wide singleton is ``scan_service()``.
"""

from __future__ import annotations

import atexit
import os
import sys
import threading
import time
import weakref
from collections import OrderedDict, deque
from collections.abc import Callable, Sequence

from repro.core import trace
from repro.core.faults import DeadlineExceeded, is_retryable


class ScanCancelled(RuntimeError):
    """Raised by a ScanHandle whose scan was cancelled mid-stream."""


class AdmissionRejected(RuntimeError):
    """Raised by ``submit`` when a tenant with ``on_limit="reject"`` is
    already at its ``max_active`` admitted-scan bound."""


class Tenant:
    """Service-side state of one registered tenant (DESIGN.md §11).

    ``weight`` is the tenant's fair share: stride scheduling charges
    ``1/weight`` virtual time per dispatch, so relative dispatch rates
    under saturation converge to the weight ratio.  ``max_active``
    bounds concurrently admitted scans (None = unbounded) with
    ``on_limit`` picking the over-limit behavior (``"reject"`` raises
    :class:`AdmissionRejected`, ``"queue"`` blocks the submitter).
    ``slo_s`` is an optional per-scan latency target feeding the
    adaptive pool sizer."""

    __slots__ = ("name", "weight", "max_active", "on_limit", "slo_s",
                 "seq", "fetch_pass", "item_pass", "active",
                 "dispatches", "latencies")

    def __init__(self, name: str, weight: int = 1,
                 max_active: int | None = None, on_limit: str = "reject",
                 slo_s: float | None = None, seq: int = 0):
        if weight < 1:
            raise ValueError(f"tenant weight must be >= 1, got {weight}")
        if on_limit not in ("reject", "queue"):
            raise ValueError(f"on_limit must be 'reject' or 'queue', "
                             f"got {on_limit!r}")
        self.name = name
        self.weight = int(weight)
        self.max_active = max_active
        self.on_limit = on_limit
        self.slo_s = slo_s
        self.seq = seq                 # registration order (tiebreak)
        self.fetch_pass = 0.0          # stride virtual time, fetch grants
        self.item_pass = 0.0           # stride virtual time, RG dispatches
        self.active = 0                # admitted scans in service
        self.dispatches = 0            # row-group "open" dispatches won
        self.latencies: deque = deque(maxlen=16)   # recent scan walls (s)


# ---------------------------------------------------------------------------
# decode-worker CPU affinity (REPRO_DECODE_AFFINITY — carried ROADMAP lever)
# ---------------------------------------------------------------------------

_AFFINITY_ENV = "REPRO_DECODE_AFFINITY"
#: spec → outcome of the last pin attempt ("pinned" / "unsupported")
_affinity_status: dict[str, str] = {}


def _affinity_cpus(spec: str) -> list[int]:
    """CPUs named by an affinity spec: ``auto`` → every CPU this process
    may run on (workers stripe across them); else a comma list with
    ``lo-hi`` ranges (``0,2`` / ``0-3``), filtered to the allowed set."""
    avail = sorted(os.sched_getaffinity(0))
    if spec.lower() == "auto":
        return avail
    cpus: list[int] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "-" in part:
            lo, hi = part.split("-", 1)
            cpus.extend(range(int(lo), int(hi) + 1))
        else:
            cpus.append(int(part))
    allowed = set(avail)
    return [c for c in cpus if c in allowed]


def _apply_affinity(worker_idx: int) -> None:
    """Pin the calling decode worker to one CPU from the
    REPRO_DECODE_AFFINITY set (worker_idx stripes across it).  A no-op
    when the env var is unset/off, and *silently degrades* on platforms
    without sched_setaffinity or with an unparsable spec — pinning is an
    optimization, never a correctness requirement."""
    spec = os.environ.get(_AFFINITY_ENV, "").strip()
    if not spec or spec.lower() in ("0", "off", "none"):
        return
    try:
        cpus = _affinity_cpus(spec)
        if not cpus:
            raise ValueError(f"empty affinity set: {spec!r}")
        # pid 0 = the calling thread on Linux: each worker pins itself
        os.sched_setaffinity(0, {cpus[worker_idx % len(cpus)]})
        _affinity_status[spec] = "pinned"
    except (AttributeError, OSError, ValueError):
        _affinity_status[spec] = "unsupported"


def decode_affinity_mode() -> str:
    """The pinning in effect, for ScanMetrics: ``off`` when unset;
    ``<spec>:pinned`` once a worker pinned successfully;
    ``<spec>:unsupported`` when the platform refused;
    ``<spec>:configured`` when set but no pool worker has started yet."""
    spec = os.environ.get(_AFFINITY_ENV, "").strip()
    if not spec or spec.lower() in ("0", "off", "none"):
        return "off"
    return f"{spec}:{_affinity_status.get(spec, 'configured')}"


def default_max_workers() -> int:
    """Adaptive-pool ceiling: leave one core for consume/fetch.  Override
    with REPRO_SCAN_MAX_WORKERS."""
    env = os.environ.get("REPRO_SCAN_MAX_WORKERS")
    if env is not None:
        return max(1, int(env))
    return max(1, (os.cpu_count() or 2) - 1)


class OpaqueDecodeJob:
    """One-item decode job wrapping a ``decode_rg`` callable: the adapter
    for scanners without ``decode_job`` (test stubs) and for scanners
    whose ``decode_rg`` was instance-patched (tests/instrumentation),
    where the patched callable must keep owning the whole decode.  The
    single implementation of this shape — ``Scanner.decode_job`` reuses
    it (core/scan.py)."""

    def __init__(self, scanner, rg_index, raws):
        self.scanner = scanner
        self.rg_index = rg_index
        self.raws = raws
        self.cols = None

    def phase1_tasks(self):
        return []

    def phase2_tasks(self):
        return [self._decode]

    def _decode(self):
        self.cols, _ = self.scanner.decode_rg(self.rg_index, self.raws)

    def finalize(self):
        assert self.cols is not None
        return self.cols


class _RgJob:
    """One fetched row group moving through the per-chunk decode DAG:
    open → phase-1 items (decompress) → phase-2 items (groups/fallbacks)
    → finalize (join) → each subscriber scan's in-order done queue.

    **Cooperative scans**: identical concurrent scans (same file contents,
    column selection, decode backend, storage shape) *subscribe* to an
    already-in-flight job for a row group instead of fetching and decoding
    it again — the serving-loop case where N clients query the same hot
    file.  ``subscribers`` lists the (scan, seq) pairs awaiting this job's
    columns; the decoded results are delivered to all of them (read-only
    DecodeResults are safe to share)."""

    __slots__ = ("rg_index", "raws", "io_dt", "job", "pending",
                 "phase", "chunk_times", "p2_start", "key", "subscribers",
                 "failed", "enq_t")

    def __init__(self, seq_scan, seq: int, rg_index: int, raws,
                 io_dt: float, key):
        self.rg_index = rg_index
        self.raws = raws
        self.io_dt = io_dt
        self.job = None           # built by the "open" item
        self.pending = 0          # outstanding items of the current phase
        self.phase = 0            # 0=open, 1, 2, 3 (fused stage-B)
        self.chunk_times: list[float] = []
        self.p2_start = 0         # chunk_times index of the first phase-2
                                  # item (the phase barrier, for the model)
        self.enq_t = 0.0          # when the current phase's items were
                                  # queued (trace queue-wait histogram)
        self.key = key            # sharing identity, None → not shareable
        self.subscribers: list[tuple] = [(seq_scan, seq)]
        self.failed = False       # an item of this job raised; queued and
                                  # in-flight siblings must stand down

    def live_scan(self):
        """First subscriber scan still interested in this job, or None."""
        for scan, _ in self.subscribers:
            if not scan.dead:
                return scan
        return None


def _share_key(scanner) -> tuple | None:
    """Identity under which two scans may share fetch+decode work: file
    *contents* (the planner cache token carries path + size + mtime),
    column selection, decode backend, and the storage model (its kind and
    timing parameters — a sim-backend scan must not inherit a real
    backend's io_dt or vice versa).  None → never share (no planner, or an
    instance-patched fetch/decode that sharing would bypass)."""
    planner = getattr(scanner, "planner", None)
    if planner is None:
        return None
    if ("decode_rg" in getattr(scanner, "__dict__", {})
            or "fetch_rg" in getattr(scanner, "__dict__", {})):
        return None
    if getattr(scanner, "fault_plan", None) is not None:
        # fault-injection scans exist to exercise the real fetch+decode
        # path: they must neither reuse a clean scan's work (skipping
        # the injection) nor publish their own into the shared window
        return None
    storage = getattr(scanner, "storage", None)
    return (planner.cache_token,
            tuple(scanner.columns),
            scanner.decode_backend,
            getattr(storage, "kind", "real"),
            getattr(storage, "n_lanes", None),
            getattr(storage, "lane_bandwidth", None),
            getattr(storage, "latency", None),
            getattr(scanner, "coalesce_gap", None),
            getattr(scanner, "fused_spec", None))


class _ScanState:
    """Service-side state of one submitted scan."""

    def __init__(self, service: "ScanService", scanner, plan: list[int],
                 depth: int, workers_hint: int | None, label: str,
                 priority: int = 0, retries: int = 3,
                 deadline: float | None = None,
                 tenant: Tenant | None = None):
        self.scanner = scanner
        self.tenant = tenant           # owning Tenant, None = untenanted
        self.t_submit = time.monotonic()
        self.plan = plan
        self.depth = max(1, depth)
        self.workers_hint = workers_hint
        self.label = label
        self.priority = priority
        # fault-recovery state (DESIGN.md §6): a transiently failed row
        # group (decode worker died, refetchable corruption) is requeued
        # for a fresh fetch+decode while budget lasts; ``refetch`` seqs
        # keep holding their in-flight credit (released only on ack), so
        # a retry can never over-subscribe the scan's depth bound.
        self.retries_left = max(0, retries)
        self.deadline = (None if deadline is None
                         else time.monotonic() + deadline)
        self.refetch: deque = deque()
        self.share_key = _share_key(scanner)
        self.shared_rgs = 0            # RGs satisfied by cooperative jobs
        self.workers_seen = 1          # max pool width while this scan ran
        self.credits = self.depth      # fetch permits (in-flight RG bound)
        self.next_fetch = 0            # next plan position to fetch
        self.ready: deque = deque()    # work items ready for the pool
        self.done: dict[int, tuple] = {}
        self.error: BaseException | None = None
        self.cancelled = False
        self.finished = False
        # stage wall spans (first start → last end) for RunReport
        self.fetch_span = [float("inf"), 0.0]
        self.decode_span = [float("inf"), 0.0]
        self.done_cv = threading.Condition(service._lock)

    @property
    def dead(self) -> bool:
        return self.error is not None or self.cancelled or self.finished

    def past_deadline(self) -> bool:
        return (self.deadline is not None
                and time.monotonic() > self.deadline)

    def span(self, which: str) -> float:
        lo, hi = self.fetch_span if which == "fetch" else self.decode_span
        return max(0.0, hi - lo) if hi else 0.0


class ScanHandle:
    """Client side of one scan: iterate to receive
    ``(rg_index, cols, io_dt, dec_dt, chunk_times, p2_start)`` strictly in
    plan order (``chunk_times`` lists the RG's decode item walls in
    completion order — open, phase-1 items, transition, phase-2 items,
    finalize — and ``p2_start`` indexes the first phase-2 item, the
    barrier the modeled schedule must honor).  Advancing the iterator
    *acks* the previous row group — releasing its in-flight credit and
    reporting its consume time to the
    adaptive sizer — so call ``next`` only after consuming.  ``cancel()``
    stops the scan without poisoning the pool."""

    def __init__(self, service: "ScanService", scan: _ScanState):
        self._svc = service
        self._scan = scan
        self._next_seq = 0
        self._t_delivered: float | None = None
        self._last_item: tuple | None = None

    def __iter__(self) -> "ScanHandle":
        return self

    def __next__(self) -> tuple:
        svc, scan = self._svc, self._scan
        with svc._lock:
            if self._t_delivered is not None:
                svc._ack_locked(scan, self._last_item,
                                time.perf_counter() - self._t_delivered)
                self._t_delivered = None
            if self._next_seq >= len(scan.plan) and scan.error is None:
                svc._finish_scan_locked(scan)
                raise StopIteration
            while (self._next_seq not in scan.done and not scan.dead):
                if scan.past_deadline():
                    svc._deadline_fail_locked(scan)
                    break
                scan.done_cv.wait(timeout=0.1)
            if scan.error is not None or scan.cancelled:
                err, cancelled = scan.error, scan.cancelled
                svc._finish_scan_locked(scan)
                if err is not None:
                    raise err
                if cancelled:
                    raise ScanCancelled(f"scan {scan.label} cancelled")
            item = scan.done.pop(self._next_seq)
        self._next_seq += 1
        self._t_delivered = time.perf_counter()
        self._last_item = item
        return item

    def cancel(self) -> None:
        """Idempotent: safe to call any number of times, from ``close``,
        ``__del__``, or interpreter-shutdown (atexit) paths — a finished
        scan short-circuits without touching the service."""
        scan = self._scan
        if scan.finished:
            return
        try:
            with self._svc._lock:
                if not scan.finished:
                    scan.cancelled = True
                    self._svc._finish_scan_locked(scan)
        except Exception:
            # during interpreter finalization the service's threads and
            # condition variables may already be torn down; the scan dies
            # with the process, so there is nothing left to release
            if not sys.is_finalizing():
                raise

    # A handle abandoned before exhaustion would otherwise leak its scan
    # registration (round-robin slot, pinned decoded RGs, fetch credits)
    # in the process-wide service for the life of the process — close on
    # scope exit and as a GC safety net.
    close = cancel

    def __enter__(self) -> "ScanHandle":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            if not self._scan.finished:
                self.close()
        except Exception:
            pass

    @property
    def workers(self) -> int:
        """Pool width to report/model for this scan: the explicit hint when
        given, else the widest pool observed *while the scan ran* (the
        pool may resize after the scan finishes)."""
        if self._scan.workers_hint:
            return self._scan.workers_hint
        return max(1, self._scan.workers_seen)

    def stage_walls(self) -> dict[str, float]:
        return {"fetch": self._scan.span("fetch"),
                "decode": self._scan.span("decode")}

    @property
    def shared_rgs(self) -> int:
        """Row groups this scan received from another scan's in-flight
        job (cooperative scans) instead of fetching + decoding itself."""
        return self._scan.shared_rgs


class ScanService:
    """One shared fetch thread + one shared decode pool for all scans."""

    def __init__(self, workers: int | None = None, adaptive: bool = True,
                 max_workers: int | None = None, resize_every: int = 8,
                 fetch_threads: int = 1, device=None,
                 window_bytes: int = 0):
        self._lock = threading.RLock()
        self._work_cv = threading.Condition(self._lock)
        self._fetch_cv = threading.Condition(self._lock)
        self._admit_cv = threading.Condition(self._lock)
        self._scans: list[_ScanState] = []
        # multi-tenant front end (DESIGN.md §11): registered tenants,
        # the virtual weight-1 tenant untenanted scans charge when they
        # share a priority class with tenanted ones, and the delivered-
        # result window — a byte-capped LRU of recently delivered
        # shareable row groups (off at 0, cold paths stay exact)
        self._tenants: dict[str, Tenant] = {}
        self._default_tenant = Tenant("-", weight=1, seq=-1)
        self.window_bytes = max(0, int(window_bytes))
        self._window: OrderedDict[tuple, tuple] = OrderedDict()
        self._window_nbytes = 0
        self.window_hits = 0
        self._rr = 0               # decode round-robin cursor
        self._fetch_rr = 0         # fetch round-robin cursor
        self._inflight: dict[tuple, _RgJob] = {}   # cooperative-scan jobs
        self.shared_rgs = 0        # total RGs served by subscription
        self.adaptive = adaptive
        self.max_workers = max_workers or default_max_workers()
        # the paper's one-channel NVMe model wants exactly one fetch
        # thread (the default); >1 overlaps blocking reads of concurrent
        # scans on high-latency real backends (network FS / many files)
        self.fetch_threads = max(1, fetch_threads)
        # multi-device sharding (dataset/executor.py): a per-device
        # service runs its decode workers under jax.default_device(device)
        # so launches land device-resident; None keeps jax's default
        self.device = device
        # _policy is what the adaptive sizer asks for; the effective target
        # additionally honors active scans' explicit workers hints
        self._policy = max(1, workers) if workers else 1
        self._target = self._policy
        self._n_workers = 0
        self._shrink = 0           # workers asked to retire
        self._shutdown = False
        self._fetch_pool: list[threading.Thread] = []
        self._threads: list[threading.Thread] = []
        # adaptive window accumulators (delivered-RG stage times)
        self._win = {"io": 0.0, "dec": 0.0, "cons": 0.0, "rgs": 0}
        self.resize_every = max(1, resize_every)
        self.resize_events: list[int] = []   # pool sizes after each resize
        _ALL_SERVICES.add(self)

    # -- public API ---------------------------------------------------------

    def register_tenant(self, name: str, weight: int = 1,
                        max_active: int | None = None,
                        on_limit: str = "reject",
                        slo_s: float | None = None) -> Tenant:
        """Register (or re-configure) a tenant.  ``submit(tenant=name)``
        with an unregistered name auto-registers it at weight 1,
        unbounded — explicit registration is how a tenant gets a weight,
        an admission bound, or an SLO."""
        with self._lock:
            ten = self._tenants.get(name)
            if ten is None:
                ten = Tenant(name, weight=weight, max_active=max_active,
                             on_limit=on_limit, slo_s=slo_s,
                             seq=len(self._tenants))
                self._tenants[name] = ten
            else:
                Tenant(name, weight=weight, on_limit=on_limit)  # validate
                ten.weight = int(weight)
                ten.max_active = max_active
                ten.on_limit = on_limit
                ten.slo_s = slo_s
            return ten

    def tenant(self, name: str) -> Tenant | None:
        with self._lock:
            return self._tenants.get(name)

    def _tenant_locked(self, name: str) -> Tenant:
        ten = self._tenants.get(name)
        if ten is None:
            ten = Tenant(name, seq=len(self._tenants))
            self._tenants[name] = ten
        return ten

    def clear_delivered_window(self) -> None:
        """Drop every retained delivered row group (cold-scan ladders:
        a cleared window forces real refetch + redecode)."""
        with self._lock:
            self._window.clear()
            self._window_nbytes = 0

    @property
    def window_entries(self) -> int:
        with self._lock:
            return len(self._window)

    def submit(self, scanner, row_groups: Sequence[int] | None = None,
               predicate_stats=None, depth: int = 2,
               workers_hint: int | None = None,
               label: str = "scan", priority: int = 0,
               retries: int = 3,
               deadline: float | None = None,
               tenant: str | None = None) -> ScanHandle:
        """Register one scan; returns its in-order consume handle.
        ``priority`` selects the scan's strict service class (lower is
        served first; round-robin within a class).  ``retries`` is the
        scan's transient-failure budget (requeued row groups across the
        whole scan); ``deadline`` is a whole-scan wall budget in seconds —
        once exceeded the scan fails with DeadlineExceeded (never
        retried).  ``tenant`` attributes the scan to a registered tenant
        for weighted fair scheduling and admission control (an unknown
        name auto-registers at weight 1, unbounded); at the tenant's
        ``max_active`` bound this either raises
        :class:`AdmissionRejected` or blocks until a slot frees,
        per its ``on_limit``."""
        plan = list(scanner.plan(predicate_stats, row_groups))
        with self._lock:
            if self._shutdown:
                raise RuntimeError("ScanService is shut down")
            ten = self._admit_locked(tenant)
            scan = _ScanState(self, scanner, plan, depth, workers_hint,
                              label, priority=priority, retries=retries,
                              deadline=deadline, tenant=ten)
            self._scans.append(scan)
            self._ensure_threads_locked()
            self._retarget_locked()
            scan.workers_seen = max(1, self.pool_size)
            self._fetch_cv.notify_all()
        return ScanHandle(self, scan)

    def _admit_locked(self, tenant: str | None) -> Tenant | None:
        """Admission control: charge one active-scan slot to the tenant,
        rejecting or queueing at its ``max_active`` bound.  An idle
        tenant re-joins the stride clock at the minimum active virtual
        time, so banked idleness can never become a dispatch burst."""
        if tenant is None:
            return None
        ten = self._tenant_locked(tenant)
        reg = trace.registry()
        if ten.max_active is not None and ten.active >= ten.max_active:
            if ten.on_limit == "reject":
                reg.counter_inc("scheduler.admission_rejects")
                raise AdmissionRejected(
                    f"tenant {ten.name}: {ten.active} active scans at "
                    f"max_active={ten.max_active}")
            reg.counter_inc("scheduler.admission_queued")
            tr = trace.active()
            t0 = time.perf_counter() if tr is not None else 0.0
            while ten.active >= ten.max_active and not self._shutdown:
                self._admit_cv.wait(timeout=0.1)
            if tr is not None:
                tr.complete("queued", "frontend", t0, time.perf_counter(),
                            tenant=ten.name)
            if self._shutdown:
                raise RuntimeError("ScanService is shut down")
        if ten.active == 0:
            actives = [t for t in self._tenants.values() if t.active > 0]
            if actives:
                ten.fetch_pass = max(ten.fetch_pass,
                                     min(t.fetch_pass for t in actives))
                ten.item_pass = max(ten.item_pass,
                                    min(t.item_pass for t in actives))
        ten.active += 1
        reg.gauge_set(f"scheduler.tenant_depth.{ten.name}", ten.active)
        return ten

    @property
    def pool_size(self) -> int:
        return self._n_workers - self._shrink

    @property
    def active_scans(self) -> int:
        with self._lock:
            return len(self._scans)

    def shutdown(self) -> None:
        with self._lock:
            self._shutdown = True
            # cancel every active scan: workers/fetch are about to exit, so
            # an un-cancelled consumer would wait on done_cv forever
            for scan in list(self._scans):
                scan.cancelled = True
                scan.done_cv.notify_all()
            self._work_cv.notify_all()
            self._fetch_cv.notify_all()
            self._admit_cv.notify_all()
        for t in self._fetch_pool + self._threads:
            t.join(timeout=5.0)

    # -- thread management --------------------------------------------------

    def _ensure_threads_locked(self) -> None:
        while len(self._fetch_pool) < self.fetch_threads:
            t = threading.Thread(
                target=self._fetch_loop, daemon=True,
                name=f"scan-service-fetch-{len(self._fetch_pool)}")
            self._fetch_pool.append(t)
            t.start()
        self._spawn_to_target_locked()

    def _spawn_to_target_locked(self) -> None:
        while self._n_workers - self._shrink < self._target:
            if self._shrink > 0:     # un-retire instead of spawning
                self._shrink -= 1
                continue
            t = threading.Thread(target=self._worker_loop, daemon=True,
                                 args=(len(self._threads),),
                                 name=f"scan-service-{len(self._threads)}")
            self._n_workers += 1
            self._threads.append(t)
            t.start()

    def _retarget_locked(self) -> None:
        """Recompute the effective pool target: the adaptive policy value
        (capped at max_workers), floored by any active scan's explicit
        workers hint, never below one."""
        hints = [s.workers_hint for s in self._scans if s.workers_hint]
        self._target = max(min(self._policy, self.max_workers),
                           *(hints or [1]), 1)
        if self._target > self._n_workers - self._shrink:
            self._spawn_to_target_locked()
        elif self._target < self._n_workers - self._shrink:
            self._shrink = self._n_workers - self._target
            self._work_cv.notify_all()

    def _resize_window_locked(self) -> None:
        w = self._win
        if w["rgs"] < self.resize_every:
            return
        if self.adaptive:
            # observed per-stage wall ratio over the window: how many decode
            # servers the stream can keep busy against its slower of
            # fetch/consume.  decode-bound → grow toward cpu_count-1;
            # fetch/consume-bound → shrink toward 1.
            bound = max(w["io"], w["cons"], 1e-9)
            self._policy = max(1, int(round(w["dec"] / bound)))
            # SLO-aware sizing (DESIGN.md §11): an active tenant whose
            # recent mean scan latency misses its target asks for one
            # extra decode worker on top of the ratio policy
            for t in self._tenants.values():
                if (t.slo_s is not None and t.active > 0 and t.latencies
                        and (sum(t.latencies) / len(t.latencies)
                             > t.slo_s)):
                    self._policy = min(self.max_workers, self._policy + 1)
                    trace.registry().counter_inc("scheduler.slo_boosts")
                    break
        self._win = {"io": 0.0, "dec": 0.0, "cons": 0.0, "rgs": 0}
        self._retarget_locked()
        self.resize_events.append(self._target)
        reg = trace.registry()
        reg.gauge_set("scheduler.pool_target", self._target)
        reg.counter_inc("scheduler.resizes")

    # -- fetch stage --------------------------------------------------------

    def _service_order_locked(self, cursor: int, which: str = "fetch"
                              ) -> list[tuple[_ScanState, int]]:
        """Active scans in service order: ascending priority class, with
        the round-robin rotation (by ``cursor``) applied *within* each
        class.  Each entry carries the scan's rotation offset inside its
        own class — what the cursor must advance by when that scan is
        chosen, so scans skipped in *other* classes never skew a class's
        rotation.  All-default-priority workloads reduce to the flat
        rotated list (offset == list position) the pre-priority scheduler
        iterated.

        A class containing any *tenanted* scan switches to weighted fair
        ordering instead (``_fair_order_locked``); an all-untenanted
        class keeps this legacy rotation bit-for-bit."""
        by_prio: dict[int, list[_ScanState]] = {}
        for s in self._scans:
            by_prio.setdefault(s.priority, []).append(s)
        out: list[tuple[_ScanState, int]] = []
        for prio in sorted(by_prio):
            cls = by_prio[prio]
            if any(s.tenant is not None for s in cls):
                out.extend(self._fair_order_locked(cls, cursor, which))
                continue
            k = cursor % len(cls)
            out.extend((scan, off)
                       for off, scan in enumerate(cls[k:] + cls[:k]))
        return out

    def _fair_order_locked(self, cls: list[_ScanState], cursor: int,
                           which: str) -> list[tuple[_ScanState, int]]:
        """Stride order for one priority class: tenants ascend by their
        virtual time (``fetch_pass`` or ``item_pass`` — fetch grants and
        decode dispatches are charged separately), registration order
        breaking ties; scans rotate round-robin *within* a tenant via
        ``cursor`` exactly like the legacy per-class rotation.
        Untenanted scans charge the shared weight-1 virtual tenant."""
        groups: dict[int, list[_ScanState]] = {}
        tenants: dict[int, Tenant] = {}
        order: list[Tenant] = []
        for s in cls:
            t = s.tenant if s.tenant is not None else self._default_tenant
            if id(t) not in groups:
                groups[id(t)] = []
                tenants[id(t)] = t
                order.append(t)
        # group scans after discovery so per-tenant lists keep submit order
        for s in cls:
            t = s.tenant if s.tenant is not None else self._default_tenant
            groups[id(t)].append(s)
        attr = "fetch_pass" if which == "fetch" else "item_pass"
        order.sort(key=lambda t: (getattr(t, attr), t.seq))
        out: list[tuple[_ScanState, int]] = []
        for t in order:
            tl = groups[id(t)]
            k = cursor % len(tl)
            out.extend((scan, off)
                       for off, scan in enumerate(tl[k:] + tl[:k]))
        return out

    def _next_fetch_locked(self
                           ) -> tuple[_ScanState, int, bool, bool] | None:
        """Next (scan, seq, subscribed, is_retry) to fetch, priority-
        ordered round-robin across scans with fetch credit.  When an
        identical job for that row group is already in flight (cooperative
        scans), the scan subscribes to it instead — no fetch, no decode,
        the credit stays held until the delivered RG is acked like any
        other.  ``refetch`` seqs (transient-failure requeues) are served
        before new fetch-ahead, already hold their credit, and never
        share — a retry exists to pull *fresh* bytes."""
        n = len(self._scans)
        for scan, off in self._service_order_locked(self._fetch_rr,
                                                    "fetch"):
            if scan.dead:
                continue
            if scan.refetch:
                self._fetch_rr = (self._fetch_rr + off + 1) % max(1, n)
                self._charge_fetch_locked(scan)
                return scan, scan.refetch.popleft(), False, True
            if scan.credits <= 0 or scan.next_fetch >= len(scan.plan):
                continue
            self._fetch_rr = (self._fetch_rr + off + 1) % max(1, n)
            self._charge_fetch_locked(scan)
            scan.credits -= 1
            seq = scan.next_fetch
            scan.next_fetch += 1
            if scan.share_key is not None:
                key = (scan.share_key, scan.plan[seq])
                job = self._inflight.get(key)
                if job is not None:
                    job.subscribers.append((scan, seq))
                    scan.shared_rgs += 1
                    self.shared_rgs += 1
                    return scan, seq, True, False
                if self._window_deliver_locked(scan, seq, key):
                    return scan, seq, True, False
            return scan, seq, False, False
        return None

    def _charge_fetch_locked(self, scan: _ScanState) -> None:
        """Stride accounting: one fetch grant advances the owning
        tenant's fetch-side virtual time by ``1/weight``."""
        ten = scan.tenant if scan.tenant is not None \
            else self._default_tenant
        ten.fetch_pass += 1.0 / ten.weight

    def _window_deliver_locked(self, scan: _ScanState, seq: int,
                               key: tuple) -> bool:
        """Serve one row group from the delivered-result window: the
        retained decoded columns go straight to the scan's in-order done
        queue — no fetch, no decode, the held credit releases on ack
        like any delivery."""
        if self.window_bytes <= 0:
            return False
        hit = self._window.get(key)
        if hit is None:
            return False
        self._window.move_to_end(key)
        cols, io_dt, dec_dt, chunk_times, p2_start, _nb = hit
        scan.done[seq] = (scan.plan[seq], cols, io_dt, dec_dt,
                          list(chunk_times), p2_start)
        scan.shared_rgs += 1
        self.shared_rgs += 1
        self.window_hits += 1
        trace.registry().counter_inc("scheduler.window_hits")
        tr = trace.active()
        if tr is not None:
            tr.instant("window_hit", "io", scan=scan.label,
                       rg=scan.plan[seq],
                       **({"tenant": scan.tenant.name}
                          if scan.tenant is not None else {}))
        scan.done_cv.notify_all()
        return True

    def _window_store_locked(self, key: tuple, cols, io_dt: float,
                             dec_dt: float, chunk_times: list[float],
                             p2_start: int) -> None:
        """Retain one delivered shareable row group, evicting LRU
        entries past the byte cap (decoded payload bytes)."""
        nb = 0
        try:
            for c in cols.values():
                arr = getattr(c, "array", None)
                nb += int(getattr(arr, "nbytes", 0) or 0)
        except AttributeError:
            pass
        nb = max(1, nb)
        if nb > self.window_bytes:
            return                      # larger than the whole window
        old = self._window.pop(key, None)
        if old is not None:
            self._window_nbytes -= old[5]
        self._window[key] = (cols, io_dt, dec_dt, list(chunk_times),
                             p2_start, nb)
        self._window_nbytes += nb
        while self._window_nbytes > self.window_bytes and self._window:
            _, evicted = self._window.popitem(last=False)
            self._window_nbytes -= evicted[5]

    def _fetch_loop(self) -> None:
        while True:
            with self._lock:
                if self._shutdown:
                    return
                got = self._next_fetch_locked()
                if got is None:
                    self._fetch_cv.wait(timeout=0.1)
                    continue
            scan, seq, subscribed, is_retry = got
            if subscribed:
                continue
            if scan.past_deadline():
                self._deadline_fail(scan)
                continue
            t0 = time.perf_counter()
            try:
                raws, io_dt = scan.scanner.fetch_rg(scan.plan[seq])
            except BaseException as e:
                self._handle_failure(e, [(scan, seq)], None)
                continue
            t1 = time.perf_counter()
            tr = trace.active()
            if tr is not None:
                tr.complete("fetch", "io", t0, t1, scan=scan.label,
                            rg=scan.plan[seq], io_dt=io_dt, retry=is_retry,
                            **({"tenant": scan.tenant.name}
                               if scan.tenant is not None else {}))
                trace.registry().observe("scheduler.fetch_wall_s", t1 - t0)
            with self._lock:
                scan.fetch_span[0] = min(scan.fetch_span[0], t0)
                scan.fetch_span[1] = max(scan.fetch_span[1], t1)
                # the adaptive window compares *host* stage walls, so it
                # accumulates the measured fetch time here — io_dt may be
                # simulated (sim backend) and would dwarf the real cost
                self._win["io"] += t1 - t0
                if scan.dead:
                    continue
                # retried row groups never re-register for sharing: their
                # purpose is fresh bytes decoded from scratch
                key = (None if scan.share_key is None or is_retry
                       else (scan.share_key, scan.plan[seq]))
                rgjob = _RgJob(scan, seq, scan.plan[seq], raws, io_dt, key)
                rgjob.enq_t = t1
                if key is not None and key not in self._inflight:
                    # two fetch-pool threads may race the same key for
                    # different scans; first registration wins (the loser
                    # just decodes its own copy — duplicated work, never
                    # wrong results)
                    self._inflight[key] = rgjob
                scan.ready.append(("open", rgjob, None))
                self._work_cv.notify()

    # -- decode stage -------------------------------------------------------

    def _next_item_locked(self, prefer: _ScanState | None
                          ) -> tuple[_ScanState, tuple] | None:
        """Next work item, priority-ordered fair round-robin across scans
        at *row-group* granularity: a worker that just ran an item of
        ``prefer`` keeps
        draining that scan (its in-flight RG finishes and delivers before
        the pool switches away — decode locality, and consumers
        desynchronize instead of bursting), and the round-robin cursor
        advances only at job boundaries."""
        if (prefer is not None and not prefer.dead and prefer.ready
                and prefer in self._scans):
            item = prefer.ready.popleft()
            self._charge_dispatch_locked(prefer, item)
            return prefer, item
        n = len(self._scans)
        for scan, off in self._service_order_locked(self._rr, "item"):
            while scan.ready:
                item = scan.ready.popleft()
                if item[1].live_scan() is None or item[1].failed:
                    continue   # no subscriber left / job failed — drop it
                self._rr = (self._rr + off + 1) % max(1, n)
                self._charge_dispatch_locked(scan, item)
                return scan, item
        return None

    def _charge_dispatch_locked(self, scan: _ScanState,
                                item: tuple) -> None:
        """Stride accounting at row-group granularity: winning a decode
        slot for an "open" item (a fresh row group entering the pool)
        advances the owning tenant's item-side virtual time by
        ``1/weight`` and counts one dispatch — the share the fairness
        tests measure.  Continuation items of an already-open row group
        are never re-charged."""
        if item[0] != "open":
            return
        ten = scan.tenant if scan.tenant is not None \
            else self._default_tenant
        ten.item_pass += 1.0 / ten.weight
        ten.dispatches += 1

    def _worker_loop(self, worker_idx: int = 0) -> None:
        _apply_affinity(worker_idx)
        if self.device is not None:
            import jax
            with jax.default_device(self.device):
                self._worker_loop_inner()
        else:
            self._worker_loop_inner()

    def _worker_loop_inner(self) -> None:
        prefer: _ScanState | None = None
        while True:
            with self._lock:
                got = None
                while got is None:
                    if self._shutdown:
                        return
                    if self._shrink > 0:
                        self._shrink -= 1
                        self._n_workers -= 1
                        return
                    got = self._next_item_locked(prefer)
                    if got is None:
                        prefer = None
                        self._work_cv.wait(timeout=0.2)
            scan, item = got
            try:
                delivered = self._run_item(scan, item)
                prefer = None if delivered else scan
            except BaseException as e:  # noqa: BLE001 — isolated per scan
                prefer = None
                # a failing item affects exactly the scans sharing its job
                # (usually one); the pool and every other scan live on.
                # Transient failures requeue the row group for a fresh
                # fetch within each subscriber's retry budget; the rest
                # fail their scan.
                self._handle_failure(e, list(item[1].subscribers), item[1])

    def _run_item(self, scan: _ScanState, item: tuple) -> bool:
        """Execute one work item; returns True when it completed (and
        delivered) its whole row-group job."""
        kind, rgjob, fn = item
        if rgjob.failed:
            return False
        live = rgjob.live_scan()
        if live is not None and live.past_deadline():
            raise DeadlineExceeded(
                f"scan {live.label}: deadline exceeded")
        t0 = time.perf_counter()
        tr = trace.active()
        if tr is not None and rgjob.enq_t:
            trace.registry().observe("scheduler.queue_wait_s",
                                     max(0.0, t0 - rgjob.enq_t))
        if kind == "open":
            rgjob.job = self._job_for(scan.scanner, rgjob.rg_index,
                                      rgjob.raws)
            tasks = list(rgjob.job.phase1_tasks())
            rgjob.phase = 1
            self._note_item(scan, rgjob, t0, "open")
            return self._enqueue_phase(scan, rgjob, tasks)
        if kind == "task":
            fn()
            self._note_item(scan, rgjob, t0,
                            {1: "decompress", 2: "decode"}.get(rgjob.phase,
                                                               "fused"))
            with self._lock:
                if rgjob.failed:
                    return False   # a sibling item failed concurrently
                rgjob.pending -= 1
                if rgjob.pending > 0:
                    return False
            return self._advance(scan, rgjob)
        raise AssertionError(kind)

    def _enqueue_phase(self, scan: _ScanState, rgjob: _RgJob,
                       tasks: list[Callable[[], None]]) -> bool:
        """Queue one phase's items, or fall through to the next phase /
        finalize when the phase is empty.  Continuation items go to the
        *front* of the scan's queue, ahead of later row groups' "open"
        items — an in-flight RG always finishes before the next one
        starts, so in-order delivery is never starved by fetch-ahead."""
        if not tasks:
            return self._advance(scan, rgjob)
        with self._lock:
            rgjob.pending = len(tasks)
            rgjob.enq_t = time.perf_counter()
            target = rgjob.live_scan()   # a subscriber may have died
            if target is None:
                return False
            for fn in reversed(tasks):
                target.ready.appendleft(("task", rgjob, fn))
            self._work_cv.notify_all()
        return False

    def _advance(self, scan: _ScanState, rgjob: _RgJob) -> bool:
        """Phase transition on the worker that drained the previous phase:
        1 → build+queue phase-2 items; 2 → queue fused phase-3 items when
        the job has any (late materialization); else finalize (join) and
        deliver."""
        if rgjob.failed:
            return False
        if rgjob.phase == 1:
            t0 = time.perf_counter()
            tasks = list(rgjob.job.phase2_tasks())
            rgjob.phase = 2
            self._note_item(scan, rgjob, t0, "transition")
            rgjob.p2_start = len(rgjob.chunk_times)
            return self._enqueue_phase(scan, rgjob, tasks)
        if rgjob.phase == 2:
            getter = getattr(rgjob.job, "phase3_tasks", None)
            tasks = list(getter()) if getter is not None else []
            rgjob.phase = 3
            if tasks:
                # the fused stage needs every phase-2 column decoded; the
                # modeled schedule treats the whole decode as one serial
                # span for such jobs (p2_start = 0 — conservative)
                t0 = time.perf_counter()
                self._note_item(scan, rgjob, t0, "transition")
                rgjob.p2_start = 0
                return self._enqueue_phase(scan, rgjob, tasks)
            # empty: fall straight through to finalize with NO extra
            # chunk-time item, so unfused accounting is untouched
        t0 = time.perf_counter()
        cols = rgjob.job.finalize()
        self._note_item(scan, rgjob, t0, "finalize")
        dec_dt = sum(rgjob.chunk_times)
        with self._lock:
            # decode side of the adaptive window accrues ONCE per job here
            # — a cooperative job has many subscribers but ran one decode
            self._win["dec"] += dec_dt
            if (rgjob.key is not None
                    and self._inflight.get(rgjob.key) is rgjob):
                self._inflight.pop(rgjob.key)
                if self.window_bytes > 0:
                    # delivered-result window: retain the decoded columns
                    # under the same share identity, so an identical scan
                    # arriving after this one finishes still reuses them
                    self._window_store_locked(rgjob.key, cols, rgjob.io_dt,
                                              dec_dt,
                                              list(rgjob.chunk_times),
                                              rgjob.p2_start)
            for sub, seq in rgjob.subscribers:
                if sub.dead:
                    continue
                sub.done[seq] = (rgjob.rg_index, cols, rgjob.io_dt,
                                 dec_dt, list(rgjob.chunk_times),
                                 rgjob.p2_start)
                sub.done_cv.notify_all()
        return True

    def _note_item(self, scan: _ScanState, rgjob: _RgJob,
                   t0: float, kind: str = "item") -> None:
        t1 = time.perf_counter()
        tr = trace.active()
        if tr is not None:
            tr.complete(kind, "decode", t0, t1, scan=scan.label,
                        rg=rgjob.rg_index,
                        **({"tenant": scan.tenant.name}
                           if scan.tenant is not None else {}))
        with self._lock:
            rgjob.chunk_times.append(t1 - t0)
            for sub, _ in rgjob.subscribers:
                sub.decode_span[0] = min(sub.decode_span[0], t0)
                sub.decode_span[1] = max(sub.decode_span[1], t1)

    @staticmethod
    def _job_for(scanner, rg_index: int, raws):
        mk = getattr(scanner, "decode_job", None)
        if mk is not None:
            return mk(rg_index, raws)
        return OpaqueDecodeJob(scanner, rg_index, raws)

    # -- completion / failure ----------------------------------------------

    def _ack_locked(self, scan: _ScanState, item: tuple | None,
                    consume_dt: float) -> None:
        scan.credits += 1
        scan.workers_seen = max(scan.workers_seen, self.pool_size)
        if item is not None:
            # consume is per-consumer; fetch accrued at fetch time and
            # decode at delivery time (once per job — cooperative jobs
            # have many subscribers but ran one decode), all measured
            # host walls, never simulated io_dt
            self._win["cons"] += consume_dt
            self._win["rgs"] += 1
            self._resize_window_locked()
        self._fetch_cv.notify_all()

    def _migrate_items_locked(self, scan: _ScanState) -> None:
        """Re-home queued items whose jobs other scans still subscribe to
        (cooperative scans) before this scan's queue is torn down."""
        moved = False
        n = len(scan.ready)
        for _ in range(n):
            item = scan.ready.popleft()
            target = item[1].live_scan()
            if target is not None and target is not scan:
                target.ready.append(item)
                moved = True
        if moved:
            self._work_cv.notify_all()

    def _purge_inflight_locked(self) -> None:
        """Drop in-flight shared jobs nobody subscribes to anymore, so a
        future scan cannot join a job whose items were discarded."""
        for key in [k for k, j in self._inflight.items()
                    if j.live_scan() is None]:
            self._inflight.pop(key)

    def _fail_scan(self, scan: _ScanState, exc: BaseException) -> None:
        with self._lock:
            if scan.error is None and not scan.finished:
                scan.error = exc
            self._migrate_items_locked(scan)
            scan.ready.clear()
            self._purge_inflight_locked()
            scan.done_cv.notify_all()
            self._fetch_cv.notify_all()

    def _deadline_fail(self, scan: _ScanState) -> None:
        with self._lock:
            self._deadline_fail_locked(scan)

    def _deadline_fail_locked(self, scan: _ScanState) -> None:
        """Expire one scan's whole-scan deadline: counted as a timeout,
        never retried (the deadline IS the budget)."""
        if scan.dead:
            return
        cf = getattr(scan.scanner, "count_fault", None)
        if cf is not None:
            cf(timeouts=1)
        tr = trace.active()
        if tr is not None:
            tr.instant("deadline", "fault", scan=scan.label)
        self._fail_scan(scan, DeadlineExceeded(
            f"scan {scan.label}: deadline exceeded"))

    def _handle_failure(self, exc: BaseException,
                        subscribers: list[tuple["_ScanState", int]],
                        rgjob: "_RgJob | None") -> None:
        """Route one failed fetch (``rgjob`` None) or decode item to its
        subscriber scans (DESIGN.md §6).  Transient failures *requeue* the
        row group for a fresh fetch + decode within the scan's retry
        budget — evicting anything the failed attempt pushed into the
        shared caches first, so a retry always decodes fresh bytes.
        Everything else permanently fails that scan only: its shared-cache
        entries are evicted (no poisoning), its queued items drop, and the
        pool and every other scan live on."""
        with self._lock:
            if rgjob is not None:
                if rgjob.failed:
                    return   # a concurrent sibling item already routed it
                rgjob.failed = True
                if (rgjob.key is not None
                        and self._inflight.get(rgjob.key) is rgjob):
                    self._inflight.pop(rgjob.key)
            for scan, seq in subscribers:
                if scan.dead:
                    continue
                if scan.past_deadline():
                    self._deadline_fail_locked(scan)
                    continue
                if isinstance(exc, DeadlineExceeded):
                    # this scan's own deadline is fine (checked above): a
                    # cooperative sibling's budget expired and killed the
                    # shared job — not this scan's fault, requeue free
                    retryable = True
                else:
                    retryable = is_retryable(exc)
                    rd = getattr(scan.scanner, "retry_decode", None)
                    if rd is not None:
                        # counts checksum/timeout once and evicts this
                        # RG's shared-cache entries (retry or not)
                        retryable = rd(scan.plan[seq], exc) and retryable
                if retryable and scan.retries_left > 0:
                    scan.retries_left -= 1
                    cf = getattr(scan.scanner, "count_fault", None)
                    if cf is not None:
                        cf(retries=1)
                    # the seq keeps holding its in-flight credit (released
                    # only on ack), so the retry cannot over-subscribe the
                    # scan's depth bound
                    scan.refetch.append(seq)
                    tr = trace.active()
                    if tr is not None:
                        tr.instant("requeue", "fault", scan=scan.label,
                                   rg=scan.plan[seq],
                                   error=type(exc).__name__)
                    trace.registry().counter_inc("scheduler.requeues")
                    continue
                # permanent: drop every shared-cache entry this scan's
                # planner may have populated, then fail it in isolation
                planner = getattr(scan.scanner, "planner", None)
                if planner is not None:
                    try:
                        planner.evict_file()
                    except Exception:
                        pass
                self._fail_scan(scan, exc)
            self._fetch_cv.notify_all()

    def _finish_scan_locked(self, scan: _ScanState) -> None:
        if scan.finished:
            return
        scan.finished = True
        ten = scan.tenant
        if ten is not None:
            # release the admission slot and record the scan's wall for
            # the SLO-aware sizer; queued submitters wake here
            ten.active = max(0, ten.active - 1)
            ten.latencies.append(time.monotonic() - scan.t_submit)
            trace.registry().gauge_set(
                f"scheduler.tenant_depth.{ten.name}", ten.active)
            self._admit_cv.notify_all()
        self._migrate_items_locked(scan)
        scan.ready.clear()
        scan.done.clear()
        self._purge_inflight_locked()
        if scan in self._scans:
            self._scans.remove(scan)
        self._rr = 0 if not self._scans else self._rr % len(self._scans)
        self._fetch_rr = 0 if not self._scans else \
            self._fetch_rr % len(self._scans)
        self._retarget_locked()
        scan.done_cv.notify_all()
        self._fetch_cv.notify_all()


# ---------------------------------------------------------------------------
# process-wide singleton
# ---------------------------------------------------------------------------

#: every live ScanService, for process-wide cache clears (cold ladders)
_ALL_SERVICES: "weakref.WeakSet[ScanService]" = weakref.WeakSet()

_SERVICE: ScanService | None = None
_SERVICE_LOCK = threading.Lock()


def clear_delivered_windows() -> None:
    """Clear the delivered-result window of every live ScanService —
    the cold-scan ladders' guarantee that each round refetches and
    redecodes for real (tests/test_system.py, bench_encoding,
    bench_compression, tools/chaos_check.py)."""
    for svc in list(_ALL_SERVICES):
        try:
            svc.clear_delivered_window()
        except Exception:
            pass


def scan_service() -> ScanService:
    """The process-wide ScanService every run_overlapped/q6/q12 call
    shares (created on first use)."""
    global _SERVICE
    with _SERVICE_LOCK:
        if _SERVICE is None:
            _SERVICE = ScanService()
        return _SERVICE


def shutdown_scan_service() -> None:
    """Tear down the singleton (tests, atexit); idempotent — the next
    scan_service() call builds a fresh one."""
    global _SERVICE
    with _SERVICE_LOCK:
        if _SERVICE is not None:
            _SERVICE.shutdown()
            _SERVICE = None


@atexit.register
def _shutdown_at_exit() -> None:
    # Interpreter-shutdown net: tear the singleton down while its threads
    # and condition variables are still joinable, so abandoned ScanHandles
    # collected during final GC find a finished service instead of racing
    # a half-torn-down interpreter (their cancel() additionally guards on
    # sys.is_finalizing for handles that outlive even this hook).
    try:
        shutdown_scan_service()
    except Exception:
        pass
