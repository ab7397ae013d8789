"""Scan flight recorder + process metrics registry (DESIGN.md §10).

The paper's thesis makes "why is this scan slow" the central operational
question, but end-of-run aggregates (ScanMetrics counters, stage_walls)
cannot show pipeline bubbles, prefetch stalls, steal storms, or retry
bursts *inside* a run.  This module records a bounded, thread-safe event
timeline — typed spans with thread/scan/fragment/RG attribution — that
exports as Chrome/Perfetto trace-event JSON (``chrome://tracing``,
https://ui.perfetto.dev) and feeds ``tools/trace_report.py``'s
critical-path and stage-bucket attribution.

Design constraints, in order:

1. **Off by default, near-zero cost when off.**  Every instrumented site
   guards on ``trace.active()`` — one module-global load and a None
   check — and reuses the ``perf_counter`` timestamps the site already
   takes for ScanMetrics, so tracing-off adds no timing calls and
   tracing-on adds one lock + list append per event (the ≤5% CI budget,
   tools/trace_check.py).
2. **Bounded.**  The recorder is a flight recorder, not a log: a global
   event cap plus a per-scan cap (one chatty scan cannot evict the
   others' events).  Overflow increments drop counters that export in
   the trace metadata — silent truncation never reads as "nothing
   happened".
3. **Thread-safe.**  Fetch threads, decode workers, consume threads,
   fragment workers and device workers all record concurrently; events
   carry their recording thread id for per-track rendering.

Enablement: the ``REPRO_TRACE`` environment variable (``1``/``true`` →
record; any other non-empty non-zero value → record *and* export to that
path at process exit), or programmatically via ``trace.request(...)`` —
the refcounted context manager behind every ``trace=`` kwarg
(``run_overlapped``, ``run_dataset_scan``, …): ``True`` records for the
duration, a path string additionally exports on exit.  The query front
end also follows the JAX profiler (``follow_profiler``): a query served
while a profiler session collects host events turns the recorder on,
with a cap sized for a whole measured window (``REPRO_TRACE_CAP`` still
overrides it); ``followed`` hands that recorder over once.

One clock: every recorder, once created, and every query the front end
starts emit a clock anchor (``clock_anchor``), a ``repro_clock`` profiler
annotation that carries its own ``perf_counter_ns``.  From the anchors a
profile records, ``bench/span_reduce.py`` maps the recorder's events
onto the device trace's time base.

The **metrics registry** is the aggregate sibling: process-wide
counters / gauges / histograms (pool depth, queue wait, fetch wall,
steals, retries) that cost one dict update at coarse
boundaries and snapshot into ``ScanMetrics.registry_snapshot`` /
``DatasetRunReport.registry_snapshot`` — informational columns only,
never a gated count.  Registry updates at per-item granularity are also
gated on ``active()`` so the tracing-off hot path stays untouched.

Event vocabulary (``tools/trace_report.py`` buckets on these):

  cat "io"        fetch (per-RG coalesced batch), storage_read,
                  prefetch_issue / prefetch_hit / prefetch_miss,
                  retry_attempt / fetch_timeout / short_read
  cat "decode"    open, decompress (phase 1), transition, decode
                  (phase 2), fused (phase 3), finalize, decode_rg
                  (monolithic inline/blocking decode)
  cat "consume"   consume (per-RG reducer on the caller's thread),
                  exact_sum (Q6's exact DECIMAL reduce of one row group:
                  args.rows, args.blocks)
  cat "scan"      scan (whole-run span), dataset_scan, distributed_scan
  cat "fragment"  fragment (per-attempt), shard_assign, steal,
                  quarantine
  cat "fault"     fault_injected, requeue, checksum_failure, deadline
  cat "kernel"    kernel_launch (instant, counted n)
  cat "frontend"  queued (submit to run, and any admission wait)
  cat "device"    device_wait (the host blocked on a device result;
                  args.site names where)

The decode plan's pallas groups add ``pack`` (arena and array fill on
the host) and ``stage`` (their host→device transfer) under cat
"decode"; the unfused consume adds ``to_device`` under cat "consume"
for a host-resident column it uploads (a device column is read where it
is).  A small result brought to the host (Q6's partial sums, Q12's
counts) is a ``device_wait`` as a whole.

Multi-tenant attribution (DESIGN.md §11): fetch and decode-item spans
emitted by the scheduler carry an ``args.tenant`` tag when the scan was
submitted under a registered tenant; ``window_hit`` instants (cat
"io") mark row groups served from the delivered-result window instead
of storage, and ``result_cache_hit`` instants mark whole fragments
served from the fragment result cache.  ``tools/trace_report.py`` aggregates these into a
per-tenant wall-attribution breakdown; untagged spans are charged to
the shared ``-`` tenant, mirroring the scheduler's weight-1 virtual
tenant.  The registry's tenancy surface: counters
``scheduler.window_hits``, ``scheduler.admission_rejects``,
``scheduler.admission_queued``, ``scheduler.slo_boosts``,
``result_cache.{hits,misses,evictions,invalidated}``, and one
``scheduler.tenant_depth.<name>`` gauge per tenant (current active
scans — the per-tenant queue depth).  Exact decimals: counters
``decimal_exact_queries`` and ``fused_declined_decimal`` (per Q6 over
DECIMAL operands, and per such query that asked for fusion), and
``int64_narrowed_chunks`` (per INT64 chunk decoded on the device as
int32, while the recorder is on).
"""

from __future__ import annotations

import json
import os
import threading
import time

#: default global event cap (REPRO_TRACE_CAP overrides); at ~7 events
#: per row group a 64k buffer holds ~9k row groups of timeline
DEFAULT_CAP = 65_536
#: per-scan share of the buffer: one scan label may hold at most this
#: fraction of the global cap before its events start dropping
PER_SCAN_FRACTION = 0.5
#: event cap of a recorder that a profiler session turned on (REPRO_TRACE_CAP
#: overrides): a 51 s window of Q6 over 486 one-page row groups records
#: about 200k events
PROFILER_CAP = 1 << 20
#: name of the clock-anchor annotation (``clock_anchor``)
ANCHOR = "repro_clock"


def clock_anchor() -> None:
    """A profiler annotation named ``ANCHOR`` that carries the
    ``perf_counter_ns`` taken as it opens: one point on both clocks.
    Outside a profiler session it is an inactive TraceMe."""
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(ANCHOR, pc_ns=time.perf_counter_ns()):
        pass


def _profiling() -> bool:
    """True while a JAX profiler session collects host events."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation.is_enabled()


class TraceEvent:
    """One recorded event.  ``ts``/``dur`` are perf_counter seconds
    relative to the tracer's epoch; ``ph`` is the Chrome phase ("X"
    complete span, "i" instant)."""

    __slots__ = ("name", "cat", "ph", "ts", "dur", "tid", "args")

    def __init__(self, name: str, cat: str, ph: str, ts: float,
                 dur: float, tid: int, args: dict):
        self.name = name
        self.cat = cat
        self.ph = ph
        self.ts = ts
        self.dur = dur
        self.tid = tid
        self.args = args

    def to_chrome(self, pid: int) -> dict:
        ev = {"name": self.name, "cat": self.cat, "ph": self.ph,
              "ts": self.ts * 1e6, "pid": pid, "tid": self.tid}
        if self.ph == "X":
            ev["dur"] = self.dur * 1e6
        elif self.ph == "i":
            ev["s"] = "t"
        if self.args:
            ev["args"] = self.args
        return ev


class MetricsRegistry:
    """Process-wide counters / gauges / histograms.

    Lock-protected plain dicts: ``counter_inc`` adds, ``gauge_set``
    overwrites, ``observe`` accumulates (count, sum, min, max) — cheap
    enough for coarse-grained call sites (per row group / per resize),
    with per-item sites additionally gated on ``trace.active()``.
    ``snapshot()`` returns a plain-dict copy safe to stash in reports.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, list[float]] = {}

    def counter_inc(self, name: str, n: float = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def gauge_set(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                self._hists[name] = [1, value, value, value]
            else:
                h[0] += 1
                h[1] += value
                h[2] = min(h[2], value)
                h[3] = max(h[3], value)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {
                    name: {"count": h[0], "sum": h[1], "min": h[2],
                           "max": h[3], "mean": h[1] / max(1, h[0])}
                    for name, h in self._hists.items()},
            }

    def clear(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


class Tracer:
    """Bounded thread-safe event recorder (see module docstring)."""

    def __init__(self, cap: int | None = None):
        if cap is None:
            cap = int(os.environ.get("REPRO_TRACE_CAP", DEFAULT_CAP))
        self.cap = max(16, cap)
        self.scan_cap = max(8, int(self.cap * PER_SCAN_FRACTION))
        self.epoch = time.perf_counter()
        self._lock = threading.Lock()
        self._events: list[TraceEvent] = []
        self._per_scan: dict[object, int] = {}
        self.dropped = 0
        self.dropped_by_scan: dict[object, int] = {}
        clock_anchor()

    # -- recording ----------------------------------------------------------

    def _admit_locked(self, args: dict) -> bool:
        if len(self._events) >= self.cap:
            self.dropped += 1
            return False
        scan = args.get("scan")
        if scan is not None:
            n = self._per_scan.get(scan, 0)
            if n >= self.scan_cap:
                self.dropped += 1
                self.dropped_by_scan[scan] = \
                    self.dropped_by_scan.get(scan, 0) + 1
                return False
            self._per_scan[scan] = n + 1
        return True

    def complete(self, name: str, cat: str, t0: float, t1: float,
                 **args) -> None:
        """Record a complete span from two perf_counter stamps the call
        site already took (the zero-extra-timing contract)."""
        with self._lock:
            if not self._admit_locked(args):
                return
            self._events.append(TraceEvent(
                name, cat, "X", t0 - self.epoch, max(0.0, t1 - t0),
                threading.get_ident(), args))

    def instant(self, name: str, cat: str, **args) -> None:
        ts = time.perf_counter() - self.epoch
        with self._lock:
            if not self._admit_locked(args):
                return
            self._events.append(TraceEvent(
                name, cat, "i", ts, 0.0, threading.get_ident(), args))

    class _Span:
        __slots__ = ("tracer", "name", "cat", "args", "t0")

        def __init__(self, tracer, name, cat, args):
            self.tracer = tracer
            self.name = name
            self.cat = cat
            self.args = args

        def __enter__(self):
            self.t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self.tracer.complete(self.name, self.cat, self.t0,
                                 time.perf_counter(), **self.args)

    def span(self, name: str, cat: str, **args) -> "Tracer._Span":
        """Context-manager span for sites without existing timestamps."""
        return Tracer._Span(self, name, cat, args)

    # -- inspection / export ------------------------------------------------

    def events(self) -> list[TraceEvent]:
        with self._lock:
            return list(self._events)

    def event_count(self) -> int:
        with self._lock:
            return len(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._per_scan.clear()
            self.dropped = 0
            self.dropped_by_scan.clear()
        self.epoch = time.perf_counter()

    def to_chrome(self) -> dict:
        """The Chrome/Perfetto trace-event document (``traceEvents`` +
        metadata: drop counters and the registry snapshot)."""
        pid = os.getpid()
        with self._lock:
            events = [e.to_chrome(pid) for e in self._events]
            dropped = self.dropped
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "dropped": dropped,
                "cap": self.cap,
                "registry": registry().snapshot(),
            },
        }

    def export(self, path: str) -> str:
        doc = self.to_chrome()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


# ---------------------------------------------------------------------------
# module-level enablement (env var + refcounted request())
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_tracer: Tracer | None = None
_env_checked = False
_requests = 0          # active trace.request() contexts
_env_on = False        # REPRO_TRACE kept the tracer on
_followed: Tracer | None = None   # the recorder follow_profiler turned on
_registry = MetricsRegistry()


def registry() -> MetricsRegistry:
    """The process-wide metrics registry (always available; callers at
    per-item granularity should still gate on ``active()``)."""
    return _registry


def _resolve_env_locked() -> None:
    global _env_checked, _env_on, _tracer
    _env_checked = True
    val = os.environ.get("REPRO_TRACE", "").strip()
    if not val or val.lower() in ("0", "off", "false", "none"):
        return
    _env_on = True
    if _tracer is None:
        _tracer = Tracer()
    if val.lower() not in ("1", "true", "on", "yes"):
        # a path value: export the flight recorder at process exit
        import atexit
        tr = _tracer
        atexit.register(lambda: tr.export(val))


def active() -> Tracer | None:
    """The live tracer, or None when tracing is off — THE hot-path guard
    every instrumented site calls (module-global load + None check)."""
    tr = _tracer
    if tr is not None:
        return tr
    if _env_checked:
        return None
    with _lock:
        if not _env_checked:
            _resolve_env_locked()
        return _tracer


def enabled() -> bool:
    return active() is not None


def enable(cap: int | None = None) -> Tracer:
    """Turn the recorder on (idempotent); returns the tracer."""
    global _tracer, _env_checked
    with _lock:
        if not _env_checked:
            _resolve_env_locked()
        if _tracer is None:
            _tracer = Tracer(cap=cap)
        return _tracer


def disable() -> None:
    """Turn the recorder off.  The Tracer object itself stays valid for
    callers still holding a reference (events remain readable)."""
    global _tracer
    with _lock:
        _tracer = None


def follow_profiler() -> Tracer | None:
    """The live tracer for one served query (the front end's per-query
    check).  While a JAX profiler session collects host events the
    recorder is on: turned on here, with ``REPRO_TRACE_CAP`` or else
    ``PROFILER_CAP``, if nothing else turned it on.  A recorder this
    turned on goes off at ``stop_following``, and is let go at the first
    call after the session has ended if ``followed`` has not taken it."""
    global _tracer, _followed
    tr = active()
    if tr is None:
        if not _profiling():
            if _followed is not None:
                followed()
            return None
        cap = int(os.environ.get("REPRO_TRACE_CAP", PROFILER_CAP))
        with _lock:
            if _tracer is None:
                _tracer = _followed = Tracer(cap=cap)
            return _tracer
    if tr is _followed and not _profiling():
        followed()
        return None
    return tr


def stop_following() -> None:
    """Turn off a recorder that ``follow_profiler`` turned on; its events
    stay for ``followed``."""
    global _tracer
    with _lock:
        if _tracer is not None and _tracer is _followed:
            _tracer = None


def followed() -> Tracer | None:
    """Hand over the recorder the newest profiler session turned on, once:
    it goes off if it is still on, and this module keeps no reference to
    it or its events."""
    global _tracer, _followed
    with _lock:
        tr, _followed = _followed, None
        if tr is not None and _tracer is tr:
            _tracer = None
    return tr


def reset() -> None:
    """Test hook: drop the tracer, forget the env resolution, zero the
    refcount, and clear the registry — the next ``active()`` re-reads
    REPRO_TRACE."""
    global _tracer, _env_checked, _requests, _env_on, _followed
    with _lock:
        _tracer = None
        _env_checked = False
        _requests = 0
        _env_on = False
        _followed = None
    _registry.clear()


class _Request:
    """Refcounted enable: nested/concurrent ``trace=`` runs share one
    tracer; the recorder turns off only when the last request exits and
    REPRO_TRACE didn't independently keep it on."""

    def __init__(self, path: str | None):
        self.path = path
        self.tracer: Tracer | None = None

    def __enter__(self) -> Tracer:
        global _requests
        self.tracer = enable()
        with _lock:
            _requests += 1
        return self.tracer

    def __exit__(self, *exc) -> None:
        global _requests
        if self.path is not None:
            self.tracer.export(self.path)
        with _lock:
            _requests = max(0, _requests - 1)
            last = _requests == 0
        if last and not _env_on and self.tracer is not _followed:
            disable()


class _NullRequest:
    def __enter__(self) -> Tracer | None:
        return active()

    def __exit__(self, *exc) -> None:
        pass


def request(arg: bool | str | None):
    """The context manager behind every ``trace=`` kwarg:

      None / False   no change (returns whatever is already active)
      True           record for the duration of the context
      "<path>"       record and export Chrome JSON to <path> on exit
    """
    if arg is None or arg is False:
        return _NullRequest()
    return _Request(arg if isinstance(arg, str) else None)
