"""Read the program's flight recorder beside the device trace.

The program records host spans (``repro.core.trace``: fetch, decompress,
decode, pack, stage, device_wait, consume, to_host, to_device, queued, …)
on ``perf_counter``.  A query served while a JAX profiler session runs
turns that recorder on, and every recorder and every query the front end
starts emit a clock anchor: a ``repro_clock`` profiler annotation that
carries the ``perf_counter_ns`` it opened at.

* ``window_spans`` and ``per_query_ms`` give the per-layer readers
  (``bench/metrics/*_ms_per_query.py``) the recorder's spans inside a
  run's window; a recorder that dropped any event fails the run.
* ``anchors`` reads the clock anchors of a profile; ``Clock`` fits the
  profiler's clock as a linear function of ``perf_counter`` from them, so
  each recorder span lands on the device trace's time base.
* ``idle_by_stage`` charges each instant of device idle to the innermost
  leaf span over it (the shortest), or to none; ``span_coverage`` gives,
  per query, the share of submit→done that leaf spans cover;
  ``label_gaps`` names the longest idle gaps
  ``"<innermost leaf span> / <host event>"``.  Each takes
  ``trace_reduce.extract``'s cut.
"""

from __future__ import annotations

import collections
import glob
import heapq
import os
import statistics

from bench import trace_reduce

ANCHOR = "repro_clock"
#: spans that frame others (a run, a fragment); every other complete span
#: is a leaf stage
STRUCTURAL = frozenset({"scan", "dataset_scan", "distributed_scan",
                        "fragment"})
NONE = "none"

Span = collections.namedtuple("Span", "name start end")


# ---------------------------------------------------------------------------
# the recorder's spans, for the per-layer readers
# ---------------------------------------------------------------------------

def recorder():
    """The recorder the profiler session of the run turned on, handed over
    by the program once; None when the program has none."""
    from repro.core import trace
    followed = getattr(trace, "followed", None)
    return followed() if followed is not None else None


def spans_of(tracer) -> list[Span]:
    """The recorder's complete spans, in ``perf_counter`` seconds."""
    return [Span(e.name, tracer.epoch + e.ts, tracer.epoch + e.ts + e.dur)
            for e in tracer.events() if e.ph == "X"]


#: the run whose spans were read last, and those spans: the program hands
#: its recorder over once, and every reader of one run reads the same
_read: list = [None, None]


def window_spans(run) -> list[Span] | None:
    """The recorder's complete spans that start inside the run's window
    (first submit to last answer of its completed queries); None when
    there is no query or no recorder."""
    if not run.records:
        return None
    if _read[0] is run:
        return _read[1]
    tracer = recorder()
    if tracer is None:
        return None
    if tracer.dropped:
        raise RuntimeError(f"the flight recorder dropped {tracer.dropped} "
                           f"events (cap {tracer.cap})")
    w0 = min(r.submitted for r in run.records)
    w1 = max(r.done for r in run.records)
    spans = [s for s in spans_of(tracer) if w0 <= s.start <= w1]
    _read[:] = [run, spans]
    return spans


def per_query_ms(run, names: set[str]) -> float | None:
    """The window's spans of these names, summed, per completed query,
    in ms."""
    spans = window_spans(run)
    if spans is None:
        return None
    total = sum(s.end - s.start for s in spans if s.name in names)
    return total / len(run.records) * 1e3


# ---------------------------------------------------------------------------
# the device trace, on one clock with the recorder
# ---------------------------------------------------------------------------

def anchors(trace_dir: str) -> list[list[int]]:
    """The clock anchors of the newest ``.xplane.pb`` under ``trace_dir``
    (the one ``trace_reduce.extract`` reads): one ``[perf_counter_ns,
    profiler_ns]`` pair each, in order."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        pc = dict(ev.stats).get("pc_ns")
                        if pc is not None:
                            out.append([int(pc), ev.start_ns])
    return sorted(out)


class Clock:
    """Profiler ns as a linear function of ``perf_counter`` seconds, fitted
    by least squares to the anchors (one anchor: an offset alone)."""

    def __init__(self, anchors: list):
        if not anchors:
            raise ValueError("no clock anchor in the profile")
        self.x0 = anchors[0][0]
        xs = [pc - self.x0 for pc, _ in anchors]
        ys = [prof for _, prof in anchors]
        mx, my = statistics.fmean(xs), statistics.fmean(ys)
        var = sum((x - mx) ** 2 for x in xs)
        self.slope = (sum((x - mx) * (y - my) for x, y in zip(xs, ys)) / var
                      if var > 0 else 1.0)
        self.offset = my - self.slope * mx
        self.n = len(anchors)
        self.residual_ns = max(abs(y - self.ns(pc / 1e9))
                               for (pc, _), y in zip(anchors, ys))

    def ns(self, pc_s: float) -> float:
        return self.offset + self.slope * (pc_s * 1e9 - self.x0)

    def span(self, s: Span) -> Span:
        return Span(s.name, self.ns(s.start), self.ns(s.end))


def idle_intervals(cut: dict) -> list[tuple[float, float]]:
    """The device's idle intervals inside the window (profiler ns), chip
    by chip, as ``trace_reduce.reduce`` finds them."""
    w0, w1 = cut["window"]
    gaps = []
    for events in cut["devices"].values():
        spans = []
        for _, start, dur in trace_reduce._top_level(events):
            s, e = max(start, w0), min(start + dur, w1)
            if e > s:
                spans.append((s, e))
        edges = [w0] + [x for iv in trace_reduce._union(spans)
                        for x in iv] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    return gaps


def _attribute(windows: list[tuple[float, float]],
               spans: list[Span]) -> dict[str, float]:
    """Charge each instant inside ``windows`` (disjoint intervals) to the
    shortest leaf span over it, by name, or to ``NONE``."""
    points = []
    for s, e in windows:
        points += [(s, 1, 0, None), (e, 0, 0, None)]
    for i, sp in enumerate(spans):
        if sp.name not in STRUCTURAL and sp.end > sp.start:
            points += [(sp.start, 1, 1, i), (sp.end, 0, 1, i)]
    points.sort(key=lambda p: (p[0], p[1]))
    out: dict[str, float] = {}
    heap: list[tuple[float, int]] = []
    gone: set[int] = set()
    inside, prev = 0, None
    for t, opening, is_span, i in points:
        if inside and prev is not None and t > prev:
            while heap and heap[0][1] in gone:
                heapq.heappop(heap)
            name = spans[heap[0][1]].name if heap else NONE
            out[name] = out.get(name, 0.0) + (t - prev)
        prev = t
        if not is_span:
            inside += 1 if opening else -1
        elif opening:
            heapq.heappush(heap, (spans[i].end - spans[i].start, i))
        else:
            gone.add(i)
    return out


def idle_by_stage(cut: dict, spans: list[Span], clock: Clock
                  ) -> dict[str, float]:
    """Seconds of device idle in the window under each leaf span (the
    innermost one), and under none, averaged over the chips."""
    mapped = [clock.span(s) for s in spans]
    out: collections.Counter = collections.Counter()
    w0, w1 = cut["window"]
    for events in cut["devices"].values():
        one = {"window": [w0, w1], "devices": {"d": events}}
        for name, ns in _attribute(idle_intervals(one), mapped).items():
            out[name] += ns / 1e9 / len(cut["devices"])
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def span_coverage(records, spans: list[Span]) -> list[float]:
    """Per query, the share of submit→done that leaf spans cover."""
    out = []
    for r in records:
        inside = [s for s in spans
                  if s.end > r.submitted and s.start < r.done]
        shares = _attribute([(r.submitted, r.done)], inside)
        out.append(1.0 - shares.get(NONE, 0.0) / (r.done - r.submitted))
    return out


def label_gaps(cut: dict, spans: list[Span], clock: Clock | None
               ) -> list[list]:
    """The longest idle gaps, in ``trace_reduce.reduce``'s order, each
    named ``"<innermost leaf span> / <host event>"`` where a leaf span
    covers its middle, else by the host event alone."""
    gaps = idle_intervals(cut)
    gaps.sort(key=lambda g: g[0] - g[1])
    mapped = [clock.span(s) for s in spans
              if s.name not in STRUCTURAL] if clock else []
    out = []
    for s, e in gaps[:trace_reduce.TOP]:
        host = trace_reduce._host_doing(cut["host"], s, e)
        mid = (s + e) / 2
        over = [m for m in mapped if m.start <= mid < m.end]
        leaf = min(over, key=lambda m: m.end - m.start).name if over \
            else None
        out.append([f"{leaf} / {host}" if leaf else host, (e - s) / 1e9])
    return out
