"""Post-mortem analysis of one flight-recorder export (DESIGN.md §10).

Consumes the Chrome/Perfetto trace-event JSON written by
``core/trace.py`` (``Tracer.export``) and answers the question the raw
timeline can't: *which stage bounds this scan?*  Three views:

  validate   schema check — required keys, known phase types, no
             negative timestamps/durations, balanced begin/end pairs.
  buckets    every instrumented span is attributed to exactly one of
             ``fetch`` / ``decompress`` / ``decode`` / ``consume`` by a
             fixed priority (consume > decode > decompress > fetch —
             overlapped work counts toward the *latest* pipeline stage,
             which is the one that would have to shrink for wall time
             to improve); uncovered run time is ``stall``.  The five
             buckets partition the run wall exactly.
  report     run wall (from the outermost scan span), the bucket
             breakdown, per-row-group critical-path chains
             (fetch → decode items → consume), an effective-bandwidth
             breakdown (stored bytes fetched, logical bytes consumed),
             a per-tenant wall attribution (DESIGN.md §11 — spans the
             scheduler tagged with ``args.tenant``; untagged work is
             charged to the shared ``-`` tenant), and the named
             bottleneck stage — the largest bucket.

Usage:
    python tools/trace_report.py TRACE.json [--json]

``--json`` prints the machine-readable report (tools/trace_check.py
consumes it); the default is a human summary.  Exit code is non-zero
when the trace fails validation.
"""

from __future__ import annotations

import argparse
import json
import sys

VALID_PH = ("X", "i", "M", "B", "E")

#: span name → attribution bucket; structural spans (scan / fragment /
#: dataset_scan / the front end's queued) frame the timeline and are
#: deliberately unmapped.  ``pack``/``stage`` nest in decode
#: items, ``to_device`` (a host-resident column's upload) in consume,
#: and ``to_host`` with it for older traces; a ``device_wait`` counts
#: as decode, the device work it waits for, unless a consume covers it.
BUCKET_OF = {
    "fetch": "fetch", "storage_read": "fetch",
    "decompress": "decompress",
    "open": "decode", "transition": "decode", "decode": "decode",
    "fused": "decode", "finalize": "decode", "decode_rg": "decode",
    "pack": "decode", "stage": "decode", "device_wait": "decode",
    "consume": "consume", "to_host": "consume", "to_device": "consume",
}

#: attribution priority, latest pipeline stage first (module docstring)
PRIORITY = ("consume", "decode", "decompress", "fetch")

#: outermost structural spans, in precedence order — the run wall comes
#: from the widest one present
RUN_SPANS = ("distributed_scan", "dataset_scan", "scan")


def load_trace(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def validate_trace(doc: dict) -> list[str]:
    """Schema errors for one exported trace document (empty == valid)."""
    errors: list[str] = []
    if not isinstance(doc, dict):
        return ["trace document is not a JSON object"]
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["missing or non-list 'traceEvents'"]
    if "displayTimeUnit" not in doc:
        errors.append("missing 'displayTimeUnit'")
    open_spans: dict[tuple, int] = {}
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            errors.append(f"event {i}: not an object")
            continue
        name = e.get("name")
        if not isinstance(name, str) or not name:
            errors.append(f"event {i}: missing name")
            name = "?"
        ph = e.get("ph")
        if ph not in VALID_PH:
            errors.append(f"event {i} ({name}): bad ph {ph!r}")
            continue
        for key in ("pid", "tid"):
            if key not in e:
                errors.append(f"event {i} ({name}): missing {key}")
        ts = e.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errors.append(f"event {i} ({name}): bad ts {ts!r}")
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"event {i} ({name}): negative or missing "
                              f"dur {dur!r}")
        elif ph == "B":
            open_spans[(e.get("tid"), name)] = \
                open_spans.get((e.get("tid"), name), 0) + 1
        elif ph == "E":
            key = (e.get("tid"), name)
            if open_spans.get(key, 0) <= 0:
                errors.append(f"event {i} ({name}): E without B")
            else:
                open_spans[key] -= 1
    for (tid, name), n in open_spans.items():
        if n:
            errors.append(f"span {name} (tid {tid}): {n} unclosed B")
    return errors


def _x_events(doc: dict) -> list[dict]:
    return [e for e in doc.get("traceEvents", [])
            if e.get("ph") == "X" and isinstance(e.get("ts"), (int, float))
            and isinstance(e.get("dur"), (int, float))]


def _extent(doc: dict) -> tuple[float, float]:
    """(lo, hi) µs: the outermost structural span when present, else the
    envelope of every complete event."""
    xs = _x_events(doc)
    if not xs:
        return 0.0, 0.0
    for name in RUN_SPANS:
        spans = [e for e in xs if e["name"] == name]
        if spans:
            top = max(spans, key=lambda e: e["dur"])
            return float(top["ts"]), float(top["ts"] + top["dur"])
    lo = min(e["ts"] for e in xs)
    hi = max(e["ts"] + e["dur"] for e in xs)
    return float(lo), float(hi)


def attribute_buckets(doc: dict) -> dict:
    """Partition the run extent into the five buckets (µs).

    A coordinate sweep over every bucketed span: each elementary
    interval is charged to the highest-priority bucket covering it, or
    ``stall`` when nothing does.  Sums are exact — the values add up to
    ``wall_us`` to float precision.
    """
    lo, hi = _extent(doc)
    out = {b: 0.0 for b in PRIORITY}
    out["stall"] = 0.0
    out["wall_us"] = hi - lo
    if hi <= lo:
        return out
    deltas: dict[float, dict[str, int]] = {}
    for e in _x_events(doc):
        b = BUCKET_OF.get(e["name"])
        if b is None:
            continue
        s = max(lo, float(e["ts"]))
        t = min(hi, float(e["ts"] + e["dur"]))
        if t <= s:
            continue
        deltas.setdefault(s, {}).setdefault(b, 0)
        deltas[s][b] += 1
        deltas.setdefault(t, {}).setdefault(b, 0)
        deltas[t][b] -= 1
    active = {b: 0 for b in PRIORITY}
    prev = lo
    for t in sorted(set(deltas) | {hi}):
        seg = min(t, hi) - prev
        if seg > 0:
            for b in PRIORITY:
                if active[b] > 0:
                    out[b] += seg
                    break
            else:
                out["stall"] += seg
        for b, d in deltas.get(t, {}).items():
            active[b] += d
        prev = min(t, hi)
    return out


def critical_path(doc: dict) -> dict:
    """Per-row-group serial chains (fetch → decode items → consume, µs)
    and the longest one — the chain a latency optimization must shorten
    first."""
    chains: dict[tuple, dict] = {}
    for e in _x_events(doc):
        args = e.get("args") or {}
        if "rg" not in args:
            continue
        b = BUCKET_OF.get(e["name"])
        if b is None:
            continue
        key = (args.get("scan", "?"), args["rg"])
        c = chains.setdefault(key, {"scan": key[0], "rg": key[1],
                                    "fetch": 0.0, "decompress": 0.0,
                                    "decode": 0.0, "consume": 0.0})
        c[b] += float(e["dur"])
    rgs = sorted(chains.values(),
                 key=lambda c: (c["scan"], c["rg"]))
    for c in rgs:
        c["total"] = c["fetch"] + c["decompress"] + c["decode"] \
            + c["consume"]
    longest = max(rgs, key=lambda c: c["total"], default=None)
    return {"chains": rgs, "longest": longest}


def bandwidth(doc: dict) -> dict:
    """Effective-bandwidth breakdown over the run extent: stored bytes
    moved by the storage layer vs logical bytes delivered to consume."""
    lo, hi = _extent(doc)
    wall_s = max(1e-12, (hi - lo) * 1e-6)
    stored = sum(int((e.get("args") or {}).get("bytes", 0))
                 for e in _x_events(doc)
                 if e["name"] == "storage_read")
    logical = sum(int((e.get("args") or {}).get("logical_bytes", 0))
                  for e in _x_events(doc)
                  if e["name"] == "consume")
    return {"stored_bytes": stored, "logical_bytes": logical,
            "stored_bw_mbps": stored / wall_s / 1e6,
            "effective_bw_mbps": logical / wall_s / 1e6}


def tenant_attribution(doc: dict) -> dict:
    """Per-tenant wall attribution (DESIGN.md §11).

    Every bucketed complete event is charged to the tenant named in its
    ``args`` — the scheduler tags fetch and decode-item spans with the
    owning tenant — and untagged work rides the shared ``-`` tenant,
    mirroring the weight-1 virtual tenant in the scheduler itself.
    Values are summed span-time µs, *not* exclusive wall: concurrent
    tenants overlap, so per-tenant ``busy_us`` can add up to more than
    the run wall.  ``window_hit`` instants are counted per tenant too —
    row groups a tenant received from the delivered-result window
    instead of fetching.
    """
    out: dict[str, dict] = {}

    def entry(ten: str) -> dict:
        t = out.get(ten)
        if t is None:
            t = {b: 0.0 for b in PRIORITY}
            t.update(busy_us=0.0, spans=0, window_hits=0)
            out[ten] = t
        return t

    for e in _x_events(doc):
        b = BUCKET_OF.get(e["name"])
        if b is None:
            continue
        t = entry(str((e.get("args") or {}).get("tenant", "-")))
        t[b] += float(e["dur"])
        t["busy_us"] += float(e["dur"])
        t["spans"] += 1
    for e in doc.get("traceEvents", []):
        if isinstance(e, dict) and e.get("ph") == "i" \
                and e.get("name") == "window_hit":
            entry(str((e.get("args") or {})
                      .get("tenant", "-")))["window_hits"] += 1
    return dict(sorted(out.items()))


def build_report(doc: dict) -> dict:
    """The full machine-readable report for one trace document."""
    buckets = attribute_buckets(doc)
    stage_buckets = {k: v for k, v in buckets.items() if k != "wall_us"}
    bottleneck = max(stage_buckets, key=stage_buckets.get) \
        if buckets["wall_us"] > 0 else "empty"
    events = doc.get("traceEvents", [])
    other = doc.get("otherData", {}) or {}
    counts: dict[str, int] = {}
    for e in events:
        if isinstance(e, dict) and isinstance(e.get("name"), str):
            counts[e["name"]] = counts.get(e["name"], 0) + 1
    return {
        "wall_us": buckets["wall_us"],
        "buckets_us": stage_buckets,
        "bottleneck": bottleneck,
        "bandwidth": bandwidth(doc),
        "critical_path": critical_path(doc),
        "per_tenant": tenant_attribution(doc),
        "event_counts": dict(sorted(counts.items())),
        "n_events": len(events),
        "dropped": other.get("dropped", 0),
        "registry": other.get("registry", {}),
    }


def format_report(rep: dict) -> str:
    lines = [f"wall: {rep['wall_us'] / 1e3:.3f} ms  "
             f"({rep['n_events']} events, {rep['dropped']} dropped)"]
    wall = max(1e-12, rep["wall_us"])
    for b in (*PRIORITY, "stall"):
        us = rep["buckets_us"][b]
        lines.append(f"  {b:<10} {us / 1e3:9.3f} ms  "
                     f"{100.0 * us / wall:5.1f}%")
    lines.append(f"bottleneck: {rep['bottleneck']}")
    bw = rep["bandwidth"]
    lines.append(f"bandwidth: stored {bw['stored_bw_mbps']:.1f} MB/s "
                 f"({bw['stored_bytes']} B), effective "
                 f"{bw['effective_bw_mbps']:.1f} MB/s "
                 f"({bw['logical_bytes']} B)")
    tenants = rep.get("per_tenant", {})
    if any(name != "-" for name in tenants):
        total_busy = max(1e-12, sum(t["busy_us"] for t in tenants.values()))
        for name, t in tenants.items():
            lines.append(
                f"  tenant {name:<8} {t['busy_us'] / 1e3:9.3f} ms busy "
                f"{100.0 * t['busy_us'] / total_busy:5.1f}%  "
                f"(fetch {t['fetch'] / 1e3:.3f} / decode "
                f"{(t['decompress'] + t['decode']) / 1e3:.3f} / consume "
                f"{t['consume'] / 1e3:.3f}, {t['spans']} spans, "
                f"{t['window_hits']} window hits)")
    longest = rep["critical_path"]["longest"]
    if longest:
        lines.append(f"critical path: scan={longest['scan']} "
                     f"rg={longest['rg']} total="
                     f"{longest['total'] / 1e3:.3f} ms "
                     f"(fetch {longest['fetch'] / 1e3:.3f} / decode "
                     f"{(longest['decompress'] + longest['decode']) / 1e3:.3f}"
                     f" / consume {longest['consume'] / 1e3:.3f})")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("trace", help="trace-event JSON exported by "
                                  "core/trace.py")
    ap.add_argument("--json", action="store_true",
                    help="print the machine-readable report")
    args = ap.parse_args()
    doc = load_trace(args.trace)
    errors = validate_trace(doc)
    if errors:
        print(f"[trace_report] {args.trace}: INVALID", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        return 1
    rep = build_report(doc)
    if args.json:
        print(json.dumps(rep, indent=2, sort_keys=True))
    else:
        print(format_report(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
