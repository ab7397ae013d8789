#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name.  ``BENCHMARK.json`` pairs each cell (an entry
of ``workloads``) with a configuration, ``bench/configs/<config>.json``,
and a traffic mix, ``bench/traffic/<traffic>.json``; each metric is read
by ``bench/metrics/<metric>.py``; a cell's correctness limits are in
``bench/limits/<cell>.json``.

A run generates the TPC-H tables from ``--seed`` (``bench/tpch_gen.py``)
and writes them with the program's own ``TabFileWriter`` under the
configuration's file layout; it serves the traffic through one
``QueryFrontEnd`` with its default settings, each source a scanner on the
pallas decode path, and warms every query of the mix up once.  Clients
then issue queries in a closed loop for ``--seconds`` seconds; the window
closes when the last query issued in it completes.  After the window the
plain numpy reference (``bench/queries.py``) checks every answer.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the window.  The last lines on standard error, and the ``checks`` key that
ends the result line, give each compared number beside its limit.

The run exits nonzero and prints no result when JAX finds no TPU (or fewer
than the cell's chips), when kernels would run in interpret mode, when a
scanned column decodes on the host fallback, when a query launches no
kernel, or when the program is not in the checkout.  Generated files, the
compile cache and the trace live under ``bench/.cache/`` (or the cache
where ``JAX_COMPILATION_CACHE_DIR`` says).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE = os.path.join(BENCH, ".cache")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# the TPU runtime logs to /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", os.path.join(CACHE, "tpu_logs"))

from bench import footer, queries, tpch_gen, trace_reduce  # noqa: E402


LATE_S = 60.0     # how long past the window's close an answer may come


class BenchError(RuntimeError):
    """The run is invalid: it prints no result and exits nonzero."""


def log(msg: str) -> None:
    print(msg, flush=True)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: list[dict]          # the BENCHMARK.json entries to report


def load_cell(name: str, trace: bool) -> Cell:
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    return Cell(
        name=name, chips=w["chips"],
        config=_json(os.path.join(BENCH, "configs", f"{w['config']}.json")),
        traffic=_json(os.path.join(BENCH, "traffic",
                                   f"{w['traffic']}.json")),
        limits=_json(os.path.join(BENCH, "limits", f"{name}.json")),
        metrics=spec["per_layer" if trace else "end_to_end"])


def load_reader(metric: str):
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# set-up: device, program, data
# ---------------------------------------------------------------------------

def import_program() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "core", "__init__.py")):
        raise BenchError("the program (src/repro) is not in this checkout")
    if src not in sys.path:
        sys.path.insert(0, src)


def use_compile_cache() -> str:
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CACHE, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_tpu(chips: int) -> None:
    """Fail unless JAX's default backend is a TPU with ``chips`` devices
    and the Pallas kernels compile for it (no interpret mode)."""
    import jax

    from repro.kernels.common import interpret_default
    backend = jax.default_backend()
    if backend != "tpu":
        raise BenchError(f"JAX found no TPU (default backend: {backend})")
    if len(jax.devices()) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(jax.devices())}")
    if interpret_default():
        raise BenchError("Pallas kernels would run in interpret mode")


def file_config(layout: dict):
    from repro.core.config import CompressionSpec, EncodingPolicy, FileConfig
    return FileConfig(
        rows_per_rg=layout["rows_per_rg"],
        target_pages_per_chunk=layout["target_pages_per_chunk"],
        encodings=EncodingPolicy(layout["encodings"]),
        compression=CompressionSpec(codec=layout["codec"],
                                    min_gain=layout["min_gain"],
                                    level=layout["level"]))


def write_tables(config: dict, seed: int, workdir: str,
                 needed: dict[str, list[str]]):
    """Generate the seeded tables chunk by chunk and stream the first
    ``config["rows"][table]`` rows of each table the traffic reads into
    one file, row group by row group, so that every seed gives the same
    row counts and shapes.  Returns the paths, the footers and the read
    columns of each table, kept for the reference."""
    import numpy as np

    from repro.core.schema import Field, LogicalType, PhysicalType, Schema
    from repro.core.table import Table
    from repro.core.writer import TabFileWriter

    fc = file_config(config["file_layout"])
    threads = min(16, os.cpu_count() or 1)
    writers, schemas, pending, kept, paths = {}, {}, {}, {}, {}
    for t in needed:
        schemas[t] = Schema([Field(n, PhysicalType[p], LogicalType(lg))
                             for n, p, lg in tpch_gen.TABLES[t]])
        paths[t] = os.path.join(workdir, f"{t}.tab")
        writers[t] = TabFileWriter(paths[t], fc, threads).begin(schemas[t])
        pending[t], kept[t] = [], {c: [] for c in needed[t]}

    def flush(t: str, final: bool) -> None:
        rows = sum(p.num_rows for p in pending[t])
        if not rows or (rows < fc.rows_per_rg and not final):
            return
        buf = pending[t][0] if len(pending[t]) == 1 else \
            Table.concat(pending[t])
        start = 0
        while buf.num_rows - start >= fc.rows_per_rg:
            writers[t].write_row_group(
                buf.slice(start, start + fc.rows_per_rg))
            start += fc.rows_per_rg
        rest = buf.slice(start, buf.num_rows)
        pending[t] = [rest] if rest.num_rows else []
        if final and rest.num_rows:
            writers[t].write_row_group(rest)
            pending[t] = []

    stream = tpch_gen.capped_chunks(config["scale_factor"], seed,
                                    {t: config["rows"][t] for t in needed})
    try:
        for chunk in stream:
            for t in needed:
                cols = chunk[t]
                if not len(cols[needed[t][0]]):
                    continue
                pending[t].append(Table(cols, schemas[t]))
                for c in needed[t]:
                    kept[t][c].append(cols[c])
                flush(t, final=False)
    except ValueError as e:
        raise BenchError(str(e)) from None
    metas = {}
    for t in needed:
        flush(t, final=True)
        writers[t].finish()
        metas[t] = footer.read(paths[t])
        kept[t] = {c: np.concatenate(v) for c, v in kept[t].items()}
    return paths, metas, kept


# ---------------------------------------------------------------------------
# the served window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    query: str
    client: int
    submitted: float
    done: float = 0.0
    result: object = None
    reports: tuple = ()
    error: str | None = None

    @property
    def latency_s(self) -> float:
        return self.done - self.submitted


class Served:
    """The front end, one tenant per client name, and the query sources."""

    def __init__(self, traffic: dict, paths: dict[str, str]):
        from repro.core.scan import open_scanner
        from repro.serve.engine import QueryFrontEnd
        self.fe = QueryFrontEnd()
        self.clients = traffic["clients"]
        for tenant, weight in {c["tenant"]: c.get("weight", 1)
                               for c in self.clients}.items():
            self.fe.register_tenant(tenant, weight=weight)

        def scanner(table, query):
            return open_scanner(paths[table],
                                columns=queries.COLUMNS[query][table],
                                decode_backend="pallas")

        self.sources = []
        for c in self.clients:
            src = tuple(scanner(t, c["query"])
                        for t in queries.COLUMNS[c["query"]])
            self.sources.append(src[0] if len(src) == 1 else src)

    def one(self, i: int, trace: bool,
            deadline: float | None = None) -> Record:
        """Client ``i``'s next query, from submit to host values; one that
        has not answered by ``deadline`` (perf_counter) has failed."""
        query = self.clients[i]["query"]
        rec = Record(query, i, time.perf_counter())
        if trace:
            import jax
            with jax.profiler.TraceAnnotation(f"bench_query {query} c{i}"):
                return self._run(rec, deadline)
        return self._run(rec, deadline)

    def _run(self, rec: Record, deadline: float | None) -> Record:
        tid = self.fe.submit(self.clients[rec.client]["tenant"], rec.query,
                             self.sources[rec.client])
        timeout = (None if deadline is None
                   else max(0.0, deadline - time.perf_counter()))
        try:
            result, rec.reports = self.fe.result(tid, timeout)
            rec.result = queries.answer(rec.query, result)
        except Exception as e:  # noqa: BLE001 — counted as a failed query
            rec.error = repr(e)
        rec.done = time.perf_counter()
        return rec

    def window(self, seconds: float, trace: bool) -> list[Record]:
        """Every client in a closed loop until ``seconds`` have passed;
        returns every query issued.  Each has until a minute after that
        to answer (``LATE_S``); one that has not is a failed query."""
        records: list[Record] = []
        lock = threading.Lock()
        t_end: list[float] = []
        start = threading.Barrier(
            len(self.clients),
            action=lambda: t_end.append(time.perf_counter() + seconds))

        def client(i: int) -> None:
            start.wait()
            while time.perf_counter() < t_end[0]:
                rec = self.one(i, trace, t_end[0] + LATE_S)
                with lock:
                    records.append(rec)

        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"bench-client-{i}")
                   for i in range(len(self.clients))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return records

    def close(self) -> None:
        self.fe.shutdown()
        self.sources = []


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while on."""

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_listener(self._event)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_listener(self._event)

    def _event(self, name: str, **_) -> None:
        if self.on and name in ("/jax/compilation_cache/cache_misses",
                                "/jax/compilation_cache/cache_hits"):
            self.n += 1


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    records: list[Record]        # every query of the window, completed
    window_s: float
    setup_s: float
    logical_bytes: dict[str, int]    # per query, from the footers
    required_bytes: dict[str, int]   # per query, from the footers
    trace: dict | None = None        # trace_reduce.reduce's output
    peaks: dict | None = None        # bench/peaks.json for this device


def peaks_for(kind: str) -> dict:
    table = _json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


def measure(args, cell: Cell, require) -> dict:
    os.environ.pop("REPRO_FUSED", None)   # the program's default, always
    import_program()
    import jax
    use_compile_cache()
    (require or require_tpu)(cell.chips)
    devices = jax.devices()
    from repro.kernels.ops import host_fallback_counts

    workdir = os.path.join(CACHE, "data", cell.name)
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    trace_dir = os.path.join(CACHE, "trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    served = None
    try:
        mix = {c["query"] for c in cell.traffic["clients"]}
        needed = queries.columns_of(mix)
        t0 = time.perf_counter()
        paths, metas, kept = write_tables(cell.config, args.seed, workdir,
                                          needed)
        for t, m in metas.items():
            stored = sum(p["stored_size"] for rg in m["row_groups"]
                         for c in rg["columns"] for p in c["pages"])
            log(f"data: {t} {m['num_rows']} rows, {len(m['row_groups'])} "
                f"row groups, {stored} stored bytes")
        log(f"data written in {time.perf_counter() - t0:.3f} s")
        logical = {q: sum(footer.logical_bytes(metas[t], cols)
                          for t, cols in queries.COLUMNS[q].items())
                   for q in mix}
        required = {q: sum(footer.required_stored_bytes(
                        metas[t], cols, queries.ZONES[q].get(t, {}))
                        for t, cols in queries.COLUMNS[q].items())
                    for q in mix}
        log(f"bytes per query: logical {logical}, required stored "
            f"{required}")

        served = Served(cell.traffic, paths)
        first = {}
        for i, c in enumerate(cell.traffic["clients"]):
            first.setdefault(c["query"], i)
        for i in first.values():
            rec = served.one(i, trace=False)
            if rec.error:
                raise BenchError(f"warm-up {rec.query} failed: {rec.error}")
        compiles = CompileCounter()
        fallbacks0 = host_fallback_counts()

        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        setup_s = time.perf_counter() - T_START
        compiles.on = True
        t0 = time.perf_counter()
        if args.trace:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                records = served.window(args.seconds, trace=True)
        else:
            records = served.window(args.seconds, trace=False)
        window_s = time.perf_counter() - t0
        compiles.close()
        reduced = None
        if args.trace:
            jax.profiler.stop_trace()
            reduced = trace_reduce.reduce(trace_reduce.extract(trace_dir))
        log(f"window: {len(records)} queries in {window_s:.3f} s; "
            f"{compiles.n} programs compiled or loaded in the window")

        scanned = {c for cols in needed.values() for c in cols}
        fell_back = {c: n for c, n in
                     (host_fallback_counts() - fallbacks0).items()
                     if c in scanned}
        if fell_back:
            raise BenchError(f"host fallback decoded {fell_back}")
        for r in records:
            if not r.error and not sum(rep.metrics.n_kernel_launches
                                       for rep in r.reports):
                raise BenchError(f"a {r.query} query launched no kernel")
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    finally:
        if served is not None:
            served.close()
        shutil.rmtree(workdir, ignore_errors=True)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference, once the window has closed and the program's state
    # is freed
    t0 = time.perf_counter()
    checks, failed = {}, sum(1 for r in records if r.error)
    for q in sorted(mix):
        want = queries.reference(q, {t: kept[t] for t in queries.COLUMNS[q]})
        errs = [queries.error(q, r.result, want) for r in records
                if r.query == q and not r.error]
        name = queries.CHECK[q]
        checks[name] = {"value": max(errs) if errs else float("inf"),
                        "limit": cell.limits[name]}
    log(f"reference checked {len(records) - failed} answers in "
        f"{time.perf_counter() - t0:.3f} s")
    correct = (failed == 0 and bool(records)
               and all(c["value"] <= c["limit"] for c in checks.values()))

    dev = devices[0]
    run = Run(records=[r for r in records if not r.error],
              window_s=window_s, setup_s=setup_s, logical_bytes=logical,
              required_bytes=required, trace=reduced,
              peaks=peaks_for(dev.device_kind) if args.trace else None)
    metrics = {}
    for m in cell.metrics:
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None, *, require=None) -> int:
    """CLI entry.  ``require`` replaces the TPU check (tests rehearse the
    rest of a run on the CPU with it)."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload, bool(args.trace))
        out = measure(args, cell, require)
    except BenchError as e:
        print(f"bench: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
