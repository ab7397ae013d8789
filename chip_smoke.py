#!/usr/bin/env python3
"""Bring-up smoke test: TPC-H Q6/Q12 through the served scan path on a TPU.

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the four-chip sharded-scan phase only

One chip: generates TPC-H ``lineitem`` and ``orders`` at SF 10 from a fixed
seed under ACCELERATOR_OPTIMIZED, then serves Q6 unfused, Q6 fused and Q12
through ``QueryFrontEnd`` on the pallas decode backend, each once cold and
once warm, and checks the answers against the numpy oracles of
``core/query.py`` (Q6 within rel 1e-5, Q12 exactly).  It also serves Q6
with ``fused="reference"`` and reports whether the fused kernel's result is
bit-identical to that twin on the chip.

Four chips: writes SF 1 ``lineitem`` as a 16-fragment dataset and requires
``q6(dataset, devices=4)`` to be bit-identical to ``devices=1``, with four
distinct devices that each scanned fragments and each holding its own
shard's decoded columns.

The lines before the last are informational (walls include compilation
on the cold run; they are not a benchmark).  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  The script exits nonzero and
prints no result when JAX finds no TPU, when it runs outside a checkout,
or when any check fails: a failed or rejected ticket, a pallas run that
launched no kernel, a Q6/Q12 column decoded by the host fallback, a fused
row group served by the unfused consume, or kernels in interpret mode.

It runs in one process and starts no other.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/`` in the
checkout; generated files go to ``.smoke/`` in the checkout and are
removed at exit.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SF = 10.0
FOUR_CHIP_SF = 1.0
FRAGMENTS = 16
SEED = 7
TENANT = "smoke"
Q6_RTOL = 1e-5


class SmokeError(RuntimeError):
    """A check failed: the run prints no result and exits nonzero."""


def log(msg: str) -> None:
    print(msg, flush=True)


def import_repro() -> None:
    src = os.path.join(ROOT, "src")
    if not os.path.isfile(os.path.join(src, "repro", "core", "__init__.py")):
        raise SmokeError(f"no src/repro next to {os.path.basename(__file__)}"
                         ": run it from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def require_tpu() -> None:
    """Fail unless JAX's default backend is a TPU and the Pallas kernels
    compile for it (no interpret mode)."""
    import jax

    from repro.kernels.common import interpret_default
    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeError(f"JAX found no TPU (default backend: {backend}); "
                         "this smoke test runs only on the chip")
    if interpret_default():
        raise SmokeError("Pallas kernels would run in interpret mode")


def bits(x: float) -> str:
    import numpy as np
    return np.float64(x).tobytes().hex()


# ---------------------------------------------------------------------------
# oracles: the same seeded chunks the writer wrote, through numpy
# ---------------------------------------------------------------------------

def oracles(sf: float) -> tuple[float, dict[str, int]]:
    import numpy as np

    from repro.core.query import (Q6_COLUMNS, Q12_LINEITEM_COLUMNS,
                                  Q12_ORDERS_COLUMNS, q6_reference,
                                  q12_reference)
    from repro.data.tpch import tpch_chunks
    q6, q12 = 0.0, {}
    for oc, lc in tpch_chunks(sf, SEED, include_strings=False):
        line = {c: np.asarray(lc[c])
                for c in set(Q6_COLUMNS) | set(Q12_LINEITEM_COLUMNS)}
        orders = {c: np.asarray(oc[c]) for c in Q12_ORDERS_COLUMNS}
        q6 += q6_reference(line)
        for k, v in q12_reference(line, orders).items():
            q12[k] = q12.get(k, 0) + v
    return q6, q12


# ---------------------------------------------------------------------------
# one chip: the served main path
# ---------------------------------------------------------------------------

class Server:
    """Submits queries through one QueryFrontEnd and checks each run."""

    def __init__(self):
        from repro.serve.engine import QueryFrontEnd
        # no delivered-result window: every run decodes on the device
        self.fe = QueryFrontEnd(window_bytes=0)
        self.fe.register_tenant(TENANT)

    def run(self, label: str, query: str, source, **kw):
        from repro.kernels.common import kernel_launch_count
        n0 = kernel_launch_count()
        t0 = time.perf_counter()
        tid = self.fe.submit(TENANT, query, source, **kw)
        try:
            result, reports = self.fe.result(tid)
        except Exception as e:  # noqa: BLE001 — reported as a failure
            state = self.fe.poll(tid)["state"]
            raise SmokeError(f"{label}: ticket {state}: {e!r}") from e
        state = self.fe.poll(tid)["state"]
        if state != "done":
            raise SmokeError(f"{label}: ticket {state}")
        # results are host values (float / dict of ints) once returned
        result = (float(result) if query == "q6"
                  else {k: int(v) for k, v in result.items()})
        wall = time.perf_counter() - t0
        launches = kernel_launch_count() - n0
        decoded = sum(r.metrics.logical_bytes for r in reports)
        if launches == 0:
            raise SmokeError(f"{label}: launched no kernel")
        return result, wall, launches, decoded

    def cold_warm(self, label: str, query: str, source, **kw):
        out = []
        for phase in ("cold", "warm"):
            res, wall, launches, decoded = self.run(f"{label} {phase}",
                                                    query, source, **kw)
            log(f"{label}: {phase} wall {wall:.3f} s, {launches} kernel "
                f"launches, {decoded} decoded bytes")
            out.append(res)
        if out[0] != out[1]:
            raise SmokeError(f"{label}: warm result {out[1]} differs from "
                             f"cold result {out[0]}")
        return out[0]

    def close(self):
        self.fe.shutdown()


def one_chip(workdir: str, sf: float) -> None:
    from repro.core import ACCELERATOR_OPTIMIZED
    from repro.core.query import (Q6_COLUMNS, Q12_LINEITEM_COLUMNS,
                                  Q12_ORDERS_COLUMNS,
                                  legacy_fused_consume_count)
    from repro.core.scan import open_scanner
    from repro.data import tpch
    from repro.kernels.ops import host_fallback_counts

    t0 = time.perf_counter()
    metas = tpch.write_tpch(workdir, sf=sf, seed=SEED,
                            config=ACCELERATOR_OPTIMIZED,
                            include_strings=False)
    lm, om = metas["lineitem"], metas["orders"]
    log(f"data: SF {sf:g}, lineitem {lm.num_rows} rows "
        f"({len(lm.row_groups)} row groups, {lm.stored_bytes} stored "
        f"bytes), orders {om.num_rows} rows ({len(om.row_groups)} row "
        f"groups, {om.stored_bytes} stored bytes); written in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    q6_want, q12_want = oracles(sf)
    log(f"oracle: q6 {q6_want!r}, q12 {q12_want}; "
        f"{time.perf_counter() - t0:.1f} s")

    lpath, opath = metas["lineitem_path"], metas["orders_path"]

    def scanner(path, cols):
        return open_scanner(path, columns=list(cols), decode_backend="pallas")

    fallbacks0 = host_fallback_counts()
    legacy0 = legacy_fused_consume_count()
    server = Server()
    try:
        q6_unfused = server.cold_warm(
            "q6 unfused", "q6", scanner(lpath, Q6_COLUMNS), fused=False)
        q6_fused = server.cold_warm(
            "q6 fused", "q6", scanner(lpath, Q6_COLUMNS), fused=True)
        q6_ref, _, _, _ = server.run(
            "q6 fused=reference", "q6", scanner(lpath, Q6_COLUMNS),
            fused="reference")
        q12 = server.cold_warm(
            "q12", "q12", (scanner(lpath, Q12_LINEITEM_COLUMNS),
                           scanner(opath, Q12_ORDERS_COLUMNS)))
    finally:
        server.close()

    identical = bits(q6_fused) == bits(q6_ref)
    log(f"q6 fused vs fused=reference bit-identical: {identical} "
        f"({q6_fused!r} vs {q6_ref!r})")
    log(f"q6 unfused {q6_unfused!r}, fused {q6_fused!r}, oracle "
        f"{q6_want!r}; q12 {q12}")

    problems = []
    for label, got in (("q6 unfused", q6_unfused), ("q6 fused", q6_fused)):
        if abs(got - q6_want) > Q6_RTOL * abs(q6_want):
            problems.append(f"{label} {got!r} is not within rel "
                            f"{Q6_RTOL} of the oracle {q6_want!r}")
    if q12 != q12_want:
        problems.append(f"q12 {q12} != oracle {q12_want}")
    scan_cols = set(Q6_COLUMNS) | set(Q12_LINEITEM_COLUMNS) \
        | set(Q12_ORDERS_COLUMNS)
    fell_back = {c: n for c, n in
                 (host_fallback_counts() - fallbacks0).items()
                 if c in scan_cols}
    if fell_back:
        problems.append(f"host fallback decoded {fell_back}")
    legacy = legacy_fused_consume_count() - legacy0
    if legacy:
        problems.append(f"{legacy} fused row groups took the unfused "
                        "consume")
    if problems:
        raise SmokeError("; ".join(problems))


# ---------------------------------------------------------------------------
# four chips: sharded dataset scan
# ---------------------------------------------------------------------------

def four_chip(workdir: str, sf: float) -> None:
    import jax

    from repro.core import ACCELERATOR_OPTIMIZED
    from repro.core.query import (Q6_COLUMNS, q6, q6_reference,
                                  q6_rg_stats_predicate)
    from repro.data import tpch
    from repro.dataset import write_dataset
    from repro.dataset.executor import run_distributed_scan
    from repro.dataset.planner import plan_dataset_scan

    t0 = time.perf_counter()
    line, _ = tpch.generate_tables(sf=sf, seed=SEED, include_strings=False)
    want = q6_reference({c: line[c] for c in Q6_COLUMNS})
    ds = write_dataset(line, os.path.join(workdir, "lineitem"),
                       ACCELERATOR_OPTIMIZED, fragments=FRAGMENTS, threads=4)
    log(f"data: SF {sf:g} lineitem {line.num_rows} rows in "
        f"{len(ds.fragments)} fragments, {ds.stored_bytes} stored bytes; "
        f"{time.perf_counter() - t0:.1f} s")

    results = {}
    for d in (1, 4, 4, 1):
        t0 = time.perf_counter()
        r, rep = q6(ds, devices=d, fused=False)
        wall = time.perf_counter() - t0
        log(f"q6 devices={d}: {r!r} in {wall:.3f} s, devices "
            f"{rep.device_names}, fragments per device "
            f"{rep.device_fragments}, {rep.n_kernel_launches} kernel "
            f"launches")
        results.setdefault(d, []).append((r, rep))

    problems = []
    got = {bits(r) for runs in results.values() for r, _ in runs}
    if len(got) != 1:
        problems.append(f"q6 results differ across device counts: "
                        f"{[(d, r) for d, runs in results.items() for r, _ in runs]}")
    r1 = results[1][0][0]
    if abs(r1 - want) > Q6_RTOL * abs(want):
        problems.append(f"q6 {r1!r} is not within rel {Q6_RTOL} of the "
                        f"oracle {want!r}")
    for _, rep in results[4]:
        if len(set(rep.device_names)) != 4:
            problems.append(f"devices=4 ran on {rep.device_names}")
        if min(rep.device_fragments) <= 0:
            problems.append(f"a device scanned no fragment: "
                            f"{rep.device_fragments}")
    platform = jax.default_backend()
    if any(d.platform != platform for d in jax.devices()[:4]):
        problems.append(f"devices are not all {platform}")

    # each shard's decoded columns live on the device that scanned it
    seen: dict[str, set[str]] = {}

    def record(acc, rg_index, cols):
        where = seen.setdefault(threading.current_thread().name, set())
        for res in cols.values():
            if getattr(res, "on_device", False):
                where.update(str(dv) for dv in res.array.devices())
        return acc

    plan = plan_dataset_scan(ds, columns=list(Q6_COLUMNS),
                             predicate_stats=q6_rg_stats_predicate)
    _, rep = run_distributed_scan(plan, record, None, devices=4)
    for d, name in enumerate(rep.device_names):
        held = seen.get(f"dataset-device-{d}", set())
        log(f"shard {d}: {rep.device_fragments[d]} fragments, decoded "
            f"columns on {sorted(held)}")
        if held != {name}:
            problems.append(f"shard {d} decoded onto {sorted(held)}, "
                            f"not {name}")
    if problems:
        raise SmokeError("; ".join(problems))


# ---------------------------------------------------------------------------

def main(argv=None, *, sf: float | None = None, require=None,
         workdir: str | None = None) -> int:
    """CLI entry.  ``sf``, ``require`` (the device check) and ``workdir``
    default to the chip run's; tests inject them to rehearse on a CPU."""
    ap = argparse.ArgumentParser(
        description="Serve TPC-H Q6/Q12 through the scan engine on a TPU "
                    "and check the answers.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sharded-scan phase")
    args = ap.parse_args(argv)
    workdir = workdir or os.path.join(ROOT, ".smoke")
    try:
        import_repro()
        from repro.compile_cache import use_compile_cache
        cache = use_compile_cache()
        (require or require_tpu)()
        import jax
        devices = jax.devices()
        if len(devices) < args.chips:
            raise SmokeError(f"--chips {args.chips} needs {args.chips} "
                             f"devices; JAX found {len(devices)}")
        dev = devices[0]
        log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
            f"compile cache {cache}")
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        try:
            if args.chips == 4:
                four_chip(workdir, FOUR_CHIP_SF if sf is None else sf)
            else:
                one_chip(workdir, SF if sf is None else sf)
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
