# One module per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
#
# ``--smoke`` runs a CI-sized subset (tiny scale factor, 1 repeat) of the
# scan-path suites so per-PR regressions in decode/planning/I-O are caught
# without the full benchmark cost.
import argparse
import os
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-SF subset for CI (scan-path suites only)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (e.g. bench_queries)")
    ap.add_argument("--trace", action="store_true",
                    help="record each suite with the flight recorder "
                         "(core/trace.py) and write trace_<tag>.json "
                         "next to the CSVs")
    args = ap.parse_args()
    if args.smoke:
        os.environ.setdefault("BENCH_SF", "0.01")
        # smoke rows are small enough that extra best-of rounds are cheap,
        # and the CI perf gate needs the min to be noise-proof
        os.environ.setdefault("BENCH_ROUNDS", "5")
        os.environ["BENCH_SMOKE"] = "1"   # bench_concurrent: N subset

    from benchmarks.common import flush_csv
    from repro.compile_cache import use_compile_cache

    use_compile_cache()

    suites = [
        ("bench_page_count", "fig2a"),      # Fig 2(a): page-count sweep
        ("bench_rg_size", "fig2b"),         # Fig 2(b): RG-size sweep
        ("bench_encoding", "fig3"),         # Fig 3: FLEX + SSD scaling
        ("bench_compression", "fig3c"),     # Fig 3: Insight-4 deltas
        ("bench_queries", "fig5"),          # Fig 5: Q6/Q12 query level
        ("bench_scan_plan", "scan_plan"),   # DecodePlan launch/IO economy
        ("bench_concurrent", "concurrent"),  # ScanService N-scan sharing
        ("bench_dataset", "dataset"),       # dataset pruning + sharding
        ("bench_distributed", "distributed"),  # devices × storage backends
        ("bench_rewriter", "sec5"),         # §5: rewriter overhead
        ("bench_kernels", "kernels"),       # §3: per-encoding decode bw
        ("roofline", "roofline"),           # §Roofline from dry-run JSONs
    ]
    if args.smoke:
        suites = [s for s in suites
                  if s[0] in ("bench_queries", "bench_scan_plan",
                              "bench_concurrent", "bench_dataset",
                              "bench_distributed")]
    if args.only:
        keep = set(args.only.split(","))
        suites = [s for s in suites if s[0] in keep]

    tracer = None
    if args.trace:
        from repro.core import trace
        from benchmarks.common import RESULTS_DIR
        tracer = trace.enable()

    print("name,us_per_call,derived")
    failures = []
    suffix = "_smoke" if args.smoke else ""
    for mod_name, tag in suites:
        try:
            if tracer is not None:
                tracer.clear()
            mod = __import__(f"benchmarks.{mod_name}",
                             fromlist=["run"])
            mod.run()
            flush_csv(f"{tag}{suffix}.csv")
            if tracer is not None:
                tracer.export(os.path.join(RESULTS_DIR,
                                           f"trace_{tag}{suffix}.json"))
        except Exception:
            failures.append(mod_name)
            traceback.print_exc()
    if tracer is not None:
        from repro.core import trace
        trace.disable()
    if failures:
        print(f"FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
