"""Benchmark harness: one entry per paper table/figure (DESIGN.md §6).

Prints ``name,us_per_call,derived`` CSV, and persists every suite's rows
to ``results/BENCH_<suite>.json`` (``obs.save_bench``): the previous
snapshot is pushed onto the file's bounded ``history`` list, so the perf
trajectory accumulates per run — render it with
``python results/make_tables.py --bench``. ``--no-save`` keeps a run
print-only; ``--out-dir`` redirects the snapshots.

``--quick`` runs the kernel, ZO-path, round-engine, and roofline benches;
default additionally runs the paper-figure suites (≈10-20 min on CPU).
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument("--no-save", action="store_true",
                    help="don't snapshot rows to results/BENCH_*.json")
    ap.add_argument("--out-dir", default=None,
                    help="snapshot directory (default: results/)")
    args = ap.parse_args()

    from repro.utils import compile_cache
    compile_cache.enable()
    from benchmarks import (kernels_bench, roofline_report, round_bench,
                            sim_bench, workloads_bench, zo_path_bench)
    suites = [("kernels", kernels_bench.run),
              ("zo_path", zo_path_bench.run),
              ("round", round_bench.run),
              ("sim", sim_bench.run),
              ("algos", sim_bench.run_algos),
              ("scenario", sim_bench.run_scenario),
              ("tiered", sim_bench.run_tiered),
              ("workloads", workloads_bench.run),
              ("roofline", roofline_report.run)]
    if not args.quick:
        # the Sec. V-B figure harness (one vmapped sweep per figure; CSVs
        # land in results/) at smoke scale — the full grids run via the
        # slow-marked test / the paper_figures CLI
        from benchmarks import paper_figures as pf
        suites = [("figures", pf.run)] + suites

    print("name,us_per_call,derived")
    failed = False
    for tag, fn in suites:
        if args.only and args.only != tag:
            continue
        try:
            rows = list(fn())
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:  # noqa: BLE001
            failed = True
            print(f"{tag}/ERROR,0,nan", flush=True)
            traceback.print_exc(file=sys.stderr)
            continue
        if not args.no_save:
            try:
                from repro import obs
                obs.save_bench(tag, rows, out_dir=args.out_dir,
                               config={"quick": args.quick})
            except Exception:  # noqa: BLE001 — a snapshot failure must
                traceback.print_exc(file=sys.stderr)  # not fail the bench
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
