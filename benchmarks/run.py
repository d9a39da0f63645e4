"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (via ``common.emit``) plus the
human-readable tables. ``--full`` uses the paper's Table I budgets (slow);
the default quick mode preserves every comparison's structure at CI-scale
budgets.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only <name>]
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time

from repro.core.exec_cache import enable_compile_cache

from . import (bench_distributed_tuner, bench_iteration_counts,
               bench_kernel_autotune, bench_matmul_peak, bench_optimizations,
               bench_roofline_model, bench_size_sweep, bench_triad)
from .common import emit

BENCHES = {
    "matmul_peak": bench_matmul_peak.run,          # Tables IV/V
    "triad": bench_triad.run,                      # Table VI
    "iteration_counts": bench_iteration_counts.run,  # Table VII
    "optimizations": bench_optimizations.run,      # Tables VIII-XI (headline)
    "size_sweep": bench_size_sweep.run,            # Fig. 6
    "roofline_model": bench_roofline_model.run,    # Fig. 1
    "kernel_autotune": bench_kernel_autotune.run,  # beyond-paper
    # beyond-paper: execution backends + search-strategy comparison
    "distributed_tuner": bench_distributed_tuner.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper Table I budgets (minutes -> ~1h)")
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--resume", nargs="?", const=".tuning_sessions",
                    default=None, metavar="DIR",
                    help="persist tuning trials under DIR (default "
                         ".tuning_sessions) and skip configs already "
                         "evaluated by a previous --resume run")
    ap.add_argument("--report", action="store_true",
                    help="after the benches, render the cache-backed "
                         "roofline dashboard from the --resume cache dir")
    ap.add_argument("--html", default=None, metavar="PATH",
                    help="after the benches, write a self-contained HTML "
                         "dashboard (rooflines + run-ledger trends and "
                         "regression verdicts) from the --resume cache dir")
    args = ap.parse_args()
    enable_compile_cache()
    quick = not args.full

    print("name,us_per_call,derived")
    selected = {args.only: BENCHES[args.only]} if args.only else BENCHES
    for name, fn in selected.items():
        kwargs = {"quick": quick}
        # cache-aware benches opt in by taking a cache_dir kwarg
        if (args.resume is not None
                and "cache_dir" in inspect.signature(fn).parameters):
            kwargs["cache_dir"] = args.resume
        t0 = time.perf_counter()
        try:
            fn(**kwargs)
            emit(f"{name}/total", (time.perf_counter() - t0) * 1e6, "ok")
        except Exception as e:  # noqa: BLE001
            emit(f"{name}/total", (time.perf_counter() - t0) * 1e6,
                 f"FAIL:{type(e).__name__}")
            print(f"[benchmarks] {name} failed: {e}", file=sys.stderr)
            raise

    if args.report or args.html:
        import pathlib

        from repro.core import build_reports, load_trials
        from repro.core.report import render_markdown

        cache_dir = pathlib.Path(args.resume or ".tuning_sessions")
        trials = load_trials(cache_dir) if cache_dir.is_dir() else []
        reports, skipped = build_reports(trials)
        if args.report:
            if reports:
                print()
                print(render_markdown(reports, skipped))
            elif skipped:
                print(f"\n[report] no reportable fingerprint under "
                      f"{cache_dir}/:", file=sys.stderr)
                for fp, reason in skipped:
                    print(f"[report]   {fp}: {reason}", file=sys.stderr)
            else:
                print(f"\n[report] no cached trials under {cache_dir}/ — "
                      "run with --resume so roofline_model persists its "
                      "dgemm/triad sessions first.", file=sys.stderr)
        if args.html:
            from repro.history import RunLedger, write_dashboard

            ledger_path = cache_dir / "history.jsonl"
            ledger = RunLedger(ledger_path) if ledger_path.exists() else None
            stamp = time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime())
            write_dashboard(args.html, reports, skipped, ledger=ledger,
                            title="Benchmark dashboard",
                            subtitle=f"generated {stamp} from "
                                     f"{cache_dir}/")
            print(f"[report] wrote {args.html}")


if __name__ == "__main__":
    main()
