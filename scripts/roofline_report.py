#!/usr/bin/env python
"""Render roofline dashboards from persisted trial caches — no re-measuring.

    PYTHONPATH=src python scripts/roofline_report.py .tuning_sessions/nightly.jsonl
    PYTHONPATH=src python scripts/roofline_report.py .tuning_sessions \
        --csv roofline.csv
    PYTHONPATH=src python scripts/roofline_report.py .tuning_sessions \
        --html roofline.html --history .tuning_sessions/history.jsonl

Takes one or more cache files (or directories of ``*.jsonl`` session
caches), groups the trials by benchmark × hardware fingerprint, extracts
the DGEMM incumbent (compute ceiling ``F_p``) and the per-size TRIAD
incumbents (memory slopes ``B_a``), and emits a markdown dashboard per
fingerprint — measured peaks with confidence intervals from the stored
Welford moments, an ASCII roofline with achieved-kernel markers, a
%-of-roof gap table — plus a side-by-side comparison across fingerprints.

``--html`` additionally writes a **self-contained HTML dashboard** (inline
CSS/JS/SVG, no external deps); with ``--history LEDGER`` it also embeds
per-series trend lines with CI bands and the regression verdicts from the
performance-history ledger (see ``docs/history.md``); with ``--trace
TRACE`` it embeds a per-trial drill-down table from a session trace
(``scripts/tune.py --trace``, see ``docs/observability.md``).

``--attribute WORKLOAD`` profiles a whole-model workload (train_step /
prefill_step / decode_step / dgemm over a small ModelConfig), joins each
HLO op's cost with its measured device time when the profiler yields
device tracks (static HLO-only attribution otherwise), classifies every
op against the empirical roofs recovered from the given caches, and adds
a per-op attribution section to the markdown and HTML dashboards (see
``docs/attribution.md``). Cache paths become optional in this mode; with
no usable cache the published peaks of the device the workload runs on
stand in (clearly marked), and a device kind without published peaks is
an error.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parent.parent
for p in (str(_REPO), str(_REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from repro.core import build_reports, load_trials  # noqa: E402
from repro.core.exec_cache import enable_compile_cache  # noqa: E402
from repro.core.report import (DGEMM_BENCHMARK, TRIAD_BENCHMARK,  # noqa: E402
                               render_csv, render_markdown)


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="cache files or directories of *.jsonl caches "
                         "(optional with --attribute)")
    ap.add_argument("--dgemm-benchmark", default=DGEMM_BENCHMARK,
                    help="benchmark name supplying the compute peak")
    ap.add_argument("--triad-benchmark", default=TRIAD_BENCHMARK,
                    help="benchmark name supplying the bandwidth slopes")
    ap.add_argument("--confidence", type=float, default=0.99)
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the markdown dashboard here (default stdout)")
    ap.add_argument("--csv", default=None, metavar="PATH",
                    help="also write the flat CSV (curves, marks, gaps)")
    ap.add_argument("--html", default=None, metavar="PATH",
                    help="also write a self-contained HTML dashboard "
                         "(inline CSS/JS/SVG, no external deps)")
    ap.add_argument("--history", default=None, metavar="LEDGER",
                    help="run-ledger JSONL to embed trend lines and "
                         "regression verdicts into the --html dashboard")
    ap.add_argument("--trace", default=None, metavar="TRACE",
                    help="session trace JSONL (scripts/tune.py --trace) to "
                         "embed a per-trial drill-down table into the "
                         "--html dashboard")
    ap.add_argument("--max-trial-rows", type=int, default=200,
                    metavar="N",
                    help="row cap of the --trace drill-down table "
                         "(default 200)")
    ap.add_argument("--attribute", default=None, metavar="WORKLOAD",
                    help="attribute one workload's HLO ops against the "
                         "empirical roofs (train_step | prefill_step | "
                         "decode_step | dgemm)")
    ap.add_argument("--arch", default=None, metavar="ARCH",
                    help="smoke-scale model architecture for --attribute "
                         "(default: tiny dense toy; see repro.configs)")
    ap.add_argument("--static", action="store_true",
                    help="force static HLO-only attribution (skip the "
                         "profiled invocation)")
    ap.add_argument("--attribution-json", default=None, metavar="PATH",
                    help="write the --attribute report as JSON (CI "
                         "artifact)")
    args = ap.parse_args()
    enable_compile_cache()

    if not args.paths and not args.attribute:
        ap.error("at least one cache path is required (or --attribute)")

    trials = []
    for p in args.paths:
        path = pathlib.Path(p)
        if not path.exists():
            print(f"error: no such cache: {p}", file=sys.stderr)
            return 2
        trials.extend(load_trials(path))
    if not trials and not args.attribute:
        print("error: no readable trials in the given cache(s)",
              file=sys.stderr)
        return 1

    reports, skipped = build_reports(
        trials, dgemm_benchmark=args.dgemm_benchmark,
        triad_benchmark=args.triad_benchmark, confidence=args.confidence)
    if not reports:
        # a --history ledger can still carry an HTML trend dashboard even
        # when no fingerprint has roofline-complete (dgemm+triad) trials
        print("no reportable fingerprint — need unpruned trials of "
              f"both {args.dgemm_benchmark!r} and {args.triad_benchmark!r}:",
              file=sys.stderr)
        for fp, reason in skipped:
            print(f"  {fp}: {reason}", file=sys.stderr)
        if not (args.html and args.history) and not args.attribute:
            print("error: nothing to render", file=sys.stderr)
            return 1

    attribution = None
    if args.attribute:
        import jax  # noqa: E402

        from repro.core.roofline import published_peaks  # noqa: E402
        from repro.models.workloads import build_workload  # noqa: E402
        from repro.obs.attribution import Roofs, attribute  # noqa: E402
        from repro.obs.attribution import roofs_from_trials  # noqa: E402

        roofs = roofs_from_trials(args.paths) if args.paths else None
        if roofs is None:
            # no empirical roofs in the caches: classify against the
            # published peaks of the device the workload runs on, clearly
            # marked; a device the table lacks is an error, not a default
            kind = jax.devices()[0].device_kind
            try:
                spec = published_peaks(kind)
            except KeyError as e:
                print(f"error: no empirical roofs in the given caches and "
                      f"{e.args[0]}", file=sys.stderr)
                return 2
            roofs = Roofs(peak_flops=spec.peak_flops,
                          bandwidths=dict(spec.mem_bandwidths),
                          fingerprint=f"{spec.name} (published)")
            print(f"note: no empirical roofs recovered; classifying "
                  f"against {spec.name} published peaks", file=sys.stderr)
        try:
            workload = build_workload(args.attribute, args.arch)
        except ValueError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        attribution = attribute(workload, roofs, force_static=args.static)
        print(f"attributed {len(attribution.ops)} ops of "
              f"{args.attribute} ({attribution.mode} mode, "
              f"unattributed {attribution.unattributed_frac * 100:.1f}%)",
              file=sys.stderr)
        if args.attribution_json:
            import json

            pathlib.Path(args.attribution_json).write_text(
                json.dumps(attribution.to_json(), indent=2),
                encoding="utf-8")
            print(f"wrote {args.attribution_json}")

    # in the ledger-only continue-path reports is empty: --out/--csv still
    # write (a header-only dashboard/CSV), never silently skip a requested
    # artifact while exiting 0
    markdown = render_markdown(reports, skipped)
    if attribution is not None:
        markdown = markdown + "\n" + attribution.to_markdown()
    if args.out:
        pathlib.Path(args.out).write_text(markdown, encoding="utf-8")
        print(f"wrote {args.out}")
    elif reports or attribution is not None:
        sys.stdout.write(markdown)
    if args.csv:
        pathlib.Path(args.csv).write_text(render_csv(reports),
                                          encoding="utf-8")
        print(f"wrote {args.csv}")
    if args.html:
        import time

        from repro.history import RunLedger, write_dashboard

        ledger = None
        if args.history:
            history_path = pathlib.Path(args.history)
            if not history_path.exists():
                print(f"error: no such ledger: {args.history}",
                      file=sys.stderr)
                return 2
            ledger = RunLedger(history_path)
        trial_rows = ()
        if args.trace:
            trace_path = pathlib.Path(args.trace)
            if not trace_path.exists():
                print(f"error: no such trace: {args.trace}",
                      file=sys.stderr)
                return 2
            from repro.obs import load_events, trial_summaries
            trial_rows = trial_summaries(load_events(trace_path))
        stamp = time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime())
        write_dashboard(args.html, reports, skipped, ledger=ledger,
                        title="Roofline & performance history",
                        subtitle=f"generated {stamp} from "
                                 f"{len(trials)} cached trials",
                        confidence=args.confidence, trials=trial_rows,
                        attribution=attribution,
                        max_trial_rows=args.max_trial_rows)
        print(f"wrote {args.html}")
    return 0


if __name__ == "__main__":
    import os

    try:
        sys.exit(main())
    except BrokenPipeError:
        # stdout closed early (e.g. piped into `head`) — not an error
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        sys.exit(0)
