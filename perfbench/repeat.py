#!/usr/bin/env python
"""Run one cell several times, each run its own process, and report each
metric's spread.

    python perfbench/repeat.py --workload <cell> --seeds 11,12,13 \
        --seconds 30 [--trace 0|1] [--out <file>.jsonl]

The runs go one after another from this process, which never touches JAX,
so each run holds the chip alone. Each run's last line is appended to
``--out``, and its standard error written beside it
(``<out>.<seed>.err``). The summary gives, per metric, the median and the
spread: the distance between the first and third quartiles
(``statistics.quantiles``, n=4) as a share of the median.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def summarize(lines: list[dict]) -> dict:
    out: dict = {}
    names = sorted({k for line in lines for k in line.get("metrics", {})})
    for name in names:
        values = [line["metrics"][name]["value"] for line in lines
                  if name in line.get("metrics", {})]
        out[name] = {"median": statistics.median(values),
                     "spread": spread(values), "n": len(values),
                     "values": values}
    checks = sorted({k for line in lines for k in line.get("checks", {})})
    for name in checks:
        values = [line["checks"][name]["value"] for line in lines
                  if name in line.get("checks", {})]
        out[f"check:{name}"] = {"max": max(values), "min": min(values),
                                "n": len(values)}
    out["correct"] = [line.get("correct") for line in lines]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="30")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    lines = []
    out = Path(args.out) if args.out else None
    for seed in args.seeds.split(","):
        cmd = [sys.executable, str(ROOT / "perfbench" / "run.py"),
               "--workload", args.workload, "--seed", seed,
               "--seconds", args.seconds, "--trace", args.trace]
        t0 = time.perf_counter()
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        last = proc.stdout.strip().splitlines()[-1:] or [""]
        try:
            line = json.loads(last[0])
        except json.JSONDecodeError:
            line = {"error": proc.stderr[-3000:]}
        line["seed"] = int(seed)
        line["rc"] = proc.returncode
        line["process_s"] = wall
        print(json.dumps(line), flush=True)
        print("\n".join(proc.stderr.strip().splitlines()[-8:]),
              file=sys.stderr, flush=True)
        if out is not None:
            out.parent.mkdir(parents=True, exist_ok=True)
            with open(out, "a", encoding="utf-8") as fh:
                fh.write(json.dumps(line) + "\n")
            Path(f"{out}.{seed}.err").write_text(proc.stderr)
        lines.append(line)
    print(json.dumps({"summary": summarize(
        [x for x in lines if "metrics" in x])}), flush=True)
    return 0 if all(x.get("rc") == 0 for x in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
