#!/usr/bin/env python
"""Run one benchmark cell once on the chip this process holds.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``, from process start to the first trial):
the accelerator, every config of the cell's space through the program's
precompile hooks (from the persistent compile cache after the cell's
first run), and, for a model cell, the program's weights on the device.

The window: tuning sessions through the program's own entry
(``TuningSession(...).run()`` over a ``Tuner`` with the cell's space and
settings), back to back, each with a fresh trial cache, until
``--seconds`` have passed; the session running then goes on to its
verdict and counts. ``--trace 1`` instead runs one session under the
profiler and reports the per-layer metrics.

After the window: the verdicts' device rates, then the check that decides
``correct``. The last line of standard output is one JSON object; a
machine without a TPU, or with fewer chips than the cell asks for, exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: JAX's persistent compilation cache: a fixed directory in the checkout
COMPILE_CACHE = ROOT / ".jax_cache"


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""

    cell: object
    family: object
    sessions: list
    setup_s: float
    window_s: float
    compile_s: float
    verdict: dict
    peaks: dict
    trace: object = None                 # perfbench.trace.Trace
    span: tuple[float, float] | None = None   # the traced session, in us


def settings_from(config: dict, traffic: dict):
    """The cell's ``EvaluationSettings``: the configuration's budgets, where
    it states them, and the traffic's flags."""
    from repro.core import Direction, EvaluationSettings
    budgets = config.get("budgets", {})
    clash = {k for k in budgets.keys() & traffic["settings"].keys()
             if budgets[k] != traffic["settings"][k]}
    if clash:
        raise ValueError(f"the traffic restates the configuration's "
                         f"budgets otherwise: {sorted(clash)}")
    flags = {**budgets, **traffic["settings"]}
    flags["direction"] = Direction[flags.get("direction", "maximize").upper()]
    return EvaluationSettings(**flags)


def run_cell(cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, peaks: dict | None = None,
             family=None) -> tuple[dict, list]:
    """Set-up, window, readings and check of one run; returns the result
    line (without the checks) and the numbers compared."""
    import jax

    from perfbench import peaks as peak_table
    from perfbench.compile_clock import CompileClock
    from perfbench.session import SPAN_NAMES, run_session

    devices = jax.devices()
    peaks = peaks or peak_table.peaks(devices[0].device_kind)
    clock = CompileClock()
    t_family = time.perf_counter()
    fam = family or cell.family()
    fam.cell_name = cell.name
    fam.settings = settings_from(cell.config, cell.traffic)
    t_built = time.perf_counter()
    fam.setup()
    setup_s = time.perf_counter() - t_start
    print(f"set-up: {t_family - t_start:.3f} s to the devices, "
          f"{t_built - t_family:.3f} s building the family, "
          f"{setup_s - (t_built - t_start):.3f} s in its set-up",
          file=sys.stderr)
    _log_memory("after set-up", devices[0])

    sessions, tr, span = [], None, None
    c0 = clock.read()
    t0 = time.perf_counter()
    if trace:
        from perfbench.trace import Trace, load_events
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            with jax.profiler.trace(trace_dir, create_perfetto_trace=True,
                                    profiler_options=options):
                sessions.append(run_session(0, fam))
            tr = Trace.from_events(load_events(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        s = tr.span("session")
        span = (s.start, s.end)
    else:
        while True:
            sessions.append(run_session(len(sessions), fam))
            if sessions[-1].failed or time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    c1 = clock.read()
    compile_s = c1[0] - c0[0]
    print(f"window: {window_s:.3f} s, {c1[1] - c0[1]} compile events "
          f"({compile_s:.3f} s), {c1[2] - c0[2]} persistent-cache hits",
          file=sys.stderr)
    stats = devices[0].memory_stats() or {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    _log_memory("after the window", devices[0])
    for i, s in enumerate(sessions):
        if s.error:
            print(f"session {i} failed: {s.error}", file=sys.stderr)
        else:
            print(f"session {i}: {s.wall_s:.3f} s, verdict "
                  f"{s.result.best_config} score {s.result.best_score}, "
                  f"{s.result.total_samples} samples, {s.result.n_pruned} "
                  f"pruned", file=sys.stderr)

    rates = fam.rates(seed)
    verdict = fam.verdict_metrics(sessions, rates, peaks)
    run = Run(cell=cell, family=fam, sessions=sessions, setup_s=setup_s,
              window_s=window_s, compile_s=compile_s, verdict=verdict,
              peaks=peaks, trace=tr, span=span)
    line: dict = {"attempted": len(sessions),
                  "failed": sum(1 for s in sessions if s.failed)}
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        from perfbench.cell import load_reader
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line["metrics"] = metrics
    if trace:
        device["busy_s"] = tr.busy_s(*span)
        device["window_s"] = (span[1] - span[0]) * 1e-6
        ops = sorted(tr.op_seconds(*span).items(), key=lambda kv: -kv[1])
        line["breakdown"] = {"device_ops": [[n, s] for n, s in ops[:10]],
                             "idle_gaps": tr.longest_gaps(*span, SPAN_NAMES)}
    line["device"] = device
    line["window_overrun_s"] = window_s - seconds if not trace else None
    line["verdicts"] = [s.result.best_config for s in sessions
                        if not s.failed]
    line["rates"] = rates

    fam.release()
    from perfbench.check import Compared
    try:
        compared = fam.check(seed, sessions, rates)
    except Exception as e:   # a check that cannot run is a failed check
        print(f"check raised {type(e).__name__}: {e}", file=sys.stderr)
        compared = [Compared("check_ran", float("inf"), 0.0)]
    return line, compared


def _log_memory(when: str, device) -> None:
    stats = device.memory_stats() or {}
    print(f"memory {when}: in use {stats.get('bytes_in_use')} "
          f"peak {stats.get('peak_bytes_in_use')}", file=sys.stderr)


def emit(line: dict, compared: list) -> None:
    """Print the compared numbers as the last lines of standard error, and
    the result as the last line of standard output, checks last."""
    for c in compared:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr)
    correct = bool(compared) and all(c.ok for c in compared) \
        and line["failed"] == 0
    out = {"correct": correct, **line,
           "checks": {c.name: {"value": c.value, "limit": c.limit}
                      for c in compared}}
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    from perfbench.cell import Cell
    cell = Cell.load(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    import jax
    t_jax = time.perf_counter()
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    print(f"start: {t_jax - T_START:.3f} s importing jax, "
          f"{time.perf_counter() - t_jax:.3f} s to the devices",
          file=sys.stderr)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"perfbench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    line, compared = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START)
    emit(line, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
