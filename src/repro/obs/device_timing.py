"""On-device timing via ``jax.profiler.trace``.

Host clock brackets (what the samplers measure) include dispatch
latency, transfer waits and scheduler jitter on top of the kernel's
device-side busy time.  :func:`profile_sample` runs one invocation
inside a profiler window, parses the ``perfetto_trace.json.gz`` the
profiler writes, and reports the device's busy time beside the host
bracket — the first direct measurement of what the host brackets miss.

How the trace is read:

- a device is a profiler *process* whose name marks it as one
  (``/device:TPU:0``, ``/device:GPU:0``); host processes are named
  ``/host:CPU`` and carry the Python threads;
- one device shows the same work on several *lines* (threads): on a
  TPU, "XLA Modules" spans each program and "XLA Ops" each op inside
  it. Busy time is therefore the **union** of the event intervals on
  the device's "XLA Ops" line, each instant counted once; a device
  without such a line contributes the union over all of its lines;
- per-op time (:class:`DeviceOps.by_name`) sums the durations of the
  events of each op name on that same line.

Caveats, all by design:

- a profiled invocation is *slower* than an unprofiled one (the trace
  collector adds overhead), so the evaluator only profiles one extra
  sample per incumbent-candidate trial, never the measured samples;
- on the CPU backend XLA emits no device tracks, so the parse finds
  nothing and the functions return ``None`` — callers there degrade to
  host timing;
- on an accelerator a profile that fails, or finds no device track,
  raises: a measurement path on the chip never degrades in silence.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

__all__ = ["DeviceOps", "DeviceTiming", "device_timing_available",
           "profile_ops", "profile_sample"]

# substrings that mark a profiler process as device-side; host
# processes are named "/host:CPU"
_DEVICE_MARKERS = ("/device:gpu", "/device:tpu", "gpu:", "tpu:")

#: the line of a device process that carries one event per executed op
OPS_LINE = "XLA Ops"


@dataclasses.dataclass(frozen=True)
class DeviceTiming:
    """One profiled invocation: device busy time vs the host bracket."""

    device_time_s: float
    host_time_s: float
    skew_s: float  # host bracket minus device busy time
    n_events: int
    source: str

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def device_timing_available() -> bool:
    """True when jax's profiler is importable (not whether a device
    track will actually appear — that depends on the backend)."""
    try:
        import jax

        return hasattr(jax, "profiler") and hasattr(jax.profiler, "trace")
    except Exception:
        return False


@dataclasses.dataclass(frozen=True)
class DeviceOps:
    """Per-op device busy time of one profiled invocation.

    ``total_s`` is the union of the op intervals (busy time, each
    instant once); ``by_name`` keys are normalized event names (leading
    ``%`` and any ``scope/`` prefix stripped) so they join against HLO
    instruction names, and repeated executions of one op are summed.
    """

    total_s: float
    by_name: dict[str, float]
    n_events: int
    source: str
    host_time_s: float = 0.0  # host bracket of the profiled invocation

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _looks_device(process_name: str) -> bool:
    name = process_name.lower()
    return any(marker in name for marker in _DEVICE_MARKERS)


def normalize_op_name(name: str) -> str:
    """Trace event name -> HLO instruction name (best effort): profilers
    prefix op names with module scopes (``jit_f/.../%fusion.1``)."""
    return name.rsplit("/", 1)[-1].strip().lstrip("%")


def union_length(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    total = 0.0
    cur_start = cur_end = None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def parse_device_ops(events: list) -> Optional[DeviceOps]:
    """Reduce Chrome-trace ``events`` to device busy time and per-op time
    (rules in the module docstring); ``None`` when no device process
    carries a complete event."""
    device_pids = set()
    ops_lines = set()
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "M":
            continue
        name = str((ev.get("args") or {}).get("name", ""))
        if ev.get("name") == "process_name" and _looks_device(name):
            device_pids.add(ev.get("pid"))
        elif ev.get("name") == "thread_name" and name == OPS_LINE:
            ops_lines.add((ev.get("pid"), ev.get("tid")))
    per_line: dict[tuple, list] = {}
    for ev in events:
        if (isinstance(ev, dict) and ev.get("ph") == "X"
                and ev.get("pid") in device_pids):
            per_line.setdefault((ev["pid"], ev.get("tid")), []).append(ev)
    chosen: list[dict] = []
    for pid in device_pids:
        lines = [key for key in per_line if key[0] == pid]
        ops = [key for key in lines if key in ops_lines]
        for key in ops or lines:
            chosen.extend(per_line[key])
    if not chosen:
        return None
    total_us = 0.0
    for pid in device_pids:
        total_us += union_length([
            (float(ev["ts"]), float(ev["ts"]) + float(ev.get("dur", 0.0)))
            for ev in chosen if ev["pid"] == pid])
    by_name: dict[str, float] = {}
    for ev in chosen:
        key = normalize_op_name(str(ev.get("name", "")))
        if key:
            by_name[key] = (by_name.get(key, 0.0)
                            + float(ev.get("dur", 0.0)) * 1e-6)
    return DeviceOps(total_s=total_us * 1e-6, by_name=by_name,
                     n_events=len(chosen), source="")


def _parse_trace_dir(root: Path) -> Optional[DeviceOps]:
    candidates = sorted(root.rglob("perfetto_trace.json.gz"))
    if not candidates:
        return None
    source = candidates[-1]
    with gzip.open(source, "rt", encoding="utf-8", errors="replace") as fh:
        doc = json.load(fh)
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return None
    ops = parse_device_ops(events)
    return None if ops is None else dataclasses.replace(ops,
                                                       source=str(source))


def profile_ops(sample_fn: Callable[[], object],
                log_dir: Optional[str | Path] = None,
                ) -> Optional[DeviceOps]:
    """Run ``sample_fn`` once under the profiler; parse *per-op* device
    time and the host bracket around the call.

    ``log_dir=None`` uses (and removes) a temporary directory; pass a
    path to keep the raw profile for inspection. Off the accelerator
    every failure gives ``None``; on it, every failure raises.
    """
    import jax

    on_device = jax.default_backend() != "cpu"
    tmp = None
    try:
        if log_dir is None:
            tmp = tempfile.mkdtemp(prefix="repro-devprof-")
            log_dir = tmp
        try:
            with jax.profiler.trace(str(log_dir),
                                    create_perfetto_trace=True):
                t0 = time.perf_counter()
                out = sample_fn()
                # drain async dispatch so the host bracket closes after
                # the device work it is compared against (skew_s)
                jax.block_until_ready(out)
                host_s = time.perf_counter() - t0
            ops = _parse_trace_dir(Path(log_dir))
        except Exception:
            if on_device:
                raise
            return None
        if ops is None:
            if on_device:
                raise RuntimeError(
                    f"profiler trace under {log_dir} has no device track "
                    f"on backend {jax.default_backend()!r}")
            return None
        return dataclasses.replace(ops, host_time_s=host_s)
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def profile_sample(sample_fn: Callable[[], object],
                   log_dir: Optional[str | Path] = None,
                   ) -> Optional[DeviceTiming]:
    """Run ``sample_fn`` once under the jax profiler: device busy time
    beside the host bracket. Same contract as :func:`profile_ops`."""
    ops = profile_ops(sample_fn, log_dir)
    if ops is None:
        return None
    return DeviceTiming(device_time_s=ops.total_s,
                        host_time_s=ops.host_time_s,
                        skew_s=ops.host_time_s - ops.total_s,
                        n_events=ops.n_events, source=ops.source)
