"""The busy/idle reduction, on a trace recorded on one TPU v5e (a scanned
kernel: a module event, a ``while`` op and its body on "XLA Ops")."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from perfbench.trace import Trace, union, union_length

DATA = Path(__file__).resolve().parent / "data" / "tpu_v5e_scan_trace.json"

#: the XLA Ops line's events, by hand: copy-start.1 .. copy-done, with the
#: ``while`` op enclosing its body, so the union runs from the first start
#: to the last end less four gaps of 78, 1172, 78 and 1250 picoseconds
FIRST, LAST = 41354.478, 45054.498 + 102.177656
GAPS_US = 0.000078 + 0.001172 + 0.000078 + 0.00125


def events(extra=()):
    doc = json.loads(DATA.read_text())
    return doc["traceEvents"] + list(extra)


def span(name, ts, dur):
    return {"ph": "X", "pid": 701, "tid": 1, "name": name, "ts": ts,
            "dur": dur}


def test_union_counts_each_instant_once():
    assert union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert union_length([(0, 10), (2, 3), (4, 5)]) == 10
    assert union_length([]) == 0


def test_busy_is_the_union_of_the_ops_line():
    tr = Trace.from_events(events())
    busy = tr.busy_s(41000.0, 46000.0)
    assert busy == pytest.approx((LAST - FIRST - GAPS_US) * 1e-6, rel=1e-9)
    # a plain sum would count the while op and its body twice
    total = sum(tr.op_seconds(41000.0, 46000.0).values())
    assert total > 1.9 * busy
    # the module line spans the same work
    assert len(tr.modules[3]) == 1


def test_busy_is_clipped_to_the_window():
    tr = Trace.from_events(events())
    assert tr.busy_s(42000.0, 43000.0) == pytest.approx(1000e-6)
    assert tr.busy_s(0.0, 41000.0) == 0.0


def test_per_op_seconds():
    ops = Trace.from_events(events()).op_seconds(41000.0, 46000.0)
    assert ops["fusion.8"] == pytest.approx(
        (800.771328 + 799.9475 + 800.441406 + 800.584922) * 1e-6)
    assert ops["while"] == pytest.approx(3610.96e-6)


def test_idle_gaps_are_named_by_the_host_span():
    extra = [span("session", 40000.0, 7000.0),
             span("sample", 40500.0, 600.0),
             span("invocation_setup", 45500.0, 1000.0)]
    tr = Trace.from_events(events(extra))
    s = tr.span("session")
    gaps = tr.longest_gaps(s.start, s.end, ("session", "sample",
                                            "invocation_setup"))
    # longest first, each named by the innermost span over its middle
    assert gaps[0][0] == "invocation_setup"
    assert gaps[0][1] == pytest.approx((47000.0 - LAST) * 1e-6)
    assert gaps[1][0] == "sample"
    assert gaps[1][1] == pytest.approx((FIRST - 40000.0) * 1e-6)
    idle = sum(g for _, g in tr.longest_gaps(s.start, s.end, (), limit=100))
    assert idle + tr.busy_s(s.start, s.end) == pytest.approx(7000e-6)


def test_a_trace_without_a_device_reads_no_busy_time():
    host_only = [e for e in events() if e.get("pid") != 3]
    tr = Trace.from_events(host_only + [span("session", 0.0, 10.0)])
    assert tr.busy_s(0.0, 10.0) == 0.0
    assert tr.gaps(0.0, 10.0) == [(0.0, 10.0)]


def _device(pid, ops=(), modules=()):
    """A device process with an "XLA Ops" and an "XLA Modules" line."""
    meta = [{"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": "/device:TPU:0"}},
            {"ph": "M", "pid": pid, "tid": 1, "name": "thread_name",
             "args": {"name": "XLA Ops"}},
            {"ph": "M", "pid": pid, "tid": 2, "name": "thread_name",
             "args": {"name": "XLA Modules"}}]
    return meta + [{"ph": "X", "pid": pid, "tid": tid, "name": name,
                    "ts": ts, "dur": dur}
                   for tid, line in ((1, ops), (2, modules))
                   for name, ts, dur in line]


def _run(trace, start, end):
    from perfbench.peaks import PEAKS
    family = types.SimpleNamespace(flash_flops_per_call=3.94e9,
                                   flash_bytes_per_call=8.19e5,
                                   step_flops=1.97e12)
    return types.SimpleNamespace(trace=trace, span=(start, end),
                                 family=family, peaks=PEAKS["TPU v5 lite"])


def test_kernel_and_step_shares_from_their_events():
    from perfbench.cell import load_reader

    ops = [("flash_attention.3", 100.0, 100.0),
           ("flash_attention.4", 300.0, 100.0), ("fusion.1", 500.0, 400.0)]
    modules = [("jit_train_step(123)", 90.0, 20_000.0)]
    run = _run(Trace.from_events(_device(9, ops, modules)), 0.0, 30_000.0)
    # 3.94e9 FLOPs take 20 us at 197 TFLOP/s (the bytes 1 us): two calls
    # in 200 us of kernel time are 20% of the roofline
    assert load_reader("flash_roofline")(run) == pytest.approx(20.0)
    # 1.97e12 FLOPs in 20 ms is half of the peak
    assert load_reader("step_mfu")(run) == pytest.approx(50.0)
    # busy 600 us of the 30 ms window
    assert load_reader("device_idle_share")(run) == pytest.approx(98.0)


@pytest.mark.parametrize("name", ["flash_roofline", "step_mfu",
                                  "device_idle_share"])
def test_trace_readers_stay_silent_without_their_events(name):
    """A share of a roofline or of the peak is never read as 0: with no
    event to read, the reader returns nothing and the line leaves the
    metric out."""
    from perfbench.cell import load_reader

    host_only = [e for e in events() if e.get("pid") != 3]
    run = _run(Trace.from_events(host_only), 0.0, 10.0)
    assert load_reader(name)(run) is None
    assert load_reader(name)(_run(None, 0.0, 10.0)) is None
