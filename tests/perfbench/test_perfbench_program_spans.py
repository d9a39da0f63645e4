"""The readers of the program's own spans and counters, on a session traced
on one TPU v5e: a two-config DGEMM session (C+I+O, two invocations of up
to four samples) under the profiler, with the harness's spans and the
program's ``repro.*`` spans on the host line and the device's "XLA Ops"
and "XLA Modules" lines, trimmed to the session."""

from __future__ import annotations

import json
import types
from pathlib import Path

import pytest

from perfbench.cell import load_reader
from perfbench.session import SPAN_NAMES
from perfbench.trace import Trace, union

DATA = (Path(__file__).resolve().parent / "data"
        / "tpu_v5e_program_spans_trace.json")
SPAN_READERS = ("sample_device_share", "operand_share", "persist_share")


def events(program: bool = True) -> list[dict]:
    doc = json.loads(DATA.read_text())["traceEvents"]
    if program:
        return doc
    return [e for e in doc if not str(e.get("name", "")).startswith("repro.")]


def run_of(trace, sessions=()):
    s = trace.span("session")
    return types.SimpleNamespace(trace=trace, span=(s.start, s.end),
                                 sessions=list(sessions), family=None,
                                 peaks=None)


def named(evs, name):
    return [e for e in evs if e.get("ph") == "X" and e["name"] == name]


def test_the_recording_holds_the_spans_the_readers_read():
    evs = events()
    for name in ("repro.tune", "repro.audit", "repro.build",
                 "repro.operands", "repro.preheat", "repro.dispatch",
                 "repro.cache_io", "repro.ledger_io"):
        assert named(evs, name), name
    trials = named(evs, "repro.trial")
    assert [t["args"]["trial"] for t in trials] == ["0", "1"]
    assert all(d["args"]["trial"] in ("0", "1")
               for d in named(evs, "repro.dispatch"))


def _expected(name, evs, session):
    lo, hi = session["ts"], session["ts"] + session["dur"]
    if name == "sample_device_share":
        device = {e["pid"] for e in evs if e.get("ph") == "M"
                  and "/device:TPU" in str(e["args"].get("name"))}
        ops_line = {(e["pid"], e["tid"]) for e in evs if e.get("ph") == "M"
                    and e["args"].get("name") == "XLA Ops"}
        ops = union([(e["ts"], e["ts"] + e["dur"]) for e in evs
                     if e.get("ph") == "X" and e["pid"] in device
                     and (e["pid"], e["tid"]) in ops_line])
        brackets = named(evs, "repro.dispatch") + named(evs, "repro.sync")
        inside = sum(max(0.0, min(b, e["ts"] + e["dur"]) - max(a, e["ts"]))
                     for e in brackets for a, b in ops)
        return 100.0 * inside / sum(e["dur"] for e in brackets)
    spans = {"operand_share": ("repro.operands",),
             "persist_share": ("repro.cache_io", "repro.ledger_io")}[name]
    # the spans of one name never overlap on the session's thread
    return 100.0 * sum(e["dur"] for n in spans for e in named(evs, n)
                       if lo <= e["ts"] <= hi) / (hi - lo)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_program_span_readers_on_the_recorded_trace(name):
    evs = events()
    (session,) = named(evs, "session")
    value = load_reader(name)(run_of(Trace.from_events(evs)))
    assert value == pytest.approx(_expected(name, evs, session), rel=1e-9)
    assert 0.0 < value < 100.0


def _session(compiles, trials, failed=False):
    counters = {} if compiles is None else {"compile.calls": compiles}
    result = types.SimpleNamespace(metrics={"counters": counters},
                                   trials=(None,) * trials)
    return types.SimpleNamespace(result=result, failed=failed)


def test_compiles_per_trial_reads_the_sessions_counter():
    read = load_reader("compiles_per_trial")
    trace = Trace.from_events(events())
    sessions = [_session(3, 4), _session(1, 4), _session(9, 1, failed=True)]
    assert read(run_of(trace, sessions)) == pytest.approx(0.5)
    # a session that compiled nothing reports no counter at all
    assert read(run_of(trace, [_session(2, 4), _session(None, 4)])) \
        == pytest.approx(0.25)


@pytest.mark.parametrize("name", SPAN_READERS + ("compiles_per_trial",))
def test_program_readers_stay_silent_without_the_programs_spans(name):
    """A program without the spans or the counter (an older commit) gives
    the reader nothing to read: it returns nothing and does not raise."""
    trace = Trace.from_events(events(program=False))
    older = [_session(None, 4)]
    assert load_reader(name)(run_of(trace, older)) is None
    no_trace = types.SimpleNamespace(trace=None, span=None, sessions=older)
    assert load_reader(name)(no_trace) is None


@pytest.mark.parametrize("name", ["device_idle_share", "flash_roofline",
                                  "step_mfu", "longest_gaps"])
def test_harness_readings_do_not_see_the_programs_spans(name):
    """The harness's own readings come out the same on the trace with and
    without the program's spans: none of its names starts with
    ``repro.``."""
    def reading(program):
        trace = Trace.from_events(events(program))
        run = run_of(trace)
        if name == "longest_gaps":
            return trace.longest_gaps(*run.span, SPAN_NAMES, limit=100)
        run.family = types.SimpleNamespace(flash_flops_per_call=3.94e9,
                                           flash_bytes_per_call=8.19e5,
                                           step_flops=1.97e12)
        from perfbench.peaks import PEAKS
        run.peaks = PEAKS["TPU v5 lite"]
        return load_reader(name)(run)

    assert reading(True) == reading(False)
    if name in ("device_idle_share", "longest_gaps"):
        assert reading(True)
