"""Phase-bucket profiling of the tuning harness itself.

The paper's 116x search-time win came from cutting *sample counts*; the
next order of magnitude is per-trial overhead, and you cannot cut what
you cannot see. This module is the minimal instrumentation layer the
harness self-benchmark (``scripts/bench_harness.py``) activates to
attribute a tuning session's wall clock to phase buckets:

  ``audit``     the pre-run workload audit (``Tuner._validate_workload``)
  ``build``     a trial's ``benchmark(config)`` call
  ``setup``     invocation-factory work, which holds:
  ``operands``  making an invocation's operands
  ``preheat``   the untimed first call
  ``compile``   one lowering + compilation (:func:`compiling`)
  ``dispatch``  timed kernel work, inside the samplers' clock readings
  ``sync``      device synchronization at the end of a batched sample
  ``stats``     Welford updates + stop-condition evaluation
  ``cache_io``  trial-cache JSONL appends
  ``ledger_io`` the run-ledger append at the end of a session

Buckets may nest (a cache-served ``compile`` happens inside ``setup``);
each records its own wall time independently, so buckets are a
*profile*, not a partition — ``bench_harness`` derives its headline
non-measured metric from session wall clock and kernel-time references,
and uses these buckets to explain where the overhead went.

Instrumentation sites call :func:`phase`, which is a no-op (two global
reads, no allocation) unless a :class:`PhaseProfiler` *or* a span
exporter is installed — the hot per-sample paths stay hardware-fast when
nobody is watching.  Thread-safe: concurrent trials on the thread backend fold
into the same buckets under a lock.

The module is also the **span seam** for ``repro.obs`` and for the JAX
profiler. Every :func:`phase` and :func:`trace_span` site feeds whichever
span exporters are installed:

* a :class:`~repro.obs.trace.TraceRecorder` (installed via
  :func:`set_trace_sink`) writes each span to its JSONL file, with
  parent links — per-trial attribution the folded buckets cannot give;
* while the JAX profiler is collecting, :func:`profiler_spans` (entered
  once per ``Tuner.tune()``) turns each span into a
  ``jax.profiler.TraceAnnotation`` named ``repro.<name>``, on the
  profiler's own clock beside the device's ops. Its args carry the
  enclosing trial's index and config label, so spans of one trial share
  them.

Instants go to the recorder only. Core modules never import
``repro.obs``; they call the sink-agnostic helpers here
(:func:`trace_span`, :func:`trace_instant`, :func:`compiling`), which
no-op when nothing is installed.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Optional

__all__ = ["PROFILER_PREFIX", "PhaseProfiler", "PhaseStats", "compile_thread",
           "compiling", "phase", "profiler", "profiler_spans",
           "set_trace_sink", "trace_instant", "trace_sink", "trace_span"]


class PhaseStats:
    """Accumulated (wall seconds, enter count) of one bucket."""

    __slots__ = ("seconds", "count")

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def to_json(self) -> dict:
        return {"seconds": self.seconds, "count": self.count}


class _NullPhase:
    """Shared no-op context manager returned when no sink is active."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return None


_NULL = _NullPhase()


class _Span:
    __slots__ = ("profiler", "name", "t0")

    def __init__(self, profiler: "PhaseProfiler", name: str):
        self.profiler = profiler
        self.name = name

    def __enter__(self):
        self.t0 = self.profiler.clock()
        return self

    def __exit__(self, *exc):
        self.profiler.add(self.name, self.profiler.clock() - self.t0)
        return False


class PhaseProfiler:
    """Collects phase buckets while installed as the active profiler.

    Use as a context manager (installation is process-global — one
    profiler at a time; nested installs raise)::

        prof = PhaseProfiler()
        with prof:
            tuner.tune(benchmark)
        print(prof.report())
    """

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self._lock = threading.Lock()
        self._buckets: dict[str, PhaseStats] = {}

    # -- collection -----------------------------------------------------------
    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            st = self._buckets.get(name)
            if st is None:
                st = self._buckets[name] = PhaseStats()
            st.seconds += seconds
            st.count += 1

    def phase(self, name: str) -> _Span:
        return _Span(self, name)

    # -- reading --------------------------------------------------------------
    def buckets(self) -> dict[str, PhaseStats]:
        with self._lock:
            return dict(self._buckets)

    def to_json(self) -> dict:
        return {name: st.to_json()
                for name, st in sorted(self.buckets().items())}

    def report(self) -> str:
        rows = [f"  {name:<10s} {st.seconds * 1e3:9.3f} ms x{st.count}"
                for name, st in sorted(self.buckets().items())]
        return "harness phases:\n" + "\n".join(rows) if rows \
            else "harness phases: (empty)"

    # -- installation ---------------------------------------------------------
    def __enter__(self) -> "PhaseProfiler":
        global _ACTIVE
        with _INSTALL_LOCK:
            if _ACTIVE is not None:
                raise RuntimeError("a PhaseProfiler is already active")
            _ACTIVE = self
        return self

    def __exit__(self, *exc) -> bool:
        global _ACTIVE
        with _INSTALL_LOCK:
            _ACTIVE = None
        return False


_INSTALL_LOCK = threading.Lock()
_ACTIVE: Optional[PhaseProfiler] = None

#: prefix of every span name on the profiler's trace, so that no
#: program span is ever read as one of a caller's own annotations
PROFILER_PREFIX = "repro."

# the span sink every phase()/trace_span() site feeds: the recorder, the
# profiler exporter, both, or None; rebuilt by _install() whenever one
# of the two below changes, so the sites read one global
_TRACE = None
# the installed TraceRecorder (repro.obs.trace), or None; duck-typed so
# this module never has to import obs
_RECORDER = None
# the profiler exporter, and how many tune() calls hold it
_EXPORTER = None
_EXPORTS = 0


def profiler() -> Optional[PhaseProfiler]:
    """The currently installed profiler, or ``None``."""
    return _ACTIVE


def _install() -> None:
    global _TRACE
    rec, exp = _RECORDER, _EXPORTER
    _TRACE = (rec if exp is None else exp if rec is None
              else _BothSinks(rec, exp))


def set_trace_sink(sink) -> None:
    """Install/clear the trace recorder (called by ``TraceRecorder``)."""
    global _RECORDER
    with _INSTALL_LOCK:
        _RECORDER = sink
        _install()


def trace_sink():
    """The installed trace recorder, or ``None`` when it is off."""
    return _RECORDER


def _arg(value):
    """A span attribute as a ``TraceAnnotation`` arg. The profiler packs
    args into the event name as ``name#k=v,k=v#``, so a value holds no
    ``,`` or ``#``: a config becomes ``k=v k=v``."""
    if isinstance(value, (bool, int, float)):
        return value
    if isinstance(value, dict):
        return " ".join(f"{k}={value[k]}" for k in sorted(value))
    return str(value).replace(",", " ").replace("#", " ")


class _ProfilerSpan:
    """One span as a ``jax.profiler.TraceAnnotation``; its args carry the
    enclosing trial's ``trial`` index and ``config`` label."""

    __slots__ = ("_spans", "_name", "_cat", "_attrs", "_annotation")

    def __init__(self, spans: "_ProfilerSpans", name: str, cat: str,
                 attrs: dict):
        self._spans = spans
        self._name = name
        self._cat = cat
        self._attrs = attrs
        self._annotation = None

    def __enter__(self):
        stack = self._spans.stack()
        tags = stack[-1] if stack else {}
        attrs = self._attrs
        if self._cat == "trial":
            tags = {"trial": attrs.get("index")}
        if "config" in attrs:
            tags = {**tags, "config": _arg(attrs["config"])}
        stack.append(tags)
        args = {k: _arg(v) for k, v in attrs.items()}
        args.update(tags)
        self._annotation = self._spans.annotation(
            f"{PROFILER_PREFIX}{self._name}", **args)
        self._annotation.__enter__()
        return self

    def set(self, **attrs):
        self._annotation.set_metadata(
            **{k: _arg(v) for k, v in attrs.items()})

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        self._spans.stack().pop()
        return False


class _ProfilerSpans:
    """Span exporter onto the JAX profiler's host line. Instants are not
    exported."""

    def __init__(self, annotation):
        self.annotation = annotation
        self._local = threading.local()

    def stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, cat: str = "phase", *, context: bool = False,
             **attrs) -> _ProfilerSpan:
        return _ProfilerSpan(self, name, cat, attrs)

    def instant(self, name: str, **attrs) -> None:
        return None


class _SpanPair:
    """One span open on two exporters."""

    __slots__ = ("_first", "_second")

    def __init__(self, first, second):
        self._first = first
        self._second = second

    def __enter__(self):
        self._first.__enter__()
        self._second.__enter__()
        return self

    def set(self, **attrs):
        self._first.set(**attrs)
        self._second.set(**attrs)

    def __exit__(self, *exc):
        self._second.__exit__(*exc)
        self._first.__exit__(*exc)
        return False


class _BothSinks:
    """The recorder and the profiler exporter fed from one site."""

    __slots__ = ("_recorder", "_exporter")

    def __init__(self, recorder, exporter: _ProfilerSpans):
        self._recorder = recorder
        self._exporter = exporter

    def span(self, name: str, cat: str = "phase", *, context: bool = False,
             **attrs) -> _SpanPair:
        return _SpanPair(
            self._recorder.span(name, cat=cat, context=context, **attrs),
            self._exporter.span(name, cat=cat, **attrs))

    def instant(self, name: str, **attrs) -> None:
        self._recorder.instant(name, **attrs)


@contextlib.contextmanager
def profiler_spans():
    """Export every span to the JAX profiler while this block runs, when
    the profiler is collecting as it is entered (``Tuner.tune`` enters it
    once per call); otherwise install nothing."""
    global _EXPORTER, _EXPORTS
    import jax

    if not jax.profiler.TraceAnnotation.is_enabled():
        yield
        return
    with _INSTALL_LOCK:
        _EXPORTS += 1
        if _EXPORTER is None:
            _EXPORTER = _ProfilerSpans(jax.profiler.TraceAnnotation)
            _install()
    try:
        yield
    finally:
        with _INSTALL_LOCK:
            _EXPORTS -= 1
            if _EXPORTS == 0:
                _EXPORTER = None
                _install()


class _DualPhase:
    """One ``phase()`` site feeding bucket and/or span sinks."""

    __slots__ = ("name", "_prof", "_sink", "_span", "_bucket", "_attrs")

    def __init__(self, name: str, prof: Optional[PhaseProfiler], sink,
                 attrs: Optional[dict] = None):
        self.name = name
        self._prof = prof
        self._sink = sink
        self._span = None
        self._bucket = None
        self._attrs = attrs or {}

    def __enter__(self):
        if self._prof is not None:
            self._bucket = self._prof.phase(self.name).__enter__()
        self._span = self._sink.span(self.name, cat="phase",
                                     **self._attrs).__enter__()
        return self

    def set(self, **attrs):
        self._span.set(**attrs)

    def __exit__(self, *exc):
        self._span.__exit__(*exc)
        if self._bucket is not None:
            self._bucket.__exit__(*exc)
        return False


def phase(name: str):
    """Context manager timing one phase span; free when nobody watches.

    Feeds the active :class:`PhaseProfiler` buckets and the installed span
    exporters, whichever are installed.
    """
    active = _ACTIVE
    sink = _TRACE
    if sink is None:
        if active is None:
            return _NULL
        return active.phase(name)
    return _DualPhase(name, active, sink)


# which caller drives this thread's compiles, where one says so
_COMPILER = threading.local()


def compile_thread(source: str) -> None:
    """Label every later compile on this thread with ``source`` (the
    compile pipeline's worker calls it once)."""
    _COMPILER.source = source


def compiling(source: str):
    """The ``compile`` phase around one lowering plus compilation, counted
    in the ``compile.calls`` counter. ``source`` names the call site
    (``exec_cache``, ``workload``, ``trace_cost``); a compile on the
    pipeline's worker thread reports ``pipeline`` instead."""
    from repro.obs.metrics import metrics

    metrics().inc("compile.calls")
    sink = _TRACE
    if sink is None:
        return phase("compile")
    source = getattr(_COMPILER, "source", source)
    return _DualPhase("compile", _ACTIVE, sink, {"source": source})


def trace_span(name: str, cat: str = "phase", *, context: bool = False,
               **attrs):
    """Open a span on the span exporters; shared no-op when none is
    installed."""
    sink = _TRACE
    if sink is None:
        return _NULL
    return sink.span(name, cat=cat, context=context, **attrs)


def trace_instant(name: str, **attrs) -> None:
    """Emit an instant event on the trace recorder, if one is installed."""
    sink = _TRACE
    if sink is not None:
        sink.instant(name, **attrs)
