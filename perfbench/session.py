"""Tuning sessions driven through the program's own entry, with the
harness's host spans around each call into the workloads layer.

Spans are ``jax.profiler.TraceAnnotation``s, so in a traced run they sit
on the profiler's clock beside the device's events; their host-clock
durations are summed here as well, for the untraced runs.
"""

from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from typing import Callable, Optional

import jax

#: the harness's host spans, outermost first
SPAN_NAMES = ("session", "trial", "trial_build", "invocation_setup", "sample",
              "precompile")


def _label(cfg: dict) -> str:
    return ",".join(f"{k}={cfg[k]}" for k in sorted(cfg))


class Spans:
    """Wraps a benchmark callable so that each call into the workloads
    layer (the trial's build, each invocation's set-up, each sample) runs
    inside a named span, and sums the invocation set-up seconds."""

    def __init__(self):
        self.invocation_setup_s = 0.0
        self._open: dict[str, jax.profiler.TraceAnnotation] = {}

    def wrap(self, bench: Callable) -> Callable:
        def wrapped(cfg: dict):
            label = _label(cfg)
            trial = jax.profiler.TraceAnnotation("trial", config=label)
            trial.__enter__()
            self._open[label] = trial
            with jax.profiler.TraceAnnotation("trial_build", config=label):
                factory = bench(cfg)

            def invocation():
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("invocation_setup",
                                                  config=label):
                    sampler = factory()
                self.invocation_setup_s += time.perf_counter() - t0

                def sample():
                    with jax.profiler.TraceAnnotation("sample"):
                        return sampler()

                return sample

            return invocation

        precompile = getattr(bench, "precompile", None)
        if precompile is not None:
            def traced_precompile(cfg: dict) -> None:
                with jax.profiler.TraceAnnotation("precompile",
                                                  config=_label(cfg)):
                    precompile(cfg)

            wrapped.precompile = traced_precompile
        audit_spec = getattr(bench, "audit_spec", None)
        if audit_spec is not None:
            wrapped.audit_spec = audit_spec
        return wrapped

    def trial_done(self, cfg: dict, _result) -> None:
        """The session's ``progress`` callback: closes the trial's span."""
        trial = self._open.pop(_label(cfg), None)
        if trial is not None:
            trial.__exit__(None, None, None)

    def close(self) -> None:
        for trial in self._open.values():
            trial.__exit__(None, None, None)
        self._open.clear()


@dataclasses.dataclass
class SessionRecord:
    """One session: its wall seconds from first trial to verdict, the
    program's ``TuningResult`` (``None`` when it raised), and the seconds
    its invocations spent in set-up."""

    wall_s: float
    result: object
    invocation_setup_s: float
    error: Optional[str] = None

    @property
    def failed(self) -> bool:
        return self.result is None or self.result.best_config is None


def run_session(index: int, family) -> SessionRecord:
    """One ``TuningSession`` over the family's space and settings, with a
    fresh trial cache directory, so that no trial is served from an
    earlier session."""
    from repro.core import Tuner, TuningSession

    spans = Spans()
    bench = spans.wrap(family.benchmark)
    cache_dir = tempfile.mkdtemp(prefix="perfbench-session-")
    result, error = None, None
    try:
        with jax.profiler.TraceAnnotation("session", index=index):
            t0 = time.perf_counter()
            try:
                session = TuningSession(
                    f"{family.cell_name}-{index}",
                    Tuner(family.space, family.settings), bench,
                    cache_dir=cache_dir,
                    benchmark_name=family.benchmark_name)
                result = session.run(progress=spans.trial_done)
            except Exception as e:   # a failed session is counted, not fatal
                error = f"{type(e).__name__}: {e}"
            wall = time.perf_counter() - t0
    finally:
        spans.close()
        shutil.rmtree(cache_dir, ignore_errors=True)
    return SessionRecord(wall_s=wall, result=result,
                         invocation_setup_s=spans.invocation_setup_s,
                         error=error)
