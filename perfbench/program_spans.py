"""The program's own spans in a traced run.

While the profiler collects, the program writes each of its spans as a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` on the host line
(``repro.core.profiling``), beside the harness's own spans and on the
same clock as the device's ops. A program without them (an older commit)
leaves nothing here to read, and every function returns nothing."""

from __future__ import annotations

from perfbench.trace import union

PREFIX = "repro."


def intervals(run, *names: str) -> list[tuple[float, float]]:
    """Disjoint intervals, in microseconds, covered by the program's spans
    of these names inside the traced session."""
    if run.trace is None or run.span is None:
        return []
    start, end = run.span
    wanted = {PREFIX + name for name in names}
    return union([(max(s.start, start), min(s.end, end))
                  for s in run.trace.spans
                  if s.name in wanted and s.end > start and s.start < end])


def length(intervals: list[tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: list[tuple[float, float]],
            b: list[tuple[float, float]]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def session_share(run, *names: str):
    """Percent of the traced session's wall under the program's spans of
    these names; nothing where the trace holds none of them."""
    covered = intervals(run, *names)
    if not covered:
        return None
    start, end = run.span
    return 100.0 * length(covered) / (end - start)
