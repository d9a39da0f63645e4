"""Share of the evaluator's timed brackets in which the device was busy:
the device's busy time inside the program's ``repro.dispatch`` and
``repro.sync`` spans over their length. The rest is host overhead that
the host-timed score counts as kernel time."""

from perfbench import program_spans


def read(run):
    brackets = program_spans.intervals(run, "dispatch", "sync")
    if not brackets or not run.trace.ops:
        return None
    pid = sorted(run.trace.ops, key=str)[0]
    busy = run.trace.busy(pid, *run.span)
    return (100.0 * program_spans.overlap(busy, brackets)
            / program_spans.length(brackets))
