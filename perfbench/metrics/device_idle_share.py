"""Share of the traced session's wall in which no op ran on the device:
1 - union of the "XLA Ops" intervals over the session span."""


def read(run):
    if run.trace is None or run.span is None:
        return None
    start, end = run.span
    window = (end - start) * 1e-6
    busy = run.trace.busy_s(start, end)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
