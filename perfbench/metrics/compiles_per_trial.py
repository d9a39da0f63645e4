"""Lowerings plus compilations the program made per trial in the traced
session, the pre-run audit's included: its ``compile.calls`` counter in
the session's ``TuningResult.metrics`` over the session's trials."""


def read(run):
    done = [s.result for s in run.sessions if not s.failed]
    counts = [((r.metrics or {}).get("counters") or {}).get("compile.calls")
              for r in done]
    trials = sum(len(r.trials) for r in done)
    if not trials or all(c is None for c in counts):
        return None
    return sum(c or 0 for c in counts) / trials
