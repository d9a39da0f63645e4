"""Timed samples the evaluator took per trial in the traced session."""


def read(run):
    trials = sum(len(s.result.trials) for s in run.sessions if not s.failed)
    if not trials:
        return None
    return sum(s.result.total_samples for s in run.sessions
               if not s.failed) / trials
