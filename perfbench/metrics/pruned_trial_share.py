"""Share of the traced session's trials that stop conditions pruned."""


def read(run):
    trials = sum(len(s.result.trials) for s in run.sessions if not s.failed)
    if not trials:
        return None
    pruned = sum(s.result.n_pruned for s in run.sessions if not s.failed)
    return 100.0 * pruned / trials
