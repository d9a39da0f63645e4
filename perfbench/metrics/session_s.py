"""Session time: the window's wall seconds, first trial to last verdict,
over the number of sessions in it (the paper's search time)."""


def read(run):
    if run.trace is not None or not run.sessions:
        return None
    return run.window_s / len(run.sessions)
