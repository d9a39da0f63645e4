"""Share of the traced session's wall spent inside the workloads layer's
invocation factories (operands, pre-heat), by the harness's host span."""


def read(run):
    wall = sum(s.wall_s for s in run.sessions)
    if wall <= 0:
        return None
    return 100.0 * sum(s.invocation_setup_s for s in run.sessions) / wall
