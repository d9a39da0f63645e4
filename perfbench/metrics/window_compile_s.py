"""Seconds of compile events (persistent-cache reads included) that JAX
reported inside the traced session."""


def read(run):
    return run.compile_s
