"""Share of the traced session's wall in which the invocation factories
made operands: the program's ``repro.operands`` spans."""

from perfbench import program_spans


def read(run):
    return program_spans.session_share(run, "operands")
