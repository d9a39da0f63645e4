"""Share of the traced session's wall spent persisting: the program's
trial-cache appends (``repro.cache_io``) and its run-ledger append
(``repro.ledger_io``)."""

from perfbench import program_spans


def read(run):
    return program_spans.session_share(run, "cache_io", "ledger_io")
