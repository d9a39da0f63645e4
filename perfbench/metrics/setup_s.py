"""Set-up: process start to the first trial (accelerator, executables from
the persistent compile cache, weights)."""


def read(run):
    return run.setup_s
