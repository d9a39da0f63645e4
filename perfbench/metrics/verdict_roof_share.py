"""The verdict kernel's steady device rate over the published peak that
bounds it, in percent, averaged over the run's sessions."""


def read(run):
    return run.verdict.get("verdict_roof_share")
