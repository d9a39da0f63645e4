"""The verdict step's model FLOP/s utilization, in percent: required FLOPs
per step over the seconds of consecutive steps, over the bf16 peak."""


def read(run):
    return run.verdict.get("verdict_mfu")
