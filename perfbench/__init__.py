"""Chip benchmark of the tuner: timed tuning sessions, checked verdicts.

Run one cell with ``python perfbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; ``perfbench/README.md`` describes the files.
"""
