#!/usr/bin/env python
"""Readings that set the hybrid cell's limits: the program's numbers over
many seeds, and the numbers of the control and of each planted fault, as
``control.py`` takes them for granite-3's cell.

    python perfbench/hybrid_control.py --workload granite4h_micro.ssd_cio \
        --program-seeds 1,2,... [--control-seeds 101,102] \
        [--faults half_sequence,...]

One process, on one chip, at the cell's own sizes. The program's step is
the first config's, compiled as the sessions compile it. The control is
the reference with float8 matmul operands in the step's place. The
faults: a step that returns no change (zero gradients), one that leaves
the second half of the sequence out, and one whose table gradient is
doubled; ``--faults`` names those to read (all three by default), each on
the control seeds. Each reading is printed as one JSON line;
``control.py`` has the kernel cells' readings.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


FAULTS = ("state_unchanged", "half_sequence", "answer_altered")


def readings(fam, program_seeds, control_seeds, faults=FAULTS) -> None:
    import jax
    import jax.numpy as jnp

    from perfbench.control import emit
    from repro.models.workloads import train_step_fn

    cfg = next(iter(fam.space.configs()))
    w = fam._workload(cfg)
    program = w.compiled()
    orig = train_step_fn(fam.arch, w.step)
    half = fam.seq // 2

    def unchanged(p, b):
        loss, grads = orig(p, b)
        return loss, jax.tree.map(jnp.zeros_like, grads)

    def half_sequence(p, b):
        return orig(p, {"tokens": b["tokens"][:, :half]})

    def altered(p, b):
        loss, grads = orig(p, b)
        grads["embed"]["table"] = grads["embed"]["table"] * 2
        return loss, grads

    planted = {"state_unchanged": unchanged, "half_sequence": half_sequence,
               "answer_altered": altered}
    faults = {name: jax.jit(planted[name]).lower(*w.args).compile()
              for name in faults}
    del w
    fam.release()
    for seed in program_seeds:
        emit("program", seed, fam.check(seed, [], {},
                                        steps={"program": program}))
    for seed in control_seeds:
        emit("control", seed, fam.check(seed, [], {}, control=True))
        for name, fn in faults.items():
            emit(name, seed, fam.check(seed, [], {}, steps={name: fn}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default=",".join(FAULTS))
    args = ap.parse_args(argv)
    from perfbench.cell import Cell
    from perfbench.run import COMPILE_CACHE, settings_from
    cell = Cell.load(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("perfbench hybrid control: needs a TPU", file=sys.stderr)
        return 2
    fam = cell.family()
    fam.cell_name = cell.name
    fam.settings = settings_from(cell.config, cell.traffic)
    # the program's weights; the first config's step is the only one
    # read, compiled in ``readings``
    from repro.models.workloads import _materialized
    jax.block_until_ready(_materialized(fam.arch))
    readings(fam, [int(s) for s in args.program_seeds.split(",") if s],
             [int(s) for s in args.control_seeds.split(",") if s],
             [f for f in args.faults.split(",") if f])
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"seconds": time.perf_counter() - T_START,
                      "memory_peak_bytes": stats.get("peak_bytes_in_use")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
