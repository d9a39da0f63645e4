#!/usr/bin/env python
"""Readings that set the check's limits: the program's numbers over many
seeds, and the numbers of the control and of each planted fault.

    python perfbench/control.py --workload <cell> --program-seeds 1,2,... \
        --control-seeds 101,102,103

One process, on the chip the cell asks for, at the cell's own sizes. The
control is the reference one precision lower put in the program's place.
The faults are planted in the program's answer: an answer altered where
it is produced, a verdict that names the slowest config, a score that
counts its work twice; for a training step also a step that returns no
change (zero gradients) and one that leaves half the batch out. Each
reading is printed as one JSON line. The benchmark's own runs never run
this; ``PERF.md`` gives the readings and the limits set from them.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def emit(kind: str, seed: int, compared) -> None:
    print(json.dumps({"kind": kind, "seed": seed,
                      "checks": {c.name: c.value for c in compared}}),
          flush=True)


def fake_session(cfg: dict, score: float):
    from perfbench.session import SessionRecord
    result = types.SimpleNamespace(best_config=cfg, best_score=score)
    return SessionRecord(wall_s=0.0, result=result, invocation_setup_s=0.0)


def kernel_readings(fam, program_seeds, control_seeds) -> None:
    from perfbench.session import _label, run_session

    def alter(x):
        return x.reshape(-1).at[0].add(1.0).reshape(x.shape)

    rates = fam.rates(control_seeds[0])
    configs = {_label(c): c for c in fam.space.configs()}
    slowest = min(configs, key=rates["host"].get)
    for seed in program_seeds:
        emit("program", seed, fam.check(seed, [], rates))
    for seed in control_seeds:
        emit("control", seed, fam.check(seed, [], rates, control=True))
        emit("answer_altered", seed, fam.check(seed, [], rates, alter=alter))
    # a verdict that names the config the evaluator's own measure puts
    # lowest, with the score that measure gives it
    emit("verdict_slowest", control_seeds[0], fam.check(
        control_seeds[0],
        [fake_session(configs[slowest], rates["host"][slowest] / 1e9)],
        rates))
    # a score that counts its work twice: one real session whose samplers
    # double every reading
    import benchmarks.common as common
    timed = common.timed_sampler

    def doubled(fn, work, **kw):
        return timed(fn, 2.0 * work, **kw)

    common.timed_sampler = doubled
    try:
        session = run_session(0, fam)
    finally:
        common.timed_sampler = timed
    emit("score_doubled", control_seeds[0],
         fam.check(control_seeds[0], [session], rates))
    print(json.dumps({"rates": rates}), flush=True)


def train_readings(fam, program_seeds, control_seeds) -> None:
    import jax
    import jax.numpy as jnp
    from perfbench.session import _label
    from repro.models.transformer import StepConfig
    from repro.models.workloads import train_step_fn

    cfg = next(iter(fam.space.configs()))
    w = fam._workload(cfg)
    program = w.compiled()
    step = StepConfig(use_flash=bool(cfg["use_flash"]),
                      flash_block_q=int(cfg["flash_block_q"]),
                      flash_block_k=int(cfg["flash_block_k"]),
                      remat=bool(cfg["remat"]))
    orig = train_step_fn(fam.arch, step)
    half = fam.batch // 2

    def zero(p, b):
        loss, grads = orig(p, b)
        return loss, jax.tree.map(jnp.zeros_like, grads)

    def half_batch(p, b):
        return orig(p, {"tokens": b["tokens"][:half]})

    def altered(p, b):
        loss, grads = orig(p, b)
        grads["embed"]["table"] = grads["embed"]["table"] * 2
        return loss, grads

    faults = {name: jax.jit(fn).lower(*w.args).compile()
              for name, fn in (("state_unchanged", zero),
                               ("half_batch", half_batch),
                               ("answer_altered", altered))}
    del w
    fam.release()
    label = _label(cfg)
    for seed in program_seeds:
        emit("program", seed, fam.check(seed, [], {},
                                        steps={label: program}))
    for seed in control_seeds:
        emit("control", seed, fam.check(seed, [], {}, control=True))
        for name, fn in faults.items():
            emit(name, seed, fam.check(seed, [], {}, steps={name: fn}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    args = ap.parse_args(argv)
    from perfbench.cell import Cell
    from perfbench.run import COMPILE_CACHE, settings_from
    cell = Cell.load(args.workload)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("perfbench control: needs a TPU", file=sys.stderr)
        return 2
    fam = cell.family()
    fam.cell_name = cell.name
    fam.settings = settings_from(cell.config, cell.traffic)
    fam.setup()
    program = [int(s) for s in args.program_seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",")]
    if cell.traffic["family"] == "train_step":
        train_readings(fam, program, control)
    else:
        kernel_readings(fam, program, control)
    print(json.dumps({"seconds": time.perf_counter() - T_START}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
