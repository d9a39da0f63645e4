"""What the DGEMM and TRIAD families share: one executable per config,
timed by the sessions through the program's executable cache, two rates
per config taken by the harness after the window, and a check of each
executable against a plain float32 reference on operands drawn from the
seed.

``device`` keeps the queue full and syncs once, so it reads the kernel's
device rate: ``verdict_roof_share`` reads it. ``host`` times single calls
with their sync, as the evaluator's own sampler does, so it reads what the
session's scores read: a session is judged by it. The verdict has to rank
near the top of the host ranking, and the score has to lie near the
verdict's host rate."""

from __future__ import annotations

import math
import statistics
import time

import jax
import jax.numpy as jnp

from perfbench.check import Compared


def steady_rate(exe, args, work: float, min_s: float = 0.5) -> float:
    """Work per second of back-to-back calls, the queue kept full and one
    sync at the end, over at least ``min_s`` of host clock."""
    jax.block_until_ready(exe(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(exe(*args))
    one = max(time.perf_counter() - t0, 1e-6)
    calls = max(2, math.ceil(min_s / one))
    out = None
    t0 = time.perf_counter()
    for _ in range(calls):
        out = exe(*args)
    jax.block_until_ready(out)
    return work * calls / (time.perf_counter() - t0)


def host_rate(exe, args, work: float, samples: int = 25) -> float:
    """Work per second of one call and its sync, the evaluator's measure
    (work over the host-clock seconds around the call): the median of
    ``samples`` calls after one untimed call."""
    jax.block_until_ready(exe(*args))
    times = []
    for _ in range(samples):
        t0 = time.perf_counter()
        jax.block_until_ready(exe(*args))
        times.append(time.perf_counter() - t0)
    return work / statistics.median(times)


def rel_err(got, want) -> float:
    """Largest |got - want| over the largest |want|."""
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


class KernelFamily:
    """Subclasses set ``name``, ``kernel`` (the program's function that its
    executable cache keys on), ``peak_key``, and define ``configs``,
    ``shapes``, ``work``, ``operands``, ``reference`` and ``control``."""

    name: str
    peak_key: str
    err_name: str

    def __init__(self, config: dict, traffic: dict):
        self.config = config
        self.traffic = traffic
        self.limits = traffic["limits"]

    # -- the session's pieces -------------------------------------------------
    @property
    def space(self):
        from repro.core import grid
        return grid(**{k: tuple(v) for k, v in self.axes().items()})

    @property
    def benchmark_name(self) -> str:
        return self.name

    def setup(self) -> None:
        """Every config's executable through the program's precompile hook,
        so that the sessions find them in the executable cache."""
        for cfg in self.space.configs():
            self.benchmark.precompile(cfg)

    def executable(self, cfg: dict):
        """The executable the sessions timed for ``cfg``: it has to be in
        the program's executable cache already, else the check would test
        a program the window never ran."""
        from repro.core import default_cache
        cache = default_cache()
        shapes = self.shapes(cfg)
        if cache.key_for(self.kernel, shapes) not in cache:
            raise LookupError(f"{self.name} {cfg}: no executable in the "
                              f"program's cache for the timed shapes")
        return cache.compile(self.kernel, shapes)

    # -- readings after the window -------------------------------------------
    def rates(self, seed: int) -> dict[str, dict[str, float]]:
        """``{"device": {label: rate}, "host": {label: rate}}`` over every
        config of the space, in work per second."""
        from perfbench.session import _label
        out: dict[str, dict[str, float]] = {"device": {}, "host": {}}
        for i, cfg in enumerate(self.space.configs()):
            args = self.operands(jax.random.fold_in(_key(seed), i), cfg)
            exe, work = self.executable(cfg), self.work(cfg)
            out["device"][_label(cfg)] = steady_rate(exe, args, work)
            out["host"][_label(cfg)] = host_rate(exe, args, work)
            del args
        return out

    def verdict_metrics(self, sessions, rates, peaks) -> dict[str, float]:
        from perfbench.session import _label
        shares = [100.0 * rates["device"][_label(s.result.best_config)]
                  / peaks[self.peak_key]
                  for s in sessions if not s.failed]
        if not shares:
            return {}
        return {"verdict_roof_share": sum(shares) / len(shares)}

    def check(self, seed: int, sessions, rates, *, control: bool = False,
              alter=None) -> list[Compared]:
        """Each executable against the reference, then each verdict and
        its score against the harness's own host rates. ``control`` puts the
        reference, computed one precision lower, in the executables' place;
        ``alter`` is applied to each executable's answer (a planted
        fault)."""
        from perfbench.session import _label
        worst = 0.0
        for i, cfg in enumerate(self.space.configs()):
            args = self.operands(jax.random.fold_in(_key(seed), 1000 + i), cfg)
            want = self.reference(*args)
            got = (self.control(*args) if control
                   else self.executable(cfg)(*args))
            if alter is not None:
                got = alter(got)
            worst = max(worst, rel_err(got, want))
            del args, want, got
        out = [Compared(self.err_name, worst, self.limits[self.err_name])]
        gaps, score_gaps = [], []
        for s in sessions:
            if s.failed:
                continue
            label = _label(s.result.best_config)
            # the session scores in giga-units of work per second
            score = s.result.best_score * 1e9
            ranking = rates["host"]
            score_gaps.append(abs(score / ranking[label] - 1.0))
            gaps.append(1.0 - ranking[label] / max(ranking.values()))
        if "verdict_gap" in self.limits and gaps:
            out.append(Compared("verdict_gap", max(gaps),
                                self.limits["verdict_gap"]))
        if "score_gap" in self.limits and score_gaps:
            out.append(Compared("score_gap", max(score_gaps),
                                self.limits["score_gap"]))
        return out

    def release(self) -> None:
        pass


def _key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
