#!/usr/bin/env python
"""Bring the tuner's main path up on one TPU chip, and check it.

    python chip_smoke.py

One process drives the library's own entry points at real sizes:

0. ``device``      requires a TPU: on any other platform the script exits
                   non-zero before any phase runs. Prints the device kind
                   and count, the JAX version, and the hardware
                   fingerprint that trial caches are keyed by.
1. ``roofline``    CI-pruned ``TuningSession``s of the registered dgemm
                   and triad benchmarks (the path ``scripts/tune.py``
                   drives), then the roofline report: F_p and one B_a per
                   working set, each held to at most 105% of the chip's
                   published peak.
2. ``kernels``     every Pallas kernel compiled for the chip (its module
                   must hold a ``tpu_custom_call``), run, and compared
                   with its ``ref.py`` computed at float32 precision.
3. ``model_step``  a ``TuningSession`` over flash-attention tiles inside
                   the granite-3-2b train step at its published width and
                   depth, then three steps of the incumbent: finite loss,
                   equal to the loss of a step without the kernel within
                   the bf16 bound ``LOSS_RTOL``, kernel present in the
                   compiled step, peak device memory.
4. ``device_time`` one profiled dgemm sample and the measured-mode
                   roofline attribution of the train step: the device's
                   busy time may not exceed the host bracket around it.

Each phase prints one line with its wall seconds, its compile seconds
(XLA compilation, reads of JAX's persistent compilation cache included)
and its readings. They are bring-up readings, not benchmark results.
Any failed check raises, so the script exits non-zero and never prints
its last line, which on success is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "<device_kind>", "count": 1}}

Compilations persist in JAX's compilation cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else ``<repo>/.jax_cache``.

The phase functions take their sizes, so tests run them small on the CPU,
where the kernels run in interpret mode and the checks that only a chip
can answer (kernel present, measured device time, peaks) are not made.
"""

from __future__ import annotations

import gc
import json
import math
import pathlib
import sys
import tempfile
import threading
import time

REPO = pathlib.Path(__file__).resolve().parent
for _p in (str(REPO), str(REPO / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import (dgemm_benchmark,  # noqa: E402
                               dgemm_invocation_factory, model_step_family,
                               model_step_workload, session_settings,
                               triad_benchmark)
from repro import configs  # noqa: E402
from repro.core import (Tuner, TuningSession, build_reports,  # noqa: E402
                        grid, hardware_fingerprint, load_trials)
from repro.core.cache import config_key  # noqa: E402
from repro.core.exec_cache import enable_compile_cache  # noqa: E402
from repro.core.roofline import MachineSpec, published_peaks  # noqa: E402
from repro.kernels import interpret_mode  # noqa: E402
from repro.kernels.flash_attention import attention_ref, flash_attention  # noqa: E402
from repro.kernels.matmul import matmul_pallas, matmul_ref  # noqa: E402
from repro.kernels.ssd import ssd_chunk_scan_pallas, ssd_chunk_scan_ref  # noqa: E402
from repro.kernels.triad import LANES, triad_pallas, triad_ref  # noqa: E402
from repro.obs.attribution import attribute, roofs_from_trials  # noqa: E402
from repro.obs.device_timing import profile_sample  # noqa: E402

LABEL = "(bring-up reading, not a benchmark result)"
MiB, GiB = 1 << 20, 1 << 30

#: a measured roof above this share of the published peak is a broken
#: measurement, not a fast chip
PEAK_SLACK = 1.05
#: |loss(flash) - loss(no flash)| <= LOSS_RTOL * |loss(no flash)|: two
#: bf16 units in the last place (bf16 carries 8 significant bits)
LOSS_RTOL = 2.0 ** -7
#: kernel-vs-reference bound, relative to the reference's largest
#: magnitude: bf16 outputs (and f32 matmuls that the MXU may run as
#: bf16 passes) get two bf16 ulps; the f32 elementwise TRIAD gets f32's
BF16_TOL = 2.0 ** -7
F32_TOL = 2.0 ** -20

#: the sizes main() runs at (tests pass small ones)
ROOFLINE = dict(dgemm_dims=(4096, 8192), dgemm_k=(2048, 8192),
                triad_bytes=(256 * MiB, 1 * GiB, 4 * GiB))
KERNELS = dict(matmul_n=4096, matmul_tile=512, triad_bytes=1 * GiB,
               triad_br=256,
               # granite-3-2b attention: batch 2, 32 q heads over 8 kv
               # heads, seq 2048, head dim 64; tiles 512 and 128
               flash=((2, 32, 8, 2048, 64, 512), (2, 32, 8, 2048, 64, 128)),
               # mamba2-130m SSD: 24 heads, 8 chunks of 256, P=64, N=128
               ssd=(1, 24, 8, 256, 64, 128))
MODEL = dict(batch=2, seq=2048, block_qs=(256, 512), block_k=512)


class SmokeFailure(RuntimeError):
    """A bring-up check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds of XLA compilation and persistent-cache hits, summed from
    JAX's own monitoring events (a cache hit's compile event lasts only
    as long as the read)."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self._lock = threading.Lock()   # compiles also run on pipeline threads
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == self.COMPILE_EVENT:
            with self._lock:
                self.seconds += duration_secs

    def _event(self, event: str, **_) -> None:
        if event == self.HIT_EVENT:
            with self._lock:
                self.hits += 1


def run_phase(clock: CompileClock, name: str, fn, *args, **kwargs) -> dict:
    """Run one phase and print its line; keys starting with ``_`` carry
    objects to later phases and are not printed."""
    gc.collect()
    t0, c0, h0 = time.perf_counter(), clock.seconds, clock.hits
    readings = fn(*args, **kwargs)
    wall = time.perf_counter() - t0
    shown = " ".join(f"{k}={v}" for k, v in readings.items()
                     if not k.startswith("_"))
    print(f"phase {name}: wall_s={wall} compile_s={clock.seconds - c0} "
          f"cache_hits={clock.hits - h0} | {shown} {LABEL}", flush=True)
    return readings


def _max_err(out, ref) -> tuple[float, float]:
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return (float(jnp.max(jnp.abs(out - ref))), float(jnp.max(jnp.abs(ref))))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_device() -> dict:
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "jax": jax.__version__,
            "fingerprint": hardware_fingerprint()}


def phase_roofline(cache_dir: str, *, dgemm_dims, dgemm_k, triad_bytes,
                   peaks: MachineSpec | None) -> dict:
    """Tune the roofs, assemble the report, hold it to the peaks."""
    out: dict = {}
    spaces = {"dgemm": (grid(n=dgemm_dims, m=dgemm_dims, k=dgemm_k),
                        dgemm_benchmark),
              "triad": (grid(n_bytes=triad_bytes), triad_benchmark)}
    for name, (space, bench) in spaces.items():
        tuner = Tuner(space, session_settings(name))
        result = TuningSession(f"roofline-{name}", tuner, bench,
                               cache_dir=cache_dir,
                               benchmark_name=name).run()
        out[f"{name}_trials"] = len(result.trials)
        out[f"{name}_pruned"] = result.n_pruned
    fingerprint = hardware_fingerprint()
    reports, skipped = build_reports(load_trials(pathlib.Path(cache_dir)))
    report = next((r for r in reports if r.fingerprint == fingerprint), None)
    check(report is not None, f"no roofline report for {fingerprint}: "
                              f"{skipped}")
    out["F_p_GFLOPs"] = report.dgemm.score
    out["F_p_config"] = config_key(report.dgemm.config)
    for subsystem, inc in report.bandwidths:
        out[f"B_a_GBs[{subsystem}]"] = inc.score
    if peaks is not None:
        bw = peaks.mem_bandwidths["hbm"]
        out["F_p_of_peak"] = report.peak_flops / peaks.peak_flops
        check(report.peak_flops <= PEAK_SLACK * peaks.peak_flops,
              f"F_p {report.peak_flops:.4g} FLOP/s is above "
              f"{PEAK_SLACK:.0%} of the published {peaks.peak_flops:.4g}")
        for subsystem, inc in report.bandwidths:
            check(inc.score * 1e9 <= PEAK_SLACK * bw,
                  f"B_a {subsystem} {inc.score * 1e9:.4g} B/s is above "
                  f"{PEAK_SLACK:.0%} of the published {bw:.4g}")
    out["_roofs"] = roofs_from_trials([cache_dir], fingerprint)
    return out


def phase_kernels(*, matmul_n: int, matmul_tile: int, triad_bytes: int,
                  triad_br: int, flash, ssd) -> dict:
    """Compile and run every Pallas kernel against its reference."""
    out: dict = {}
    interpret = interpret_mode()
    key = jax.random.key(0)

    def normal(i, shape, dtype=jnp.float32, scale=1.0):
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        return (x * scale).astype(dtype)

    def run(name: str, kernel, ref, args, tol: float) -> None:
        compiled = jax.jit(kernel).lower(*args).compile()
        if not interpret:
            check("tpu_custom_call" in compiled.as_text(),
                  f"{name}: the compiled module holds no tpu_custom_call")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref)(*args)
        err, scale = _max_err(got, want)
        out[f"{name}_max_err"] = err
        check(err <= tol * max(scale, 1.0),
              f"{name}: max |kernel - ref| = {err} exceeds "
              f"{tol} x {max(scale, 1.0)}")

    n, t = matmul_n, matmul_tile
    a, b = normal(1, (n, n), jnp.bfloat16), normal(2, (n, n), jnp.bfloat16)
    run(f"matmul_bf16_{n}", lambda x, y: matmul_pallas(
        x, y, bm=t, bn=t, bk=t, interpret=interpret), matmul_ref, (a, b),
        BF16_TOL)
    del a, b

    rows = triad_bytes // (3 * 4 * LANES)
    rows -= rows % triad_br
    a, b = normal(3, (rows, LANES)), normal(4, (rows, LANES))
    run(f"triad_{triad_bytes}B", lambda x, y: triad_pallas(
        x, y, 3.0, br=triad_br, interpret=interpret),
        lambda x, y: triad_ref(x, y, 3.0), (a, b), F32_TOL)
    del a, b

    for bsz, heads, kv_heads, seq, dim, tile in flash:
        q = normal(5, (bsz, heads, seq, dim), jnp.bfloat16)
        k = normal(6, (bsz, kv_heads, seq, dim), jnp.bfloat16)
        v = normal(7, (bsz, kv_heads, seq, dim), jnp.bfloat16)
        run(f"flash_h{heads}kv{kv_heads}s{seq}d{dim}_t{tile}",
            lambda q_, k_, v_, t_=tile: flash_attention(
                q_, k_, v_, causal=True, bq=t_, bk=t_, interpret=interpret),
            lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=True),
            (q, k, v), BF16_TOL)
        del q, k, v

    bsz, heads, chunks, q_len, p_dim, n_state = ssd
    xdt = normal(8, (bsz, heads, chunks, q_len, p_dim), scale=0.5)
    bm = normal(9, (bsz, chunks, q_len, n_state), scale=0.5)
    cm = normal(10, (bsz, chunks, q_len, n_state), scale=0.5)
    decay = -jax.random.uniform(jax.random.fold_in(key, 11),
                                (bsz, heads, chunks, q_len),
                                minval=0.01, maxval=0.2)
    cum = jnp.cumsum(decay, axis=-1)
    run(f"ssd_h{heads}c{chunks}q{q_len}p{p_dim}n{n_state}",
        lambda *xs: ssd_chunk_scan_pallas(*xs, interpret=interpret),
        ssd_chunk_scan_ref, (xdt, bm, cm, cum), BF16_TOL)
    return out


def phase_model_step(cache_dir: str, *, cfg, batch: int, seq: int,
                     block_qs, block_k: int) -> dict:
    """Tune the flash tiles inside the train step, then run the winner."""
    family = model_step_family("train_step", cfg, batch_size=batch,
                               seq_len=seq)
    space = grid(use_flash=(1,), flash_block_q=tuple(block_qs),
                 flash_block_k=(block_k,), remat=(1,))
    result = TuningSession("model-train-step",
                           Tuner(space, session_settings("train_step")),
                           family, cache_dir=cache_dir,
                           benchmark_name="train_step").run()
    best = result.best_config
    check(best is not None, "every train-step config was pruned")
    out: dict = {"model": cfg.name, "layers": cfg.n_layers,
                 "d_model": cfg.d_model, "batch": batch, "seq": seq,
                 "trials": len(result.trials),
                 "incumbent": config_key(best),
                 "incumbent_GFLOPs": result.best_score}

    workload = model_step_workload("train_step", cfg, best,
                                   batch_size=batch, seq_len=seq)
    compiled = workload.compiled()
    if not interpret_mode():
        check("tpu_custom_call" in compiled.as_text(),
              "the compiled train step holds no tpu_custom_call")
    losses, step_s = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        loss, grads = jax.block_until_ready(compiled(*workload.args))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
        del grads
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    out["losses"] = losses
    out["step_s"] = step_s

    reference = model_step_workload("train_step", cfg,
                                    {**best, "use_flash": 0},
                                    batch_size=batch, seq_len=seq)
    ref_loss, grads = jax.block_until_ready(
        reference.compiled()(*reference.args))
    del grads
    ref_loss = float(ref_loss)
    diff = abs(losses[0] - ref_loss)
    out["loss_no_flash"] = ref_loss
    out["loss_diff"] = diff
    out["loss_bound"] = LOSS_RTOL * abs(ref_loss)
    check(diff <= LOSS_RTOL * abs(ref_loss),
          f"flash loss {losses[0]} vs no-flash loss {ref_loss}: "
          f"|diff| {diff} > {LOSS_RTOL} x |ref|")
    stats = jax.devices()[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use", "n/a")
    out["_workload"] = workload
    return out


def phase_device_time(*, roofs, workload, dgemm_n: int) -> dict:
    """Device busy time of a dgemm sample and of the train step."""
    out: dict = {}
    on_chip = not interpret_mode()
    sampler = dgemm_invocation_factory(dgemm_n, dgemm_n, dgemm_n)()
    timing = profile_sample(sampler)
    check(timing is not None or not on_chip,
          "no device timing from the dgemm profile")
    if timing is not None:
        out["dgemm_device_s"] = timing.device_time_s
        out["dgemm_host_s"] = timing.host_time_s
        check(timing.device_time_s <= timing.host_time_s,
              f"dgemm device busy {timing.device_time_s} s exceeds the "
              f"host bracket {timing.host_time_s} s")
    report = attribute(workload, roofs)
    out["attribution_mode"] = report.mode
    check(report.mode == "measured" or not on_chip,
          f"train-step attribution ran in {report.mode!r} mode")
    if report.mode == "measured":
        out["step_device_s"] = report.device_total_s
        out["step_host_s"] = report.host_time_s
        out["ops"] = len(report.ops)
        out["unattributed_frac"] = report.unattributed_frac
        check(report.device_total_s <= report.host_time_s,
              f"train-step device busy {report.device_total_s} s exceeds "
              f"the host bracket {report.host_time_s} s")
    return out


def main() -> int:
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, and JAX found platform "
              f"{device.platform!r} ({device.device_kind}); no phase ran",
              file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    peaks = published_peaks(device.device_kind)
    run_phase(clock, "device", phase_device)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as cache_dir:
        roof = run_phase(clock, "roofline", phase_roofline, cache_dir,
                         peaks=peaks, **ROOFLINE)
        run_phase(clock, "kernels", phase_kernels, **KERNELS)
        model = run_phase(clock, "model_step", phase_model_step, cache_dir,
                          cfg=configs.get("granite_3_2b"), **MODEL)
        run_phase(clock, "device_time", phase_device_time,
                  roofs=roof["_roofs"], workload=model["_workload"],
                  dgemm_n=4096)
    print(json.dumps({"ok": True,
                      "device": {"platform": device.platform,
                                 "kind": device.device_kind,
                                 "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
