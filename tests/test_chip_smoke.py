"""chip_smoke.py at tiny sizes on the CPU: every phase runs end to end
(interpret-mode kernels; the checks only a chip can answer are not
made), main() refuses the CPU before any phase, and the process-pool
backend refuses to start workers where the backend is not the CPU."""

import importlib.util
import math
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("chip-smoke"))


@pytest.fixture(scope="module")
def roofline(smoke, cache_dir):
    return smoke.phase_roofline(cache_dir, dgemm_dims=(256,), dgemm_k=(256,),
                                triad_bytes=(1 << 20,), peaks=None)


@pytest.fixture(scope="module")
def model_step(smoke, cache_dir):
    from repro.models.workloads import TINY_CONFIG

    return smoke.phase_model_step(cache_dir, cfg=TINY_CONFIG, batch=2,
                                  seq=64, block_qs=(32, 64), block_k=64)


def test_main_refuses_cpu_before_any_phase(smoke, capsys):
    assert smoke.main() == 2
    captured = capsys.readouterr()
    assert "platform 'cpu'" in captured.err
    assert "phase" not in captured.out
    assert '"ok"' not in captured.out


def test_phase_device(smoke):
    out = smoke.phase_device()
    assert out["platform"] == "cpu"
    assert out["count"] >= 1
    assert out["fingerprint"].startswith("cpu:")


def test_phase_roofline_reports_both_roofs(roofline):
    assert roofline["dgemm_trials"] == 1 and roofline["triad_trials"] == 1
    assert roofline["F_p_GFLOPs"] > 0
    assert roofline["B_a_GBs[mem[1MiB]]"] > 0
    assert roofline["_roofs"] is not None


def test_phase_roofline_holds_measurements_to_the_peaks(smoke, tmp_path):
    from repro.core.roofline import MachineSpec

    tiny_peaks = MachineSpec(name="slow", peak_flops=1.0,
                             mem_bandwidths={"hbm": 1.0})
    with pytest.raises(smoke.SmokeFailure, match="above 105%"):
        smoke.phase_roofline(str(tmp_path), dgemm_dims=(256,),
                             dgemm_k=(256,), triad_bytes=(1 << 20,),
                             peaks=tiny_peaks)


def test_phase_kernels_match_references(smoke):
    out = smoke.phase_kernels(matmul_n=256, matmul_tile=128,
                              triad_bytes=1 << 20, triad_br=8,
                              flash=((1, 4, 2, 256, 64, 128),
                                     (1, 4, 1, 256, 64, 64)),
                              ssd=(1, 2, 2, 16, 8, 16))
    errs = {k: v for k, v in out.items() if k.endswith("_max_err")}
    assert len(errs) == 5
    assert all(math.isfinite(v) for v in errs.values())


def test_phase_model_step_tunes_and_matches_reference(model_step):
    assert model_step["trials"] == 2
    assert len(model_step["losses"]) == 3
    assert model_step["loss_diff"] <= model_step["loss_bound"]
    assert model_step["_workload"].step.use_flash


def test_phase_device_time_on_cpu_is_static(smoke, roofline, model_step):
    out = smoke.phase_device_time(roofs=roofline["_roofs"],
                                  workload=model_step["_workload"],
                                  dgemm_n=128)
    assert out["attribution_mode"] == "static"


def test_run_phase_prints_one_labelled_line(smoke, capsys):
    import jax

    clock = smoke.CompileClock()
    out = smoke.run_phase(clock, "probe", lambda: {"x": 1, "_hidden": 2})
    line = capsys.readouterr().out.strip()
    assert out == {"x": 1, "_hidden": 2}
    assert line.startswith("phase probe: wall_s=")
    assert "compile_s=" in line and "x=1" in line and "_hidden" not in line
    assert line.endswith(smoke.LABEL)
    jax.jit(lambda v: v * 3.0)(1.0)      # a fresh compile moves the clock
    assert clock.seconds > 0


def test_process_pool_refuses_non_cpu_backend(monkeypatch):
    import jax

    from benchmarks.common import synthetic_benchmark
    from repro.core import ProcessPoolBackend, Tuner, executor, grid
    from repro.core.evaluator import EvaluationSettings

    spawned = []
    monkeypatch.setattr(executor, "ProcessPoolExecutor",
                        lambda *a, **kw: spawned.append(kw))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tuner = Tuner(grid(x=(1, 2)),
                  EvaluationSettings(max_invocations=1, max_iterations=1))
    with pytest.raises(RuntimeError, match="refuses to start workers"):
        tuner.tune(synthetic_benchmark, backend=ProcessPoolBackend(2),
                   validate="off")
    assert not spawned
