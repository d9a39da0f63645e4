"""Pass 1 — workload audit: is the benchmark measuring what it claims?

The evaluator converts time into GFLOP/s (or GB/s) by dividing a
*declared* work term by the measured duration
(:func:`repro.core.evaluator.timed_sampler`). Every roofline placement
downstream inherits that constant, so a wrong declaration poisons the
whole analysis while every CI happily converges — the paper's <2% error
budget assumes the work term is right. This pass traces the benchmark's
kernel and cross-checks:

  MS101  declared work vs traced cost beyond tolerance
  MS102  traced computation is dead / constant-folded (a DCE'd kernel
         times an empty executable and reports fantasy throughput)
  MS103  traced dtype differs from the declared one (f32 masquerading
         as DGEMM when x64 is disabled)

Benchmarks opt in by exposing an ``audit_spec`` attribute: a callable
``config -> WorkloadSpec`` naming the pure jax function, example
arguments (``jax.ShapeDtypeStruct`` avoids allocation), and the declared
work in raw FLOPs/bytes — computed by the *same helper* the invocation
factory uses, so the audit checks the shared formula against reality.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

from .findings import Finding, make_finding

__all__ = ["TracedCost", "WorkloadSpec", "audit_benchmark",
           "audit_workload", "trace_cost"]


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """Declaration of one benchmark's timed kernel, for the audit.

    ``work`` is in raw units (FLOPs or bytes) per timed call; the
    invocation factory may scale it for display (e.g. /1e9 for GFLOP/s)
    but must derive it from the same formula.
    """

    fn: Callable                     # pure jax callable to trace
    args: tuple                      # example args (ShapeDtypeStructs ok)
    work: float                      # declared work per timed call
    unit: str                        # "flops" | "bytes"
    dtype: Optional[str] = None      # declared compute dtype, e.g. "float32"
    name: str = "workload"
    tolerance: float = 0.05          # relative declared-vs-traced tolerance

    def __post_init__(self):
        if self.unit not in ("flops", "bytes"):
            raise ValueError(f"unit must be 'flops' or 'bytes', "
                             f"got {self.unit!r}")


@dataclasses.dataclass(frozen=True)
class TracedCost:
    """What the compiler says the kernel actually does."""

    flops: float
    bytes_accessed: float
    out_dtypes: tuple[str, ...]
    n_eqns: int                      # jaxpr equations (0 = constant-folded)

    def work(self, unit: str) -> float:
        return self.flops if unit == "flops" else self.bytes_accessed


def trace_cost(fn: Callable, args: Sequence[Any]) -> TracedCost:
    """Lower + compile ``fn`` and extract its cost.

    Primary source is the backend's ``cost_analysis`` (exact on CPU/TPU);
    when it reports neither flops nor bytes the optimized HLO text is
    re-parsed with :func:`repro.analysis.hlo.parse_hlo_cost`.
    """
    import jax

    from repro.analysis.hlo import parse_hlo_cost
    from repro.core.profiling import compiling

    with compiling("trace_cost"):
        compiled = jax.jit(fn).lower(*args).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    ca = ca or {}
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))
    if flops == 0.0 and bytes_accessed == 0.0:
        cost = parse_hlo_cost(compiled.as_text())
        flops, bytes_accessed = cost.flops, cost.bytes_accessed
    jaxpr = jax.make_jaxpr(fn)(*args)
    out_dtypes = tuple(str(v.aval.dtype) for v in jaxpr.jaxpr.outvars
                       if hasattr(v, "aval"))
    return TracedCost(flops=flops, bytes_accessed=bytes_accessed,
                      out_dtypes=out_dtypes, n_eqns=len(jaxpr.jaxpr.eqns))


def _anchor(obj) -> tuple[str, int]:
    """Best-effort (path, line) of a python callable, for finding anchors."""
    import inspect
    try:
        path = inspect.getsourcefile(obj) or "<unknown>"
        _, line = inspect.getsourcelines(obj)
    except (TypeError, OSError):
        path, line = "<unknown>", 0
    return path, line


def audit_workload(spec: WorkloadSpec,
                   path: str = "<workload>", line: int = 0) -> list[Finding]:
    """Run the declared-vs-traced checks on one :class:`WorkloadSpec`."""
    findings: list[Finding] = []
    try:
        traced = trace_cost(spec.fn, spec.args)
    except Exception as e:  # trace/compile failed: report, don't crash
        return [make_finding(
            "MS104", path, line,
            f"{spec.name}: tracing the audit spec failed: "
            f"{type(e).__name__}: {e}")]
    traced_work = traced.work(spec.unit)
    if traced.n_eqns == 0 or traced_work == 0.0:
        findings.append(make_finding(
            "MS102", path, line,
            f"{spec.name}: declared {spec.work:.4g} {spec.unit} but the "
            f"traced kernel performs none (jaxpr eqns={traced.n_eqns}, "
            f"traced {spec.unit}={traced_work:.4g}) — the timed "
            f"computation was dead-code-eliminated or constant-folded"))
    else:
        rel = abs(spec.work - traced_work) / traced_work
        if rel > spec.tolerance:
            findings.append(make_finding(
                "MS101", path, line,
                f"{spec.name}: declared {spec.work:.6g} {spec.unit} but "
                f"trace shows {traced_work:.6g} ({rel:.1%} off, tolerance "
                f"{spec.tolerance:.0%}) — every derived {spec.unit}/s "
                f"score is scaled by this error"))
    if spec.dtype is not None and traced.out_dtypes \
            and any(dt != spec.dtype for dt in traced.out_dtypes):
        findings.append(make_finding(
            "MS103", path, line,
            f"{spec.name}: declared dtype {spec.dtype} but traced outputs "
            f"are {', '.join(sorted(set(traced.out_dtypes)))} — check "
            f"jax_enable_x64 / input dtypes (a demoted kernel does "
            f"different work than declared)"))
    return findings


def audit_benchmark(benchmark, config,
                    name: Optional[str] = None) -> list[Finding]:
    """Audit a tuner benchmark (``config -> InvocationFactory``) for one
    configuration, via its ``audit_spec`` attribute.

    A benchmark without ``audit_spec`` yields a single info-level MS100:
    not auditable is worth knowing, but never fails a run.
    """
    label = name or getattr(benchmark, "__name__", repr(benchmark))
    path, line = _anchor(benchmark)
    builder = getattr(benchmark, "audit_spec", None)
    if builder is None:
        return [make_finding(
            "MS100", path, line,
            f"{label}: no audit_spec attribute; workload audit skipped "
            f"(attach one to enable declared-vs-traced checking)")]
    try:
        spec = builder(config)
    except Exception as e:
        return [make_finding(
            "MS104", path, line,
            f"{label}: audit_spec({config!r}) raised "
            f"{type(e).__name__}: {e}")]
    return audit_workload(spec, path=path, line=line)
