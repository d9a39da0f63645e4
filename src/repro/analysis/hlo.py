"""Parse collective traffic and compute/memory cost out of compiled HLO text.

``cost_analysis`` does not report collective bytes, so we scan the compiled
module for all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops, take each op's result shape, and convert to
estimated per-device link traffic with the standard ring-algorithm factors:

    all-reduce        2 * bytes * (n-1)/n      (reduce-scatter + all-gather)
    all-gather        bytes * (n-1)/n          (result = gathered size)
    reduce-scatter    bytes * (n-1)            (operand = result * n)
    all-to-all        bytes * (n-1)/n
    collective-permute bytes                   (point-to-point)

where n is the replica-group size parsed from the op's replica_groups.

:func:`parse_hlo_cost` is the text-level sibling for compute cost: it
re-derives FLOP and byte counts for dot / elementwise / copy-like ops from
the module text alone. The measurement-soundness linter
(:mod:`repro.lint.workload`) cross-checks a benchmark's *declared* work
term against this traced cost (falling back to it when the backend's
``cost_analysis`` reports nothing), so a DGEMM that silently stopped doing
2·n·m·k FLOPs is caught before the tuner spends hours timing it.
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
    # fp8 family (one byte each) — a quantized op must not land in the
    # unhandled tally and silently undercount traffic
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "f8e4m3fnuz": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e8m0fnu": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# one result shape: an array with an optional layout, whose TPU form
# nests parens (f32[8,128]{1,0:T(8,128)S(1)}), or a tuple of them
_SHAPE_TEXT = (r"(?:\((?:[^()]|\([^()]*\))*\)"
               r"|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)")

# e.g.  %ag = bf16[16,1024]{1,0} all-gather(%x), ...
#       ROOT %tuple = (f32[4]{0}, f32[4]{0}) all-reduce(...)
_OP_RE = re.compile(
    r"=\s*(?P<shape>" + _SHAPE_TEXT + r")\s*"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start|-done)?\(",
)

_SHAPE_RE = re.compile(r"(?P<dtype>[a-z0-9]+)\[(?P<dims>[0-9,]*)\]")

_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype = m.group("dtype")
        if dtype not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


@dataclasses.dataclass(frozen=True)
class CollectiveStats:
    """Per-device collective traffic estimate."""

    bytes_by_op: dict[str, float]
    count_by_op: dict[str, int]

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_op.values())

    @property
    def total_count(self) -> int:
        return sum(self.count_by_op.values())

    def summary(self) -> str:
        parts = [f"{op}:{cnt}x/{by/1e6:.1f}MB"
                 for op, (cnt, by) in sorted(
                     {o: (self.count_by_op[o], self.bytes_by_op[o])
                      for o in self.count_by_op}.items())]
        return " ".join(parts) if parts else "none"


# ---------------------------------------------------------------------------
# Compute/memory cost extraction (measurement-soundness audit, pass 1)
# ---------------------------------------------------------------------------

# ops whose FLOP count is one op per result element
_ELEMENTWISE_OPS = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "remainder", "atan2", "compare", "and", "or", "xor", "not", "negate",
    "abs", "sign", "floor", "ceil", "round-nearest-afz", "round-nearest-even",
    "exponential", "exponential-minus-one", "log", "log-plus-one", "tanh",
    "sqrt", "rsqrt", "cbrt", "cosine", "sine", "tan", "logistic", "erf",
    "clamp", "select",
})

# pure data movement: no FLOPs, operand + result bytes count as traffic
_COPY_OPS = frozenset({
    "copy", "transpose", "reshape", "broadcast", "concatenate", "slice",
    "dynamic-slice", "dynamic-update-slice", "pad", "reverse", "gather",
    "convert", "iota",
})

# structural ops that move no data themselves (fusion bodies are separate
# computations in the same text, so their inner ops are already counted)
_SKIP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "fusion", "call",
    "bitcast", "bitcast-convert", "after-all", "partition-id", "replica-id",
    "custom-call", "while", "conditional", "domain", "opt-barrier",
}) | set(_COLLECTIVES)

# reductions: one FLOP per *input* element folded into the result
# (reduce of N elements to M results performs ~N combiner applications)
_REDUCE_OPS = frozenset({"reduce", "reduce-window"})

# generic "name = shape op(" — the op token is the word before the operand
# list; versioned names (%add.0) carry the version after the paren match
_COST_OP_RE = re.compile(
    r"=\s*(?P<shape>" + _SHAPE_TEXT + r")\s*(?P<op>[a-z][a-z0-9-]*)\(")

_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=[^_\s]+_(?P<rhs>[^-\s]+)->")


def _shape_numel(shape_str: str) -> int:
    """Total element count over every sub-shape of ``shape_str`` (tuples
    sum; a scalar ``f32[]`` counts 1; unknown dtypes still count)."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        n = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n
    return total


def _operand_text(line: str, start: int) -> str:
    """The text between the op's opening paren at ``start`` and its
    balanced closing paren (operand lists never nest in practice, but
    ``fusion(..., calls=...)`` attributes keep this honest)."""
    depth = 0
    for i in range(start, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i]
    return line[start + 1:]


def _split_operands(text: str) -> list[str]:
    """Top-level comma split of an operand list (shapes carry commas
    inside ``[...]``, layouts inside ``{...}``, tuples inside ``(...)``)."""
    parts: list[str] = []
    depth = 0
    start = 0
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(text[start:i])
            start = i + 1
    parts.append(text[start:])
    return [p.strip() for p in parts if p.strip()]


def _operand_shapes(operands: str, symbols: dict[str, str]) -> str:
    """The operand list rewritten as its shapes, space-separated.

    Older XLA prints each operand with its shape (``f32[64,32]{1,0}
    %a``); newer XLA prints bare names (``%a.1``), so a bare name is
    resolved through ``symbols``, the enclosing computation's
    ``name -> result shape`` table. Unknown names resolve to nothing
    (zero bytes) rather than guessing."""
    shapes = []
    for part in _split_operands(operands):
        if _SHAPE_RE.match(part) or part.startswith("("):
            shapes.append(part.rsplit("%", 1)[0] if "%" in part else part)
        else:
            shapes.append(symbols.get(part.lstrip("%"), ""))
    return " ".join(s for s in shapes if s)


@dataclasses.dataclass(frozen=True)
class HloCost:
    """Text-derived compute/memory cost estimate of one HLO module.

    ``flops`` counts dot contractions (2·M·N·K) and one op per result
    element for elementwise ops; ``bytes_accessed`` counts operand plus
    result bytes of every costed op (an upper-bound traffic model: fused
    intermediates are counted even though they never reach memory).
    ``unhandled`` tallies op kinds the model does not cost — nonzero
    entries mean the estimate is partial, not that parsing failed.
    """

    flops: float
    bytes_accessed: float
    flops_by_op: dict[str, float]
    bytes_by_op: dict[str, float]
    unhandled: dict[str, int]

    def summary(self) -> str:
        return (f"flops={self.flops:.3g} bytes={self.bytes_accessed:.3g}"
                + (f" unhandled={sorted(self.unhandled)}"
                   if self.unhandled else ""))


def _op_cost(op: str, shape: str, operands: str,
             line: str) -> "tuple[float, float] | None":
    """(flops, bytes) of one costed instruction, ``None`` when the op kind
    is not modeled. ``operands`` is the resolved operand shape text
    (:func:`_operand_shapes`). Shared by the module-aggregate and per-op
    parsers so the two views can never disagree on a single
    instruction."""
    moved = _shape_bytes(shape) + _shape_bytes(operands)
    if op == "dot":
        lhs = _SHAPE_RE.search(operands)
        contract = 1
        cm = _CONTRACT_RE.search(line)
        if lhs is not None and cm is not None and cm.group(1):
            dims = [int(d) for d in lhs.group("dims").split(",")] \
                if lhs.group("dims") else []
            for idx in cm.group(1).split(","):
                i = int(idx)
                if 0 <= i < len(dims):
                    contract *= dims[i]
        return 2.0 * _shape_numel(shape) * contract, float(moved)
    if op == "convolution":
        # the TPU compiler emits matmuls as convolutions
        # (dim_labels=bf_io->bf): each output element accumulates one
        # product per kernel element outside the kernel's output-feature
        # dim 'o'
        kernel = list(_SHAPE_RE.finditer(operands))[1:2]
        labels = _DIM_LABELS_RE.search(line)
        if not kernel or labels is None:
            return None
        dims = [int(d) for d in kernel[0].group("dims").split(",")
                if d]
        o_dim = labels.group("rhs").find("o")
        if len(dims) != len(labels.group("rhs")) or o_dim < 0:
            return None
        per_output = math.prod(dims) // dims[o_dim]
        return 2.0 * _shape_numel(shape) * per_output, float(moved)
    if op in _ELEMENTWISE_OPS:
        return float(_shape_numel(shape)), float(moved)
    if op in _REDUCE_OPS:
        # one combiner application per input element (init scalars are
        # part of the operand list; their O(1) contribution is noise)
        return float(_shape_numel(operands)), float(moved)
    if op in _COPY_OPS:
        return 0.0, float(moved)
    return None


def parse_hlo_cost(hlo_text: str) -> HloCost:
    """Extract FLOP/byte costs for dot / elementwise / reduce / copy ops
    from HLO text (compiled ``.as_text()`` or handwritten fixtures).

    Fusion *bodies* are separate named computations in the same text, so
    counting every line once costs fused ops exactly once; the calling
    ``fusion`` instruction itself is structural and skipped.
    """
    flops_by_op: dict[str, float] = defaultdict(float)
    bytes_by_op: dict[str, float] = defaultdict(float)
    unhandled: dict[str, int] = defaultdict(int)
    for _, _, _, op, shape, operands, line in _instructions(hlo_text):
        if op in _SKIP_OPS or op.endswith("-start") or op.endswith("-done"):
            continue
        cost = _op_cost(op, shape, operands, line)
        if cost is None:
            unhandled[op] += 1
            continue
        flops, moved = cost
        if flops:
            flops_by_op[op] += flops
        bytes_by_op[op] += moved
    return HloCost(flops=sum(flops_by_op.values()),
                   bytes_accessed=sum(bytes_by_op.values()),
                   flops_by_op=dict(flops_by_op),
                   bytes_by_op=dict(bytes_by_op),
                   unhandled=dict(unhandled))


# ---------------------------------------------------------------------------
# Per-op records (roofline attribution, repro.obs.attribution)
# ---------------------------------------------------------------------------

#: structural entry-computation ops that never appear on a device timeline
_STRUCTURAL_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "domain", "opt-barrier", "partition-id", "replica-id",
})

#: caller ops whose cost is their called computation's total
_CALLER_OPS = frozenset({"fusion", "call"})

# computation header, column 0:  "%fused_computation.1 (p: f32[4]) -> ... {"
# or "ENTRY %main.5 (...) -> ... {"  (handwritten fixtures may omit "%")
_COMP_RE = re.compile(
    r"^(?P<entry>ENTRY\s+)?%?(?P<name>[\w.$-]+)\s*\(")

_INSTR_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.$-]+)\s*=")

_CALLS_RE = re.compile(r"(?:calls|to_apply)=%?(?P<callee>[\w.$-]+)")


def _instructions(hlo_text: str):
    """Yield ``(computation, is_entry, name, op, shape, operand_shapes,
    line)`` for every instruction line, with operand shapes resolved
    through the computation's symbol table (definitions precede uses in
    HLO text). Lines before any computation header (handwritten
    fixtures) belong to the computation named ``""``."""
    current = ""
    is_entry = False
    symbols: dict[str, str] = {}
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            cm = _COMP_RE.match(line)
            if cm and line.rstrip().endswith("{") \
                    and ("->" in line or cm.group("entry")):
                current = cm.group("name")
                is_entry = bool(cm.group("entry"))
                symbols = {}
                continue
        m = _COST_OP_RE.search(line)
        if m is None:
            continue
        nm = _INSTR_NAME_RE.match(line)
        name = nm.group("name") if nm else m.group("op")
        shape = m.group("shape")
        operands = _operand_shapes(_operand_text(line, m.end() - 1), symbols)
        symbols[name] = shape
        yield current, is_entry, name, m.group("op"), shape, operands, line


@dataclasses.dataclass(frozen=True)
class OpCost:
    """One entry-computation instruction with its text-derived cost.

    ``kind`` is the HLO opcode (fusions keep ``fusion``; their cost is
    the called computation's total). ``modeled`` is False for op kinds
    the cost model does not cover — the record still exists (so measured
    device time can be joined against it) but carries zero cost.
    """

    name: str                    # instruction name, "%" stripped
    kind: str
    flops: float
    bytes_accessed: float
    modeled: bool = True

    @property
    def intensity(self) -> float:
        """Arithmetic intensity I = FLOPs / bytes of this op (paper
        Eq. 1). Zero-traffic compute is ``inf``; zero-work movement 0."""
        if self.bytes_accessed <= 0:
            return float("inf") if self.flops > 0 else 0.0
        return self.flops / self.bytes_accessed


@dataclasses.dataclass(frozen=True)
class ModuleOps:
    """Per-op cost records of one HLO module's entry computation."""

    ops: tuple[OpCost, ...]
    unhandled: dict[str, int]    # op kinds seen but not cost-modeled

    @property
    def flops(self) -> float:
        return sum(o.flops for o in self.ops)

    @property
    def bytes_accessed(self) -> float:
        return sum(o.bytes_accessed for o in self.ops)

    def by_name(self) -> dict[str, OpCost]:
        return {o.name: o for o in self.ops}


def parse_hlo_ops(hlo_text: str) -> ModuleOps:
    """Per-instruction cost records of the ENTRY computation.

    Unlike :func:`parse_hlo_cost` (which flattens every computation into
    module totals), this keeps one record per *entry* instruction — the
    granularity a device timeline reports — and rolls each ``fusion`` /
    ``call`` instruction's cost up from its called computation, so a
    fused elementwise chain is attributed to the one op Perfetto will
    show. ``while``/``conditional`` bodies are counted once (trip counts
    are not in the text); they land in ``unhandled`` to flag the
    estimate as partial.
    """
    # pass 1: bucket instruction lines per computation
    comps: dict[str, list[tuple[str, str, str, str, str]]] = {}
    entry: "str | None" = None
    for comp, is_entry, name, op, shape, operands, line in \
            _instructions(hlo_text):
        if is_entry:
            entry = comp
        comps.setdefault(comp, []).append((name, op, shape, operands, line))
    if entry is None:
        # handwritten fixtures without an ENTRY header: the implicit
        # top-level bucket (or the only computation present)
        entry = "" if comps.get("") else next(iter(comps), "")

    unhandled: dict[str, int] = defaultdict(int)

    def comp_cost(comp: str, seen: frozenset) -> tuple[float, float]:
        """Summed (flops, bytes) of one computation, callees resolved."""
        if comp in seen:  # pragma: no cover - malformed recursive module
            return 0.0, 0.0
        total_f = total_b = 0.0
        for name, op, shape, operands, line in comps.get(comp, ()):
            f, b, _ = record_cost(op, shape, operands, line, seen | {comp})
            total_f += f
            total_b += b
        return total_f, total_b

    def record_cost(op: str, shape: str, operands: str, line: str,
                    seen: frozenset) -> tuple[float, float, bool]:
        """(flops, bytes, modeled) of one instruction."""
        base = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done") or base in _STRUCTURAL_OPS:
            return 0.0, 0.0, True
        if base in _CALLER_OPS or base in ("while", "conditional"):
            cm = _CALLS_RE.search(line)
            if base in ("while", "conditional"):
                unhandled[base] += 1   # body counted once, trips unknown
                cm = re.search(r"body=%?(?P<callee>[\w.$-]+)", line) or cm
            if cm is not None:
                f, b = comp_cost(cm.group("callee"), seen)
                return f, b, True
            return 0.0, 0.0, False
        if base in _COLLECTIVES:
            # traffic only; link bytes are parse_collectives' business
            return (0.0, float(_shape_bytes(shape) + _shape_bytes(operands)),
                    True)
        cost = _op_cost(base, shape, operands, line)
        if cost is None:
            unhandled[base] += 1
            return 0.0, 0.0, False
        return cost[0], cost[1], True

    ops: list[OpCost] = []
    for name, op, shape, operands, line in comps.get(entry, ()):
        base = op[:-6] if op.endswith("-start") else op
        if op.endswith("-done") or base in _STRUCTURAL_OPS:
            continue
        f, b, modeled = record_cost(op, shape, operands, line, frozenset())
        ops.append(OpCost(name=name, kind=base, flops=f, bytes_accessed=b,
                          modeled=modeled))
    return ModuleOps(ops=tuple(ops), unhandled=dict(unhandled))


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    bytes_by_op: dict[str, float] = defaultdict(float)
    count_by_op: dict[str, int] = defaultdict(int)
    seen_start: set[str] = set()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        # avoid double counting async -start/-done pairs: count -start, skip
        # -done (its result repeats the -start shape)
        if "-done(" in line:
            continue
        op = m.group("op")
        size = _shape_bytes(m.group("shape"))
        if "-start(" in line and op == "all-reduce":
            # all-reduce-start result is the final tensor shape; fine.
            pass
        n = _group_size(line, n_devices)
        if n <= 1:
            continue
        frac = (n - 1) / n
        if op == "all-reduce":
            traffic = 2.0 * size * frac
        elif op == "all-gather":
            traffic = size * frac
        elif op == "reduce-scatter":
            traffic = size * (n - 1)
        elif op == "all-to-all":
            traffic = size * frac
        else:  # collective-permute
            traffic = float(size)
        bytes_by_op[op] += traffic
        count_by_op[op] += 1
    return CollectiveStats(bytes_by_op=dict(bytes_by_op),
                           count_by_op=dict(count_by_op))
