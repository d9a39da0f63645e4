"""The flash-attention backward's share of its roofline in the traced
session: the least time its calls could take over the summed device time
of the backward kernels' events on the "XLA Ops" line (``flash_bwd_dkv*``
and ``flash_bwd_dq*``), one backward per ``flash_bwd_dq`` event.

A backward's least time is the larger of its FLOPs over the peak FLOP/s
and its bytes over the peak bandwidth: five causal matmuls against the
forward's two (S and dP recomputed once, dV, dK and dQ), so 2.5 times the
forward's FLOPs; Q, O, dO and dQ over the query heads and K, V, dK and dV
over the key/value heads, twice the forward's bytes. A program whose
attention backward runs no such kernel reports nothing."""

KERNEL = "flash_bwd_"
CALL = "flash_bwd_dq"
#: a backward's FLOPs and bytes as multiples of the forward's
FLOPS_RATIO = 2.5
BYTES_RATIO = 2.0


def read(run):
    flops = getattr(run.family, "flash_flops_per_call", None)
    moved = getattr(run.family, "flash_bytes_per_call", None)
    if run.trace is None or not flops or not moved:
        return None
    events = run.trace.events_named(*run.span,
                                    lambda name: name.startswith(KERNEL))
    calls = sum(1 for ev in events
                if str(ev.get("name", "")).startswith(CALL))
    seconds = sum(float(ev.get("dur", 0.0)) for ev in events) * 1e-6
    if not calls or seconds <= 0:
        return None
    least = max(FLOPS_RATIO * flops / run.peaks["flops"],
                BYTES_RATIO * moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * calls / seconds
