"""The Pallas SSD chunk-scan kernel's share of its roofline in the traced
session: the least time its calls could take (the larger of their FLOPs
over the peak FLOP/s and their bytes over the peak bandwidth; the masked
Q x Q terms count their useful half) over the summed device time of the
kernel's events on the "XLA Ops" line, one event per call. The forward
and its recomputation under remat both call the kernel; the backward does
not (it differentiates the reference scan)."""

KERNEL = "ssd_chunk_scan"


def read(run):
    flops = getattr(run.family, "ssd_flops_per_call", None)
    moved = getattr(run.family, "ssd_bytes_per_call", None)
    if run.trace is None or not flops or not moved:
        return None
    events = run.trace.events_named(*run.span,
                                    lambda name: name.startswith(KERNEL))
    seconds = sum(float(ev.get("dur", 0.0)) for ev in events) * 1e-6
    if not events or seconds <= 0:
        return None
    least = max(flops / run.peaks["flops"],
                moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(events) / seconds
