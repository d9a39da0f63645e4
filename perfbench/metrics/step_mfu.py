"""The whole training step's share of the bf16 peak in the traced session:
the FLOPs a step requires (no remat recompute) times the step program's
runs on the "XLA Modules" line, over their summed device time."""

MODULE = "jit_train_step"


def read(run):
    flops = getattr(run.family, "step_flops", None)
    if run.trace is None or not flops:
        return None
    events = run.trace.events_named(*run.span,
                                    lambda name: name.startswith(MODULE),
                                    line="XLA Modules")
    seconds = sum(float(ev.get("dur", 0.0)) for ev in events) * 1e-6
    if not events or seconds <= 0:
        return None
    return 100.0 * flops * len(events) / seconds / run.peaks["flops"]
