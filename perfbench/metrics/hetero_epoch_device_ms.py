"""Cohort epoch: device milliseconds per epoch of the cohort engine's
fused epoch program, the module ``jit_hetero_epoch``, averaged over the
epochs the trace kept and over the chips.

The profiler keeps a bounded number of device events: a 50-epoch trace
of 48 Table-4 sites (about 200,000 op events per epoch) keeps about the
first 30 epochs' events and drops the rest.  So the module's time is
divided by its recorded events (one per fused epoch), never more than
the epochs traced, and not by the epochs traced.  None where no such
module ran (a homogeneous population runs ``jit_epoch``)."""
import devtrace as T

MODULE = "jit_hetero_epoch"


def read(ctx):
    if not ctx["epochs"]:
        return None
    lo, hi = ctx["window"]
    per_chip = []
    for dev in ctx["trace"]["devices"].values():
        kept = T.clip([e for e in dev["modules"]
                       if e[0].split("(")[0] == MODULE], lo, hi)
        if kept:
            per_chip.append(sum(t - s for s, t in kept)
                            / min(len(kept), ctx["epochs"]))
    return sum(per_chip) / len(per_chip) * 1e-6 if per_chip else None
