"""Fit driver: the 95th percentile of the traced fit's epoch intervals on
the host clock (marks: the fit's start and every ``on_epoch_end``; the
fit's return closes the last), so the fit's re-stacking and write-back,
garbage collections and host stalls between epochs all show in its tail."""
import statistics


def read(ctx):
    v = ctx["epoch_intervals_s"]
    if len(v) < 2:
        return None
    return 1e3 * statistics.quantiles(v, n=20, method="inclusive")[-1]
