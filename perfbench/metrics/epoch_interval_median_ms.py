"""Fit driver: the median of the traced fit's epoch intervals on the host
clock: one epoch's device program plus the fit loop's host work around it,
a steadier statistic beside ``epoch_interval_p95_ms``."""
import statistics


def read(ctx):
    v = ctx["epoch_intervals_s"]
    return 1e3 * statistics.median(v) if v else None
