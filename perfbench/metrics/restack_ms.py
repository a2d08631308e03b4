"""Fit driver: host milliseconds of the ``restack`` span (stacking the
population, pool and best parameters at a fit's start), on the trace's
clock, mean over the traced fits."""
import layers as L


def read(ctx):
    return L.span_reading(ctx, "restack", L.mean_or_none)
