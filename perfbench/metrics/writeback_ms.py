"""Fit driver: host milliseconds of the ``writeback`` span (``sync()``:
the stacked state written back into the clients and the pool), on the
trace's clock, mean over the traced fits."""
import layers as L


def read(ctx):
    return L.span_reading(ctx, "writeback", L.mean_or_none)
