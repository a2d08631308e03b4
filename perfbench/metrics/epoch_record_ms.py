"""Fit driver: the median over the traced epochs of the ``record`` span
(selection bookkeeping, histories and the round series, after the
epoch's results are read back), on the trace's clock."""
import layers as L


def read(ctx):
    return L.span_reading(ctx, "record", L.median_or_none)
