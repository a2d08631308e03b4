"""Fit driver: host milliseconds of the ``results`` span
(``Federation.results()``, the ``test_pass`` inside it included), on the
trace's clock, mean over the traced fits."""
import layers as L


def read(ctx):
    return L.span_reading(ctx, "results", L.mean_or_none)
