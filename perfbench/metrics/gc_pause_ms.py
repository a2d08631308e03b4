"""Fit driver: host milliseconds of Python's generation-2 garbage
collections in the traced window, summed over the program's ``gc`` spans
(0 where none ran)."""
import layers as L


def read(ctx):
    return L.span_reading(ctx, "gc", sum)
