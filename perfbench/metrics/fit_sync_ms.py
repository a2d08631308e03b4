"""Fit driver: host milliseconds from a fit's last epoch end to the return
of ``fit()`` (the write-back ``sync()``, ``results()`` and the test pass),
mean over the traced fits."""


def read(ctx):
    v = ctx["fit_sync_s"]
    return 1e3 * sum(v) / len(v) if v else None
