"""Fused epoch: device milliseconds per epoch of the epoch program, the
summed duration of its module events on each chip (averaged over chips)
over the epochs traced.  The cell's file names the module."""
import devtrace as T


def read(ctx):
    name, ex = ctx["spec"].get("epoch_module"), ctx["trace"]
    if not name or not ctx["epochs"]:
        return None
    lo, hi = ctx["window"]
    ns = T.mean_over_devices(ex, lambda d: T.summed_ns(
        d["modules"], lo, hi, lambda e: e[0].split("(")[0] == name))
    return ns * 1e-6 / ctx["epochs"] if ns else None
