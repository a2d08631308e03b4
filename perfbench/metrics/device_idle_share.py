"""Device: the share of the traced window in which no op ran on a chip
(one minus the union of its op intervals over the window), averaged over
the chips."""
import devtrace as T


def read(ctx):
    ex = ctx["trace"]
    if not ex["devices"]:
        return None
    lo, hi = ctx["window"]
    busy = T.mean_over_devices(ex, lambda d: T.busy_ns(d["ops"], lo, hi))
    return 100.0 * (1.0 - busy / (hi - lo))
