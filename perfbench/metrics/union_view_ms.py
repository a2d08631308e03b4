"""Union view: device milliseconds per epoch of the cohort epoch
module's ops under the ``union_view`` scope (the scatter of every
cohort's heads and probe batches into the padded union pool, and the
projection of the blended heads back to each cohort's native nf),
averaged over the chips, per epoch the trace kept (the module's recorded
events, at most the epochs traced: see ``hetero_epoch_device_ms``).
None without scope maps, which only ``layers.extract`` provides."""
import devtrace as T
import layers as L


def read(ctx):
    ex, module = ctx["trace"], ctx["spec"].get("epoch_module")
    if not ex.get("op_scopes") or not module or not ctx["epochs"]:
        return None
    lo, hi = ctx["window"]
    per_chip = []
    for dev in ex["devices"].values():
        kept = T.clip([e for e in dev["modules"]
                       if e[0].split("(")[0] == module], lo, hi)
        if kept:
            ns = L.scope_ns(dev, ex["op_scopes"], module, lo, hi,
                            ("union_view",))
            per_chip.append(ns / min(len(kept), ctx["epochs"]))
    ms = sum(per_chip) / len(per_chip) * 1e-6 if per_chip else 0.0
    return ms or None
