"""Local train step: device milliseconds per traced epoch of the epoch
module's ops under the ``train_step`` scope (the vmapped Adam step of
every sub-round)."""
import layers as L


def read(ctx):
    return L.scope_ms_per_epoch(ctx, ("train_step",))
