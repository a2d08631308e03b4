"""Eq.-7 scoring: device milliseconds per traced epoch of the epoch
module's ops under the ``eq7_score`` scope (the vmap scorer or the
``pool_mlp`` kernel); what is left of ``policy_round_ms`` is the client
scan's own serial cost."""
import layers as L


def read(ctx):
    return L.scope_ms_per_epoch(ctx, ("eq7_score",))
