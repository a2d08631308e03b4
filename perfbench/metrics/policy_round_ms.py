"""Policy round: device milliseconds per traced epoch of the epoch
module's ops under the ``policy_round`` scope (the union of their
intervals: the serial client scan with selection, blend and publish, and
the Eq.-7 scorer inside it), averaged over the chips."""
import layers as L


def read(ctx):
    return L.scope_ms_per_epoch(ctx, ("policy_round",))
