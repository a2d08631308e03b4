"""Client-sharded federation: the fused epoch across a device mesh.

The batched engine (``repro.core.federation._fit_batched``) stacks all C
clients' state on a leading axis and scans the whole epoch inside one jitted
dispatch.  This module runs that SAME epoch body under
:func:`jax.shard_map` on a 1-D
:class:`jax.sharding.Mesh` with a ``clients`` axis, so the population is
*partitioned* across devices instead of living on one:

* **Device-local training.**  Per-client state (params, optimizer state,
  best-params, the epoch's R-batches, validation splits) is placed with a
  ``NamedSharding`` partitioning the leading client axis — derived from the
  ParamSpec schema via ``sharding.rules.FED_RULES``, which is what finally
  makes the schema-first sharding layer load-bearing for the federation
  path.  The vmapped Adam step and the per-epoch eval then run on each
  device's C/D-client block with no communication at all.

* **Explicit pool exchange, sharded scoring.**  The Eq.-7/Eq.-8 policy
  round is inherently sequential in the global client order (client i
  scores the heads already republished by clients < i in the same
  sub-round — the property that makes the batched engine
  selection-identical to the sequential oracle).  Each exchange round
  therefore ALL-GATHERS the pool candidates — the freshly trained heads
  plus that round's probe batches — along the ``clients`` axis (the probe
  gathers are issued before the train step so XLA may overlap them with
  its compute) and replays :func:`~repro.core.federation._policy_round_body`
  on the gathered view.  The sequential dependency lives in the pool
  CARRY, not in the scoring, so the expensive part — the Eq.-7 error
  matrix — is sharded: each device scores only its contiguous ``ns/D``
  chunk of the flattened pool against the scoring client's probes, takes
  a per-chunk argmin, and a tiny ``(D, nf)`` all-gather of (value, global
  index) pairs reduces to the global argmin
  (:func:`~repro.core.federation.merge_sharded_argmin` — ties to the
  LOWEST flat pool index, exactly ``jnp.argmin``'s first occurrence, the
  pinned tie-break rule).  Everything downstream of the argmin (blend,
  publish, aging, RNG fold-in) is O(pool) and runs replicated — same
  replicated PRNG key, same reduced index on every device — so the pool,
  its staleness ages, and the selection trace still end each round
  REPLICATED without a psum, and each device slices its own clients'
  blended heads back out.  Selection policies that need the full error
  matrix (not a pure argmin) all-gather their sharded chunks instead;
  policies that never score run replicated as before.  See docs/SCALING.md
  for the cost model (per-device O(C/D · pool) scoring + O(pool) gather
  replaces the old replicated O(C · pool) = O(C²) wall).

* **Bounded-staleness cadence.**  ``RoundSchedule(exchange_every=k)``
  exchanges on every k-th sub-round of an epoch (the segmented scan in
  ``federation._epoch_body``); intermediate rounds are pure local
  training — no gathers, no policy round, no pool aging.  k=1 is
  bit-identical to the historical per-sub-round exchange; k>1 rides the
  ``MaxStaleness`` PoolPolicy's bounded ages, which tick per EXCHANGE so
  ``max_age`` keeps its meaning in exchange rounds.  Per-epoch comms are
  accounted analytically in ``dispatch_stats["pool_bytes_gathered"]`` /
  ``["exchange_rounds"]``.

The mesh path is bit-compatible with the single-device engine: same scan
body, same key sequence, same selections (pinned by
``tests/test_mesh_federation.py`` both in-process and under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
:class:`~repro.core.federation.Federation` accepts ``mesh=`` and falls back
to the single-device path automatically when the mesh has one device.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import networks as N
from repro.core.policies import FederationPolicies
from repro.sharding import spec as S
from repro.sharding.rules import CLIENT_AXIS, FED_RULES


def make_mesh(axis_names=(CLIENT_AXIS,), devices=None) -> Mesh:
    """A 1-D device mesh for client-sharded federation.

    ``axis_names`` must be a 1-tuple naming the client axis (default
    ``("clients",)``, the name ``FED_RULES`` maps); ``devices`` defaults to
    every local device.  ``Federation(..., mesh=make_mesh())`` is the whole
    opt-in: with one device the engine falls back to the single-device
    fused path, with D devices the C clients are partitioned into C/D
    blocks (C must divide evenly — :func:`validate_mesh`).
    """
    if len(tuple(axis_names)) != 1:
        raise ValueError(
            f"client-sharded federation uses a 1-D mesh, got axes "
            f"{tuple(axis_names)} (shard other axes inside the model, not "
            f"across clients)")
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), tuple(axis_names))


def mesh_devices(mesh: Optional[Mesh]) -> int:
    """Device count of ``mesh`` (1 for None — the single-device path)."""
    return 1 if mesh is None else int(mesh.devices.size)


def client_axis(mesh: Mesh) -> str:
    """The mesh's client axis name (its only axis; validated)."""
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"client-sharded federation needs a 1-D mesh with a single "
            f"client axis; got axes {mesh.axis_names}")
    return mesh.axis_names[0]


def validate_mesh(mesh: Mesh, n_clients: int) -> None:
    """Raise unless ``mesh`` can host ``n_clients`` stacked clients: 1-D
    mesh, client count divisible by device count (each device owns a
    contiguous, equal block of clients — ragged blocks would silently
    change the all-gathered client order)."""
    client_axis(mesh)
    d = mesh_devices(mesh)
    if n_clients % d:
        raise ValueError(
            f"{n_clients} clients cannot shard evenly over {d} devices "
            f"(clients % devices must be 0); pad the population or use a "
            f"divisor-sized mesh")


def participation_multiple(mesh: Optional[Mesh]) -> int:
    """The granularity a sampled active set must respect on this mesh: the
    device count of a multi-device 1-D ``clients`` mesh, else 1.  The
    participation sampler rounds its per-wave counts to this multiple —
    and under a heterogeneous population the STRATIFIED sampler rounds
    each nf stratum to it, since every wave cohort must itself divide the
    device count (see :func:`validate_mesh` /
    ``cohorts.validate_cohort_mesh``)."""
    if mesh is None:
        return 1
    client_axis(mesh)
    return mesh_devices(mesh)


def param_pspecs(nf: int, w: int, n_clients: int, mesh: Mesh):
    """PartitionSpec tree for the stacked ``(C, ...)`` HFL parameter tree,
    derived from the ParamSpec schema: the per-client H/E/P schema is
    stacked on a logical ``clients`` axis and mapped through
    ``sharding.rules.FED_RULES`` — P(clients) on the leading axis of every
    leaf, everything else replicated."""
    schema = S.stack(N.hfl_schema(nf, w), n_clients,
                     axis_name=CLIENT_AXIS)
    rules = dict(FED_RULES)
    if client_axis(mesh) != CLIENT_AXIS:
        rules = {CLIENT_AXIS: client_axis(mesh)}
    return S.partition_specs(schema, rules, mesh)


def shard_fit_state(mesh: Mesh, nf: int, w: int, n_clients: int, *,
                    params, opt_state, pool_heads, pool_age, key,
                    best_val, best_params, rounds_data, val_data):
    """Place the batched engine's fit-state on the mesh and return it in the
    same order.  Per-client trees get the schema-derived client
    partitioning; the pool, its age vector and the PRNG key are replicated
    (every device carries the full pool — the policy round's invariant);
    the scan-stacked train data ``(n_sub, C, R, ...)`` partitions its
    SECOND axis."""
    validate_mesh(mesh, n_clients)
    axis = client_axis(mesh)
    pspecs = param_pspecs(nf, w, n_clients, mesh)
    named = lambda ps: NamedSharding(mesh, ps)
    clients_sh = named(P(axis))
    rep = named(P())
    params = jax.device_put(params, jax.tree_util.tree_map(named, pspecs))
    best_params = jax.device_put(
        best_params, jax.tree_util.tree_map(named, pspecs))
    opt_state = jax.device_put(opt_state, clients_sh)
    pool_heads = jax.device_put(pool_heads, rep)
    pool_age = jax.device_put(pool_age, rep)
    key = jax.device_put(key, rep)
    best_val = jax.device_put(best_val, clients_sh)
    rounds_data = tuple(jax.device_put(t, named(P(None, axis)))
                        for t in rounds_data)
    val_data = tuple(jax.device_put(t, clients_sh) for t in val_data)
    return (params, opt_state, pool_heads, pool_age, key, best_val,
            best_params, rounds_data, val_data)


def replicate(mesh: Mesh, x):
    """Put ``x`` on every device of ``mesh`` (the per-epoch activity mask)."""
    return jax.device_put(x, NamedSharding(mesh, P()))


@functools.lru_cache(maxsize=None)
def _make_mesh_epoch_fn(lr: float, nf: int, w: int,
                        policies: FederationPolicies, use_kernel: bool,
                        do_federate: bool, do_eval: bool, mesh: Mesh,
                        n_clients: int, exchange_every: int = 1,
                        admission=None, trust=None, telemetry=None):
    """Compile-cached client-sharded whole-epoch function — the mesh twin of
    ``federation._make_epoch_fn``: the SAME shared epoch computation
    (``federation._epoch_body``), same signature, same donation contract,
    wrapped in ``shard_map`` with the pool-exchange hooks injected:

    * train step + eval run on each device's local C/D-client block,
    * ``gather`` all-gathers (heads, probe batch) along the client axis so
      each exchange round replays the policy round on the global view
      (replicated PRNG key → identical computation on every device →
      the pool/ages/selections end the round replicated with no psum),
      ``shard=(axis, D)`` makes ``_policy_round_body`` score only each
      device's contiguous pool chunk and merge per-chunk argminima
      through a tiny (D, nf) gather, and ``local_rows`` slices the local
      clients' blended heads back out,
    * ``exchange_every`` segments the scan into k-round groups (see
      ``_epoch_body``) — the cadence is static, so every device traces the
      identical collective schedule (no ``lax.cond`` around collectives),
    * outputs: per-client values partitioned, pool/key/selections
      replicated.

    Cache key adds (w, mesh, n_clients, exchange_every) to the
    single-device key — the PartitionSpecs depend on the first three, and
    jit's per-shape cache sits underneath as before."""
    from repro.core.federation import _epoch_body

    axis = client_axis(mesh)
    c_loc = n_clients // mesh_devices(mesh)
    pspecs = param_pspecs(nf, w, n_clients, mesh)
    cl, rep, data = P(axis), P(), P(None, axis)

    def gather(tree):
        """Local client blocks -> the full (C, ...) tree in the global
        client order every device agrees on."""
        return jax.lax.all_gather(tree, axis, tiled=True)

    def local_rows(tree):
        """This device's C/D-client block of a gathered (C, ...) tree."""
        i0 = jax.lax.axis_index(axis) * c_loc
        return jax.tree_util.tree_map(
            lambda g: jax.lax.dynamic_slice_in_dim(g, i0, c_loc, 0), tree)

    epoch = _epoch_body(lr, nf, policies, use_kernel, do_federate, do_eval,
                        exchange_every=exchange_every, gather=gather,
                        local_rows=local_rows,
                        shard=(axis, mesh_devices(mesh)),
                        admission=admission, trust=trust,
                        telemetry=telemetry)
    out_specs = (pspecs, cl, rep, rep, rep, cl, pspecs,
                 cl if do_eval else None, rep)
    if admission is not None:
        # the admission guard's per-opportunity rejection mask is computed
        # from the replicated pool carry — replicated like the selections
        out_specs = out_specs + (rep,)
    in_specs = (pspecs, cl, rep, rep, rep, cl, pspecs,
                data, data, data, rep, cl, cl, cl)
    if trust is not None:
        # the trust layer's host-derived inputs (signature stack / mask
        # pair / dummy) and its per-round stats are replicated: the whole
        # publication tail runs inside the replicated policy round
        in_specs = in_specs + (rep,)
        out_specs = out_specs + (rep,)
    if telemetry is not None:
        # the in-graph per-round metrics series (selection histogram,
        # Eq.-7 score aggregates, staleness ages) is derived from the
        # replicated pool carry / psum-reduced sharded scores, so it comes
        # back replicated; a single ``rep`` covers the whole tuple (specs
        # are pytree prefixes, as for the trust stats pair above)
        out_specs = out_specs + (rep,)
    sharded = jax.shard_map(
        epoch, mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1, 2, 3, 4, 5, 6))
