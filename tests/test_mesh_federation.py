"""Client-sharded federation parity: the fused epoch under a `clients` mesh
must be selection- and value-identical to the single-device engine, on every
device count.  In-process tests build a mesh over whatever devices the host
exposes (1 in plain tier-1, 4 under the CI mesh-parity step's
``XLA_FLAGS=--xla_force_host_platform_device_count=4``); the subprocess
acceptance test ALWAYS exercises a genuine 4-device mesh with a 32-client
population, including a bit-exact save/restore round-trip, regardless of the
parent's device count (jax locks the host device count at first init)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import mesh_federation as MF
from repro.core.federation import Callback, Federation
from repro.core.hfl import FederatedClient, HFLConfig

ROOT = Path(__file__).resolve().parent.parent


def _mk_clients(cfg, C=8, nf=2, n=40, seed0=100):
    out = []
    for i in range(C):
        rng = np.random.default_rng(seed0 + i)
        mk = lambda m: (rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
                        rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
                        rng.normal(size=m).astype(np.float32))
        out.append(FederatedClient(f"c{i}", nf, cfg, mk(n), mk(40), mk(40),
                                   jax.random.PRNGKey(i)))
    return out


class _RoundCounter(Callback):
    def __init__(self):
        self.rounds = []

    def on_round(self, fed, epoch, rnd):
        self.rounds.append((epoch, rnd))


def _assert_identical(h_a, h_b, *, exact_val=True):
    assert set(h_a) == set(h_b)
    for name in h_a:
        assert h_a[name]["selections"] == h_b[name]["selections"]
        assert h_a[name]["rounds"] == h_b[name]["rounds"]
        if exact_val:
            np.testing.assert_array_equal(h_a[name]["val"], h_b[name]["val"])
        else:
            np.testing.assert_allclose(h_a[name]["val"], h_b[name]["val"],
                                       rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Mesh construction + validation
# ---------------------------------------------------------------------------

def test_make_mesh_defaults_to_local_devices():
    mesh = MF.make_mesh()
    assert mesh.axis_names == ("clients",)
    assert MF.mesh_devices(mesh) == len(jax.devices())


def test_make_mesh_rejects_multi_axis():
    with pytest.raises(ValueError, match="1-D mesh"):
        MF.make_mesh(("clients", "model"))


def test_mesh_requires_batched_engine():
    cfg = HFLConfig(mode="always", epochs=1, R=20)
    with pytest.raises(ValueError, match="engine='batched'"):
        Federation(_mk_clients(cfg, C=2), cfg, engine="sequential",
                   mesh=MF.make_mesh())


def test_mesh_rejects_non_divisible_population():
    if len(jax.devices()) < 2:
        pytest.skip("divisibility only binds on a multi-device mesh")
    cfg = HFLConfig(mode="always", epochs=1, R=20)
    C = len(jax.devices()) + 1
    with pytest.raises(ValueError, match="shard evenly"):
        Federation(_mk_clients(cfg, C=C), cfg, engine="batched",
                   mesh=MF.make_mesh())


# ---------------------------------------------------------------------------
# In-process parity over the local device count (1 in tier-1, 4 in the CI
# mesh step — same assertions either way)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ("always", "hfl"))
def test_mesh_matches_no_mesh(mode):
    """mesh= must not change a single number: identical selections, round
    counts, and bit-identical validation histories vs the plain batched
    engine, whatever the local device count."""
    cfg = HFLConfig(mode=mode, epochs=4, R=20, patience=2)
    h_plain = Federation(_mk_clients(cfg), cfg, engine="batched").fit()
    fed = Federation(_mk_clients(cfg), cfg, engine="batched",
                     mesh=MF.make_mesh())
    h_mesh = fed.fit()
    assert fed.dispatch_stats["path"] == "fused"
    assert fed.dispatch_stats["devices"] == \
        (len(jax.devices()) if len(jax.devices()) > 1 else 1)
    assert fed.dispatch_stats["dispatches_per_epoch"] == 1.0
    _assert_identical(h_plain, h_mesh)


def test_single_device_mesh_falls_back():
    """A one-device mesh takes the plain single-device path (no shard_map),
    and is — trivially — selection-identical to running without a mesh."""
    cfg = HFLConfig(mode="always", epochs=2, R=20)
    mesh1 = MF.make_mesh(devices=jax.devices()[:1])
    fed = Federation(_mk_clients(cfg, C=3), cfg, engine="batched",
                     mesh=mesh1)
    assert fed._exec_mesh() is None
    h_mesh = fed.fit()
    assert fed.dispatch_stats["devices"] == 1
    h_plain = Federation(_mk_clients(cfg, C=3), cfg, engine="batched").fit()
    _assert_identical(h_plain, h_mesh)


def test_mesh_chunked_path_parity():
    """Per-round callbacks force the chunked path under a mesh too — same
    compiled sharded body per sub-round, every on_round fired, identical
    results."""
    cfg = HFLConfig(mode="always", epochs=3, R=20)
    h_plain = Federation(_mk_clients(cfg), cfg, engine="batched").fit()
    counter = _RoundCounter()
    fed = Federation(_mk_clients(cfg), cfg, engine="batched",
                     mesh=MF.make_mesh(), callbacks=[counter])
    h_mesh = fed.fit()
    assert fed.dispatch_stats["path"] == "chunked"
    assert counter.rounds == [(e, r) for e in range(3) for r in range(2)]
    _assert_identical(h_plain, h_mesh)


def test_mesh_save_restore_bit_identical(tmp_path):
    cfg = HFLConfig(mode="hfl", epochs=6, R=20, patience=2)
    mesh = MF.make_mesh()
    h_straight = Federation(_mk_clients(cfg), cfg, engine="batched",
                            mesh=mesh).fit()
    fed = Federation(_mk_clients(cfg), cfg, engine="batched", mesh=mesh)
    fed.fit(epochs=3)
    fed.save(tmp_path / "ck")
    manifest = json.loads((tmp_path / "ck" / "manifest.json").read_text())
    assert manifest["mesh_devices"] == MF.mesh_devices(mesh)
    # checkpoints are mesh-agnostic: resume sharded AND unsharded
    h_mesh = Federation.restore(tmp_path / "ck", _mk_clients(cfg),
                                mesh=mesh).fit()
    h_plain = Federation.restore(tmp_path / "ck", _mk_clients(cfg)).fit()
    for h_resumed in (h_mesh, h_plain):
        for name in h_straight:
            assert h_straight[name]["val"] == h_resumed[name]["val"]
            assert h_straight[name]["selections"] == \
                h_resumed[name]["selections"]
            assert h_straight[name]["best_val"] == h_resumed[name]["best_val"]


def test_schema_derived_pspecs_partition_client_axis():
    """The ParamSpec schema -> FED_RULES -> PartitionSpec pipeline puts the
    `clients` mesh axis on the leading (stacked-client) dimension of every
    parameter leaf and nothing else — the schema layer is what decides the
    federation sharding."""
    from jax.sharding import PartitionSpec as P
    mesh = MF.make_mesh()
    specs = MF.param_pspecs(nf=3, w=4, n_clients=len(jax.devices()) * 2,
                            mesh=mesh)
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert leaves, "schema produced no PartitionSpecs"
    for ps in leaves:
        assert isinstance(ps, P)
        assert tuple(ps) in ((MF.CLIENT_AXIS,), ()), ps


# ---------------------------------------------------------------------------
# Acceptance pin: 32 clients on a forced 4-device mesh (subprocess — jax
# locks the host platform device count at first init)
# ---------------------------------------------------------------------------

_SUBPROCESS = r"""
import collections, gc, json, os, sys, tempfile
import numpy as np
import jax
assert jax.device_count() == 4, jax.devices()
from repro.core import mesh_federation as MF
from repro.core.federation import Callback, Federation
from repro.core.hfl import FederatedClient, HFLConfig

class LiveBytes(Callback):
    # live-array bytes per device while the mesh fit's state is live
    def on_epoch_end(self, fed, epoch, val, active):
        per = collections.Counter()
        for a in jax.live_arrays():
            for s in a.addressable_shards:
                per[s.device] += s.data.nbytes
        self.per_device = [per[d] for d in jax.devices()]

def mk_clients(cfg, C=32, nf=2, n=40, seed0=100):
    out = []
    for i in range(C):
        rng = np.random.default_rng(seed0 + i)
        mk = lambda m: (rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
                        rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
                        rng.normal(size=m).astype(np.float32))
        out.append(FederatedClient(f"h{i:03d}", nf, cfg, mk(n), mk(40),
                                   mk(40), jax.random.PRNGKey(i)))
    return out

cfg = HFLConfig(mode="always", epochs=3, R=20)
mesh = MF.make_mesh()

h_oracle = Federation(mk_clients(cfg), cfg, engine="batched").fit()
gc.collect()    # the oracle's arrays (a fit leaves a cycle via its sync)
live = LiveBytes()
fed = Federation(mk_clients(cfg), cfg, engine="batched", mesh=mesh,
                 callbacks=[live])
h_mesh = fed.fit()
# no array of the fit sits whole on device 0: every device holds the same
# share of the partitioned state plus the replicated pool
balanced = (max(live.per_device) - min(live.per_device)
            <= 0.01 * max(live.per_device))
expect = {
    "engine": "batched", "path": "fused", "devices": 4, "cohorts": 1,
    "epochs": 3, "dispatches": 3, "dispatches_per_epoch": 1.0,
    "exchange_every": 1,
}
assert {k: fed.dispatch_stats[k] for k in expect} == expect, \
    fed.dispatch_stats
assert fed.dispatch_stats["pool_bytes_gathered"] > 0, fed.dispatch_stats
sel_identical = all(h_oracle[n]["selections"] == h_mesh[n]["selections"]
                    for n in h_oracle)
val_identical = all(h_oracle[n]["val"] == h_mesh[n]["val"]
                    for n in h_oracle)

with tempfile.TemporaryDirectory() as d:
    ck = os.path.join(d, "ck")
    h_straight = Federation(mk_clients(cfg), cfg, engine="batched",
                            mesh=mesh).fit()
    fed2 = Federation(mk_clients(cfg), cfg, engine="batched", mesh=mesh)
    fed2.fit(epochs=1)
    fed2.save(ck)
    h_resumed = Federation.restore(ck, mk_clients(cfg), mesh=mesh).fit()
    ck_identical = all(
        h_straight[n]["val"] == h_resumed[n]["val"]
        and h_straight[n]["selections"] == h_resumed[n]["selections"]
        and h_straight[n]["best_val"] == h_resumed[n]["best_val"]
        for n in h_straight)

print("RESULT " + json.dumps({"sel_identical": sel_identical,
                              "val_identical": val_identical,
                              "ck_identical": ck_identical,
                              "bytes_balanced": balanced}))
"""


def _run_forced_devices(script: str, n_devices: int) -> dict:
    """Run ``script`` in a subprocess with a forced n-device CPU host (jax
    locks the host platform device count at first init) and return its
    RESULT json."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout
    return json.loads(line[-1][len("RESULT "):])


def test_32_clients_on_forced_4_device_mesh():
    """ISSUE 4 acceptance: with XLA_FLAGS=--xla_force_host_platform_device_
    count=4, a 32-client population runs the fused epoch on a 4-device
    `clients` mesh with selections identical to the single-device oracle,
    Federation.save/restore round-trips the sharded state bit-exactly, and
    live bytes are equal on every device during the fit (the clients' own
    copies are not left whole on device 0)."""
    res = _run_forced_devices(_SUBPROCESS, 4)
    assert res == {"sel_identical": True, "val_identical": True,
                   "ck_identical": True, "bytes_balanced": True}


# ---------------------------------------------------------------------------
# Acceptance pin: bounded-staleness cadence on a forced 8-device mesh —
# comms counters shrink with exchange_every, and a checkpoint written from
# the 8-device mesh restores bit-identically onto one device
# ---------------------------------------------------------------------------

_SUBPROCESS_8 = r"""
import json, os, sys, tempfile
import numpy as np
import jax
assert jax.device_count() == 8, jax.devices()
from repro.core import mesh_federation as MF
from repro.core.federation import Federation, RoundSchedule
from repro.core.hfl import FederatedClient, HFLConfig

def mk_clients(cfg, C=16, nf=2, n=60, seed0=100):
    out = []
    for i in range(C):
        rng = np.random.default_rng(seed0 + i)
        mk = lambda m: (rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
                        rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
                        rng.normal(size=m).astype(np.float32))
        out.append(FederatedClient(f"h{i:03d}", nf, cfg, mk(n), mk(40),
                                   mk(40), jax.random.PRNGKey(i)))
    return out

cfg = HFLConfig(mode="always", epochs=2, R=20)   # n=60 -> 3 sub-rounds
mesh = MF.make_mesh()
res, stats = {}, {}
for k in (1, 2):
    sched = RoundSchedule(cfg.epochs, cfg.R, exchange_every=k)
    fed = Federation(mk_clients(cfg), cfg, engine="batched",
                     schedule=sched, mesh=mesh)
    h_mesh = fed.fit()
    stats[k] = fed.dispatch_stats
    h_or = Federation(mk_clients(cfg), cfg, engine="batched",
                      schedule=sched).fit()
    res[f"sel_identical_k{k}"] = all(
        h_or[n]["selections"] == h_mesh[n]["selections"] for n in h_or)
    res[f"rounds_identical_k{k}"] = all(
        h_or[n]["rounds"] == h_mesh[n]["rounds"] for n in h_or)
    res[f"val_close_k{k}"] = all(
        np.allclose(h_or[n]["val"], h_mesh[n]["val"], rtol=1e-6, atol=1e-7)
        for n in h_or)
res["devices_8"] = stats[1]["devices"] == 8
# comms counters: k=2 exchanges 1 of 3 sub-rounds per epoch (vs 3) and
# gathers proportionally fewer bytes
res["exchange_rounds"] = [stats[1]["exchange_rounds"],
                          stats[2]["exchange_rounds"]]
res["counters_shrink"] = (
    stats[2]["exchange_rounds"] < stats[1]["exchange_rounds"]
    and 0 < stats[2]["pool_bytes_gathered"] < stats[1]["pool_bytes_gathered"]
    and stats[1]["exchange_rounds"] == cfg.epochs * 3
    and stats[2]["exchange_rounds"] == cfg.epochs * 1)

# 8-device save -> 1-device (no-mesh) restore, bit-identical continuation
sched = RoundSchedule(cfg.epochs, cfg.R, exchange_every=2)
with tempfile.TemporaryDirectory() as d:
    ck = os.path.join(d, "ck")
    h_straight = Federation(mk_clients(cfg), cfg, engine="batched",
                            schedule=sched, mesh=mesh).fit()
    fed2 = Federation(mk_clients(cfg), cfg, engine="batched",
                      schedule=sched, mesh=mesh)
    fed2.fit(epochs=1)
    fed2.save(ck)
    manifest = json.load(open(os.path.join(ck, "manifest.json")))
    restored = Federation.restore(ck, mk_clients(cfg))   # no mesh: 1 device
    h_resumed = restored.fit()
    res["manifest_cadence"] = (
        manifest["schedule"]["exchange_every"] == 2
        and manifest["mesh_devices"] == 8
        and restored.schedule.exchange_every == 2)
    res["ck_identical"] = all(
        h_straight[n]["val"] == h_resumed[n]["val"]
        and h_straight[n]["selections"] == h_resumed[n]["selections"]
        and h_straight[n]["best_val"] == h_resumed[n]["best_val"]
        for n in h_straight)

print("RESULT " + json.dumps(res))
"""


def test_cadence_comms_and_restore_on_forced_8_device_mesh():
    """ISSUE 6 acceptance: on a forced 8-virtual-device mesh, dispatch_stats
    comms counters shrink as exchange_every grows (fewer exchange rounds,
    fewer pool bytes gathered), selections stay identical to the 1-device
    oracle at every cadence, and a checkpoint saved from the 8-device mesh
    restores bit-identically onto a single device."""
    res = _run_forced_devices(_SUBPROCESS_8, 8)
    assert res["devices_8"], res
    assert res["counters_shrink"], res
    assert res["manifest_cadence"], res
    assert res["ck_identical"], res
    for k in (1, 2):
        assert res[f"sel_identical_k{k}"], res
        assert res[f"rounds_identical_k{k}"], res
        assert res[f"val_close_k{k}"], res
