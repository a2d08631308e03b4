"""Heterogeneous cohort engine: mixed-nf, ragged-length populations on the
batched fast path must reproduce the sequential oracle — identical
selections and round counts, validation histories equal to float precision
(the discrete decisions are exact; values can differ in the last ulp
because the cohort-stacked train step batches its matmuls differently from
the oracle's per-client steps, the same tolerance story as the homogeneous
engine's oracle-parity pins).  Within the batched family (fused vs chunked,
save/restore) results are bit-identical.

The mesh tests run over whatever devices the host exposes (1 in plain
tier-1 — the fallback path; 4 under the CI cohort-parity step's
``XLA_FLAGS=--xla_force_host_platform_device_count=4``); the subprocess
acceptance test ALWAYS exercises a genuine 4-device mesh against a mixed
population, regardless of the parent's device count."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cohorts as CO
from repro.core import mesh_federation as MF
from repro.core.federation import Callback, Federation, _selection_lut
from repro.core.hfl import FederatedClient, HFLConfig

ROOT = Path(__file__).resolve().parent.parent

# (nf, n_train) per client: 3 cohorts — two multi-client, one singleton —
# with ragged train lengths (47 also exercises the partial-batch drop)
MIXED = ((3, 60), (2, 40), (3, 60), (4, 47), (2, 40))


def _mk_clients(cfg, spec=MIXED, seed0=100, n_eval=30):
    out = []
    for i, (nf, n) in enumerate(spec):
        rng = np.random.default_rng(seed0 + i)
        mk = lambda m, nf=nf: (
            rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
            rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
            rng.normal(size=m).astype(np.float32))
        out.append(FederatedClient(f"c{i}", nf, cfg, mk(n), mk(n_eval),
                                   mk(n_eval), jax.random.PRNGKey(i)))
    return out


def _fit_quiet(fed, **kw):
    with pytest.warns(UserWarning, match="partial batch"):
        return fed.fit(**kw)


class _RoundCounter(Callback):
    def __init__(self):
        self.rounds = []

    def on_round(self, fed, epoch, rnd):
        self.rounds.append((epoch, rnd))


def _assert_oracle_parity(h_seq, h_bat, *, rtol=1e-6, atol=1e-6):
    assert set(h_seq) == set(h_bat)
    for name in h_seq:
        assert h_seq[name]["selections"] == h_bat[name]["selections"]
        assert h_seq[name]["rounds"] == h_bat[name]["rounds"]
        np.testing.assert_allclose(h_seq[name]["val"], h_bat[name]["val"],
                                   rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Planning
# ---------------------------------------------------------------------------

def test_plan_groups_by_nf_and_shapes():
    cfg = HFLConfig(mode="always", epochs=1, R=20)
    plan = CO.plan_cohorts(_mk_clients(cfg), R=20)
    assert len(plan.cohorts) == 3
    assert [(co.nf, co.members, co.n_sub) for co in plan.cohorts] == [
        (3, (0, 2), 3), (2, (1, 4), 2), (4, (3,), 2)]
    assert plan.C == 5 and plan.max_nf == 4 and plan.n_sub_max == 3
    assert plan.nfs == (3, 2, 3, 4, 2)
    assert plan.n_subs == (3, 2, 3, 2, 2)


def test_plan_feat_valid_mask():
    cfg = HFLConfig(mode="always", epochs=1, R=20)
    fv = CO.plan_cohorts(_mk_clients(cfg), R=20).feat_valid()
    assert fv.shape == (5, 4)
    assert fv.sum(axis=1).tolist() == [3, 2, 3, 4, 2]
    assert fv[1].tolist() == [True, True, False, False]


def test_plan_same_nf_different_lengths_split_cohorts():
    """Same nf but ragged lengths cannot stack — separate cohorts."""
    cfg = HFLConfig(mode="always", epochs=1, R=20)
    plan = CO.plan_cohorts(_mk_clients(cfg, ((3, 40), (3, 60), (3, 40))),
                           R=20)
    assert [(co.nf, co.members) for co in plan.cohorts] == [
        (3, (0, 2)), (3, (1,))]


def test_plan_rejects_mixed_head_width():
    cfg_a = HFLConfig(mode="always", epochs=1, R=20, w=3)
    cfg_b = HFLConfig(mode="always", epochs=1, R=20, w=4)
    clients = _mk_clients(cfg_a, ((2, 40),)) + [
        FederatedClient("cw", 2, cfg_b,
                        *(_mk_clients(cfg_b, ((2, 40),))[0].train,) * 3,
                        jax.random.PRNGKey(9))]
    with pytest.raises(ValueError, match="head widths"):
        CO.plan_cohorts(clients, R=20)


def test_hetero_lut_matches_homogeneous_lut_on_uniform_nf():
    """With uniform nf the padded LUT must degenerate to the homogeneous
    engine's rectangular one."""
    names = ["b", "a", "c"]
    np.testing.assert_array_equal(
        CO.hetero_selection_lut(names, [3, 3, 3], 3),
        _selection_lut(names, 3))


def test_hetero_lut_mixed_nf():
    """Padded flat indices map to the oracle's sorted-by-(name, feature)
    foreign positions, with ragged per-client widths."""
    names, nfs = ["t", "a", "z"], [2, 3, 1]   # selector "t": foreign = a, z
    lut = CO.hetero_selection_lut(names, nfs, max_nf=3)
    # for "t" (row 0): a's 3 features rank 0..2, z's single feature rank 3
    assert lut[0, 1 * 3:2 * 3].tolist() == [0, 1, 2]
    assert lut[0, 2 * 3:3 * 3].tolist() == [3, -1, -1]
    assert lut[0, 0:3].tolist() == [-1, -1, -1]          # own rows
    # for "a" (row 1): t's 2 features rank 0..1, z's one ranks 2
    assert lut[1, 0:3].tolist() == [0, 1, -1]
    assert lut[1, 2 * 3:3 * 3].tolist() == [2, -1, -1]


# ---------------------------------------------------------------------------
# Oracle parity (the acceptance surface)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ("always", "hfl"))
def test_cohorted_matches_sequential_oracle(mode):
    """Mixed-nf ragged population: the cohort engine's selections and round
    counts are identical to the sequential oracle, validation histories
    equal to float precision, via ONE fused dispatch per epoch."""
    cfg = HFLConfig(mode=mode, epochs=5, R=20, patience=2)
    h_seq = _fit_quiet(Federation(_mk_clients(cfg), cfg,
                                  engine="sequential"))
    fed = Federation(_mk_clients(cfg), cfg, engine="batched")
    h_bat = _fit_quiet(fed)
    st = fed.dispatch_stats
    assert st["path"] == "fused" and st["cohorts"] == 3
    assert st["dispatches_per_epoch"] == 1.0
    assert [pc["clients"] for pc in st["per_cohort"]] == [2, 2, 1]
    assert [pc["sub_rounds"] for pc in st["per_cohort"]] == [3, 2, 2]
    _assert_oracle_parity(h_seq, h_bat)
    if mode == "always":   # every client federates in every live sub-round
        assert [h_bat[f"c{i}"]["rounds"] for i in range(5)] == \
            [15, 10, 15, 10, 10]


def test_fully_ragged_singleton_cohorts_match_oracle():
    """Every client its own cohort (all lengths distinct): still correct,
    still one dispatch per epoch."""
    spec = ((2, 40), (3, 60), (4, 80), (2, 55))
    cfg = HFLConfig(mode="always", epochs=3, R=20)
    h_seq = _fit_quiet(Federation(_mk_clients(cfg, spec), cfg,
                                  engine="sequential"))
    fed = Federation(_mk_clients(cfg, spec), cfg, engine="batched")
    h_bat = _fit_quiet(fed)
    assert fed.dispatch_stats["cohorts"] == 4
    assert fed.dispatch_stats["dispatches_per_epoch"] == 1.0
    _assert_oracle_parity(h_seq, h_bat)


def test_bounded_pool_staleness_matches_oracle():
    """MaxStaleness on a ragged population exercises the subtle staleness
    clock: the pool ages once per sub-round in which federation could run
    among still-live clients, and exhausted clients' entries go stale."""
    from repro.core.policies import (AlphaBlend, ArgminSelection,
                                     FederationPolicies, MaxStaleness,
                                     PlateauSwitch)
    pol = FederationPolicies(switch=PlateauSwitch(patience=1),
                             selection=ArgminSelection(),
                             transfer=AlphaBlend(alpha=0.2),
                             pool=MaxStaleness(max_age=2))
    cfg = HFLConfig(mode="hfl", epochs=6, R=20, patience=1)
    h_seq = _fit_quiet(Federation(_mk_clients(cfg), cfg, policies=pol,
                                  engine="sequential"))
    h_bat = _fit_quiet(Federation(_mk_clients(cfg), cfg, policies=pol,
                                  engine="batched"))
    _assert_oracle_parity(h_seq, h_bat)


def test_cohorted_kernel_path_matches_vmap_path():
    """use_pool_kernel=True sweeps the padded union pool through the Pallas
    kernel (zero-padded invalid rows masked to inf) — selections must be
    identical to the vmap fallback."""
    import dataclasses
    cfg_v = HFLConfig(mode="always", epochs=2, R=20)
    cfg_k = dataclasses.replace(cfg_v, use_pool_kernel=True)
    h_v = _fit_quiet(Federation(_mk_clients(cfg_v), cfg_v, engine="batched"))
    h_k = _fit_quiet(Federation(_mk_clients(cfg_k), cfg_k, engine="batched"))
    for name in h_v:
        assert h_v[name]["selections"] == h_k[name]["selections"]


# ---------------------------------------------------------------------------
# Fused vs chunked; callbacks
# ---------------------------------------------------------------------------

def test_cohorted_fused_equals_chunked_bit_identical():
    """Per-round callbacks force the chunked path — same compiled body per
    sub-round, every on_round fired (n_sub_max per epoch), results
    BIT-identical to the fused path."""
    cfg = HFLConfig(mode="always", epochs=3, R=20)
    h_fused = _fit_quiet(Federation(_mk_clients(cfg), cfg,
                                    engine="batched"))
    counter = _RoundCounter()
    fed = Federation(_mk_clients(cfg), cfg, engine="batched",
                     callbacks=[counter])
    h_chunk = _fit_quiet(fed)
    assert fed.dispatch_stats["path"] == "chunked"
    assert fed.dispatch_stats["dispatches_per_epoch"] == 3.0   # n_sub_max
    assert counter.rounds == [(e, r) for e in range(3) for r in range(3)]
    for name in h_fused:
        assert h_fused[name]["selections"] == h_chunk[name]["selections"]
        assert h_fused[name]["rounds"] == h_chunk[name]["rounds"]
        np.testing.assert_array_equal(h_fused[name]["val"],
                                      h_chunk[name]["val"])


# ---------------------------------------------------------------------------
# Save/restore through the cohort path
# ---------------------------------------------------------------------------

def test_cohorted_save_restore_bit_identical(tmp_path):
    cfg = HFLConfig(mode="hfl", epochs=6, R=20, patience=2)
    h_straight = _fit_quiet(Federation(_mk_clients(cfg), cfg,
                                       engine="batched"))
    fed = Federation(_mk_clients(cfg), cfg, engine="batched")
    _fit_quiet(fed, epochs=3)
    fed.save(tmp_path / "ck")
    h_resumed = _fit_quiet(Federation.restore(tmp_path / "ck",
                                              _mk_clients(cfg)))
    for name in h_straight:
        assert h_straight[name]["val"] == h_resumed[name]["val"]
        assert h_straight[name]["selections"] == \
            h_resumed[name]["selections"]
        assert h_straight[name]["best_val"] == h_resumed[name]["best_val"]


# ---------------------------------------------------------------------------
# Mesh (in-process over the local device count; 4 devices in the CI step)
# ---------------------------------------------------------------------------

# 2 cohorts x 4 clients: shards evenly over 1, 2 or 4 devices
MESH_SPEC = ((2, 40), (3, 60), (2, 40), (3, 60),
             (2, 40), (3, 60), (2, 40), (3, 60))


def test_cohorted_mesh_matches_no_mesh():
    """mesh= on a heterogeneous population: identical selections and round
    counts, values within float precision, whatever the local device
    count (per-cohort client blocks batch their train matmuls differently,
    so the last ulp can move — selections cannot)."""
    cfg = HFLConfig(mode="always", epochs=3, R=20)
    h_plain = Federation(_mk_clients(cfg, MESH_SPEC), cfg,
                         engine="batched").fit()
    fed = Federation(_mk_clients(cfg, MESH_SPEC), cfg, engine="batched",
                     mesh=MF.make_mesh())
    h_mesh = fed.fit()
    st = fed.dispatch_stats
    assert st["cohorts"] == 2 and st["path"] == "fused"
    assert st["devices"] == (len(jax.devices())
                             if len(jax.devices()) > 1 else 1)
    _assert_oracle_parity(h_plain, h_mesh, rtol=1e-6, atol=1e-6)


def test_cohorted_mesh_rejects_non_divisible_cohorts():
    if len(jax.devices()) < 2:
        pytest.skip("divisibility only binds on a multi-device mesh")
    cfg = HFLConfig(mode="always", epochs=1, R=20)
    spec = MESH_SPEC + ((2, 40),)     # one cohort no longer divides D
    fed = Federation(_mk_clients(cfg, spec), cfg, engine="batched",
                     mesh=MF.make_mesh())
    with pytest.raises(ValueError, match="cohort sizes"):
        fed.fit()


# ---------------------------------------------------------------------------
# Acceptance pin: mixed population on a forced 4-device mesh (subprocess —
# jax locks the host platform device count at first init)
# ---------------------------------------------------------------------------

_SUBPROCESS = r"""
import collections, gc, json
import numpy as np
import jax
assert jax.device_count() == 4, jax.devices()
from repro.core.federation import Callback, Federation
from repro.core import mesh_federation as MF
from repro.core.hfl import FederatedClient, HFLConfig

SPEC = ((2, 40), (3, 60), (2, 40), (3, 60),
        (2, 40), (3, 60), (2, 40), (3, 60))

def mk_clients(cfg, seed0=100):
    out = []
    for i, (nf, n) in enumerate(SPEC):
        rng = np.random.default_rng(seed0 + i)
        mk = lambda m, nf=nf: (
            rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
            rng.normal(size=(m, nf, cfg.w)).astype(np.float32),
            rng.normal(size=m).astype(np.float32))
        out.append(FederatedClient(f"h{i:03d}", nf, cfg, mk(n), mk(30),
                                   mk(30), jax.random.PRNGKey(i)))
    return out

class LiveBytes(Callback):
    # live-array bytes per device while the mesh fit's state is live
    def on_epoch_end(self, fed, epoch, val, active):
        per = collections.Counter()
        for a in jax.live_arrays():
            for s in a.addressable_shards:
                per[s.device] += s.data.nbytes
        self.per_device = [per[d] for d in jax.devices()]

cfg = HFLConfig(mode="always", epochs=3, R=20)
h_oracle = Federation(mk_clients(cfg), cfg, engine="sequential").fit()
gc.collect()
live = LiveBytes()
fed = Federation(mk_clients(cfg), cfg, engine="batched",
                 mesh=MF.make_mesh(), callbacks=[live])
h_mesh = fed.fit()
# no cohort stack or client copy sits whole on device 0
balanced = (max(live.per_device) - min(live.per_device)
            <= 0.01 * max(live.per_device))
st = fed.dispatch_stats
assert st["devices"] == 4 and st["cohorts"] == 2, st
assert st["path"] == "fused" and st["dispatches_per_epoch"] == 1.0, st
sel_identical = all(h_oracle[n]["selections"] == h_mesh[n]["selections"]
                    for n in h_oracle)
rounds_identical = all(h_oracle[n]["rounds"] == h_mesh[n]["rounds"]
                       for n in h_oracle)
val_close = all(np.allclose(h_oracle[n]["val"], h_mesh[n]["val"],
                            rtol=1e-6, atol=1e-6) for n in h_oracle)
print("RESULT " + json.dumps({"sel_identical": sel_identical,
                              "rounds_identical": rounds_identical,
                              "val_close": val_close,
                              "bytes_balanced": balanced}))
"""


def test_mixed_population_on_forced_4_device_mesh():
    """ISSUE 5 acceptance: a mixed-nf ragged population client-shards its
    cohorts over a genuine 4-device `clients` mesh with selections
    identical to the sequential oracle, and live bytes equal on every
    device during the fit."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, out.stdout
    res = json.loads(line[-1][len("RESULT "):])
    assert res == {"sel_identical": True, "rounds_identical": True,
                   "val_close": True, "bytes_balanced": True}


# ---------------------------------------------------------------------------
# Padded union-pool pieces
# ---------------------------------------------------------------------------

def test_masked_kernel_sweep_infs_invalid_rows():
    """pool_mlp_errors_features_masked: valid rows equal the unmasked sweep,
    invalid (zero-padded) rows come back +inf."""
    from repro.core import networks as N
    from repro.kernels.pool_mlp.ops import (pool_mlp_errors_features,
                                            pool_mlp_errors_features_masked)
    from repro.sharding import spec as S

    w, R, ns, nf = 3, 20, 6, 2
    heads = [S.materialize(N.head_schema(w), jax.random.PRNGKey(i))
             for i in range(ns)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *heads)
    # zero two rows, as feature padding does
    valid = np.array([True, True, False, True, False, True])
    stacked = jax.tree_util.tree_map(
        lambda p: p * valid.reshape((ns,) + (1,) * (p.ndim - 1)), stacked)
    xd = jax.random.normal(jax.random.PRNGKey(1), (nf, R, w))
    y = jax.random.normal(jax.random.PRNGKey(2), (R,))
    ref = pool_mlp_errors_features(stacked, xd, y, block_pool=4)
    out = pool_mlp_errors_features_masked(stacked, xd, y,
                                          jnp.asarray(valid), block_pool=4)
    assert np.all(np.isinf(np.asarray(out)[:, ~valid]))
    np.testing.assert_array_equal(np.asarray(out)[:, valid],
                                  np.asarray(ref)[:, valid])


def test_stack_hetero_pool_pads_and_roundtrips():
    from repro.core.hfl import HeadPool
    cfg = HFLConfig(mode="always", epochs=1, R=20)
    clients = _mk_clients(cfg)
    pool = HeadPool()
    for c in clients:
        pool.publish(c.name, c.params["heads"], c.nf)
    names = [c.name for c in clients]
    nfs = [c.nf for c in clients]
    stacked = CO.stack_hetero_pool(pool, names, nfs, max_nf=4)
    for leaf in jax.tree_util.tree_leaves(stacked):
        assert leaf.shape[:2] == (5, 4)
    # padded rows are zero; real rows round-trip exactly
    for i, c in enumerate(clients):
        row = jax.tree_util.tree_map(lambda p: p[i], stacked)
        for k in jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(lambda p: p[c.nf:], row)):
            assert not np.any(k)
        orig = c.params["heads"]
        for a, b in zip(jax.tree_util.tree_leaves(
                jax.tree_util.tree_map(lambda p: p[:c.nf], row)),
                jax.tree_util.tree_leaves(orig)):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Generated heterogeneous populations (data + experiment layers)
# ---------------------------------------------------------------------------

def test_make_hetero_population_cycles_nf():
    from repro.data.synthetic import make_hetero_population
    pop = make_hetero_population(6, seed=0, nf_choices=(2, 3, 4),
                                 n_patients=4, n_events=120)
    assert [len(h.feature_names) for h in pop] == [2, 3, 4, 2, 3, 4]
    assert all(h.streams[0].nf == len(h.feature_names) for h in pop)


def test_hetero_population_trains_on_cohort_engine():
    from repro.core.experiment import hetero_population_clients
    cfg = HFLConfig(mode="always", epochs=2, R=10)
    clients, packs = hetero_population_clients(
        4, cfg, seed=0, n_patients=5, n_events=150, nf_choices=(2, 3))
    assert {c.nf for c in clients} == {2, 3}
    fed = Federation(clients, cfg, engine="batched")
    hist = fed.fit()
    assert fed.dispatch_stats["cohorts"] >= 2
    for h in hist.values():
        assert len(h["val"]) == 2 and np.isfinite(h["test"])
