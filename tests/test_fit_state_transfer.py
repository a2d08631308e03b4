"""The batched fit driver's state transfer at a fit's two ends.

Write-back copies the stacked ``(C, ...)`` state to the host in one
``device_get`` and hands each client, and the head pool, read-only numpy
row views of it; the next fit stacks those host rows on the host and
places each stacked tree on the device once.  Both ends are byte copies,
so every client leaf and pool entry must equal what slicing the stacked
device state row by row gives, and two fits of 2 epochs must reproduce
one fit of 4 bit for bit — on the homogeneous engine and on the cohort
engine (mixed nf)."""
import jax
import numpy as np
import pytest

from repro.core import cohorts as CO
from repro.core import federation as FED
from repro.core.experiment import tensor_population
from repro.core.federation import Federation, RoundSchedule
from repro.core.hfl import HFLConfig

POPULATIONS = {"homogeneous": (3,), "mixed_nf": (2, 3)}


def _cfg():
    return HFLConfig(epochs=4, R=10, mode="always", seed=0)


def _fed(nf_choices, n=6):
    cfg = _cfg()
    clients = tensor_population(n, cfg, seed=0, nf_choices=nf_choices,
                                n_train=20, n_eval=10).build(range(n))
    return Federation(clients, cfg, engine="batched",
                      schedule=RoundSchedule(cfg.epochs, cfg.R))


def _row_by_row(tree, i):
    """Client i's row of a stacked device tree, one eager slice per leaf —
    the per-row write-back the host copy replaces."""
    return jax.tree_util.tree_map(lambda p: np.asarray(p[i]), tree)


def _assert_trees_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert np.asarray(x).dtype == np.asarray(y).dtype
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("kind", sorted(POPULATIONS))
def test_writeback_equals_row_by_row_slices(kind, monkeypatch):
    """Every client leaf and pool entry after a fit is the byte-exact row
    of the stacked device state the fit ended with."""
    seen = []
    real = FED._to_host

    def capture(tree):
        seen.append(jax.tree_util.tree_map(lambda x: x, tree))
        return real(tree)

    monkeypatch.setattr(FED, "_to_host", capture)
    monkeypatch.setattr(CO, "_to_host", capture)
    fed = _fed(POPULATIONS[kind])
    fed.fit(epochs=2)
    assert len(seen) == 1
    if kind == "homogeneous":
        params, opt, best, heads, _, _ = seen[0]
        rows = {c.name: (i, params, opt, best, i)
                for i, c in enumerate(fed.clients)}
    else:
        params, opt, best, _, heads, _ = seen[0]
        plan = CO.plan_cohorts(fed.clients, fed.schedule.R)
        rows = {}
        for k, co in enumerate(plan.cohorts):
            for r, i in enumerate(co.members):
                rows[fed.clients[i].name] = (r, params[k], opt[k], best[k], i)
    for c in fed.clients:
        r, p, o, b, i = rows[c.name]
        _assert_trees_equal(c.params, _row_by_row(p, r))
        _assert_trees_equal(c.opt_state, _row_by_row(o, r))
        _assert_trees_equal(c.best_params, _row_by_row(b, r))
        pool_row = _row_by_row(heads, i)
        for f in range(c.nf):
            _assert_trees_equal(fed.pool.entries[(c.name, f)],
                                _row_by_row(pool_row, f))


@pytest.mark.parametrize("kind", sorted(POPULATIONS))
def test_client_state_is_read_only_host_arrays(kind):
    fed = _fed(POPULATIONS[kind])
    fed.fit(epochs=1)
    trees = [t for c in fed.clients
             for t in (c.params, c.opt_state, c.best_params)]
    trees += list(fed.pool.entries.values())
    for leaf in jax.tree_util.tree_leaves(trees):
        assert isinstance(leaf, np.ndarray)
        assert not leaf.flags.writeable
        with pytest.raises(ValueError, match="read-only"):
            leaf[...] = 0


@pytest.mark.parametrize("kind", sorted(POPULATIONS))
def test_two_fits_equal_one_fit(kind):
    """The second fit restacks host rows; one fit of 4 never leaves the
    device.  Histories, selections, test MSEs and every state leaf agree
    bit for bit."""
    split, whole = _fed(POPULATIONS[kind]), _fed(POPULATIONS[kind])
    split.fit(epochs=2)
    h_split = split.fit(epochs=2)
    h_whole = whole.fit(epochs=4)
    assert h_split == h_whole
    for a, b in zip(split.clients, whole.clients):
        _assert_trees_equal((a.params, a.opt_state, a.best_params),
                            (b.params, b.opt_state, b.best_params))
    assert split.pool.entries.keys() == whole.pool.entries.keys()
    for k in split.pool.entries:
        _assert_trees_equal(split.pool.entries[k], whole.pool.entries[k])
    assert split.pool.ages == whole.pool.ages
    np.testing.assert_array_equal(np.asarray(split._key),
                                  np.asarray(whole._key))
