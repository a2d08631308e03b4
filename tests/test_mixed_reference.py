"""The cohort engine against the plain reference, on the CPU.

Six sites in three feature sets (nf 3, 4, 5, site h observing
``nf_choices[h % 3]`` as in the benchmark's ``table4-mixed48``), at the
paper's Table-4 widths, from seeded random weights
(``perfbench/population.py``), run two epochs through
``Federation(engine="batched")`` with the Eq.-7/8 exchange every
sub-round: the fit takes the cohort path, one fused dispatch per epoch.
``perfbench/reference.py`` runs the same two epochs in plain
``jax.numpy`` (``mode="config"``: float32 at ``HIGHEST`` off the TPU),
replaying the run's own Eq.-7 choices, and each client's parameters,
Adam first moment, validation and test MSE are compared.

Tolerances.  Program and reference both compute in float32 here (the
CPU's default matmul is a float32 one), so they differ only in the order
of summation, over four Adam steps of about ``lr = 0.01`` each: on three
seeds the largest gaps were 6e-7 for a parameter, 3e-7 for a moment
(absolute) and 1.3e-7 for an MSE (relative).  The tolerances, 1e-5,
leave more than ten times that.  One
precision lower, bfloat16, rounds every operand to 8 bits of mantissa
(4e-3 relative), which flips the sign of small gradient components and
so moves parameters by whole Adam steps (1e-2) and MSEs by 1e-3 or more:
the reference's ``mode="control"`` must fail at least one tolerance.
"""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.federation import Federation, RoundSchedule
from repro.core.hfl import FederatedClient, HFLConfig

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "perfbench"))

import population as P  # noqa: E402
import reference  # noqa: E402

SEED = 2**31 + 5
SITES, EPOCHS = 6, 2
# absolute, on each element of the parameters and of Adam's first moment
PARAM_ATOL = 1e-5
# relative, on each client's validation MSE (every epoch) and test MSE
MSE_RTOL = 1e-5


def _config() -> dict:
    """``table4-mixed48`` at a size the CPU runs in seconds: six sites,
    splits of 100/20/20 samples (two sub-rounds of R = 50)."""
    with open(ROOT / "perfbench" / "configs" / "table4-mixed48.json") as f:
        conf = json.load(f)
    conf.update(sites=SITES, patients_per_site=10, events_per_patient=200,
                epochs=EPOCHS,
                split_lengths={"train": 100, "valid": 20, "test": 20})
    return conf


@pytest.fixture(scope="module")
def run():
    """The program's fit and the reference of the same epochs."""
    conf = _config()
    choices = conf["nf_choices"]
    nfs = [choices[h % len(choices)] for h in range(SITES)]
    sites = P.make_sites(SEED, nfs, conf["patients_per_site"],
                         conf["events_per_patient"], conf["w"],
                         conf["split_lengths"])
    weights, params0 = P.init_weights(SEED, nfs, conf)
    cfg = HFLConfig(w=conf["w"], R=conf["R"], alpha=conf["alpha"],
                    lr=conf["lr"], epochs=EPOCHS, mode="always",
                    seed=SEED & 0x7FFFFFFF)
    clients = []
    for h, (s, p) in enumerate(zip(sites, weights)):
        c = FederatedClient(f"h{h:03d}", nfs[h], cfg, s["train"],
                            s["valid"], s["test"], jax.random.PRNGKey(h))
        c.params, c.best_params = p, p
        c.opt_state = c.opt.init(p)
        clients.append(c)
    fed = Federation(clients, cfg, engine="batched",
                     schedule=RoundSchedule(EPOCHS, conf["R"],
                                            exchange_every=1))
    hist = fed.fit(epochs=EPOCHS)
    names = [c.name for c in clients]
    prog = {"params": [jax.device_get(c.params) for c in clients],
            "m": [jax.device_get(c.opt_state["m"]) for c in clients],
            "val": [hist[n]["val"] for n in names],
            "test": [hist[n]["test"] for n in names]}
    ref = reference.run_epochs(sites, params0, conf, True, EPOCHS,
                               forced=[hist[n]["selections"]
                                       for n in names])
    return {"fed": fed, "prog": prog, "ref": ref, "sites": sites,
            "params0": params0, "conf": conf}


def _leaves(trees):
    return [np.asarray(a, np.float64) for t in trees
            for a in jax.tree_util.tree_leaves(t)]


def _within(a: dict, b: dict) -> dict:
    """Which of the compared quantities of ``a`` lie within their
    tolerance of ``b``'s."""
    out = {}
    for k in ("params", "m"):
        out[k] = all(np.allclose(x, y, rtol=0, atol=PARAM_ATOL)
                     for x, y in zip(_leaves(a[k]), _leaves(b[k])))
    out["val"] = np.allclose(np.asarray(a["val"], np.float64),
                             np.asarray(b["val"], np.float64),
                             rtol=MSE_RTOL, atol=0)
    out["test"] = np.allclose(np.asarray(a["test"], np.float64),
                              np.asarray(b["test"], np.float64),
                              rtol=MSE_RTOL, atol=0)
    return out


def test_fit_takes_the_cohort_path(run):
    stats = run["fed"].dispatch_stats
    assert stats["cohorts"] == 3
    assert stats["dispatches_per_epoch"] == 1.0
    assert stats["dispatches"] == EPOCHS
    assert [(c["nf"], c["clients"]) for c in stats["per_cohort"]] == \
        [(3, 2), (4, 2), (5, 2)]
    assert stats["exchange_rounds"] == EPOCHS * 2


@pytest.mark.parametrize("quantity", ("params", "m", "val", "test"))
def test_program_matches_reference(run, quantity):
    assert _within(run["prog"], run["ref"])[quantity]


def test_bf16_control_fails_a_tolerance(run):
    """The reference in bfloat16, making its own Eq.-7 choices, against
    the float32 reference replaying them: one precision lower than the
    configuration's does not pass."""
    ctl = reference.run_epochs(run["sites"], run["params0"], run["conf"],
                               True, EPOCHS, mode="control")
    ref = reference.run_epochs(run["sites"], run["params0"], run["conf"],
                               True, EPOCHS, forced=ctl["choices"])
    assert not all(_within(ctl, ref).values())
