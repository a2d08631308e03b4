"""Faults planted in the program underneath a run, for the checks that
``correct`` catches them (``tests/test_faults.py`` on the CPU,
``calibrate.py --fault-seeds`` on the chip at a cell's size).  Each is a
context manager that patches the program and clears its compiled epoch
programs, so the next ``fit()`` traces the broken path."""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp


def _unchanged(opt, params, opt_state, xs, xd, y):
    return params, opt_state, jnp.zeros(())


def _half_batch(orig, opt, params, opt_state, xs, xd, y):
    h = y.shape[0] // 2
    return orig(opt, params, opt_state, xs[:h], xd[:h], y[:h])


def _off_val(orig, params, xs, xd, y):
    return 1.5 * orig(params, xs, xd, y)


def _per_cohort(f, tree):
    """``f`` on a stacked tree, or on each cohort's of a tuple of them."""
    return tuple(f(t) for t in tree) if isinstance(tree, tuple) else f(tree)


def _epoch_wrapper(fix):
    """Wrap an epoch body's epoch function (``federation._epoch_body`` or
    ``cohorts._hetero_epoch_body``): both take and return the carried
    (params, opt_state, pool, ages, key, best_val, best_params) first.
    ``fix(args, out)`` returns the outputs to hand on."""
    def make(orig):
        def body(*a, **kw):
            epoch = orig(*a, **kw)

            def wrapped(*args, **kwargs):
                return fix(args, epoch(*args, **kwargs))
            return wrapped
        return body
    return make


def _adam_reset(orig):
    # Adam's step count back to nought at every epoch's start: from the
    # second epoch on the bias correction restarts
    reset = lambda o: {**o, "step": jnp.zeros_like(o["step"])}

    def body(*a, **kw):
        epoch = orig(*a, **kw)

        def wrapped(*args, **kwargs):
            args = (args[0], _per_cohort(reset, args[1])) + tuple(args[2:])
            return epoch(*args, **kwargs)
        return wrapped
    return body


def _best_stuck(args, out):
    # save-best keeps the first epoch's parameters: once a best exists it
    # is never replaced, though the best validation MSE moves on
    had = lambda bv: jnp.isfinite(bv)
    def keep(bv, old, new):
        return jax.tree_util.tree_map(
            lambda o, n: jnp.where(had(bv).reshape(
                bv.shape + (1,) * (o.ndim - 1)), o, n), old, new)
    bv_in, bp_in, bp_out = args[5], args[6], out[6]
    if isinstance(bp_out, tuple):
        bp = tuple(keep(b, o, n) for b, o, n in zip(bv_in, bp_in, bp_out))
    else:
        bp = keep(bv_in, bp_in, bp_out)
    return out[:6] + (bp,) + out[7:]


FROZEN = 1


def _one_client_frozen(args, out):
    # one client's epoch dropped: its parameters and Adam state come back
    # as they went in (the first cohort's row FROZEN)
    def back(old, new):
        return jax.tree_util.tree_map(
            lambda o, n: n.at[FROZEN].set(o[FROZEN]), old, new)
    if isinstance(out[0], tuple):
        p = (back(args[0][0], out[0][0]),) + out[0][1:]
        o = (back(args[1][0], out[1][0]),) + out[1][1:]
    else:
        p, o = back(args[0], out[0]), back(args[1], out[1])
    return (p, o) + out[2:]


def _targets():
    """(owners, attribute, make) per fault: the batched engine and the
    cohort engine each hold their own reference to the step, the eval and
    the epoch body."""
    from repro.core import cohorts as CO
    from repro.core import federation as F
    engines = (F, CO)
    bodies = ((F, "_epoch_body"), (CO, "_hetero_epoch_body"))
    return {
        # a step that returns its state unchanged
        "state_unchanged": (engines, "_train_step", lambda o: _unchanged),
        # half of each batch left out, the mean taken over the rest
        "half_batch": (engines, "_train_step",
                       lambda o: functools.partial(_half_batch, o)),
        # an answer altered where it is produced: the validation MSE
        "val_altered": (engines, "_eval_mse",
                        lambda o: functools.partial(_off_val, o)),
        # one client of the population left untrained
        "one_client_frozen": (bodies, None,
                              _epoch_wrapper(_one_client_frozen)),
        # faults that act from the second epoch on
        "adam_reset": (bodies, None, _adam_reset),
        "best_stuck": (bodies, None, _epoch_wrapper(_best_stuck)),
    }


NAMES = ("state_unchanged", "half_batch", "val_altered", "one_client_frozen",
         "adam_reset", "best_stuck")


def _clear():
    from repro.core import cohorts as CO
    from repro.core import federation as F
    for fn in (F._make_epoch_fn, CO._make_hetero_epoch_fn,
               CO._make_mesh_hetero_epoch_fn):
        fn.cache_clear()
    jax.clear_caches()


@contextlib.contextmanager
def planted(name: str):
    owners, attr, make = _targets()[name]
    if attr is None:                   # (owner, attribute) pairs
        owners, attrs = zip(*owners)
    else:
        attrs = (attr,) * len(owners)
    origs = [getattr(o, a) for o, a in zip(owners, attrs)]
    for o, a, orig in zip(owners, attrs, origs):
        setattr(o, a, make(orig))
    _clear()
    try:
        yield
    finally:
        for o, a, orig in zip(owners, attrs, origs):
            setattr(o, a, orig)
        _clear()
