"""Plain reference of federated epochs (paper §4.2, Table 4, Eqs. 7-8).

Written from the paper, in straightforward ``jax.numpy``, importing
nothing of the program.  Epochs of the paper's protocol over a
population, starting from given weights:

* each sub-round r, every client takes one Adam step (lr, b1=0.9,
  b2=0.999, eps=1e-8) on its r-th block of R train samples, with the
  multi-task MSE of Eqs. 3 and 6 (final + every preliminary head);
* then, when the cell exchanges, clients in list order each score every
  other client's published heads on their own last R samples, feature by
  feature (Eq. 7, squared error), take the best, blend it in
  (Eq. 8, H <- a H_hat + (1 - a) H) and publish their blended heads, so a
  later client sees an earlier client's new heads in the same sub-round;
* after the last sub-round, the validation MSE of every client; its best
  parameters are the epoch's where that MSE is strictly below every
  earlier epoch's (save-best, paper §5.2), and after the last epoch the
  test MSE is the best parameters'.  Adam's step count runs on across
  epochs.

``forced`` replays a run's own Eq.-7 choices (the answers it gave, as a
served model's tokens are replayed): the reference then scores each choice
against its own best score, and follows that choice.  Without ``forced``
it takes its own argmin.

Precision.  The configurations state float32 parameters, activations
and optimizer state, with every matmul at JAX's default precision, which
on a TPU is one bfloat16 pass: both operands rounded to bfloat16, the
products summed in float32, in the forward pass and in both matmuls of
its gradient.  ``mode="config"`` computes exactly that, written out
(``_dot_one_pass``), where the default means it, on a TPU; elsewhere the
default is a float32 matmul, computed at ``HIGHEST``.
``mode="control"`` is the next precision below the configuration's:
parameters, activations, optimizer state and matmuls all in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ACTS = ("sigmoid", "sigmoid", "lrelu", "lrelu")
B1, B2, EPS = 0.9, 0.999, 1e-8


BF16, F32 = jnp.bfloat16, jnp.float32


@jax.custom_vjp
def _dot_one_pass(x, w):
    """``x @ w`` in one bfloat16 pass, products summed in float32."""
    return jnp.dot(x.astype(BF16), w.astype(BF16), preferred_element_type=F32)


def _dot_one_pass_fwd(x, w):
    return _dot_one_pass(x, w), (x, w)


def _dot_one_pass_bwd(res, g):
    x, w = res
    gb, xb, wb = g.astype(BF16), x.astype(BF16), w.astype(BF16)
    dx = jnp.dot(gb, wb.T, preferred_element_type=F32)
    lead = tuple(range(x.ndim - 1))
    dw = jnp.tensordot(xb, gb, axes=(lead, lead), preferred_element_type=F32)
    return dx, dw


_dot_one_pass.defvjp(_dot_one_pass_fwd, _dot_one_pass_bwd)


def _dot(x, w, prec):
    if prec == "one_pass":
        return _dot_one_pass(x, w)
    if prec == "highest":
        return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
    return jnp.dot(x, w)


def _mlp(p, x, prec):
    n = len(p) // 2
    for i in range(n):
        x = _dot(x, p[f"w{i}"], prec) + p[f"b{i}"]
        if i < n - 1:
            x = (jax.nn.sigmoid(x) if ACTS[i] == "sigmoid"
                 else jnp.where(x >= 0, x, 0.01 * x))
    return x


def _head(h, xd, prec):
    return _mlp(h, xd, prec)[..., 0]


def _forward(params, xs, xd, prec):
    nf = xd.shape[1]
    prelim = jnp.stack([_head(jax.tree_util.tree_map(lambda a: a[f],
                                                     params["heads"]),
                              xd[:, f], prec) for f in range(nf)], axis=1)
    e = _mlp(params["embed"], xs.reshape(xs.shape[0], -1), prec)
    y = _mlp(params["pred"], jnp.concatenate([prelim, e], -1), prec)[..., 0]
    return y, prelim


def _loss(params, xs, xd, y, prec):
    y_hat, prelim = _forward(params, xs, xd, prec)
    return (jnp.mean((y - y_hat) ** 2)
            + jnp.mean(jnp.sum((y[:, None] - prelim) ** 2, axis=-1)))


@functools.partial(jax.jit, static_argnames=("prec", "lr"))
def _adam_step(params, m, v, t, xs, xd, y, *, prec, lr):
    g = jax.grad(_loss)(params, xs, xd, y, prec)
    dt = jax.tree_util.tree_leaves(params)[0].dtype
    m = jax.tree_util.tree_map(lambda a, b: B1 * a + (1 - B1) * b, m, g)
    v = jax.tree_util.tree_map(lambda a, b: B2 * a + (1 - B2) * b * b, v, g)
    c1 = (1 - B1 ** t).astype(dt)
    c2 = (1 - B2 ** t).astype(dt)
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + EPS),
        params, m, v)
    return params, m, v


@functools.partial(jax.jit, static_argnames=("prec",))
def _mse(params, xs, xd, y, *, prec):
    y_hat, _ = _forward(params, xs, xd, prec)
    return jnp.mean((y.astype(jnp.float32) - y_hat.astype(jnp.float32)) ** 2)


@functools.partial(jax.jit, static_argnames=("nf", "prec", "alpha"))
def _exchange(pool, heads, xd, y, lo, forced, *, nf, prec, alpha):
    """One client's Eq.-7/Eq.-8 turn of a policy round.

    ``pool``: every client's published heads, rows stacked; the client's own
    rows are ``[lo, lo + nf)``.  Scores every row on each probe feature
    (squared error), leaves the client's own rows out, takes ``forced`` rows
    where they are >= 0 (a run's own choices) and the argmin elsewhere,
    blends the chosen heads into the client's and publishes them.  Returns
    (pool, blended heads, each choice's gap to the best score as a share of
    it, the chosen rows)."""
    preds = jax.vmap(lambda h: jax.vmap(lambda x: _head(h, x, prec))(
        jnp.moveaxis(xd, 1, 0)))(pool)                       # (rows, nf, R)
    err = jnp.mean((y[None, None, :].astype(F32) - preds.astype(F32)) ** 2,
                   axis=-1).T                                # (nf, rows)
    rows = jnp.arange(err.shape[1])
    own = (rows >= lo) & (rows < lo + nf)
    err = jnp.where(own[None, :] | ~jnp.isfinite(err), jnp.inf, err)
    best = jnp.min(err, axis=1)
    idx = jnp.where(forced >= 0, forced, jnp.argmin(err, axis=1))
    got = err[jnp.arange(nf), idx]
    gap = (got - best) / jnp.maximum(best, 1e-30)
    sel = jax.tree_util.tree_map(lambda p: p[idx], pool)
    new = jax.tree_util.tree_map(lambda s, t: alpha * s + (1 - alpha) * t,
                                 sel, heads)
    pool = jax.tree_util.tree_map(
        lambda p, h: jax.lax.dynamic_update_slice_in_dim(p, h.astype(p.dtype),
                                                         lo, 0), pool, new)
    return pool, new, gap, idx


def run_epochs(sites, params0, cfg: dict, exchange: bool, epochs: int, *,
               mode: str = "config", forced=None):
    """``epochs`` epochs from ``params0`` (a list of per-client trees).

    ``sites``: per client {"train"|"valid"|"test": (xs, xd, y)} arrays.
    ``forced``: per client, per exchange sub-round of every epoch in order,
    the nf chosen positions in the sorted foreign pool (the program's
    ``selections``), or None.
    Returns {"params", "m", "val", "test", "choices", "gaps"}: ``val`` per
    client and epoch; ``test`` of each client's best parameters (save-best
    on the validation MSE, strictly lower, from no epoch); ``choices`` per
    client in the ``forced`` layout; ``gaps`` per choice, how far its score
    lies above the best, as a share of the best."""
    if mode == "config":
        one_pass = jax.default_backend() == "tpu"
        dtype, prec = F32, "one_pass" if one_pass else "highest"
    elif mode == "control":
        dtype, prec = BF16, "bf16"
    else:
        raise ValueError(f"mode {mode!r}: config or control")
    R, alpha, lr = cfg["R"], cfg["alpha"], cfg["lr"]
    cast = lambda t: jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, dtype), t)
    C = len(sites)
    nfs = [s["train"][0].shape[1] for s in sites]
    params = [cast(p) for p in params0]
    m = [jax.tree_util.tree_map(jnp.zeros_like, p) for p in params]
    v = [jax.tree_util.tree_map(jnp.zeros_like, p) for p in params]
    train = [cast(s["train"]) for s in sites]
    valid = [cast(s["valid"]) for s in sites]
    n_sub = [len(s["train"][2]) // R for s in sites]
    offs = np.concatenate([[0], np.cumsum(nfs)]).astype(np.int64)
    # the published pool: every client's heads, rows in (client, feature)
    # order, which is the sorted (name, feature) order of the clients
    pool = jax.tree_util.tree_map(lambda *hs: jnp.concatenate(hs),
                                  *[p["heads"] for p in params])
    turns = [0] * C                     # exchange turns taken per client
    picked = [[] for _ in range(C)]     # (device rows, lo, nf) per turn
    gaps = []
    best_val = [np.inf] * C
    best = list(params)
    val = [[] for _ in range(C)]
    t = 0
    for _ in range(epochs):
        for r in range(max(n_sub)):
            t += 1
            live = [i for i in range(C) if r < n_sub[i]]
            for i in live:
                sl = slice(r * R, (r + 1) * R)
                params[i], m[i], v[i] = _adam_step(
                    params[i], m[i], v[i], jnp.asarray(t, jnp.float32),
                    *(a[sl] for a in train[i]), prec=prec, lr=lr)
            if not exchange:
                continue
            for i in live:
                sl = slice(r * R, (r + 1) * R)
                lo, nf = int(offs[i]), nfs[i]
                if forced is None:
                    rows = np.full(nf, -1, np.int64)
                else:              # sorted-foreign position -> pool row
                    k = np.asarray(forced[i][turns[i]], np.int64)
                    rows = np.where(k < lo, k, k + nf)
                    rows = np.where((k >= 0) & (rows < offs[-1]), rows, -1)
                pool, new, gap, idx = _exchange(
                    pool, params[i]["heads"], train[i][1][sl],
                    train[i][2][sl], jnp.asarray(lo, jnp.int32),
                    jnp.asarray(rows, jnp.int32), nf=nf, prec=prec,
                    alpha=alpha)
                params[i] = {**params[i], "heads": new}
                turns[i] += 1
                gaps.append(gap)
                picked[i].append((idx, lo, nf))
        for i in range(C):
            vi = float(_mse(params[i], *valid[i], prec=prec))
            val[i].append(vi)
            if vi < best_val[i]:
                best_val[i], best[i] = vi, params[i]
    test = [float(_mse(best[i], *cast(sites[i]["test"]), prec=prec))
            for i in range(C)]
    rows = jax.device_get([[x for x, _, _ in p] for p in picked])
    choices = [[np.where(a < lo, a, a - nf).tolist()
                for a, (_, lo, nf) in zip(rs, p)]
               for rs, p in zip(rows, picked)]
    host = lambda t: jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), t)
    gaps = np.concatenate([np.asarray(g, np.float64).ravel()
                           for g in jax.device_get(gaps)]) if gaps \
        else np.zeros(0)
    return {"params": [host(p) for p in params], "m": [host(a) for a in m],
            "val": val, "test": test, "choices": choices, "gaps": gaps}
