"""Traffic: a synthetic-hospital population built from the run's seed.

A copy of the construction in ``repro.data.synthetic`` and
``repro.core.experiment`` (``make_population``, ``population_spec``,
``_normalize_streams``, ``pack_feature_tensors``), vectorised over the
patients of a site so that set-up stays short, and with the split lengths
fixed by the configuration so that every seed does the same work:

* every site observes one shared Ornstein-Uhlenbeck latent state through
  its own generated observation operator (nf feature channels and a label
  channel, each a jittered draw from the two paper hospitals' channels);
* at each tick exactly one channel is observed;
* values are z-scored per channel with the train split's statistics;
* each label event becomes one sample: the sparse and dense (nf, w)
  tensors of paper §3 and the label.

A site whose splits come out shorter than the configured lengths is drawn
again from the same generator, so the shapes never depend on the seed.
The benchmark also makes the clients' initial weights here, on the device,
in one jitted call from the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Z_DIM = 6
THETA, SIGMA = 0.08, 1.0

# (mean, std, latent weights, observation-frequency weight) of the two
# paper hospitals' feature and label channels (repro.data.synthetic)
CHANNEL_BANK = (
    (80.0, 14.0, (1.0, 0.3, 0.0, 0.0, 0.2, 0.0), 5.18),
    (96.5, 2.5, (0.0, -0.8, 0.4, 0.0, 0.0, 0.1), 3.42),
    (18.0, 4.5, (0.3, -0.5, 0.0, 0.6, 0.0, 0.0), 3.39),
    (122.0, 18.0, (0.5, 0.0, 0.9, 0.0, -0.2, 0.0), 2.10),
    (64.0, 12.0, (0.4, 0.0, 0.8, 0.0, -0.3, 0.1), 2.09),
    (78.0, 13.0, (1.0, 0.25, 0.0, 0.0, 0.15, 0.0), 2.76),
    (18.5, 4.0, (0.3, -0.5, 0.0, 0.6, 0.0, 0.0), 2.74),
    (96.0, 2.8, (0.0, -0.8, 0.45, 0.0, 0.0, 0.1), 2.67),
    (84.0, 13.0, (0.45, 0.0, 0.85, 0.0, -0.25, 0.05), 1.29),
    (118.0, 17.0, (0.5, 0.0, 0.9, 0.0, -0.2, 0.0), 1.29),
)
SPLITS = ("train", "valid", "test")


def seed_words(seed: int, stream: int) -> np.random.SeedSequence:
    """An independent stream of the run's seed (any non-negative int)."""
    return np.random.SeedSequence([int(seed), stream])


def _site_spec(rng: np.random.Generator, nf: int) -> dict:
    """One site's channels: nf features + the label, each a perturbed draw
    from the channel bank (``synthetic.population_spec``)."""
    picks = rng.choice(len(CHANNEL_BANK), size=nf + 1,
                       replace=nf + 1 > len(CHANNEL_BANK))
    mu, sd, wz, freq = [], [], [], []
    for b in picks:
        m, s, z, f = CHANNEL_BANK[b]
        mu.append(m * (1 + 0.08 * rng.normal()))
        sd.append(s * abs(1 + 0.15 * rng.normal()) + 1e-3)
        wz.append(np.asarray(z) + 0.1 * rng.normal(size=Z_DIM))
        freq.append(f * np.exp(0.4 * rng.normal()))
    wz = np.asarray(wz)
    return {"nf": nf, "mu": np.asarray(mu), "sd": np.asarray(sd),
            "wz": wz / np.maximum(np.linalg.norm(wz, axis=1,
                                                 keepdims=True), 1e-9),
            "p": np.asarray(freq) / np.sum(freq)}


def _site_streams(rng, spec, n_patients: int, n_events: int):
    """(channels, values) of every patient, shape (P, T): OU latent state
    at exponential gaps, one channel per tick, label noise 0.15 and
    feature noise 0.25 (``synthetic.make_patient``)."""
    P, T, nf = n_patients, n_events, spec["nf"]
    dt = rng.exponential(scale=1.0, size=(P, T))
    z = np.empty((P, T, Z_DIM))
    z[:, 0] = rng.normal(size=(P, Z_DIM))
    decay = np.exp(-THETA * dt)
    scale = np.sqrt(SIGMA ** 2 * (1 - decay ** 2) / (2 * THETA))
    eps = rng.normal(size=(P, T, Z_DIM))
    for t in range(1, T):
        z[:, t] = z[:, t - 1] * decay[:, t, None] + eps[:, t] * scale[:, t,
                                                                        None]
    ch = rng.choice(nf + 1, size=(P, T), p=spec["p"]).astype(np.int32)
    sig = np.einsum("ptk,ptk->pt", z, spec["wz"][ch])
    noise = np.where(ch == nf, 0.15, 0.25)
    vals = spec["mu"][ch] + spec["sd"][ch] * (
        0.9 * sig + noise * rng.normal(size=(P, T)))
    return ch, vals.astype(np.float32)


def _pack(ch, vals, nf: int, w: int):
    """Per patient, the (X_sparse, X_dense, y) samples of its label ticks,
    most recent first (``feature_tensors.pack_feature_tensors``)."""
    P, T = ch.shape
    feat = ch < nf
    xs = np.zeros((P, T, nf, w), np.float32)
    for l in range(w):              # X^S[i, l]: tick t-1-l if it carried i
        src = np.arange(T) - 1 - l
        ok = src >= 0
        c = np.where(ok, ch[:, np.clip(src, 0, None)], nf)
        v = np.where(ok, vals[:, np.clip(src, 0, None)], 0.0)
        hit = (c[..., None] == np.arange(nf)) & (c < nf)[..., None]
        xs[:, :, :, l] = np.where(hit, v[..., None], 0.0)
    xd = np.zeros((P, T, nf, w), np.float32)
    hist = np.zeros((P, nf, w), np.float32)   # X^D: last w available values
    rows = np.arange(P)
    for t in range(T):
        xd[:, t] = hist
        f = feat[:, t]
        c = ch[f, t]
        hist[rows[f], c, 1:] = hist[rows[f], c, :-1]
        hist[rows[f], c, 0] = vals[f, t]
    lab = ~feat
    return [(xs[p][lab[p]], xd[p][lab[p]], vals[p][lab[p]])
            for p in range(P)]


def _site(rng, nf: int, n_patients: int, n_events: int, w: int):
    spec = _site_spec(rng, nf)
    ch, vals = _site_streams(rng, spec, n_patients, n_events)
    perm = rng.permutation(n_patients)
    n_tr, n_va = int(0.6 * n_patients), int(0.2 * n_patients)
    split_rows = {"train": perm[:n_tr], "valid": perm[n_tr:n_tr + n_va],
                  "test": perm[n_tr + n_va:]}
    # z-score every channel with the train split's statistics
    tr = ch[split_rows["train"]]
    tv = vals[split_rows["train"]]
    for c in range(nf + 1):
        v = tv[tr == c]
        if len(v):
            vals = np.where(ch == c, (vals - v.mean()) / max(1e-6, v.std()),
                            vals).astype(np.float32)
    samples = _pack(ch, vals, nf, w)
    return {s: tuple(np.concatenate([samples[p][k] for p in rows])
                     for k in range(3))
            for s, rows in split_rows.items()}


def make_sites(seed: int, nfs, n_patients: int, n_events: int, w: int,
               lengths: dict):
    """Packed splits of ``len(nfs)`` sites, site h observing ``nfs[h]``
    features, each split cut to ``lengths[split]`` samples."""
    rng = np.random.default_rng(seed_words(seed, 1))
    sites = []
    for nf in nfs:
        while True:
            s = _site(rng, int(nf), n_patients, n_events, w)
            if all(len(s[k][2]) >= lengths[k] for k in SPLITS):
                break
        sites.append({k: tuple(a[:lengths[k]] for a in s[k])
                      for k in SPLITS})
    return sites


# ---------------------------------------------------------------------------
# Weights: Table-4 networks, uniform with sd 1/sqrt(fan_in), zero biases
# ---------------------------------------------------------------------------

def mlp_dims(cfg: dict, nf: int):
    """Layer widths of the three Table-4 networks at ``nf`` features."""
    w = cfg["w"]
    return {"heads": (w,) + tuple(cfg["head_widths"]),
            "embed": (nf * w,) + tuple(cfg["embed_widths"]) + (w,),
            "pred": (nf + w,) + tuple(cfg["pred_widths"])}


def _mlp_init(key, dims, lead=()):
    out = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        lim = np.sqrt(3.0 / a)
        out[f"w{i}"] = jax.random.uniform(jax.random.fold_in(key, i),
                                          lead + (a, b), jnp.float32,
                                          -lim, lim)
        out[f"b{i}"] = jnp.zeros(lead + (b,), jnp.float32)
    return out


NETS = ("heads", "embed", "pred")


@functools.partial(jax.jit, static_argnames=("groups",))
def _init_groups(key, groups):
    """One draw per feature count: ``groups`` is ((nf, n, dims...), ...);
    each leaf comes out stacked over that group's n clients."""
    out = []
    for nf, n, *dims in groups:
        k = jax.random.fold_in(key, nf)
        nets = dict(zip(NETS, dims))
        out.append({"heads": _mlp_init(jax.random.fold_in(k, 0),
                                       nets["heads"], (n, nf)),
                    "embed": _mlp_init(jax.random.fold_in(k, 1),
                                       nets["embed"], (n,)),
                    "pred": _mlp_init(jax.random.fold_in(k, 2),
                                      nets["pred"], (n,))})
    return tuple(out)


def init_weights(seed: int, nfs, cfg: dict):
    """Every client's initial parameters in the program's tree layout
    (float32), made on the device in one jitted call from the seed (one
    stacked draw per feature count).  Returns (per-client trees on the
    device, the same on the host): the split into per-client trees goes
    through the host, which compiles nothing."""
    nfs = [int(n) for n in nfs]
    kinds = sorted(set(nfs))
    groups = tuple((nf, nfs.count(nf)) + tuple(mlp_dims(cfg, nf)[k]
                                                for k in NETS)
                   for nf in kinds)
    key = jax.random.key(
        int(seed_words(seed, 2).generate_state(1)[0]), impl="rbg")
    stacked = jax.device_get(_init_groups(key, groups))
    rows = {nf: iter(range(n)) for nf, n, *_ in groups}
    host = []
    for nf in nfs:
        i = next(rows[nf])
        host.append(jax.tree_util.tree_map(lambda a: a[i],
                                           stacked[kinds.index(nf)]))
    return jax.device_put(host), host
