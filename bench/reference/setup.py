"""Plain reference of Fed-TGAN's set-up (§4.1 encoders, §4.2 weights).

From the clients' raw rows and the same seeds the system is given:

* categorical columns: the union of the clients' categories and each
  client's frequency table;
* continuous columns: each client fits a Gaussian mixture of at most
  ``max_modes`` modes by EM (quantile start, a weight floor, modes under
  ``weight_threshold`` pruned); the federator draws row-count-proportional
  samples from the client mixtures and fits the global mixture on them;
* the divergence matrix: Jensen-Shannon distance (base 2) of each client's
  categorical frequencies to the global ones, 1-D Wasserstein distance of
  samples of each client mixture to samples of the global one on a
  min-max scale; the weights are Fig. 4's column normalisation, row sum,
  similarity complement plus row share, softmax;
* CTGAN's mode-specific normalisation of every client's rows: a mode drawn
  by Gumbel-max over the mixture's log joint, ``alpha = (x - mu) / 4 sd``
  clipped to [-1, 1], and one-hots for modes and categories.

Written in ``jax.numpy`` float32 (the precision the system runs) and
numpy; it imports nothing of the system under test.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LOG2PI = math.log(2 * math.pi)


@partial(jax.jit, static_argnames=("k", "n_iter"))
def fit_mixture(x, key, k: int = 10, n_iter: int = 60,
                weight_threshold: float = 5e-3):
    """EM fit of a ``k``-mode Gaussian mixture to 1-D ``x``.  Returns
    (weights, means, stds, valid)."""
    x = x.astype(jnp.float32)
    n = x.shape[0]
    sd = jnp.maximum(jnp.std(x), 1e-6)
    means = (jnp.quantile(x, jnp.linspace(0.02, 0.98, k))
             + 1e-4 * sd * jax.random.normal(key, (k,)))
    stds = jnp.full((k,), sd)
    w = jnp.full((k,), 1.0 / k)
    floor = 1e-4 * sd + 1e-9

    def em(_, c):
        w, means, stds = c
        z = (x[:, None] - means[None]) / stds[None]
        lj = (-0.5 * z * z - jnp.log(stds)[None] - 0.5 * LOG2PI
              + jnp.log(jnp.maximum(w, 1e-12))[None])
        r = jnp.exp(lj - jax.scipy.special.logsumexp(lj, axis=1,
                                                     keepdims=True))
        nk = jnp.sum(r, axis=0)
        w = (nk + 1e-6) / (n + k * 1e-6)
        means = jnp.sum(r * x[:, None], axis=0) / jnp.maximum(nk, 1e-8)
        var = jnp.sum(r * (x[:, None] - means[None]) ** 2, axis=0)
        stds = jnp.sqrt(var / jnp.maximum(nk, 1e-8) + floor ** 2)
        return w, means, stds

    w, means, stds = jax.lax.fori_loop(0, n_iter, em, (w, means, stds))
    valid = (w > weight_threshold).at[jnp.argmax(w)].set(True)
    return w, means, stds, valid


@partial(jax.jit, static_argnames=("n",))
def sample_mixture(mix, key, n: int):
    w, means, stds, valid = mix
    kc, kn = jax.random.split(key)
    p = jnp.where(valid, w, 0.0)
    p = p / jnp.sum(p)
    comp = jax.random.categorical(kc, jnp.log(jnp.maximum(p, 1e-12)),
                                  shape=(n,))
    return means[comp] + stds[comp] * jax.random.normal(kn, (n,))


def jsd(p, q) -> float:
    """Jensen-Shannon distance, base 2, float64."""
    p = np.asarray(p, np.float64)
    q = np.asarray(q, np.float64)
    p, q = p / max(p.sum(), 1e-12), q / max(q.sum(), 1e-12)
    m = 0.5 * (p + q)

    def kl(a, b):
        a, b = a / max(a.sum(), 1e-12), b / max(b.sum(), 1e-12)
        r = np.log2(np.maximum(a, 1e-12)) - np.log2(np.maximum(b, 1e-12))
        return float(np.sum(np.where(a > 0, a * r, 0.0)))

    return math.sqrt(max(0.5 * (kl(p, m) + kl(q, m)), 0.0))


def wasserstein(u, v) -> float:
    """1-D Wasserstein distance on a common quantile grid."""
    u, v = np.sort(np.asarray(u, np.float64)), np.sort(np.asarray(v,
                                                                  np.float64))
    n = max(len(u), len(v))
    t = (np.arange(n) + 0.5) / n
    return float(np.mean(np.abs(np.quantile(u, t) - np.quantile(v, t))))


def fig4_weights(S, n_rows) -> np.ndarray:
    S = np.asarray(S, np.float64)
    n = np.asarray(n_rows, np.float64)
    col = S.sum(axis=0, keepdims=True)
    Sn = np.where(col > 0, S / np.maximum(col, 1e-12), 1.0 / S.shape[0])
    ss = Sn.sum(axis=1)
    sd = 1.0 - ss / max(ss.sum(), 1e-12) + n / max(n.sum(), 1e-12)
    e = np.exp(sd - sd.max())
    return e / e.sum()


def federation(parts: list[np.ndarray], kinds: list[str], seed: int, *,
               max_modes: int = 10, samples_cap: int = 20_000,
               wd_samples: int = 4096) -> dict:
    """§4.1 and §4.2 for raw client tables ``parts`` (categorical columns
    hold integer ids), with the key layout the harness gives the system
    (stats, init, weighting, model, encode split off ``seed``)."""
    P, Q = len(parts), len(kinds)
    key = jax.random.PRNGKey(seed)
    k_stats, k_init, k_w, _, k_enc = jax.random.split(key, 5)
    n_rows = [int(p.shape[0]) for p in parts]

    client_mix = []
    for i, d in enumerate(parts):
        keys = jax.random.split(jax.random.fold_in(k_stats, i), Q)
        client_mix.append({j: fit_mixture(jnp.asarray(d[:, j], jnp.float32),
                                          keys[j], k=max_modes)
                           for j in range(Q) if kinds[j] == "continuous"})

    supports, global_freq, client_freq = {}, {}, [dict() for _ in parts]
    global_mix = {}
    keys = jax.random.split(k_init, Q)
    total = sum(n_rows)
    draws = [max(1, int(round(samples_cap * n / max(total, 1))))
             for n in n_rows]
    for j, kind in enumerate(kinds):
        if kind == "categorical":
            sup = np.unique(np.concatenate([p[:, j] for p in parts]))
            supports[j] = sup
            counts = np.stack([np.array([(p[:, j] == c).sum() for c in sup],
                                        np.float64) for p in parts])
            tot = counts.sum(axis=0)
            global_freq[j] = tot / max(tot.sum(), 1.0)
            for i in range(P):
                client_freq[i][j] = counts[i] / max(counts[i].sum(), 1.0)
        else:
            ks = jax.random.split(keys[j], P + 1)
            pooled = jnp.concatenate([
                sample_mixture(client_mix[i][j], ks[i], draws[i])
                for i in range(P)])
            global_mix[j] = fit_mixture(pooled, ks[P], k=max_modes)

    S = np.zeros((P, Q), np.float64)
    wkeys = jax.random.split(k_w, P * Q)
    for i in range(P):
        for j, kind in enumerate(kinds):
            if kind == "categorical":
                S[i, j] = jsd(client_freq[i][j], global_freq[j])
                continue
            kij = wkeys[i * Q + j]
            a = np.asarray(sample_mixture(client_mix[i][j], kij, wd_samples))
            b = np.asarray(sample_mixture(global_mix[j],
                                          jax.random.fold_in(kij, 7),
                                          wd_samples))
            lo, scale = b.min(), max(b.max() - b.min(), 1e-9)
            S[i, j] = wasserstein((a - lo) / scale, (b - lo) / scale)

    widths = [len(supports[j]) if kinds[j] == "categorical" else max_modes
              for j in range(Q)]
    encoded = [encode(d, kinds, supports, global_mix,
                      jax.random.fold_in(k_enc, i), max_modes)
               for i, d in enumerate(parts)]
    return {"S": S, "weights": fig4_weights(S, n_rows), "widths": widths,
            "supports": supports, "mixtures": global_mix,
            "encoded": encoded, "n_rows": n_rows}


def encode(table: np.ndarray, kinds, supports, mixtures, key,
           max_modes: int = 10) -> np.ndarray:
    """CTGAN's mode-specific normalisation of one raw table."""
    n, Q = table.shape
    keys = jax.random.split(key, Q)
    cols = []
    for j, kind in enumerate(kinds):
        if kind == "categorical":
            rank = np.searchsorted(supports[j], table[:, j])
            cols.append(np.eye(len(supports[j]), dtype=np.float32)[rank])
            continue
        w, means, stds, valid = (np.asarray(a) for a in mixtures[j])
        g = np.asarray(jax.random.gumbel(keys[j], (n, max_modes),
                                         jnp.float32))
        alpha, mode = _encode_column(jnp.asarray(table[:, j], jnp.float32),
                                     jnp.asarray(means), jnp.asarray(stds),
                                     jnp.asarray(np.where(
                                         valid, np.log(np.maximum(w, 1e-12)),
                                         -1e30), jnp.float32),
                                     jnp.asarray(g))
        cols.append(np.asarray(alpha)[:, None])
        cols.append(np.eye(max_modes, dtype=np.float32)[np.asarray(mode)])
    return np.concatenate(cols, axis=1)


@jax.jit
def _encode_column(x, means, stds, logw, g):
    z = (x[:, None] - means[None]) / stds[None]
    score = -0.5 * z * z - jnp.log(stds)[None] - 0.5 * LOG2PI + logw[None] + g
    mode = jnp.argmax(score, axis=1)
    alpha = jnp.clip((x - means[mode]) / (4.0 * stds[mode]), -1.0, 1.0)
    return alpha, mode
