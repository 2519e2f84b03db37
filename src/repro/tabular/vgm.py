"""Variational-Gaussian-Mixture (VGM) encoder for continuous columns.

CTGAN / Fed-TGAN fit a BayesianGaussianMixture with up to ``max_modes``
components per continuous column, prune insignificant components, and use
the surviving modes for mode-specific normalization.  We implement the same
behaviour as a JAX EM-fitted GMM with a Dirichlet-style weight floor: modes
whose mixture weight falls below ``weight_threshold`` are pruned, which is
the operative property Fed-TGAN relies on (sklearn's variational prior
likewise drives unused components' weights to ~0).

All functions are pure and jit-friendly; EM runs as a ``lax.scan``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

_LOG2PI = float(np.log(2.0 * np.pi))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class VGMParams:
    """Parameters of a fitted per-column Gaussian mixture.

    ``valid`` masks the modes that survived pruning.  Shapes are static at
    ``max_modes`` so the pytree is jit/shard friendly.
    """

    weights: jnp.ndarray  # (K,)
    means: jnp.ndarray    # (K,)
    stds: jnp.ndarray     # (K,)
    valid: jnp.ndarray    # (K,) bool

    def tree_flatten(self):
        return (self.weights, self.means, self.stds, self.valid), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_modes(self) -> jnp.ndarray:
        return jnp.sum(self.valid.astype(jnp.int32))


def _log_prob_matrix(x: jnp.ndarray, means: jnp.ndarray, stds: jnp.ndarray,
                     log_weights: jnp.ndarray) -> jnp.ndarray:
    """(N, K) log p(x_i, z=k)."""
    z = (x[:, None] - means[None, :]) / stds[None, :]
    log_pdf = -0.5 * (z * z) - jnp.log(stds)[None, :] - 0.5 * _LOG2PI
    return log_pdf + log_weights[None, :]


@partial(jax.jit, static_argnames=("max_modes", "n_iter"))
def fit_vgm(x: jnp.ndarray, key: jax.Array, *, max_modes: int = 10,
            n_iter: int = 60, weight_threshold: float = 5e-3) -> VGMParams:
    """Fit a GMM to 1-D data ``x`` via EM with weight-floor pruning.

    Initialization: quantile-spread means (deterministic given data) plus a
    tiny key-derived jitter to break ties on constant data.
    """
    obs.counter("jit_traces")["vgm.fit"] += 1
    x = x.astype(jnp.float32)
    n = x.shape[0]
    data_std = jnp.maximum(jnp.std(x), 1e-6)

    qs = jnp.linspace(0.02, 0.98, max_modes)
    means0 = jnp.quantile(x, qs)
    means0 = means0 + 1e-4 * data_std * jax.random.normal(key, (max_modes,))
    stds0 = jnp.full((max_modes,), data_std)
    weights0 = jnp.full((max_modes,), 1.0 / max_modes)

    min_std = 1e-4 * data_std + 1e-9

    def em_step(carry, _):
        weights, means, stds = carry
        log_w = jnp.log(jnp.maximum(weights, 1e-12))
        log_joint = _log_prob_matrix(x, means, stds, log_w)      # (N, K)
        log_norm = jax.scipy.special.logsumexp(log_joint, axis=1, keepdims=True)
        resp = jnp.exp(log_joint - log_norm)                     # (N, K)
        nk = jnp.sum(resp, axis=0)                               # (K,)
        # Dirichlet-style floor: keeps dead components numerically alive but
        # with ~zero weight, mirroring the variational prior's behaviour.
        new_weights = (nk + 1e-6) / (n + max_modes * 1e-6)
        new_means = jnp.sum(resp * x[:, None], axis=0) / jnp.maximum(nk, 1e-8)
        var = jnp.sum(resp * (x[:, None] - new_means[None, :]) ** 2, axis=0)
        new_stds = jnp.sqrt(var / jnp.maximum(nk, 1e-8) + min_std ** 2)
        return (new_weights, new_means, new_stds), None

    (weights, means, stds), _ = jax.lax.scan(
        em_step, (weights0, means0, stds0), None, length=n_iter)

    valid = weights > weight_threshold
    # Guarantee at least one valid mode.
    best = jnp.argmax(weights)
    valid = valid.at[best].set(True)
    return VGMParams(weights=weights, means=means, stds=stds, valid=valid)


@partial(jax.jit, static_argnames=("n",))
def sample_vgm(params: VGMParams, key: jax.Array, n: int) -> jnp.ndarray:
    """Draw ``n`` samples from a fitted VGM (used by the federator to
    bootstrap client distributions, Fed-TGAN §4.1 step 1)."""
    kc, kn = jax.random.split(key)
    w = jnp.where(params.valid, params.weights, 0.0)
    w = w / jnp.sum(w)
    comp = jax.random.categorical(kc, jnp.log(jnp.maximum(w, 1e-12)), shape=(n,))
    eps = jax.random.normal(kn, (n,))
    return params.means[comp] + params.stds[comp] * eps


NEG_INF = -1e30


def kernel_log_weights(params: VGMParams) -> jnp.ndarray:
    """Log mixture weights in the kernel convention: pruned modes carry
    ``-inf`` (well, -1e30) so a Gumbel-argmax can never select them."""
    return jnp.where(params.valid,
                     jnp.log(jnp.maximum(params.weights, 1e-12)), NEG_INF)


def pack_vgm_params(vgms: Sequence[VGMParams], kmax: int | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pack per-column VGMs into ``(Q, Kmax)`` arrays for the fused
    table-wide kernel.  Columns with fewer than Kmax modes are padded with
    ``-inf`` log-weights (never selected), mean 0 and std 1 (keeps the
    Gaussian log-pdf finite in the padding)."""
    ks = [int(p.means.shape[0]) for p in vgms]
    kmax = max(ks, default=0) if kmax is None else kmax
    Q = len(vgms)
    means = jnp.zeros((Q, kmax), jnp.float32)
    stds = jnp.ones((Q, kmax), jnp.float32)
    logw = jnp.full((Q, kmax), NEG_INF, jnp.float32)
    for q, (p, k) in enumerate(zip(vgms, ks)):
        means = means.at[q, :k].set(p.means.astype(jnp.float32))
        stds = stds.at[q, :k].set(p.stds.astype(jnp.float32))
        logw = logw.at[q, :k].set(kernel_log_weights(p))
    return means, stds, logw


@jax.jit
def encode_column(x: jnp.ndarray, params: VGMParams,
                  key: jax.Array) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CTGAN mode-specific normalization.

    Returns ``alpha`` (N,) scalar in [-1,1] (value normalized within its
    sampled mode: (x-mu_k)/(4 sigma_k)) and ``beta`` (N, K) one-hot mode
    indicator.  The mode is *sampled* from the responsibilities, exactly as
    in CTGAN's training-time encoding.
    """
    log_w = jnp.log(jnp.where(params.valid, jnp.maximum(params.weights, 1e-12), 1e-12))
    log_joint = _log_prob_matrix(x.astype(jnp.float32), params.means, params.stds, log_w)
    comp = jax.random.categorical(key, log_joint, axis=1)        # (N,)
    mu = params.means[comp]
    sd = params.stds[comp]
    alpha = jnp.clip((x - mu) / (4.0 * sd), -1.0, 1.0)
    beta = jax.nn.one_hot(comp, params.means.shape[0])
    return alpha, beta


@jax.jit
def decode_column(alpha: jnp.ndarray, beta: jnp.ndarray,
                  params: VGMParams) -> jnp.ndarray:
    """Invert :func:`encode_column` (used on generator output)."""
    comp = jnp.argmax(beta, axis=1)
    mu = params.means[comp]
    sd = params.stds[comp]
    return jnp.clip(alpha, -1.0, 1.0) * 4.0 * sd + mu


def merge_client_vgms(client_params: list[VGMParams], client_rows: list[int],
                      key: jax.Array, *, max_modes: int = 10,
                      samples_cap: int = 20_000) -> VGMParams:
    """Federator-side global VGM fit (Fed-TGAN §4.1 step 1, continuous),
    ONE column at a time.

    Bootstraps ``N_i``-proportional samples from every client's local VGM and
    refits a single global VGM on the union — never touching client data.
    Kept as the per-column oracle for :func:`merge_client_vgms_table`.
    """
    total = sum(client_rows)
    keys = jax.random.split(key, len(client_params) + 1)
    parts = []
    for p, n_i, k in zip(client_params, client_rows, keys[:-1]):
        n_draw = max(1, int(round(samples_cap * n_i / max(total, 1))))
        parts.append(sample_vgm(p, k, n_draw))
    data = jnp.concatenate(parts)
    return fit_vgm(data, keys[-1], max_modes=max_modes)


def merge_client_vgms_table(client_params: Sequence[Sequence[VGMParams]],
                            client_rows: Sequence[int], keys: jnp.ndarray, *,
                            max_modes: int = 10,
                            samples_cap: int = 20_000) -> VGMParams:
    """Vmapped federator merge: ALL continuous columns in one pass.

    Reuses the packed ``(Q, K)`` layout idea from the fused kernels:
    per-client params stack into ``(Q, P, K)`` arrays and the
    bootstrap-sample + refit pipeline of :func:`merge_client_vgms` vmaps
    over the column axis instead of looping in Python.  ``client_params``
    is indexed ``[client][column]`` and every entry must share the same
    ``K`` (callers group columns by ``max_modes``); ``keys`` carries one
    per-column PRNG key, so each column sees EXACTLY the same randoms as
    the per-column loop — the two paths are bit-identical.

    Returns a :class:`VGMParams` pytree with a leading column axis.
    """
    P = len(client_params)
    Q = len(client_params[0])
    total = sum(client_rows)
    n_draws = [max(1, int(round(samples_cap * n_i / max(total, 1))))
               for n_i in client_rows]

    def pack(f):                                      # (Q, P, K)
        return jnp.stack([jnp.stack([f(client_params[i][q])
                                     for i in range(P)]) for q in range(Q)])
    weights = pack(lambda p: p.weights)
    means = pack(lambda p: p.means)
    stds = pack(lambda p: p.stds)
    valid = pack(lambda p: p.valid)

    def merge_one(w_pk, m_pk, s_pk, v_pk, key):
        ks = jax.random.split(key, P + 1)
        parts = [sample_vgm(VGMParams(w_pk[i], m_pk[i], s_pk[i], v_pk[i]),
                            ks[i], n_draws[i]) for i in range(P)]
        return fit_vgm(jnp.concatenate(parts), ks[P], max_modes=max_modes)

    return jax.vmap(merge_one)(weights, means, stds, valid, keys)
