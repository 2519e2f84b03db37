"""VGM mode-specific normalization Pallas kernels.

The tabular-encoding hot loop of Fed-TGAN/CTGAN: for every cell of a
continuous column, evaluate K Gaussian modes, Gumbel-sample a mode from the
responsibilities, and emit (alpha, one-hot beta).  On a 40k x 30-column
table re-encoded every round this is the dominant client-side preprocessing
cost; it is embarrassingly parallel over rows — ideal VPU work.

Two kernels live here:

``vgm_encode``        — the original single-column kernel (one dispatch per
                        continuous column; rows tiled by ``block_n``).
``vgm_encode_table``  — the fused table-wide kernel: ALL continuous columns
                        in ONE ``pallas_call``.  Per-column mode parameters
                        are packed into ``(Q, Kmax)`` arrays (columns with
                        fewer than Kmax modes carry ``-inf`` log-weights in
                        the padding, so padded modes are never argmax'd) and
                        the grid tiles ``(row_block, column)``.  Each grid
                        cell writes its column's ``[alpha, beta_0..beta_K]``
                        slot of the ``(N, Q*(1+Kmax))`` output, so the
                        per-column ``jnp.concatenate`` of the loop path
                        disappears — a single static gather (fused into the
                        caller's jit) maps slots to the final CTGAN row
                        layout.

Example — two continuous columns with two modes each, packed ``(Q, Kmax)``
params.  Zero Gumbel noise makes the likeliest mode win deterministically,
and a value AT a mode mean normalizes to alpha = 0:

    >>> import jax.numpy as jnp
    >>> from repro.kernels.vgm_encode import vgm_encode_table
    >>> means = jnp.array([[-1.0, 1.0], [0.0, 5.0]])     # (Q=2, Kmax=2)
    >>> stds = jnp.ones((2, 2))
    >>> logw = jnp.zeros((2, 2))
    >>> x = jnp.array([[-1.0, 5.0], [1.0, 0.0]])         # (N=2, Q=2)
    >>> g = jnp.zeros((2, 4))                            # (N, Q*Kmax)
    >>> slots = vgm_encode_table(x, means, stds, logw, g, block_n=2,
    ...                          interpret=True)
    >>> slots.shape                                      # (N, Q*(1+Kmax))
    (2, 6)
    >>> slots[0].tolist()    # row 0: [alpha_0, beta_0..] [alpha_1, beta_1..]
    [0.0, 1.0, 0.0, 0.0, 0.0, 1.0]

Column 0 of row 0 sits at mode 0's mean (-1.0) and column 1 at mode 1's
mean (5.0): both alphas are 0 and the betas one-hot the winning mode.
Columns with fewer than Kmax real modes pad ``log_weights`` with ``-inf``
(see ``tabular.vgm.pack_vgm_params``), which zeroes their win probability.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..tabular.vgm import NEG_INF    # single source of the padding sentinel

_LOG2PI = math.log(2.0 * math.pi)


def _mode_normalize(x, means, stds, logw, g):
    """Shared body of both kernels: Gumbel-argmax mode pick + mode-specific
    normalization.  x (bn, 1); means/stds/logw (1, K); g (bn, K).  Returns
    (alpha (bn,), comp (bn,) int32, onehot (bn, K)); all inputs pre-cast
    to f32."""
    z = (x - means) / stds
    logits = -0.5 * z * z - jnp.log(stds) - 0.5 * _LOG2PI + logw + g
    comp = jnp.argmax(logits, axis=1)                   # (bn,)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == comp[:, None]).astype(jnp.float32)
    mu = jnp.sum(onehot * means, axis=1)
    sd = jnp.sum(onehot * stds, axis=1)
    alpha = jnp.clip((x[:, 0] - mu) / (4.0 * sd), -1.0, 1.0)
    return alpha, comp, onehot


def _vgm_kernel(x_ref, means_ref, stds_ref, logw_ref, gumbel_ref,
                alpha_ref, beta_ref):
    alpha, _, onehot = _mode_normalize(
        x_ref[...].astype(jnp.float32), means_ref[...].astype(jnp.float32),
        stds_ref[...].astype(jnp.float32), logw_ref[...].astype(jnp.float32),
        gumbel_ref[...].astype(jnp.float32))
    alpha_ref[...] = alpha[:, None]
    beta_ref[...] = onehot


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def vgm_encode(x: jnp.ndarray, means: jnp.ndarray, stds: jnp.ndarray,
               log_weights: jnp.ndarray, gumbel: jnp.ndarray, *,
               block_n: int = 1024, interpret: bool = False):
    """x: (N,); means/stds/log_weights: (K,); gumbel: (N, K).
    Returns (alpha (N,), beta (N, K)).  Invalid modes must carry
    log_weights = -inf (the ops wrapper arranges K-padding that way)."""
    N = x.shape[0]
    K = means.shape[0]
    pad_n = (-N) % block_n
    if pad_n:
        x = jnp.pad(x, (0, pad_n))
        gumbel = jnp.pad(gumbel, ((0, pad_n), (0, 0)))
    Np = N + pad_n

    alpha, beta = pl.pallas_call(
        _vgm_kernel,
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((block_n, K), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, K), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Np, 1), jnp.float32),
            jax.ShapeDtypeStruct((Np, K), jnp.float32),
        ],
        interpret=interpret,
    )(x[:, None], means[None, :], stds[None, :], log_weights[None, :], gumbel)
    return alpha[:N, 0], beta[:N]


def _vgm_table_kernel(x_ref, means_ref, stds_ref, logw_ref, gumbel_ref,
                      out_ref):
    """One (row block, column) cell.  The ``[alpha, beta_0..]`` slot tile is
    built with lane selects on the full ``(bn, 1+K)`` tile: lane 0 takes
    alpha, lane 1+k the one-hot of mode k — no lane-offset stores."""
    alpha, comp, _ = _mode_normalize(
        x_ref[...].astype(jnp.float32), means_ref[...].astype(jnp.float32),
        stds_ref[...].astype(jnp.float32), logw_ref[...].astype(jnp.float32),
        gumbel_ref[...].astype(jnp.float32))
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(lane == 0, alpha[:, None],
                             (lane == comp[:, None] + 1).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def vgm_encode_table(x_cols: jnp.ndarray, means: jnp.ndarray,
                     stds: jnp.ndarray, log_weights: jnp.ndarray,
                     gumbel: jnp.ndarray, *, block_n: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """Fused multi-column VGM encode: ONE dispatch for the whole table.

    x_cols: (N, Q) continuous columns; means/stds/log_weights: (Q, Kmax)
    packed per-column mode params (pad unused modes with log_weights=-inf
    and stds=1); gumbel: (N, Q*Kmax) laid out column-major-by-slot (column
    q occupies lanes [q*Kmax, (q+1)*Kmax)).

    Returns slots (N, Q*(1+Kmax)): column q's slot is
    ``[alpha_q, beta_q_0 .. beta_q_{Kmax-1}]`` at offset ``q*(1+Kmax)``.

    Inside, the kernel runs column-major — x ``(Q, N, 1)``, gumbel
    ``(Q, N, Kmax)``, params ``(Q, 1, Kmax)``, slots ``(Q, N, 1+Kmax)`` —
    with the column axis squeezed out of every block, so each block's
    last two dims are (row tile, full width) as Mosaic requires.  The
    transposes around the call fuse into the caller's jit.
    """
    N, Q = x_cols.shape
    K = means.shape[1]
    S = 1 + K
    pad_n = (-N) % block_n
    Np = N + pad_n
    with jax.named_scope("kernel_layout"):
        if pad_n:
            x_cols = jnp.pad(x_cols, ((0, pad_n), (0, 0)))
            gumbel = jnp.pad(gumbel, ((0, pad_n), (0, 0)))
        x_cm = x_cols.T[:, :, None]                          # (Q, Np, 1)
        g_cm = gumbel.reshape(Np, Q, K).transpose(1, 0, 2)   # (Q, Np, K)

    def param_spec():
        return pl.BlockSpec((None, 1, K), lambda i, j: (j, 0, 0))

    slots = pl.pallas_call(
        _vgm_table_kernel,
        grid=(Np // block_n, Q),
        in_specs=[
            pl.BlockSpec((None, block_n, 1), lambda i, j: (j, i, 0)),
            param_spec(), param_spec(), param_spec(),
            pl.BlockSpec((None, block_n, K), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_n, S), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, Np, S), jnp.float32),
        interpret=interpret,
    )(x_cm, means[:, None, :], stds[:, None, :], log_weights[:, None, :],
      g_cm)
    with jax.named_scope("kernel_layout"):
        return slots.transpose(1, 0, 2).reshape(Np, Q * S)[:N]
