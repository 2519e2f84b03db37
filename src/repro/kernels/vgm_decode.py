"""Fused VGM mode-specific DECODE Pallas kernel.

The inverse of :mod:`.vgm_encode`'s fused table kernel: generator output
arrives as per-column ``[alpha, beta_0..beta_{Kmax-1}]`` slots (the same
``(Q, Kmax)``-packed layout, padded beta lanes carrying ``-inf`` so the
mode argmax can never land on them), and every continuous column is
inverted — argmax mode select + mode-specific denormalization
``clip(alpha) * 4 * sigma_k + mu_k`` — in ONE ``pallas_call`` instead of
one ``decode_column`` dispatch per column.  Grid tiles ``(row_block,
column)`` exactly like the encode kernel, so on TPU both directions of the
synthesis pipeline are a single Mosaic program each.

Example — one column with two modes; the ``-inf`` beta lane can never win
the argmax, so each row denormalizes against its selected live mode:

    >>> import jax.numpy as jnp
    >>> from repro.kernels.vgm_decode import vgm_decode_table
    >>> from repro.tabular.vgm import NEG_INF
    >>> means = jnp.array([[0.0, 10.0]])                 # (Q=1, Kmax=2)
    >>> stds = jnp.array([[1.0, 2.0]])
    >>> slots = jnp.array([[0.5, NEG_INF, 0.0],          # argmax -> mode 1
    ...                    [-0.25, 0.0, NEG_INF]])       # argmax -> mode 0
    >>> vgm_decode_table(slots, means, stds, block_n=2,
    ...                  interpret=True).tolist()
    [[14.0], [-1.0]]

Row 0 inverts ``alpha * 4 * sigma_1 + mu_1 = 0.5 * 4 * 2 + 10``; row 1
``-0.25 * 4 * 1 + 0``.  This is bit-identical to the per-column
``tabular.vgm.decode_column`` oracle (same clip/multiply order).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mode_denormalize(slots, means, stds):
    """Shared body: slots (bn, 1+K) = [alpha, beta...]; means/stds (1, K).
    Returns (bn,) raw values.  Matches tabular.vgm.decode_column op-for-op
    (same clip / multiply order) so the fused path is bit-identical.

    The beta argmax runs over the whole slot tile with the alpha lane
    masked to a true ``-inf`` (below the ``NEG_INF`` padding sentinel, so
    it never ties a padded lane) instead of slicing lanes ``1:`` — an
    unaligned lane slice Mosaic would have to shift."""
    alpha = slots[:, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, slots.shape, 1)
    winner = jnp.argmax(jnp.where(lane == 0, -jnp.inf, slots), axis=1)
    # a row of all-(-inf) betas leaves lane 0 winning: mode 0, as the
    # ref's argmax over the betas alone picks
    comp = jnp.maximum(winner - 1, 0)
    onehot = (jax.lax.broadcasted_iota(jnp.int32, means.shape, 1)
              == comp[:, None]).astype(jnp.float32)
    mu = jnp.sum(onehot * means, axis=1)
    sd = jnp.sum(onehot * stds, axis=1)
    return jnp.clip(alpha, -1.0, 1.0) * 4.0 * sd + mu


def _vgm_decode_kernel(slots_ref, means_ref, stds_ref, out_ref):
    out_ref[...] = _mode_denormalize(
        slots_ref[...].astype(jnp.float32),
        means_ref[...].astype(jnp.float32),
        stds_ref[...].astype(jnp.float32))[:, None]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def vgm_decode_table(slots: jnp.ndarray, means: jnp.ndarray,
                     stds: jnp.ndarray, *, block_n: int = 1024,
                     interpret: bool = False) -> jnp.ndarray:
    """Fused multi-column VGM decode: ONE dispatch for the whole table.

    slots: (N, Q*(1+Kmax)) — column q's slot ``[alpha_q, beta_q_0..]`` at
    offset ``q*(1+Kmax)`` (the encode kernel's output layout; padded beta
    lanes must hold ``-inf`` so argmax never selects them);
    means/stds: (Q, Kmax) packed per-column mode params (padding: mean 0,
    std 1 — never selected, keeps the arithmetic finite).

    Returns x_cols (N, Q) raw continuous columns, bit-identical to running
    ``tabular.vgm.decode_column`` per column on the unpacked spans.

    Inside, the kernel runs column-major like the encode kernel: slots
    ``(Q, N, 1+Kmax)``, params ``(Q, 1, Kmax)``, output ``(Q, N, 1)``,
    with the column axis squeezed out of every block.
    """
    N = slots.shape[0]
    Q, K = means.shape
    S = 1 + K
    pad_n = (-N) % block_n
    Np = N + pad_n
    with jax.named_scope("kernel_layout"):
        if pad_n:
            slots = jnp.pad(slots, ((0, pad_n), (0, 0)))
        slots_cm = slots.reshape(Np, Q, S).transpose(1, 0, 2)  # (Q, Np, S)

    out = pl.pallas_call(
        _vgm_decode_kernel,
        grid=(Np // block_n, Q),
        in_specs=[
            pl.BlockSpec((None, block_n, S), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, 1, K), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((None, 1, K), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_n, 1), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, Np, 1), jnp.float32),
        interpret=interpret,
    )(slots_cm, means[:, None, :], stds[:, None, :])
    with jax.named_scope("kernel_layout"):
        return out[:, :N, 0].T
