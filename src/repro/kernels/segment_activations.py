"""Fused per-span activation Pallas kernel (the CTGAN generator head).

The generator's output layer is a patchwork of per-span activations —
tanh over each VGM alpha scalar, Gumbel-softmax (temperature ``tau``,
optionally straight-through ``hard``) over each mode/category one-hot
span.  The per-span loop in ``gan.ctgan.apply_activations`` issues ~2
dispatches per span (a slice + a softmax) on every generator forward;
after the PR-1/PR-2 fusions of encode and decode it was the last
column-count-proportional dispatch loop on the synthesis hot path.

``segment_activations`` applies ALL spans in ONE ``pallas_call``: spans
are packed into the same padded layout idiom as ``vgm_encode_table`` /
``vgm_decode_table`` — a ``(S, Wmax)`` grid where logit lanes beyond a
span's width carry ``-inf``, so the softmax assigns them exactly zero
mass and the hard argmax can never select them — and the grid tiles
``(row_block, span)``.

``segment_activations_packed`` wraps the kernel and the jnp oracle
(:func:`repro.kernels.ref.segment_activations_ref`) under ONE
``jax.custom_vjp`` whose backward replays the oracle's VJP, so the
straight-through estimator's gradients match the per-span loop on both
routes (the Pallas forward alone would be opaque to autodiff).

Example — one tanh alpha span + one 3-wide one-hot span, applied through
the :func:`repro.kernels.ops.segment_activations` wrapper (which draws
the per-span uniforms and packs/unpacks the ``(S, Wmax)`` layout):

    >>> import jax, jax.numpy as jnp
    >>> from repro.kernels import ops
    >>> from repro.tabular.encoders import SpanInfo
    >>> spans = (SpanInfo(0, 1, "tanh", 0, False),
    ...          SpanInfo(1, 3, "softmax", 0, True))
    >>> logits = jnp.array([[0.0, 2.0, -1.0, 0.5]])
    >>> out = ops.segment_activations(logits, spans, jax.random.PRNGKey(0),
    ...                               0.2, hard=True, use_pallas=False)
    >>> out.shape
    (1, 4)
    >>> float(out[0, 0])                    # tanh span: tanh(0.0)
    0.0
    >>> sorted(out[0, 1:].tolist())         # hard draw: a valid one-hot
    [0.0, 0.0, 1.0]

The one-hot span went through Gumbel-softmax at tau=0.2 with the
straight-through ``hard`` estimator: the forward value is exactly
one-hot, while gradients flow through the soft sample.  Padded lanes
(spans narrower than Wmax) carry ``-inf`` logits, take exactly zero
softmax mass, and can never be the argmax.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import ref
from .ref import GUMBEL_EPS


@dataclasses.dataclass(frozen=True)
class SpanLayout:
    """Static packing of an encoded-row span list into ``(S, Wmax)``.

    ``pack_src``/``pack_pad`` gather the (B, dim) row into the padded
    (B, S*Wmax) lane layout (padded lanes read position 0 and are then
    masked to ``-inf``); ``unpack_src`` is the inverse gather — because
    spans tile the row contiguously in order, the p-th live lane IS
    encoded position p.  ``kinds`` carries 1.0 rows for tanh spans.
    """
    spans: tuple
    wmax: int
    dim: int
    # host numpy (NOT jnp): the builder may first run inside a jit trace,
    # where materializing device constants would leak tracers.
    pack_src: np.ndarray       # (S*Wmax,) int32
    pack_pad: np.ndarray       # (S*Wmax,) bool
    unpack_src: np.ndarray     # (dim,) int32
    kinds: np.ndarray          # (S, Wmax) float32


@functools.lru_cache(maxsize=None)
def build_span_layout(spans: tuple) -> SpanLayout:
    """Build (once per span tuple) the packed activation layout."""
    spans = tuple(spans)
    S = len(spans)
    wmax = max(s.width for s in spans)
    pack_src = np.zeros(S * wmax, np.int32)
    pack_pad = np.ones(S * wmax, bool)
    kinds = np.zeros((S, wmax), np.float32)
    dim = 0
    for i, s in enumerate(spans):
        assert s.start == dim, "spans must tile the encoded row contiguously"
        base = i * wmax
        pack_src[base:base + s.width] = s.start + np.arange(s.width)
        pack_pad[base:base + s.width] = False
        if s.activation == "tanh":
            kinds[i] = 1.0
        dim += s.width
    unpack_src = np.flatnonzero(~pack_pad).astype(np.int32)
    return SpanLayout(spans=spans, wmax=wmax, dim=dim, pack_src=pack_src,
                      pack_pad=pack_pad, unpack_src=unpack_src, kinds=kinds)


def _segment_act_block(x, u, kinds, tau, hard):
    """Shared body: x/u (bn, W) packed logits and uniforms for one span,
    kinds (1, W) tanh flag row.  Mirrors ``apply_activations`` op-for-op
    (jax.nn.softmax's max/exp/sum/div chain, the Gumbel transform with the
    shared ``GUMBEL_EPS``, the ST expression's association) so the fused
    path is bit-identical to the per-span loop."""
    g = -jnp.log(-jnp.log(u + GUMBEL_EPS) + GUMBEL_EPS)
    z = (x + g) / tau
    m = jnp.max(z, axis=1, keepdims=True)
    e = jnp.exp(z - m)
    y = e / jnp.sum(e, axis=1, keepdims=True)
    if hard:
        comp = jnp.argmax(y, axis=1)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
                  == comp[:, None]).astype(jnp.float32)
        y = (onehot - y) + y              # ST forward, loop's association
    return jnp.where(kinds > 0.5, jnp.tanh(x), y)


def _segment_act_kernel(x_ref, u_ref, kinds_ref, out_ref, *, tau, hard):
    out_ref[...] = _segment_act_block(
        x_ref[...].astype(jnp.float32), u_ref[...].astype(jnp.float32),
        kinds_ref[...].astype(jnp.float32), tau, hard)


@functools.partial(jax.jit,
                   static_argnames=("tau", "hard", "block_n", "interpret"))
def segment_activations(packed_x: jnp.ndarray, packed_u: jnp.ndarray,
                        kinds: jnp.ndarray, *, tau: float,
                        hard: bool = False, block_n: int = 1024,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused whole-row activations: ONE dispatch for every span.

    packed_x: (N, S*Wmax) logits in span-slot layout, ``-inf`` in padded
    lanes; packed_u: (N, S*Wmax) per-span uniforms (padded lanes must be
    in (0, 1), e.g. 0.5 — their Gumbels stay finite and ``-inf`` logits
    zero them out); kinds: (S, Wmax) rows of 1.0 for tanh spans.

    Returns packed activations (N, S*Wmax): tanh rows hold tanh(x) in
    live lanes, softmax rows hold the Gumbel-softmax (ST one-hot when
    ``hard``) with exactly zero mass on padded lanes.

    Inside, the kernel runs span-major — ``(S, N, Wmax)`` with the span
    axis squeezed out of every block — so each block's last two dims are
    (row tile, full span width) as Mosaic requires.
    """
    N = packed_x.shape[0]
    S, W = kinds.shape
    pad_n = (-N) % block_n
    Np = N + pad_n

    def span_major(a):                                    # (S, Np, W)
        return a.reshape(Np, S, W).transpose(1, 0, 2)

    with jax.named_scope("kernel_layout"):
        if pad_n:
            packed_x = jnp.pad(packed_x, ((0, pad_n), (0, 0)))
            packed_u = jnp.pad(packed_u, ((0, pad_n), (0, 0)),
                               constant_values=0.5)
        x_sm, u_sm = span_major(packed_x), span_major(packed_u)

    def rows_block():
        return pl.BlockSpec((None, block_n, W), lambda i, j: (j, i, 0))

    out = pl.pallas_call(
        functools.partial(_segment_act_kernel, tau=tau, hard=hard),
        grid=(Np // block_n, S),
        in_specs=[rows_block(), rows_block(),
                  pl.BlockSpec((None, 1, W), lambda i, j: (j, 0, 0))],
        out_specs=rows_block(),
        out_shape=jax.ShapeDtypeStruct((S, Np, W), jnp.float32),
        interpret=interpret,
    )(x_sm, u_sm, kinds[:, None, :])
    with jax.named_scope("kernel_layout"):
        return out.transpose(1, 0, 2).reshape(Np, S * W)[:N]


def _packed_primal(packed_x, packed_u, kinds, tau, hard, use_pallas,
                   interpret, block_n):
    if use_pallas:
        return segment_activations(packed_x, packed_u, kinds, tau=tau,
                                   hard=hard, block_n=block_n,
                                   interpret=interpret)
    return ref.segment_activations_ref(packed_x, packed_u, kinds, tau, hard)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def segment_activations_packed(packed_x, packed_u, kinds, tau, hard,
                               use_pallas, interpret, block_n):
    """Differentiable packed activations: kernel or ref forward, with the
    jnp oracle's VJP as the backward on BOTH routes — gradients therefore
    match ``jax.grad`` through the per-span loop, including the straight-
    through estimator in ``hard`` mode."""
    return _packed_primal(packed_x, packed_u, kinds, tau, hard, use_pallas,
                          interpret, block_n)


def _packed_fwd(packed_x, packed_u, kinds, tau, hard, use_pallas, interpret,
                block_n):
    out = _packed_primal(packed_x, packed_u, kinds, tau, hard, use_pallas,
                         interpret, block_n)
    return out, (packed_x, packed_u, kinds)


def _packed_bwd(tau, hard, use_pallas, interpret, block_n, residuals, ct):
    packed_x, packed_u, kinds = residuals
    _, vjp = jax.vjp(
        lambda x: ref.segment_activations_ref(x, packed_u, kinds, tau, hard),
        packed_x)
    return vjp(ct)[0], jnp.zeros_like(packed_u), jnp.zeros_like(kinds)


segment_activations_packed.defvjp(_packed_fwd, _packed_bwd)
