"""Jit'd public wrappers for the Pallas kernels with jnp fallbacks.

``use_pallas=None`` auto-routes: on TPU every fused stage lowers to
Mosaic; on CPU it runs the bit-identical jnp ref, and ``interpret=True``
forces the kernel through the Pallas interpreter for kernel-path tests.
``use_pallas=False`` routes to the ref oracles so every call site can be
flipped for A/B testing.
"""
from __future__ import annotations

import collections
import contextlib

import jax
import jax.numpy as jnp

from .. import obs
from . import ref
from .flash_attention import flash_attention as _flash
from .mlstm_chunk import mlstm_chunk as _mlstm_chunk
from .segment_activations import (build_span_layout,
                                  segment_activations_packed)
from .vgm_decode import vgm_decode_table as _vgm_decode_table
from .vgm_encode import vgm_encode as _vgm_encode
from .vgm_encode import vgm_encode_table as _vgm_encode_table
from .weighted_agg import weighted_agg as _weighted_agg

_ON_TPU = jax.default_backend() == "tpu"

# The decode ref runs under jit (unlike the other eager refs): the fused
# decode must bit-match the jitted per-column ``decode_column`` oracle, and
# XLA's FMA contraction of ``alpha * 4 * sd + mu`` only happens inside jit.
_vgm_decode_table_ref = jax.jit(ref.vgm_decode_table_ref)

# The merge ref is jitted for the same reason: the fed layer asserts the
# fused federator merge bit-matches the scaled-sum oracle, which means
# both routes must see identical XLA contraction decisions.
_weighted_agg_ref = jax.jit(ref.weighted_agg_ref)

# Batched (per-edge) twins for the hierarchical merge tier: one vmapped
# kernel/oracle call merges every edge aggregator's stack at once.
_weighted_agg_edges_ref = jax.jit(jax.vmap(ref.weighted_agg_ref))

# Backend resolutions per wrapper call, keyed ``<stage>`` (Pallas route)
# or ``<stage>_ref`` (jnp route).  A wrapper called eagerly resolves once
# per call, which is once per device dispatch; under ``jit`` the wrapper
# runs only while JAX traces, so the count is per trace, however often
# the compiled program then runs.  Benchmarks use it to prove the fused
# encode path issues ONE kernel where the per-column loop issues Q_cont.
# Reset with ``DISPATCH_COUNTS.clear()`` or ``obs.reset()``.
DISPATCH_COUNTS: collections.Counter = obs.counter("kernel_routes")

# Pallas-route calls that ran in the interpreter (per stage, counted like
# DISPATCH_COUNTS).  On the chip this must stay empty: a kernel quietly
# interpreted there would run the stage on the host while the run still
# reports the Pallas route.
INTERPRET_COUNTS: collections.Counter = obs.counter("kernel_interpret")

# Row-tile cap for the TPU kernels (VMEM-sized VPU tiles).
_MAX_ROW_TILE = 1024


def _route(stage: str, use_pallas, interpret):
    """Resolve one call's backend and count it in ``DISPATCH_COUNTS``.

    Returns ``None`` for the jnp-ref route, else the ``interpret`` flag of
    the Pallas route (the kernel on TPU, or whenever ``interpret`` is
    requested explicitly; the ref on CPU, where interpret-mode emulation
    is pure overhead)."""
    if use_pallas is None:
        use_pallas = _ON_TPU or interpret is not None
    if not use_pallas:
        DISPATCH_COUNTS[stage + "_ref"] += 1
        return None
    DISPATCH_COUNTS[stage] += 1
    interp = (not _ON_TPU) if interpret is None else bool(interpret)
    if interp:
        INTERPRET_COUNTS[stage] += 1
    return interp


def row_tile(n: int) -> int:
    """TPU row tile for ``n`` rows: the fewest blocks of at most 1024
    rows, each a multiple of 8 (the sublane tile).  A 500-row batch runs
    as one 504-row block and 40,000 rows as 40 blocks of 1000; the rows
    padded past ``n`` stay under 8 per block."""
    n = max(int(n), 1)
    per_block = -(-n // -(-n // _MAX_ROW_TILE))  # ceil(n / blocks)
    return -(-per_block // 8) * 8


def _block_rows(n: int, interp: bool, block_n):
    """Row tile of a call: ``block_n`` if given, else the whole table in
    interpret mode (per-grid-cell emulation overhead dominates there) and
    :func:`row_tile` on the chip."""
    if block_n is not None:
        return block_n
    return max(int(n), 1) if interp else row_tile(n)


@contextlib.contextmanager
def dispatch_scope():
    """Attribute kernel dispatches to one code region without resetting
    the global counter.

    Benchmarks own the whole process and may ``DISPATCH_COUNTS.clear()``;
    the serving path cannot — several requests (and the warm-up trainer)
    interleave on one counter.  The scope yields a ``Counter`` that is
    filled with this region's dispatch deltas on exit:

        with ops.dispatch_scope() as d:
            plan.decode(encoded)
        assert stage_dispatches(d, "vgm_decode_table") == 1
    """
    before = DISPATCH_COUNTS.copy()
    scoped: collections.Counter = collections.Counter()
    try:
        yield scoped
    finally:
        for k, v in DISPATCH_COUNTS.items():
            delta = v - before.get(k, 0)
            if delta:
                scoped[k] = delta


def stage_dispatches(counts, stage: str) -> int:
    """Total dispatches for one pipeline stage, summed across backend
    routes (``<stage>`` Pallas + ``<stage>_ref`` jnp oracle), so callers
    assert the one-dispatch-per-stage contract independently of where
    the auto-routing sent the call."""
    return sum(v for k, v in counts.items() if k == stage or
               k == stage + "_ref")


def flash_attention(q, k, v, *, causal=True, window=None,
                    use_pallas=True, interpret=None, **kw):
    if not use_pallas:
        return ref.attention_ref(q, k, v, causal=causal, window=window)
    interp = (not _ON_TPU) if interpret is None else interpret
    return _flash(q, k, v, causal=causal, window=window,
                  interpret=interp, **kw)


def vgm_encode(x, params, key, *, use_pallas=None, interpret=None,
               block_n=1024):
    """Drop-in for tabular.vgm.encode_column: params is a VGMParams; the
    Gumbel noise is drawn here so kernel and ref see identical randoms.

    ``use_pallas=None`` auto-routes: the kernel on TPU (or whenever
    ``interpret`` is requested explicitly), the jnp reference on CPU where
    interpret-mode emulation is pure overhead.  Both are bit-identical."""
    from ..tabular.vgm import kernel_log_weights
    K = params.means.shape[0]
    logw = kernel_log_weights(params)
    gumbel = jax.random.gumbel(key, (x.shape[0], K), jnp.float32)
    interp = _route("vgm_encode", use_pallas, interpret)
    if interp is None:
        return ref.vgm_encode_ref(x, params.means, params.stds, logw, gumbel)
    return _vgm_encode(x, params.means, params.stds, logw, gumbel,
                       block_n=block_n, interpret=interp)


def vgm_encode_table(x_cols, means, stds, log_weights, gumbel, *,
                     use_pallas=None, interpret=None, block_n=None):
    """Fused table-wide VGM encode: all continuous columns in ONE kernel
    dispatch.  Packed ``(Q, Kmax)`` params (see tabular.vgm.pack_vgm_params)
    and pre-drawn gumbel (N, Q*Kmax); returns slots (N, Q*(1+Kmax)).

    ``use_pallas=None`` auto-routes like :func:`vgm_encode`.  ``block_n=None``
    picks the row tile: :func:`row_tile` on TPU; the whole table in
    interpret mode, where per-grid-cell emulation overhead dominates and
    one row block per column is fastest."""
    interp = _route("vgm_encode_table", use_pallas, interpret)
    if interp is None:
        return ref.vgm_encode_table_ref(x_cols, means, stds, log_weights,
                                        gumbel)
    return _vgm_encode_table(x_cols, means, stds, log_weights, gumbel,
                             block_n=_block_rows(x_cols.shape[0], interp,
                                                 block_n),
                             interpret=interp)


def vgm_decode_table(slots, means, stds, *, use_pallas=None, interpret=None,
                     block_n=None):
    """Fused table-wide VGM decode: all continuous columns inverted in ONE
    kernel dispatch.  ``slots`` is the encode kernel's output layout
    (N, Q*(1+Kmax)) with -inf in padded beta lanes; means/stds the packed
    ``(Q, Kmax)`` params.  Returns raw columns (N, Q).

    ``use_pallas=None`` auto-routes like :func:`vgm_encode_table`, and
    ``block_n=None`` picks the same row tile policy."""
    interp = _route("vgm_decode_table", use_pallas, interpret)
    if interp is None:
        return _vgm_decode_table_ref(slots, means, stds)
    return _vgm_decode_table(slots, means, stds,
                             block_n=_block_rows(slots.shape[0], interp,
                                                 block_n),
                             interpret=interp)


def segment_activations(logits, spans, key, tau, hard=False, *,
                        use_pallas=None, interpret=None, block_n=None):
    """Drop-in for gan.ctgan.apply_activations: tanh + Gumbel-softmax over
    the whole encoded row layout in ONE kernel dispatch instead of ~2 per
    span.  Differentiable (custom VJP matches ``jax.grad`` through the
    per-span loop, ST estimator included).

    The per-span uniforms are drawn here from the SAME
    ``jax.random.split(key, len(spans))`` streams as the loop — span i
    draws with key i at shape (N, w_i), padded to Wmax — so kernel, ref,
    and loop see identical randoms and agree bit-for-bit on values.

    ``use_pallas=None`` auto-routes like :func:`vgm_encode_table`, and
    ``block_n=None`` picks the same row tile policy."""
    layout = build_span_layout(tuple(spans))
    n = logits.shape[0]
    keys = jax.random.split(key, len(layout.spans))
    us = []
    for i, s in enumerate(layout.spans):
        if s.activation == "tanh":
            us.append(jnp.full((n, layout.wmax), 0.5, jnp.float32))
        else:
            u = jax.random.uniform(keys[i], (n, s.width), jnp.float32)
            us.append(jnp.pad(u, ((0, 0), (0, layout.wmax - s.width)),
                              constant_values=0.5))
    with jax.named_scope("kernel_layout"):
        packed_u = jnp.concatenate(us, axis=1)
        packed_x = jnp.where(layout.pack_pad[None, :], -jnp.inf,
                             jnp.take(logits.astype(jnp.float32),
                                      layout.pack_src, axis=1))
    tau, hard = float(tau), bool(hard)
    interp = _route("segment_activations", use_pallas, interpret)
    if interp is None:
        out = segment_activations_packed(packed_x, packed_u, layout.kinds,
                                         tau, hard, False, False, 0)
    else:
        out = segment_activations_packed(packed_x, packed_u, layout.kinds,
                                         tau, hard, True, interp,
                                         _block_rows(n, interp, block_n))
    with jax.named_scope("kernel_layout"):
        return jnp.take(out, layout.unpack_src, axis=1)


def mlstm_chunk(q, k, v, log_f, log_i, *, use_pallas=True, interpret=None,
                chunk=128):
    """Chunkwise mLSTM hidden states (pre-o-gate); q pre-scaled."""
    if not use_pallas:
        return ref.mlstm_chunk_ref(q, k, v, log_f, log_i)
    interp = (not _ON_TPU) if interpret is None else interpret
    return _mlstm_chunk(q, k, v, log_f, log_i, chunk=chunk, interpret=interp)


def weighted_average_flat(stacked, weights, *, use_pallas=None,
                          interpret=None, block_d=16_384):
    """Fused federator merge: stacked (P, D) client vectors -> (D,) merged.

    ``use_pallas=None`` auto-routes like :func:`vgm_encode_table` (Pallas
    kernel on TPU, jitted jnp oracle on CPU — bit-identical), and every
    call counts toward the one-merge-dispatch-per-round contract the fed
    layer asserts (``weighted_agg`` / ``weighted_agg_ref`` in
    ``DISPATCH_COUNTS``)."""
    interp = _route("weighted_agg", use_pallas, interpret)
    if interp is None:
        return _weighted_agg_ref(stacked, weights)
    return _weighted_agg(stacked, weights, block_d=block_d, interpret=interp)


def weighted_average_edges(stacked, weights, *, use_pallas=None,
                           interpret=None, block_d=16_384):
    """Edge tier of the hierarchical federator merge: (E, C, D) per-edge
    client stacks x (E, C) weights -> (E, D) per-edge merged vectors, ALL
    edges in ONE dispatch (the kernel vmapped over the edge axis; same
    in-kernel defensive normalization per edge — an all-zero edge merges
    to exact zeros).

    ``use_pallas=None`` auto-routes like :func:`weighted_average_flat`,
    and the call counts ONCE toward the one-merge-dispatch-per-tier
    contract (``weighted_agg`` / ``weighted_agg_ref``)."""
    interp = _route("weighted_agg", use_pallas, interpret)
    if interp is None:
        return _weighted_agg_edges_ref(stacked, weights)
    return jax.vmap(
        lambda s, w: _weighted_agg(s, w, block_d=block_d,
                                   interpret=interp))(stacked, weights)


def weighted_average_tree(stacked_tree, weights, **kw):
    """Pytree version of the federator merge (leaves carry a leading client
    axis P) — the kernel-backed twin of core.aggregation.weighted_average."""
    def one(leaf):
        P = leaf.shape[0]
        flat = leaf.reshape(P, -1)
        return weighted_average_flat(flat, weights, **kw).reshape(leaf.shape[1:])
    return jax.tree.map(one, stacked_tree)
