"""shard_map rendering of the one-program round: clients on a mesh axis.

The vmap path (:class:`repro.fed.FederatedProgram`) stacks clients on a
leading array axis and lets GSPMD place them; this module makes the
placement EXPLICIT for multi-host meshes, building on the pattern of
:func:`repro.core.fedavg.shard_map_federated_round`: each client-axis
slice runs its own ``RoundEngine.local_round`` on its local shard of the
:class:`repro.synth.SamplerTables` (batches drawn on device inside the
slice — nothing is presampled), and the federator merge is ONE weighted
``psum`` over the client axis (the collective twin of the fused
``weighted_agg`` merge).  §4.2 weights are still resolved in-program
from the divergence matrix, outside the shard_map, where they are
replicated; GSPMD reshards them onto the client axis.

``launch.fed_dryrun --arch ctgan-paper --shard-map`` lowers this path on
the 16x16 production mesh, proving the multi-host placement compiles.

Example — a 1-device "mesh" still exercises the whole path (P=1, the
psum is an identity merge):

    >>> import jax, numpy as np
    >>> from repro.fed import setup_federation, shard_map_global_round
    >>> from repro.gan.ctgan import CTGANConfig
    >>> from repro.tabular import ColumnSpec
    >>> rng = np.random.default_rng(0)
    >>> parts = [np.stack([rng.normal(size=32),
    ...                    rng.integers(0, 2, 32)], 1)]
    >>> schema = [ColumnSpec("x", "continuous", max_modes=2),
    ...           ColumnSpec("c", "categorical")]
    >>> cfg = CTGANConfig(batch_size=4, gen_hidden=(8,), disc_hidden=(8,),
    ...                   pac=2, z_dim=4)
    >>> fe = setup_federation(parts, schema, cfg, seed=0, weighting="uniform")
    >>> mesh = jax.make_mesh((1,), ("clients",))
    >>> prog = shard_map_global_round(mesh, cfg, fe.spans, fe.cond_spans,
    ...                               batch=4, local_steps=1,
    ...                               weighting="uniform",
    ...                               client_axes=("clients",))
    >>> with mesh:
    ...     states, metrics = jax.jit(prog)(fe.states, fe.tables, fe.S,
    ...                                     fe.n_rows, jax.random.PRNGKey(0))
    >>> metrics["d_loss"].shape                    # (clients, local_steps)
    (1, 1)
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import PartitionSpec as P

from ..core.aggregation import psum_weighted
from ..gan.ctgan import CTGANConfig
from ..synth import RoundEngine
from ..tabular.encoders import SpanInfo
from .program import resolve_weights


def shard_map_weighted_round(mesh, engine: RoundEngine, *,
                             client_axes: tuple[str, ...] = ("data",)):
    """``round_fn(states, tables, w, keys) -> (states, metrics)`` with
    every argument carrying a leading client axis sharded over
    ``client_axes``.  Per slice: one local round on the local tables
    shard, then the weighted-psum merge of G and D params (weights must
    sum to 1 over the axis — softmax output)."""
    ca = tuple(client_axes)

    def inner(states, tables, w, keys):
        # each slice holds (1, ...) — peel the local client off, train,
        # merge through the collective, and put the axis back
        st = jax.tree.map(lambda x: x[0], states)
        tb = jax.tree.map(lambda x: x[0], tables)
        st, metrics = engine.local_round(st, tb, keys[0])
        merged = psum_weighted((st.g_params, st.d_params), w[0], ca)
        st = st._replace(g_params=merged[0], d_params=merged[1])
        return (jax.tree.map(lambda x: x[None], st),
                jax.tree.map(lambda x: x[None], metrics))

    axis_size = 1
    for a in ca:
        axis_size *= mesh.shape[a]

    def round_fn(states, tables, w, keys):
        P_clients = jax.tree.leaves(states)[0].shape[0]
        if P_clients != axis_size:
            # each slice trains exactly one client (inner peels x[0]); a
            # mismatch would silently drop clients from the merge
            raise ValueError(
                f"stacked client axis ({P_clients}) must equal the client "
                f"mesh axis size ({axis_size} over {ca})")
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(ca), P(ca), P(ca), P(ca)),
            out_specs=(P(ca), P(ca)),
            check_vma=False,
        )(states, tables, w, keys)

    return round_fn


def shard_map_global_round(mesh, cfg: CTGANConfig, spans: Sequence[SpanInfo],
                           cond_spans: Sequence[SpanInfo], *, batch: int,
                           local_steps: int, weighting: str = "fedtgan",
                           client_axes: tuple[str, ...] = ("data",),
                           engine: RoundEngine | None = None):
    """The full one-program global round, shard_map edition: in-program
    §4.2 weighting (replicated) + per-slice local rounds + weighted-psum
    merge.  Jit it (optionally with explicit in_shardings) inside a
    ``with mesh:`` block; ``launch.fed_dryrun`` lowers exactly this."""
    engine = engine or RoundEngine(cfg, tuple(spans), tuple(cond_spans),
                                   batch=batch, local_steps=local_steps)
    round_fn = shard_map_weighted_round(mesh, engine, client_axes=client_axes)

    def program(states, tables, S, n_rows, key):
        w = resolve_weights(weighting, S, n_rows)
        keys = jax.random.split(key, n_rows.shape[0])
        return round_fn(states, tables, w, keys)

    return program
