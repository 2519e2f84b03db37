"""SPMD federated round: the production rendering of Fed-TGAN's training
loop on a TPU mesh.

Clients map onto mesh axes (DESIGN.md §4): each slice of the client axis
holds one client's model replica + local data shard.  A round is

    local `lax.scan` of E update steps  (no cross-client collectives)
    -> ONE weighted psum of the aggregated part of the state
       (Fed-TGAN's federator merge, weights from §4.2)

``make_federated_round`` is model-agnostic: you provide the per-client
``step_fn(state, batch) -> (state, metrics)`` and a lens that says which
part of the state is aggregated (params; optimizer moments stay local).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .aggregation import psum_weighted

PyTree = Any


def default_lens(state):
    """For states with ``.params``: aggregate params, keep the rest."""
    return state.params


def default_merge(state, merged_params):
    return state._replace(params=merged_params)


def make_federated_round(step_fn: Callable,
                         *,
                         client_axis: str | tuple[str, ...] = "data",
                         lens: Callable = default_lens,
                         merge: Callable = default_merge) -> Callable:
    """Returns ``round_fn(state, batches, weight) -> (state, metrics)``.

    Meant to run INSIDE shard_map/jit with ``state`` replicated per client
    slice, ``batches`` carrying a leading local-steps axis, and ``weight``
    this client's scalar aggregation weight (softmax over clients == sums
    to 1 over the axis).
    """
    def round_fn(state, batches, weight):
        def body(st, batch):
            return step_fn(st, batch)
        state, metrics = jax.lax.scan(body, state, batches)
        merged = psum_weighted(lens(state), weight, client_axis)
        state = merge(state, merged)
        return state, metrics

    return round_fn


def fedprox_wrap(step_fn, mu: float, lens: Callable = default_lens,
                 merge: Callable = default_merge):
    """FedProx (Li et al. 2020): add a proximal pull toward the round's
    global params to every local step — stabilizes Non-IID local drift on
    top of Fed-TGAN's weighting (beyond-paper option).

    The wrapped step takes (state, (batch, global_params))."""
    def prox_step(state, batch_and_global):
        batch, global_params = batch_and_global
        state, metrics = step_fn(state, batch)
        new_params = jax.tree.map(
            lambda p, g: p - mu * (p - g.astype(p.dtype)),
            lens(state), global_params)
        return merge(state, new_params), metrics
    return prox_step


def sample_participation(weights: jnp.ndarray, key: jax.Array,
                         fraction: float) -> jnp.ndarray:
    """Partial-participation cohort draw: keep each client with prob
    ``fraction``; if the drawn cohort is EMPTY, one key-chosen rescue
    client is kept so a round is never empty.  Returns the (P,) bool keep
    mask — the form the fed layer's degraded-round path composes with its
    fault masks before the single renormalize-and-merge.

    The rescue only fires on an empty draw (probability ``(1-f)^P``) and
    picks uniformly from the round key — NOT a fixed client.  The old
    behavior (always force-keep ``argmax(weights)``) biased the cohort:
    under uniform/tied weights client 0's effective participation rate
    was 1.0 instead of ``fraction`` (chi-squared regression in
    ``tests/test_fedavg_features.py``)."""
    P = weights.shape[0]
    k_keep, k_rescue = jax.random.split(key)
    keep = jax.random.bernoulli(k_keep, fraction, (P,))
    rescue = jnp.arange(P) == jax.random.randint(k_rescue, (), 0, P)
    return jnp.where(jnp.any(keep), keep, rescue)


def sample_client_weights(weights: jnp.ndarray, key: jax.Array,
                          fraction: float) -> jnp.ndarray:
    """Partial participation: keep each client with prob ``fraction``
    (at least one survives), renormalize §4.2 weights over the sampled
    cohort.  Dropped clients get weight 0 — their slice trains but
    contributes nothing to the merge (SPMD-friendly: no dynamic shapes)."""
    keep = sample_participation(weights, key, fraction)
    w = jnp.where(keep, weights, 0.0)
    return w / jnp.maximum(jnp.sum(w), 1e-12)


def shard_map_federated_round(mesh, step_fn, state_specs,
                              *, client_axis="data", lens=default_lens,
                              merge=default_merge):
    """Wrap :func:`make_federated_round` in a shard_map over ``mesh``.

    - ``state`` is replicated over ``client_axis`` on entry (every client
      starts each round from the merged model — Fed-TGAN's redistribution)
      and replicated again on exit (post-psum all slices agree).
    - ``batches`` carry (client, local_steps, ...) leading axes, sharded on
      the client axis.
    - ``weights`` is the (P,) §4.2 weight vector, sharded on the client axis.
    - per-client metrics come back with a leading client axis.
    """
    round_fn = make_federated_round(step_fn, client_axis=client_axis,
                                    lens=lens, merge=merge)

    def inner(state, batches, w):
        # batches arrive as (1, E, ...) per slice; metrics leave as (1, E)
        local_batches = jax.tree.map(lambda x: x[0], batches)
        state, metrics = round_fn(state, local_batches, w[0])
        return state, jax.tree.map(lambda x: x[None], metrics)

    def wrapped(state, batches, weights):
        batch_in_specs = jax.tree.map(lambda _: P(client_axis), batches)
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(state_specs, batch_in_specs, P(client_axis)),
            out_specs=(state_specs, P(client_axis)),
            check_vma=False,
        )(state, batches, weights)

    return wrapped
