"""One-program federated rounds: the whole global round as ONE dispatch.

The simulation drivers used to run Fed-TGAN as a Python loop — a jitted
per-round program, but a host hop between every round, and a per-leaf
merge.  :class:`FederatedProgram` lowers the complete global round into a
single XLA program:

    vmapped ``RoundEngine.local_round`` over the stacked client axis
      (each client: E x (on-device conditional draw + D step + G step))
    -> Fig.4 §4.2 weighting, recomputed IN-PROGRAM from the divergence
       matrix (``weights_from_divergence``; uniform / quantity-only
       selectable for the paper's ablations)
    -> ONE fused ``weighted_agg`` merge of generator AND discriminator
       parameters together (:func:`repro.fed.merge.fused_weighted_merge`)
    -> broadcast of the merged model back onto the client axis

and ``run`` scans that round over per-round keys, so an entire training
run between eval points is one dispatch: only model state, sampler
tables, the (P, Q) divergence matrix, and PRNG keys ever cross the host
boundary.  The shard_map rendering for multi-host meshes lives in
:mod:`repro.fed.sharded`.

Example — two IID clients, one global round; after the round every
client holds the SAME merged generator (the broadcast step):

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from repro.fed import FederatedProgram, setup_federation
    >>> from repro.gan.ctgan import CTGANConfig
    >>> from repro.tabular import ColumnSpec
    >>> rng = np.random.default_rng(0)
    >>> schema = [ColumnSpec("x", "continuous", max_modes=2),
    ...           ColumnSpec("c", "categorical")]
    >>> parts = [np.stack([rng.normal(size=48),
    ...                    rng.integers(0, 3, 48)], 1) for _ in range(2)]
    >>> cfg = CTGANConfig(batch_size=8, gen_hidden=(16,), disc_hidden=(16,),
    ...                   pac=2, z_dim=4)
    >>> fe = setup_federation(parts, schema, cfg, seed=0, weighting="uniform")
    >>> prog = FederatedProgram(cfg, fe.spans, fe.cond_spans, batch=8,
    ...                         local_steps=2, weighting="uniform")
    >>> states, metrics = prog.round(fe.states, fe.tables, fe.S, fe.n_rows,
    ...                              jax.random.PRNGKey(1))
    >>> metrics["d_loss"].shape                    # (clients, local steps)
    (2, 2)
    >>> g0, g1 = (jax.tree.map(lambda x, i=i: x[i], states.g_params)
    ...           for i in (0, 1))
    >>> bool(all(jnp.array_equal(a, b) for a, b in
    ...          zip(jax.tree.leaves(g0), jax.tree.leaves(g1))))
    True
    >>> _, m = prog.run(states, fe.tables, fe.S, fe.n_rows,
    ...                 prog.fold_round_keys(jax.random.PRNGKey(2), 0, 3))
    >>> m["g_loss"].shape                   # (rounds, clients, local steps)
    (3, 2, 2)
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from .. import obs
from ..core.fedavg import fedprox_wrap, sample_participation
from ..core.weighting import (quantity_only_weights, uniform_weights,
                              weights_from_divergence)
from ..gan.ctgan import CTGANConfig
from ..gan.dp import DPConfig, make_dp_train_steps
from ..gan.trainer import GANState, make_train_steps
from ..kernels import ops
from ..synth import RoundEngine, SamplerTables
from ..tabular.encoders import SpanInfo
from .faults import (FaultPlan, UpdateGuard, apply_faults, guard_ok,
                     update_diagnostics)
from .merge import (flatten_stacked, fused_weighted_merge, replicate,
                    tiered_weighted_merge, tiered_weighted_merge_flat,
                    unflatten_merged)

WEIGHTINGS = ("fedtgan", "uniform", "quantity")


class _Entry:
    """A jitted entry point of :class:`FederatedProgram` that counts what
    the host asks of it: each call adds one to ``fed.dispatches`` and its
    global rounds to ``fed.rounds`` and runs under the host span
    ``repro.fed.<name>``; each jit trace of the body adds one to
    ``jit_traces`` under ``fed.<name>`` (the body runs only when JAX
    traces it, so the count shows which entry point retraced).
    ``lower`` is the jitted function's own."""

    def __init__(self, name: str, fn, scanned: bool):
        def traced(*args):
            obs.counter("jit_traces")["fed." + name] += 1
            return fn(*args)

        traced.__name__ = fn.__name__
        self._jit = jax.jit(traced)
        self.lower = self._jit.lower
        self._span = "repro.fed." + name
        self._scanned = scanned

    def __call__(self, *args):
        fed = obs.counter("fed")
        fed["dispatches"] += 1
        fed["rounds"] += args[4].shape[0] if self._scanned else 1
        with obs.span(self._span):
            return self._jit(*args)


def _gan_lens(state: GANState):
    """FedProx lens for GANState: both networks' params are aggregated
    (optimizer moments stay local, as in the paper's merge)."""
    return (state.g_params, state.d_params)


def _gan_merge(state: GANState, params) -> GANState:
    return state._replace(g_params=params[0], d_params=params[1])


def resolve_weights(weighting: str, S: jnp.ndarray,
                    n_rows: jnp.ndarray) -> jnp.ndarray:
    """The §4.2 weight vector, as pure jnp so it composes into the jitted
    round: ``fedtgan`` = Fig.4 steps 1-4 on the divergence matrix,
    ``uniform`` = vanilla FL, ``quantity`` = the Fed\\SW ablation.  ``S``
    is ignored (and may be a zeros placeholder) except under ``fedtgan``.
    """
    if weighting == "fedtgan":
        return weights_from_divergence(S, n_rows)
    if weighting == "quantity":
        return quantity_only_weights(n_rows)
    if weighting == "uniform":
        return uniform_weights(n_rows.shape[0])
    raise ValueError(f"unknown weighting {weighting!r}; options: {WEIGHTINGS}")


class FederatedProgram:
    """Client-sharded federated execution for one table schema.

    Wraps a :class:`repro.synth.RoundEngine` and composes its vmapped
    local rounds with in-program weighting and the fused whole-model
    merge.  ``round`` runs ONE global round per dispatch; ``run`` scans
    global rounds over a stacked key axis (one dispatch per chunk of
    rounds).  ``global_round`` is the un-jitted pure function for callers
    that lower it themselves (the mesh dry-run).
    """

    def __init__(self, cfg: CTGANConfig, spans: Sequence[SpanInfo],
                 cond_spans: Sequence[SpanInfo], *, batch: int,
                 local_steps: int, weighting: str = "fedtgan",
                 engine: RoundEngine | None = None,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None,
                 participation: float = 1.0,
                 fedprox_mu: float = 0.0,
                 guard: UpdateGuard | None = None,
                 client_chunk: int | None = None,
                 n_edges: int | None = None,
                 dp: DPConfig | None = None):
        if weighting not in WEIGHTINGS:
            raise ValueError(f"unknown weighting {weighting!r}; "
                             f"options: {WEIGHTINGS}")
        if not 0.0 < participation <= 1.0:
            raise ValueError(f"participation must be in (0, 1], "
                             f"got {participation}")
        if client_chunk is not None and client_chunk < 1:
            raise ValueError(f"client_chunk must be >= 1, "
                             f"got {client_chunk}")
        if n_edges is not None and n_edges < 1:
            raise ValueError(f"n_edges must be >= 1, got {n_edges}")
        self.cfg = cfg
        self.weighting = weighting
        self.participation = float(participation)
        self.fedprox_mu = float(fedprox_mu)
        self.guard = guard
        # Scale renderings (see docs/FEDERATION.md "Scaling to thousands
        # of clients"): client_chunk switches local rounds to
        # scan-of-vmap chunks (bit-exact, fixed activation memory);
        # n_edges switches the merge to the two-tier clients → edges →
        # federator form (ulp-equal to the flat merge, one fused
        # weighted_agg per tier).
        self.client_chunk = client_chunk
        self.n_edges = n_edges
        # dp swaps every client's scanned D/G step for the DP-SGD variant
        # (per-pack clip + Gaussian noise, repro.gan.dp) — the round keeps
        # its one-program shape; only the local step body changes.
        self.dp = dp
        if engine is None:
            step_fn = None
            if dp is not None:
                step_fn = make_dp_train_steps(cfg, tuple(spans),
                                              tuple(cond_spans),
                                              l2_clip=dp.l2_clip,
                                              noise_mult=dp.noise_mult)
            if self.fedprox_mu > 0:
                step_fn = fedprox_wrap(
                    step_fn or make_train_steps(cfg, tuple(spans),
                                                tuple(cond_spans)),
                    self.fedprox_mu, lens=_gan_lens, merge=_gan_merge)
            engine = RoundEngine(cfg, tuple(spans), tuple(cond_spans),
                                 batch=batch, local_steps=local_steps,
                                 step_fn=step_fn)
        elif self.fedprox_mu > 0 or dp is not None:
            raise ValueError("pass either a prebuilt engine or "
                             "fedprox_mu/dp, not both (the prox/DP step "
                             "wraps or replaces the step_fn)")
        self.engine = engine
        self._merge_kw = dict(use_pallas=use_pallas, interpret=interpret)
        self.round = _Entry("round", self.global_round, False)
        self.run = _Entry("run", self._run_impl, True)
        self.round_faulted = _Entry("round_faulted",
                                    self.faulted_global_round, False)
        self.run_faulted = _Entry("run_faulted", self._run_faulted_impl,
                                  True)
        self.round_traced = _Entry("round_traced", self.traced_global_round,
                                   False)
        self.run_traced = _Entry("run_traced", self._run_traced_impl, True)

    # -- the one-program round -------------------------------------------

    @jax.named_scope("merge")
    def merge_states(self, states: GANState, w: jnp.ndarray) -> GANState:
        """Federator merge + redistribution: G and D parameters flattened
        into ONE ``weighted_agg`` dispatch (one per tier under
        hierarchical aggregation), then broadcast back onto the client
        axis.  Optimizer moments stay local (the paper aggregates model
        parameters only)."""
        P = w.shape[0]
        tree = {"g": states.g_params, "d": states.d_params}
        if self.n_edges is None:
            merged = fused_weighted_merge(tree, w, **self._merge_kw)
        else:
            merged = tiered_weighted_merge(tree, w, self.n_edges,
                                           **self._merge_kw)
        return states._replace(g_params=replicate(merged["g"], P),
                               d_params=replicate(merged["d"], P))

    def _clients(self, states: GANState, tables: SamplerTables,
                 key: jax.Array):
        """Vmapped local rounds (chunked scan-of-vmap when
        ``client_chunk`` is set), with the round's global params threaded
        in as the FedProx anchor when drift control is on (every client's
        pre-round params ARE the broadcast global model)."""
        P = jax.tree.leaves(states.g_params)[0].shape[0]
        aux = _gan_lens(states) if self.fedprox_mu > 0 else None
        return self.engine.clients_round(states, tables,
                                         jax.random.split(key, P), aux,
                                         client_chunk=self.client_chunk)

    def weighted_round(self, states: GANState, tables: SamplerTables,
                       w: jnp.ndarray, key: jax.Array):
        """One global round given resolved weights: vmapped local rounds
        + fused merge + broadcast.  Metrics: (clients, local_steps)."""
        states, metrics = self._clients(states, tables, key)
        return self.merge_states(states, w), metrics

    def global_round(self, states: GANState, tables: SamplerTables,
                     S: jnp.ndarray, n_rows: jnp.ndarray, key: jax.Array):
        """One global round with the §4.2 weighting computed in-program
        from the divergence matrix.  Pure: compose freely under jit/scan
        or lower on a mesh (see ``launch.fed_dryrun``)."""
        w = resolve_weights(self.weighting, S, n_rows)
        return self.weighted_round(states, tables, w, key)

    def _run_impl(self, states: GANState, tables: SamplerTables,
                  S: jnp.ndarray, n_rows: jnp.ndarray,
                  round_keys: jax.Array):
        """Scan ``global_round`` over the leading axis of ``round_keys``:
        R global rounds — local training, weighting, merge, broadcast —
        in ONE dispatch.  Weights are resolved once (the divergence
        matrix is protocol data, fixed for the run).  Metrics come back
        stacked (rounds, clients, local_steps)."""
        w = resolve_weights(self.weighting, S, n_rows)

        def body(st, k):
            return self.weighted_round(st, tables, w, k)

        return jax.lax.scan(body, states, round_keys)

    # -- the traced round (transmitted artifacts surfaced as outputs) ----

    def traced_round(self, states: GANState, tables: SamplerTables,
                     w: jnp.ndarray, key: jax.Array):
        """:meth:`weighted_round` that ALSO returns the round's
        transmitted artifacts — the flat ``(P, D)`` post-local-training
        update stack that feeds the fused merge.  This is exactly the
        per-round privacy surface an honest-but-curious federator (or a
        wire eavesdropper) observes, recorded for the attack harness
        (:mod:`repro.privacy`).

        The merge math is the SAME flatten → ``weighted_average_flat`` →
        unflatten pass :meth:`merge_states` performs, just with the flat
        stack kept as an output, so the traced round is bit-identical to
        the untraced one (``tests/test_privacy.py``).  Returns
        ``(states, metrics, flat_updates)``."""
        P = w.shape[0]
        states, metrics = self._clients(states, tables, key)
        with jax.named_scope("merge"):
            tree = {"g": states.g_params, "d": states.d_params}
            flat = flatten_stacked(tree)
            if self.n_edges is None:
                merged = ops.weighted_average_flat(flat, w, **self._merge_kw)
            else:
                merged = tiered_weighted_merge_flat(flat, w, self.n_edges,
                                                    **self._merge_kw)
            out = unflatten_merged(merged, tree)
            states = states._replace(g_params=replicate(out["g"], P),
                                     d_params=replicate(out["d"], P))
        return states, metrics, flat

    def traced_global_round(self, states: GANState, tables: SamplerTables,
                            S: jnp.ndarray, n_rows: jnp.ndarray,
                            key: jax.Array):
        """:meth:`global_round` through the traced path: returns
        ``(states, metrics, artifacts)`` where ``artifacts`` carries the
        ``(P, D)`` update stack and the resolved §4.2 weights."""
        w = resolve_weights(self.weighting, S, n_rows)
        states, metrics, flat = self.traced_round(states, tables, w, key)
        return states, metrics, {"updates": flat, "weights": w}

    def _run_traced_impl(self, states: GANState, tables: SamplerTables,
                         S: jnp.ndarray, n_rows: jnp.ndarray,
                         round_keys: jax.Array):
        """Scan :meth:`traced_round` over round keys: R rounds in ONE
        dispatch, with the per-round transmitted stacks coming back
        stacked ``(R, P, D)`` in the artifacts dict — the replayable
        record the trace recorder persists."""
        w = resolve_weights(self.weighting, S, n_rows)

        def body(st, k):
            st, m, flat = self.traced_round(st, tables, w, k)
            return st, (m, flat)

        states, (metrics, flats) = jax.lax.scan(body, states, round_keys)
        return states, metrics, {"updates": flats, "weights": w}

    # -- the degraded round (fault masks + guard + masked merge) ---------

    def faulted_round(self, states: GANState, tables: SamplerTables,
                      w: jnp.ndarray, key: jax.Array, fault: FaultPlan):
        """One global round under a (P,)-sliced :class:`FaultPlan`:
        vmapped local rounds, fault injection on the TRANSMITTED update
        stack, the non-finite/update-norm guard, then mask + renormalize
        folded into the SAME single fused ``weighted_agg`` dispatch as
        the dense round.

        Survivor math: ``w_eff = w * participate * guard_ok``, values of
        masked clients sanitized to exact zeros (0-weight x NaN would
        still be NaN), the kernel renormalizes over the survivors.  An
        all-masked round FREEZES (keeps the previous global model) —
        never a divide by zero; the host-side :meth:`FaultPlan.validate`
        is where that becomes a typed error.

        With a neutral plan (everyone participates, nothing corrupted,
        guard passing) this is bit-identical to :meth:`weighted_round`.

        Extra metrics (all (P,) per round): ``client_ok`` (survived the
        mask+guard), ``client_suspect`` (advisory corruption signal, fed
        to the retry blocklist even when the guard is off),
        ``update_norm``, ``w_eff`` (renormalized effective weights) and
        scalar ``merged`` (False = the round froze)."""
        P = w.shape[0]
        participate = fault.participate
        if self.participation < 1.0:
            kp, key = jax.random.split(key)
            participate = participate & sample_participation(
                w, kp, self.participation)
        with jax.named_scope("merge"):
            prev_flat = flatten_stacked({"g": states.g_params,
                                         "d": states.d_params})
        states, metrics = self._clients(states, tables, key)
        with jax.named_scope("merge"):
            tree = {"g": states.g_params, "d": states.d_params}
            flat = apply_faults(flatten_stacked(tree), prev_flat,
                                fault.nan_mask, fault.scale)
            norm_mult = (self.guard.norm_mult if self.guard is not None
                         and self.guard.norm_mult > 0 else None)
            diag = update_diagnostics(
                flat, prev_flat, participate,
                **({} if norm_mult is None else {"norm_mult": norm_mult}))
            ok = guard_ok(self.guard, diag, participate)
            w_eff = w * ok
            wsum = jnp.sum(w_eff)
            flat_safe = jnp.where(ok[:, None], flat, 0.0)
            if self.n_edges is None:
                merged = ops.weighted_average_flat(flat_safe, w_eff,
                                                   **self._merge_kw)
            else:
                # same mask + renormalize math, folded tier-wise: a fully
                # masked edge carries tier weight 0 and exact-zero values,
                # so in-kernel renormalization still happens per tier.
                merged = tiered_weighted_merge_flat(flat_safe, w_eff,
                                                    self.n_edges,
                                                    **self._merge_kw)
            merged = jnp.where(wsum > 0, merged, prev_flat[0])
            out = unflatten_merged(merged, tree)
            states = states._replace(g_params=replicate(out["g"], P),
                                     d_params=replicate(out["d"], P))
        metrics = dict(metrics, client_ok=ok,
                       client_suspect=participate & diag["suspect"],
                       update_norm=diag["norm"],
                       w_eff=w_eff / jnp.maximum(wsum, 1e-12),
                       merged=wsum > 0)
        return states, metrics

    def faulted_global_round(self, states: GANState, tables: SamplerTables,
                             S: jnp.ndarray, n_rows: jnp.ndarray,
                             key: jax.Array, fault: FaultPlan):
        """:meth:`global_round` with a per-round fault slice — the pure
        function ``launch.fed_dryrun --faults`` lowers on the mesh."""
        w = resolve_weights(self.weighting, S, n_rows)
        return self.faulted_round(states, tables, w, key, fault)

    def _run_faulted_impl(self, states: GANState, tables: SamplerTables,
                          S: jnp.ndarray, n_rows: jnp.ndarray,
                          round_keys: jax.Array, plan: FaultPlan):
        """Scan :meth:`faulted_round` over (round keys, fault slices):
        a whole degraded stretch — dropouts, stragglers, corruption,
        guard, masked merges — in ONE dispatch.  ``plan`` leaves carry a
        leading (R,) axis aligned with ``round_keys``."""
        w = resolve_weights(self.weighting, S, n_rows)

        def body(st, xs):
            k, fault = xs
            return self.faulted_round(st, tables, w, k, fault)

        return jax.lax.scan(body, states, (round_keys, plan))

    # -- key plumbing ----------------------------------------------------

    @staticmethod
    def fold_round_keys(key: jax.Array, start: int, stop: int) -> jax.Array:
        """The simulation drivers' round-key stream — ``fold_in(key, r)``
        for absolute round indices ``start..stop-1`` — stacked for
        ``run``.  Using the same stream is what makes the one-program
        path bit-comparable to the per-round host loop.

        Vectorized as ONE ``vmap(fold_in)`` over the round index range:
        the old per-round Python loop was O(R) host dispatches, which
        dominated setup at the R needed for P=1024 sweeps.  Bit-exact
        against the loop (regression in ``tests/test_fed_scale.py``)."""
        return jax.vmap(lambda r: jax.random.fold_in(key, r))(
            jnp.arange(start, stop))
