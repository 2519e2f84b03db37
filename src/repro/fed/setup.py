"""Federation bring-up: the §4.1/§4.2 protocol packaged for the engine.

One call runs the full pre-training protocol the paper specifies and
returns everything the one-program round needs as device-ready state:

  1. clients ship per-column statistics (``compute_client_stats``) —
     never raw rows;
  2. the federator unions categories and merges client VGMs into global
     encoders (``federated_encoder_init``);
  3. the (P, Q) divergence matrix S is built from the SAME protocol data
     (``build_divergence_matrix``) — kept, not reduced to weights, so the
     jitted round can recompute Fig.4 steps 1-4 in-program;
  4. every client's rows are encoded through the fused one-dispatch plan
     and stacked into vmap-ready :class:`repro.synth.SamplerTables`;
  5. the federator initializes ONE model and replicates it (identical
     start on every client).

The result is a :class:`Federation`: hand its ``states/tables/S/n_rows``
straight to :class:`repro.fed.FederatedProgram`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.encoding import (FederatedInit, client_vgm_dicts,
                             compute_client_stats, federated_encoder_init)
from ..core.weighting import build_divergence_matrix
from ..gan.ctgan import CTGANConfig
from ..gan.trainer import GANState, init_gan_state
from ..synth import DeviceSampler, SamplerTables, stack_sampler_tables
from ..tabular.encoders import ColumnSpec, TableEncoders
from .program import WEIGHTINGS, resolve_weights


@dataclasses.dataclass
class Federation:
    """Protocol outputs + device-ready round inputs for one federation."""
    init: FederatedInit
    enc: TableEncoders
    spans: tuple
    cond_spans: tuple
    tables: SamplerTables          # stacked client axis, vmap-ready
    states: GANState               # stacked client axis, identical start
    S: jnp.ndarray                 # (P, Q) divergence matrix (zeros unless
                                   # weighting="fedtgan" requested it)
    n_rows: jnp.ndarray            # (P,) float32 local row counts
    weights: jnp.ndarray           # (P,) resolved §4.2 weights (host copy,
                                   # for reporting; the program recomputes)
    weighting: str
    client_stats: list | None = None   # per-client §4.1 payloads (ClientStats:
                                       # raw cat-frequency tables + local VGM
                                       # fits) — the literal setup-time privacy
                                       # surface, kept for the attack harness's
                                       # trace recorder (repro.privacy)

    @property
    def n_clients(self) -> int:
        return int(self.n_rows.shape[0])

    def fault_plan(self, regime: str, rounds: int, *, seed: int = 0):
        """Render a named fault regime (see ``fed.scenarios.FAULTS``) into
        a validated (rounds, P) :class:`~repro.fed.faults.FaultPlan` sized
        for this federation — the input ``FederatedProgram.run_faulted``
        scans alongside the round keys.  Returns None for ``"none"``."""
        from .scenarios import build_fault_plan   # lazy: scenarios is heavy
        return build_fault_plan(regime, rounds, self.n_clients, seed=seed)


def tile_federation(fe: Federation, P: int) -> Federation:
    """Replicate a staged federation's client axis out to ``P`` clients.

    The §4.1 protocol cost (per-client stats, VGM fits, encoders,
    encoding) is paid once at the base federation's size; states, sampler
    tables, divergence rows, and row counts tile on device — which is how
    the P=1024 ``fed_bench`` sweeps stage thousand-client rounds without
    a thousand host-side encoder fits.  Tiled clients get FRESH per-slice
    rng streams (``fold_in`` of the federator model key by client index,
    matching ``setup_federation``'s layout), so replicas do not draw in
    lockstep.  ``P`` must be a multiple of the base client count."""
    base = fe.n_clients
    if P < base or P % base:
        raise ValueError(f"P={P} must be a positive multiple of the base "
                         f"client count {base}")
    if P == base:
        return fe
    reps = P // base

    def tile(t):
        return jax.tree.map(
            lambda x: jnp.tile(x, (reps,) + (1,) * (x.ndim - 1)), t)

    states = tile(fe.states)
    rng0 = fe.states.rng[0]
    states = states._replace(
        rng=jax.vmap(lambda i: jax.random.fold_in(rng0, i))(jnp.arange(P)))
    n_rows = jnp.tile(fe.n_rows, reps)
    S = jnp.tile(fe.S, (reps, 1))
    w = jax.jit(resolve_weights, static_argnums=0)(fe.weighting, S, n_rows)
    return dataclasses.replace(fe, tables=tile(fe.tables), states=states,
                               S=S, n_rows=n_rows, weights=w,
                               client_stats=(fe.client_stats * reps
                                             if fe.client_stats else None))


def setup_federation(client_data: list[np.ndarray], schema: list[ColumnSpec],
                     cfg: CTGANConfig, seed: int,
                     weighting: str = "fedtgan") -> Federation:
    """Run the §4.1 init + §4.2 Step 0 and stage the federation on device.

    Key streams match the original simulation drivers (stats, init,
    weighting, model, encode split off one seed in that order), so runs
    are reproducible against the pre-fed-layer history.
    """
    if weighting not in WEIGHTINGS:
        raise ValueError(f"unknown weighting {weighting!r}; "
                         f"options: {WEIGHTINGS}")
    P = len(client_data)
    key = jax.random.PRNGKey(seed)
    k_stats, k_init, k_w, k_model, k_enc = jax.random.split(key, 5)

    # Host spans (while obs is on).  vgm_fit, encoder_init and states
    # leave their results on the device, so they time the host's work and
    # enqueueing; divergence and encode read results back to the host, so
    # they also wait for the device work queued before them.
    with obs.span("repro.setup.vgm_fit"):
        stats = [compute_client_stats(d, schema,
                                      jax.random.fold_in(k_stats, i))
                 for i, d in enumerate(client_data)]
    with obs.span("repro.setup.encoder_init"):
        init = federated_encoder_init(stats, schema, k_init)
    n_rows = jnp.asarray(init.n_rows, jnp.float32)

    with obs.span("repro.setup.divergence"):
        if weighting == "fedtgan":
            S = build_divergence_matrix(schema, init.client_cat_freqs,
                                        client_vgm_dicts(stats),
                                        init.encoders, init.global_cat_freqs,
                                        k_w)
        else:
            # placeholder with the right client axis; dead code in-program
            S = jnp.zeros((P, len(schema)), jnp.float32)
        # jitted so the host copy folds EXACTLY like the in-program
        # recompute: the eager trace can round the Fig.4 softmax a final
        # ulp differently, and GAN rounds amplify that into
        # host-vs-program parity noise
        w = jax.jit(resolve_weights, static_argnums=0)(weighting, S, n_rows)

    enc = init.encoders
    # stack the per-client sampler tables right away so only ONE device
    # copy (the stacked, vmap-ready one) stays resident for the run
    with obs.span("repro.setup.encode"):
        tables = stack_sampler_tables([DeviceSampler(
            np.asarray(enc.encode(d, jax.random.fold_in(k_enc, i))), enc)
            for i, d in enumerate(client_data)])
    # Federator initializes ONE model and distributes it (identical start).
    with obs.span("repro.setup.states"):
        state0 = init_gan_state(k_model, cfg, enc.cond_dim, enc.encoded_dim)
        states = [state0._replace(rng=jax.random.fold_in(state0.rng, i))
                  for i in range(P)]
        states = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    return Federation(init, enc, tuple(enc.spans()),
                      tuple(enc.condition_spans()), tables, states,
                      S, n_rows, w, weighting, client_stats=stats)
