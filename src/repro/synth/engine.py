"""The device-resident federated round loop.

``RoundEngine`` composes :func:`repro.synth.sampler.draw_batch` with the
jitted CTGAN train steps inside one ``lax.scan``: a client's whole local
round — E x (conditional batch draw + D step + G step) — lowers into a
single XLA program with zero host transfers between steps.  The PR-1
presampled path (``presample_rounds`` / ``make_round_batches``) staged
every batch through numpy and shipped ``rounds x steps x batch x dim``
arrays in; here only the model state and one PRNG key cross the boundary
per round.

``vmap`` over a stacked client axis (tables from
:func:`stack_sampler_tables`) runs all clients "in parallel" exactly like
the simulation drivers, and scanning over round keys runs many rounds in
one dispatch (``run``).

Example — train a tiny engine round and synthesize through the fused
decode path (the whole pipeline this module fronts):

    >>> import jax, numpy as np
    >>> from repro.gan.ctgan import CTGANConfig
    >>> from repro.gan.trainer import init_gan_state
    >>> from repro.synth import DeviceSampler, RoundEngine, synthesize_table
    >>> from repro.tabular import ColumnSpec, fit_centralized_encoders
    >>> rng = np.random.default_rng(0)
    >>> table = np.stack([rng.normal(size=64), rng.integers(0, 3, 64)], 1)
    >>> schema = [ColumnSpec("x", "continuous", max_modes=3),
    ...           ColumnSpec("c", "categorical")]
    >>> key = jax.random.PRNGKey(0)
    >>> enc = fit_centralized_encoders(table, schema, key)
    >>> cfg = CTGANConfig(batch_size=8, gen_hidden=(16,), disc_hidden=(16,),
    ...                   pac=2, z_dim=4)
    >>> engine = RoundEngine(cfg, enc.spans(), enc.condition_spans(),
    ...                      batch=8, local_steps=2)
    >>> sampler = DeviceSampler(np.asarray(enc.encode(table, key)), enc)
    >>> state = init_gan_state(key, cfg, enc.cond_dim, enc.encoded_dim)
    >>> state, metrics = engine.run_round(state, sampler.tables, key)
    >>> int(state.step), metrics["d_loss"].shape   # E local steps ran
    (2, (2,))
    >>> raw = synthesize_table(state.g_params, key, cfg, enc, 5)
    >>> raw.shape                                  # (rows, columns), float64
    (5, 2)
    >>> bool(np.isin(raw[:, 1], enc.label_encoders[1].categories).all())
    True
"""
from __future__ import annotations

from functools import partial
from typing import Sequence

import jax

from ..gan.ctgan import CTGANConfig, apply_activations_fused, generator_forward
from ..gan.trainer import GANState, make_train_steps, sample_synthetic
from ..tabular.encoders import SpanInfo, TableEncoders
from .sampler import SamplerTables, draw_batch


class RoundEngine:
    """Jitted sampler-in-the-loop round runner for one table schema.

    ``local_round`` is pure (state, tables, key) -> (state, metrics) and
    deliberately un-jitted so callers can compose it — vmap it over a
    client axis, wrap it with an aggregation step — inside their own jit.
    ``run_round`` / ``run`` are the pre-jitted single-client entry points.
    """

    def __init__(self, cfg: CTGANConfig, spans: Sequence[SpanInfo],
                 cond_spans: Sequence[SpanInfo], *, batch: int,
                 local_steps: int, step_fn=None, dp=None):
        """``dp`` (a :class:`repro.gan.dp.DPConfig`) swaps the scanned
        D/G step for the DP-SGD variant — per-pack clipping + Gaussian
        noising from :func:`repro.gan.dp.make_dp_train_steps` — INSIDE
        the same ``lax.scan``, so a DP'd round is still one program.
        Mutually exclusive with a prebuilt ``step_fn`` (the DP step IS
        the step_fn)."""
        if dp is not None and step_fn is not None:
            raise ValueError("pass either a prebuilt step_fn or dp=, not "
                             "both (the DP config builds the step)")
        self.cfg = cfg
        self.batch = int(batch)
        self.local_steps = int(local_steps)
        self.cond_dim = sum(s.width for s in cond_spans)
        self.dp = dp
        if dp is not None:
            from ..gan.dp import make_dp_train_steps
            step_fn = make_dp_train_steps(cfg, tuple(spans),
                                          tuple(cond_spans),
                                          l2_clip=dp.l2_clip,
                                          noise_mult=dp.noise_mult)
        self.step_fn = step_fn or make_train_steps(cfg, tuple(spans),
                                                   tuple(cond_spans))
        self.run_round = jax.jit(self.local_round)
        self._run_cache: dict[int, object] = {}

    def local_round(self, state: GANState, tables: SamplerTables,
                    key: jax.Array, aux=None):
        """E local steps under one lax.scan, batches drawn on device.

        The round's E x batch conditional draws happen as ONE vectorized
        ``draw_batch`` call at the top of the jitted round (draws are iid,
        so this is distribution-identical to per-step draws and ~10%
        faster on CPU — one threefry/gather pass instead of E), then the
        scan consumes the (E, batch, ...) stack.  Still zero host
        transfers: the draw lives inside the same XLA program as the
        steps.  Returns (state, metrics with leading steps axis).

        ``aux`` (optional pytree) is round-constant context threaded to
        every step as ``step_fn(state, (batch, aux))`` — the hook the fed
        layer uses to hand FedProx-wrapped steps the round's global
        params (see :func:`repro.core.fedavg.fedprox_wrap`)."""
        E = self.local_steps
        with jax.named_scope("draw"):
            big = draw_batch(tables, key, E * self.batch, self.cond_dim)
            batches = jax.tree.map(
                lambda a: a.reshape(E, self.batch, *a.shape[1:]), big)

        def body(st, b):
            return self.step_fn(st, b if aux is None else (b, aux))
        return jax.lax.scan(body, state, batches)

    def clients_round(self, states: GANState, tables: SamplerTables,
                      keys: jax.Array, aux=None, *,
                      client_chunk: int | None = None):
        """All clients' local rounds "in parallel": ``local_round``
        vmapped over the stacked client axis (states/tables from
        ``stack_sampler_tables``, one key per client).  Pure and
        un-jitted like ``local_round`` — the fed layer composes it with
        the weighted merge inside ONE jitted global round
        (:class:`repro.fed.FederatedProgram`).  ``aux`` (if given) is a
        stacked pytree vmapped alongside the states.

        ``client_chunk`` switches the dense vmap to scan-of-vmap: the
        client axis is reshaped into ``(P/chunk, chunk)`` and
        ``lax.map`` runs one vmapped chunk at a time, so the round's
        LIVE activation memory is proportional to ``chunk`` instead of
        ``P`` — the rendering that makes P=1024 fit.  Per-client math
        is untouched (each client's ops never mix across the vmap
        axis), so chunked output is BIT-identical to the dense vmap
        (``tests/test_fed_scale.py``); the chunk size must divide P."""
        P = keys.shape[0]
        if client_chunk is None or client_chunk >= P:
            if aux is None:
                return jax.vmap(self.local_round)(states, tables, keys)
            return jax.vmap(self.local_round)(states, tables, keys, aux)
        if client_chunk < 1 or P % client_chunk:
            raise ValueError(f"client_chunk={client_chunk} must be >= 1 "
                             f"and divide the client count P={P}")
        n_chunks = P // client_chunk

        def chunk(t):
            return jax.tree.map(
                lambda x: x.reshape(n_chunks, client_chunk, *x.shape[1:]), t)

        def unchunk(t):
            return jax.tree.map(
                lambda x: x.reshape(P, *x.shape[2:]), t)

        def one_chunk(args):
            if aux is None:
                st, tb, k = args
                return jax.vmap(self.local_round)(st, tb, k)
            st, tb, k, ax = args
            return jax.vmap(self.local_round)(st, tb, k, ax)

        xs = (chunk(states), chunk(tables), chunk(keys))
        if aux is not None:
            xs = xs + (chunk(aux),)
        out_states, metrics = jax.lax.map(one_chunk, xs)
        return unchunk(out_states), unchunk(metrics)

    def run(self, state: GANState, tables: SamplerTables, key: jax.Array,
            rounds: int):
        """Many rounds in ONE dispatch: scan of local_round over round
        keys.  Metrics come back stacked (rounds, steps)."""
        fn = self._run_cache.get(rounds)
        if fn is None:
            def scanned(st, tb, k):
                def body(s, rk):
                    return self.local_round(s, tb, rk)
                return jax.lax.scan(body, st, jax.random.split(k, rounds))
            fn = self._run_cache[rounds] = jax.jit(scanned)
        return fn(state, tables, key)


@partial(jax.jit, static_argnames=("cfg", "spans", "cond_dim", "n_samples",
                                   "hard", "use_pallas", "interpret"))
def sample_synthetic_conditional(g_params: dict, key: jax.Array,
                                 cfg: CTGANConfig, spans: tuple,
                                 tables: SamplerTables, cond_dim: int,
                                 n_samples: int, hard: bool = True,
                                 use_pallas: bool | None = None,
                                 interpret: bool | None = None):
    """Draw synthetic encoded rows with REAL conditional vectors.

    CTGAN's actual sampling mode: each row's condition vector is drawn
    from the table's training-by-sampling marginals (the log-frequency
    CDFs in ``tables``) instead of zeroed as in ``sample_synthetic``, so
    generated categories follow the smoothed real-data frequencies.  One
    jitted program: cond draw + generator forward + fused whole-row
    activations — still zero per-span dispatches."""
    kc, kz, ka = jax.random.split(key, 3)
    cond, _, _ = draw_batch(tables, kc, n_samples, cond_dim)
    z = jax.random.normal(kz, (n_samples, cfg.z_dim))
    logits = generator_forward(g_params, z, cond, len(cfg.gen_hidden))
    return apply_activations_fused(logits, tuple(spans), ka, cfg.tau,
                                   hard=hard, use_pallas=use_pallas,
                                   interpret=interpret)


def synthesize_table(g_params: dict, key: jax.Array, cfg: CTGANConfig,
                     enc: TableEncoders, n_samples: int, *,
                     hard: bool = True, tables: SamplerTables | None = None,
                     use_pallas: bool | None = None,
                     interpret: bool | None = None):
    """Generator -> raw table through the fused synthesis path.

    One jitted program for generator forward + whole-row activations
    (``sample_synthetic`` with ONE ``segment_activations`` dispatch
    instead of ~2 per span) plus ONE ``vgm_decode_table`` kernel dispatch
    for all continuous columns (and one vectorized categorical inverse
    pass).  Zero per-span/per-column dispatches end to end.  Returns a
    (n_samples, Q) float64 numpy table.

    ``tables`` switches to conditional sampling: condition vectors are
    drawn from these :class:`SamplerTables` marginals instead of zeroed
    (see :func:`sample_synthetic_conditional`) — the mode the serving
    layer exposes per registered tenant.
    """
    if tables is None:
        encoded = sample_synthetic(g_params, key, cfg, tuple(enc.spans()),
                                   enc.cond_dim, n_samples, hard,
                                   use_pallas, interpret)
    else:
        encoded = sample_synthetic_conditional(g_params, key, cfg,
                                               tuple(enc.spans()), tables,
                                               enc.cond_dim, n_samples, hard,
                                               use_pallas, interpret)
    return enc.decode_plan().decode(encoded, use_pallas=use_pallas,
                                    interpret=interpret)
