"""The four decentralized-training architectures evaluated in the paper
(§3, §5): Centralized, vanilla FL-TGAN, Fed-TGAN (ours), and MD-TGAN — all
driving the SAME CTGAN substrate so comparisons are apples-to-apples.

Simulation model: all clients execute "in parallel" as a stacked client
axis, mirroring the paper's rpc_async fan-out.  Federated training runs
through the :mod:`repro.fed` execution layer: ``setup_federation`` stages
the §4.1 protocol + §4.2 divergence matrix on device, and
:class:`repro.fed.FederatedProgram` lowers whole global rounds — vmapped
local rounds, in-program Fig.4 weighting, ONE fused ``weighted_agg``
merge, broadcast — into single dispatches (``program="fed"``; the
per-round host loop survives as ``program="host"``, the parity oracle
and benchmark baseline).  Per-round wall-clock and bytes-on-wire come
from :mod:`.comm_model`.

Training rounds run through the device-resident :mod:`repro.synth`
engine: conditional batches are drawn inside the round's ``lax.scan``
(no presampled host batches), and synthesis for evaluation goes through
the fused one-dispatch decode kernel.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import (latest_step, restore_fed_checkpoint,
                          save_fed_checkpoint)
from ..fed.faults import (DEFAULT_NORM_MULT, FaultPlan, NoSurvivingClients,
                          PoisonedRunError, UpdateGuard, apply_faults_tree,
                          guard_ok, no_faults, sanitize_stacked,
                          update_diagnostics)
from ..fed.merge import flatten_stacked
from ..fed.merge import replicate as _replicate
from ..fed.program import FederatedProgram
from ..fed.setup import setup_federation
from ..gan.ctgan import CTGANConfig
from ..gan.dp import DPConfig
from ..gan.trainer import GANState, init_gan_state
from ..synth import DeviceSampler, RoundEngine, draw_batch, synthesize_table
from ..tabular.encoders import ColumnSpec, TableEncoders, fit_centralized_encoders
from ..tabular.metrics import similarity_report
from . import comm_model
from .aggregation import weighted_average
from .fedavg import sample_participation

# run_federated's guard default: "pick for me" — UpdateGuard() when a
# FaultPlan is given (a chaos run should survive), no guard otherwise.
_AUTO_GUARD = object()


@dataclasses.dataclass
class FedRunResult:
    name: str
    weights: np.ndarray
    history: list[dict]            # per eval point: round, metrics
    encoders: TableEncoders
    final_g_params: dict
    seconds: float
    comm_bytes_per_round: float
    retries: int = 0               # poisoned eval chunks re-run from ckpt
    blocked: np.ndarray | None = None   # (P,) retry blocklist at exit
    epsilon: float | None = None   # DP (eps, delta) spent per client over the
                                   # run (None when dp= was off; inf when the
                                   # batch exceeds the smallest client, where
                                   # the subsampling estimate is undefined)
    final_d_params: dict | None = None  # merged discriminator (fed/host runs)


def _states_finite(states: GANState) -> bool:
    """Host-side check that the merged model survived the chunk."""
    return all(bool(jnp.all(jnp.isfinite(l))) for l in
               jax.tree.leaves((states.g_params, states.d_params)))


def run_federated(client_data: list[np.ndarray], schema: list[ColumnSpec],
                  *, cfg: CTGANConfig = CTGANConfig(), rounds: int = 20,
                  local_steps: int = 1, seed: int = 0,
                  weighting: str = "fedtgan",
                  eval_real: np.ndarray | None = None,
                  eval_every: int = 5, eval_samples: int = 4096,
                  name: str | None = None,
                  program: str = "fed",
                  faults: FaultPlan | None = None,
                  guard=_AUTO_GUARD,
                  participation: float = 1.0,
                  fedprox_mu: float = 0.0,
                  client_chunk: int | None = None,
                  edges: int | None = None,
                  ckpt_dir: str | None = None,
                  resume: bool = False,
                  max_retries: int = 2,
                  retry_backoff: float = 0.0,
                  dp: DPConfig | None = None,
                  trace=None) -> FedRunResult:
    """Fed-TGAN (weighting='fedtgan'), vanilla FL ('uniform'), or the
    Fed\\SW ablation ('quantity').

    ``program="fed"`` (default): the one-program path — every stretch of
    rounds between eval points is ONE dispatch of
    :class:`repro.fed.FederatedProgram` (scan of global rounds, fused
    merge).  ``program="host"``: the legacy per-round jitted loop with
    the per-leaf :func:`weighted_average` merge — kept as the numerical
    oracle (`tests/test_fed_engine.py`) and the `fed` benchmark baseline.
    Both paths consume the same round-key stream, so they are directly
    comparable at identical seeds — including under a ``FaultPlan``
    (both honor the same schedule, guard, and masked merge).

    Degraded-mode knobs:

    ``faults`` — an (R, P) :class:`~repro.fed.faults.FaultPlan`; rounds
    run through the deadline-masked path (mask + guard + renormalize
    folded into the same single fused merge dispatch).
    ``guard`` — :class:`~repro.fed.faults.UpdateGuard` policy for zeroing
    corrupt updates in-program; defaults to ``UpdateGuard()`` when a plan
    is given, off otherwise; pass ``None`` to force it off (diagnostics
    stay advisory).
    ``participation`` — partial participation fraction; each round keeps
    each client with this probability (highest-weight client always
    survives) via :func:`~repro.core.fedavg.sample_participation`.
    ``fedprox_mu`` — FedProx proximal pull toward the round's global
    params for the survivors (:func:`~repro.core.fedavg.fedprox_wrap`).
    ``client_chunk`` — run local rounds as scan-of-vmap chunks of this
    size (bit-exact vs dense; activation memory fixed per chunk — the
    large-P rendering).  Works with both programs (the host oracle's
    client stage goes through the same chunked path).
    ``edges`` — hierarchical aggregation: merge through this many edge
    aggregators then the federator, one fused ``weighted_agg`` per tier
    (ulp-equal to the flat merge).  ``program="fed"`` only — the host
    oracle keeps the flat per-leaf merge it is the parity baseline for.
    ``ckpt_dir`` — write a checkpoint (states + round cursor + blocklist)
    after every eval chunk; ``resume=True`` restarts from the latest one
    bit-exactly (round keys are absolute).
    ``max_retries`` — on a poisoned chunk (non-finite merged state) the
    run restores the chunk-start state, blocks the suspect clients, and
    re-runs; after ``max_retries`` poisoned chunks it raises
    :class:`~repro.fed.faults.PoisonedRunError`.  ``retry_backoff`` adds
    ``retry_backoff * attempt`` seconds of sleep before each re-run.

    Privacy knobs:

    ``dp`` — a :class:`~repro.gan.dp.DPConfig`; every client's local D
    step becomes DP-SGD (per-pack clip + Gaussian noise,
    :mod:`repro.gan.dp`) INSIDE the scanned round, so the DP'd global
    round is still ONE fused-merge dispatch.  The result's ``epsilon``
    reports the strong-composition estimate at the SMALLEST client (the
    worst-cased guarantee); ``inf`` when the batch exceeds that client's
    rows (the subsampling estimate is undefined there).
    ``trace`` — a :class:`repro.privacy.RoundTrace` to record the run's
    transmitted artifacts into (setup-time §4.1 stats + every round's
    ``(P, D)`` update stack), for the attack harness.  Works under both
    programs (bit-identical round math either way); incompatible with
    the degraded path (faults/guard/partial participation — a masked
    round's wire surface is not the dense stack this records).
    """
    if program not in ("fed", "host"):
        raise ValueError(f"unknown program {program!r}; options: fed, host")
    if edges is not None and program != "fed":
        raise ValueError("hierarchical aggregation (edges=) requires "
                         "program='fed'; the host oracle keeps the flat "
                         "per-leaf merge")
    P = len(client_data)
    if guard is _AUTO_GUARD:
        guard = UpdateGuard() if faults is not None else None
    use_faulted = (faults is not None or guard is not None
                   or participation < 1.0)
    if trace is not None and use_faulted:
        raise ValueError("trace= records the dense transmitted stack; it "
                         "cannot be combined with faults/guard/partial "
                         "participation (the degraded path masks the wire)")
    if use_faulted and faults is None:
        faults = no_faults(rounds, P)
    if faults is not None:
        if (faults.rounds, faults.n_clients) != (rounds, P):
            raise ValueError(
                f"FaultPlan is {(faults.rounds, faults.n_clients)}, run "
                f"needs (rounds, clients) = {(rounds, P)}")
        faults.validate()
    fe = setup_federation(client_data, schema, cfg, seed, weighting)
    enc = fe.enc
    if trace is not None:
        trace.record_setup(fe)
        trace.meta.setdefault("program", program)
        trace.meta.setdefault("seed", seed)
        trace.meta.setdefault("dp", dp is not None)
    prog = FederatedProgram(cfg, fe.spans, fe.cond_spans,
                            batch=cfg.batch_size, local_steps=local_steps,
                            weighting=weighting, participation=participation,
                            fedprox_mu=fedprox_mu, guard=guard,
                            client_chunk=client_chunk, n_edges=edges, dp=dp)
    n_min = int(np.min(np.asarray(fe.n_rows)))
    epsilon = None
    if dp is not None:
        epsilon = (dp.epsilon(rounds * local_steps, cfg.batch_size, n_min)
                   if cfg.batch_size <= n_min else float("inf"))

    model_bytes = comm_model.pytree_bytes(
        jax.tree.map(lambda x: x[0], (fe.states.g_params, fe.states.d_params)))
    bytes_round = comm_model.fl_bytes_per_round(P, model_bytes)

    history = []
    key_eval = jax.random.PRNGKey(seed + 999)
    key_round = jax.random.PRNGKey(seed + 777)
    t0 = time.perf_counter()

    def evaluate(r: int, states: GANState, d_loss, g_loss):
        """Eval at absolute round r (0-based) through the fused synthesis
        path; appends the similarity report to history."""
        g = jax.tree.map(lambda x: x[0], states.g_params)
        synth_raw = synthesize_table(g, jax.random.fold_in(key_eval, r),
                                     cfg, enc, eval_samples)
        rep = similarity_report(eval_real, synth_raw, schema)
        rep.update(round=r + 1, d_loss=float(d_loss), g_loss=float(g_loss),
                   t=time.perf_counter() - t0)
        history.append(rep)

    def is_eval_round(r: int) -> bool:
        return eval_real is not None and ((r + 1) % eval_every == 0
                                          or r == rounds - 1)

    states = fe.states
    w = fe.weights

    if program == "host":
        # the per-round host-loop oracle; the faulted variant mirrors
        # FederatedProgram.faulted_round with the per-leaf merge so
        # host/fed parity holds under every FaultPlan.
        def one_round(states, tables, key):
            states, metrics = prog._clients(states, tables, key)
            merged_g = weighted_average(states.g_params, w)
            merged_d = weighted_average(states.d_params, w)
            states = states._replace(g_params=_replicate(merged_g, P),
                                     d_params=_replicate(merged_d, P))
            return states, metrics

        def one_round_traced(states, tables, key):
            # the oracle's traced rendering: SAME per-leaf merge, with the
            # transmitted (P, D) stack surfaced for the recorder — so a
            # host-recorded trace is directly comparable to a fed one.
            states, metrics = prog._clients(states, tables, key)
            flat = flatten_stacked({"g": states.g_params,
                                    "d": states.d_params})
            merged_g = weighted_average(states.g_params, w)
            merged_d = weighted_average(states.d_params, w)
            states = states._replace(g_params=_replicate(merged_g, P),
                                     d_params=_replicate(merged_d, P))
            return states, metrics, flat

        def one_round_faulted(states, tables, key, fault):
            participate = fault.participate
            if participation < 1.0:
                kp, key = jax.random.split(key)
                participate = participate & sample_participation(
                    w, kp, participation)
            prev_g, prev_d = states.g_params, states.d_params
            states, metrics = prog._clients(states, tables, key)
            tree_prev = {"g": prev_g, "d": prev_d}
            tree_f = apply_faults_tree(
                {"g": states.g_params, "d": states.d_params}, tree_prev,
                fault.nan_mask, fault.scale)
            nm = (guard.norm_mult if guard is not None
                  and guard.norm_mult > 0 else DEFAULT_NORM_MULT)
            diag = update_diagnostics(flatten_stacked(tree_f),
                                      flatten_stacked(tree_prev),
                                      participate, norm_mult=nm)
            ok = guard_ok(guard, diag, participate)
            w_eff = w * ok
            wsum = jnp.sum(w_eff)
            safe = sanitize_stacked(tree_f, ok)
            freeze = lambda m, p: jnp.where(wsum > 0, m, p[0])
            merged_g = jax.tree.map(freeze, weighted_average(safe["g"], w_eff),
                                    prev_g)
            merged_d = jax.tree.map(freeze, weighted_average(safe["d"], w_eff),
                                    prev_d)
            states = states._replace(g_params=_replicate(merged_g, P),
                                     d_params=_replicate(merged_d, P))
            metrics = dict(metrics, client_ok=ok,
                           client_suspect=participate & diag["suspect"],
                           update_norm=diag["norm"],
                           w_eff=w_eff / jnp.maximum(wsum, 1e-12),
                           merged=wsum > 0)
            return states, metrics

        one_round = jax.jit(one_round)
        one_round_traced = jax.jit(one_round_traced)
        one_round_faulted = jax.jit(one_round_faulted)

    def run_chunk(states, start, stop, plan_chunk):
        """Rounds start..stop inclusive.  Returns (states, (d, g) last-
        round mean losses, (chunk_rounds, P) per-round suspect matrix)."""
        suspects = np.zeros((stop + 1 - start, P), bool)
        if program == "host":
            for r in range(start, stop + 1):
                k = jax.random.fold_in(key_round, r)
                if plan_chunk is None:
                    if trace is not None:
                        states, metrics, flat = one_round_traced(
                            states, fe.tables, k)
                        trace.record_round(r, np.asarray(flat))
                    else:
                        states, metrics = one_round(states, fe.tables, k)
                else:
                    fault = jax.tree.map(lambda a: a[r - start], plan_chunk)
                    states, metrics = one_round_faulted(states, fe.tables,
                                                        k, fault)
                    suspects[r - start] = np.asarray(
                        metrics["client_suspect"])
            losses = (jnp.mean(metrics["d_loss"]),
                      jnp.mean(metrics["g_loss"]))
        else:
            keys = prog.fold_round_keys(key_round, start, stop + 1)
            if plan_chunk is None:
                if trace is not None:
                    states, metrics, arts = prog.run_traced(
                        states, fe.tables, fe.S, fe.n_rows, keys)
                    stacks = np.asarray(arts["updates"])
                    for i, r in enumerate(range(start, stop + 1)):
                        trace.record_round(r, stacks[i])
                else:
                    states, metrics = prog.run(states, fe.tables, fe.S,
                                               fe.n_rows, keys)
            else:
                states, metrics = prog.run_faulted(states, fe.tables, fe.S,
                                                   fe.n_rows, keys,
                                                   plan_chunk)
                suspects = np.asarray(metrics["client_suspect"])
            losses = (jnp.mean(metrics["d_loss"][-1]),
                      jnp.mean(metrics["g_loss"][-1]))
        return states, losses, suspects

    stops = [r for r in range(rounds) if is_eval_round(r)]
    if rounds and (not stops or stops[-1] != rounds - 1):
        stops.append(rounds - 1)
    start = 0
    retries = 0
    blocked = np.zeros(P, bool)
    if resume and ckpt_dir and latest_step(ckpt_dir) is not None:
        start, states, blocked = restore_fed_checkpoint(ckpt_dir, fe.states,
                                                        P)
    for stop in stops:
        if stop < start:
            continue                      # chunk already checkpointed
        chunk_plan = None
        if use_faulted:
            chunk_plan = (faults.slice_rounds(start, stop + 1)
                          .block_clients(blocked).validate())
        while True:
            new_states, losses, suspects = run_chunk(states, start, stop,
                                                     chunk_plan)
            if not use_faulted or _states_finite(new_states):
                break
            # poisoned chunk: block the suspects, restore the chunk-start
            # state (held right here — checkpoints cover process death),
            # and re-run the same rounds.
            retries += 1
            if retries > max_retries:
                raise PoisonedRunError(
                    f"global state non-finite after rounds "
                    f"{start}..{stop}; retry budget ({max_retries}) "
                    f"exhausted")
            # blocklist from the FIRST suspect round: once the merge is
            # poisoned, every later round flags everyone (all clients
            # train from NaN params) — the union would block the world.
            bad_rounds = np.nonzero(suspects.any(axis=1))[0]
            offenders = (suspects[bad_rounds[0]] & ~blocked
                         if bad_rounds.size else np.zeros(P, bool))
            if not offenders.any():
                raise PoisonedRunError(
                    f"global state non-finite after rounds {start}..{stop} "
                    f"but no client is suspect — cannot form a blocklist")
            blocked |= offenders
            chunk_plan = (faults.slice_rounds(start, stop + 1)
                          .block_clients(blocked).validate())
            if retry_backoff > 0:
                time.sleep(retry_backoff * retries)
        states = new_states
        if ckpt_dir:
            save_fed_checkpoint(ckpt_dir, stop + 1, states, blocked)
        if is_eval_round(stop):
            evaluate(stop, states, *losses)
        start = stop + 1
    dt = time.perf_counter() - t0
    return FedRunResult(name or f"fed-{weighting}", np.asarray(fe.weights),
                        history, enc,
                        jax.tree.map(lambda x: x[0], states.g_params),
                        dt, bytes_round, retries=retries,
                        blocked=blocked if use_faulted else None,
                        epsilon=epsilon,
                        final_d_params=jax.tree.map(lambda x: x[0],
                                                    states.d_params))


def run_centralized(data: np.ndarray, schema: list[ColumnSpec], *,
                    cfg: CTGANConfig = CTGANConfig(), epoch_steps: int = 20,
                    epochs: int = 1, seed: int = 0,
                    eval_real: np.ndarray | None = None,
                    eval_every: int = 5, eval_samples: int = 4096) -> FedRunResult:
    """Single-site baseline: pooled data, centrally fitted encoders."""
    key = jax.random.PRNGKey(seed)
    k_enc, k_model, k_e2 = jax.random.split(key, 3)
    enc = fit_centralized_encoders(data, schema, k_enc)
    spans = tuple(enc.spans())
    cond_spans = tuple(enc.condition_spans())
    sampler = DeviceSampler(np.asarray(enc.encode(data, k_e2)), enc)
    state = init_gan_state(k_model, cfg, enc.cond_dim, enc.encoded_dim)
    engine = RoundEngine(cfg, spans, cond_spans, batch=cfg.batch_size,
                         local_steps=epoch_steps)

    history = []
    key_ep = jax.random.PRNGKey(seed + 333)
    t0 = time.perf_counter()
    for ep in range(epochs):
        # whole epoch = one jitted scan (draws + steps on device)
        state, metrics = engine.run_round(state, sampler.tables,
                                          jax.random.fold_in(key_ep, ep))
        if eval_real is not None and ((ep + 1) % eval_every == 0 or ep == epochs - 1):
            synth_raw = synthesize_table(state.g_params,
                                         jax.random.fold_in(key, ep), cfg,
                                         enc, eval_samples)
            rep = similarity_report(eval_real, synth_raw, schema)
            rep.update(round=ep + 1, d_loss=float(metrics["d_loss"][-1]),
                       g_loss=float(metrics["g_loss"][-1]),
                       t=time.perf_counter() - t0)
            history.append(rep)
    dt = time.perf_counter() - t0
    return FedRunResult("centralized", np.ones(1), history, enc,
                        state.g_params, dt, 0.0)


def run_mdtgan(client_data: list[np.ndarray], schema: list[ColumnSpec], *,
               cfg: CTGANConfig = CTGANConfig(), epochs: int = 20,
               steps_per_epoch: int = 1, seed: int = 0,
               eval_real: np.ndarray | None = None, eval_every: int = 5,
               eval_samples: int = 4096, swap: bool = True) -> FedRunResult:
    """MD-GAN [9] adapted to CTGAN: ONE central generator, one
    discriminator per client, uniform gradient averaging for G, and the
    peer-to-peer discriminator swap each epoch."""
    P = len(client_data)
    # MD also needs agreed encoders; grant it the same §4.1 init (the paper
    # does the same for fairness).
    fe = setup_federation(client_data, schema, cfg, seed, "uniform")
    enc, spans, cond_spans, tables, states = (fe.enc, fe.spans, fe.cond_spans,
                                              fe.tables, fe.states)
    # keep one central G (slice 0), stack of P discriminators.
    g_state = jax.tree.map(lambda x: x[0], states)

    def md_step(g_params, g_opt, d_states, tables, key):
        """One global step: every client D trains on central-G fakes; G
        updates from the average of per-client generator losses.  Client
        batches are drawn on device (no host staging)."""
        from ..gan.ctgan import (apply_activations_fused, conditional_loss,
                                 discriminator_forward, generator_forward,
                                 gradient_penalty)
        from ..optim import adam
        opt = adam(cfg.lr, cfg.b1, cfg.b2)
        key, kb = jax.random.split(key)
        conds, masks, reals = jax.vmap(
            lambda tb, k: draw_batch(tb, k, cfg.batch_size, enc.cond_dim))(
            tables, jax.random.split(kb, P))
        n_hidden = len(cfg.gen_hidden)

        def d_loss_one(d_params, cond, real, k):
            kz, ka, k1, k2, kgp = jax.random.split(k, 5)
            z = jax.random.normal(kz, (real.shape[0], cfg.z_dim))
            fake = apply_activations_fused(
                generator_forward(g_params, z, cond, n_hidden), spans, ka, cfg.tau)
            fi = jnp.concatenate([fake, cond], 1)
            ri = jnp.concatenate([real, cond], 1)
            yf = discriminator_forward(d_params, fi, k1, cfg)
            yr = discriminator_forward(d_params, ri, k2, cfg)
            return (jnp.mean(yf) - jnp.mean(yr)
                    + cfg.gp_lambda * gradient_penalty(d_params, ri, fi, kgp, cfg))

        def d_update(dst, cond, real, k):
            grads = jax.grad(d_loss_one)(dst.d_params, cond, real, k)
            d_params, d_opt = opt.update(grads, dst.d_opt, dst.d_params)
            return dst._replace(d_params=d_params, d_opt=d_opt)

        kd = jax.random.split(key, P + 1)
        d_states = jax.vmap(d_update)(d_states, conds, reals,
                                      jnp.stack(list(kd[:P])))

        def g_loss(gp, k):
            def per_client(d_params, cond, mask, kk):
                kz, ka, kdd = jax.random.split(kk, 3)
                z = jax.random.normal(kz, (cond.shape[0], cfg.z_dim))
                logits = generator_forward(gp, z, cond, n_hidden)
                fake = apply_activations_fused(logits, spans, ka, cfg.tau)
                fi = jnp.concatenate([fake, cond], 1)
                yf = discriminator_forward(d_params, fi, kdd, cfg)
                return -jnp.mean(yf) + conditional_loss(logits, cond, mask,
                                                        cond_spans)
            ks = jax.random.split(k, P)
            losses = jax.vmap(per_client)(d_states.d_params, conds, masks, ks)
            return jnp.mean(losses)          # equal weights — MD-GAN's flaw

        gl, g_grads = jax.value_and_grad(g_loss)(g_params, kd[P])
        g_params, g_opt = opt.update(g_grads, g_opt, g_params)
        return g_params, g_opt, d_states, gl

    md_step = jax.jit(md_step)
    d_bytes = comm_model.pytree_bytes(jax.tree.map(lambda x: x[0],
                                                   states.d_params))
    bytes_epoch = comm_model.md_bytes_per_epoch(
        P, steps_per_epoch, cfg.batch_size,
        enc.encoded_dim + enc.cond_dim, d_bytes, swap=swap)

    g_params, g_opt = g_state.g_params, g_state.g_opt
    d_states = states
    history = []
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed + 1)
    t0 = time.perf_counter()
    for ep in range(epochs):
        for _ in range(steps_per_epoch):
            key, k = jax.random.split(key)
            g_params, g_opt, d_states, gl = md_step(g_params, g_opt,
                                                    d_states, tables, k)
        if swap:                                   # p2p discriminator swap
            perm = rng.permutation(P)
            d_states = jax.tree.map(lambda x: x[perm], d_states)
        if eval_real is not None and ((ep + 1) % eval_every == 0 or ep == epochs - 1):
            synth_raw = synthesize_table(g_params, jax.random.fold_in(key, ep),
                                         cfg, enc, eval_samples)
            rep = similarity_report(eval_real, synth_raw, schema)
            rep.update(round=ep + 1, g_loss=float(gl),
                       t=time.perf_counter() - t0)
            history.append(rep)
    dt = time.perf_counter() - t0
    return FedRunResult("md-tgan", np.full(P, 1.0 / P), history, enc,
                        g_params, dt, bytes_epoch)
