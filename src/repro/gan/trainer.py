"""Jitted CTGAN train steps + local-epoch runners.

``make_train_steps`` builds (disc_step, gen_step, combined_step) closed over
the encoded-row spans and config.  ``local_train_scan`` runs E local steps
under ``lax.scan`` — the unit of work a federated client performs between
aggregations; it is vmap-able over a stacked client axis, which is how the
simulation drivers execute all clients "in parallel" like the real system.

The training drivers now compose ``make_train_steps`` with on-device
conditional sampling through :class:`repro.synth.RoundEngine`, so
``make_round_batches`` / ``local_train_scan`` remain here as the
presampled-path baseline (benchmarked against the engine in
``benchmarks/synth_bench.py``).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..optim import adam
from ..tabular.encoders import SpanInfo
from .ctgan import (CTGANConfig, apply_activations_fused, conditional_loss,
                    discriminator_forward, generator_forward,
                    gradient_penalty, init_discriminator, init_generator)


class GANState(NamedTuple):
    g_params: dict
    d_params: dict
    g_opt: tuple
    d_opt: tuple
    step: jnp.ndarray
    rng: jax.Array


def init_gan_state(key: jax.Array, cfg: CTGANConfig, cond_dim: int,
                   data_dim: int) -> GANState:
    kg, kd, kr = jax.random.split(key, 3)
    g = init_generator(kg, cfg, cond_dim, data_dim)
    d = init_discriminator(kd, cfg, cond_dim, data_dim)
    opt = adam(cfg.lr, cfg.b1, cfg.b2)
    return GANState(g, d, opt.init(g), opt.init(d),
                    jnp.zeros((), jnp.int32), kr)


def make_train_steps(cfg: CTGANConfig, spans: Sequence[SpanInfo],
                     cond_spans: Sequence[SpanInfo]):
    """Returns ``step(state, batch) -> (state, metrics)`` where ``batch`` is
    (cond, mask, real) float32 arrays.  One step = 1 critic + 1 generator
    update (CTGAN's n_critic=1)."""
    n_hidden = len(cfg.gen_hidden)
    opt = adam(cfg.lr, cfg.b1, cfg.b2)
    spans = tuple(spans)
    cond_spans = tuple(cond_spans)

    def d_loss_fn(d_params, g_params, cond, real, key):
        kz, ka, kd1, kd2, kgp = jax.random.split(key, 5)
        z = jax.random.normal(kz, (real.shape[0], cfg.z_dim))
        logits = generator_forward(g_params, z, cond, n_hidden)
        fake = apply_activations_fused(logits, spans, ka, cfg.tau)
        fake_in = jnp.concatenate([fake, cond], axis=1)
        real_in = jnp.concatenate([real, cond], axis=1)
        y_fake = discriminator_forward(d_params, fake_in, kd1, cfg)
        y_real = discriminator_forward(d_params, real_in, kd2, cfg)
        gp = gradient_penalty(d_params, real_in, fake_in, kgp, cfg)
        wgan = jnp.mean(y_fake) - jnp.mean(y_real)
        return wgan + cfg.gp_lambda * gp, (wgan, gp)

    def g_loss_fn(g_params, d_params, cond, mask, key):
        kz, ka, kd = jax.random.split(key, 3)
        z = jax.random.normal(kz, (cond.shape[0], cfg.z_dim))
        logits = generator_forward(g_params, z, cond, n_hidden)
        fake = apply_activations_fused(logits, spans, ka, cfg.tau)
        fake_in = jnp.concatenate([fake, cond], axis=1)
        y_fake = discriminator_forward(d_params, fake_in, kd, cfg)
        ce = conditional_loss(logits, cond, mask, cond_spans)
        return -jnp.mean(y_fake) + ce, ce

    def step(state: GANState, batch):
        cond, mask, real = batch
        key, kd, kg = jax.random.split(state.rng, 3)
        with jax.named_scope("d_step"):
            (dl, (wgan, gp)), d_grads = jax.value_and_grad(
                d_loss_fn, has_aux=True)(state.d_params, state.g_params,
                                         cond, real, kd)
            d_params, d_opt = opt.update(d_grads, state.d_opt,
                                         state.d_params)
        with jax.named_scope("g_step"):
            (gl, ce), g_grads = jax.value_and_grad(g_loss_fn, has_aux=True)(
                state.g_params, d_params, cond, mask, kg)
            g_params, g_opt = opt.update(g_grads, state.g_opt,
                                         state.g_params)
        new = GANState(g_params, d_params, g_opt, d_opt, state.step + 1, key)
        return new, {"d_loss": dl, "g_loss": gl, "wgan": wgan, "gp": gp, "ce": ce}

    return step


def make_round_batches(samplers, rounds: int, steps_per_round: int,
                       batch: int):
    """Stage (cond, mask, real) batches for vmapped local scans.

    Each client's sampler draws all ``rounds x steps x batch`` rows in one
    vectorized pass (no per-row host loop); the per-client results stack
    into ``(clients, rounds, steps, batch, ...)`` jnp arrays ready to be
    indexed per round and fed to ``jax.vmap(local_train_scan)``."""
    conds, masks, reals = zip(*[s.presample_rounds(rounds, steps_per_round,
                                                   batch) for s in samplers])
    return (jnp.asarray(np.stack(conds)), jnp.asarray(np.stack(masks)),
            jnp.asarray(np.stack(reals)))


def local_train_scan(step_fn, state: GANState, round_batches):
    """Run E pre-sampled local steps via lax.scan.

    ``round_batches``: (cond, mask, real) each with leading steps axis."""
    def body(st, batch):
        st, metrics = step_fn(st, batch)
        return st, metrics
    return jax.lax.scan(body, state, round_batches)


@partial(jax.jit, static_argnames=("cfg", "spans", "cond_dim", "n_samples",
                                   "hard", "use_pallas", "interpret"))
def sample_synthetic(g_params: dict, key: jax.Array, cfg: CTGANConfig,
                     spans: tuple, cond_dim: int, n_samples: int,
                     hard: bool = True, use_pallas: bool | None = None,
                     interpret: bool | None = None) -> jnp.ndarray:
    """Draw synthetic encoded rows (cond vector zeroed, as in CTGAN's
    unconditional sampling mode).  Generator forward + fused whole-row
    activations in one jitted program — zero per-span dispatches."""
    kz, ka = jax.random.split(key)
    z = jax.random.normal(kz, (n_samples, cfg.z_dim))
    cond = jnp.zeros((n_samples, cond_dim))
    logits = generator_forward(g_params, z, cond, len(cfg.gen_hidden))
    return apply_activations_fused(logits, spans, ka, cfg.tau, hard=hard,
                                   use_pallas=use_pallas, interpret=interpret)
