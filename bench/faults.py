"""Faults planted under a cell's timed path, to show its check fails them.

Each fault takes ``patch(owner, name, value)`` (``monkeypatch.setattr`` in
the tests, plain ``setattr`` in ``bench/readings.py``) and breaks the
program underneath before the cell builds it.  The check of a sound
program must read each of them as not correct.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _wrap_step(patch, wrap):
    import repro.synth.engine as engine
    build = engine.make_train_steps
    patch(engine, "make_train_steps", lambda *a, **k: wrap(build(*a, **k)))


def unchanged(patch):
    """Training: a step that returns its state unchanged."""
    _wrap_step(patch, lambda step: lambda st, b: (st, step(st, b)[1]))


def half_batch(patch):
    """Training: half of the batch left out, the mean taken over the
    rest."""
    _wrap_step(patch, lambda step: lambda st, b: step(
        st, tuple(x[:x.shape[0] // 2] for x in b)))


def no_merge(patch):
    """Training: the federated average (the exchange between clients)
    left out."""
    import repro.fed.program as program
    patch(program.FederatedProgram, "merge_states",
          lambda self, states, w: states)


def weights_toward_uniform(patch):
    """Training: the merge's §4.2 weights pulled 1% toward uniform where
    the merge kernel takes them."""
    from repro.kernels import ops
    avg = ops.weighted_average_flat
    patch(ops, "weighted_average_flat", lambda flat, w, **kw: avg(
        flat, 0.99 * w / w.sum() + 0.01 / w.shape[0], **kw))


def one_bf16_pass(patch):
    """Training: every matrix product's operands rounded to bfloat16."""
    import repro.gan.trainer as trainer
    bf = lambda t: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), t)
    g, d = trainer.generator_forward, trainer.discriminator_forward
    patch(trainer, "generator_forward",
          lambda p, z, c, n: g(bf(p), bf(z), bf(c), n))
    patch(trainer, "discriminator_forward",
          lambda p, x, k, cfg, train=True: d(bf(p), bf(x), k, cfg, train))


TRAIN = (unchanged, half_batch, no_merge, weights_toward_uniform,
         one_bf16_pass)
