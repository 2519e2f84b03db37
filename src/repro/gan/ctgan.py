"""CTGAN (Xu et al., NeurIPS'19) in pure JAX — the tabular GAN that
Fed-TGAN federates.

Faithful pieces: residual FC generator with BN+ReLU, per-span output
activations (tanh for VGM alphas, Gumbel-softmax tau=0.2 for one-hots),
PacGAN discriminator (pac=10) with LeakyReLU+Dropout, WGAN-GP critic loss,
conditional-vector + training-by-sampling, Adam(2e-4, betas=(0.5,0.9)).

Params are plain dicts (pytrees); all forward/loss functions are pure.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from ..kernels.ref import GUMBEL_EPS
from ..tabular.encoders import SpanInfo


@dataclasses.dataclass(frozen=True)
class CTGANConfig:
    z_dim: int = 128
    gen_hidden: tuple[int, ...] = (256, 256)
    disc_hidden: tuple[int, ...] = (256, 256)
    pac: int = 10
    tau: float = 0.2
    gp_lambda: float = 10.0
    dropout: float = 0.5
    lr: float = 2e-4
    b1: float = 0.5
    b2: float = 0.9
    batch_size: int = 500


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------

def _linear_init(key, fan_in, fan_out):
    kw, kb = jax.random.split(key)
    lim = 1.0 / jnp.sqrt(fan_in)
    return {"w": jax.random.uniform(kw, (fan_in, fan_out), jnp.float32, -lim, lim),
            "b": jax.random.uniform(kb, (fan_out,), jnp.float32, -lim, lim)}


def init_generator(key: jax.Array, cfg: CTGANConfig, cond_dim: int,
                   data_dim: int) -> dict:
    keys = jax.random.split(key, len(cfg.gen_hidden) + 1)
    params, dim = {}, cfg.z_dim + cond_dim
    for i, h in enumerate(cfg.gen_hidden):
        params[f"res{i}"] = {
            "fc": _linear_init(keys[i], dim, h),
            "bn_scale": jnp.ones((h,), jnp.float32),
            "bn_bias": jnp.zeros((h,), jnp.float32),
        }
        dim += h                                  # residual concat
    params["out"] = _linear_init(keys[-1], dim, data_dim)
    return params


def is_pre_bn_bias(path) -> bool:
    """True for a generator leaf ``res*/fc/b`` (a ``tree_flatten_with_path``
    key path): the bias right before a batch norm, which subtracts it
    again, so its exact gradient is zero and what a backward pass computes
    for it is rounding noise."""
    keys = [getattr(k, "key", None) for k in path]
    return keys[-2:] == ["fc", "b"] and str(keys[0]).startswith("res")


def init_discriminator(key: jax.Array, cfg: CTGANConfig, cond_dim: int,
                       data_dim: int) -> dict:
    keys = jax.random.split(key, len(cfg.disc_hidden) + 1)
    params, dim = {}, (data_dim + cond_dim) * cfg.pac
    for i, h in enumerate(cfg.disc_hidden):
        params[f"fc{i}"] = _linear_init(keys[i], dim, h)
        dim = h
    params["out"] = _linear_init(keys[-1], dim, 1)
    return params


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _batch_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=0, keepdims=True)
    var = jnp.var(x, axis=0, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def generator_forward(params: dict, z: jnp.ndarray, cond: jnp.ndarray,
                      n_hidden: int) -> jnp.ndarray:
    """Returns raw logits over the encoded row layout."""
    h = jnp.concatenate([z, cond], axis=1)
    for i in range(n_hidden):
        p = params[f"res{i}"]
        y = h @ p["fc"]["w"] + p["fc"]["b"]
        y = _batch_norm(y, p["bn_scale"], p["bn_bias"])
        y = jax.nn.relu(y)
        h = jnp.concatenate([h, y], axis=1)       # CTGAN Residual
    return h @ params["out"]["w"] + params["out"]["b"]


def apply_activations(logits: jnp.ndarray, spans: Sequence[SpanInfo],
                      key: jax.Array, tau: float,
                      hard: bool = False) -> jnp.ndarray:
    """Per-span tanh / Gumbel-softmax (straight-through when ``hard``).

    The per-span oracle loop (~2 dispatches per span).  The hot paths use
    :func:`apply_activations_fused` — one kernel dispatch for the whole
    row layout, bit-identical values and matching gradients.
    """
    parts = []
    keys = jax.random.split(key, len(spans))
    for s, k in zip(spans, keys):
        seg = logits[:, s.start:s.start + s.width]
        if s.activation == "tanh":
            parts.append(jnp.tanh(seg))
        else:
            g = -jnp.log(-jnp.log(jax.random.uniform(k, seg.shape)
                                  + GUMBEL_EPS) + GUMBEL_EPS)
            y = jax.nn.softmax((seg + g) / tau, axis=1)
            if hard:
                y_hard = jax.nn.one_hot(jnp.argmax(y, axis=1), s.width)
                # ST estimator: forward y_hard, backward the soft grad
                y = y_hard - jax.lax.stop_gradient(y) + y
            parts.append(y)
    return jnp.concatenate(parts, axis=1)


def apply_activations_fused(logits: jnp.ndarray, spans: Sequence[SpanInfo],
                            key: jax.Array, tau: float, hard: bool = False,
                            *, use_pallas: bool | None = None,
                            interpret: bool | None = None) -> jnp.ndarray:
    """Fused drop-in for :func:`apply_activations`: ALL spans in ONE
    ``kernels.ops.segment_activations`` dispatch (same per-span key
    streams, so values are bit-identical to the loop; the custom VJP
    matches its gradients, ST estimator included).

    The fused path computes and returns float32 — the encoded row
    layout's dtype everywhere in this repo.  Callers feeding wider
    logits (e.g. under x64) should not expect dtype preservation.
    """
    from ..kernels import ops
    return ops.segment_activations(logits, spans, key, tau, hard=hard,
                                   use_pallas=use_pallas,
                                   interpret=interpret)


def discriminator_forward(params: dict, x: jnp.ndarray, key: jax.Array,
                          cfg: CTGANConfig, train: bool = True) -> jnp.ndarray:
    """PacGAN: rows are grouped in packs of ``pac`` before the MLP."""
    b = x.shape[0] // cfg.pac
    h = x.reshape(b, -1)
    keys = jax.random.split(key, len(cfg.disc_hidden))
    for i in range(len(cfg.disc_hidden)):
        p = params[f"fc{i}"]
        h = h @ p["w"] + p["b"]
        h = jax.nn.leaky_relu(h, 0.2)
        if train and cfg.dropout > 0:
            keep = jax.random.bernoulli(keys[i], 1 - cfg.dropout, h.shape)
            h = jnp.where(keep, h / (1 - cfg.dropout), 0.0)
    return (h @ params["out"]["w"] + params["out"]["b"])[:, 0]


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

@jax.named_scope("gradient_penalty")
def gradient_penalty(d_params: dict, real: jnp.ndarray, fake: jnp.ndarray,
                     key: jax.Array, cfg: CTGANConfig) -> jnp.ndarray:
    """WGAN-GP with pac-aware interpolation (one epsilon per pack)."""
    kz, kd = jax.random.split(key)
    b = real.shape[0] // cfg.pac
    eps = jax.random.uniform(kz, (b, 1, 1))
    r = real.reshape(b, cfg.pac, -1)
    f = fake.reshape(b, cfg.pac, -1)
    inter = (eps * r + (1 - eps) * f).reshape(real.shape)

    def critic(x):
        return jnp.sum(discriminator_forward(d_params, x, kd, cfg, train=False))

    g = jax.grad(critic)(inter).reshape(b, -1)
    gn = jnp.sqrt(jnp.sum(g * g, axis=1) + 1e-12)
    return jnp.mean((gn - 1.0) ** 2)


def conditional_loss(logits: jnp.ndarray, cond: jnp.ndarray,
                     mask: jnp.ndarray, spans: Sequence[SpanInfo]) -> jnp.ndarray:
    """Cross-entropy forcing the generator to emit the conditioned category.

    ``cond`` is the concatenated condition vector over condition spans,
    ``mask`` (B, n_cond_spans) one-hot selects which span was conditioned.
    """
    total = jnp.zeros(logits.shape[0])
    pos = 0
    for si, s in enumerate(spans):
        seg = logits[:, s.start:s.start + s.width]
        tgt = cond[:, pos:pos + s.width]
        logp = jax.nn.log_softmax(seg, axis=1)
        ce = -jnp.sum(tgt * logp, axis=1)
        total = total + ce * mask[:, si]
        pos += s.width
    return jnp.mean(total)
