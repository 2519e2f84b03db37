"""Training driver.

Two modes:
  * ``--federated``: Fed-TGAN-style rounds over P simulated clients —
    token-frequency similarity weights (the paper's §4.2 adapted to token
    data), local steps, weighted aggregation (Pallas kernel path).
  * default: synchronous data-parallel training (the 'centralized'
    reference in federated terms).

On this CPU container use ``--smoke`` (reduced configs).  On real hardware
drop ``--smoke`` and the full assigned config trains under the production
mesh sharding from launch.shardings.

  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 20 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --federated --clients 4 --rounds 5 --local-steps 2 --non-iid
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_NAMES, get_config, get_smoke_config
from ..data.tokens import (TokenDatasetSpec, client_token_streams,
                           fed_weights_from_token_stats,
                           synthetic_token_batches, token_frequency_stats)
from ..kernels import ops as kernel_ops
from ..models import Transformer, TrainState, make_train_step
from ..optim import adam, cosine_schedule
from .compile_cache import enable_compile_cache


def _batch_dict(cfg, tokens: np.ndarray, key) -> dict:
    b = {"labels": jnp.asarray(tokens)}
    if cfg.embed_inputs:
        b["tokens"] = jnp.asarray(tokens)
    else:
        b["features"] = jax.random.normal(
            key, (*tokens.shape, cfg.d_model), jnp.float32).astype(jnp.bfloat16)
    if cfg.xattn_tokens:
        b["vision"] = jax.random.normal(
            key, (tokens.shape[0], cfg.xattn_tokens, cfg.d_model),
            jnp.float32).astype(jnp.bfloat16)
    return b


def run_centralized(cfg, *, steps, batch, seq, lr, seed=0, log_every=5):
    model = Transformer(cfg)
    opt = adam(cosine_schedule(lr, warmup=max(steps // 10, 1), total=steps),
               b1=0.9, b2=0.95, max_grad_norm=1.0)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    state = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    step_fn = jax.jit(make_train_step(model, opt))
    spec = TokenDatasetSpec(cfg.vocab, seq)
    data = synthetic_token_batches(spec, batch, steps, seed=seed)
    hist = []
    t0 = time.perf_counter()
    for s in range(steps):
        state, m = step_fn(state, _batch_dict(cfg, data[s], key))
        if (s + 1) % log_every == 0 or s == steps - 1:
            loss = float(m["loss"])
            hist.append({"step": s + 1, "loss": loss,
                         "t": time.perf_counter() - t0})
            print(f"step {s+1:5d} loss {loss:.4f} "
                  f"({(time.perf_counter()-t0)/(s+1):.2f}s/step)")
    return state, hist


def run_federated(cfg, *, clients, rounds, local_steps, batch, seq, lr,
                  iid=True, seed=0, weighting="fedtgan", dp=None):
    """Fed-TGAN rounds on a language model: vmapped client-parallel local
    training + similarity-weighted merge.

    ``dp`` (a :class:`repro.gan.dp.DPConfig`) switches the merge to
    DP-FedAvg: every client's transmitted delta is L2-clipped to
    ``l2_clip`` and Gaussian noise is added to the weighted mean —
    client-level DP on the wire, the LM counterpart of the per-pack
    DP-SGD the tabular engine runs (see docs/PRIVACY.md)."""
    model = Transformer(cfg)
    opt = adam(lr, b1=0.9, b2=0.95, max_grad_norm=1.0)
    key = jax.random.PRNGKey(seed)

    spec = TokenDatasetSpec(cfg.vocab, seq)
    streams = client_token_streams(spec, clients, batch,
                                   rounds * local_steps, iid=iid, seed=seed)
    # ---- the paper's init protocol, token-adapted ----
    stats = [token_frequency_stats(s, cfg.vocab) for s in streams]
    n_tok = [int(s.size) for s in streams]
    if weighting == "fedtgan":
        w = fed_weights_from_token_stats(stats, n_tok)
    else:
        w = jnp.full((clients,), 1.0 / clients)
    print(f"client weights: {np.asarray(w).round(4)}")

    params = model.init(key)
    state0 = TrainState(params, opt.init(params), jnp.zeros((), jnp.int32))
    states = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (clients,) + x.shape), state0)
    step_fn = make_train_step(model, opt)

    def one_round(states, tokens, rkey):
        """tokens: (P, E, B, S)."""
        start = jax.tree.map(lambda x: x[0], states.params)

        def local(st, toks):
            def body(s, tk):
                return step_fn(s, {"tokens": tk, "labels": tk})
            return jax.lax.scan(body, st, toks)
        states, metrics = jax.vmap(local)(states, tokens)
        if dp is not None:
            from ..gan.dp import _clip_tree, _noise_tree
            deltas = jax.tree.map(lambda p, s: p - s[None], states.params,
                                  start)
            clipped = jax.vmap(lambda d: _clip_tree(d, dp.l2_clip))(deltas)
            mean_d = kernel_ops.weighted_average_tree(clipped, w,
                                                      use_pallas=False)
            mean_d = _noise_tree(mean_d, rkey,
                                 dp.noise_mult * dp.l2_clip / clients)
            merged = jax.tree.map(lambda s, d: s + d, start, mean_d)
        else:
            merged = kernel_ops.weighted_average_tree(states.params, w,
                                                      use_pallas=False)
        merged = jax.tree.map(
            lambda m: jnp.broadcast_to(m[None], (clients,) + m.shape), merged)
        return states._replace(params=merged), metrics

    one_round = jax.jit(one_round)
    hist = []
    t0 = time.perf_counter()
    for r in range(rounds):
        toks = jnp.asarray(np.stack(
            [s[r * local_steps:(r + 1) * local_steps] for s in streams]))
        states, m = one_round(states, toks, jax.random.fold_in(key, r))
        loss = float(jnp.mean(m["loss"]))
        hist.append({"round": r + 1, "loss": loss,
                     "t": time.perf_counter() - t0})
        print(f"round {r+1:4d} mean-loss {loss:.4f}")
    if dp is not None:
        # every client participates every round: q = 1, one release/round
        eps = dp.epsilon(rounds, clients, clients)
        print(f"client-level DP: clip {dp.l2_clip} noise {dp.noise_mult} "
              f"-> eps ~= {eps:.3g} (delta {dp.delta})")
    return states, hist, np.asarray(w)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--federated", action="store_true")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--local-steps", type=int, default=2)
    ap.add_argument("--non-iid", action="store_true")
    ap.add_argument("--uniform-weights", action="store_true")
    ap.add_argument("--dp-noise", type=float, default=None,
                    help="client-level DP noise multiplier for the "
                         "federated merge (off when unset)")
    ap.add_argument("--dp-clip", type=float, default=1.0,
                    help="per-client update L2 clip for --dp-noise")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.dp_noise is not None:
        from ..gan.dp import DPConfig
        dp = DPConfig(l2_clip=args.dp_clip, noise_mult=args.dp_noise)
    else:
        dp = None
    if args.federated:
        run_federated(cfg, clients=args.clients, rounds=args.rounds,
                      local_steps=args.local_steps, batch=args.batch,
                      seq=args.seq, lr=args.lr, iid=not args.non_iid,
                      weighting="uniform" if args.uniform_weights else "fedtgan",
                      dp=dp)
    else:
        run_centralized(cfg, steps=args.steps, batch=args.batch,
                        seq=args.seq, lr=args.lr)


if __name__ == "__main__":
    main()
