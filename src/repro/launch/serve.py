"""Serving drivers: LLM prefill+decode, and streaming tabular synthesis.

LLM mode (batched prefill + token-by-token decode):

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --batch 4 --prompt-len 32 --gen 16

Tabular mode (the paper's own serving workload — a short federated
warm-up, then a mixed-size request trace through the streaming
``repro.serve`` subsystem; see docs/SERVING.md):

  PYTHONPATH=src python -m repro.launch.serve --tabular \
      --requests 16 --sizes 100,256,777 [--conditional]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_NAMES, get_config, get_smoke_config, supported_shapes
from ..models import Transformer, make_serve_step
from .compile_cache import enable_compile_cache


def prefill_and_decode(cfg, *, batch, prompt_len, gen_tokens, seed=0,
                       temperature=1.0, replay_prefill=False):
    """One-pass prefill (Transformer.prefill) + token-by-token decode.
    ``replay_prefill`` uses the decode path to fill the caches instead —
    the two are asserted equivalent in tests/test_prefill.py."""
    model = Transformer(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab)

    serve = jax.jit(make_serve_step(model))
    extras = {}
    if cfg.xattn_tokens:
        extras["vision"] = jax.random.normal(
            key, (batch, cfg.xattn_tokens, cfg.d_model)).astype(jnp.bfloat16)

    max_len = prompt_len + gen_tokens
    t0 = time.perf_counter()
    if replay_prefill:
        from ..models.attention import KVCache
        caches = jax.tree.map(
            lambda c: KVCache(c.k, c.v, jnp.zeros_like(c.length))
            if isinstance(c, KVCache) else c,
            model.init_caches(batch, max_len),
            is_leaf=lambda x: isinstance(x, KVCache))
        logits = None
        for t in range(prompt_len):
            logits, caches = serve(params, caches,
                                   {"token": prompts[:, t:t+1], **extras})
    else:
        prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len))
        logits, caches = prefill(params, {"tokens": prompts, **extras})
    t_prefill = time.perf_counter() - t0

    # ---- decode ----
    out_tokens = []
    tok = jnp.argmax(logits, axis=-1)[:, None]
    t0 = time.perf_counter()
    for t in range(gen_tokens):
        key, k = jax.random.split(key)
        logits, caches = serve(params, caches, {"token": tok, **extras})
        if temperature > 0:
            tok = jax.random.categorical(k, logits / temperature)[:, None]
        else:
            tok = jnp.argmax(logits, axis=-1)[:, None]
        out_tokens.append(np.asarray(tok[:, 0]))
    t_decode = time.perf_counter() - t0
    gen = np.stack(out_tokens, axis=1)
    return gen, {"prefill_s": t_prefill, "decode_s": t_decode,
                 "tok_per_s": batch * gen_tokens / max(t_decode, 1e-9)}


def run_tabular_server(*, requests: int = 16,
                       sizes: tuple[int, ...] = (100, 256, 777),
                       rounds: int = 4, local_steps: int = 2,
                       n_rows: int = 1500, conditional: bool = False,
                       scheduler: str = "fifo", seed: int = 0,
                       quiet: bool = False) -> dict:
    """Warm up a generator federatedly, then serve a mixed-size trace
    through the streaming subsystem (``repro.serve``).

    The canonical zero-to-serving path used by ``--tabular`` here and by
    ``examples/serve_batched.py``: a short Fed-TGAN run produces
    (g_params, encoders), the table is registered with a ladder fitted to
    the expected sizes, ``warmup()`` compiles one program per bucket, and
    the trace drains through the double-buffered pipeline.
    ``scheduler="continuous"`` drains by deficit-round-robin dispatch
    cycles instead of FIFO (identical on this single-tenant trace — the
    flag is the production switch; see docs/SERVING.md).  Returns the
    server stats dict plus throughput fields."""
    from ..core.architectures import run_federated
    from ..gan.ctgan import CTGANConfig
    from ..serve import (StreamingSynthesizer, TableRegistry,
                         ladder_from_sizes)
    from ..tabular import make_dataset, partition_quantity_skew

    def say(msg):
        if not quiet:
            print(msg)

    ds = make_dataset("adult", n_rows=n_rows, seed=seed)
    parts = partition_quantity_skew(ds, n_clients=3, small_rows=200)
    cfg = CTGANConfig(batch_size=100, gen_hidden=(128, 128),
                      disc_hidden=(128, 128), pac=10, z_dim=64)
    say(f"warm-up: {rounds} federated rounds on {ds.name} "
        f"({ds.n_rows} rows, {len(ds.schema)} cols)")
    res = run_federated(parts, ds.schema, cfg=cfg, rounds=rounds,
                        local_steps=local_steps, seed=seed)

    registry = TableRegistry()
    key = jax.random.PRNGKey(seed + 7)
    registry.register(
        ds.name, cfg, res.encoders, res.final_g_params,
        ladder=ladder_from_sizes(sizes),
        encoded=np.asarray(res.encoders.encode(ds.data, key)))
    server = StreamingSynthesizer(registry, scheduler=scheduler)
    built = server.warmup(conditional=conditional)   # only the mode served
    ladder = registry.get(ds.name).ladder.buckets
    say(f"warmup: compiled {built} programs for buckets {ladder}")

    for r in range(requests):
        server.submit(ds.name, sizes[r % len(sizes)],
                      key=jax.random.fold_in(key, r),
                      conditional=conditional)
    t0 = time.perf_counter()
    responses = server.serve()
    dt = time.perf_counter() - t0

    stats = server.stats()
    rows = sum(r.rows for r in responses)
    stats.update(seconds=dt, rows_per_s=rows / max(dt, 1e-9),
                 buckets=list(ladder))
    say(f"served {len(responses)} requests / {rows} rows in {dt:.2f}s "
        f"({stats['rows_per_s']:.0f} rows/s) — "
        f"{stats['serving_compiles']} recompiles, "
        f"{stats['cache_hits']}/{len(responses)} jit cache hits, "
        f"decode dispatches {stats['decode_dispatches']} (1 per request, "
        f"was {sum(c.kind == 'continuous' for c in ds.schema)} per-column)")
    return stats


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--tabular", action="store_true",
                    help="serve streaming tabular synthesis instead of an "
                         "LLM (repro.serve subsystem)")
    ap.add_argument("--requests", type=int, default=16,
                    help="[tabular] trace length")
    ap.add_argument("--sizes", default="100,256,777",
                    help="[tabular] comma list of request row counts, "
                         "cycled over the trace")
    ap.add_argument("--rounds", type=int, default=4,
                    help="[tabular] federated warm-up rounds")
    ap.add_argument("--conditional", action="store_true",
                    help="[tabular] draw condition vectors from the "
                         "table's sampler marginals")
    ap.add_argument("--scheduler", choices=("fifo", "continuous"),
                    default="fifo",
                    help="[tabular] queue drain: submission-order FIFO or "
                         "continuous batching (per-tenant deficit round "
                         "robin dispatch cycles)")
    args = ap.parse_args()

    if args.tabular:
        run_tabular_server(
            requests=args.requests,
            sizes=tuple(int(s) for s in args.sizes.split(",")),
            rounds=args.rounds, conditional=args.conditional,
            scheduler=args.scheduler)
        return

    if "decode_32k" not in supported_shapes(args.arch):
        raise SystemExit(f"{args.arch} is encoder-only: no decode step "
                         "(DESIGN.md §5)")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    gen, stats = prefill_and_decode(cfg, batch=args.batch,
                                    prompt_len=args.prompt_len,
                                    gen_tokens=args.gen)
    print(f"generated {gen.shape} tokens | prefill {stats['prefill_s']:.2f}s "
          f"decode {stats['decode_s']:.2f}s "
          f"({stats['tok_per_s']:.1f} tok/s)")
    print("sample:", gen[0][:16])


if __name__ == "__main__":
    main()
