#!/usr/bin/env python3
"""Chip smoke: federated CTGAN trains and serves on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the client-sharded round, four chips

One chip, one process, in this order:

1. ``train``: ``run_federated(program="fed")`` on Adult (40,000 rows, the
   paper's subsample) split over 5 clients by quantity skew, with the
   paper's CTGAN (``configs/ctgan_paper.py``).  It runs 3 rounds of 2
   local steps in one eval chunk.  Losses and the merged G and D must be
   finite.  Every fused stage must take the Mosaic route, with no ``*_ref``
   dispatch and no interpreted kernel.
2. ``kernels``: each of the four fused kernels, at the run's shapes,
   against its jnp ref (``use_pallas=False``), within the tolerances
   stated in ``TOLERANCES``.
3. ``serve``: the trained generator behind the continuous-batching
   server.  It must serve with 0 recompiles and 1 decode dispatch per
   request, and each response must be bit-identical to
   ``synthesize_table`` at its bucket.

``--chips 4`` runs only the client-sharded round
(``fed.shard_map_global_round``: one client per chip on a ``("clients",)``
mesh) and what it is compared with, the vmap ``FederatedProgram`` round
on one chip.  It prints where each client's state lives.

Each phase prints its compile and steady seconds.  The last line of
stdout is ``{"ok": true, "device": {...}}``; any failure exits non-zero
before it.  Without a TPU (``jax.devices()[0].platform != "tpu"``) the
script exits non-zero before any work.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ROUNDS, LOCAL_STEPS = 3, 2
SERVE_SIZES = (100, 256, 777)

# Kernel vs jnp ref on the chip: (atol, largest share of elements allowed
# past atol).  Mosaic and XLA lower exp/log/tanh/divide separately, and
# f32 transcendentals agree only to a few ulp (~1e-6 relative).
#   vgm_encode_table: alpha in [-1, 1] holds to 1e-5.  The mode pick is a
#     Gumbel argmax: where two modes' scores tie within that ulp error,
#     either pick is an equally valid sample, and a flipped pick moves
#     alpha and two one-hot lanes, so 1e-4 of elements may differ.
#   segment_activations (soft, tau=0.2): softmax outputs in [0, 1].  The
#     /tau multiplies the Gumbel noise's ulp error by 5 and exp turns it
#     into relative error, so ~1e-5 is the expected ceiling; 1e-4 leaves
#     10x headroom.  -log(-log(u)) is ill-conditioned for u within a few
#     ulp of 1, where one ulp in the inner log moves a draw by O(1):
#     such elements, 1e-3 at most, are equally valid samples.
#   vgm_decode_table: argmax over given betas is exact; the denormalize
#     alpha*4*sd + mu may fuse differently: 1e-5 relative, none past it.
#   weighted_agg: a 5-term weighted sum, summation order may differ by a
#     few ulp of the parameters' scale (~0.1): 1e-6, none past it.
TOLERANCES = {
    "vgm_encode_table": (1e-5, 1e-4),
    "segment_activations": (1e-4, 1e-3),
    "vgm_decode_table": (1e-5, 0.0),
    "weighted_agg": (1e-6, 0.0),
}
STAGES = tuple(TOLERANCES)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(msg):
    print(msg, flush=True)


class CompileClock:
    """XLA backend compile seconds, as JAX reports them, with the time
    each compile ended, so a window's share can be told apart."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.events: list[tuple[float, float]] = []    # (ended at, secs)

        def on_duration(name, secs, **_):
            if name == self.EVENT:
                self.events.append((time.perf_counter(), secs))
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def since(self, t0: float) -> float:
        return sum(s for t, s in self.events if t >= t0)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        out = {}
        yield out
        out["wall_s"] = time.perf_counter() - t0
        out["compile_s"] = self.since(t0)
        say(f"[{name}] " + " ".join(f"{k}={v}" for k, v in out.items()))


def finite_tree(tree) -> bool:
    import jax
    import numpy as np
    return all(bool(np.isfinite(np.asarray(x)).all())
               for x in jax.tree.leaves(tree))


def assert_pallas_route(counts, interpreted, stages, phase):
    """Every listed stage dispatched on the Mosaic route, none on a ref,
    none interpreted."""
    refs = {k: v for k, v in counts.items() if k.endswith("_ref") and v}
    check(not refs, f"{phase}: jnp-ref dispatches on the chip: {refs}")
    check(not interpreted, f"{phase}: interpreted kernels: {dict(interpreted)}")
    for s in stages:
        check(counts.get(s, 0) > 0, f"{phase}: no {s} dispatch "
                                    f"(counts {dict(counts)})")


def compare(name, got, ref):
    import numpy as np
    atol, max_share = TOLERANCES[name]
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite kernel output")
    diff = np.abs(got - ref)
    if name == "vgm_decode_table":          # relative: raw column units
        diff = diff / np.maximum(np.abs(ref), 1.0)
    share = float(np.mean(diff > atol))
    say(f"  {name}: shape={got.shape} max_abs_diff={float(np.max(diff))} "
        f"share_past_{atol}={share} (allowed {max_share})")
    check(share <= max_share, f"{name}: {share} of elements past {atol}")


def train(clock, cfg, ds, parts):
    import numpy as np
    from repro.core.architectures import run_federated
    from repro.kernels import ops
    ops.DISPATCH_COUNTS.clear()
    ops.INTERPRET_COUNTS.clear()
    with clock.phase("train") as t:
        res = run_federated(parts, ds.schema, cfg=cfg, rounds=ROUNDS,
                            local_steps=LOCAL_STEPS, seed=0,
                            weighting="fedtgan", program="fed",
                            eval_real=ds.data, eval_every=ROUNDS)
    # FedRunResult.seconds runs from after setup to the end of the eval
    rounds_start = time.perf_counter() - res.seconds
    rounds_compile = clock.since(rounds_start)
    say(f"[train] setup_s={t['wall_s'] - res.seconds} "
        f"rounds_and_eval_s={res.seconds} "
        f"rounds_and_eval_compile_s={rounds_compile} "
        f"rounds_and_eval_excl_compile_s={res.seconds - rounds_compile} "
        f"({ROUNDS} rounds x {LOCAL_STEPS} local steps, {len(parts)} "
        f"clients, batch {cfg.batch_size}, one eval)")
    check(len(res.history) == 1, f"expected one eval chunk, got "
                                 f"{len(res.history)}")
    h = res.history[-1]
    say(f"  weights={[round(float(w), 4) for w in res.weights]} "
        f"d_loss={h['d_loss']} g_loss={h['g_loss']} "
        f"avg_jsd={h.get('avg_jsd')}")
    check(np.isfinite([h["d_loss"], h["g_loss"]]).all(),
          f"non-finite losses {h}")
    check(finite_tree(res.final_g_params), "merged G non-finite")
    check(finite_tree(res.final_d_params), "merged D non-finite")
    say(f"  dispatches={dict(ops.DISPATCH_COUNTS)}")
    assert_pallas_route(ops.DISPATCH_COUNTS, ops.INTERPRET_COUNTS, STAGES,
                        "train")
    return res


def kernels(clock, cfg, parts, res):
    """Each fused kernel against its jnp ref at the run's shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.fed import flatten_stacked
    from repro.gan.ctgan import generator_forward
    from repro.kernels import ops

    enc = res.encoders
    plan = enc.plan()
    big = np.asarray(parts[-1])                       # the 40,000-row client
    x = jnp.asarray(big[:, list(plan.cont_cols)], jnp.float32)
    key = jax.random.PRNGKey(11)
    gumbel = jax.random.gumbel(key, (x.shape[0], plan.means.size))
    z = jax.random.normal(jax.random.fold_in(key, 1),
                          (cfg.batch_size, cfg.z_dim))
    logits = generator_forward(res.final_g_params, z,
                               jnp.zeros((cfg.batch_size, enc.cond_dim)),
                               len(cfg.gen_hidden))
    spans = tuple(enc.spans())
    flat = flatten_stacked({"g": jax.tree.map(lambda a: a[None],
                                              res.final_g_params),
                            "d": jax.tree.map(lambda a: a[None],
                                              res.final_d_params)})[0]
    n_clients = len(parts)
    stacked = flat[None] + 0.01 * jax.random.normal(
        jax.random.fold_in(key, 2), (n_clients, flat.shape[0]))
    weights = jnp.asarray(res.weights, jnp.float32)

    slots_ref = ops.vgm_encode_table(x, plan.means, plan.stds, plan.logw,
                                     gumbel, use_pallas=False)
    calls = {
        "vgm_encode_table": lambda p: ops.vgm_encode_table(
            x, plan.means, plan.stds, plan.logw, gumbel, use_pallas=p),
        "segment_activations": lambda p: ops.segment_activations(
            logits, spans, jax.random.fold_in(key, 3), cfg.tau, hard=False,
            use_pallas=p),
        "vgm_decode_table": lambda p: ops.vgm_decode_table(
            slots_ref, plan.means, plan.stds, use_pallas=p),
        "weighted_agg": lambda p: ops.weighted_average_flat(
            stacked, weights, use_pallas=p),
    }
    for name, call in calls.items():
        ops.INTERPRET_COUNTS.clear()
        with clock.phase(f"kernel {name}") as t:
            jax.block_until_ready(call(True))          # compile + first run
            t0 = time.perf_counter()
            got = jax.block_until_ready(call(True))
            t["steady_s"] = time.perf_counter() - t0
        check(not ops.INTERPRET_COUNTS, f"{name}: interpreted on the chip")
        compare(name, got, call(False))


def serve(clock, cfg, res):
    import jax
    import numpy as np
    from repro.kernels import ops
    from repro.serve import (StreamingSynthesizer, TableRegistry,
                             ladder_from_sizes)
    from repro.synth import synthesize_table

    registry = TableRegistry()
    registry.register("adult", cfg, res.encoders, res.final_g_params,
                      ladder=ladder_from_sizes(SERVE_SIZES))
    srv = StreamingSynthesizer(registry, scheduler="continuous")
    ops.DISPATCH_COUNTS.clear()
    ops.INTERPRET_COUNTS.clear()
    with clock.phase("serve warmup") as t:
        t["programs_built"] = srv.warmup()
    keys = [jax.random.PRNGKey(100 + i) for i in range(len(SERVE_SIZES))]
    with clock.phase("serve") as t:                # steady: warm programs
        for rows, k in zip(SERVE_SIZES, keys):
            srv.submit("adult", rows, key=k)
        responses = srv.serve()
        t["rows"] = sum(SERVE_SIZES)
    stats = srv.stats()
    say(f"  serving_compiles={stats['serving_compiles']} "
        f"decode_dispatches={stats['decode_dispatches']} "
        f"buckets={registry.get('adult').ladder.buckets}")
    check(stats["serving_compiles"] == 0, f"recompiles while serving: {stats}")
    check(set(stats["decode_dispatches"]) == {1},
          f"decode dispatches per request: {stats['decode_dispatches']}")
    check(len(responses) == len(SERVE_SIZES), "missing responses")
    for r, k in zip(sorted(responses, key=lambda r: r.rid), keys):
        check(np.isfinite(r.data).all(), f"request {r.rid}: non-finite rows")
        oracle = synthesize_table(res.final_g_params, k, cfg, res.encoders,
                                  r.bucket)
        check(np.array_equal(r.data, oracle[:r.rows]),
              f"request {r.rid} ({r.rows} rows, bucket {r.bucket}) differs "
              f"from synthesize_table")
    assert_pallas_route(ops.DISPATCH_COUNTS, ops.INTERPRET_COUNTS,
                        ("segment_activations", "vgm_decode_table"), "serve")


# Merged parameters, shard_map round vs vmap round (np.allclose form).
# With f32 matmuls both renderings compute one round and differ only by
# summation order: the 4-device CPU rehearsal of this phase measured
# 1.19e-7 at most outside the pre-batch-norm biases.  The bound must sit
# well under what a wrong merge moves: the clients start from one model
# and drift apart by ~lr per local step, so a merge weight off by 0.01
# moves a leaf by ~lr * LOCAL_STEPS * 0.01 = 4e-6 (lr 2e-4), past 1e-6.
PARAM_RTOL, PARAM_ATOL = 1e-5, 1e-6
# Networks' outputs on fixed inputs, as a share of the output's scale.
OUTPUT_RTOL = 1e-4


def close_leaves(which, sm, vm, pre_bn_bound):
    """Leafwise agreement of two stacked parameter trees (every client
    slot).  Returns (worst abs diff, worst share of the bound, worst over
    the pre-BN biases, the names of leaves past their bound)."""
    import jax
    import numpy as np
    from repro.gan.ctgan import is_pre_bn_bias
    worst, share, worst_bias, bad = 0.0, 0.0, 0.0, []
    flat_sm = jax.tree_util.tree_flatten_with_path(sm)[0]
    for (path, a), b in zip(flat_sm, jax.tree.leaves(vm)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        d = np.abs(a - b)
        if which == "g_params" and is_pre_bn_bias(path):
            worst_bias = max(worst_bias, float(d.max()))
            ok = d.max() <= pre_bn_bound
        else:
            worst = max(worst, float(d.max()))
            ratio = float(np.max(d / (PARAM_ATOL + PARAM_RTOL * np.abs(b))))
            share = max(share, ratio)
            ok = ratio <= 1.0
        if not ok:
            bad.append(f"{which}{jax.tree_util.keystr(path)}: "
                       f"max_abs_diff={float(d.max())}")
    return worst, share, worst_bias, bad


def four_chips(clock, cfg, ds, devices):
    """One client per chip through ``shard_map_global_round`` against the
    vmap ``FederatedProgram`` round on one chip, both under f32 matmuls
    (``highest``) so the two renderings differ only by summation order.

    Agreement: per-client D/G losses to 1e-4 relative; every merged G and
    D leaf, in every client's slot, to ``PARAM_RTOL``/``PARAM_ATOL``; the
    merged generator's and discriminator's outputs on fixed inputs to
    ``OUTPUT_RTOL`` of their scale.  The generator's pre-batch-norm
    biases (``is_pre_bn_bias``) are held only to what Adam can move them
    in the round (1.6 * lr per step for b1=0.5, b2=0.9, from either
    side): their exact gradient is zero, so Adam turns summation-order
    noise into steps of up to lr (pinned on the CPU in
    tests/test_fed_engine.py); the batch norm cancels them in the
    generator's output, which the output check holds tight."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.fed import (FederatedProgram, setup_federation,
                           shard_map_global_round)
    from repro.gan.ctgan import discriminator_forward, generator_forward
    from repro.tabular.datasets import partition_quantity_skew

    n = len(devices)
    parts = partition_quantity_skew(ds, n, seed=0)
    with clock.phase("setup") as t:
        fe = setup_federation(parts, ds.schema, cfg, seed=0,
                              weighting="fedtgan")
        t["clients"] = n
    mesh = Mesh(np.asarray(devices), ("clients",))
    on_clients = NamedSharding(mesh, PartitionSpec("clients"))
    states, tables = jax.device_put((fe.states, fe.tables), on_clients)
    key = jax.random.PRNGKey(5)

    def placement(tree):
        leaf = jax.tree.leaves(tree.g_params)[0]
        return {int(sh.index[0].start or 0): str(sh.device)
                for sh in leaf.addressable_shards}

    with jax.default_matmul_precision("highest"):
        prog = jax.jit(shard_map_global_round(
            mesh, cfg, fe.spans, fe.cond_spans, batch=cfg.batch_size,
            local_steps=LOCAL_STEPS, weighting="fedtgan",
            client_axes=("clients",)))
        vmap_prog = FederatedProgram(cfg, fe.spans, fe.cond_spans,
                                     batch=cfg.batch_size,
                                     local_steps=LOCAL_STEPS,
                                     weighting="fedtgan")
        one_chip = jax.device_put((fe.states, fe.tables, fe.S, fe.n_rows),
                                  devices[0])
        runs = {}
        for name, run in (
                ("shard_map round, one client per chip",
                 lambda: prog(states, tables, fe.S, fe.n_rows, key)),
                ("vmap round, one chip",
                 lambda: vmap_prog.round(*one_chip, key))):
            with mesh, clock.phase(name) as t:
                jax.block_until_ready(run())               # compile + run
                t0 = time.perf_counter()
                runs[name] = jax.block_until_ready(run())
                t["steady_s"] = time.perf_counter() - t0
        (st_sm, m_sm), (st_vm, m_vm) = runs.values()
        say(f"  client -> device, input states: {placement(states)}")
        say(f"  client -> device, shard_map output: {placement(st_sm)}")
        say(f"  client -> device, vmap output: {placement(st_vm)}")
        check(len(set(placement(st_sm).values())) == n,
              "shard_map did not spread the clients over the chips")

        for k in ("d_loss", "g_loss"):
            a, b = np.asarray(m_sm[k]), np.asarray(m_vm[k])
            say(f"  {k}: shard_map={a.ravel().tolist()} "
                f"vmap={b.ravel().tolist()}")
            check(np.allclose(a, b, rtol=1e-4, atol=1e-6),
                  f"{k} differs between renderings")

        adam_bound = 2 * 1.6 * cfg.lr * LOCAL_STEPS
        bad = []
        for which in ("g_params", "d_params"):
            worst, share, worst_bias, past = close_leaves(
                which, getattr(st_sm, which), getattr(st_vm, which),
                adam_bound)
            bad += past
            say(f"  merged {which} max_abs_diff={worst} worst_share_of_"
                f"bound={share} (bound {PARAM_ATOL} + {PARAM_RTOL} * |p|)"
                + (f" pre_bn_bias_max_abs_diff={worst_bias} "
                   f"(bound {adam_bound})" if which == "g_params" else ""))
        check(not bad, "merged models differ: " + "; ".join(bad))

        first = lambda tree: jax.tree.map(lambda x: np.asarray(x[0]), tree)
        k_in = jax.random.PRNGKey(9)
        z = jax.random.normal(k_in, (cfg.batch_size, cfg.z_dim))
        cond = jnp.zeros((cfg.batch_size, fe.enc.cond_dim))
        d_in = jax.random.normal(
            jax.random.fold_in(k_in, 1),
            (cfg.batch_size, fe.enc.encoded_dim + fe.enc.cond_dim))
        nets = {
            "generator": lambda st: generator_forward(
                first(st.g_params), z, cond, len(cfg.gen_hidden)),
            "discriminator": lambda st: discriminator_forward(
                first(st.d_params), d_in, k_in, cfg, train=False),
        }
        for name, net in nets.items():
            got, ref = (np.asarray(net(st)) for st in (st_sm, st_vm))
            bound = OUTPUT_RTOL * max(1.0, float(np.max(np.abs(ref))))
            d_out = float(np.max(np.abs(got - ref)))
            say(f"  {name} output max_abs_diff={d_out} (bound {bound})")
            check(d_out <= bound, f"merged {name}s disagree")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-sharded round and its "
                         "one-chip comparison")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {SRC}")
    sys.path.insert(0, str(SRC))

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform}")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX sees "
                 f"{len(devices)} device(s)")
    say(f"compile cache: {cache}")

    from repro.configs.ctgan_paper import CONFIG, N_CLIENTS
    from repro.tabular.datasets import make_dataset, partition_quantity_skew

    clock = CompileClock()
    ds = make_dataset("adult", seed=0)
    if args.chips == 4:
        four_chips(clock, CONFIG, ds, devices[:4])
    else:
        parts = partition_quantity_skew(ds, N_CLIENTS, seed=0)
        res = train(clock, CONFIG, ds, parts)
        kernels(clock, CONFIG, parts, res)
        serve(clock, CONFIG, res)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
