"""Federated CTGAN training: what the window drives in a ``train`` cell.

Set-up builds one federation and one compiled ``FederatedProgram.run``
(``rounds_per_dispatch`` global rounds of ``local_steps`` local steps per
dispatch), installs generator and critic weights made from the seed,
drives that program through its first ``check_dispatches`` dispatches
(which compiles it) and hands the same program and state to the window.
The window keeps ``ahead_dispatches`` dispatches queued ahead of the
one the host waits for (closed loop), so a host stall shorter than that
queue leaves the chip fed; when ``--seconds`` is up it sends nothing
more, waits for all it sent, and only then reads the clock.  The rate
counts every row of every client's batches in every round completed,
over all of that time.

The check runs after the window.  ``run`` scans its rounds inside one
dispatch, so the host sees only a dispatch's start and end state and
every round's losses; each later round of a dispatch starts from state
the host never sees, and a GAN amplifies rounding from round to round by an
amount that depends on the seed.  So each checked dispatch is compared
where no earlier round's rounding enters: its first round, which the
plain reference (``bench/reference``) takes from the program's full
state at the dispatch's start, with the program's own parameters after
that round from its traced path (``run_traced``, the same scanned round
returning each round's client parameters before the merge), replayed
after the window; the replay's last merge against its own inputs; and
every client's slot where ``run`` ended (``bench/compare.py``).
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, table
from bench.system import annotation, model_config, schema, seed_key
from bench.reference import ctgan as ref

from repro.fed import FederatedProgram, setup_federation
from repro.fed.merge import unflatten_merged
from repro.gan.trainer import GANState
from repro.optim.optimizers import AdamState


def harness_state(key, m, cond_dim, data_dim, n_clients):
    """Initial weights and per-client streams made by the benchmark from
    the seed: one jitted call on the device, float32."""
    def make(key):
        g, d = ref.init_params(key, m, cond_dim, data_dim)
        rngs = jax.vmap(lambda i: jax.random.fold_in(
            jax.random.fold_in(key, 1), i))(jnp.arange(n_clients))
        return g, d, rngs
    return jax.jit(make)(key)


def to_program(g, d, rngs, n_clients: int) -> GANState:
    stack = lambda t: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n_clients,) + x.shape), t)
    zeros = lambda t: jax.tree.map(jnp.zeros_like, stack(t))
    count = jnp.zeros((n_clients,), jnp.int32)
    return GANState(stack(g), stack(d), AdamState(zeros(g), zeros(g), count),
                    AdamState(zeros(d), zeros(d), count), count, rngs)


def per_client(st: GANState) -> list[dict]:
    """The program's stacked state (on the host) as one plain dict per
    client."""
    pick = lambda t, i: jax.tree.map(lambda x: x[i], t)
    return [{"g": pick(st.g_params, i), "d": pick(st.d_params, i),
             "g_mu": pick(st.g_opt.mu, i), "g_nu": pick(st.g_opt.nu, i),
             "d_mu": pick(st.d_opt.mu, i), "d_nu": pick(st.d_opt.nu, i),
             "g_count": st.g_opt.count[i], "d_count": st.d_opt.count[i],
             "rng": st.rng[i]} for i in range(st.step.shape[0])]


class Cell:
    def __init__(self, spec: dict, seed: int):
        self.spec = spec
        self.seed = int(seed)
        self.m = spec["model"]
        self.t = spec["traffic_mix"]
        self.limits = spec["limits"]
        self.cfg = model_config(self.m)
        self._sound = {}

    # -- set-up ------------------------------------------------------------

    def setup(self):
        m, t = self.m, self.t
        key = seed_key(self.seed)
        data, self.kinds = table.make(m, self.seed)
        self.parts = table.partition_quantity_skew(
            data, t["clients"], t["small_rows"], self.seed)
        t0 = time.perf_counter()
        fe = setup_federation(self.parts, schema(m, self.kinds), self.cfg,
                              seed=self.seed & 0x7FFFFFFF,
                              weighting=t["weighting"])
        jax.block_until_ready((fe.tables, fe.S))
        self.federation_setup_s = time.perf_counter() - t0
        self.fe = fe
        P = len(self.parts)
        g, d, rngs = harness_state(jax.random.fold_in(key, 1), m,
                                   fe.enc.cond_dim, fe.enc.encoded_dim, P)
        self.states = to_program(g, d, rngs, P)
        self.prog = FederatedProgram(self.cfg, fe.spans, fe.cond_spans,
                                     batch=m["batch_size"],
                                     local_steps=t["local_steps"],
                                     weighting=t["weighting"])
        R = t["rounds_per_dispatch"]
        self.rows_per_dispatch = R * P * t["local_steps"] * m["batch_size"]
        self.key_base = jax.random.fold_in(key, 2)
        self.next_dispatch = 0
        # the checked dispatches: the window's own call on its own keys
        self.snapshots = [jax.device_get(self.states)]
        self.prog_losses = []
        for _ in range(t["check_dispatches"]):
            self.states, met = self._dispatch(self.states)
            self.prog_losses.append(np.stack(
                [np.asarray(met["d_loss"]), np.asarray(met["g_loss"])], -1))
            self.snapshots.append(jax.device_get(self.states))
        self.encoded = [np.asarray(fe.tables.encoded[i, :n])
                        for i, n in enumerate(np.asarray(fe.n_rows, int))]
        # shapes the per-layer readers count work from
        self.n_clients = P
        self.cond_dim, self.data_dim = fe.enc.cond_dim, fe.enc.encoded_dim
        self.span_widths = [s.width for s in fe.spans]
        self.span_kinds = [s.activation for s in fe.spans]
        self.n_params = sum(x.size for x in jax.tree.leaves((g, d)))
        n = t["max_dispatches"]
        self.window_keys = self.round_keys(
            self.next_dispatch, n).reshape(n, R, 2)
        jax.block_until_ready(self.states)

    def round_keys(self, i: int, n: int = 1) -> np.ndarray:
        """Round keys of dispatches i .. i+n-1, ``(n * R, 2)``."""
        R = self.t["rounds_per_dispatch"]
        return np.asarray(jax.vmap(lambda r: jax.random.fold_in(
            self.key_base, r))(jnp.arange(i * R, (i + n) * R)))

    def _dispatch(self, states, keys=None):
        keys = self.round_keys(self.next_dispatch) if keys is None else keys
        self.next_dispatch += 1
        fe = self.fe
        return self.prog.run(states, fe.tables, fe.S, fe.n_rows, keys)

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float, annotate: bool) -> dict:
        """Closed loop for ``seconds``: the host waits for dispatch i only
        once dispatch i + ``ahead_dispatches`` is queued, and after the
        deadline drains the queue before the clock is read.  The keys were
        made in set-up, so the loop does nothing but dispatch and wait."""
        keys = self.window_keys
        n_keys = len(keys)
        ahead = self.t["ahead_dispatches"]
        ann = annotation(annotate)
        states = self.states
        inflight = collections.deque()
        done = failed = 0

        def wait_oldest():
            nonlocal done, failed
            with ann("device_get"):
                loss = np.asarray(inflight.popleft())
            done += 1
            failed += int(not np.isfinite(loss).all())

        t0 = time.perf_counter()
        deadline = t0 + seconds
        i = 0
        while i < n_keys and time.perf_counter() < deadline:
            with ann("dispatch"):
                states, met = self._dispatch(states, keys[i])
            inflight.append(met["d_loss"])
            i += 1
            if len(inflight) > ahead:
                wait_oldest()
        while inflight:
            wait_oldest()
        jax.block_until_ready(states)
        elapsed = time.perf_counter() - t0
        self.states = states
        rows = done * self.rows_per_dispatch
        return {"attempted": done, "failed": failed, "seconds": elapsed,
                "rows": rows,
                "metrics": {"train_rows_per_s": rows / elapsed}}

    def free(self):
        """Frees the window's state, replays each checked dispatch through
        the program's traced path, and frees the program."""
        del self.states
        fe, R = self.fe, self.t["rounds_per_dispatch"]
        self.traced = []
        for k in range(self.t["check_dispatches"]):
            end, _, art = self.prog.run_traced(
                jax.device_put(self.snapshots[k]), fe.tables, fe.S,
                fe.n_rows, self.round_keys(k))
            flats = art["updates"]
            self.traced.append({
                "first": np.asarray(flats[0]), "last": np.asarray(flats[-1]),
                "before_last": np.asarray(flats[-2]) if R > 1 else None,
                "weights": np.asarray(art["weights"], np.float64),
                "end": jax.device_get(end)})
        del self.prog, self.fe

    # -- the check -----------------------------------------------------------

    def check(self) -> dict:
        """Numbers compared with their limits: ``{name: (value, limit)}``."""
        return compare.limited(self.readings(), self.limits)

    def readings(self, subject: str = "program",
                 precision: str = "highest") -> dict:
        """The compared numbers of this run, worst over the checked
        dispatches.  ``subject`` is the program or, for the readings that
        limits are set from, the reference itself at ``precision`` or with
        one of ``compare.REFERENCE_FAULTS`` planted, in the program's
        place."""
        spans = ref.spans_for(self.kinds, ref.column_widths(
            self.parts, self.kinds, self.m["max_modes"]))
        n_pad = max(e.shape[0] for e in self.encoded)
        tables = ref.stack([ref.sampler_tables(e, spans, n_pad)
                            for e in self.encoded])
        local = lambda p, half=False: ref.make_local(
            self.m, spans, self.t["local_steps"], p, half)
        if "fn" not in self._sound:
            self._sound["fn"] = local("highest")
        other = (local(precision, subject == "half_batch")
                 if subject != "program" else None)
        out = []
        for k in range(self.t["check_dispatches"]):
            if k not in self._sound:
                self._sound[k] = self.reference_round(self._sound["fn"], k,
                                                      tables)
            theirs = self._sound[k]
            ours = (self.program_dispatch(k) if other is None else
                    self.reference_round(other, k, tables, fault=subject))
            out.append(compare.train_numbers(ours, theirs,
                                             self.traced[k]["weights"]))
        return compare.worst(out)

    def program_dispatch(self, k: int) -> dict:
        """Dispatch k as the program took it, read where no rounding of an
        earlier round enters: the first round's losses (``run``) and
        parameters after its local steps (the traced replay), both from
        the dispatch's start state; the traced replay's last merge against
        its own inputs; and every client's parameters where ``run``
        ended."""
        tr = self.traced[k]
        tmpl = {"g": self.snapshots[k].g_params,
                "d": self.snapshots[k].d_params}
        params = lambda flats: [unflatten_merged(f, tmpl) for f in flats]
        start = [{"g": c["g"], "d": c["d"]}
                 for c in per_client(self.snapshots[k])]
        merge_from = (start[0] if tr["before_last"] is None
                      else ref.merge(params(tr["before_last"]),
                                     tr["weights"]))
        end = tr["end"]
        return {"start": start, "post": params(tr["first"]),
                "losses": self.prog_losses[k][0],
                "merge_in": params(tr["last"]), "merge_from": merge_from,
                "merged": [{"g": c["g"], "d": c["d"]}
                           for c in per_client(end)],
                "slots": [{"g": c["g"], "d": c["d"]}
                          for c in per_client(self.snapshots[k + 1])]}

    def reference_round(self, local_fn, k: int, tables,
                        fault: str = "") -> dict:
        """The first round of dispatch k taken by the reference from the
        program's state at the dispatch's start (parameters, Adam moments,
        counts, streams), then merged with the program's §4.2 weights."""
        first = per_client(self.snapshots[k])
        st, loss = local_fn(ref.stack(first), tables,
                            jnp.asarray(self.round_keys(k)[0], jnp.uint32))
        host = ref.unstack(jax.device_get(st), len(first))
        post = [{"g": c["g"], "d": c["d"]} for c in host]
        w = self.traced[k]["weights"]
        if fault == "no_merge":
            merged = post
        else:
            wm = (0.99 * w + 0.01 / len(w) if fault == "weights_toward_uniform"
                  else w)
            merged = [ref.merge(post, wm)] * len(post)
        start = [{"g": c["g"], "d": c["d"]} for c in first]
        return {"start": start, "post": post, "losses": np.asarray(loss),
                "mu_norms": [{net: compare.leaf_norms(c[f"{net}_mu"])
                              for net in ("g", "d")} for c in host],
                "merge_in": post, "merge_from": start[0], "merged": merged,
                "slots": merged}
