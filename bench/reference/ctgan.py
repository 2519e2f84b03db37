"""Plain reference of federated CTGAN (Xu et al. 2019; Fed-TGAN, Zhao et al. 2021).

Straight ``jax.numpy`` in float32, written from the papers' equations: a
residual generator with batch norm, per-span tanh / Gumbel-softmax heads,
a PacGAN critic with dropout, WGAN-GP, the conditional cross-entropy,
CTGAN's training-by-sampling draw, Adam(2e-4, 0.5, 0.9) and the
weighted federated average.  It imports nothing of the system under test.

Matrix products take an explicit ``precision``: ``"highest"`` is the
float32 the configuration states; ``"bf16_3x"`` is the control that has
to fail the comparison, three bfloat16 passes: the TPU's own ``high``
precision, written out (high x high, high x low, low x high halves of
each operand) on a backend that computes every float32 product in full,
as the CPU does.

State is a plain dict per client::

    {"g": params, "d": params, "g_mu", "g_nu", "d_mu", "d_nu": like params,
     "g_count", "d_count": int, "rng": uint32[2]}

Random streams follow the published procedure step by step; the harness
hands the reference the same seeds it hands the system, so both draw the
same noise.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

GUMBEL_EPS = 1e-20


class Span(NamedTuple):
    start: int
    width: int
    kind: str            # "tanh" | "softmax"


def spans_for(schema: Sequence[str], widths: Sequence[int]) -> list[Span]:
    """Encoded row layout: a continuous column is [alpha (tanh), mode
    one-hot (softmax, ``widths[j]`` modes)], a categorical column one
    one-hot of ``widths[j]`` categories, in schema order."""
    out, pos = [], 0
    for kind, w in zip(schema, widths):
        if kind == "continuous":
            out.append(Span(pos, 1, "tanh"))
            pos += 1
        out.append(Span(pos, int(w), "softmax"))
        pos += int(w)
    return out


def column_widths(parts: Sequence[np.ndarray], schema: Sequence[str],
                  max_modes: int) -> list[int]:
    """One-hot width of each column: the categories the clients hold
    together, or ``max_modes`` mixture modes."""
    return [len(np.unique(np.concatenate([p[:, j] for p in parts])))
            if kind == "categorical" else max_modes
            for j, kind in enumerate(schema)]


def cond_spans(spans: Sequence[Span]) -> list[Span]:
    return [s for s in spans if s.kind == "softmax"]


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _linear(key, fan_in, fan_out):
    kw, kb = jax.random.split(key)
    lim = 1.0 / math.sqrt(fan_in)
    return {"w": jax.random.uniform(kw, (fan_in, fan_out), jnp.float32,
                                    -lim, lim),
            "b": jax.random.uniform(kb, (fan_out,), jnp.float32, -lim, lim)}


def init_params(key, m: dict, cond_dim: int, data_dim: int):
    """Generator and critic parameters (PyTorch's default linear init)."""
    kg, kd = jax.random.split(key)
    gk = jax.random.split(kg, len(m["gen_hidden"]) + 1)
    g, dim = {}, m["z_dim"] + cond_dim
    for i, h in enumerate(m["gen_hidden"]):
        g[f"res{i}"] = {"fc": _linear(gk[i], dim, h),
                        "bn_scale": jnp.ones((h,), jnp.float32),
                        "bn_bias": jnp.zeros((h,), jnp.float32)}
        dim += h
    g["out"] = _linear(gk[-1], dim, data_dim)
    dk = jax.random.split(kd, len(m["disc_hidden"]) + 1)
    d, dim = {}, (data_dim + cond_dim) * m["pac"]
    for i, h in enumerate(m["disc_hidden"]):
        d[f"fc{i}"] = _linear(dk[i], dim, h)
        dim = h
    d["out"] = _linear(dk[-1], dim, 1)
    return g, d


# ---------------------------------------------------------------------------
# networks
# ---------------------------------------------------------------------------

def dot(a, b, precision):
    if precision == "highest":
        return jnp.dot(a, b, precision="highest")
    if precision != "bf16_3x":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.dot(a, b, precision="high")
    hi = lambda x: x.astype(jnp.bfloat16)
    a_hi, b_hi = hi(a), hi(b)
    a_lo, b_lo = hi(a - a_hi.astype(a.dtype)), hi(b - b_hi.astype(b.dtype))
    mm = lambda x, y: jnp.dot(x, y, preferred_element_type=jnp.float32)
    return mm(a_hi, b_hi) + (mm(a_hi, b_lo) + mm(a_lo, b_hi))


def generator(g, z, cond, n_hidden, precision):
    h = jnp.concatenate([z, cond], axis=1)
    for i in range(n_hidden):
        p = g[f"res{i}"]
        y = dot(h, p["fc"]["w"], precision) + p["fc"]["b"]
        mu = jnp.mean(y, axis=0, keepdims=True)
        var = jnp.mean((y - mu) ** 2, axis=0, keepdims=True)
        y = (y - mu) / jnp.sqrt(var + 1e-5) * p["bn_scale"] + p["bn_bias"]
        h = jnp.concatenate([h, jnp.maximum(y, 0.0)], axis=1)
    return dot(h, g["out"]["w"], precision) + g["out"]["b"]


def activations(logits, spans, key, tau, hard):
    """tanh on alphas, Gumbel-softmax (tau) on one-hots; one key per span
    in layout order; ``hard`` is the straight-through one-hot."""
    parts = []
    keys = jax.random.split(key, len(spans))
    for s, k in zip(spans, keys):
        x = logits[:, s.start:s.start + s.width]
        if s.kind == "tanh":
            parts.append(jnp.tanh(x))
            continue
        u = jax.random.uniform(k, x.shape, jnp.float32)
        gumbel = -jnp.log(-jnp.log(u + GUMBEL_EPS) + GUMBEL_EPS)
        y = jax.nn.softmax((x + gumbel) / tau, axis=1)
        if hard:
            one = jax.nn.one_hot(jnp.argmax(y, axis=1), s.width)
            y = one - jax.lax.stop_gradient(y) + y
        parts.append(y)
    return jnp.concatenate(parts, axis=1)


def gumbel_scores(logits, spans, key):
    """Per softmax span, the score whose argmax the hard head takes:
    ``logit + Gumbel`` (dividing by tau does not move the argmax)."""
    keys = jax.random.split(key, len(spans))
    out = []
    for s, k in zip(spans, keys):
        if s.kind == "tanh":
            continue
        x = logits[:, s.start:s.start + s.width]
        u = jax.random.uniform(k, x.shape, jnp.float32)
        out.append(x - jnp.log(-jnp.log(u + GUMBEL_EPS) + GUMBEL_EPS))
    return out


def critic(d, x, key, m, precision, train=True):
    """PacGAN: ``pac`` rows per critic input; LeakyReLU(0.2), dropout."""
    h = x.reshape(x.shape[0] // m["pac"], -1)
    keys = jax.random.split(key, len(m["disc_hidden"]))
    for i in range(len(m["disc_hidden"])):
        p = d[f"fc{i}"]
        h = dot(h, p["w"], precision) + p["b"]
        h = jnp.where(h >= 0, h, 0.2 * h)
        if train and m["dropout"] > 0:
            keep = jax.random.bernoulli(keys[i], 1 - m["dropout"], h.shape)
            h = jnp.where(keep, h / (1 - m["dropout"]), 0.0)
    return (dot(h, d["out"]["w"], precision)
            + d["out"]["b"])[:, 0]


def gradient_penalty(d, real, fake, key, m, precision):
    """WGAN-GP with one interpolation weight per pack."""
    ke, kd = jax.random.split(key)
    b = real.shape[0] // m["pac"]
    eps = jax.random.uniform(ke, (b, 1, 1))
    inter = (eps * real.reshape(b, m["pac"], -1)
             + (1 - eps) * fake.reshape(b, m["pac"], -1)).reshape(real.shape)
    grad = jax.grad(lambda x: jnp.sum(
        critic(d, x, kd, m, precision, train=False)))(inter).reshape(b, -1)
    norm = jnp.sqrt(jnp.sum(grad * grad, axis=1) + 1e-12)
    return jnp.mean((norm - 1.0) ** 2)


def conditional_loss(logits, cond, mask, cspans):
    total = jnp.zeros(logits.shape[0])
    pos = 0
    for i, s in enumerate(cspans):
        logp = jax.nn.log_softmax(logits[:, s.start:s.start + s.width], axis=1)
        total = total - jnp.sum(cond[:, pos:pos + s.width] * logp,
                                axis=1) * mask[:, i]
        pos += s.width
    return jnp.mean(total)


# ---------------------------------------------------------------------------
# one local step: 1 critic update + 1 generator update (n_critic = 1)
# ---------------------------------------------------------------------------

def adam(grads, mu, nu, count, params, m):
    count = count + 1
    c = count.astype(jnp.float32)
    b1, b2 = m["b1"], m["b2"]

    def one(g, mo, v, p):
        mo = mo * b1 + g * (1 - b1)
        v = v * b2 + g * g * (1 - b2)
        step = (mo / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + 1e-8)
        return p - m["lr"] * step, mo, v

    out = jax.tree.map(one, grads, mu, nu, params)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2), count


def local_step(st, batch, m, spans, precision):
    cond, mask, real = batch
    cs = cond_spans(spans)
    nh = len(m["gen_hidden"])
    key, kd, kg = jax.random.split(st["rng"], 3)

    def d_loss(d):
        kz, ka, k1, k2, kgp = jax.random.split(kd, 5)
        z = jax.random.normal(kz, (real.shape[0], m["z_dim"]))
        fake = activations(generator(st["g"], z, cond, nh, precision),
                           spans, ka, m["tau"], False)
        fake_in = jnp.concatenate([fake, cond], axis=1)
        real_in = jnp.concatenate([real, cond], axis=1)
        wgan = (jnp.mean(critic(d, fake_in, k1, m, precision))
                - jnp.mean(critic(d, real_in, k2, m, precision)))
        return wgan + m["gp_lambda"] * gradient_penalty(
            d, real_in, fake_in, kgp, m, precision)

    dl, dg = jax.value_and_grad(d_loss)(st["d"])
    d, d_mu, d_nu, d_count = adam(dg, st["d_mu"], st["d_nu"],
                                  st["d_count"], st["d"], m)

    def g_loss(g):
        kz, ka, k1 = jax.random.split(kg, 3)
        z = jax.random.normal(kz, (cond.shape[0], m["z_dim"]))
        logits = generator(g, z, cond, nh, precision)
        fake = activations(logits, spans, ka, m["tau"], False)
        y = critic(d, jnp.concatenate([fake, cond], axis=1), k1, m,
                   precision)
        return -jnp.mean(y) + conditional_loss(logits, cond, mask, cs)

    gl, gg = jax.value_and_grad(g_loss)(st["g"])
    g, g_mu, g_nu, g_count = adam(gg, st["g_mu"], st["g_nu"],
                                  st["g_count"], st["g"], m)
    new = {"g": g, "d": d, "g_mu": g_mu, "g_nu": g_nu, "d_mu": d_mu,
           "d_nu": d_nu, "g_count": g_count, "d_count": d_count, "rng": key}
    return new, (dl, gl)


# ---------------------------------------------------------------------------
# training-by-sampling
# ---------------------------------------------------------------------------

def sampler_tables(encoded: np.ndarray, spans: Sequence[Span], n_pad: int):
    """CTGAN's sampler index for one client's encoded rows: per condition
    span, rows grouped by category (stable), category counts, and the
    cumulative log-frequency distribution categories are drawn from.
    Rows are padded to ``n_pad`` so every client shares one shape."""
    cs = cond_spans(spans)
    n = encoded.shape[0]
    cmax = max(s.width for s in cs)
    counts = np.zeros((len(cs), cmax), np.int64)
    starts = np.zeros((len(cs), cmax + 1), np.int64)
    order = np.zeros((len(cs), n_pad), np.int64)
    probs = np.zeros((len(cs), cmax), np.float64)
    for i, s in enumerate(cs):
        cat = encoded[:, s.start:s.start + s.width].argmax(axis=1)
        counts[i, :s.width] = np.bincount(cat, minlength=s.width)
        starts[i, 1:] = np.cumsum(counts[i])
        order[i, :n] = np.argsort(cat, kind="stable")
        logf = np.log(counts[i, :s.width] + 1.0)
        probs[i, :s.width] = logf / max(logf.sum(), 1e-12)
    cum = np.cumsum(probs, axis=1)
    cum[:, -1] = 1.0
    rows = np.zeros((n_pad, encoded.shape[1]), np.float32)
    rows[:n] = encoded
    return {"rows": jnp.asarray(rows), "cum": jnp.asarray(cum, jnp.float32),
            "counts": jnp.asarray(counts, jnp.int32),
            "starts": jnp.asarray(starts, jnp.int32),
            "order": jnp.asarray(order, jnp.int32),
            "widths": jnp.asarray([s.width for s in cs], jnp.int32),
            "fallback": jnp.asarray(counts.argmax(axis=1), jnp.int32),
            "offsets": jnp.asarray(np.cumsum([0] + [s.width for s in cs])[:-1],
                                   jnp.int32)}


def draw(t: dict, key, batch: int, cond_dim: int):
    """One conditional batch: a condition span uniformly, a category by
    log-frequency, a real row of that category uniformly."""
    n_spans = t["cum"].shape[0]
    k_span, k_cat, k_row = jax.random.split(key, 3)
    span = jax.random.randint(k_span, (batch,), 0, n_spans)
    u = jax.random.uniform(k_cat, (batch,))
    c = jnp.sum(t["cum"][span] < u[:, None], axis=1).astype(jnp.int32)
    c = jnp.minimum(c, t["widths"][span] - 1)
    c = jnp.where(t["counts"][span, c] == 0, t["fallback"][span], c)
    cnt = t["counts"][span, c]
    pos = (jax.random.uniform(k_row, (batch,)) * cnt).astype(jnp.int32)
    pos = jnp.minimum(pos, jnp.maximum(cnt - 1, 0))
    rows = t["order"][span, t["starts"][span, c] + pos]
    cond = jax.nn.one_hot(t["offsets"][span] + c, cond_dim, dtype=jnp.float32)
    mask = jax.nn.one_hot(span, n_spans, dtype=jnp.float32)
    return cond, mask, t["rows"][rows]


# ---------------------------------------------------------------------------
# one global round: every client's local steps, then the weighted average
# ---------------------------------------------------------------------------

def make_local(m: dict, spans, local_steps: int, precision: str,
               half: bool = False):
    """jit(states, tables, key) -> (states, losses): every client's local
    steps of one global round, before the merge.  ``states`` and
    ``tables`` carry a leading client axis; each client's batches are
    drawn up front from its share of the round key.  Losses:
    ``(clients, local_steps, 2)``.  ``half`` takes each step on the
    first half of its batch (a planted fault)."""
    cdim = sum(s.width for s in cond_spans(spans))
    batch = m["batch_size"]

    def client(st, tables, key):
        cond, mask, real = draw(tables, key, local_steps * batch, cdim)
        losses = []
        for e in range(local_steps):
            sl = slice(e * batch, e * batch + (batch // 2 if half else batch))
            st, loss = local_step(st, (cond[sl], mask[sl], real[sl]), m,
                                  spans, precision)
            losses.append(jnp.stack(loss))
        return st, jnp.stack(losses)

    def local(states, tables, key):
        P = jax.tree.leaves(states["g"])[0].shape[0]
        return jax.vmap(client)(states, tables, jax.random.split(key, P))

    return jax.jit(local)


def merge(params: list, weights) -> dict:
    """The weighted federated average of the clients' parameter trees, in
    float64 on the host."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    return jax.tree.map(lambda *xs: np.tensordot(
        w, np.stack([np.asarray(x, np.float64) for x in xs]), axes=1),
        *params)


def stack(trees: list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def unstack(tree, n: int) -> list:
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]
