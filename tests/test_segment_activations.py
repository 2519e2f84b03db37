"""Fused segment-activation kernel: value/gradient parity with the
per-span ``apply_activations`` loop, straight-through hard-mode validity,
Gumbel hard-draw distribution, and the end-to-end one-dispatch-per-stage
regression for the device synthesis pipeline.

Values are asserted BIT-exact (the fused path replays the loop's exact
per-span key streams and op chain); gradients are asserted to a few-ulp
tolerance (XLA fuses the softmax VJP differently for narrow span widths,
~1e-8 absolute — see the custom VJP in kernels.segment_activations).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.gan.ctgan import (CTGANConfig, apply_activations,
                             apply_activations_fused)
from repro.gan.trainer import init_gan_state
from repro.kernels import ops, ref
from repro.kernels.segment_activations import (build_span_layout,
                                               segment_activations)
from repro.synth import DeviceSampler, RoundEngine, synthesize_table
from repro.tabular import fit_centralized_encoders, make_dataset
from repro.tabular.encoders import SpanInfo

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hst
    HAS_HYPOTHESIS = True
except ImportError:                      # optional dev dep (requirements-dev)
    HAS_HYPOTHESIS = False

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")

GRAD_TOL = dict(rtol=1e-5, atol=1e-6)


def _random_layout(rng, wmax_cap=10, max_spans=6):
    """Random contiguous span layout: mixed tanh/softmax, widths 1..Wmax."""
    S = int(rng.integers(1, max_spans + 1))
    spans, pos = [], 0
    for i in range(S):
        w = int(rng.integers(1, wmax_cap + 1))
        act = "tanh" if rng.random() < 0.4 else "softmax"
        spans.append(SpanInfo(pos, w, act, i, act == "softmax"))
        pos += w
    return tuple(spans), pos


def _paths(spans, logits, akey, tau, hard):
    """(loop, fused-ref-route, fused-kernel-route) outputs."""
    loop = jax.jit(lambda l: apply_activations(l, spans, akey, tau,
                                               hard=hard))(logits)
    fused_ref = jax.jit(lambda l: apply_activations_fused(
        l, spans, akey, tau, hard=hard, use_pallas=False))(logits)
    fused_kernel = apply_activations_fused(logits, spans, akey, tau,
                                           hard=hard, interpret=True)
    return loop, fused_ref, fused_kernel


def _check_value_parity(spans, dim, batch, seed, tau=0.2, hard=False,
                        kernel_rtol=None):
    """Loop == fused ref bit for bit; the interpret-mode kernel too, or
    within ``kernel_rtol`` where a caller states why."""
    key = jax.random.PRNGKey(seed)
    kl, ka = jax.random.split(key)
    logits = jax.random.normal(kl, (batch, dim), jnp.float32) * 3.0
    loop, fused_ref, fused_kernel = _paths(spans, logits, ka, tau, hard)
    np.testing.assert_array_equal(np.asarray(loop), np.asarray(fused_ref))
    if kernel_rtol is None:
        np.testing.assert_array_equal(np.asarray(loop),
                                      np.asarray(fused_kernel))
    else:
        np.testing.assert_allclose(np.asarray(fused_kernel),
                                   np.asarray(loop), rtol=kernel_rtol,
                                   atol=0)
    return logits, ka, np.asarray(loop)


def _check_grad_parity(spans, dim, batch, seed, tau=0.2, hard=False):
    key = jax.random.PRNGKey(seed)
    kl, ka, kc = jax.random.split(key, 3)
    logits = jax.random.normal(kl, (batch, dim), jnp.float32) * 3.0
    ct = jax.random.normal(kc, (batch, dim), jnp.float32)
    g_loop = jax.grad(lambda l: jnp.sum(
        apply_activations(l, spans, ka, tau, hard=hard) * ct))(logits)
    g_ref = jax.grad(lambda l: jnp.sum(apply_activations_fused(
        l, spans, ka, tau, hard=hard, use_pallas=False) * ct))(logits)
    g_kernel = jax.grad(lambda l: jnp.sum(apply_activations_fused(
        l, spans, ka, tau, hard=hard, interpret=True) * ct))(logits)
    np.testing.assert_allclose(np.asarray(g_loop), np.asarray(g_ref),
                               **GRAD_TOL)
    np.testing.assert_allclose(np.asarray(g_loop), np.asarray(g_kernel),
                               **GRAD_TOL)


class TestFusedLoopParity:
    """Deterministic sweep (runs without hypothesis): fused == loop."""

    @pytest.mark.parametrize("seed,batch,hard", [
        (0, 1, False), (1, 1, True),          # batch-1 edge
        (2, 33, False), (3, 64, True),
        (4, 257, False), (5, 257, True),      # odd batch, row-pad path
    ])
    def test_values_bit_exact(self, seed, batch, hard):
        rng = np.random.default_rng(seed)
        spans, dim = _random_layout(rng)
        _check_value_parity(spans, dim, batch, seed, hard=hard)

    @pytest.mark.parametrize("seed,batch,hard", [
        (10, 1, False), (11, 33, True), (12, 257, False), (13, 129, True),
    ])
    def test_grads_match(self, seed, batch, hard):
        rng = np.random.default_rng(seed)
        spans, dim = _random_layout(rng)
        _check_grad_parity(spans, dim, batch, seed, hard=hard)

    def test_st_grad_equals_soft_grad(self):
        """ST estimator sign regression: the hard path's gradient IS the
        soft path's gradient (the one-hot term carries none) — a flipped
        sign in `y_hard - stop_gradient(y) + y` would negate it."""
        rng = np.random.default_rng(77)
        spans, dim = _random_layout(rng)
        key = jax.random.PRNGKey(77)
        kl, ka, kc = jax.random.split(key, 3)
        logits = jax.random.normal(kl, (48, dim)) * 3.0
        ct = jax.random.normal(kc, (48, dim))
        for fn in (apply_activations,
                   lambda *a, **k: apply_activations_fused(
                       *a, **k, use_pallas=False)):
            g_soft = jax.grad(lambda l: jnp.sum(
                fn(l, spans, ka, 0.2, hard=False) * ct))(logits)
            g_hard = jax.grad(lambda l: jnp.sum(
                fn(l, spans, ka, 0.2, hard=True) * ct))(logits)
            np.testing.assert_allclose(np.asarray(g_hard),
                                       np.asarray(g_soft), **GRAD_TOL)

    def test_all_tanh_layout(self):
        spans = (SpanInfo(0, 1, "tanh", 0, False),
                 SpanInfo(1, 1, "tanh", 1, False))
        _check_value_parity(spans, 2, 17, 21)
        _check_grad_parity(spans, 2, 17, 21)

    def test_single_wide_softmax(self):
        spans = (SpanInfo(0, 11, "softmax", 0, True),)
        _check_value_parity(spans, 11, 40, 22, hard=True)

    def test_hard_outputs_are_one_hot(self):
        """ST hard mode: every softmax span row carries (up to float
        cancellation in the ST expression, ~1 ulp) exactly one 1.0."""
        rng = np.random.default_rng(33)
        spans, dim = _random_layout(rng)
        _, _, out = _check_value_parity(spans, dim, 101, 33, hard=True)
        for s in spans:
            seg = out[:, s.start:s.start + s.width]
            if s.activation == "softmax":
                onehot = np.eye(s.width, dtype=np.float32)[seg.argmax(1)]
                np.testing.assert_allclose(seg, onehot, atol=1e-6)
                assert ((seg > 0.5).sum(axis=1) == 1).all()
            else:
                assert np.all(np.abs(seg) <= 1.0)

    def test_soft_rows_sum_to_one(self):
        rng = np.random.default_rng(44)
        spans, dim = _random_layout(rng)
        _, _, out = _check_value_parity(spans, dim, 64, 44)
        for s in spans:
            if s.activation == "softmax":
                seg = out[:, s.start:s.start + s.width]
                np.testing.assert_allclose(seg.sum(axis=1), 1.0, atol=1e-5)

    def test_ops_wrapper_routes_agree(self):
        """ref route vs Pallas-interpret route, via the (jitted, as at
        every call site) ops wrapper."""
        rng = np.random.default_rng(55)
        spans, dim = _random_layout(rng)
        key = jax.random.PRNGKey(55)
        logits = jax.random.normal(key, (77, dim)) * 2.0
        a = jax.jit(lambda l: ops.segment_activations(
            l, spans, key, 0.2, use_pallas=False))(logits)
        b = jax.jit(lambda l: ops.segment_activations(
            l, spans, key, 0.2, interpret=True))(logits)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPackedKernel:
    """The Pallas kernel against the packed jnp oracle directly."""

    @pytest.mark.parametrize("N,block_n", [(256, 128), (300, 128), (5, 8)])
    def test_matches_packed_ref(self, N, block_n):
        rng = np.random.default_rng(7)
        spans, dim = _random_layout(rng)
        layout = build_span_layout(spans)
        key = jax.random.PRNGKey(7)
        kx, ku = jax.random.split(key)
        S, W = layout.kinds.shape
        x = jnp.where(jnp.asarray(layout.pack_pad)[None, :], -jnp.inf,
                      jax.random.normal(kx, (N, S * W)) * 3.0)
        u = jax.random.uniform(ku, (N, S * W), jnp.float32,
                               minval=1e-6, maxval=1.0 - 1e-6)
        for hard in (False, True):
            out = segment_activations(x, u, layout.kinds, tau=0.2,
                                      hard=hard, block_n=block_n,
                                      interpret=True)
            expect = jax.jit(ref.segment_activations_ref,
                             static_argnums=(3, 4))(x, u, layout.kinds,
                                                    0.2, hard)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(expect))

    def test_padded_lanes_carry_zero_mass(self):
        """Softmax padding invariant: -inf lanes get exactly 0 (soft) and
        are never the hard argmax."""
        spans = (SpanInfo(0, 3, "softmax", 0, True),
                 SpanInfo(3, 9, "softmax", 1, True))
        layout = build_span_layout(spans)
        key = jax.random.PRNGKey(9)
        N = 130
        S, W = layout.kinds.shape
        x = jnp.where(jnp.asarray(layout.pack_pad)[None, :], -jnp.inf,
                      jax.random.normal(key, (N, S * W)) * 4.0)
        u = jax.random.uniform(jax.random.fold_in(key, 1), (N, S * W))
        for hard in (False, True):
            out = np.asarray(segment_activations(
                x, u, layout.kinds, tau=0.2, hard=hard, block_n=64,
                interpret=True)).reshape(N, S, W)
            assert (out[:, 0, 3:] == 0.0).all()     # span 0 pads at lane 3+
            if hard:
                assert (out.argmax(axis=2)[:, 0] < 3).all()


if HAS_HYPOTHESIS:
    class TestPropertyParity:
        """Hypothesis sweep over random span layouts and batch sizes."""

        @settings(max_examples=12, deadline=None)
        @given(hst.integers(0, 10_000), hst.integers(1, 257),
               hst.booleans())
        def test_values_bit_exact(self, seed, batch, hard):
            # Loop vs fused ref stays bit-exact.  The interpret-mode kernel
            # is held to 1e-5 relative: XLA's CPU backend emits ``exp``
            # differently inside the jitted ref's fusion than inside the
            # interpreted kernel body, up to 4 ulp on identical inputs
            # (seed=5042, batch=1: z equal bit for bit, exp(z - m) 4.8e-7
            # apart near 1.0, hence 2.2e-6 relative in the softmax).
            rng = np.random.default_rng(seed)
            spans, dim = _random_layout(rng)
            _check_value_parity(spans, dim, batch, seed, hard=hard,
                                kernel_rtol=1e-5)

        @settings(max_examples=8, deadline=None)
        @given(hst.integers(0, 10_000), hst.integers(1, 257),
               hst.booleans())
        def test_grads_match(self, seed, batch, hard):
            rng = np.random.default_rng(seed)
            spans, dim = _random_layout(rng)
            _check_grad_parity(spans, dim, batch, seed, hard=hard)

        @settings(max_examples=8, deadline=None)
        @given(hst.integers(0, 10_000), hst.integers(1, 128))
        def test_hard_one_hot_validity(self, seed, batch):
            rng = np.random.default_rng(seed)
            spans, dim = _random_layout(rng)
            key = jax.random.PRNGKey(seed)
            out = np.asarray(apply_activations_fused(
                jax.random.normal(key, (batch, dim)) * 3.0, spans,
                jax.random.fold_in(key, 1), 0.2, hard=True,
                use_pallas=False))
            for s in spans:
                if s.activation == "softmax":
                    seg = out[:, s.start:s.start + s.width]
                    assert ((seg > 0.5).sum(axis=1) == 1).all()
                    np.testing.assert_allclose(seg.sum(axis=1), 1.0,
                                               atol=1e-5)


class TestHardDrawDistribution:
    def test_chi_squared_matches_loop_frequencies(self):
        """Fused hard Gumbel-softmax draws land on categories with the
        same frequencies as the per-span loop under the same key
        discipline (independent keys, chi-squared against the analytic
        Gumbel-max target softmax(logits) — mirroring the PR-2 device
        sampler test)."""
        spans = (SpanInfo(0, 6, "softmax", 0, True),
                 SpanInfo(6, 1, "tanh", 1, False),
                 SpanInfo(7, 4, "softmax", 2, True))
        dim = 11
        n = 60_000
        key = jax.random.PRNGKey(17)
        row = jax.random.normal(key, (1, dim)) * 1.5
        logits = jnp.tile(row, (n, 1))
        out_f = np.asarray(apply_activations_fused(
            logits, spans, jax.random.fold_in(key, 1), 0.2, hard=True,
            use_pallas=False))
        out_l = np.asarray(jax.jit(lambda l: apply_activations(
            l, spans, jax.random.fold_in(key, 2), 0.2, hard=True))(logits))

        chi2_total, dof_total = 0.0, 0
        for s in spans:
            if s.activation != "softmax":
                continue
            seg = row[0, s.start:s.start + s.width]
            p = np.asarray(jax.nn.softmax(seg))     # Gumbel-max marginal
            for out in (out_f, out_l):
                obs = out[:, s.start:s.start + s.width].argmax(1)
                counts = np.bincount(obs, minlength=s.width).astype(float)
                exp = p * n
                keep = exp >= 5
                chi2_total += (((counts - exp) ** 2 / exp)[keep]).sum()
                dof_total += max(int(keep.sum()) - 1, 1)
            # and the two paths agree with each other
            f_freq = np.bincount(
                out_f[:, s.start:s.start + s.width].argmax(1),
                minlength=s.width) / n
            l_freq = np.bincount(
                out_l[:, s.start:s.start + s.width].argmax(1),
                minlength=s.width) / n
            np.testing.assert_allclose(f_freq, l_freq, atol=0.02)
        # ~p>0.9999 bound: mean + 4 sigma of a chi2_dof variate
        assert chi2_total < dof_total + 4.0 * np.sqrt(2.0 * dof_total), \
            (chi2_total, dof_total)


def _count(*names):
    return sum(ops.DISPATCH_COUNTS[n] for n in names)


class TestEndToEndDispatchCounts:
    """The synthesis pipeline stays one-kernel-per-stage: future PRs
    can't silently reintroduce per-column/per-span dispatch loops."""

    @pytest.fixture(scope="class")
    def fitted(self):
        ds = make_dataset("adult", n_rows=500, seed=13)
        key = jax.random.PRNGKey(13)
        enc = fit_centralized_encoders(ds.data, ds.schema, key)
        return ds, enc, key

    def test_synthesize_table_one_dispatch_per_stage(self, fitted):
        ds, enc, key = fitted
        # distinctive cfg => sample_synthetic retraces (counts are
        # recorded at trace time for jitted wrappers)
        cfg = CTGANConfig(batch_size=24, gen_hidden=(24, 24),
                          disc_hidden=(24, 24), pac=4, z_dim=12)
        state = init_gan_state(jax.random.fold_in(key, 1), cfg,
                               enc.cond_dim, enc.encoded_dim)
        ops.DISPATCH_COUNTS.clear()
        encoded = enc.encode(ds.data, jax.random.fold_in(key, 2))
        assert _count("vgm_encode_table", "vgm_encode_table_ref") == 1
        raw = synthesize_table(state.g_params, jax.random.fold_in(key, 3),
                               cfg, enc, 37)
        assert _count("segment_activations", "segment_activations_ref") == 1
        assert _count("vgm_decode_table", "vgm_decode_table_ref") == 1
        assert raw.shape == (37, len(ds.schema))
        ops.DISPATCH_COUNTS.clear()

    def test_round_engine_constant_dispatches(self, fitted):
        """One engine round traces exactly 2 fused activation dispatches
        (one generator forward in the D loss, one in the G loss) — a
        constant, NOT proportional to the span/column count."""
        ds, enc, key = fitted
        cfg = CTGANConfig(batch_size=20, gen_hidden=(16, 16),
                          disc_hidden=(16, 16), pac=4, z_dim=8)
        spans, cond_spans = tuple(enc.spans()), tuple(enc.condition_spans())
        state = init_gan_state(jax.random.fold_in(key, 4), cfg,
                               enc.cond_dim, enc.encoded_dim)
        sampler = DeviceSampler(
            np.asarray(enc.encode(ds.data, jax.random.fold_in(key, 5))), enc)
        engine = RoundEngine(cfg, spans, cond_spans, batch=20, local_steps=2)
        ops.DISPATCH_COUNTS.clear()
        st, _ = engine.run_round(state, sampler.tables,
                                 jax.random.fold_in(key, 6))
        assert int(st.step) == 2
        assert _count("segment_activations", "segment_activations_ref") == 2
        assert _count("vgm_encode_table", "vgm_encode_table_ref",
                      "vgm_encode", "vgm_encode_ref") == 0
        ops.DISPATCH_COUNTS.clear()
