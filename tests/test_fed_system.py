"""End-to-end system behaviour: the four architectures (§3/§5) on small
synthetic tables, plus the SPMD federated round vs the vmap simulation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm_model
from repro.core.aggregation import weighted_average, psum_weighted
from repro.core.architectures import (run_centralized, run_federated,
                                      run_mdtgan)
from repro.gan.ctgan import CTGANConfig
from repro.tabular import make_dataset, partition_full_copy, partition_quantity_skew

CFG = CTGANConfig(batch_size=60, gen_hidden=(32, 32), disc_hidden=(32, 32),
                  pac=6, z_dim=32)


@pytest.fixture(scope="module")
def ds():
    return make_dataset("adult", n_rows=600, seed=0)


class TestDrivers:
    def test_federated_runs_and_evaluates(self, ds):
        parts = partition_full_copy(ds, 3)
        res = run_federated(parts, ds.schema, cfg=CFG, rounds=2,
                            local_steps=1, eval_real=ds.data, eval_every=1,
                            eval_samples=256)
        assert len(res.history) == 2
        for h in res.history:
            assert 0 <= h["avg_jsd"] <= 1
            assert h["avg_wd"] >= 0
            assert np.isfinite(h["d_loss"])
        np.testing.assert_allclose(res.weights.sum(), 1.0, rtol=1e-5)

    def test_vanilla_fl_is_uniform(self, ds):
        parts = partition_quantity_skew(ds, 3, small_rows=80)
        res = run_federated(parts, ds.schema, cfg=CFG, rounds=1,
                            local_steps=1, weighting="uniform")
        np.testing.assert_allclose(res.weights, 1 / 3, atol=1e-6)

    def test_fedtgan_upweights_big_client(self, ds):
        parts = partition_quantity_skew(ds, 3, small_rows=80)
        res = run_federated(parts, ds.schema, cfg=CFG, rounds=1,
                            local_steps=1, weighting="fedtgan")
        assert res.weights[-1] == res.weights.max()

    def test_centralized_runs(self, ds):
        res = run_centralized(ds.data, ds.schema, cfg=CFG, epoch_steps=2,
                              epochs=1, eval_real=ds.data, eval_every=1,
                              eval_samples=256)
        assert len(res.history) == 1

    def test_mdtgan_runs(self, ds):
        parts = partition_full_copy(ds, 3)
        res = run_mdtgan(parts, ds.schema, cfg=CFG, epochs=1,
                         steps_per_epoch=1, eval_real=ds.data, eval_every=1,
                         eval_samples=256)
        assert len(res.history) == 1
        assert res.comm_bytes_per_round > 0


class TestAggregation:
    def test_weighted_average_identity(self, key):
        tree = {"w": jax.random.normal(key, (4, 8, 8))}
        merged = weighted_average(tree, jnp.array([1.0, 0.0, 0.0, 0.0]))
        np.testing.assert_allclose(np.asarray(merged["w"]),
                                   np.asarray(tree["w"][0]), rtol=1e-6)

    def test_weighted_average_linearity(self, key):
        tree = jax.random.normal(key, (3, 16))
        w = jnp.array([0.2, 0.3, 0.5])
        m = weighted_average(tree, w)
        expect = (tree * w[:, None]).sum(0)
        np.testing.assert_allclose(np.asarray(m), np.asarray(expect), rtol=1e-5)

    def test_psum_weighted_matches_host(self, key):
        """SPMD weighted merge over the client axis == host-side average."""
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import _make_mesh
        n = len(jax.devices())
        mesh = _make_mesh((n,), ("c",))
        vals = jax.random.normal(key, (n, 8))
        w = jax.nn.softmax(jax.random.normal(jax.random.fold_in(key, 1), (n,)))

        def merge(v, wi):
            return psum_weighted(v[0], wi[0], "c")[None]

        out = jax.shard_map(merge, mesh=mesh, in_specs=(P("c"), P("c")),
                            out_specs=P("c"), check_vma=False)(vals, w)
        expect = weighted_average(vals, w)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expect),
                                   rtol=1e-5, atol=1e-6)


class TestCommModel:
    def test_fl_cheaper_than_md_per_epoch(self):
        """The paper's §5.4 claim, analytically: at CTGAN scale the MD
        structure moves more bytes per epoch than FL."""
        model_bytes = 5e6                      # ~CTGAN G+D
        fl = comm_model.fl_bytes_per_round(5, model_bytes)
        md = comm_model.md_bytes_per_epoch(5, steps=80, batch=500,
                                           row_bytes_dim=150,
                                           disc_bytes=2e6)
        assert md > fl

    def test_fl_scales_linearly_in_clients(self):
        b5 = comm_model.fl_bytes_per_round(5, 1e6)
        b20 = comm_model.fl_bytes_per_round(20, 1e6)
        assert b20 == 4 * b5

    def test_transfer_seconds_uses_measured_link(self):
        # 943 Mb/s -> ~1.06s for 1 Gb
        s = comm_model.transfer_seconds(943e6 / 8)
        np.testing.assert_allclose(s, 1.0, rtol=1e-6)
