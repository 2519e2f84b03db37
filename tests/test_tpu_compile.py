"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e`` topology, and refuses what Mosaic would refuse on
the chip (block shapes off the (8, 128) tiling, VMEM overuse) — things
interpret mode never checks.  Every kernel compiles with
``interpret=False`` at the paper's widths: a 500-row batch, Q=6
continuous columns x K=10 VGM modes, 15 activation spans x Wmax=42, and a
P=5 x D=300k federator merge.  Each compile must contain the Mosaic call
(``tpu_custom_call``).

The topology is described inside a fixture, never at import, so only the
worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.segment_activations import (segment_activations,
                                               segment_activations_packed)
from repro.kernels.vgm_decode import vgm_decode_table
from repro.kernels.vgm_encode import vgm_encode_table
from repro.kernels.weighted_agg import weighted_agg

N, Q, K = 500, 6, 10            # batch 500; Q continuous cols x K modes
S, W = 15, 42                   # activation spans x widest span
P, D = 5, 300_000               # clients x flat G+D parameter count
SETUP_ROWS = 40_000             # the §4.1 encode of an Adult-sized client
TAU = 0.2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _encode(n):
    bn = ops.row_tile(n)
    return (lambda x, m, s, lw, g: vgm_encode_table(
        x, m, s, lw, g, block_n=bn, interpret=False),
        [(n, Q), (Q, K), (Q, K), (Q, K), (n, Q * K)])


def _decode():
    bn = ops.row_tile(N)
    return (lambda sl, m, s: vgm_decode_table(sl, m, s, block_n=bn,
                                              interpret=False),
            [(N, Q * (1 + K)), (Q, K), (Q, K)])


def _activations_forward():
    bn = ops.row_tile(N)
    return (lambda x, u, k: segment_activations(
        x, u, k, tau=TAU, hard=True, block_n=bn, interpret=False),
        [(N, S * W), (N, S * W), (S, W)])


def _activations_value_and_grad():
    # value_and_grad keeps the Pallas forward live next to the custom-VJP
    # backward (a bare grad of a sum would let XLA drop the forward)
    bn = ops.row_tile(N)

    def fn(x, u, k):
        return jax.value_and_grad(lambda x: jnp.sum(segment_activations_packed(
            x, u, k, TAU, True, True, False, bn)))(x)
    return fn, [(N, S * W), (N, S * W), (S, W)]


def _merge():
    return (lambda s, w: weighted_agg(s, w, interpret=False),
            [(P, D), (P,)])


CASES = {
    "vgm_encode_table": lambda: _encode(N),
    "vgm_encode_table_setup_rows": lambda: _encode(SETUP_ROWS),
    "vgm_decode_table": _decode,
    "segment_activations": _activations_forward,
    "segment_activations_grad": _activations_value_and_grad,
    "weighted_agg": _merge,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]()
    hlo = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the HLO"


@pytest.mark.parametrize("n,tile", [(500, 504), (1, 8), (1024, 1024),
                                    (40_000, 1000), (1025, 520)])
def test_row_tile_divides_padded_rows(n, tile):
    assert ops.row_tile(n) == tile
    assert tile % 8 == 0


SCOPES = {"draw", "d_step", "gradient_penalty", "g_step", "merge",
          "kernel_layout"}


def _scopes(op_name):
    """The scope names in an op's name stack, transformation wrappers
    (``transpose(jvp(d_step))``, ``vmap(draw)``) taken off."""
    out = []
    for part in op_name.split("/"):
        while "(" in part and not part.startswith("jit(") \
                and part.endswith(")"):
            part = part[part.index("(") + 1:-1]
        if part in SCOPES:
            out.append(part)
    return out


@pytest.mark.parametrize("target", ["cpu", "v5e"])
def test_round_phases_named_in_compiled_program(request, monkeypatch,
                                                target):
    """``FederatedProgram.run`` at tiny widths, 2 rounds: every phase scope
    reaches the compiled program's op metadata, and on the chip's
    compile the Mosaic calls sit in their phases."""
    import re

    import numpy as np

    from repro.fed import FederatedProgram, setup_federation
    from repro.gan.ctgan import CTGANConfig
    from repro.tabular import ColumnSpec
    rng = np.random.default_rng(0)
    schema = [ColumnSpec("x", "continuous", max_modes=2),
              ColumnSpec("c", "categorical")]
    parts = [np.stack([rng.normal(size=48), rng.integers(0, 3, 48)], 1)
             for _ in range(2)]
    cfg = CTGANConfig(batch_size=8, gen_hidden=(16,), disc_hidden=(16,),
                      pac=2, z_dim=4)
    fe = setup_federation(parts, schema, cfg, seed=0)
    keys = FederatedProgram.fold_round_keys(jax.random.PRNGKey(2), 0, 2)
    args = (fe.states, fe.tables, fe.S, fe.n_rows, keys)
    if target == "v5e":
        sharding = request.getfixturevalue("one_chip")
        args = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), args)
        monkeypatch.setattr(ops, "_ON_TPU", True)   # take the kernel routes
    prog = FederatedProgram(cfg, fe.spans, fe.cond_spans, batch=8,
                            local_steps=1)
    hlo = prog.run.lower(*args).compile().as_text()
    instr = re.findall(r'%([^\s=]+) = [^\n]*?op_name="([^"]*)"', hlo)
    found = {s for _, stack in instr for s in _scopes(stack)}
    assert found == SCOPES
    calls = {name: _scopes(stack)[-1] for name, stack in instr
             if name.startswith(("weighted_agg", "jvp_jit_segment_act"))}
    if target == "v5e":
        assert sorted(calls.values()) == ["d_step", "g_step", "merge"]
        assert all(calls[n] == "merge" for n in calls
                   if n.startswith("weighted_agg"))
        assert hlo.count("tpu_custom_call") >= 3
