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
