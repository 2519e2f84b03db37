"""Operations and bytes that each measured piece of work requires.

Counted from shapes alone: the matrix products of a CTGAN step (other
arithmetic is a rounding error beside them) and the bytes each kernel
must move at the least, so a share of a peak can only overstate the
time a piece needs, never the work.  Padding a kernel adds is not
required work and is not counted.

A dense layer ``in -> out`` over ``n`` rows is ``2 n in out`` operations
forward, the same again for the gradient of its input and again for the
gradient of its weights.
"""
from __future__ import annotations


def _dense(n, dims):
    """Forward operations of a chain of dense layers ``dims[0] -> ...``."""
    return sum(2 * n * a * b for a, b in zip(dims[:-1], dims[1:]))


def generator_layers(m: dict, cond_dim: int, data_dim: int):
    """(in, out) of each generator layer (residual concatenation)."""
    dims, d = [], m["z_dim"] + cond_dim
    for h in m["gen_hidden"]:
        dims.append((d, h))
        d += h
    dims.append((d, data_dim))
    return dims


def train_step(m: dict, cond_dim: int, data_dim: int) -> float:
    """Operations of one local step (one critic and one generator update)
    per row of the batch.

    With G and C the forward operations of generator and critic over the
    batch, and G0, C0 those of their first layers:

    * critic update: G forward; C on fake and on real rows; the weight
      gradients of both passes (2C) and their hidden-input gradients
      (2(C - C0)); the gradient penalty's forward (C), its input gradient
      (C) and the parameter gradient through both (4C): G + 12C - 2C0;
    * generator update: G forward, C forward, the critic's input gradient
      (C), the generator's weight gradients (G) and hidden-input
      gradients (G - G0): 3G - G0 + 2C.
    """
    n = m["batch_size"]
    gl = generator_layers(m, cond_dim, data_dim)
    G = sum(2 * n * a * b for a, b in gl)
    G0 = 2 * n * gl[0][0] * gl[0][1]
    packs = n // m["pac"]
    cdims = [(data_dim + cond_dim) * m["pac"], *m["disc_hidden"], 1]
    C = _dense(packs, cdims)
    C0 = _dense(packs, cdims[:2])
    return ((G + 12 * C - 2 * C0) + (3 * G - G0 + 2 * C)) / n


def segment_activations_bytes(rows: int, widths, kinds) -> int:
    """Logits read, one uniform per softmax lane read, activations written
    (float32)."""
    lanes = sum(widths)
    soft = sum(w for w, k in zip(widths, kinds) if k == "softmax")
    return 4 * rows * (2 * lanes + soft)


def weighted_agg_bytes(clients: int, params: int) -> int:
    """Every client's flat parameter vector read, the merged one written."""
    return 4 * params * (clients + 1)


def weighted_agg_flops(clients: int, params: int) -> int:
    return 2 * clients * params
