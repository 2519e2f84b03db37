"""The whole training step's share of the chip's bf16 peak while the
device works: the matrix operations the rounds completed in the traced
window require (``flops.train_step`` per row) over the device's busy time
in the trace (the union of its op intervals)."""
from bench import flops


def read(ctx):
    if ctx["spec"]["traffic_mix"]["kind"] != "train":
        return None
    cell, busy = ctx["cell"], ctx["trace"].busy_s
    if not ctx["window"]["rows"] or busy <= 0:
        return None
    need = flops.train_step(cell.m, cell.cond_dim, cell.data_dim) * ctx[
        "window"]["rows"]
    return 100.0 * need / busy / ctx["peaks"]["flops_bf16"]
