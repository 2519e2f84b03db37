"""``segment_activations`` kernel time in the trace against the least time
its bytes need at the chip's HBM bandwidth (one call per critic and one
per generator update, all clients' batches in one call)."""
from bench import flops


def read(ctx):
    if ctx["spec"]["traffic_mix"]["kind"] != "train":
        return None
    k = ctx["trace"].kernel("segment_activations")
    if k is None:
        return None
    seconds, calls = k
    cell = ctx["cell"]
    rows = cell.n_clients * cell.m["batch_size"]
    need = calls * flops.segment_activations_bytes(
        rows, cell.span_widths, cell.span_kinds)
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
