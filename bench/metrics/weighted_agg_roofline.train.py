"""``weighted_agg`` (the federated merge of G and D) kernel time in the
trace against the least time its bytes need at the chip's HBM bandwidth;
the operations (2 per client and parameter) need far less."""
from bench import flops


def read(ctx):
    if ctx["spec"]["traffic_mix"]["kind"] != "train":
        return None
    k = ctx["trace"].kernel("weighted_agg")
    if k is None:
        return None
    seconds, calls = k
    cell, peaks = ctx["cell"], ctx["peaks"]
    need = calls * max(
        flops.weighted_agg_bytes(cell.n_clients, cell.n_params)
        / peaks["hbm_bytes_per_s"],
        flops.weighted_agg_flops(cell.n_clients, cell.n_params)
        / peaks["flops_bf16"])
    return 100.0 * need / seconds
