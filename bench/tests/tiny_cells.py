"""The cells cut to a size a CPU test can run (the chip runs them at
their configured sizes)."""
import time


def tiny(spec):
    """A cell's spec at CPU-test size: small table, narrow networks."""
    spec["model"].update(rows=1500, z_dim=16, gen_hidden=[32, 32],
                         disc_hidden=[32, 32], pac=5, batch_size=50)
    spec["traffic_mix"].update(small_rows=100, clients=3, max_dispatches=20,
                               rounds_per_dispatch=5, ahead_dispatches=2)


def run_tiny(cell, seed=5, seconds=0.5):
    """Run a cell on the CPU at test size; returns the result line."""
    from bench import harness
    return harness.run(cell, seed, seconds, False,
                       t_start=time.perf_counter(), require_chip=False,
                       patch_spec=tiny)


def tiny_cell(name, seed, seconds=None):
    """A cell object at test size, set up (and driven for ``seconds``
    where given), its program freed."""
    from bench import harness
    spec = harness.cell_spec(harness.load_benchmark(), name)
    tiny(spec)
    cell = harness.kind_module(spec["traffic_mix"]["kind"]).Cell(spec, seed)
    cell.setup()
    if seconds is not None:
        cell.window(seconds, False)
    cell.free()
    return spec, cell
