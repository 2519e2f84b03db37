"""Host seconds of the §4.1/§4.2 set-up (``fed.setup.setup_federation``:
client statistics, VGM fits and merge, divergence, encoding), measured
around the benchmark's call into it."""


def read(ctx):
    return getattr(ctx["cell"], "federation_setup_s", None)
