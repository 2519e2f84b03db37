"""Device time of the layout work around the Pallas kernels (scope
``kernel_layout``: packing and padding into the kernels' span-major
blocks, and unpacking after), per global round of the traced window,
summed over chips."""
from bench import scopes


def read(ctx):
    return scopes.us_per_round(ctx, "kernel_layout")
