"""Device time of the conditional batch draw (scope ``draw``: the on-device
sampler and the batch reshape at the top of each local round), per
global round of the traced window, summed over chips."""
from bench import scopes


def read(ctx):
    return scopes.us_per_round(ctx, "draw")
