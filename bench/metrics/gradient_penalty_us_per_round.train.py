"""Device time of the WGAN-GP gradient penalty (scope ``gradient_penalty``,
inside the critic update: the interpolation, the critic's input gradient
and its backward), per global round of the traced window, summed over
chips."""
from bench import scopes


def read(ctx):
    return scopes.us_per_round(ctx, "gradient_penalty")
