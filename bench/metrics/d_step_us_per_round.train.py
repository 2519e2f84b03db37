"""Device time of the critic update (scope ``d_step``: the generator
forward that makes its fake rows, the critic loss, its gradient and the
Adam update), without the gradient penalty and the kernel layout copies
inside it, per global round of the traced window, summed over chips."""
from bench import scopes


def read(ctx):
    return scopes.us_per_round(ctx, "d_step")
