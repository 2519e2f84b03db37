"""Device time of the generator update (scope ``g_step``: the generator
and critic forward, the generator loss, its gradient and the Adam
update), without the kernel layout copies inside it, per global round of
the traced window, summed over chips."""
from bench import scopes


def read(ctx):
    return scopes.us_per_round(ctx, "g_step")
