"""Device time of the federated merge (scope ``merge``: flatten the clients'
generator and critic parameters, the ``weighted_agg`` kernel, unflatten
and broadcast back onto the client axis), per global round of the traced
window, summed over chips."""
from bench import scopes


def read(ctx):
    return scopes.us_per_round(ctx, "merge")
