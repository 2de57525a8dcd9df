"""Device busy time of the routed experts' grouped products (``moe.experts``) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("moe.experts",))
