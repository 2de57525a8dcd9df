"""Device busy time of the shared expert (``moe.shared``) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("moe.shared",))
