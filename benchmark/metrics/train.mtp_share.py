"""Device busy time of everything under the multi-token-prediction module (``mtp``: its norms and projection, its expert layer with attention; the shared head's second pass and the second loss are outside the module) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("mtp",))
