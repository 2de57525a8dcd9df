"""Device busy time under ``conv.mix`` (the short convolution's two gates and its taps between the two products: no product, bytes only) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("conv.mix",))
