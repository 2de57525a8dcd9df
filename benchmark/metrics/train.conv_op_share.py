"""Device busy time under the short-convolution operator's module (``conv``: its two products, the gates and the taps; forward, replay and backward) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("conv",))
