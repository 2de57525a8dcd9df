"""Device busy time under ``moe.preroute`` (a router that reads the layer's input ahead of attention: its float32 product, the softmax, the top-k, the weights, the counting sort of the slots by held expert and ``order``; forward, replay and backward) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("moe.preroute",))
