"""Device busy time under the scope ``attn.gate`` (the gate a head of every attention layer: its projection, the sigmoid and the product with the heads' outputs; forward, replay and backward) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("attn.gate",))
