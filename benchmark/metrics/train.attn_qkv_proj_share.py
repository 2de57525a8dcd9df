"""Device busy time under the head-shaped projections of ``Attention`` (modules ``wq``, ``wk``, ``wv`` inside ``attn``: forward, replay, both products of the backward, and whatever the compiler fuses into an operation that keeps one of those names, such as the optimizer's update of the kernel) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("wq", "wk", "wv"))
