"""Device busy time under ``kda.scan`` (everything of ``ops/kda.py``: the chunks' masked products, the triangular inverse, the recurrence across chunks, forward, the backward's remake of the states and its reverse pass) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("kda.scan",))
