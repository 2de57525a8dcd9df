"""Device busy time under ``kda.conv`` (the three streams' causal taps with their mask at document starts, SiLU, and the norms of q and k: no product, bytes only; forward, replay and backward) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("kda.conv",))
