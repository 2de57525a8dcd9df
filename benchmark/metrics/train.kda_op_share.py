"""Device busy time under the Kimi-delta-attention operator's module (``kda``: its six products, the taps and norms, the decays, the chunked delta rule and the gated output product; forward, replay and backward) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("kda",))
