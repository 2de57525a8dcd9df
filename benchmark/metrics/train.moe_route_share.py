"""Device busy time of the expert share layer around its products (``moe.route``: float32 router, sigmoid, top-k, weights; ``moe.dispatch``: the sort of the slots by held expert and the gather into slot order; ``moe.combine``: the gather back and the weighted sum) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("moe.route", "moe.dispatch", "moe.combine"))
