"""Device busy time under ``sparse.index`` (the indexer's projections, ``index_scores`` in its three passes, the mask from the thresholds), ``sparse.select`` (each query's top-k threshold) and ``sparse.index_loss`` (the indexer's loss and its gradient) over device busy time: what choosing the keys costs, beside the attention over them."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("sparse.index", "sparse.select", "sparse.index_loss"))
