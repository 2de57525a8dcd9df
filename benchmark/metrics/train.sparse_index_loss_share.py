"""Device busy time under ``sparse.index_loss`` (the indexer's loss and its gradient to the indexer's three inputs: the ``index_loss`` kernel since PR 33, blockwise XLA before it, and the transposes and casts around either) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("sparse.index_loss",))
