"""Device busy time under ``sparse.select`` (each query's exact top-k threshold over its visible keys, the ``sparse_select`` kernel; once a layer and step, a recomputed layer keeps the thresholds) over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("sparse.select",))
