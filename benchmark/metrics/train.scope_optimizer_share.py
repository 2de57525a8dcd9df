"""Device busy time under the ``optimizer`` scope (optax update and apply, gradient norm) over device busy time, from the trace's ``op_name``s."""

from benchmark import spans


def read(obs):
    return spans.train_scope_share(obs, "optimizer")
