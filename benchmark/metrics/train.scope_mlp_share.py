"""Device busy time under the feed-forward modules (``mlp``, ``mlp_norm``; ``moe`` and its scopes) over device busy time, from the trace's ``op_name``s."""

from benchmark import spans


def read(obs):
    return spans.train_scope_share(obs, "mlp")
