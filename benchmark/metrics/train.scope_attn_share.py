"""Device busy time under the attention modules (``attn``, ``attn_norm``: projections, rope, flash kernels) over device busy time, from the trace's ``op_name``s."""

from benchmark import spans


def read(obs):
    return spans.train_scope_share(obs, "attn")
