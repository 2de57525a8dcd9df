"""Device busy time under the layer scan and no module of a layer (its slicing and stacked writes, hoisted operations, residual adds) over device busy time, from the trace's ``op_name``s."""

from benchmark import spans


def read(obs):
    return spans.train_scope_share(obs, "scan")
