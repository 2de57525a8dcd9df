"""Device busy time under none of the five scopes (the embedding lookup and its scatter-add, operations the compiler gave no ``op_name``) over device busy time, from the trace's ``op_name``s."""

from benchmark import spans


def read(obs):
    return spans.train_scope_share(obs, "unscoped")
