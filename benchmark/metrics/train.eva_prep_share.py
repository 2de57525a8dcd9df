"""Device busy time under the scope ``eva.prep`` (a chunk's summary key and value from its keys' softmax against ``phi``, the two weighted sums and ``mu``: plain XLA, bound by memory; forward, replay and backward) over device busy time."""


def read(obs):
    from benchmark import counts_evabyte

    return counts_evabyte.scope_share(obs, ("eva.prep",))
