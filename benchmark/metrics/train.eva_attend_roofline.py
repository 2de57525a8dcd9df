"""Needed operations of the entries the queries see (the exact keys of L(t) and the summaries of R(t): two products of the head's width an entry forward, four backward, 32 heads, every layer, the documents of the traced steps as they lie in their rows: ``benchmark/counts_evabyte.py``) a second of device time in the flash kernels under ``eva.local`` and ``eva.remote``, over the chip's bf16 peak."""


def read(obs):
    from benchmark import counts_evabyte

    return counts_evabyte.attend_roofline(obs)
