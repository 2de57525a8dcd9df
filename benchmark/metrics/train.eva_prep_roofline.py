"""Bytes that the summaries need to move (bfloat16, every layer and position of the traced steps: k and v read and a summary key and value a chunk written, forward and once more in the replay; backward, k, v and the summaries' cotangents read and k's and v's written: ``benchmark/counts_evabyte.py``) a second of device time under ``eva.prep``, over the chip's HBM bandwidth."""


def read(obs):
    from benchmark import counts_evabyte

    return counts_evabyte.prep_roofline(obs)
