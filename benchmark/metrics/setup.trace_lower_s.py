"""``compile.trace`` and ``compile.lower`` spans before the window, overlaps on a thread counted once: Python and MLIR work that no cache saves."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.trace_lower_s)
