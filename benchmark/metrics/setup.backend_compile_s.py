"""``compile.backend`` spans before the window whose ``cache`` is not ``hit``: XLA and Mosaic compiles. About 0 on a warm run."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.backend_compile_s)
