"""The ``cache_load_ms`` of the ``compile.backend`` spans before the window: reading executables back from the persistent cache."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.cache_load_s)
