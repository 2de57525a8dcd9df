"""The program's ``compile.cache_misses`` counter, less the misses from the window's start on: programs compiled anew in set-up. 0 on a warm run."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.cache_misses)
