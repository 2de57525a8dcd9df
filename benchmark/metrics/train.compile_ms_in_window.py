"""Summed ``compile.*`` spans of the program inside the measured window. Must read 0."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.compile_ms_in_window)
