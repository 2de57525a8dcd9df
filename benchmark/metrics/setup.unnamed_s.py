"""``devices`` to the window's start, less the union over threads of every span of the program: what set-up still hides (the harness's host work, waits for the device outside any span)."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.unnamed_s)
