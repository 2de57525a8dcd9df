"""Compile stages before the window under no span of the program's own (the train function's own programs: ``reseed``, ``first_gradient``, ``change``): set-up a user's trial does not pay."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.harness_compile_s)
