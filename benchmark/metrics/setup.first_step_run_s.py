"""The first ``train_step`` span and its ``train.drain why=compile``, less the compile stages under them: the step's first dispatch and execution."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.first_step_run_s)
