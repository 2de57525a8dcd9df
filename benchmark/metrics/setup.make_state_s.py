"""The program's ``train.make_state`` spans before the window: ``Trainer.make_state`` on the host (shape inference, the init's compile stages, its dispatch)."""

from benchmark import setup_spans


def read(obs):
    return setup_spans.read(obs, setup_spans.make_state_s)
