"""Mean duration of the window's ``train.step_device`` spans (``Trainer.fit``'s ``fit-steps`` thread: a step from the later of the previous step's end and its dispatch to its output's readiness): the step's time over every step of the window."""

from benchmark import step_records


def read(obs):
    return step_records.read(obs, step_records.mean_ms)
