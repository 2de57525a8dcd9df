"""Mean duration of the ``train.step_device`` spans of the window's first ``steps_per_chunk`` steps (its first ``fit`` call)."""

from benchmark import step_records


def read(obs):
    return step_records.read(obs, lambda steps: step_records.mean_ms(steps[:step_records.chunk(obs)]))
