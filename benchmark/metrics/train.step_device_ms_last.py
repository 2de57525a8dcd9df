"""Mean duration of the ``train.step_device`` spans of the window's last ``steps_per_chunk`` steps (its last ``fit`` call)."""

from benchmark import step_records


def read(obs):
    return step_records.read(obs, lambda steps: step_records.mean_ms(steps[-step_records.chunk(obs):]))
