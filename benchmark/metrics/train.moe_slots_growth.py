"""The mean ``moe_slots`` of the window's last ``steps_per_chunk`` steps over that of its first (``train.step_device`` attributes): how far the router's drift moved the load inside the window."""

from benchmark import step_records


def read(obs):
    return step_records.read(obs, lambda steps: step_records.growth(steps, step_records.chunk(obs), "moe_slots"))
