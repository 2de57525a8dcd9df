"""Mean of the attribute ``moe_slots`` (the (token, choice) slots on held experts, all layers) over the ``train.step_device`` spans of every step of the window: a count a step, no line between two readings."""

from benchmark import step_records


def read(obs):
    return step_records.read(obs, lambda steps: step_records.attr_mean(steps, "moe_slots"))
