"""Slots on held experts that a buffer cut, summed over the readings of the window (``moe_slots_dropped`` of ``Trainer.fit``, gauge ``moe.slots_dropped``): the layer is dropless, so 0, and a run that reads anything else is not correct."""

from benchmark import scopes


def read(obs):
    values = scopes.counter_values(obs, "moe_slots_dropped")
    return sum(values) if values else None
