"""The busiest held expert's slots over the mean held expert's, a mean over the expert layers (``moe_load_max_over_mean`` of ``Trainer.fit``, gauge ``moe.load_max_over_mean``), averaged over the readings of the window (each chunk's last step)."""

from benchmark import scopes


def read(obs):
    values = scopes.counter_values(obs, "moe_load_max_over_mean")
    return sum(values) / len(values) if values else None
