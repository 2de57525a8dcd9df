"""Median time of an optimizer step: host clock around ``Trainer.fit`` chunks (each ends with the device drained), over the steps in the chunk."""


def read(obs):
    import statistics
    return statistics.median(obs["chunks"]) * 1e3 if obs.get("chunks") else None
