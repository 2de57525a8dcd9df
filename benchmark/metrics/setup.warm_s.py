"""Warm-up executions after the first, the readings the comparison needs, lead-in traffic."""


def read(obs):
    return obs["phases"]["warm_s"]
