"""Weights, optimizer state or page pool born on the device (and the program's own start: mesh, executor, server)."""


def read(obs):
    return obs["phases"]["build_s"]
