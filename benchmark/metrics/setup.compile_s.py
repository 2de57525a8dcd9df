"""Trace, lower and compile-or-cache-load of the cell's programs, with the first execution of each."""


def read(obs):
    return obs["phases"]["compile_s"]
