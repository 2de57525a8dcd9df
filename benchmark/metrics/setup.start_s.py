"""Process start to ``jax.devices()`` answered: interpreter, imports, backend."""


def read(obs):
    return obs["phases"]["start_s"]
