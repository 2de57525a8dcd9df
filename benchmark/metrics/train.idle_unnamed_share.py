"""Device idle time while the loop thread was inside no span of the loop thread, over the traced window."""

from benchmark import spans


def read(obs):
    return spans.train_idle_share(obs, (None,))
