"""Device idle time while the loop thread was inside ``train.drain`` (the loop thread waits for the device: metric reads, syncs), over the traced window."""

from benchmark import spans


def read(obs):
    return spans.train_idle_share(obs, ("train.drain",))
