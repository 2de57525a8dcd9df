"""Device idle time while the loop thread was inside ``train.input_wait`` or ``train.fit_setup`` (the blocked pull of a batch; ``fit`` entry to its first step), over the traced window."""

from benchmark import spans


def read(obs):
    return spans.train_idle_share(obs, ("train.input_wait", "train.fit_setup"))
