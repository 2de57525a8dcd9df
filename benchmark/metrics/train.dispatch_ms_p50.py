"""Median duration of the loop thread's ``train_step`` span (dispatch of the jitted step) over the traced steps."""

import statistics

from benchmark import spans


def read(obs):
    tl = spans.load(obs)
    loop = tl.thread_of("train_step") if tl is not None and "needed_flops" in obs else None
    if loop is None:
        return None
    return statistics.median(e - s for s, e, n in loop if n == "train_step") / 1e6
