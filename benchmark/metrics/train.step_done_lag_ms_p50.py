"""Median, over the traced steps, of the end of the step's ``train.step_wait`` annotation (``fit-steps`` thread, host plane) less the end of its run on the device's ``XLA Modules`` line, on the trace's clock: how late the host learns of a step's end, the error of ``train.step_device``."""

import statistics

from benchmark import step_records


def read(obs):
    lags = step_records.traced_lags_ms(obs)
    return statistics.median(lags) if lags else None
