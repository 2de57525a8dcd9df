"""Traces of the train step plus lowerings of any program inside the measured window. Must read 0."""


def read(obs):
    if "needed_flops" not in obs:
        return None
    return obs["train_step_traces_in_window"] + obs["lowerings_in_window"]
