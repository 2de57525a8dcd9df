"""1 - the union of device operation intervals over the traced window (training cells)."""


def read(obs):
    tr = obs.get("trace")
    if tr is None or "needed_flops" not in obs:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
