"""Device time of the flash kernels (``flash_fwd``, ``flash_dq``, ``flash_dkv``) over device busy time, from the trace."""


def read(obs):
    tr = obs.get("trace")
    if tr is None or "needed_flops" not in obs:
        return None
    t = sum(s for n, s in tr["device_ops"] if "flash_" in n)
    return t / tr["busy_s"] * 100.0
