"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read when the window closes and before the reference runs."""


def read(obs):
    if "needed_flops" not in obs:
        return None
    return obs["peak_bytes"] / 2**30
