"""Share of the window the step loop spent blocked on the prefetcher (the program's ``input_wait_ms`` gauge, summed)."""


def read(obs):
    if "input_wait_ms" not in obs:
        return None
    return obs["input_wait_ms"] / 1e3 / obs["window_s"] * 100.0
