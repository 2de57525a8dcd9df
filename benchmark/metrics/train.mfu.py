"""Needed forward and backward operations of the real tokens trained (attention inside documents in, idle experts, padding and recomputation out) a second a chip, over the chip's bf16 peak. Taken over the chunks of the window that the profiler was not tracing."""


def read(obs):
    from benchmark.peaks import peaks_for

    if "needed_flops" not in obs:
        return None
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return obs["needed_flops"] / obs["untraced_s"] / obs["chips"] / peak * 100.0
