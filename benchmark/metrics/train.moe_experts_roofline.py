"""Needed operations of the routed experts (forward and backward of three products of d_model x moe_d_ff for every slot on a held expert that the traced steps counted; recomputation and buffer rows that hold no slot do not count) a second of device time under ``moe.experts``, over the chip's bf16 peak."""

from benchmark import counts_mla_moe, scopes


def read(obs):
    from benchmark.peaks import peaks_for

    t, _tl = scopes.time_ns(obs, ("moe.experts",))
    if not t or not obs.get("traced_slots"):
        return None
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return counts_mla_moe.experts_flops(obs["sizes"], obs["traced_slots"]) / (t * 1e-9) / peak * 100.0
