"""What ``kda.scan`` needs over what it took: the larger of its needed operations over the chip's bf16 peak and its needed bytes over the HBM bandwidth (the chunked delta rule at the configuration's chunk, forward and backward, every KDA layer, every position of the traced steps, from shapes alone: ``benchmark/counts_ling.py``), over the device time under the scope ``kda.scan``. It reads the scope and no kernel's name, so whatever implements the scope is held to the same work."""

from benchmark import scopes


def read(obs):
    from benchmark import counts_ling
    from benchmark.peaks import peaks_for

    t, _tl = scopes.time_ns(obs, ("kda.scan",))
    sizes, cell = obs.get("sizes", {}), obs.get("cell")
    if not t or "kda_chunk" not in sizes or cell is None:
        return None
    mix = cell.mix
    steps = len(obs["traced_steps"]) * int(mix["steps_per_chunk"])
    positions = steps * int(mix["rows_per_chip"]) * int(mix["seq_len"])
    peaks = peaks_for(obs["device_kind"])
    needed_s = max(
        counts_ling.kda_scan_flops(sizes, positions) / peaks["bf16_flops_per_s"],
        counts_ling.kda_scan_bytes(sizes, positions) / peaks["hbm_bytes_per_s"],
    )
    return needed_s / (t * 1e-9) * 100.0
