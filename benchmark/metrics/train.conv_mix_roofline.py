"""Bytes that the short convolutions' gates and taps need to move (forward: read ``[T, 3d]``, write ``[T, d]``; backward: read ``[T, 3d]`` and ``[T, d]``, write ``[T, 3d]``; bfloat16, every conv layer, every position of the traced steps; a replayed forward does not count: ``benchmark/counts_lfm2.py``) a second of device time under ``conv.mix``, over the chip's HBM bandwidth."""

from benchmark import scopes


def read(obs):
    from benchmark import counts_lfm2
    from benchmark.peaks import peaks_for

    t, _tl = scopes.time_ns(obs, ("conv.mix",))
    sizes, cell = obs.get("sizes", {}), obs.get("cell")
    if not t or "layer_types" not in sizes or cell is None:
        return None
    mix = cell.mix
    steps = len(obs["traced_steps"]) * int(mix["steps_per_chunk"])
    positions = steps * int(mix["rows_per_chip"]) * int(mix["seq_len"])
    peak = peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    return counts_lfm2.conv_mix_bytes(sizes, positions) / (t * 1e-9) / peak * 100.0
