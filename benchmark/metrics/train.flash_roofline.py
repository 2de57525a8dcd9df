"""Needed operations of the causal pairs inside documents (two products of the head's width a pair forward, four backward, every attention layer, the documents of the traced steps: ``benchmark/counts_lfm2.py``) a second of device time in the flash kernels (``flash_fwd``, ``flash_dq``, ``flash_dkv``), over the chip's bf16 peak: the kernels' share of their roofline at the configuration's head width."""


def read(obs):
    from benchmark import counts_lfm2, traffic
    from benchmark.peaks import peaks_for

    tr, sizes, cell = obs.get("trace"), obs.get("sizes", {}), obs.get("cell")
    if tr is None or "layer_types" not in sizes or cell is None:
        return None
    t = sum(s for n, s in tr["device_ops"] if "flash_" in n)
    if not t:
        return None
    mix = cell.mix
    pool, rows = traffic.packed_pool(mix, cell.seed, sizes["vocab"], obs["chips"])
    per, k = len(pool[0]["tokens"]), int(mix["steps_per_chunk"])
    docs = [[n for row in rows[b * per:(b + 1) * per] for n in row] for b in range(len(pool))]
    traced = [i + j for i in obs["traced_steps"] for j in range(k)]
    needed = sum(counts_lfm2.flash_flops(sizes, docs[(int(mix["verify_steps"]) + i) % len(pool)]) for i in traced)
    peak = peaks_for(obs["device_kind"])["bf16_flops_per_s"]
    return needed / t / obs["chips"] / peak * 100.0
