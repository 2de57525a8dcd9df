"""Needed operations of the attention over the **selected** pairs (two products of the head's width a pair forward, four backward, every layer, the documents of the traced steps: ``benchmark/counts_keye.py``) a second of device time in the flash kernels (``flash_fwd``, ``flash_bwd``), over the chip's bf16 peak: how far the masked kernels, which visit every causal tile, are from an attention that touches the selected keys only."""


def read(obs):
    from benchmark import counts_keye

    return counts_keye.kernel_roofline(obs, "flash_", counts_keye.flash_flops)
