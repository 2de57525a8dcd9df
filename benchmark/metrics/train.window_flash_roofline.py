"""Needed operations of the pairs inside window, document and causal order (two products of the head's width a pair forward, four backward, the sliding layers' 72 query heads, the documents of the traced steps: ``benchmark/counts_laguna.py``) a second of device time in the flash kernels under the sliding-window layers' ``attn`` modules, over the chip's bf16 peak."""


def read(obs):
    from benchmark import counts_laguna

    return counts_laguna.flash_roofline(obs, "sliding_attention", counts_laguna.window_flash_flops)
