"""Needed operations of the pairs the block-wise mask keeps (clean on clean, noised on clean, noised on its own block: two products of the head's width a pair forward, four backward, 32 heads, every layer, the documents of the traced steps: ``benchmark/counts_sdar.py``) a second of device time in the flash kernels (``flash_fwd``, ``flash_bwd``, two calls a layer under their causal bound a query) under the layers' ``attn`` modules, over the chip's bf16 peak."""


def read(obs):
    from benchmark import counts_sdar

    return counts_sdar.flash_roofline(obs)
