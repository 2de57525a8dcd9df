"""Needed operations of the causal pairs inside documents (two products of the head's width a pair forward, four backward, the full-attention layers' 48 query heads, the documents of the traced steps: ``benchmark/counts_laguna.py``) a second of device time in the flash kernels under the full-attention layers' ``attn`` modules (``dense_0``, the period's last), over the chip's bf16 peak."""


def read(obs):
    from benchmark import counts_laguna

    return counts_laguna.flash_roofline(obs, "full_attention", counts_laguna.full_flash_flops)
