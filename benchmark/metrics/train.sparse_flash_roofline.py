"""Operations of the attention over every **causal** pair inside a document (two products of the head's width a pair forward, four backward, every layer, the documents of the traced steps: ``benchmark/counts_keye.py``) a second of device time in the flash kernels (``flash_fwd``, ``flash_bwd``), over the chip's bf16 peak: how well the kernels run on the tiles they visit under the selection's mask. ``train.sparse_attn_roofline`` counts the selected pairs alone over the same time; the two differ by the selected share."""


def read(obs):
    from benchmark import counts_keye

    return counts_keye.kernel_roofline(obs, "flash_", counts_keye.flash_visited_flops)
