"""Needed operations of the index scores (``index_heads`` products of ``index_head_dim`` a causal pair inside a document, every layer, once a traced step: ``benchmark/counts_keye.py``) a second of device time in the ``index_scores`` kernel, over the chip's bf16 peak. The kernel runs three times a layer (for the thresholds, for the mask, and for the mask again in the replay) and its products are half as deep as the MXU: both show here."""


def read(obs):
    from benchmark import counts_keye

    return counts_keye.kernel_roofline(obs, "index_scores", counts_keye.index_flops_forward)
