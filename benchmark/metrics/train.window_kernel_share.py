"""Device time of the flash kernels (``flash_fwd``, ``flash_bwd``) under the sliding-window layers' ``attn`` modules over device busy time: what the windowed calls cost, beside ``train.attn_kernel_share``, which holds the full layers' calls too."""


def read(obs):
    from benchmark import counts_laguna

    return counts_laguna.attn_share(obs, "sliding_attention", kernels_only=True)
