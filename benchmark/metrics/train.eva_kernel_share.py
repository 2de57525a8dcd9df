"""Device time of the flash kernels (``flash_fwd``, ``flash_bwd``) under the scopes ``eva.local`` (the windows folded into rows) and ``eva.remote`` (the summaries under their selection) over device busy time: what the EVA layer's attention calls cost; ``train.attn_kernel_share`` reads the same kernels by name alone."""


def read(obs):
    from benchmark import counts_evabyte

    return counts_evabyte.scope_share(obs, ("eva.local", "eva.remote"), kernels_only=True)
