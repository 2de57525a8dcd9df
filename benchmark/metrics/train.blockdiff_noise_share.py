"""Device busy time under ``diffusion.noise`` (the step's draw, ``[MASK]``, the two streams' assembly, a layer's bounds and pair counts) and ``diffusion.merge`` (the noised queries' own block in plain XLA and the join with the flash call on the clean keys by their log-sum-exp, forward and backward) over device busy time: what the form costs beside its kernels."""


def read(obs):
    from benchmark import counts_sdar

    return counts_sdar.scope_share(obs, ("diffusion.noise", "diffusion.merge"))
