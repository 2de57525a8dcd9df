"""Device busy time under the attention modules (``attn``: latent attention's low-rank projections, inner norms, rope and output projection) outside the flash kernels, over device busy time."""

from benchmark import scopes


def read(obs):
    return scopes.share(obs, ("attn",), but_kernels=("flash_",))
