"""Device busy time under the sliding-window layers' ``attn`` modules (``layers/layer_<j>/layer/attn`` of the layers whose kind is ``sliding_attention``: the projections, the head norms, the rotary embedding, the gate a head and the flash kernels; forward, replay and backward) over device busy time."""


def read(obs):
    from benchmark import counts_laguna

    return counts_laguna.attn_share(obs, "sliding_attention")
