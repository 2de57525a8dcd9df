"""Device busy time under the layers' ``attn`` modules of a model that trains by diffusion over blocks (both streams: the four projections over ``2L`` positions, the head norms, the rotary embedding, the bounds, the two flash calls, the noised queries' own block and the merge; forward, replay and backward) over device busy time; None for a program of another architecture."""


def read(obs):
    from benchmark import counts_sdar

    return counts_sdar.scope_share(obs, ("attn",))
