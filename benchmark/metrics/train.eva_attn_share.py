"""Device busy time under the EVA layers' ``attn`` modules (every layer of this model: the four projections, the rotary embedding, the summaries, the two flash calls and their merge; forward, replay and backward) over device busy time; None for a program of another architecture."""


def read(obs):
    from benchmark import counts_evabyte

    return counts_evabyte.scope_share(obs, ("attn",))
