"""Device busy time under the module ``lm_head`` and the scope ``loss`` of a model with several prediction heads (one product to ``pred_heads x vocab`` float32 logits, eight log-softmaxes and masked means, their backward) over device busy time; None for a program with one head."""


def read(obs):
    from benchmark import counts_evabyte

    return counts_evabyte.scope_share(obs, ("lm_head", "loss"))
