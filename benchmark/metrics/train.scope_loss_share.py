"""Device busy time under the ``loss`` scope, the final norm and the head (float32 logits, log-softmax, their backward) over device busy time, from the trace's ``op_name``s."""

from benchmark import spans


def read(obs):
    return spans.train_scope_share(obs, "loss")
