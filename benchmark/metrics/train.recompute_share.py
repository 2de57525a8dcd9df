"""Device busy time of recomputed operations (``rematted_computation`` in ``op_name``: the forward ``jax.checkpoint`` replays in the backward pass) over device busy time."""

from benchmark import spans


def read(obs):
    tl = spans.load(obs)
    if tl is None or "needed_flops" not in obs:
        return None
    if not any(op_name for ops in tl.ops for _s, _e, _n, op_name in ops):
        return None
    return tl.remat_time() / tl.busy * 100.0
