"""Device time under named scopes of the program, for the readers of a model
whose parts ``spans.TRAIN_GROUPS`` does not tell apart (latent attention, the
expert share layer, the multi-token-prediction module). A scope counts where
it is any component of an operation's ``op_name`` (a flax module name or a
``jax.named_scope``), not only the innermost: the MTP module's attention is
both under ``mtp`` and under ``attn``. Against a program or a trace without
the scope the readers find nothing and return ``None``."""

from __future__ import annotations

from benchmark import spans


def under(op_name, words) -> bool:
    if not op_name:
        return False
    *scopes, _primitive = op_name.rstrip(":").split("/")
    return any(w in words for part in scopes for w in spans._WORD.findall(part))


def time_ns(obs, words, but_kernels=()):
    """Busy nanoseconds of the traced window under any of ``words``, a mean
    over the devices, leaving out events whose name holds one of
    ``but_kernels``; ``(None, None)`` without a trace or without the scope,
    else ``(time, the timeline)``."""
    tl = spans.load(obs)
    if tl is None or "needed_flops" not in obs:
        return None, None
    words = frozenset(words)
    total = found = 0
    for ops in tl.ops:
        for start, end, name, op_name in ops:
            if under(op_name, words):
                found += 1
                if not any(k in name for k in but_kernels):
                    total += end - start
    if not found:
        return None, None
    return total / len(tl.ops), tl


def share(obs, words, but_kernels=()):
    """The same over device busy time, in percent."""
    t, tl = time_ns(obs, words, but_kernels)
    return None if t is None else t / tl.busy * 100.0


def counter_values(obs, name):
    """The readings of one step counter, one a chunk of the window."""
    return [c[name] for c in obs.get("counters", []) if name in c]
