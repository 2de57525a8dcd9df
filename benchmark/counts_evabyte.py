"""Operations and bytes that a dense decoder of EVA attention layers needs
under a head of several next-byte predictions: from shapes and the documents
as they lie in their packed rows. And where the device time of the layer's
parts lies in a trace: its named scopes.

``cfg`` is the reference's sizes (``sizes`` of ``evabyte.reference.py``). A
query at row index ``t``, position ``p`` of a document that starts at row
index ``t - p``, needs ``min(p, t mod W) + 1`` exact keys (its window's, up to
itself, inside its document) and one summary for every chunk from the first
that ends inside its document to the last before its window:
``max(0, (t // W) W / C - (t - p) // C)``. What the kernels visit beyond that
(a tile's masked pairs, a block of summaries a window does not need) does not
count, nor does a replayed forward.
"""

from __future__ import annotations

import numpy as np

from benchmark.counts_keye import traced_documents  # noqa: F401  (the same pool, cycled the same way)


def rows_of(doc_lengths, seq_len: int) -> list:
    """The documents of a step row by row: ``traffic.packed_pool`` lists a
    batch's documents in the order they lie, and first-fit put a document
    into the earliest row with room, so a row ends where the next document
    would not fit."""
    rows, room = [[]], seq_len
    for n in doc_lengths:
        n = int(n)
        if n > room:
            rows.append([])
            room = seq_len
        rows[-1].append(n)
        room -= n
    return rows


def entries(doc_lengths, cfg: dict) -> tuple:
    """``(summaries, exact keys)`` that the real queries of these documents
    see, one attention layer, the documents packed from each row's start."""
    w, c = cfg["window"], cfg["chunk"]
    remote = local = 0
    for row in rows_of(doc_lengths, cfg["max_positions"]):
        start = 0
        for n in row:
            t = start + np.arange(n, dtype=np.int64)
            local += int((np.minimum(t - start, t % w) + 1).sum())
            remote += int(np.maximum(t // w * (w // c) - start // c, 0).sum())
            start += n
    return remote, local


def matmul_params_per_token(cfg: dict) -> int:
    """Weights that every position's forward pass multiplies with: each
    layer's four attention products and SwiGLU, and the head's ``pred_heads``
    blocks. The embedding's lookup is no product."""
    d = cfg["d_model"]
    layer = 4 * d * cfg["n_heads"] * cfg["head_dim"] + 3 * d * cfg["d_ff"]
    return cfg["n_layers"] * layer + d * cfg["pred_heads"] * cfg["vocab"]


def attend_flops_forward(cfg: dict, doc_lengths) -> int:
    """Two products of ``head_dim`` a head and entry seen (exact key or
    summary), 2 operations a multiply-add, every layer."""
    return 2 * 2 * cfg["head_dim"] * cfg["n_heads"] * cfg["n_layers"] * sum(entries(doc_lengths, cfg))


def prep_flops_forward(cfg: dict, tokens: int) -> int:
    """A chunk's summaries: a position's key against ``phi`` and its share of
    the two weighted sums, ``head_dim`` multiply-adds each, every head and layer."""
    return 2 * 3 * cfg["head_dim"] * cfg["n_heads"] * cfg["n_layers"] * tokens


def train_flops(cfg: dict, doc_lengths) -> int:
    """Needed operations of forward and backward over these documents (all
    layers together): the backward pass costs twice the forward."""
    tokens = sum(int(n) for n in doc_lengths)
    return 3 * (
        2 * matmul_params_per_token(cfg) * tokens
        + attend_flops_forward(cfg, doc_lengths) + prep_flops_forward(cfg, tokens)
    )


def attend_flops(cfg: dict, doc_lengths) -> int:
    """Forward and backward operations of the entries seen alone
    (``train.eva_attend_roofline``): the backward's four products an entry
    against the forward's two."""
    return 3 * attend_flops_forward(cfg, doc_lengths)


def prep_bytes(cfg: dict, positions: int) -> int:
    """Bytes the summaries need to move, bfloat16, every layer and position of
    a row (``train.eva_prep_roofline``): forward, k and v read and a summary
    key and value a chunk written; the same once more in a recomputed layer's
    replay; backward, k and v and the summaries' two cotangents read, k's and
    v's written."""
    wide = cfg["n_heads"] * cfg["head_dim"] * 2  # one of k, v, a position, in bytes
    per_position = 2 * (2 * wide + 2 * wide / cfg["chunk"]) + (2 * wide + 2 * wide / cfg["chunk"] + 2 * wide)
    return int(cfg["n_layers"] * positions * per_position)


# ------------------------------------------------------------- from a trace


def scope_time_ns(obs, words, kernels_only: bool = False):
    """Busy nanoseconds of the traced window under any of the scopes
    ``words``, a mean over the devices (``scopes.time_ns``); with
    ``kernels_only`` of the flash kernels there alone. ``(None, None)`` without
    a trace, without this architecture's sizes or without such a scope in the
    trace (a program before the layer existed), else ``(time, the timeline)``."""
    from benchmark import scopes

    if "pred_heads" not in obs.get("sizes", {}):
        return None, None
    t, tl = scopes.time_ns(obs, words)
    if t is None or not kernels_only:
        return t, tl
    return t - scopes.time_ns(obs, words, but_kernels=("flash_",))[0], tl


def scope_share(obs, words, kernels_only: bool = False):
    """The same over device busy time, in percent."""
    t, tl = scope_time_ns(obs, words, kernels_only)
    return None if t is None else t / tl.busy * 100.0


def attend_roofline(obs):
    """Needed operations of the entries the traced steps' queries see a
    second of device time in the flash kernels under ``eva.local`` and
    ``eva.remote``, over the chip's bf16 peak, in percent."""
    from benchmark.peaks import peaks_for

    t, _tl = scope_time_ns(obs, ("eva.local", "eva.remote"), kernels_only=True)
    if not t or obs.get("cell") is None:
        return None
    ops = sum(attend_flops(obs["sizes"], docs) for docs in traced_documents(obs))
    return ops / (t * 1e-9) / obs["chips"] / peaks_for(obs["device_kind"])["bf16_flops_per_s"] * 100.0


def prep_roofline(obs):
    """Needed bytes of the traced steps' summaries a second of device time
    under ``eva.prep``, over the chip's HBM bandwidth, in percent."""
    from benchmark.peaks import peaks_for

    t, _tl = scope_time_ns(obs, ("eva.prep",))
    cell = obs.get("cell")
    if not t or cell is None:
        return None
    mix = cell.mix
    steps = len(obs["traced_steps"]) * int(mix["steps_per_chunk"])
    positions = steps * int(mix["rows_per_chip"]) * int(mix["seq_len"])
    peak = peaks_for(obs["device_kind"])["hbm_bytes_per_s"]
    return prep_bytes(obs["sizes"], positions) / (t * 1e-9) / obs["chips"] / peak * 100.0
