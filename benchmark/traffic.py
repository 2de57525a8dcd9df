"""The traffic generators. A mix is a data file under ``benchmark/traffic/``;
its ``kind`` names the generator that reads it.

Every seed gets the same multiset of sizes (lengths are taken at the
distribution's quantiles, not drawn), in another order and with other token
values, so the amount of work in a run does not depend on the seed.
"""

from __future__ import annotations

import json
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a log-normal, clipped to [lo, hi]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(median * np.exp(sigma * z)), lo, hi).astype(np.int64)


def pack_first_fit(lengths, seq_len: int):
    """First-fit packing of documents (in the order given) into rows of
    ``seq_len``; no document is split. Returns a list of rows, each a list of
    document lengths."""
    rows, room = [], []
    for n in lengths:
        n = int(n)
        for i, r in enumerate(room):
            if r >= n:
                rows[i].append(n)
                room[i] -= n
                break
        else:
            rows.append([n])
            room.append(seq_len - n)
    return rows


def packed_rows(mix: dict):
    """The mix's fixed set of packed rows: documents at the quantiles of the
    length distribution, interleaved long and short in a fixed order, packed
    first-fit, and as many full rows kept as the pool holds."""
    d = mix["documents"]
    want = mix["pool_batches"] * mix["rows_per_chip"] * mix.get("chips", 1)
    n_docs = mix["documents_per_chip"] * mix.get("chips", 1)
    lens = lognormal_quantiles(n_docs, d["median"], d["sigma"], d["min"], d["max"])
    order = np.random.default_rng(mix["order_seed"]).permutation(n_docs)
    rows = pack_first_fit(lens[order], mix["seq_len"])
    if len(rows) < want:
        raise ValueError(f"{n_docs} documents pack into {len(rows)} rows, {want} wanted")
    return rows[:want]


def packed_pool(mix: dict, seed: int, vocab: int, chips: int = 1):
    """A fixed pool of packed batches: ``tokens``, ``positions`` (restarting in
    each document), ``segment_ids`` (1.. in each row, 0 for padding) and
    ``loss_mask`` (1 on real tokens). The seed orders the rows and draws the
    tokens; the rows themselves are the same for every seed."""
    mix = dict(mix, chips=chips)
    rows = packed_rows(mix)
    rng = np.random.default_rng(seed)
    rows = [rows[i] for i in rng.permutation(len(rows))]
    s, per = mix["seq_len"], mix["rows_per_chip"] * chips
    batches = []
    for b in range(mix["pool_batches"]):
        tok = rng.integers(1, vocab, size=(per, s), dtype=np.int32)
        pos = np.zeros((per, s), np.int32)
        seg = np.zeros((per, s), np.int32)
        for r, docs in enumerate(rows[b * per:(b + 1) * per]):
            at = 0
            for j, n in enumerate(docs):
                pos[r, at:at + n] = np.arange(n)
                seg[r, at:at + n] = j + 1
                at += n
            tok[r, at:] = 0
        batches.append({
            "tokens": tok, "positions": pos, "segment_ids": seg,
            "loss_mask": (seg > 0).astype(np.int32),
        })
    return batches, rows
