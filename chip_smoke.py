#!/usr/bin/env python3
"""Chip smoke: does the program still start on the TPU?

Drives the system's three main paths once, in ONE process, on whatever TPU
devices JAX finds (one chip, or the four of a host), through the entry points
a user calls, at the widths of ``DecoderConfig.llama3_8b()`` with the depth
(and, on one chip, the vocabulary) cut to fit and seeded random weights:

1. kernels  ops/flash.py forward+backward, compiled (``interpret=False``),
            plain and packed, against the float32 blockwise reference; then
            ops/sparse_select.py's ``index_scores`` against float32 products,
            the flash pair under the mask of an exact selection, and the
            ``index_loss`` kernel against the loss written whole in float32;
2. trainer  ``experiment.lagom(train_fn, DistributedConfig(...))`` with the
            README's train_fn (``ctx.trainer`` -> ``make_state`` -> ``fit``);
3. server   the stack ``python -m maggy_tpu.serve`` builds, answering
            staggered requests through ``ServeClient`` over the socket,
            greedy tokens checked against ``generate_cached``;
4. hpo      ``lagom(train_fn, HyperparameterOptConfig(devices_per_trial=1))``
            with a real train_fn: every trial's state on its leased chip.

Any phase that raises fails the run; there is no fallback to the CPU. The last
line of stdout is the verdict, one JSON object with exactly the keys ``ok`` and
``device`` (``platform``, ``kind``, ``count``, as JAX reports them). The line
before it, ``summary: {...}``, says what ran and each phase's set-up (compile)
time; the same object is written to ``chiprun_out/chip_smoke/summary.json``
beside the logs.

    python chip_smoke.py                  # on a machine with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse-on-cpu   # toy sizes

``--rehearse-on-cpu`` is the one way to run without a TPU: toy widths, Pallas
interpreted, every output line labelled as a rehearsal. It checks the script's
control flow before chip time is spent and says nothing about the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.metadata
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
PHASES = ("kernels", "trainer", "server", "hpo")

# llama3_8b shapes (models/transformer.py): parameters of one layer, and of
# the embedding plus the untied head per vocabulary row
LAYER_PARAMS = 218.1e6
PARAMS_PER_VOCAB_ROW = 2 * 4096


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the CPU rehearsal."""

    train_cfg: dict  # overrides on DecoderConfig.llama3_8b() for training
    serve_cfg: dict  # ... and for the served model
    kernel_heads: tuple  # (q heads, kv heads, head_dim)
    kernel_cases: tuple  # (batch, seq) pairs
    index_heads: tuple  # (index heads, their width) of the selected-key case
    loss_case: tuple  # (seq, kv heads) of the indexer's loss: a row twice the keys a query keeps
    select_cases: tuple  # (seq, keys a query) at which one block of scores is held against two passes
    window_cases: tuple  # (seq, window, head_dim, (q heads, ...) over kv heads) of the windowed flash kernels
    train_seq: int
    train_steps: int
    prompt_lens: tuple
    max_new: int
    hpo_cfg: dict  # DecoderConfig fields of the HPO trials' model
    hpo_seq: int


def chip_sizes(n_chips: int, bytes_limit: int) -> Sizes:
    """llama3_8b widths cut to the chips found. Training holds 16 B per
    parameter (fp32 params, grads, Adam), so the budget is in parameters:
    70% of device memory, the rest left to activations and the fp32 logits.
    Checked against XLA's own memory analysis of the compiled step: 11.1 GiB
    peak on one chip (2 layers, quarter vocabulary), 9.6 GiB a chip on four
    (4 layers, full vocabulary) (AOT compile for v5e, PR 21)."""
    budget = 0.70 * bytes_limit * n_chips / 16
    vocab = 128_256
    if vocab * PARAMS_PER_VOCAB_ROW > budget / 2:
        vocab //= 4  # the vocabulary's four-way share: 32,064 rows
    layers = int((budget - vocab * PARAMS_PER_VOCAB_ROW) // LAYER_PARAMS)
    layers = max(1, min(4, layers))
    return Sizes(
        train_cfg={"n_layers": layers, "vocab_size": vocab},
        # serving holds fp32 params only (4.2 GB + 0.87 GB a layer at the full
        # vocabulary): two layers leave most of one chip to the KV pool
        serve_cfg={"n_layers": 2},
        kernel_heads=(32, 8, 128),
        kernel_cases=((2, 2048), (1, 8192)),
        index_heads=(16, 64),
        loss_case=(4096, 4),
        select_cases=((4096, 2048), (32768, 2048)),  # the second is the keye-vl-2.0-30b-a3b cell's row
        window_cases=((8192, 512, 128, (72, 48), 8),),  # the laguna-s-2.1 cell's row and both its head counts
        train_seq=2048,
        train_steps=6,
        prompt_lens=(6, 7, 24, 30, 100, 120, 400, 500),
        max_new=8,
        hpo_cfg=dict(
            vocab_size=8192, d_model=512, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=1408, max_seq_len=512,
        ),
        hpo_seq=512,
    )


def toy_sizes() -> Sizes:
    toy = dict(
        n_layers=2, vocab_size=512, d_model=256, n_heads=8, n_kv_heads=4,
        d_ff=512, max_seq_len=256,
    )
    return Sizes(
        train_cfg=toy,
        serve_cfg=toy,
        kernel_heads=(4, 2, 128),
        kernel_cases=((2, 256),),
        index_heads=(4, 16),
        loss_case=(256, 2),
        select_cases=((256, 64),),
        window_cases=((256, 40, 128, (3, 2), 1),),
        train_seq=128,
        train_steps=4,
        prompt_lens=(3, 5, 9, 12, 20, 26, 40, 50),
        max_new=4,
        hpo_cfg=dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
            d_ff=128, max_seq_len=64,
        ),
        hpo_seq=32,
    )


class Run:
    """One smoke run: the devices, the sizes and the labelled printer."""

    def __init__(self, rehearsal: bool):
        import jax

        self.rehearsal = rehearsal
        self.tag = "[REHEARSAL on cpu, not a device result] " if rehearsal else ""
        self.devices = jax.devices()
        self.n = len(self.devices)
        stats = self.devices[0].memory_stats() or {}
        self.bytes_limit = int(stats.get("bytes_limit", 0))
        self.sizes = (
            toy_sizes() if rehearsal else chip_sizes(self.n, self.bytes_limit)
        )

    def say(self, msg: str) -> None:
        for line in str(msg).splitlines() or [""]:
            print(f"{self.tag}{line}", flush=True)

    def memory(self) -> str:
        """Device 0's allocator view, as information."""
        s = self.devices[0].memory_stats()
        if not s:
            return "memory_stats: none on this backend"
        gib = 2.0**30
        return (
            f"in_use {s['bytes_in_use'] / gib:.2f} GiB, peak "
            f"{s['peak_bytes_in_use'] / gib:.2f} GiB of {s['bytes_limit'] / gib:.2f} GiB"
        )


def check(cond: bool, what: str) -> None:
    """An assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(what)


# --------------------------------------------------------------------- kernels


def phase_kernels(run: Run) -> dict:
    """ops/flash.py forward and backward, compiled by Mosaic, plain and with
    packed-document segment ids, against ``blockwise_attention`` in float32
    under ``highest`` matmul precision; then under a selection, and under a
    sliding window against an explicit mask."""
    import jax
    import jax.numpy as jnp

    from maggy_tpu.ops.attention import blockwise_attention
    from maggy_tpu.ops.flash import BACKWARD_KERNELS, backward_form, flash_attention

    # Tolerance: relative Frobenius error against the float32 reference.
    # bf16 carries 8 mantissa bits (2^-8 = 0.4% per rounding); the kernels
    # round P and dS to bf16 before the MXU and their outputs to bf16, so
    # about 1% is the dtype's own error and 2% leaves room for the chain. A
    # wrong mask, tile or GQA group is an O(1) error, far outside it.
    tol = 2e-2
    h, kh, d = run.sizes.kernel_heads
    interpret = run.rehearsal  # Pallas is interpreted only in the rehearsal
    f32 = jnp.float32

    # everything around the kernels is jitted too: on a TPU every eager op is
    # a compile of its own, and a hundred of them cost more than the kernels
    @functools.partial(jax.jit, static_argnames=("b", "s"))
    def make_inputs(b, s):
        keys = jax.random.split(jax.random.key(s), 4)
        q = jax.random.normal(keys[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, s, kh, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, s, kh, d), jnp.bfloat16)
        w = jax.random.normal(keys[3], (b, s, h, d), f32)
        # three packed documents a row, boundaries off the tile grid
        pos = jnp.arange(s)
        segs = (pos >= s // 3 + 5).astype(jnp.int32) + (pos >= (2 * s) // 3 - 7)
        return q, k, v, w, jnp.broadcast_to(segs, (b, s))

    def fwd_bwd(attn, q, k, v, w, seg):
        def loss(q, k, v):
            o = attn(q, k, v, causal=True, segment_ids=seg)
            return (o.astype(f32) * w).sum(), o

        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v
        )
        return (o, *grads)

    def flash(q, k, v, **kw):
        return flash_attention(q, k, v, interpret=interpret, **kw)

    flash_step = jax.jit(lambda q, k, v, w, seg: fwd_bwd(flash, q, k, v, w, seg))
    reference = jax.jit(
        lambda q, k, v, w, seg: fwd_bwd(
            blockwise_attention, q.astype(f32), k.astype(f32), v.astype(f32), w, seg
        )
    )

    @jax.jit
    def compare(got, want):
        got = [a.astype(f32) for a in got]
        return (
            [jnp.linalg.norm(a - r) / jnp.linalg.norm(r) for a, r in zip(got, want)],
            jnp.stack([jnp.isfinite(a).all() for a in got]).all(),
        )

    setup_s = 0.0
    cases = []
    for b, s in run.sizes.kernel_cases:
        q, k, v, w, segs = make_inputs(b, s)
        for seg in (None, segs):
            label = f"B={b} S={s} {'packed' if seg is not None else 'plain'}"
            t0 = time.perf_counter()
            lowered = flash_step.lower(q, k, v, w, seg)
            if not interpret:
                # the program about to run holds the Mosaic kernels, the
                # backward in the form this row and width take: it is neither
                # interpreted nor the blockwise fallback (which, with
                # interpret=False, would have raised instead)
                text = lowered.as_text()
                for name in ("flash_fwd", *BACKWARD_KERNELS[backward_form(s, d)]):
                    check(name in text, f"kernel {name} missing from the lowered step")
            compiled = lowered.compile()
            setup_s += time.perf_counter() - t0
            got = compiled(q, k, v, w, seg)
            with jax.default_matmul_precision("highest"):
                want = reference(q, k, v, w, seg)
            rel, finite = compare(got, want)
            check(bool(finite), f"flash {label}: a value is not finite")
            errs = dict(zip(("out", "dq", "dk", "dv"), (float(e) for e in rel)))
            run.say(
                f"  flash {label}: rel err "
                + " ".join(f"{n}={e:.2e}" for n, e in errs.items())
            )
            check(
                max(errs.values()) <= tol,
                f"flash {label} off the float32 reference: {errs} > {tol}",
            )
            cases.append({"case": label, **errs})

    # attention over selected keys (ops/sparse_select.py): the index scores'
    # kernel against float32 products of the same bfloat16 numbers, the exact
    # selection of a quarter of the row's keys a query, and the flash pair
    # with the selection's tile as an operand against float32 attention under
    # the same mask, at the first case's size, packed
    from maggy_tpu.models.transformer import default_attention
    from maggy_tpu.ops import sparse_select

    b, s = run.sizes.kernel_cases[0]
    heads_i, width_i = run.sizes.index_heads
    topk = s // 4
    q, k, v, w, segs = make_inputs(b, s)

    @jax.jit
    def make_index():
        keys = jax.random.split(jax.random.key(7), 3)
        return (
            jax.random.normal(keys[0], (b, heads_i, s, width_i), jnp.bfloat16),
            jax.random.normal(keys[1], (b, s, width_i), jnp.bfloat16),
            jax.random.normal(keys[2], (b, s, heads_i), f32) * (heads_i * width_i) ** -0.5,
        )

    @jax.jit
    def selection(qi, ki, wi, segs):
        seg3 = segs[:, None]
        got = sparse_select.index_scores(qi, ki, wi, seg3, 0, s, interpret=interpret)
        z = jnp.einsum("bjqd,bsd->bqjs", qi.astype(f32), ki.astype(f32), precision="highest")
        want = (wi[..., None] * jnp.maximum(z, 0.0)).sum(2)
        seen = got > -jnp.inf
        at = jnp.arange(s)
        same = (seen == ((at[:, None] >= at[None]) & (segs[:, :, None] == segs[:, None, :]))).all()
        err = jnp.linalg.norm(jnp.where(seen, got - want, 0.0)) / jnp.linalg.norm(jnp.where(seen, want, 0.0))
        mask, counts, *_ = sparse_select.select(qi, ki, wi, seg3, topk, interpret=interpret)
        by_hand = jnp.minimum(seen.sum(-1), topk).sum()
        return mask, err, same, counts, by_hand

    t0 = time.perf_counter()
    mask, err, same, counts, by_hand = selection(*make_index(), segs)
    label = f"B={b} S={s} packed, {topk} keys a query of {heads_i} x {width_i} index heads"
    run.say(f"  index_scores {label}: rel err {float(err):.2e}; selected {int(counts[0])} of {int(counts[1])} pairs, "
            f"{int(counts[2])} queries off their count")
    check(bool(same), "index_scores: a pair outside a query's document or after it is not -inf, or one inside is")
    check(float(err) <= tol, f"index_scores off the float32 products: {float(err)} > {tol}")
    check(int(counts[2]) == 0 and int(counts[0]) == int(by_hand), "the selection is not min(topk, visible) keys a query")
    masked_step = jax.jit(lambda q, k, v, w, seg, m: fwd_bwd(functools.partial(flash, selected=m), q, k, v, w, seg))
    masked_reference = jax.jit(
        lambda q, k, v, w, seg, m: fwd_bwd(
            functools.partial(default_attention, selected=m), q.astype(f32), k.astype(f32), v.astype(f32), w, seg
        )
    )
    got = masked_step(q, k, v, w, segs, mask)
    setup_s += time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        want = masked_reference(q, k, v, w, segs, mask)
    rel, finite = compare(got, want)
    check(bool(finite), f"flash under a selection {label}: a value is not finite")
    errs = dict(zip(("out", "dq", "dk", "dv"), (float(e) for e in rel)))
    run.say(f"  flash under a selection {label}: rel err " + " ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    check(max(errs.values()) <= tol, f"flash under a selection off the float32 reference: {errs} > {tol}")
    cases.append({"case": "selected " + label, "index_scores": float(err), **errs})

    # the indexer's loss: the ``index_loss`` kernel (loss and the gradients of
    # the indexer's three inputs from one pass, the heads' log-sum-exp from the
    # flash kernel under the selection) against the loss written whole on
    # float32 ``[S, S]`` arrays and differentiated by jax, one row of twice the
    # keys a query keeps, the heads over ``loss_case``'s key heads
    s, kh_l = run.sizes.loss_case
    topk = s // 2

    @jax.jit
    def loss_inputs():
        keys = jax.random.split(jax.random.key(11), 5)
        return (
            jax.random.normal(keys[0], (1, s, h, d), jnp.bfloat16),
            jax.random.normal(keys[1], (1, s, kh_l, d), jnp.bfloat16),
            jax.random.normal(keys[2], (1, heads_i, s, width_i), jnp.bfloat16),
            jax.random.normal(keys[3], (1, s, width_i), jnp.bfloat16),
            jax.random.normal(keys[4], (1, s, heads_i), f32) * (heads_i * width_i) ** -0.5,
        )

    @jax.jit
    def loss_by_kernel(q, k, qi, ki, wi):
        mask, _counts, lse_i, _thresholds = sparse_select.select(qi, ki, wi, None, topk, interpret=interpret)
        _out, lse = flash(q, k, k, selected=mask, return_lse=True)
        real = jnp.ones((1, s), bool)
        loss = lambda qi, ki, wi: sparse_select.index_loss(qi, ki, wi, q, k, lse, mask, None, real, lse_i)
        return mask, jax.value_and_grad(loss, (0, 1, 2))(qi, ki, wi)

    @jax.jit
    def loss_whole(q, k, qi, ki, wi, mask):
        keep = mask != 0
        sc = jnp.einsum("bqhd,bshd->bhqs", q.astype(f32), jnp.repeat(k.astype(f32), h // kh_l, 2)) / d**0.5
        target = jnp.where(keep, jax.nn.softmax(jnp.where(keep[:, None], sc, -1e30), -1).mean(1), 0.0)

        def loss(qi, ki, wi):
            z = jnp.einsum("bjqd,bsd->bqjs", qi, ki)
            logq = jax.nn.log_softmax(jnp.where(keep, (wi[..., None] * jnp.maximum(z, 0.0)).sum(2), -1e30), -1)
            return jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-37)) - logq), 0.0).sum(-1).mean()

        return jax.value_and_grad(loss, (0, 1, 2))(qi.astype(f32), ki.astype(f32), wi)

    t0 = time.perf_counter()
    inputs = loss_inputs()
    mask, (loss, grads) = loss_by_kernel(*inputs)
    jax.block_until_ready(grads)
    setup_s += time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        loss_want, grads_want = loss_whole(*inputs, mask)
    rel, finite = compare((loss[None], *grads), (loss_want[None], *grads_want))
    errs = dict(zip(("loss", "d_qi", "d_ki", "d_w"), (float(e) for e in rel)))
    label = f"B=1 S={s}, {topk} keys a query, {h} heads over {kh_l} of {d}, {heads_i} x {width_i} index heads"
    run.say(f"  index_loss {label}: loss {float(loss):.6f}, rel err " + " ".join(f"{n}={e:.2e}" for n, e in errs.items()))
    check(bool(finite), f"index_loss {label}: a value is not finite")
    check(max(errs.values()) <= tol, f"index_loss off the float32 formula: {errs} > {tol}")
    cases.append({"case": "index_loss " + label, **errs})

    # the selection from one block of scores a block of queries (``select``)
    # against a pass for the thresholds and a pass for the mask, which is also
    # what the flash kernels' backward runs (``selection_mask``): equal bits
    @functools.partial(jax.jit, static_argnames=("s", "k"))
    def select_both_ways(s, k):
        keys = jax.random.split(jax.random.key(13), 3)
        qi = jax.random.normal(keys[0], (1, heads_i, s, width_i), jnp.bfloat16)
        ki = jax.random.normal(keys[1], (1, s, width_i), jnp.bfloat16)
        wi = jax.random.normal(keys[2], (1, s, heads_i), f32) * (heads_i * width_i) ** -0.5
        mask, counts, _lse, thresholds = sparse_select.select(qi, ki, wi, None, k, interpret=interpret)
        rows = sparse_select._query_block(s)

        def one(at):
            scores = sparse_select.index_scores(qi, ki, wi, None, at, rows, interpret=interpret)
            return sparse_select.topk_thresholds(scores, at, k, interpret=interpret)

        first = jnp.moveaxis(sparse_select._each_block(one, s), 0, 2).reshape(1, 3, s)
        again = sparse_select.selection_mask(qi, ki, wi, None, first, interpret=interpret)
        return (thresholds != first).sum(), (mask != again).sum(), (mask != 0).sum(dtype=jnp.int32) - counts[0], counts

    for s, k in run.sizes.select_cases:
        t0 = time.perf_counter()
        off_thresholds, off_mask, off_count, counts = jax.block_until_ready(select_both_ways(s, k))
        setup_s += time.perf_counter() - t0
        label = f"B=1 S={s}, {k} keys a query"
        run.say(f"  select {label}: selected {int(counts[0])} of {int(counts[1])} pairs; against two passes "
                f"{int(off_thresholds)} thresholds and {int(off_mask)} bytes of the mask differ")
        check(int(off_thresholds) == int(off_mask) == int(off_count) == int(counts[2]) == 0,
              f"select {label}: one block of scores and two passes disagree")
        cases.append({"case": "select " + label, "selected": int(counts[0]), "visible": int(counts[1])})
    # the flash kernels under a sliding window (``flash_attention(window=)``:
    # a second bound of the visit table, a mask inside the tiles it leaves)
    # against float32 attention under an explicit [S, S] mask a block of
    # queries: forward, log-sum-exp, dq, dk, dv, on a packed row (a document
    # shorter than the window among them) and on one document
    def windowed_by_hand(q, k, v, w, seg, window):
        b, s, hq, dw = q.shape
        kv = k.shape[2]
        rows = min(s, 512)
        at, qf = jnp.arange(s), q.astype(f32).reshape(b, s, kv, hq // kv, dw)

        def whole(qf, kf, vf):
            @jax.checkpoint
            def one(first):
                mine = first + jnp.arange(rows)
                ahead = mine[:, None] - at[None, :]
                vis = (ahead >= 0) & (ahead < window)
                vis = vis[None] & (jax.lax.dynamic_slice_in_dim(seg, first, rows, 1)[:, :, None] == seg[:, None, :])
                sc = jnp.einsum("bqkgd,bskd->bkgqs", jax.lax.dynamic_slice_in_dim(qf, first, rows, 1), kf) / dw**0.5
                sc = jnp.where(vis[:, None, None], sc, -jnp.inf)
                lse = jax.nn.logsumexp(sc, axis=-1)
                return jnp.einsum("bkgqs,bskd->bqkgd", jnp.exp(sc - lse[..., None]), vf), lse

            o, lse = jax.lax.map(one, jnp.arange(s // rows) * rows)
            o = jnp.moveaxis(o, 0, 1).reshape(b, s, hq, dw)
            return (o * w).sum(), (o, jnp.moveaxis(lse, 0, 3).reshape(b, hq, s))

        (_, (o, lse)), grads = jax.value_and_grad(whole, argnums=(0, 1, 2), has_aux=True)(qf, k.astype(f32), v.astype(f32))
        return o, lse, grads[0].reshape(q.shape), grads[1], grads[2]

    def windowed_flash(q, k, v, w, seg, window):
        def loss(q, k, v):
            o, lse = flash(q, k, v, causal=True, segment_ids=seg, window=window, return_lse=True)
            return (o.astype(f32) * w).sum(), (o, lse)

        (_, (o, lse)), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o, lse, *grads)

    for s, window, dw, q_heads, kv in run.sizes.window_cases:
        for hq in q_heads:
            keys = jax.random.split(jax.random.key(s + hq), 4)
            q, k, v = (jax.random.normal(kk, (1, s, n, dw), jnp.bfloat16) for kk, n in zip(keys, (hq, kv, kv)))
            w = jax.random.normal(keys[3], (1, s, hq, dw), f32)
            pos = jnp.arange(s)
            # five documents, boundaries off the tile grid, the third shorter than the window; then one document
            cuts = (s // 5 + 3, s // 2 - 9, s // 2 - 9 + window // 2, (3 * s) // 4 + 11)
            packed_row = 1 + sum((pos >= c).astype(jnp.int32) for c in cuts)[None]
            for name, seg in (("packed", packed_row), ("one document", jnp.ones((1, s), jnp.int32))):
                label = f"B=1 S={s} window {window}, {hq}/{kv} heads of {dw}, {name}"
                t0 = time.perf_counter()
                got = jax.jit(windowed_flash, static_argnums=5)(q, k, v, w, seg, window)
                jax.block_until_ready(got)
                setup_s += time.perf_counter() - t0
                with jax.default_matmul_precision("highest"):
                    want = jax.jit(windowed_by_hand, static_argnums=5)(q, k, v, w, seg, window)
                rel, finite = compare(got, want)
                check(bool(finite), f"windowed flash {label}: a value is not finite")
                errs = dict(zip(("out", "lse", "dq", "dk", "dv"), (float(e) for e in rel)))
                run.say(f"  windowed flash {label}: rel err " + " ".join(f"{n}={e:.2e}" for n, e in errs.items()))
                check(max(errs.values()) <= tol, f"windowed flash {label} off the float32 reference: {errs} > {tol}")
                cases.append({"case": "windowed " + label, **errs})
    return {
        "setup_s": setup_s, "heads": [h, kh, d], "tolerance": tol, "cases": cases,
        "compiled": not interpret,
    }


# --------------------------------------------------------------------- trainer


def phase_trainer(run: Run) -> dict:
    """``lagom(train_fn, DistributedConfig(...))`` with the README's train_fn
    on an ``fsdp`` mesh over every chip found."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from maggy_tpu import experiment, telemetry
    from maggy_tpu.config import DistributedConfig
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train.data import synthetic_lm_batches

    sizes = run.sizes
    cfg = DecoderConfig.llama3_8b(**sizes.train_cfg)
    batch = run.n  # one row of train_seq tokens a chip
    run.say(
        f"  model: llama3_8b widths, cut to n_layers={cfg.n_layers} "
        f"vocab={cfg.vocab_size} (published: 32 layers, 128,256 rows); "
        f"batch {batch} x {sizes.train_seq} tokens, sharding=fsdp over {run.n}"
    )

    @jax.jit
    def probe(params):
        """Per leaf: 4096 values strided across it (a copy of the whole tree
        would not fit beside the optimizer state) and whether all is finite."""
        return jax.tree.map(
            lambda a: (
                a.reshape(-1)[:: max(1, a.size // 4096)][:4096],
                jnp.isfinite(a).all(),
            ),
            params,
        )

    def leaves(tree):
        return [(np.asarray(v), bool(ok)) for v, ok in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)
        )]

    def train_fn(model, dataset, hparams, reporter, ctx):
        trainer = ctx.trainer(model, optax.adamw(hparams["lr"]))
        state = trainer.make_state(jax.random.key(0), next(dataset))
        mesh_devices = set(ctx.mesh.devices.flat)
        placed = [
            leaf.sharding.device_set == mesh_devices
            for leaf in jax.tree.leaves(state.params)
        ]
        before = leaves(probe(state.params))
        state, metrics = trainer.fit(
            state, dataset, num_steps=sizes.train_steps, reporter=reporter,
            report_every=2, metric_sign=-1.0,
        )
        after = leaves(probe(state.params))
        gauges = telemetry.get().snapshot().get("gauges", {})
        # fit's float(metrics) has drained the device: the steps after the
        # first took the run's wall time less the first step's
        steady_ms = (
            sizes.train_steps / metrics["steps_per_sec"] * 1e3
            - gauges["compile_time_ms"]
        ) / (sizes.train_steps - 1)
        return {
            "metric": -metrics["loss"],
            "loss": metrics["loss"],
            "grad_norm": metrics["grad_norm"],
            "leaves": len(placed),
            "leaves_on_whole_mesh": sum(placed),
            "leaves_changed": sum(
                bool((a != b).any()) for (a, _), (b, _) in zip(after, before)
            ),
            "leaves_finite": sum(ok for _, ok in after),
            "train_step_compiles": trainer.compile_counts["train_step"],
            "first_step_ms": gauges["compile_time_ms"],
            "steady_step_ms": steady_ms,
            "fsdp": ctx.mesh.shape["fsdp"],
            "params": sum(x.size for x in jax.tree.leaves(state.params)),
        }

    t0 = time.perf_counter()
    result = experiment.lagom(
        train_fn,
        DistributedConfig(
            module=Decoder(cfg),
            dataset=synthetic_lm_batches(
                cfg.vocab_size, batch, sizes.train_seq, seed=0
            ),
            hparams={"lr": 3e-4},
            sharding="fsdp",
            name="chip_smoke_train",
        ),
    )
    wall = time.perf_counter() - t0
    out = result  # the driver's mean of each numeric output over its workers
    run.say(f"  lagom result: {json.dumps(out, default=str)}")
    check(out["loss"] == out["loss"] and abs(out["loss"]) < 1e4, f"loss {out['loss']}")
    check(out["leaves_finite"] == out["leaves"], "a parameter leaf is not finite")
    check(out["leaves_changed"] == out["leaves"], "a parameter leaf did not change")
    check(
        out["leaves_on_whole_mesh"] == out["leaves"],
        "a parameter leaf does not span the mesh's devices",
    )
    check(out["fsdp"] == run.n, f"fsdp axis {out['fsdp']} over {run.n} devices")
    check(
        out["train_step_compiles"] == 1,
        f"train step traced {out['train_step_compiles']} times, expected once",
    )
    kernels = attention_events(experiment)
    run.say(f"  attention kernels chosen: {kernels}")
    # shape-only traces (eval_shape, outside the mesh) record the unsharded
    # kernel too; what must hold is that no trace left the flash path and
    # that the step compiled for a multi-chip mesh took the sharded one
    want = "xla_dense" if run.rehearsal else ("flash" if run.n == 1 else "flash_sharded")
    allowed = {want} if run.rehearsal else {"flash", want}
    check(
        want in kernels and set(kernels) <= allowed,
        f"auto_attention chose {kernels}, expected {want}",
    )
    run.say(
        f"  steady step {out['steady_step_ms']:.1f} ms after a "
        f"{out['first_step_ms'] / 1e3:.1f} s first step (information); {run.memory()}"
    )
    return {
        "setup_s": out["first_step_ms"] / 1e3,
        "wall_s": wall,
        "n_layers": cfg.n_layers,
        "vocab": cfg.vocab_size,
        "params": out["params"],
        "loss": out["loss"],
        "step_ms": out["steady_step_ms"],
        "kernel": want,
    }


def attention_events(experiment) -> list:
    """The ``attention.kernel`` events of the experiment that just ran, from
    its workers' telemetry JSONL."""
    from maggy_tpu.core.env import EnvSing
    from maggy_tpu.telemetry.export import load_records

    env = EnvSing.get_instance()
    exp_dir = env.experiment_dir(experiment.APP_ID, experiment.RUN_ID)
    return [
        rec["attrs"]["kernel"]
        for records in load_records(env, exp_dir).values()
        for rec in records
        if rec.get("name") == "attention.kernel"
    ]


# ---------------------------------------------------------------------- server


def phase_server(run: Run) -> dict:
    """The serve CLI's stack, built by its own ``build_server`` (main() only
    adds the signal handler and the wait), driven over the socket."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.models.generate import generate_cached
    from maggy_tpu.models.transformer import default_attention
    from maggy_tpu.serve import ServeClient
    from maggy_tpu.serve.__main__ import build_server, parse_args

    sizes = run.sizes
    # the CLI loads llama3_8b by name at its full depth; a cut depth goes in
    # through its other door, a JSON file of DecoderConfig fields
    base = DecoderConfig.llama3_8b(**sizes.serve_cfg)
    fields = {
        f: getattr(base, f)
        for f in (
            "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff",
            "rope_theta", "max_seq_len", "remat", "remat_policy",
        )
    }
    cfg_path = os.path.join(OUT_DIR, "serve_config.json")
    with open(cfg_path, "w") as f:
        json.dump(fields, f)
    mesh_arg = "tp" if run.n > 1 else "none"
    run.say(
        f"  python -m maggy_tpu.serve --config {os.path.relpath(cfg_path, HERE)} "
        f"--slots 4 --mesh {mesh_arg}  (llama3_8b widths, n_layers={fields['n_layers']}, "
        f"vocab={fields['vocab_size']}, fp32 params)"
    )
    t_build = time.perf_counter()
    server, (host, port), tel = build_server(parse_args([
        "--config", cfg_path, "--slots", "4", "--mesh", mesh_arg,
        "--host", "127.0.0.1", "--port", "0", "--seed", "0",
        "--exp-dir", os.path.join(OUT_DIR, "serve"),
    ]))
    build_s = time.perf_counter() - t_build
    try:
        engine = server.scheduler.engine
        cfg, params = engine.cfg, engine.params
        rng = np.random.default_rng(0)
        prompts = [
            rng.integers(1, cfg.vocab_size, n).tolist() for n in sizes.prompt_lens
        ]
        # the last prompt extends the one before it: a shared prefix for the
        # engine's prefix reuse to find while its source is resident
        prompts[-1] = prompts[-2] + prompts[-1][len(prompts[-2]):]
        results, errors = {}, []

        def drive(i, prompt):
            try:
                time.sleep(0.2 * i)  # staggered arrivals churn the four slots
                with ServeClient((host, port), server.secret) as client:
                    results[i] = client.generate(
                        prompt, max_new=sizes.max_new, timeout=900
                    )
            except BaseException as e:  # noqa: BLE001 - re-raised on the main thread
                errors.append(e)

        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=drive, args=(i, p), daemon=True)
            for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=1000)
        serve_wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        check(len(results) == len(prompts), f"{len(results)}/{len(prompts)} answered")
        with ServeClient((host, port), server.secret) as client:
            stats = client.stats()

        # every parameter and the KV pool spread over all chips found
        all_devices = set(run.devices)
        for name, tree in (("params", params), ("kv pool", engine.cache)):
            leaves = jax.tree.leaves(tree)
            spread = sum(leaf.sharding.device_set == all_devices for leaf in leaves)
            check(
                spread == len(leaves),
                f"{spread}/{len(leaves)} {name} leaves span all {run.n} devices",
            )
        if run.n > 1:
            kv = max(jax.tree.leaves(engine.cache), key=lambda a: a.size)
            check(
                kv.addressable_shards[0].data.size * run.n == kv.size,
                f"the KV pool is replicated, not sharded: {kv.sharding}",
            )

        # greedy tokens against generate_cached on the same prompt: one
        # buffer length for every request, so one compile
        decode_model = Decoder(dataclasses.replace(cfg, decode=True))
        width = max(sizes.prompt_lens) + sizes.max_new
        exact, ties = 0, []
        # float32 full-sequence forward: adjudicates a differing token
        ref_model = Decoder(dataclasses.replace(
            cfg, dtype=jnp.float32, attention_fn=default_attention
        ))
        ref_forward = jax.jit(lambda p, t: ref_model.apply({"params": p}, t))
        # A bf16 server and a bf16 token-by-token reference round differently
        # (prefill in one pass vs one token at a time; under tensor
        # parallelism, partial sums reduced in another order), so on a
        # near-tie the two argmaxes can differ. A token counts as equal when
        # the float32 logits put it within `tie_tol` of the reference's
        # token. These logits have a standard deviation of ~1.3 and the top
        # two are 0.26 apart on average; rounding moves them by hundredths
        # (largest margin seen on one v5e: 0.023), while a token taken from
        # the wrong position, page or slot is off by several units.
        tie_tol = 0.1
        for i, prompt in enumerate(prompts):
            buf = np.zeros((1, width), np.int32)
            buf[0, : len(prompt)] = prompt
            ref = np.asarray(generate_cached(
                decode_model, params, jnp.asarray(buf), jnp.asarray([len(prompt)])
            ))[0, len(prompt): len(prompt) + sizes.max_new].tolist()
            got = results[i]
            check(len(got) == sizes.max_new, f"request {i}: {len(got)} tokens")
            if got == ref:
                exact += 1
                continue
            j = next(x for x in range(sizes.max_new) if got[x] != ref[x])
            buf[0, len(prompt): len(prompt) + j] = ref[:j]
            with jax.default_matmul_precision("highest"):
                logits = ref_forward(params, jnp.asarray(buf))[0, len(prompt) + j - 1]
            margin = abs(float(logits[ref[j]] - logits[got[j]]))
            ties.append({"request": i, "token": j, "margin": round(margin, 4)})
            check(
                margin <= tie_tol,
                f"request {i} token {j}: server {got[j]} vs generate_cached "
                f"{ref[j]}, float32 logit margin {margin:.3f} > {tie_tol}",
            )
        run.say(
            f"  {exact}/{len(prompts)} requests token-identical to generate_cached; "
            f"near-ties within {tie_tol}: {ties}"
        )
        counts = stats["compile_counts"]
        check(counts["decode"] == 1, f"decode step traced {counts['decode']} times")
        mem = stats["memory"]
        if not run.rehearsal:
            check(mem["source"] == "device", f"memory ledger source {mem['source']}")
        new_tokens = len(prompts) * sizes.max_new
        run.say(
            f"  compile counts {counts}; prefix hits {stats['prefix_hits']}; "
            f"alerts firing {stats['alerts']}; "
            f"ttft p50 {stats['ttft_ms_p50']:.0f} ms p95 {stats['ttft_ms_p95']:.0f} ms, "
            f"{new_tokens / serve_wall:.1f} new tokens/s over the {serve_wall:.1f} s "
            f"window, compiles included (information)"
        )
        gib = 2.0**30
        run.say(
            f"  ledger [{mem['source']}]: accounted {mem['accounted'] / gib:.2f} GiB "
            f"({ {k: round(v / gib, 2) for k, v in mem['accounts'].items()} }), "
            f"reported used {mem['hbm_used'] / gib:.2f} GiB; {run.memory()}"
        )
    finally:
        server.stop()
        if tel is not None:
            tel.close()
    return {
        # building the stack, plus the request window in which every prefill
        # bucket, admit and the decode step compiled
        "setup_s": build_s + serve_wall,
        "build_s": build_s,
        "requests": len(prompts),
        "token_identical": exact,
        "near_ties": ties,
        "compile_counts": counts,
        "ttft_ms_p50": stats["ttft_ms_p50"],
        "mesh": mesh_arg,
        "n_layers": fields["n_layers"],
        "vocab": fields["vocab_size"],
    }


# ------------------------------------------------------------------------- hpo


def phase_hpo(run: Run) -> dict:
    """``lagom(train_fn, HyperparameterOptConfig(devices_per_trial=1))``: a
    small real train_fn that asks for ``ctx``; each trial's state must live
    on its one leased chip and nowhere else."""
    import jax
    import optax

    from maggy_tpu import Searchspace, experiment
    from maggy_tpu.config import HyperparameterOptConfig
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train.data import synthetic_lm_batches

    sizes = run.sizes
    cfg = DecoderConfig(**sizes.hpo_cfg)
    n_trials = max(4, 2 * run.n)
    trials, lock = [], threading.Lock()

    def train_fn(hparams, reporter, ctx, devices):
        t_start = time.time()
        data = synthetic_lm_batches(cfg.vocab_size, 4, sizes.hpo_seq, seed=1)
        trainer = ctx.trainer(Decoder(cfg), optax.adamw(hparams["lr"]))
        state = trainer.make_state(jax.random.key(0), next(data))
        state, metrics = trainer.fit(
            state, data, num_steps=4, reporter=reporter, report_every=2,
            metric_sign=-1.0,
        )
        held = set()
        for leaf in jax.tree.leaves(state):
            held |= {d.id for d in leaf.sharding.device_set}
        with lock:
            trials.append({
                "lease": sorted(d.id for d in devices),
                "held": sorted(held),
                "start": t_start,
                "end": time.time(),
                "loss": metrics["loss"],
            })
        return {"metric": -metrics["loss"]}

    t0 = time.perf_counter()
    result = experiment.lagom(
        train_fn,
        HyperparameterOptConfig(
            num_trials=n_trials,
            optimizer="randomsearch",
            searchspace=Searchspace(lr=("DOUBLE", [1e-4, 1e-2])),
            direction="max",
            es_policy="none",
            devices_per_trial=1,
            name="chip_smoke_hpo",
            seed=0,
        ),
    )
    wall = time.perf_counter() - t0
    check(result["errors"] == 0, f"{result['errors']} trials errored: {result}")
    check(
        result["num_trials"] == n_trials and len(trials) == n_trials,
        f"{len(trials)} of {n_trials} trials ran",
    )
    for t in trials:
        check(len(t["lease"]) == 1, f"lease {t['lease']} is not one device")
        check(t["held"] == t["lease"], f"trial state on {t['held']}, leased {t['lease']}")
        check(t["loss"] == t["loss"], "trial loss is NaN")
    leases = sorted({t["lease"][0] for t in trials})
    check(len(leases) == run.n, f"trials used leases {leases} of {run.n} devices")
    concurrent = any(
        a["lease"] != b["lease"] and a["start"] < b["end"] and b["start"] < a["end"]
        for a in trials for b in trials
    )
    if run.n >= 2:
        check(concurrent, "no two trials held state on distinct chips at once")
    run.say(
        f"  {n_trials} trials on leases {leases}, concurrent on distinct chips: "
        f"{concurrent}; best {result['best']['params']} in {wall:.1f} s"
    )
    return {
        # each trial builds its own Trainer and compiles its own step (the
        # learning rate is a constant in the program), so the phase is set-up
        "setup_s": wall,
        "trials": n_trials,
        "leases": leases,
        "concurrent": concurrent,
    }


# ------------------------------------------------------------------------ main


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="toy sizes on JAX_PLATFORMS=cpu, Pallas interpreted, every line "
             "labelled; not a device result",
    )
    parser.add_argument(
        "--phases", default=",".join(PHASES),
        help="comma-separated subset for bring-up work; a run that skips a "
             "phase exits non-zero",
    )
    args = parser.parse_args(argv)
    wanted = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(wanted) - set(PHASES))
    if unknown:
        parser.error(f"unknown phase(s) {unknown}; choose from {PHASES}")

    os.makedirs(OUT_DIR, exist_ok=True)
    # experiment logs land in the directory a chip run brings back
    os.environ.setdefault("MAGGY_TPU_LOG_ROOT", os.path.join(OUT_DIR, "experiments"))

    import jax
    import jaxlib

    import maggy_tpu
    from maggy_tpu import util

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }
    if args.rehearse_on_cpu:
        if dev.platform != "cpu":
            parser.error("--rehearse-on-cpu is for JAX_PLATFORMS=cpu only")
    elif dev.platform != "tpu":
        print(
            f"chip_smoke: JAX found platform {dev.platform!r} ({dev.device_kind}), "
            "not a TPU. There is no CPU fallback; --rehearse-on-cpu is the toy "
            "rehearsal.", file=sys.stderr,
        )
        return 1

    run = Run(args.rehearse_on_cpu)
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    cache_dir = util.enable_compilation_cache()
    run.say(
        f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']}; jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}; maggy_tpu at {os.path.dirname(maggy_tpu.__file__)}"
    )
    run.say(
        f"compile cache: {cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})"
    )
    run.say(f"memory: {run.memory()}")

    phase_fns = {
        "kernels": phase_kernels, "trainer": phase_trainer,
        "server": phase_server, "hpo": phase_hpo,
    }
    phases = {}
    t_all = time.perf_counter()
    for name in PHASES:
        if name not in wanted:
            run.say(f"phase {name}: SKIPPED by --phases (the run will fail)")
            continue
        run.say(f"phase {name}: start")
        t0 = time.perf_counter()
        facts = phase_fns[name](run)  # any exception ends the run non-zero
        facts["wall_s"] = round(time.perf_counter() - t0, 1)
        facts["setup_s"] = round(facts["setup_s"], 1)
        phases[name] = facts
        run.say(
            f"phase {name}: ok in {facts['wall_s']} s (set-up {facts['setup_s']} s)"
        )
        gc.collect()
    ok = list(phases) == list(PHASES)
    summary = {
        "ok": ok,
        "device": device,
        "rehearsal": run.rehearsal,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__, "libtpu": libtpu},
        "compile_cache": cache_dir,
        "wall_s": round(time.perf_counter() - t_all, 1),
        "setup_s": {name: facts["setup_s"] for name, facts in phases.items()},
        "phases": phases,
    }
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    run.say(f"summary: {json.dumps(summary, default=str)}")
    # the verdict line is read by machine: these two keys and nothing else
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
