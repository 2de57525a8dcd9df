"""Plain reference of a grouped-query decoder over softmax-routed experts that
trains by diffusion over blocks: ``sdar_moe`` (``qwen3_moe``'s layer under
SDAR's training step).

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, explicit scores over the
``2L x 2L`` layout of the two streams a block of queries at a time. A row
holds ``L`` tokens ``x_i`` in packed documents, ``p_i`` the position inside the
document, ``c_i = p_i // B`` the block. For optimizer step ``n``, from
``key = fold_in(key(noise_seed), n)``::

    u = uniform(fold_in(key, 0), [rows, L])         t_i = eps + (1 - eps) u[first row index of i's block]
    m_i = uniform(fold_in(key, 1), [rows, L])_i < t_i  and i real       x~_i = [MASK] where m_i, else x_i

Two streams run through the same weights with the same rotary positions: the
clean one from ``embed(x)``, the noised one from ``embed(x~)``, where
``embed([MASK])`` is a learned vector of its own (``mask_embed``) and not a
row of the table; the layout is ``[clean; noised]``, ``2L`` positions a row. One layer, ``u = RMSNorm(h)``::

    q_h = rope(norm(W_q u_i))   k_g, v_g = rope(norm(W_k u_j)), W_v u_j      (norm: RMSNorm over the head's width)
    i sees j iff both lie in one document and
        i clean,  j clean:   c_j <= c_i
        i noised, j clean:   c_j <  c_i
        i noised, j noised:  c_j == c_i
        i clean,  j noised:  never
    a_h = softmax over the keys i sees of q_h . k_g(h) / sqrt(head_dim);   y = h + W_o [sum_j a_h[j] v_g(h)[j]]_h
    z = y + sum over e in top_k(softmax(W_r RMSNorm(y))) held here of (p_e / sum of the chosen p) SwiGLU_e(RMSNorm(y))

for all ``2L`` positions. The head reads the noised stream with no shift,
``logits_i = W_head RMSNorm(z~_i)``, and the objective is

    L = (1 / N) sum_i m_i (1 / t_i) (-log softmax(logits_i)[x_i])          N: the step's real tokens

The reference is given the same share as the chip (``held`` experts from
``offset * held``; ``conv_moe.routed_part``: every held expert computed for
every position of both streams and weighted by its coefficient or zero).

Departures from the published description, each a line of the configuration's
``assumed`` or ``departures``:

* block length 4, one noise level a block, a linear schedule with weight
  ``1 / t`` and ``eps`` 1e-3: the family's convention, not a ``config.json`` key;
* the noise key's derivation from the step's count is this repo's;
* ``[MASK]`` is a vector of its own, drawn at ``MASK_STD``: the published id
  lies outside the chip's slice of the vocabulary, and the checkpoint being
  adapted never trained it (the noised row shows id 0 there, which the traffic
  never draws);
* a masked position predicts its own token (no shift);
* what the absent experts would add is left out, as in every share cell;
* depth, experts held, vocabulary and positions are cut; weights are random.

It imports nothing of the program and takes its weights by seed under its own
leaf names (``leaf_spec``: ``moe.*`` stacked over the layers). ``low`` is a
control, as in ``decoder.py``; the router, stated in float32, gets bfloat16
operands under one; the noise, ``1 / t`` and the loss stay float32.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.conv_moe import routed_part
from benchmark.references.decoder import GRAD_SAMPLE, _round, adamw_apply, mm, rms_norm, rope  # noqa: F401
from benchmark.references.sparse_gqa_moe import _float32_low, query_block, route

# [MASK] is a vector the checkpoint being adapted never trained: at the initializer's scale
MASK_STD = 0.02
# a trained router's logits spread by a few units over the 128 experts: 0.06 * sqrt(2,048) = 2.7
ROUTER_STD = 0.06

LAYER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
    "mlp_norm", "router", "experts_gate", "experts_up", "experts_down",
)


def leaf_spec(cfg: dict) -> dict:
    """name -> (one layer's shape or the whole shape, layers it is stacked
    over (0: not stacked), std, mean); what ``benchmark/weights.py`` draws
    from. ``cfg`` is the reference's sizes."""
    d, v, h, kv, hd = cfg["d_model"], cfg["vocab"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    f, held = cfg["moe_d_ff"], cfg["held"]
    layer = {
        "attn_norm": ((d,), 0.1, 1.0),
        "wq": ((d, h * hd), 0.02, 0.0), "wk": ((d, kv * hd), 0.02, 0.0), "wv": ((d, kv * hd), 0.02, 0.0),
        "q_norm": ((hd,), 0.1, 1.0), "k_norm": ((hd,), 0.1, 1.0),
        "wo": ((h * hd, d), 0.02, 0.0),
        "mlp_norm": ((d,), 0.1, 1.0),
        "router": ((d, cfg["n_experts"]), ROUTER_STD, 0.0),
        "experts_gate": ((held, d, f), 0.02, 0.0), "experts_up": ((held, d, f), 0.02, 0.0),
        "experts_down": ((held, f, d), 0.02, 0.0),
    }
    spec = {
        "embed": ((v, d), 0, 1.0, 0.0),
        "mask_embed": ((d,), 0, MASK_STD, 0.0),
        "final_norm": ((d,), 0, 0.1, 1.0),
        "lm_head": ((d, v), 0, 0.02, 0.0),
    }
    spec.update({f"moe.{n}": (shape, cfg["n_layers"], std, mean) for n, (shape, std, mean) in layer.items()})
    return spec


def noise(batch, cfg, step):
    """``(noised tokens [rows, L], m / t [rows, L], [masked, real])`` of
    optimizer step ``step``: the docstring's first two lines."""
    tokens, positions, seg = batch["tokens"], batch["positions"], batch["segment_ids"]
    rows, length = tokens.shape
    key = jax.random.fold_in(jax.random.key(cfg["noise_seed"]), step)
    real = seg > 0
    first = jnp.arange(length)[None, :] - positions % cfg["block"]
    u = jax.random.uniform(jax.random.fold_in(key, 0), (rows, length), jnp.float32)
    t = cfg["noise_eps"] + (1.0 - cfg["noise_eps"]) * jnp.take_along_axis(u, first, axis=1)
    m = (jax.random.uniform(jax.random.fold_in(key, 1), (rows, length), jnp.float32) < t) & real
    noised = jnp.where(m, cfg["mask_token_id"], tokens)
    return noised, jnp.where(m, 1.0 / t, 0.0), jnp.stack([m.sum(dtype=jnp.float32), real.sum(dtype=jnp.float32)])


def sees(seg_q, c_q, noised_q, seg_k, c_k, noised_k):
    """bool [rows, queries, keys]: the docstring's four cases, from each
    position's document, block and stream."""
    one_doc = seg_q[:, :, None] == seg_k[:, None, :]
    cq, ck, nq, nk = c_q[:, :, None], c_k[:, None, :], noised_q[:, :, None], noised_k[:, None, :]
    return one_doc & jnp.where(nq, jnp.where(nk, ck == cq, ck < cq), ~nk & (ck <= cq))


def attention(u, w, positions, seg, noised, cfg, low=None):
    """The heads' outputs through ``W_o`` over the two streams' layout:
    ``u`` [rows, 2L, d]; ``positions``, ``seg`` [rows, 2L]; ``noised`` [2L] bool."""
    b, s, _ = u.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = rope(rms_norm(mm("bsd,de->bse", u, w["wq"], low).reshape(b, s, h, hd), w["q_norm"], eps), positions, theta)
    k = rope(rms_norm(mm("bsd,de->bse", u, w["wk"], low).reshape(b, s, kv, hd), w["k_norm"], eps), positions, theta)
    v = mm("bsd,de->bse", u, w["wv"], low).reshape(b, s, kv, hd)
    c = positions // cfg["block"]
    stream = jnp.broadcast_to(noised, (b, s))
    rows = query_block(s)
    n = s // rows

    def split(a):
        return jnp.moveaxis(a.reshape(b, n, rows, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_block(args):
        qb, segb, cb, streamb = args
        vis = sees(segb, cb, streamb, seg, c, stream)
        scores = mm("bqkgd,bskd->bkgqs", qb.reshape(b, rows, kv, h // kv, hd), k, low) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(vis[:, None, None], scores, -1e30), axis=-1)
        return mm("bkgqs,bskd->bqkgd", probs, v, low).reshape(b, rows, h * hd)

    out = jax.lax.map(one_block, (split(q), split(seg), split(c), split(stream)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * hd)
    return mm("bse,ed->bsd", out, w["wo"], low)


def layer(x, w, positions, seg, noised, cfg, low=None):
    """One layer over both streams: ``(x, slots on the share's experts)``."""
    eps = cfg["norm_eps"]
    x = x + attention(rms_norm(x, w["attn_norm"], eps), w, positions, seg, noised, cfg, low)
    xn = rms_norm(x, w["mlp_norm"], eps)
    y, slots = routed_part(xn, w, *route(xn, w["router"], cfg, low), cfg, low)
    return x + y, slots


def hidden_states(params, batch, cfg, step, low=None):
    """``(the noised stream's last output after the final norm [rows, L, d],
    m / t, {"slots", "masked"})``."""
    length = batch["tokens"].shape[1]
    noised_tokens, weights, masked = noise(batch, cfg, step)
    two = lambda a: jnp.concatenate([a, a], axis=1)
    positions, seg = two(batch["positions"]), two(batch["segment_ids"])
    noised = jnp.arange(2 * length) >= length

    @jax.checkpoint
    def body(x, w):
        x, slots = layer(x, w, positions, seg, noised, cfg, low)
        return x, slots

    at_mask = jnp.concatenate([jnp.zeros_like(weights, bool), weights > 0], axis=1)
    x = params["embed"][jnp.concatenate([batch["tokens"], noised_tokens], axis=1)]
    x, slots = jax.lax.scan(
        body, jnp.where(at_mask[..., None], params["mask_embed"], x), {n: params[f"moe.{n}"] for n in LAYER_LEAVES},
    )
    parts = {"slots": slots.sum(), "masked": masked}
    return rms_norm(x[:, length:], params["final_norm"], cfg["norm_eps"]), weights, parts


def losses(params, batch, cfg, step, low=None, block=1024):
    """``(L, parts)``: the weighted cross entropy of the masked positions' own
    tokens over the step's real tokens, the head applied in blocks."""
    h, weights, parts = hidden_states(params, batch, cfg, step, low)
    tokens = batch["tokens"]
    b, s = tokens.shape
    weights = weights * batch["loss_mask"].astype(jnp.float32)

    @jax.checkpoint
    def one_block(args):
        hb, tb, wb = args
        logp = jax.nn.log_softmax(mm("bsd,dv->bsv", hb, params["lm_head"], low), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0] * wb)

    nb = s // block if s % block == 0 else 1
    split = lambda a: jnp.moveaxis(a.reshape(b, nb, s // nb, *a.shape[2:]), 1, 0)
    ll = jnp.sum(jax.lax.map(one_block, (split(h), split(tokens), split(weights))))
    main = -ll / jnp.maximum(batch["loss_mask"].astype(jnp.float32).sum(), 1.0)
    return main, dict(parts, main=main)


def logits_of(params, batch, cfg, step, low=None):
    """The noised stream's logits whole (small sizes: the tests)."""
    h, _, _ = hidden_states(params, batch, cfg, step, low)
    return mm("bsd,dv->bsv", h, params["lm_head"], low)


def train_steps(leaf_fn, names, batches, cfg, hp, low=None, note=lambda text: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights, as ``mla_moe.train_steps`` does, step ``n``'s noise from ``n``,
    and return the same readings (``loss`` the diffusion loss, ``mtp_loss``
    zeros: this model has no second head, ``slots`` over both streams) and,
    besides, ``masked_share`` a step."""
    grad_fn = jax.jit(jax.value_and_grad(lambda p, b, n: losses(p, b, cfg, n, low), has_aux=True))
    step = jax.jit(
        lambda p, gs: jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp, low), p, *gs),
        donate_argnums=0,
    )
    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))
    sample = jax.jit(lambda a: a.reshape(-1)[:: max(1, a.size // GRAD_SAMPLE)][:GRAD_SAMPLE])
    dnorm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    start = jax.jit(lambda a: _round(a, low, "state_dtype"))
    t0 = time.perf_counter()
    p = {n: start(leaf_fn(n)) for n in names}
    note(f"reference leaves made in {time.perf_counter() - t0:.1f} s")
    out = {"loss": [], "mtp_loss": [], "slots": [], "masked_share": [], "grad_norm": None, "grad_sample": None}
    grads = []
    for n_step, batch in enumerate(batches):
        t0 = time.perf_counter()
        (_, parts), g = grad_fn(p, batch, jnp.int32(n_step))
        out["loss"].append(float(parts["main"]))
        out["mtp_loss"].append(0.0)
        out["slots"].append(int(parts["slots"]))
        masked, real = (float(a) for a in parts["masked"])
        out["masked_share"].append(masked / max(real, 1.0))
        note(f"reference loss and gradient in {time.perf_counter() - t0:.1f} s; masked share {out['masked_share'][-1]:.6f}")
        if out["grad_norm"] is None:
            out["grad_norm"] = {n: float(norm(g[n])) for n in names}
            out["grad_sample"] = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    out["delta_norm"] = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return out
