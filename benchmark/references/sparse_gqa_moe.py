"""Plain reference of a grouped-query decoder whose attention runs over the
keys a learned indexer selects, over softmax-routed experts: the language model
of ``KeyeVL2`` (``qwen3_moe``'s keys and DeepSeek-Sparse-Attention's indexer
under ``sa_config``).

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, explicit scores a block of
queries at a time (``[S, S]`` float32 is 4 GB at 32,768), ``jax.lax.top_k`` on
them. One layer, for token ``t`` of a document, with ``u = RMSNorm(x)`` and
``sg`` a stop-gradient::

    q_h = rope(norm(W_q u_t))   k_g, v_g = rope(norm(W_k u_s)), W_v u_s     (norm: RMSNorm over the head's width)
    qI_j = rope(W_qI sg(u_t))   kI = rope(LayerNorm(W_kI sg(u_s)))   w = W_w sg(u_t)
    I[t,s] = sum_j w_j relu(qI_j . kI_s) J^-1/2 Dj^-1/2              s <= t, same document
    S_t = the topk keys of largest I[t,.] (lax.top_k: ties to the earlier key); all while t sees at most topk
    a_h = softmax over S_t of q_h . k_g(h) / sqrt(head_dim);   y = x + W_o [sum_s a_h[s] v_g(h)[s]]_h
    L_I += mean over real t of KL( sg(mean_h a_h) || softmax over S_t of I[t,.] )
    z = y + sum over e in top_k(softmax(W_r RMSNorm(y))) held here of (p_e / sum of the chosen p) SwiGLU_e(RMSNorm(y))

and the objective is the next-token cross entropy plus ``L_I``: the indexer
learns from ``L_I`` alone and everything else from the cross entropy alone.
The reference is given the same share as the chip (``held`` experts from
``offset * held``; ``conv_moe.routed_part``: every held expert computed for
every token and weighted by its coefficient or zero).

It imports nothing of the program and takes its weights by seed under its own
leaf names (``leaf_spec``: ``moe.*`` stacked over the layers). ``low`` is a
control, as in ``decoder.py``; the router and the indexer's head weights,
stated in float32, get bfloat16 operands under one.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.conv_moe import _masked_ll, routed_part
from benchmark.references.decoder import GRAD_SAMPLE, _round, adamw_apply, mm, rms_norm, rope  # noqa: F401

LAYER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
    "index_q", "index_k", "index_k_scale", "index_k_bias", "index_w",
    "mlp_norm", "router", "experts_gate", "experts_up", "experts_down",
)


def leaf_spec(cfg: dict) -> dict:
    """name -> (one layer's shape or the whole shape, layers it is stacked
    over (0: not stacked), std, mean); what ``benchmark/weights.py`` draws
    from. ``cfg`` is the reference's sizes."""
    d, v, h, kv, hd = cfg["d_model"], cfg["vocab"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    j, dj, f, held = cfg["index_heads"], cfg["index_head_dim"], cfg["moe_d_ff"], cfg["held"]
    layer = {
        "attn_norm": ((d,), 0.1, 1.0),
        "wq": ((d, h * hd), 0.02, 0.0), "wk": ((d, kv * hd), 0.02, 0.0), "wv": ((d, kv * hd), 0.02, 0.0),
        "q_norm": ((hd,), 0.1, 1.0), "k_norm": ((hd,), 0.1, 1.0),
        "wo": ((h * hd, d), 0.02, 0.0),
        "index_q": ((d, j * dj), 0.02, 0.0), "index_k": ((d, dj), 0.02, 0.0),
        "index_k_scale": ((dj,), 0.1, 1.0), "index_k_bias": ((dj,), 0.02, 0.0),
        "index_w": ((d, j), 0.02, 0.0),
        "mlp_norm": ((d,), 0.1, 1.0),
        "router": ((d, cfg["n_experts"]), 0.02, 0.0),
        "experts_gate": ((held, d, f), 0.02, 0.0), "experts_up": ((held, d, f), 0.02, 0.0),
        "experts_down": ((held, f, d), 0.02, 0.0),
    }
    spec = {
        "embed": ((v, d), 0, 1.0, 0.0),
        "final_norm": ((d,), 0, 0.1, 1.0),
        "lm_head": ((d, v), 0, 0.02, 0.0),
    }
    spec.update({f"moe.{n}": (shape, cfg["n_layers"], std, mean) for n, (shape, std, mean) in layer.items()})
    return spec


def _float32_low(low):
    """What a product stated in float32 gets under a control: bfloat16 operands."""
    return {"operand_dtype": "bfloat16"} if low and low.get("operand_dtype") else None


def layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale + bias


def query_block(s: int) -> int:
    """Queries whose explicit scores are held at a time: ``[32 heads, block,
    S]`` float32 is 0.5 GB at 128 x 32,768."""
    for block in (128, 64, 32):
        if s % block == 0 and s > block:
            return block
    return s


def selected_attention(u, w, positions, segment_ids, cfg, low=None):
    """``(the heads' outputs through W_o, L_I of this layer, [pairs selected,
    pairs visible])``."""
    b, s, _ = u.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    j, dj, topk = cfg["index_heads"], cfg["index_head_dim"], cfg["topk"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = rope(rms_norm(mm("bsd,de->bse", u, w["wq"], low).reshape(b, s, h, hd), w["q_norm"], eps), positions, theta)
    k = rope(rms_norm(mm("bsd,de->bse", u, w["wk"], low).reshape(b, s, kv, hd), w["k_norm"], eps), positions, theta)
    v = mm("bsd,de->bse", u, w["wv"], low).reshape(b, s, kv, hd)
    us = jax.lax.stop_gradient(u)
    qi = rope(mm("bsd,de->bse", us, w["index_q"], low).reshape(b, s, j, dj), positions, theta)
    ki = layer_norm(mm("bsd,de->bse", us, w["index_k"], low), w["index_k_scale"], w["index_k_bias"], eps)
    ki = rope(ki[:, :, None], positions, theta)[:, :, 0]
    wi = mm("bsd,dj->bsj", us, w["index_w"], _float32_low(low)) * (j**-0.5 * dj**-0.5)
    real = (segment_ids > 0).astype(jnp.float32)
    weight = real / jnp.maximum(real.sum(), 1.0)
    rows = query_block(s)
    n = s // rows
    cols = jnp.arange(s)

    def split(a):
        return jnp.moveaxis(a.reshape(b, n, rows, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_block(args):
        first, qb, qib, wib, segb, weightb = args
        at = first + jnp.arange(rows)
        vis = (at[None, :, None] >= cols) & (segb[:, :, None] == segment_ids[:, None, :])
        z = mm("bqjd,bsd->bqjs", qib, ki, low)
        index = (wib[..., None] * jnp.maximum(z, 0.0)).sum(2)
        index = jnp.where(index == 0.0, 0.0, index)  # one zero
        if s > topk:
            vals, idx = jax.lax.top_k(jax.lax.stop_gradient(jnp.where(vis, index, -jnp.inf)), topk)
            put = jax.vmap(jax.vmap(lambda row, i, val: row.at[i].set(val > -jnp.inf)))
            sel = put(jnp.zeros((b, rows, s), bool), idx, vals)
        else:
            sel = vis
        scores = mm("bqkgd,bskd->bkgqs", qb.reshape(b, rows, kv, h // kv, hd), k, low) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(sel[:, None, None], scores, -1e30), axis=-1)
        out = mm("bkgqs,bskd->bqkgd", probs, v, low).reshape(b, rows, h * hd)
        target = jax.lax.stop_gradient(probs.mean((1, 2)))
        logq = jax.nn.log_softmax(jnp.where(sel, index, -1e30), axis=-1)
        kl = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-37)) - logq), 0.0).sum(-1)
        return out, (kl * weightb).sum(), jnp.stack([sel.sum(dtype=jnp.float32), vis.sum(dtype=jnp.float32)])

    out, kl, counts = jax.lax.map(
        one_block, (jnp.arange(n) * rows, split(q), split(qi), split(wi), split(segment_ids), split(weight))
    )
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * hd)
    return mm("bse,ed->bsd", out, w["wo"], low), kl.sum(), counts.sum(0)


def route(xn, w_router, cfg, low=None):
    """``(sel [B, S, k] expert numbers, weights [B, S, k])``: softmax over all
    the experts, the chosen probabilities over their sum. Float32; under a
    control the operands are bfloat16."""
    probs = jax.nn.softmax(mm("bsd,de->bse", xn, w_router, _float32_low(low)), axis=-1)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(probs), cfg["top_k"])
    chosen = jnp.take_along_axis(probs, sel, axis=-1)
    return sel, chosen / chosen.sum(-1, keepdims=True)


def layer(x, w, positions, segment_ids, cfg, low=None):
    """One layer: ``(x, L_I, slots on the share's experts, [selected, visible])``."""
    eps = cfg["norm_eps"]
    a, index_loss, counts = selected_attention(rms_norm(x, w["attn_norm"], eps), w, positions, segment_ids, cfg, low)
    x = x + a
    xn = rms_norm(x, w["mlp_norm"], eps)
    y, slots = routed_part(xn, w, *route(xn, w["router"], cfg, low), cfg, low)
    return x + y, index_loss, slots, counts


def hidden_states(params, batch, cfg, low=None):
    """``(the last layer's output after the final norm, {"index", "slots", "pairs"})``."""
    positions, seg = batch["positions"], batch["segment_ids"]

    @jax.checkpoint
    def body(x, w):
        x, index_loss, slots, counts = layer(x, w, positions, seg, cfg, low)
        return x, (index_loss, slots, counts)

    x, (index_loss, slots, counts) = jax.lax.scan(
        body, params["embed"][batch["tokens"]], {n: params[f"moe.{n}"] for n in LAYER_LEAVES}
    )
    parts = {"index": index_loss.sum(), "slots": slots.sum(), "pairs": counts.sum(0)}
    return rms_norm(x, params["final_norm"], cfg["norm_eps"]), parts


def losses(params, batch, cfg, low=None, block=1024):
    """``(L, parts)``: the mean next-token cross entropy through the untied
    head (``main``) plus the indexer's loss (``index``)."""
    h, parts = hidden_states(params, batch, cfg, low)
    ll, n = _masked_ll(h, params["lm_head"].T, batch, low, block)
    main = -ll / jnp.maximum(n, 1.0)
    return main + parts["index"], dict(parts, main=main)


def logits_of(params, batch, cfg, low=None):
    """The logits whole (small sizes: the tests)."""
    h, _ = hidden_states(params, batch, cfg, low)
    return mm("bsd,dv->bsv", h, params["lm_head"], low)


def train_steps(leaf_fn, names, batches, cfg, hp, low=None, note=lambda text: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights, as ``mla_moe.train_steps`` does, and return the same readings
    (``mtp_loss`` zeros: this model has no second head) and, besides,
    ``index_loss`` and ``selected_share`` a step."""
    grad_fn = jax.jit(jax.value_and_grad(lambda p, b: losses(p, b, cfg, low), has_aux=True))
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
    out = {"loss": [], "mtp_loss": [], "slots": [], "index_loss": [], "selected_share": [],
           "grad_norm": None, "grad_sample": None}
    grads = []
    for batch in batches:
        t0 = time.perf_counter()
        (_, parts), g = grad_fn(p, batch)
        out["loss"].append(float(parts["main"]))
        out["mtp_loss"].append(0.0)
        out["slots"].append(int(parts["slots"]))
        out["index_loss"].append(float(parts["index"]))
        selected, visible = (float(a) for a in parts["pairs"])
        out["selected_share"].append(selected / max(visible, 1.0))
        note(f"reference loss and gradient in {time.perf_counter() - t0:.1f} s; index loss "
             f"{out['index_loss'][-1]:.6f}, selected share {out['selected_share'][-1]:.6f}")
        if out["grad_norm"] is None:
            out["grad_norm"] = {n: float(norm(g[n])) for n in names}
            out["grad_sample"] = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    out["delta_norm"] = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return out
