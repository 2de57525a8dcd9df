"""Plain reference of a Mistral-style dense decoder.

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the published
description (Mistral-7B ``config.json`` and modelling code): RMSNorm, rotary
embedding in the split-half convention, grouped-query causal attention, SwiGLU
feed-forward. No kernels, no cache, no batching tricks. It imports nothing of
the program and takes its weights from ``benchmark/weights.py`` by seed.

``low`` is a control: the reference computed one precision step below what the
configuration states (a variant of the configuration's ``.control.json``).
``operand_dtype`` rounds both operands of every matrix product to a narrower
type and back, ``result_dtype`` every product's result, and ``state_dtype`` the
parameters and AdamW's moments after every optimizer step. ``None`` is the
reference proper.

Departures from the published model, all listed in the configuration file:
depth is cut, weights are random, and for training the objective is the
next-token cross entropy over real tokens of packed documents (attention and
loss never cross a document boundary).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
GRAD_SAMPLE = 16384  # elements of each gradient leaf compared one by one


def _round(x, low, which):
    """Round to the control's type of that name and back; the gradient passes
    straight through (a cast's own gradient would be rounded too, and in
    float8 mostly to zero)."""
    if not low or not low.get(which):
        return x
    dt = jnp.dtype(low[which])
    if dt.itemsize > 1:
        # a float32 -> bfloat16 -> float32 round trip is dropped by the TPU
        # compiler as excess precision; reduce_precision it has to keep
        info = jnp.finfo(dt)
        rounded = jax.lax.reduce_precision(x, info.nexp, info.nmant)
    else:
        rounded = x.astype(dt).astype(jnp.float32)
    return x + jax.lax.stop_gradient(rounded - x)


def mm(spec, a, b, low=None):
    out = jnp.einsum(spec, _round(a, low, "operand_dtype"), _round(b, low, "operand_dtype"), precision=HIGHEST)
    return _round(out, low, "result_dtype")


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def rope(x, positions, theta):
    """[B, S, H, D] rotated by per-token positions [B, S], halves paired."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(xn, w, positions, segment_ids, cfg, low):
    b, s, _ = xn.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    q = rope(mm("bsd,de->bse", xn, w["wq"], low).reshape(b, s, h, hd), positions, cfg["rope_theta"])
    k = rope(mm("bsd,de->bse", xn, w["wk"], low).reshape(b, s, kv, hd), positions, cfg["rope_theta"])
    v = mm("bsd,de->bse", xn, w["wv"], low).reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd)
    idx = jnp.arange(s)
    mask = (idx[None, :, None] >= idx[None, None, :]) & (
        segment_ids[:, :, None] == segment_ids[:, None, :]
    )  # [B, Sq, Sk]

    @jax.checkpoint
    def one_kv_head(args):
        qh, kh, vh = args  # [B,S,G,D], [B,S,D], [B,S,D]
        scores = mm("bqgd,bkd->bgqk", qh, kh, low) / jnp.sqrt(jnp.float32(hd))
        scores = jnp.where(mask[:, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        return mm("bgqk,bkd->bqgd", probs, vh, low)

    out = jax.lax.map(
        one_kv_head,
        (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)),
    )  # [KV, B, S, G, D]
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, h * hd)
    return mm("bse,ed->bsd", out, w["wo"], low)


def swiglu(x, w_gate, w_up, w_down, low):
    hidden = jax.nn.silu(mm("bsd,df->bsf", x, w_gate, low)) * mm("bsd,df->bsf", x, w_up, low)
    return mm("bsf,fd->bsd", hidden, w_down, low)


def layer(x, w, positions, segment_ids, cfg, low):
    eps = cfg["norm_eps"]
    x = x + attention(rms_norm(x, w["attn_norm"], eps), w, positions, segment_ids, cfg, low)
    xn = rms_norm(x, w["mlp_norm"], eps)
    return x + swiglu(xn, w["w_gate"], w["w_up"], w["w_down"], low)


LAYER_LEAVES = ("attn_norm", "mlp_norm", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def hidden_states(params, tokens, positions, segment_ids, cfg, low=None):
    """Embedding, every layer, final norm: [B, S, D]."""
    x = params["embed"][tokens]

    @jax.checkpoint
    def body(x, w):
        return layer(x, w, positions, segment_ids, cfg, low), None

    x, _ = jax.lax.scan(body, x, {n: params[n] for n in LAYER_LEAVES})
    return rms_norm(x, params["final_norm"], cfg["norm_eps"])


def lm_loss(params, batch, cfg, low=None, block=1024):
    """Mean next-token cross entropy over real tokens whose target lies in the
    same document."""
    tokens, seg = batch["tokens"], batch["segment_ids"]
    b, s = tokens.shape
    h = hidden_states(params, tokens, batch["positions"], seg, cfg, low)
    targets = jnp.roll(tokens, -1, axis=1)
    real = batch["loss_mask"].astype(jnp.float32)
    mask = jnp.roll(real, -1, axis=1) * (jnp.roll(seg, -1, axis=1) == seg)
    mask = mask.at[:, -1].set(0.0)

    @jax.checkpoint
    def one_block(args):
        hb, tb, mb = args
        logp = jax.nn.log_softmax(mm("bsd,dv->bsv", hb, params["lm_head"], low), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0] * mb)

    nb = s // block if s % block == 0 else 1
    split = lambda a: jnp.moveaxis(a.reshape(b, nb, s // nb, *a.shape[2:]), 1, 0)
    ll = jnp.sum(jax.lax.map(one_block, (split(h), split(targets), split(mask))))
    return -ll / jnp.maximum(mask.sum(), 1.0)


def adamw_apply(p0, grads, hp, low=None):
    """AdamW (as optax.adamw: decoupled decay on every leaf, bias-corrected
    moments, float32) after ``len(grads)`` steps, from the stored gradients of
    each step. Holding the gradients and not the two moments keeps the
    reference's footprint at one leaf set a step."""
    b1, b2, eps, lr, wd = hp["b1"], hp["b2"], hp["eps"], hp["lr"], hp["weight_decay"]
    t = len(grads)
    m = _round(sum((1 - b1) * b1 ** (t - 1 - i) * g for i, g in enumerate(grads)), low, "state_dtype")
    v = _round(sum((1 - b2) * b2 ** (t - 1 - i) * g * g for i, g in enumerate(grads)), low, "state_dtype")
    m_hat, v_hat = m / (1 - b1**t), v / (1 - b2**t)
    return _round(p0 - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p0), low, "state_dtype")


def train_steps(leaf_fn, names, batches, cfg, hp, low=None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights (``leaf_fn(name)`` makes one leaf). Returns each step's loss, the
    per-leaf norm of the first gradient, ``GRAD_SAMPLE`` evenly strided
    elements of each leaf of that gradient, and the per-leaf norm of the
    parameters' change after the last step (against leaves made anew, so the
    starting weights are not held beside the updated ones)."""
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(lm_loss, cfg=cfg, low=low)))
    step = jax.jit(
        lambda p, gs: jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp, low), p, *gs),
        donate_argnums=0,
    )
    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))
    sample = jax.jit(lambda a: a.reshape(-1)[:: max(1, a.size // GRAD_SAMPLE)][:GRAD_SAMPLE])
    dnorm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    start = jax.jit(lambda a: _round(a, low, "state_dtype"))
    p = {n: start(leaf_fn(n)) for n in names}
    losses, grads, grad_norm, grad_sample = [], [], None, None
    for batch in batches:
        loss, g = grad_fn(p, batch)
        losses.append(float(loss))
        if grad_norm is None:
            grad_norm = {n: float(norm(g[n])) for n in names}
            grad_sample = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    delta_norm = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return {"loss": losses, "grad_norm": grad_norm, "grad_sample": grad_sample, "delta_norm": delta_norm}
