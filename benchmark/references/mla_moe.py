"""Plain reference of a DeepSeek-V3-style decoder as ``glm4_moe_lite`` keeps it.

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the published
description (DeepSeek-V2 section 2.1 for the latent attention, DeepSeek-V3
sections 2.1.2 and 2.2 for the expert layer and the multi-token prediction,
``glm4_moe_lite``'s ``config.json`` for the sizes). With ``xn = RMSNorm(x)``:

* latent attention: ``c_q = RMSNorm(xn W_qa)``, ``q = c_q W_qb`` in heads of
  ``[q_nope ; q_rope]``; ``[c_kv ; k_r] = xn W_kva``, ``[k_nope ; v] =
  RMSNorm(c_kv) W_kvb`` in heads; the key of a head is ``[k_nope ; rope(k_r)]``
  with the one rope key shared by every head, its query ``[q_nope ;
  rope(q_rope)]``; scores over ``sqrt`` of the head's width, causal, inside
  documents;
* expert layer: ``s = sigmoid(xn W_r)`` over all the experts, ``sel = top_k(s +
  b)``, ``w_e = scale * s_e / (sum of the chosen s + 1e-20)``, ``y =
  SwiGLU_shared(xn) + sum over chosen e of w_e SwiGLU_e(xn)``. The reference is
  given the same share as the chip (``held`` experts from ``offset * held``):
  only the chosen experts of the share add to ``y``. Every held expert is
  computed for every token and weighted by ``w_e`` or zero: no sorting, no
  buffers, nothing dropped by construction;
* multi-token prediction, depth 1: ``h' = [RMSNorm(Emb(t_{i+1})) ;
  RMSNorm(h_i)] W_eh``, one more expert layer, a final norm of its own, the
  shared head; it predicts ``t_{i+2}``. ``L = L_main + mtp_weight * L_mtp``,
  each a mean over its targets inside the predictor's document.

It imports nothing of the program and takes its weights by seed under its own
leaf names (``leaf_spec``). ``low`` is a control, as in ``decoder.py``: the
products' operands, their results and the optimizer's state rounded one step
down. The router is stated in float32, so one step down for it is bfloat16
operands, whatever the control rounds the other products to.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.decoder import GRAD_SAMPLE, _round, adamw_apply, mm, rms_norm, rope, swiglu  # noqa: F401

ATTN_LEAVES = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE_LEAVES = ATTN_LEAVES + ("mlp_norm", "w_gate", "w_up", "w_down")
MOE_LEAVES = ATTN_LEAVES + (
    "mlp_norm", "router", "experts_gate", "experts_up", "experts_down",
    "shared_gate", "shared_up", "shared_down",
)


def leaf_spec(cfg: dict) -> dict:
    """name -> (one layer's shape or the whole shape, layers it is stacked
    over (0: not stacked), std, mean); what ``benchmark/weights.py`` draws
    from. ``cfg`` is the reference's sizes (``sizes`` of the configuration's
    ``.reference.py``)."""
    d, v, h = cfg["d_model"], cfg["vocab"], cfg["n_heads"]
    dq = cfg["d_nope"] + cfg["d_rope"]
    attn = {
        "attn_norm": ((d,), 0.1, 1.0),
        "wq_a": ((d, cfg["q_rank"]), 0.02, 0.0),
        "q_norm": ((cfg["q_rank"],), 0.1, 1.0),
        "wq_b": ((cfg["q_rank"], h * dq), 0.02, 0.0),
        "wkv_a": ((d, cfg["kv_rank"] + cfg["d_rope"]), 0.02, 0.0),
        "kv_norm": ((cfg["kv_rank"],), 0.1, 1.0),
        "wkv_b": ((cfg["kv_rank"], h * (cfg["d_nope"] + cfg["d_v"])), 0.02, 0.0),
        "wo": ((h * cfg["d_v"], d), 0.02, 0.0),
        "mlp_norm": ((d,), 0.1, 1.0),
    }
    f, fs = cfg["moe_d_ff"], cfg["moe_d_ff"] * cfg["n_shared"]
    moe = dict(attn, **{
        "router": ((d, cfg["n_experts"]), 0.02, 0.0),
        "experts_gate": ((cfg["held"], d, f), 0.02, 0.0),
        "experts_up": ((cfg["held"], d, f), 0.02, 0.0),
        "experts_down": ((cfg["held"], f, d), 0.02, 0.0),
        "shared_gate": ((d, fs), 0.02, 0.0),
        "shared_up": ((d, fs), 0.02, 0.0),
        "shared_down": ((fs, d), 0.02, 0.0),
    })
    dense = dict(attn, **{
        "w_gate": ((d, cfg["d_ff"]), 0.02, 0.0),
        "w_up": ((d, cfg["d_ff"]), 0.02, 0.0),
        "w_down": ((cfg["d_ff"], d), 0.02, 0.0),
    })
    spec = {
        "embed": ((v, d), 0, 1.0, 0.0),
        "final_norm": ((d,), 0, 0.1, 1.0),
        "lm_head": ((d, v), 0, 0.02, 0.0),
    }
    spec.update({f"dense.{n}": (s, cfg["n_dense"], std, mean) for n, (s, std, mean) in dense.items()})
    spec.update({f"moe.{n}": (s, cfg["n_moe"], std, mean) for n, (s, std, mean) in moe.items()})
    if cfg["mtp"]:
        spec.update({f"mtp.{n}": (s, 0, std, mean) for n, (s, std, mean) in moe.items()})
        spec.update({
            "mtp.enorm": ((d,), 0, 0.1, 1.0), "mtp.hnorm": ((d,), 0, 0.1, 1.0),
            "mtp.eh_proj": ((2 * d, d), 0, 0.02, 0.0), "mtp.final_norm": ((d,), 0, 0.1, 1.0),
        })
    return spec


def select_bias(cfg: dict) -> np.ndarray:
    """The selection bias, [expert layers (+ 1 for the MTP module), experts]:
    N(0, bias_std) from the configuration's own seed, not the run's."""
    rows = cfg["n_moe"] + cfg["mtp"]
    rng = np.random.default_rng(cfg["bias_seed"])
    return (cfg["bias_std"] * rng.standard_normal((rows, cfg["n_experts"]))).astype(np.float32)


def latent_attention(xn, w, positions, segment_ids, cfg, low):
    b, s, _ = xn.shape
    h, dn, dr, dv = cfg["n_heads"], cfg["d_nope"], cfg["d_rope"], cfg["d_v"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    c_q = rms_norm(mm("bsd,dr->bsr", xn, w["wq_a"], low), w["q_norm"], eps)
    q = mm("bsr,re->bse", c_q, w["wq_b"], low).reshape(b, s, h, dn + dr)
    c_kv = mm("bsd,dr->bsr", xn, w["wkv_a"], low)
    k_rope = rope(c_kv[..., None, cfg["kv_rank"]:], positions, theta)  # one head
    kv = mm("bsr,re->bse", rms_norm(c_kv[..., : cfg["kv_rank"]], w["kv_norm"], eps), w["wkv_b"], low)
    kv = kv.reshape(b, s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
    v = kv[..., dn:]
    idx = jnp.arange(s)
    mask = (idx[None, :, None] >= idx[None, None, :]) & (
        segment_ids[:, :, None] == segment_ids[:, None, :]
    )  # [B, Sq, Sk]

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args  # [B, S, D] each
        scores = mm("bqd,bkd->bqk", qh, kh, low) / jnp.sqrt(jnp.float32(dn + dr))
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return mm("bqk,bkd->bqd", probs, vh, low)

    out = jax.lax.map(one_head, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, s, h * dv)
    return mm("bse,ed->bsd", out, w["wo"], low)


def route(xn, w_router, bias, cfg, low=None):
    """``(sel [B, S, k] expert numbers, weights [B, S, k])`` over all the
    experts. Float32; under a control the operands are bfloat16."""
    router_low = {"operand_dtype": "bfloat16"} if low and low.get("operand_dtype") else None
    scores = jax.nn.sigmoid(mm("bsd,de->bse", xn, w_router, router_low))
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), cfg["top_k"])
    chosen = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, cfg["routed_scaling"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


def routed_part(xn, w, sel, weights, cfg, low=None):
    """The share's part of the routed experts' sum, and how many (token,
    choice) slots fell on its experts."""
    first = cfg["offset"] * cfg["held"]
    # coef[b, s, e]: token's weight for held expert e, zero where not chosen
    hit = sel[..., None] == (first + jnp.arange(cfg["held"]))  # [B, S, k, held]
    coef = jnp.sum(jnp.where(hit, weights[..., None], 0.0), axis=2)

    @jax.checkpoint
    def one_expert(y, args):
        wg, wu, wd, c = args
        return y + c[..., None] * swiglu(xn, wg, wu, wd, low), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(xn),
        (w["experts_gate"], w["experts_up"], w["experts_down"], jnp.moveaxis(coef, -1, 0)),
    )
    return y, jnp.sum(hit)


def expert_layer(xn, w, bias, cfg, low=None):
    """``(y, slots on the share's experts)``."""
    sel, weights = route(xn, w["router"], bias, cfg, low)
    y, slots = routed_part(xn, w, sel, weights, cfg, low)
    if cfg["n_shared"]:
        y = y + swiglu(xn, w["shared_gate"], w["shared_up"], w["shared_down"], low)
    return y, slots


def layer(x, w, bias, positions, segment_ids, cfg, low):
    """One layer: dense where ``bias`` is None. ``(x, slots)``."""
    eps = cfg["norm_eps"]
    x = x + latent_attention(rms_norm(x, w["attn_norm"], eps), w, positions, segment_ids, cfg, low)
    xn = rms_norm(x, w["mlp_norm"], eps)
    if bias is None:
        return x + swiglu(xn, w["w_gate"], w["w_up"], w["w_down"], low), jnp.int32(0)
    y, slots = expert_layer(xn, w, bias, cfg, low)
    return x + y, slots


def _group(params, prefix, names):
    return {n: params[f"{prefix}.{n}"] for n in names}


def hidden_states(params, batch, cfg, low=None):
    """``(last layer's output before the final norm, the MTP module's output
    after its own final norm or None, slots on the share's experts)``."""
    tokens, positions, seg = batch["tokens"], batch["positions"], batch["segment_ids"]
    bias = jnp.asarray(select_bias(cfg))
    x = params["embed"][tokens]

    @jax.checkpoint
    def dense_body(x, w):
        return layer(x, w, None, positions, seg, cfg, low)[0], None

    @jax.checkpoint
    def moe_body(x, wb):
        w, b = wb
        return layer(x, w, b, positions, seg, cfg, low)

    x, _ = jax.lax.scan(dense_body, x, _group(params, "dense", DENSE_LEAVES))
    x, slots = jax.lax.scan(moe_body, x, (_group(params, "moe", MOE_LEAVES), bias[: cfg["n_moe"]]))
    slots = jnp.sum(slots)
    if not cfg["mtp"]:
        return x, None, slots
    eps = cfg["norm_eps"]
    nxt = params["embed"][jnp.roll(tokens, -1, axis=1)]
    both = jnp.concatenate(
        [rms_norm(nxt, params["mtp.enorm"], eps), rms_norm(x, params["mtp.hnorm"], eps)], axis=-1
    )
    hm, more = moe_body(mm("bse,ed->bsd", both, params["mtp.eh_proj"], low),
                        (_group(params, "mtp", MOE_LEAVES), bias[cfg["n_moe"]]))
    return x, rms_norm(hm, params["mtp.final_norm"], eps), slots + more


def _masked_ll(h, head, batch, ahead, low, block):
    """``(sum of the targets' log-likelihoods, number of targets)`` for
    logits ``h @ head`` predicting the token ``ahead`` positions on, over real
    targets in the predictor's document; the head applied in blocks."""
    tokens, seg = batch["tokens"], batch["segment_ids"]
    b, s = tokens.shape
    targets = jnp.roll(tokens, -ahead, axis=1)
    real = batch["loss_mask"].astype(jnp.float32)
    mask = jnp.roll(real, -ahead, axis=1) * (jnp.roll(seg, -ahead, axis=1) == seg)
    mask = mask.at[:, s - ahead:].set(0.0)

    @jax.checkpoint
    def one_block(args):
        hb, tb, mb = args
        logp = jax.nn.log_softmax(mm("bsd,dv->bsv", hb, head, low), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0] * mb)

    nb = s // block if s % block == 0 else 1
    split = lambda a: jnp.moveaxis(a.reshape(b, nb, s // nb, *a.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(one_block, (split(h), split(targets), split(mask)))), mask.sum()


def losses(params, batch, cfg, low=None, block=1024):
    """``(L, {"main", "mtp", "slots"})`` with ``L = main + mtp_weight * mtp``."""
    x, hm, slots = hidden_states(params, batch, cfg, low)
    h = rms_norm(x, params["final_norm"], cfg["norm_eps"])
    ll, n = _masked_ll(h, params["lm_head"], batch, 1, low, block)
    main = -ll / jnp.maximum(n, 1.0)
    if hm is None:
        return main, {"main": main, "mtp": jnp.float32(0), "slots": slots}
    ll2, n2 = _masked_ll(hm, params["lm_head"], batch, 2, low, block)
    mtp = -ll2 / jnp.maximum(n2, 1.0)
    return main + cfg["mtp_weight"] * mtp, {"main": main, "mtp": mtp, "slots": slots}


def logits_of(params, batch, cfg, low=None):
    """Both heads' logits whole (small sizes: the tests)."""
    x, hm, _ = hidden_states(params, batch, cfg, low)
    main = mm("bsd,dv->bsv", rms_norm(x, params["final_norm"], cfg["norm_eps"]), params["lm_head"], low)
    return main, None if hm is None else mm("bsd,dv->bsv", hm, params["lm_head"], low)


def train_steps(leaf_fn, names, batches, cfg, hp, low=None, note=lambda text: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights, as ``decoder.train_steps`` does, and return the same readings
    with the loss split into its two parts and the slots on the share's
    experts counted each step."""
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
    out = {"loss": [], "mtp_loss": [], "slots": [], "grad_norm": None, "grad_sample": None}
    grads = []
    for batch in batches:
        t0 = time.perf_counter()
        (_, parts), g = grad_fn(p, batch)
        out["loss"].append(float(parts["main"]))
        note(f"reference loss and gradient in {time.perf_counter() - t0:.1f} s")
        out["mtp_loss"].append(float(parts["mtp"]))
        out["slots"].append(int(parts["slots"]))
        if out["grad_norm"] is None:
            out["grad_norm"] = {n: float(norm(g[n])) for n in names}
            out["grad_sample"] = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    out["delta_norm"] = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return out
