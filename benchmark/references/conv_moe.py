"""Plain reference of a hybrid short-convolution / attention decoder with
sparse experts, as ``lfm2_moe`` has it.

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the model's
``config.json`` keys and the layer equations of its public modelling code.
Every layer is ``h = h + operator(RMSNorm(h)); h = h + feed_forward(RMSNorm(h))``
with, by the layer's entry of ``layer_types``:

* ``conv``, the gated short convolution: ``[B, C, x] = split3(u W_in)``,
  ``z = B * x``, ``c_t = sum over j < K of w[K-1-j] * z[t-j]`` (depthwise,
  causal, one weight a channel and tap, the last tap on the current position
  as a left-padded ``Conv1d`` has it; no bias, no activation), ``y = (C * c)
  W_out``. No positions enter it. A tap that reaches before a document's first
  token is zero: a document packed in a row gives what it gives alone;
* ``full_attention``: grouped-query attention, an RMSNorm over the head's
  width on every query and key head before the rotary embedding, scores over
  ``sqrt`` of the head's width, causal, inside documents;

and as feed-forward a SwiGLU in the leading dense layers and afterwards the
expert layer: ``s = sigmoid(xn W_r)`` over all the experts, ``sel = top_k(s +
b)`` (``b`` enters the selection only), ``w_e = scale * s_e / (sum of the
chosen s + route_eps)``, ``y = sum over chosen e of w_e SwiGLU_e(xn)``, no
shared expert. The reference is given the same share as the chip (``held``
experts from ``offset * held``): only the chosen experts of the share add to
``y``; every held expert is computed for every token and weighted by ``w_e`` or
zero. A final RMSNorm, and the head is the embedding (tied).

It imports nothing of the program and takes its weights by seed under its own
leaf names (``leaf_spec``): ``d<i>.*`` the leading dense layers, ``p<j>.*`` the
``j``-th layer of the period stacked over the whole periods, ``t<i>.*`` the
layers over after the last whole period. ``low`` is a control, as in
``decoder.py``; the router, stated in float32, gets bfloat16 operands under one.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.decoder import GRAD_SAMPLE, _round, adamw_apply, mm, rms_norm, rope, swiglu  # noqa: F401

FF_BLOCK = 4096  # positions of a row the dense feed-forward takes at a time


def plan(kinds, n_dense: int):
    """``(period, whole periods, layers over)`` of the layers after the
    leading dense ones: the shortest run of kinds that repeats into them."""
    rest = list(kinds[n_dense:])
    for p in range(1, len(rest) + 1):
        if all(rest[i] == rest[i % p] for i in range(len(rest))):
            return rest[:p], len(rest) // p, rest[len(rest) // p * p:]
    raise ValueError("no layer after the dense ones")


def groups(cfg: dict):
    """``[(leaf prefix, kind, dense?, layers stacked (0: one layer, not stacked))]``
    in the order the layers run."""
    kinds, n_dense = cfg["layer_types"], cfg["n_dense"]
    period, n_periods, tail = plan(kinds, n_dense)
    return (
        [(f"d{i}", kinds[i], True, 0) for i in range(n_dense)]
        + [(f"p{j}", k, False, n_periods) for j, k in enumerate(period)]
        + [(f"t{i}", k, False, 0) for i, k in enumerate(tail)]
    )


def layer_leaves(cfg: dict, kind: str, dense: bool) -> dict:
    """One layer's leaves: name -> (shape, std, mean)."""
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    if kind == "conv":
        out = {
            "conv_norm": ((d,), 0.1, 1.0),
            "conv_in": ((d, 3 * d), 0.02, 0.0),
            "conv_w": ((cfg["conv_kernel"], d), 0.02, 0.0),
            "conv_out": ((d, d), 0.02, 0.0),
        }
    else:
        out = {
            "attn_norm": ((d,), 0.1, 1.0),
            "wq": ((d, h * hd), 0.02, 0.0),
            "wk": ((d, kv * hd), 0.02, 0.0),
            "wv": ((d, kv * hd), 0.02, 0.0),
            "q_norm": ((hd,), 0.1, 1.0),
            "k_norm": ((hd,), 0.1, 1.0),
            "wo": ((h * hd, d), 0.02, 0.0),
        }
    out["mlp_norm"] = ((d,), 0.1, 1.0)
    if dense:
        f = cfg["d_ff"]
        out.update(w_gate=((d, f), 0.02, 0.0), w_up=((d, f), 0.02, 0.0), w_down=((f, d), 0.02, 0.0))
    else:
        f, held = cfg["moe_d_ff"], cfg["held"]
        out.update(
            router=((d, cfg["n_experts"]), 0.02, 0.0),
            experts_gate=((held, d, f), 0.02, 0.0),
            experts_up=((held, d, f), 0.02, 0.0),
            experts_down=((held, f, d), 0.02, 0.0),
        )
    return out


def leaf_spec(cfg: dict) -> dict:
    """name -> (one layer's shape or the whole shape, layers it is stacked
    over (0: not stacked), std, mean); what ``benchmark/weights.py`` draws
    from. ``cfg`` is the reference's sizes."""
    spec = {
        # N(0, 0.02) as every kernel: the embedding is the head too, and rows of
        # unit variance would give logits of deviation sqrt(d_model)
        "embed": ((cfg["vocab"], cfg["d_model"]), 0, 0.02, 0.0),
        "final_norm": ((cfg["d_model"],), 0, 0.1, 1.0),
    }
    for prefix, kind, dense, stacked in groups(cfg):
        spec.update({
            f"{prefix}.{n}": (shape, stacked, std, mean)
            for n, (shape, std, mean) in layer_leaves(cfg, kind, dense).items()
        })
    return spec


def select_bias(cfg: dict) -> np.ndarray:
    """The selection bias, [expert layers, experts], in the order the layers
    run: N(0, bias_std) from the configuration's own seed, not the run's."""
    rows = len(cfg["layer_types"]) - cfg["n_dense"]
    rng = np.random.default_rng(cfg["bias_seed"])
    return (cfg["bias_std"] * rng.standard_normal((rows, cfg["n_experts"]))).astype(np.float32)


def short_conv(xn, w, segment_ids, cfg, low=None):
    gate_in, gate_out, u = jnp.split(mm("bsd,de->bse", xn, w["conv_in"], low), 3, axis=-1)
    z = gate_in * u
    taps, at = cfg["conv_kernel"], jnp.arange(z.shape[1])
    c = jnp.zeros_like(z)
    for j in range(taps):  # the tap that reaches back j positions
        same_document = (at >= j)[None, :] & (jnp.roll(segment_ids, j, axis=1) == segment_ids)
        c = c + w["conv_w"][taps - 1 - j] * jnp.where(same_document[..., None], jnp.roll(z, j, axis=1), 0.0)
    return mm("bse,ed->bsd", gate_out * c, w["conv_out"], low)


def attention(xn, w, positions, segment_ids, cfg, low=None):
    b, s, _ = xn.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = mm("bsd,de->bse", xn, w["wq"], low).reshape(b, s, h, hd)
    k = mm("bsd,de->bse", xn, w["wk"], low).reshape(b, s, kv, hd)
    v = mm("bsd,de->bse", xn, w["wv"], low).reshape(b, s, kv, hd)
    q = rope(rms_norm(q, w["q_norm"], eps), positions, theta)
    k = rope(rms_norm(k, w["k_norm"], eps), positions, theta)
    # one (row, query head) at a time: [S, S] scores
    of_head = jnp.arange(h) // (h // kv)
    flat = lambda a: jnp.moveaxis(a, 2, 1).reshape(b * h, s, hd)
    seg = jnp.repeat(segment_ids, h, axis=0)
    at = jnp.arange(s)

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh, sg = args
        mask = (at[:, None] >= at[None, :]) & (sg[:, None] == sg[None, :])
        scores = mm("qd,kd->qk", qh, kh, low) / jnp.sqrt(jnp.float32(hd))
        return mm("qk,kd->qd", jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1), vh, low)

    out = jax.lax.map(one_head, (flat(q), flat(k[:, :, of_head]), flat(v[:, :, of_head]), seg))
    out = jnp.moveaxis(out.reshape(b, h, s, hd), 1, 2).reshape(b, s, h * hd)
    return mm("bse,ed->bsd", out, w["wo"], low)


def dense_ff(xn, w, low=None):
    """The SwiGLU a block of positions at a time (its hidden width is several
    times the model's: whole, at the cell's size, it would not fit beside the
    gradients)."""
    b, s, d = xn.shape
    nb = s // FF_BLOCK if s % FF_BLOCK == 0 else 1
    blocks = jnp.moveaxis(xn.reshape(b, nb, s // nb, d), 1, 0)
    out = jax.lax.map(jax.checkpoint(lambda xb: swiglu(xb, w["w_gate"], w["w_up"], w["w_down"], low)), blocks)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, d)


def route(xn, w_router, bias, cfg, low=None):
    """``(sel [B, S, k] expert numbers, weights [B, S, k])`` over all the
    experts. Float32; under a control the operands are bfloat16."""
    router_low = {"operand_dtype": "bfloat16"} if low and low.get("operand_dtype") else None
    scores = jax.nn.sigmoid(mm("bsd,de->bse", xn, w_router, router_low))
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), cfg["top_k"])
    chosen = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, cfg["routed_scaling"] * chosen / (chosen.sum(-1, keepdims=True) + cfg["route_eps"])


def routed_part(xn, w, sel, weights, cfg, low=None):
    """The share's part of the routed experts' sum, and how many (token,
    choice) slots fell on its experts."""
    first = cfg["offset"] * cfg["held"]
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
    return routed_part(xn, w, sel, weights, cfg, low)


def layer(x, w, kind, bias, positions, segment_ids, cfg, low=None):
    """One layer, dense where ``bias`` is None. ``(x, slots)``."""
    eps = cfg["norm_eps"]
    if kind == "conv":
        x = x + short_conv(rms_norm(x, w["conv_norm"], eps), w, segment_ids, cfg, low)
    else:
        x = x + attention(rms_norm(x, w["attn_norm"], eps), w, positions, segment_ids, cfg, low)
    xn = rms_norm(x, w["mlp_norm"], eps)
    if bias is None:
        return x + dense_ff(xn, w, low), jnp.int32(0)
    y, slots = expert_layer(xn, w, bias, cfg, low)
    return x + y, slots


def _group(params, prefix):
    return {n[len(prefix) + 1:]: a for n, a in params.items() if n.startswith(prefix + ".")}


def hidden_states(params, batch, cfg, low=None):
    """``(the last layer's output after the final norm, slots on the share's experts)``."""
    positions, seg = batch["positions"], batch["segment_ids"]
    bias = jnp.asarray(select_bias(cfg))
    x = params["embed"][batch["tokens"]]

    def run(x, w, kind, b):
        return jax.checkpoint(lambda x, w, b: layer(x, w, kind, b, positions, seg, cfg, low))(x, w, b)

    gs = groups(cfg)
    period = [(prefix, kind) for prefix, kind, _dense, stacked in gs if stacked]
    n_periods = gs[cfg["n_dense"]][3]
    for prefix, kind, dense, _stacked in gs:
        if dense:
            x, _ = run(x, _group(params, prefix), kind, None)

    def one_period(x, wb):
        ws, rows = wb
        slots = jnp.int32(0)
        for j, (_prefix, kind) in enumerate(period):
            x, more = run(x, ws[j], kind, rows[j])
            slots = slots + more
        return x, slots

    in_periods = n_periods * len(period)
    x, slots = jax.lax.scan(
        one_period, x,
        ([_group(params, prefix) for prefix, _ in period], bias[:in_periods].reshape(n_periods, len(period), -1)),
    )
    slots = jnp.sum(slots)
    tail = [g for g in gs if not g[2] and not g[3]]
    for i, (prefix, kind, _dense, _stacked) in enumerate(tail):
        x, more = run(x, _group(params, prefix), kind, bias[in_periods + i])
        slots = slots + more
    return rms_norm(x, params["final_norm"], cfg["norm_eps"]), slots


def _masked_ll(h, head, batch, low, block):
    """``(sum of the targets' log-likelihoods, number of targets)`` for
    logits ``h @ head`` predicting the next token, over real targets in the
    predictor's document; the head applied in blocks."""
    tokens, seg = batch["tokens"], batch["segment_ids"]
    b, s = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    real = batch["loss_mask"].astype(jnp.float32)
    mask = jnp.roll(real, -1, axis=1) * (jnp.roll(seg, -1, axis=1) == seg)
    mask = mask.at[:, s - 1:].set(0.0)

    @jax.checkpoint
    def one_block(args):
        hb, tb, mb = args
        logp = jax.nn.log_softmax(mm("bsd,vd->bsv", hb, head, low), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0] * mb)

    nb = s // block if s % block == 0 else 1
    split = lambda a: jnp.moveaxis(a.reshape(b, nb, s // nb, *a.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(one_block, (split(h), split(targets), split(mask)))), mask.sum()


def losses(params, batch, cfg, low=None, block=1024):
    """``(L, {"main", "slots"})``: the mean next-token cross entropy through the tied head."""
    h, slots = hidden_states(params, batch, cfg, low)
    ll, n = _masked_ll(h, params["embed"], batch, low, block)
    main = -ll / jnp.maximum(n, 1.0)
    return main, {"main": main, "slots": slots}


def logits_of(params, batch, cfg, low=None):
    """The logits whole (small sizes: the tests)."""
    h, _ = hidden_states(params, batch, cfg, low)
    return mm("bsd,vd->bsv", h, params["embed"], low)


def train_steps(leaf_fn, names, batches, cfg, hp, low=None, note=lambda text: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights, as ``mla_moe.train_steps`` does, and return the same readings
    (``mtp_loss`` zeros: this model has no second head)."""
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
        out["mtp_loss"].append(0.0)
        out["slots"].append(int(parts["slots"]))
        if out["grad_norm"] is None:
            out["grad_norm"] = {n: float(norm(g[n])) for n in names}
            out["grad_sample"] = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    out["delta_norm"] = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return out
