"""Plain reference of a grouped-query decoder whose router reads a layer's
input before attention, whose experts are ReLU-gated, and whose global layers
have no positional embedding beside windowed ones that rotate:
``smallthinker`` (SmallThinker-21BA3B-Instruct).

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the model's
``config.json`` keys and the family's published form; explicit scores and an
explicit mask a block of queries at a time (``[28 heads, block, S]`` float32 is
235 MB at 128 x 16,384). Layer ``l`` for token ``t`` of a document, input ``x``::

    r = x_t W_r                       the router reads the layer's input, before any norm
    p = softmax(r) over all the experts;  E = top_k(p);  w_e = p_e / sum_{E} p
    u = RMSNorm(x)
    q_h = R_l((W_q u_t)_h)   k_g = R_l((W_k u_s)_g)   v_g = (W_v u_s)_g
    R_l: rotary on the whole head, halves paired (i, i + D/2), base theta, where rope_layout[l]; else the identity
    vis(t) = { s <= t in t's document }, and t - s < window where sliding_window_layout[l] (the query's own position counts)
    a_h = softmax over vis(t) of q_h . k_g(h)[s] / sqrt(head_dim);   h = x + W_o concat_h(sum_s a_h[s] v_g(h)[s])
    m = RMSNorm(h)
    out = h + sum over e in E held here of w_e (relu(m W_gate,e) * (m W_up,e)) W_down,e

then a final RMSNorm and an untied head; the loss is the next-token cross
entropy inside the document. The reference is given the same share as the chip
(``held`` experts from ``offset * held``): every held expert is computed for
every token and weighted by its coefficient or zero (``routed_part``), which
also counts the slots on the share's experts and, of their hidden activations
``relu(m W_gate,e)``, those that are exactly zero.

It imports nothing of the program and nothing of another model's layer; it
takes its weights by seed under its own leaf names (``leaf_spec``): ``p<j>.*``
the ``j``-th layer of the period stacked over the whole periods, ``t<i>.*`` the
layers over after the last whole period. ``low`` is a control, as in
``decoder.py``; the router, stated in float32, gets bfloat16 operands under
one. A control may also name a planted ``fault`` (``FAULTS``): the equations
above with one part replaced, which the limits of ``correct`` are held against.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.conv_moe import _group, _masked_ll, plan
from benchmark.references.decoder import GRAD_SAMPLE, _round, adamw_apply, mm, rms_norm, rope  # noqa: F401

QUERY_BLOCK = 128  # queries whose explicit scores are held at a time
# a control's ``fault``: the router reads ``RMSNorm(h)`` after attention (where the other expert
# models route), the experts' gate is SiLU, the global layers rotate as the windowed ones do,
# the windowed layers see every causal key of the document
FAULTS = ("route_after_attention", "silu_experts", "rope_on_global", "no_window")


def _fault(low) -> str:
    fault = (low or {}).get("fault", "")
    if fault and fault not in FAULTS:
        raise ValueError(f"a control's fault is one of {FAULTS}")
    return fault


def groups(cfg: dict):
    """``[(leaf prefix, windowed?, rotated?, layers stacked (0: one layer,
    not stacked))]`` in the order the layers run: the period is the shortest
    run of (windowed, rotated) that repeats into the two layouts."""
    forms = list(zip(cfg["window_layout"], cfg["rope_layout"]))
    period, n_periods, tail = plan(forms, 0)
    return (
        [(f"p{j}", bool(w), bool(r), n_periods) for j, (w, r) in enumerate(period)]
        + [(f"t{i}", bool(w), bool(r), 0) for i, (w, r) in enumerate(tail)]
    )


def layer_leaves(cfg: dict) -> dict:
    """One layer's leaves: name -> (shape, std, mean). No bias, no norm a head."""
    d, h, kv, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    f, held = cfg["moe_d_ff"], cfg["held"]
    return {
        "router": ((d, cfg["n_experts"]), 0.02, 0.0),
        "attn_norm": ((d,), 0.1, 1.0),
        "wq": ((d, h * hd), 0.02, 0.0), "wk": ((d, kv * hd), 0.02, 0.0), "wv": ((d, kv * hd), 0.02, 0.0),
        "wo": ((h * hd, d), 0.02, 0.0),
        "mlp_norm": ((d,), 0.1, 1.0),
        "experts_gate": ((held, d, f), 0.02, 0.0), "experts_up": ((held, d, f), 0.02, 0.0),
        "experts_down": ((held, f, d), 0.02, 0.0),
    }


def leaf_spec(cfg: dict) -> dict:
    """name -> (one layer's shape or the whole shape, layers it is stacked
    over (0: not stacked), std, mean); what ``benchmark/weights.py`` draws
    from. ``cfg`` is the reference's sizes."""
    spec = {
        "embed": ((cfg["vocab"], cfg["d_model"]), 0, 1.0, 0.0),
        "final_norm": ((cfg["d_model"],), 0, 0.1, 1.0),
        "lm_head": ((cfg["d_model"], cfg["vocab"]), 0, 0.02, 0.0),
    }
    for prefix, _windowed, _rotated, stacked in groups(cfg):
        spec.update({
            f"{prefix}.{n}": (shape, stacked, std, mean) for n, (shape, std, mean) in layer_leaves(cfg).items()
        })
    return spec


def attention(u, w, windowed, rotated, positions, segment_ids, cfg, low=None):
    """The heads' outputs through ``W_o``: a windowed layer bounds what a
    query sees, a rotated one turns queries and keys by their positions, and
    a layer that is neither sees every causal key with no positions at all."""
    b, s, _ = u.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    window = cfg["window"] if windowed and _fault(low) != "no_window" else 0
    q = mm("bsd,de->bse", u, w["wq"], low).reshape(b, s, h, hd)
    k = mm("bsd,de->bse", u, w["wk"], low).reshape(b, s, kv, hd)
    v = mm("bsd,de->bse", u, w["wv"], low).reshape(b, s, kv, hd)
    if rotated or _fault(low) == "rope_on_global":
        q, k = rope(q, positions, cfg["rope_theta"]), rope(k, positions, cfg["rope_theta"])
    rows = QUERY_BLOCK if s % QUERY_BLOCK == 0 and s > QUERY_BLOCK else s
    n = s // rows
    cols = jnp.arange(s)

    def split(a):
        return jnp.moveaxis(a.reshape(b, n, rows, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_block(args):
        first, qb, segb = args
        ahead = (first + jnp.arange(rows))[None, :, None] - cols  # [1, rows, S]: how far the key lies before the query
        vis = (ahead >= 0) & (segb[:, :, None] == segment_ids[:, None, :])
        if window:
            vis = vis & (ahead < window)
        scores = mm("bqkgd,bskd->bkgqs", qb.reshape(b, rows, kv, h // kv, hd), k, low) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(vis[:, None, None], scores, -1e30), axis=-1)
        return mm("bkgqs,bskd->bqkgd", probs, v, low).reshape(b, rows, h * hd)

    out = jax.lax.map(one_block, (jnp.arange(n) * rows, split(q), split(segment_ids)))
    return mm("bse,ed->bsd", jnp.moveaxis(out, 0, 1).reshape(b, s, h * hd), w["wo"], low)


def route(x, w_router, cfg, low=None):
    """``(sel [B, S, k] expert numbers, weights [B, S, k])`` from whatever the
    router reads: softmax over all the experts, the chosen probabilities over
    their sum. Float32; under a control the operands are bfloat16."""
    router_low = {"operand_dtype": "bfloat16"} if low and low.get("operand_dtype") else None
    probs = jax.nn.softmax(mm("bsd,de->bse", x, w_router, router_low), axis=-1)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(probs), cfg["top_k"])
    chosen = jnp.take_along_axis(probs, sel, axis=-1)
    return sel, chosen / chosen.sum(-1, keepdims=True)


def routed_part(m, w, sel, weights, cfg, low=None):
    """``(the share's part of the routed experts' sum, [slots on its experts,
    of their hidden activations relu(m W_gate) those exactly zero])``."""
    first = cfg["offset"] * cfg["held"]
    hit = sel[..., None] == (first + jnp.arange(cfg["held"]))  # [B, S, k, held]
    coef = jnp.sum(jnp.where(hit, weights[..., None], 0.0), axis=2)
    act = jax.nn.silu if _fault(low) == "silu_experts" else jax.nn.relu

    @jax.checkpoint
    def one_expert(carry, args):
        y, zeros = carry
        wg, wu, wd, c, on = args
        gate = act(mm("bsd,df->bsf", m, wg, low))
        out = mm("bsf,fd->bsd", gate * mm("bsd,df->bsf", m, wu, low), wd, low)
        return (y + c[..., None] * out, zeros + jnp.sum((gate == 0) & on[..., None], dtype=jnp.int32)), None

    (y, zeros), _ = jax.lax.scan(
        one_expert, (jnp.zeros_like(m), jnp.int32(0)),
        (w["experts_gate"], w["experts_up"], w["experts_down"], jnp.moveaxis(coef, -1, 0),
         jnp.moveaxis(hit.any(2), -1, 0)),
    )
    return y, jnp.stack([jnp.sum(hit, dtype=jnp.int32), zeros])


def layer(x, w, windowed, rotated, positions, segment_ids, cfg, low=None):
    """One layer: ``(out, [slots on the share's experts, zero hidden activations])``."""
    eps = cfg["norm_eps"]
    late = _fault(low) == "route_after_attention"
    picked = None if late else route(x, w["router"], cfg, low)
    h = x + attention(rms_norm(x, w["attn_norm"], eps), w, windowed, rotated, positions, segment_ids, cfg, low)
    m = rms_norm(h, w["mlp_norm"], eps)
    y, counts = routed_part(m, w, *(route(m, w["router"], cfg, low) if late else picked), cfg, low)
    return h + y, counts


def hidden_states(params, batch, cfg, low=None):
    """``(the last layer's output after the final norm, [layers, 2] counts)``."""
    positions, seg = batch["positions"], batch["segment_ids"]
    x = params["embed"][batch["tokens"]]

    def run(x, w, windowed, rotated):
        return jax.checkpoint(lambda x, w: layer(x, w, windowed, rotated, positions, seg, cfg, low))(x, w)

    gs = groups(cfg)
    period = [g for g in gs if g[3]]

    def one_period(x, ws):
        counts = []
        for (_prefix, windowed, rotated, _stacked), w in zip(period, ws):
            x, c = run(x, w, windowed, rotated)
            counts.append(c)
        return x, jnp.stack(counts)

    x, counts = jax.lax.scan(one_period, x, [_group(params, g[0]) for g in period])
    counts = [counts.reshape(-1, 2)]
    for prefix, windowed, rotated, stacked in gs:
        if not stacked:
            x, c = run(x, _group(params, prefix), windowed, rotated)
            counts.append(c[None])
    return rms_norm(x, params["final_norm"], cfg["norm_eps"]), jnp.concatenate(counts)


def hidden_zero_share(counts, cfg):
    """Of the hidden activations of the slots on held experts the share that
    is exactly zero, a mean over the layers (``[layers, 2]`` counts)."""
    counts = counts.astype(jnp.float32)
    return jnp.mean(counts[:, 1] / jnp.maximum(counts[:, 0] * cfg["moe_d_ff"], 1.0))


def losses(params, batch, cfg, low=None, block=1024):
    """``(L, {"main", "slots", "hidden_zero_share"})``: the mean next-token
    cross entropy through the untied head."""
    h, counts = hidden_states(params, batch, cfg, low)
    ll, n = _masked_ll(h, params["lm_head"].T, batch, low, block)
    main = -ll / jnp.maximum(n, 1.0)
    return main, {"main": main, "slots": counts[:, 0].sum(), "hidden_zero_share": hidden_zero_share(counts, cfg)}


def logits_of(params, batch, cfg, low=None):
    """The logits whole (small sizes: the tests)."""
    h, _ = hidden_states(params, batch, cfg, low)
    return mm("bsd,dv->bsv", h, params["lm_head"], low)


def train_steps(leaf_fn, names, batches, cfg, hp, low=None, note=lambda text: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights (``leaf_fn(name)`` makes one leaf): each step's loss and slots on
    held experts, the per-leaf norm of the first gradient, ``GRAD_SAMPLE``
    evenly strided elements of each of its leaves, and the per-leaf norm of
    the parameters' change after the last step (``mtp_loss`` zeros: this
    model has no second head)."""
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
    out = {"loss": [], "mtp_loss": [], "slots": [], "hidden_zero_share": [], "grad_norm": None, "grad_sample": None}
    grads = []
    for batch in batches:
        t0 = time.perf_counter()
        (_, parts), g = grad_fn(p, batch)
        out["loss"].append(float(parts["main"]))
        note(f"reference loss and gradient in {time.perf_counter() - t0:.1f} s")
        out["mtp_loss"].append(0.0)
        out["slots"].append(int(parts["slots"]))
        out["hidden_zero_share"].append(float(parts["hidden_zero_share"]))
        if out["grad_norm"] is None:
            out["grad_norm"] = {n: float(norm(g[n])) for n in names}
            out["grad_sample"] = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    out["delta_norm"] = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return out
