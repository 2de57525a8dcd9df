"""Plain reference of a grouped-query decoder whose layers take full or
sliding-window attention by turns, each kind with its own number of query
heads and its own rotary form, a sigmoid gate a head on the attention's
output, over softmax-routed experts beside a shared one: ``laguna``.

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the model's
``config.json`` keys; explicit scores and an explicit mask a block of queries
at a time (``[72 heads, block, S]`` float32). One layer of kind ``κ`` for token
``t`` of a document, ``u = RMSNorm(x)``, ``H`` the kind's query heads::

    q_h = R_κ(norm(W_q u_t)_h)   k_g = R_κ(norm(W_k u_s)_g)   v_g = (W_v u_s)_g        norm: RMSNorm over the head's width
    R_κ: rotary on the first ``width`` of the head's dimensions, halves paired, the others as they are;
         frequencies theta^(-2i/width), or YaRN's: (1 - m_i) f_i / factor + m_i f_i with
         m_i = 1 - clip((i - lo) / (hi - lo), 0, 1), lo = floor(c(beta_fast)), hi = ceil(c(beta_slow)),
         c(r) = width ln(original / (2 pi r)) / (2 ln theta); cos and sin times the attention factor
    vis(t) = { s <= t in t's document }, and t - s < window in a sliding layer (the query's own position counts)
    a_h = softmax over vis(t) of q_h . k_g(h)[s] / sqrt(head_dim);   o_h = sum_s a_h[s] v_g(h)[s]
    y = x + W_o concat_h(sigmoid((W_g u_t)_h) * o_h)
    dense layer:  z = y + SwiGLU(RMSNorm(y))
    expert layer: r = RMSNorm(y); p = softmax(W_r r) over all the experts; E = top_k(p); w_e = scaling * p_e / sum_{E} p
                  z = y + SwiGLU^shared(r) + sum over e in E held here of w_e SwiGLU^e(r)

then a final RMSNorm and an untied head; the loss is the next-token cross
entropy inside the document. The reference is given the same share as the chip
(``held`` experts from ``offset * held``; ``conv_moe.routed_part``: every held
expert computed for every token and weighted by its coefficient or zero).

It imports nothing of the program and takes its weights by seed under its own
leaf names (``leaf_spec``): ``d<i>.*`` the leading dense layers, ``p<j>.*`` the
``j``-th layer of the period stacked over the whole periods, ``t<i>.*`` the
layers over after the last whole period. ``low`` is a control, as in
``decoder.py``; the router, stated in float32, gets bfloat16 operands under one.
A control may also name a planted ``fault`` (``FAULTS``): the equations above
with one part left out, which the limits of ``correct`` are held against.
"""

from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.conv_moe import _group, _masked_ll, dense_ff, plan, routed_part
from benchmark.references.decoder import GRAD_SAMPLE, _round, adamw_apply, mm, rms_norm, swiglu  # noqa: F401

QUERY_BLOCK = 128  # queries whose explicit scores are held at a time
# a control's ``fault``: the sliding layers see every causal key of the document, the heads'
# outputs go ungated, the chosen experts' weights are not multiplied by the routed scaling
FAULTS = ("no_window", "no_gate", "unscaled_route")


def _fault(low) -> str:
    fault = (low or {}).get("fault", "")
    if fault and fault not in FAULTS:
        raise ValueError(f"a control's fault is one of {FAULTS}")
    return fault


def groups(cfg: dict):
    """``[(leaf prefix, kind, query heads, dense?, layers stacked (0: one
    layer, not stacked))]`` in the order the layers run."""
    kinds, heads, n_dense = cfg["layer_types"], cfg["heads_per_layer"], cfg["n_dense"]
    period, n_periods, tail = plan(kinds, n_dense)
    at = n_dense + n_periods * len(period)
    return (
        [(f"d{i}", kinds[i], heads[i], True, 0) for i in range(n_dense)]
        + [(f"p{j}", k, heads[n_dense + j], False, n_periods) for j, k in enumerate(period)]
        + [(f"t{i}", k, heads[at + i], False, 0) for i, k in enumerate(tail)]
    )


def layer_leaves(cfg: dict, h: int, dense: bool) -> dict:
    """One layer's leaves: name -> (shape, std, mean)."""
    d, kv, hd = cfg["d_model"], cfg["n_kv_heads"], cfg["head_dim"]
    out = {
        "attn_norm": ((d,), 0.1, 1.0),
        "wq": ((d, h * hd), 0.02, 0.0), "wk": ((d, kv * hd), 0.02, 0.0), "wv": ((d, kv * hd), 0.02, 0.0),
        "q_norm": ((hd,), 0.1, 1.0), "k_norm": ((hd,), 0.1, 1.0),
        "head_gate": ((d, h), 0.02, 0.0),
        "wo": ((h * hd, d), 0.02, 0.0),
        "mlp_norm": ((d,), 0.1, 1.0),
    }
    if dense:
        f = cfg["d_ff"]
        out.update(w_gate=((d, f), 0.02, 0.0), w_up=((d, f), 0.02, 0.0), w_down=((f, d), 0.02, 0.0))
    else:
        f, fs, held = cfg["moe_d_ff"], cfg["shared_d_ff"], cfg["held"]
        out.update(
            router=((d, cfg["n_experts"]), 0.02, 0.0),
            shared_gate=((d, fs), 0.02, 0.0), shared_up=((d, fs), 0.02, 0.0), shared_down=((fs, d), 0.02, 0.0),
            experts_gate=((held, d, f), 0.02, 0.0), experts_up=((held, d, f), 0.02, 0.0),
            experts_down=((held, f, d), 0.02, 0.0),
        )
    return out


def leaf_spec(cfg: dict) -> dict:
    """name -> (one layer's shape or the whole shape, layers it is stacked
    over (0: not stacked), std, mean); what ``benchmark/weights.py`` draws
    from. ``cfg`` is the reference's sizes."""
    spec = {
        "embed": ((cfg["vocab"], cfg["d_model"]), 0, 1.0, 0.0),
        "final_norm": ((cfg["d_model"],), 0, 0.1, 1.0),
        "lm_head": ((cfg["d_model"], cfg["vocab"]), 0, 0.02, 0.0),
    }
    for prefix, _kind, heads, dense, stacked in groups(cfg):
        spec.update({
            f"{prefix}.{n}": (shape, stacked, std, mean)
            for n, (shape, std, mean) in layer_leaves(cfg, heads, dense).items()
        })
    return spec


def inv_freq(form: dict):
    """The rotary frequencies of one kind of layer, float32 [width / 2]:
    ``theta^(-2i/width)``, under ``yarn`` blended with the interpolated ones
    by the linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times in the original positions (truncated)."""
    width, theta = form["width"], form["theta"]
    half = width // 2
    f = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    yarn = form.get("yarn")
    if not yarn:
        return f

    def turns(r):
        return width * math.log(yarn["original"] / (r * 2 * math.pi)) / (2 * math.log(theta))

    lo, hi = max(math.floor(turns(yarn["beta_fast"])), 0), min(math.ceil(turns(yarn["beta_slow"])), width - 1)
    hi = hi + 0.001 if hi == lo else hi
    m = 1.0 - jnp.clip((jnp.arange(width // 2, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    return (1.0 - m) * f / yarn["factor"] + m * f


def rotary(x, positions, form: dict):
    """[B, S, H, D]: the first ``width`` dimensions rotated by per-token
    positions [B, S], halves paired, cos and sin times the kind's scale; the
    other dimensions unchanged."""
    width = form["width"]
    half = width // 2
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq(form)
    scale = (form.get("yarn") or {}).get("attention_factor", 1.0)
    sin, cos = jnp.sin(ang) * scale, jnp.cos(ang) * scale
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., width:]], axis=-1)


def attention(u, w, kind, h, positions, segment_ids, cfg, low=None):
    """The gated heads' outputs through ``W_o``; ``kind`` gives the rotary
    form and whether the window bounds what a query sees."""
    b, s, _ = u.shape
    kv, hd, eps = cfg["n_kv_heads"], cfg["head_dim"], cfg["norm_eps"]
    form = cfg["rope"][kind]
    window = cfg["window"] if kind == "sliding_attention" and _fault(low) != "no_window" else 0
    q = rotary(rms_norm(mm("bsd,de->bse", u, w["wq"], low).reshape(b, s, h, hd), w["q_norm"], eps), positions, form)
    k = rotary(rms_norm(mm("bsd,de->bse", u, w["wk"], low).reshape(b, s, kv, hd), w["k_norm"], eps), positions, form)
    v = mm("bsd,de->bse", u, w["wv"], low).reshape(b, s, kv, hd)
    gate = jax.nn.sigmoid(mm("bsd,dh->bsh", u, w["head_gate"], low))
    rows = QUERY_BLOCK if s % QUERY_BLOCK == 0 and s > QUERY_BLOCK else s
    n = s // rows
    cols = jnp.arange(s)

    def split(a):
        return jnp.moveaxis(a.reshape(b, n, rows, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one_block(args):
        first, qb, segb = args
        at = first + jnp.arange(rows)
        ahead = at[None, :, None] - cols  # [1, rows, S]: how far the key lies before the query
        vis = (ahead >= 0) & (segb[:, :, None] == segment_ids[:, None, :])
        if window:
            vis = vis & (ahead < window)
        scores = mm("bqkgd,bskd->bkgqs", qb.reshape(b, rows, kv, h // kv, hd), k, low) / jnp.sqrt(jnp.float32(hd))
        probs = jax.nn.softmax(jnp.where(vis[:, None, None], scores, -1e30), axis=-1)
        return mm("bkgqs,bskd->bqkgd", probs, v, low).reshape(b, rows, h, hd)

    out = jax.lax.map(one_block, (jnp.arange(n) * rows, split(q), split(segment_ids)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)
    if _fault(low) != "no_gate":
        out = out * gate[..., None]
    return mm("bse,ed->bsd", out.reshape(b, s, h * hd), w["wo"], low)


def route(xn, w_router, cfg, low=None):
    """``(sel [B, S, k] expert numbers, weights [B, S, k])``: softmax over all
    the experts, the chosen probabilities over their sum, times the routed
    scaling. Float32; under a control the operands are bfloat16."""
    router_low = {"operand_dtype": "bfloat16"} if low and low.get("operand_dtype") else None
    probs = jax.nn.softmax(mm("bsd,de->bse", xn, w_router, router_low), axis=-1)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(probs), cfg["top_k"])
    chosen = jnp.take_along_axis(probs, sel, axis=-1)
    scaling = 1.0 if _fault(low) == "unscaled_route" else cfg["routed_scaling"]
    return sel, scaling * chosen / chosen.sum(-1, keepdims=True)


def layer(x, w, kind, heads, dense, positions, segment_ids, cfg, low=None):
    """One layer: ``(x, slots on the share's experts)``."""
    eps = cfg["norm_eps"]
    x = x + attention(rms_norm(x, w["attn_norm"], eps), w, kind, heads, positions, segment_ids, cfg, low)
    xn = rms_norm(x, w["mlp_norm"], eps)
    if dense:
        return x + dense_ff(xn, w, low), jnp.int32(0)
    y, slots = routed_part(xn, w, *route(xn, w["router"], cfg, low), cfg, low)
    return x + y + swiglu(xn, w["shared_gate"], w["shared_up"], w["shared_down"], low), slots


def hidden_states(params, batch, cfg, low=None):
    """``(the last layer's output after the final norm, slots on the share's experts)``."""
    positions, seg = batch["positions"], batch["segment_ids"]
    x = params["embed"][batch["tokens"]]

    def run(x, w, kind, heads, dense):
        return jax.checkpoint(lambda x, w: layer(x, w, kind, heads, dense, positions, seg, cfg, low))(x, w)

    gs = groups(cfg)
    slots = jnp.int32(0)
    for prefix, kind, heads, dense, _stacked in gs:
        if dense:
            x, _ = run(x, _group(params, prefix), kind, heads, True)
    period = [g for g in gs if g[4]]

    def one_period(x, ws):
        more = jnp.int32(0)
        for (_prefix, kind, heads, _dense, _stacked), w in zip(period, ws):
            x, n = run(x, w, kind, heads, False)
            more = more + n
        return x, more

    x, in_periods = jax.lax.scan(one_period, x, [_group(params, g[0]) for g in period])
    slots = slots + jnp.sum(in_periods)
    for prefix, kind, heads, dense, stacked in gs:
        if not dense and not stacked:
            x, n = run(x, _group(params, prefix), kind, heads, False)
            slots = slots + n
    return rms_norm(x, params["final_norm"], cfg["norm_eps"]), slots


def losses(params, batch, cfg, low=None, block=1024):
    """``(L, {"main", "slots"})``: the mean next-token cross entropy through the untied head."""
    h, slots = hidden_states(params, batch, cfg, low)
    ll, n = _masked_ll(h, params["lm_head"].T, batch, low, block)
    main = -ll / jnp.maximum(n, 1.0)
    return main, {"main": main, "slots": slots}


def logits_of(params, batch, cfg, low=None):
    """The logits whole (small sizes: the tests)."""
    h, _ = hidden_states(params, batch, cfg, low)
    return mm("bsd,dv->bsv", h, params["lm_head"], low)


def window_pairs(batch, window: int):
    """``(pairs inside window, document and causal order, causal pairs inside
    documents)`` of a batch, counted from an explicit mask (small sizes: the
    tests)."""
    seg = np.asarray(batch["segment_ids"])
    at = np.arange(seg.shape[1])
    ahead = at[:, None] - at[None, :]
    vis = (ahead >= 0)[None] & (seg[:, :, None] == seg[:, None, :]) & (seg > 0)[:, :, None]
    return int((vis & (ahead < window)[None]).sum()), int(vis.sum())


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
