"""Plain reference of a dense decoder whose every layer takes EVA attention
(exact keys inside a window, one learned summary a chunk before it, one
softmax over both) under a head of several next-byte predictions: ``evabyte``.

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the model's
``config.json`` keys and the equations of the issue that brought it; no
kernel, no folded windows, no log-sum-exp merge: the two masks written out as
booleans, ``[rows, S]`` and ``[rows, S / C]`` a block of queries, one softmax
over the two sets of scores side by side. A row of ``S`` positions with
document ids ``d(.)``, windows of ``W`` and chunks of ``C`` on the row's grid,
``H`` heads of width ``D``, ``scale = 1 / sqrt(D)``::

    u = RMSNorm(x) (weight 1 + g);  q, k, v = R(u W_q), R(u W_k), u W_v       R: rotary on the whole head, positions inside the document
    chunk c (positions 16c .. 16c+15), M_c = its positions in the document of its last one:
        a_j = softmax over j in M_c of scale k_j . phi_h;   ks_c = sum_j a_j k_j + mu_h;   vs_c = sum_j a_j v_j
    query t, w(t) = t // W:   L(t) = {s : w(s) = w(t), s <= t, d(s) = d(t)}    R(t) = {c : (C c) // W < w(t), d(C c + C - 1) = d(t)}
        o_t = softmax over L(t) and R(t) together of scale q_t . [k_s ; ks_c], times [v_s ; vs_c]
    y = x + o W_o;   x' = y + W_down(silu(W_gate n) * W_up n), n = RMSNorm(y)
    h = RMSNorm(x_last);  logits = h W_head, ``pred_heads`` blocks of ``vocab`` columns: block i predicts the byte i + 1 ahead
    L_i = mean cross entropy over the real positions whose target t + 1 + i lies in t's document;  L = sum_i L_i

``loss`` is ``L_0`` and ``mtp_loss`` the mean of the others (the program weighs
it by their number, which is their sum).

It imports nothing of the program and takes its weights by seed under its own
leaf names (``leaf_spec``: ``l<i>.*`` the ``i``-th layer's, one leaf a layer and
none stacked, as the program holds them). ``low`` is a control, as in ``decoder.py``; it may
also name a planted ``fault`` (``FAULTS``): the equations above with one part
left out or wrong, which the limits of ``correct`` are held against.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.decoder import GRAD_SAMPLE, _round, adamw_apply, mm, rms_norm, rope  # noqa: F401
from benchmark.references.mla_moe import _masked_ll

QUERY_BLOCK = 128  # queries whose explicit scores are held at a time
ROW_BLOCK = 2048  # positions whose feed-forward is held at a time
# a control's ``fault``: no query sees a summary; a chunk's summary runs over the whole chunk and
# every earlier chunk is seen whatever its document; the objective is head 0's loss alone
FAULTS = ("no_summaries", "summaries_cross_documents", "one_head")


def _fault(low) -> str:
    fault = (low or {}).get("fault", "")
    if fault and fault not in FAULTS:
        raise ValueError(f"a control's fault is one of {FAULTS}")
    return fault


def layer_leaves(cfg: dict) -> dict:
    """One layer's leaves: name -> (shape, std, mean). A norm's leaf is ``g``
    of its weight ``1 + g``."""
    d, f, hw = cfg["d_model"], cfg["d_ff"], cfg["n_heads"] * cfg["head_dim"]
    vector = (cfg["n_heads"], cfg["head_dim"])
    return {
        "attn_norm": ((d,), 0.1, 0.0), "mlp_norm": ((d,), 0.1, 0.0),
        "wq": ((d, hw), 0.02, 0.0), "wk": ((d, hw), 0.02, 0.0), "wv": ((d, hw), 0.02, 0.0),
        "phi": (vector, 0.5, 0.0), "mu": (vector, 0.5, 0.0),
        "wo": ((hw, d), 0.02, 0.0),
        "w_gate": ((d, f), 0.02, 0.0), "w_up": ((d, f), 0.02, 0.0), "w_down": ((f, d), 0.02, 0.0),
    }


def leaf_spec(cfg: dict) -> dict:
    """name -> (shape, layers it is stacked over (0 everywhere: no leaf is
    stacked), std, mean); what ``benchmark/weights.py`` draws from. ``cfg`` is
    the reference's sizes."""
    d, v = cfg["d_model"], cfg["vocab"]
    spec = {
        "embed": ((v, d), 0, 1.0, 0.0),
        "final_norm": ((d,), 0, 0.1, 0.0),
        "lm_head": ((d, cfg["pred_heads"] * v), 0, 0.02, 0.0),
    }
    for i in range(cfg["n_layers"]):
        spec.update({f"l{i}.{n}": (shape, 0, std, mean) for n, (shape, std, mean) in layer_leaves(cfg).items()})
    return spec


def layer_params(params: dict, i: int) -> dict:
    """The ``i``-th layer's leaves under their bare names."""
    return {n[len(f"l{i}."):]: a for n, a in params.items() if n.startswith(f"l{i}.")}


def norm(x, g, cfg):
    return rms_norm(x, 1.0 + g, cfg["norm_eps"])


def summaries(k, v, phi, mu, segment_ids, cfg, low=None):
    """``(ks, vs)`` [B, S / C, H, D]: every chunk's summary key and value."""
    b, s, h, d = k.shape
    c = cfg["chunk"]
    kc, vc = k.reshape(b, s // c, c, h, d), v.reshape(b, s // c, c, h, d)
    scores = mm("bnchd,hd->bnch", kc, phi, low) / jnp.sqrt(jnp.float32(d))
    if _fault(low) != "summaries_cross_documents":
        docs = segment_ids.reshape(b, s // c, c)
        scores = jnp.where((docs == docs[:, :, -1:])[..., None], scores, -1e30)
    a = jax.nn.softmax(scores, axis=2)
    return mm("bnch,bnchd->bnhd", a, kc, low) + mu, mm("bnch,bnchd->bnhd", a, vc, low)


def masks(at, segment_ids, cfg, low=None):
    """``(local [B, rows, S], remote [B, rows, S / C])``: what the queries at
    row positions ``at`` [rows] see of the exact keys and of the summaries."""
    s = segment_ids.shape[1]
    w, c = cfg["window"], cfg["chunk"]
    fault = _fault(low)
    cols, chunks = jnp.arange(s), jnp.arange(s // c)
    docs = jnp.take(segment_ids, at, axis=1)  # [B, rows]
    local = (
        (cols[None, :] // w == at[:, None] // w) & (cols[None, :] <= at[:, None])
    )[None] & (docs[:, :, None] == segment_ids[:, None, :])
    remote = jnp.broadcast_to(((chunks * c) // w)[None, :] < (at // w)[:, None], (segment_ids.shape[0], len(at), s // c))
    if fault != "summaries_cross_documents":
        remote = remote & (docs[:, :, None] == segment_ids[:, None, c - 1::c])
    if fault == "no_summaries":
        remote = jnp.zeros_like(remote)
    return local, remote


def attention(u, w, positions, segment_ids, cfg, low=None):
    """The heads' outputs through ``W_o``."""
    b, s, _ = u.shape
    h, hd, theta = cfg["n_heads"], cfg["head_dim"], cfg["rope_theta"]
    q = rope(mm("bsd,de->bse", u, w["wq"], low).reshape(b, s, h, hd), positions, theta)
    k = rope(mm("bsd,de->bse", u, w["wk"], low).reshape(b, s, h, hd), positions, theta)
    v = mm("bsd,de->bse", u, w["wv"], low).reshape(b, s, h, hd)
    ks, vs = summaries(k, v, w["phi"], w["mu"], segment_ids, cfg, low)
    rows = QUERY_BLOCK if s % QUERY_BLOCK == 0 and s > QUERY_BLOCK else s
    n = s // rows

    @jax.checkpoint
    def one_block(args):
        first, qb = args
        local, remote = masks(first + jnp.arange(rows), segment_ids, cfg, low)
        scale = jnp.sqrt(jnp.float32(hd))
        exact = jnp.where(local[:, None], mm("bqhd,bshd->bhqs", qb, k, low) / scale, -1e30)
        summed = jnp.where(remote[:, None], mm("bqhd,bchd->bhqc", qb, ks, low) / scale, -1e30)
        probs = jax.nn.softmax(jnp.concatenate([exact, summed], axis=-1), axis=-1)
        return mm("bhqs,bshd->bqhd", probs[..., :s], v, low) + mm("bhqc,bchd->bqhd", probs[..., s:], vs, low)

    out = jax.lax.map(one_block, (jnp.arange(n) * rows, jnp.moveaxis(q.reshape(b, n, rows, h, hd), 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * hd)
    return mm("bse,ed->bsd", out, w["wo"], low)


def feed_forward(xn, w, low=None):
    """SwiGLU, a block of positions at a time."""
    b, s, d = xn.shape
    rows = ROW_BLOCK if s % ROW_BLOCK == 0 and s > ROW_BLOCK else s

    @jax.checkpoint
    def one_block(xb):
        hidden = jax.nn.silu(mm("bsd,df->bsf", xb, w["w_gate"], low)) * mm("bsd,df->bsf", xb, w["w_up"], low)
        return mm("bsf,fd->bsd", hidden, w["w_down"], low)

    out = jax.lax.map(one_block, jnp.moveaxis(xn.reshape(b, s // rows, rows, d), 1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, d)


def layer(x, w, positions, segment_ids, cfg, low=None):
    y = x + attention(norm(x, w["attn_norm"], cfg), w, positions, segment_ids, cfg, low)
    return y + feed_forward(norm(y, w["mlp_norm"], cfg), w, low)


def hidden_states(params, batch, cfg, low=None):
    """Embedding, every layer, final norm: [B, S, D]."""
    positions, seg = batch["positions"], batch["segment_ids"]
    x = params["embed"][batch["tokens"]]
    for i in range(cfg["n_layers"]):
        x = jax.checkpoint(lambda x, w: layer(x, w, positions, seg, cfg, low))(x, layer_params(params, i))
    return norm(x, params["final_norm"], cfg)


def head_losses(params, batch, cfg, low=None, block=1024):
    """``[pred_heads]``: head ``i``'s mean cross entropy of the byte ``i + 1``
    ahead, over the real positions whose target lies in their document."""
    h = hidden_states(params, batch, cfg, low)
    v = cfg["vocab"]
    out = []
    for i in range(cfg["pred_heads"]):
        ll, n = _masked_ll(h, params["lm_head"][:, i * v:(i + 1) * v], batch, i + 1, low, block)
        out.append(-ll / jnp.maximum(n, 1.0))
    return jnp.stack(out)


def losses(params, batch, cfg, low=None, block=1024):
    """``(L, {"main", "mtp"})``: the sum of the heads' losses, head 0's and
    the mean of the others' (zero where there is one head)."""
    each = head_losses(params, batch, cfg, low, block)
    main = each[0]
    mtp = jnp.mean(each[1:]) if len(each) > 1 else jnp.float32(0)
    total = main if _fault(low) == "one_head" else jnp.sum(each)
    return total, {"main": main, "mtp": mtp}


def logits_of(params, batch, cfg, low=None):
    """The logits whole, ``[B, S, pred_heads, vocab]`` (small sizes: the tests)."""
    h = hidden_states(params, batch, cfg, low)
    out = mm("bsd,dv->bsv", h, params["lm_head"], low)
    return out.reshape(*out.shape[:-1], cfg["pred_heads"], cfg["vocab"])


def seen_entries(batch, cfg) -> tuple:
    """``(summaries, exact keys)`` that the real queries of a batch see, and
    ``(chunks in which two documents meet, chunks)``, counted from the masks
    written out (small sizes: the tests)."""
    seg = jnp.asarray(batch["segment_ids"])
    local, remote = masks(jnp.arange(seg.shape[1]), seg, cfg)
    real = np.asarray(seg > 0)[:, :, None]
    c = cfg["chunk"]
    cut = np.asarray(seg[:, ::c] != seg[:, c - 1::c])
    return (int((np.asarray(remote) & real).sum()), int((np.asarray(local) & real).sum())), (int(cut.sum()), cut.size)


def train_steps(leaf_fn, names, batches, cfg, hp, low=None, note=lambda text: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights, as ``mla_moe.train_steps`` does, and return the same readings
    (``slots`` zeros: this model routes nothing)."""
    grad_fn = jax.jit(jax.value_and_grad(lambda p, b: losses(p, b, cfg, low), has_aux=True))
    step = jax.jit(
        lambda p, gs: jax.tree.map(lambda a, *g: adamw_apply(a, list(g), hp, low), p, *gs),
        donate_argnums=0,
    )
    l2 = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))
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
        out["slots"].append(0)
        if out["grad_norm"] is None:
            out["grad_norm"] = {n: float(l2(g[n])) for n in names}
            out["grad_sample"] = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    out["delta_norm"] = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return out
