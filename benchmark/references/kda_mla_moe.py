"""Plain reference of a hybrid Kimi-delta-attention / latent-attention decoder
over group-limited sigmoid-routed experts, as ``bailing_hybrid`` has it.

Float32 ``jax.numpy`` at ``Precision.HIGHEST``, written from the model's
``config.json`` keys, Kimi Linear (arXiv:2510.26692) for the linear layer,
DeepSeek-V2 section 2.1 for the latent attention and DeepSeek-V3 section 2.1.2
for the router. Every layer is ``h = x + operator(RMSNorm(x)); out = h +
feed_forward(RMSNorm(h))`` with, by the layer's entry of ``layer_types``
(``u`` the normed input):

* ``kda``: ``q~, k~, v~ = u W_q, u W_k, u W_v`` in heads of ``kda_dim``; each
  through a depthwise causal convolution of ``conv_kernel`` taps (the last tap
  on the current position; a tap that would reach before the document's first
  token is zero) and a SiLU; ``q = l2norm(q) / sqrt(kda_dim)``, ``k =
  l2norm(k)``; ``a_t = decay_floor * sigmoid(exp(A_log_h) * (u W_f +
  dt_bias))`` a channel, ``beta_t = sigmoid(u W_beta)`` a head; then **token by
  token**, with ``S = 0`` at every document's first token,

      S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_(t-1) + beta_t k_t v_t^T
      o_t = S_t^T q_t

  (``lax.scan`` over the positions in checkpointed blocks of ``KDA_BLOCK``: no
  chunks, no WY form, no triangular system); ``y = W_o(sigmoid(u W_g)[head] *
  RMSNorm_head(o))``;
* ``mla``: ``q = u W_q`` in heads of ``[q_nope ; q_rope]`` (a full-rank query,
  no inner norm); ``[c_kv ; k_r] = u W_kva``, ``[k_nope ; v] = RMSNorm(c_kv)
  W_kvb``; the rotary embedding on the ``d_rope``-wide parts, the one rope key
  shared by every head; scores over ``sqrt(d_nope + d_rope)``, causal, inside
  documents, at the true widths (keys of 192 beside values of 128: nothing is
  padded), a block of queries at a time; ``y = W_o(sigmoid(u W_g)[head] *
  attention)``;

and as feed-forward a SwiGLU in the leading dense layers and afterwards the
expert layer: ``s = sigmoid(m W_r)`` over all the experts, ``b = s + bias``,
the experts in ``n_group`` groups of neighbours, a group's score the sum of its
two largest ``b``, the ``topk_group`` best groups stay, ``sel = top_k`` of ``b``
inside them, ``w_e = scale * s_e / (sum of the chosen s + 1e-20)``, ``y =
SwiGLU_shared(m) + sum over chosen e of w_e SwiGLU_e(m)``. The reference is
given the same share as the chip (``held`` experts from ``offset * held``):
only the chosen experts of the share add to ``y``; every held expert is
computed for every token and weighted by ``w_e`` or zero. A final RMSNorm and
an untied head; the loss is the next-token cross entropy over real tokens
whose target lies in the same document.

It imports nothing of the program and takes its weights by seed under its own
leaf names (``leaf_spec``: ``l<i>.*`` for layer ``i``, none stacked). ``low`` is
a control, as in ``decoder.py`` (the router, stated in float32, gets bfloat16
operands under one; the recurrence's state stays float32), or names a planted
``fault`` (``FAULTS``): the equations above in float32 with one part replaced
by a neighbour's.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.decoder import GRAD_SAMPLE, HIGHEST, _round, adamw_apply, mm, rms_norm, rope, swiglu  # noqa: F401

KDA_BLOCK = 128  # positions of the recurrence kept between two checkpoints
QUERY_BLOCK = 2048  # queries of a head the latent attention scores at a time

# a control's ``fault``: one decay a head, the mean over its channels (gated DeltaNet, not KDA);
# the ``- beta k k^T`` term dropped (gated linear attention); the state carried across a
# document's start; the taps reaching into the previous document; the top-k over all the experts
FAULTS = ("scalar_decay", "no_delta_correction", "state_crosses_documents", "conv_crosses_documents", "no_group_limit")


def _fault(low) -> str:
    fault = (low or {}).get("fault", "")
    if fault and fault not in FAULTS:
        raise ValueError(f"a control's fault is one of {FAULTS}")
    return fault


def layer_leaves(cfg: dict, kind: str, dense: bool) -> dict:
    """One layer's leaves: name -> (shape, std, mean)."""
    d, h = cfg["d_model"], cfg["n_heads"]
    if kind == "kda":
        e = h * cfg["kda_dim"]
        out = {"kda_norm": ((d,), 0.1, 1.0)}
        out.update({n: ((d, e), 0.02, 0.0) for n in ("wq", "wk", "wv", "wf")})
        out.update({n: ((cfg["conv_kernel"], e), 0.02, 0.0) for n in ("q_conv", "k_conv", "v_conv")})
        out.update({
            "w_beta": ((d, h), 0.02, 0.0), "head_gate": ((d, h), 0.02, 0.0),
            # ``benchmark/weights.py`` draws normals. The library draws A_log as log U(1, 16): its mean,
            # 1.957, and a deviation that puts log 16 three deviations out, so that exp(A_log) stays
            # inside the library's bound (the uniform's own 0.673 sent one head in a hundred past 34).
            # dt_bias: the first two moments of the inverse softplus of a step log-uniform in [1e-3, 1e-1]
            "A_log": ((h,), 0.272, 1.957), "dt_bias": ((h, cfg["kda_dim"]), 1.329, -4.605),
            "o_norm": ((cfg["kda_dim"],), 0.1, 1.0), "wo": ((e, d), 0.02, 0.0),
        })
    else:
        dq = cfg["d_nope"] + cfg["d_rope"]
        out = {
            "attn_norm": ((d,), 0.1, 1.0),
            "wq": ((d, h * dq), 0.02, 0.0),
            "wkv_a": ((d, cfg["kv_rank"] + cfg["d_rope"]), 0.02, 0.0),
            "kv_norm": ((cfg["kv_rank"],), 0.1, 1.0),
            "wkv_b": ((cfg["kv_rank"], h * (cfg["d_nope"] + cfg["d_v"])), 0.02, 0.0),
            "head_gate": ((d, h), 0.02, 0.0),
            "wo": ((h * cfg["d_v"], d), 0.02, 0.0),
        }
    out["mlp_norm"] = ((d,), 0.1, 1.0)
    if dense:
        f = cfg["d_ff"]
        out.update({"w_gate": ((d, f), 0.02, 0.0), "w_up": ((d, f), 0.02, 0.0), "w_down": ((f, d), 0.02, 0.0)})
    else:
        f, fs, held = cfg["moe_d_ff"], cfg["moe_d_ff"] * cfg["n_shared"], cfg["held"]
        out.update({
            "router": ((d, cfg["n_experts"]), cfg.get("router_std", 0.02), 0.0),
            "experts_gate": ((held, d, f), 0.02, 0.0), "experts_up": ((held, d, f), 0.02, 0.0),
            "experts_down": ((held, f, d), 0.02, 0.0),
            "shared_gate": ((d, fs), 0.02, 0.0), "shared_up": ((d, fs), 0.02, 0.0), "shared_down": ((fs, d), 0.02, 0.0),
        })
    return out


def leaf_spec(cfg: dict) -> dict:
    """name -> (shape, 0: not stacked, std, mean); what ``benchmark/weights.py``
    draws from. ``cfg`` is the reference's sizes (``sizes`` of the
    configuration's ``.reference.py``)."""
    d, v = cfg["d_model"], cfg["vocab"]
    spec = {"embed": ((v, d), 0, 1.0, 0.0), "final_norm": ((d,), 0, 0.1, 1.0), "lm_head": ((d, v), 0, 0.02, 0.0)}
    for i, kind in enumerate(cfg["layer_types"]):
        for n, (shape, std, mean) in layer_leaves(cfg, kind, i < cfg["n_dense"]).items():
            spec[f"l{i}.{n}"] = (shape, 0, std, mean)
    return spec


def select_bias(cfg: dict) -> np.ndarray:
    """The selection bias, [expert layers, experts]: N(0, bias_std) from the
    configuration's own seed, not the run's."""
    rows = len(cfg["layer_types"]) - cfg["n_dense"]
    rng = np.random.default_rng(cfg["bias_seed"])
    return (cfg["bias_std"] * rng.standard_normal((rows, cfg["n_experts"]))).astype(np.float32)


def starts(segment_ids):
    """[B, S] bool: the first token of the row and of every document."""
    first = jnp.ones_like(segment_ids[:, :1], bool)
    return jnp.concatenate([first, segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)


def taps(z, w, segment_ids, low=None):
    """``c_t = sum over j < K of w[K-1-j] * z[t-j]`` on z [B, S, C], a tap that
    leaves the document zero."""
    k, s = w.shape[0], z.shape[1]
    crosses = _fault(low) == "conv_crosses_documents"
    c = w[k - 1] * z
    for j in range(1, min(k, s)):
        back = jnp.pad(z[:, : s - j], ((0, 0), (j, 0), (0, 0)))
        if not crosses:
            same = jnp.pad(segment_ids[:, : s - j], ((0, 0), (j, 0)), constant_values=-1) == segment_ids
            back = jnp.where(same[..., None], back, 0.0)
        c = c + w[k - 1 - j] * back
    return c


def delta_rule(q, k, v, a, beta, start, low=None):
    """The recurrence token by token: q, k, v, a [B, S, H, D], beta [B, S, H],
    ``start`` [B, S] -> o [B, S, H, D]."""
    b, s, h, d = q.shape
    fault = _fault(low)
    if fault == "state_crosses_documents":
        start = jnp.zeros_like(start)

    def token(state, xs):
        q_, k_, v_, a_, beta_, start_ = xs
        state = jnp.where(start_[:, None, None, None], 0.0, state) * jnp.exp(a_)[..., None]
        seen = 0.0 if fault == "no_delta_correction" else jnp.einsum("bhkv,bhk->bhv", state, k_, precision=HIGHEST)
        state = state + k_[..., None] * (beta_[..., None] * (v_ - seen))[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_, precision=HIGHEST)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    size = KDA_BLOCK if s % KDA_BLOCK == 0 else s
    by_time = lambda x: jnp.moveaxis(x, 1, 0).reshape(s // size, size, *x.shape[:1], *x.shape[2:])
    _, o = jax.lax.scan(block, jnp.zeros((b, h, d, v.shape[-1]), jnp.float32), tuple(map(by_time, (q, k, v, a, beta, start))))
    return jnp.moveaxis(o.reshape(s, b, h, -1), 0, 1)


def kda(u, w, segment_ids, cfg, low=None):
    """``(y, [sum of a, its count])``."""
    b, s, _ = u.shape
    h, d = cfg["n_heads"], cfg["kda_dim"]
    unit = lambda z: z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)
    stream = lambda n: jax.nn.silu(
        taps(mm("bsd,de->bse", u, w[f"w{n}"], low), w[f"{n}_conv"], segment_ids, low)
    ).reshape(b, s, h, d)
    q, k, v = unit(stream("q")) * d**-0.5, unit(stream("k")), stream("v")
    f = mm("bsd,de->bse", u, w["wf"], low).reshape(b, s, h, d)
    a = cfg["decay_floor"] * jax.nn.sigmoid(jnp.exp(w["A_log"])[:, None] * (f + w["dt_bias"]))
    counts = jnp.stack([a.sum(), jnp.float32(a.size)])
    if _fault(low) == "scalar_decay":
        a = jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape)
    beta = jax.nn.sigmoid(mm("bsd,dh->bsh", u, w["w_beta"], low))
    q, k, v = (_round(x, low, "operand_dtype") for x in (q, k, v))
    o = delta_rule(q, k, v, a, beta, starts(segment_ids), low)
    o = rms_norm(o, w["o_norm"], cfg["norm_eps"]) * jax.nn.sigmoid(mm("bsd,dh->bsh", u, w["head_gate"], low))[..., None]
    return mm("bse,ed->bsd", o.reshape(b, s, h * d), w["wo"], low), counts


def latent_attention(u, w, positions, segment_ids, cfg, low=None):
    b, s, _ = u.shape
    h, dn, dr, dv = cfg["n_heads"], cfg["d_nope"], cfg["d_rope"], cfg["d_v"]
    theta = cfg["rope_theta"]
    q = mm("bsd,de->bse", u, w["wq"], low).reshape(b, s, h, dn + dr)
    c_kv = mm("bsd,dr->bsr", u, w["wkv_a"], low)
    k_rope = rope(c_kv[..., None, cfg["kv_rank"]:], positions, theta)
    kv = mm("bsr,re->bse", rms_norm(c_kv[..., : cfg["kv_rank"]], w["kv_norm"], cfg["norm_eps"]), w["wkv_b"], low)
    kv = kv.reshape(b, s, h, dn + dv)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], positions, theta)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
    v = kv[..., dn:]
    nb = s // QUERY_BLOCK if s % QUERY_BLOCK == 0 else 1
    at = jnp.arange(s)

    def one_head(args):
        qh, kh, vh = args  # [B, S, dq], [B, S, dq], [B, S, dv]

        @jax.checkpoint
        def one_block(blk):
            qb, tb, sb = blk  # [B, bq, dq], the queries' row indices [bq] and segment ids [B, bq]
            scores = mm("bqd,bkd->bqk", qb, kh, low) / jnp.sqrt(jnp.float32(dn + dr))
            mask = (tb[None, :, None] >= at[None, None, :]) & (sb[:, :, None] == segment_ids[:, None, :])
            return mm("bqk,bkd->bqd", jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1), vh, low)

        split = lambda x: jnp.moveaxis(x.reshape(b, nb, s // nb, *x.shape[2:]), 1, 0)
        out = jax.lax.map(one_block, (split(qh), at.reshape(nb, s // nb), split(segment_ids)))
        return jnp.moveaxis(out, 0, 1).reshape(b, s, dv)

    out = jax.lax.map(one_head, tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v)))  # [H, B, S, dv]
    out = jnp.moveaxis(out, 0, 2) * jax.nn.sigmoid(mm("bsd,dh->bsh", u, w["head_gate"], low))[..., None]
    return mm("bse,ed->bsd", out.reshape(b, s, h * dv), w["wo"], low)


def route(m, w_router, bias, cfg, low=None):
    """``(sel [B, S, k] expert numbers, weights [B, S, k])`` over all the
    experts, group-limited. Float32; under a control the operands are bfloat16."""
    router_low = {"operand_dtype": "bfloat16"} if low and low.get("operand_dtype") else None
    scores = jax.nn.sigmoid(mm("bsd,de->bse", m, w_router, router_low))
    biased = jax.lax.stop_gradient(scores + bias)
    groups = cfg["n_group"]
    if groups > 1 and _fault(low) != "no_group_limit":
        by_group = biased.reshape(*biased.shape[:-1], groups, -1)
        best_two = jnp.sort(by_group, axis=-1)[..., -2:].sum(-1)  # [B, S, groups]
        cut = jnp.sort(best_two, axis=-1)[..., groups - cfg["topk_group"], None]  # the weakest group that stays
        biased = jnp.where((best_two >= cut)[..., None], by_group, -jnp.inf).reshape(biased.shape)
    _, sel = jax.lax.top_k(biased, cfg["top_k"])
    chosen = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, cfg["routed_scaling"] * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


def expert_layer(m, w, bias, cfg, low=None):
    """``(y, slots on the share's experts)``."""
    sel, weights = route(m, w["router"], bias, cfg, low)
    first = cfg["offset"] * cfg["held"]
    hit = sel[..., None] == (first + jnp.arange(cfg["held"]))  # [B, S, k, held]
    coef = jnp.sum(jnp.where(hit, weights[..., None], 0.0), axis=2)  # a token's weight for each held expert, or 0

    @jax.checkpoint
    def one_expert(y, args):
        wg, wu, wd, c = args
        return y + c[..., None] * swiglu(m, wg, wu, wd, low), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(m),
        (w["experts_gate"], w["experts_up"], w["experts_down"], jnp.moveaxis(coef, -1, 0)),
    )
    if cfg["n_shared"]:
        y = y + swiglu(m, w["shared_gate"], w["shared_up"], w["shared_down"], low)
    return y, jnp.sum(hit)


def layer(x, w, kind, bias, positions, segment_ids, cfg, low=None):
    """One layer: dense where ``bias`` is None. ``(x, slots, [sum of a, count])``."""
    eps = cfg["norm_eps"]
    if kind == "kda":
        y, decay = kda(rms_norm(x, w["kda_norm"], eps), w, segment_ids, cfg, low)
    else:
        y = latent_attention(rms_norm(x, w["attn_norm"], eps), w, positions, segment_ids, cfg, low)
        decay = jnp.zeros(2, jnp.float32)
    h = x + y
    m = rms_norm(h, w["mlp_norm"], eps)
    if bias is None:
        return h + swiglu(m, w["w_gate"], w["w_up"], w["w_down"], low), jnp.int32(0), decay
    y, slots = expert_layer(m, w, bias, cfg, low)
    return h + y, slots, decay


def chunks_cut(segment_ids, chunk: int):
    """``[chunks of the row's grid with a document start inside, chunks]``: a
    start on a chunk's first position cuts nothing."""
    b, s = segment_ids.shape
    inside = starts(segment_ids) & (jnp.arange(s)[None, :] % chunk != 0)
    n = -(-s // chunk)
    hit = jnp.zeros((b, n), bool).at[jnp.arange(b)[:, None], jnp.arange(s)[None, :] // chunk].max(inside)
    return jnp.stack([hit.sum().astype(jnp.float32), jnp.float32(b * n)])


def hidden_states(params, batch, cfg, low=None):
    """``(the last layer's output before the final norm, counts)``: the slots on
    the share's experts and the mean log decay of the ``kda`` layers."""
    tokens, positions, seg = batch["tokens"], batch["positions"], batch["segment_ids"]
    bias = jnp.asarray(select_bias(cfg))
    x = params["embed"][tokens]
    slots, decay = jnp.int32(0), jnp.zeros(2, jnp.float32)
    for i, kind in enumerate(cfg["layer_types"]):
        w = {n[len(f"l{i}."):]: a for n, a in params.items() if n.startswith(f"l{i}.")}
        b_i = None if i < cfg["n_dense"] else bias[i - cfg["n_dense"]]
        run = jax.checkpoint(lambda x, w, kind=kind, b_i=b_i: layer(x, w, kind, b_i, positions, seg, cfg, low))
        x, more, a_sum = run(x, w)
        slots, decay = slots + more, decay + a_sum
    return x, {"slots": slots, "log_decay_mean": decay[0] / jnp.maximum(decay[1], 1.0)}


def _masked_ll(h, head, batch, low, block):
    tokens, seg = batch["tokens"], batch["segment_ids"]
    b, s = tokens.shape
    targets = jnp.roll(tokens, -1, axis=1)
    real = batch["loss_mask"].astype(jnp.float32)
    mask = jnp.roll(real, -1, axis=1) * (jnp.roll(seg, -1, axis=1) == seg)
    mask = mask.at[:, s - 1:].set(0.0)

    @jax.checkpoint
    def one_block(args):
        hb, tb, mb = args
        logp = jax.nn.log_softmax(mm("bsd,dv->bsv", hb, head, low), axis=-1)
        return jnp.sum(jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0] * mb)

    nb = s // block if s % block == 0 else 1
    split = lambda a: jnp.moveaxis(a.reshape(b, nb, s // nb, *a.shape[2:]), 1, 0)
    return jnp.sum(jax.lax.map(one_block, (split(h), split(targets), split(mask)))), mask.sum()


def losses(params, batch, cfg, low=None, block=1024):
    """``(L, {"main", "mtp", "slots", "log_decay_mean"})``: one head, so ``mtp`` is 0."""
    x, counts = hidden_states(params, batch, cfg, low)
    ll, n = _masked_ll(rms_norm(x, params["final_norm"], cfg["norm_eps"]), params["lm_head"], batch, low, block)
    main = -ll / jnp.maximum(n, 1.0)
    return main, dict(counts, main=main, mtp=jnp.float32(0))


def logits_of(params, batch, cfg, low=None):
    """The logits whole (small sizes: the tests)."""
    x, _ = hidden_states(params, batch, cfg, low)
    return mm("bsd,dv->bsv", rms_norm(x, params["final_norm"], cfg["norm_eps"]), params["lm_head"], low)


def train_steps(leaf_fn, names, batches, cfg, hp, low=None, note=lambda text: None):
    """Follow the first ``len(batches)`` optimizer steps from the seeded
    weights, as ``decoder.train_steps`` does, and return the same readings with
    the slots on the share's experts counted each step (``mtp_loss`` is 0: one head)."""
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
    out = {"loss": [], "mtp_loss": [], "slots": [], "log_decay_mean": [], "grad_norm": None, "grad_sample": None}
    grads = []
    for batch in batches:
        t0 = time.perf_counter()
        (_, parts), g = grad_fn(p, batch)
        out["loss"].append(float(parts["main"]))
        note(f"reference loss and gradient in {time.perf_counter() - t0:.1f} s")
        out["mtp_loss"].append(0.0)
        out["slots"].append(int(parts["slots"]))
        out["log_decay_mean"].append(float(parts["log_decay_mean"]))
        if out["grad_norm"] is None:
            out["grad_norm"] = {n: float(norm(g[n])) for n in names}
            out["grad_sample"] = {n: np.asarray(sample(g[n])) for n in names}
        grads.append(g)
        p = step(p, grads)
    out["delta_norm"] = {n: float(dnorm(p[n], leaf_fn(n))) for n in names}
    return out
