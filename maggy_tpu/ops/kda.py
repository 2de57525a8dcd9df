"""Kimi delta attention: the delta rule with a decay a channel, in chunks.

A head carries a state ``S`` ``[d_k, d_v]`` (float32) along the sequence,

    S_t = (I - beta_t k_t k_t^T) Diag(exp(a_t)) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t                          S = 0 at every document's start

with ``a_t <= 0`` the log of a decay for every key channel (Kimi Linear,
arXiv:2510.26692). Written with ``u_t = beta_t (v_t - S_(t-1)^T (exp(a_t) *
k_t))`` the step is ``S_t = Diag(exp(a_t)) S_(t-1) + k_t u_t^T``, so inside a
chunk of ``C`` positions, with ``g`` the running sum of ``a`` from the chunk's
first position and ``S_0`` the state that enters it,

    (I + A) U = beta * (V - (K * exp(g)) S_0)      A[t, i] = beta_t sum_c k_t k_i exp(g_t - g_i), i < t
    O = (Q * exp(g)) S_0 + P U                     P[t, i] = sum_c q_t k_i exp(g_t - g_i), i <= t
    S_C = Diag(exp(g_C)) S_0 + (K * exp(g_C - g))^T U

(the WY form: one unit lower-triangular system a chunk). That splits the work
in two:

* ``_intra``: everything that does not read the state, for all chunks at once
  in batched products: ``A``, ``P``, ``T = (I + A)^-1`` (``unit_lower_inverse``:
  forward substitution in blocks of 16, merged by products), ``W = T (beta * K *
  exp(g))``, ``U~ = T (beta * V)``, the decayed queries and keys;
* the recurrence across chunks, ``U = U~ - W S``, ``O = Qg S + P U``, ``S' =
  dec * S + Kd^T U``: four small products a chunk and head, in order. On a TPU
  the Pallas kernels ``kda_fwd`` and ``kda_bwd`` (grid: row x head parallel,
  chunks ``arbitrary``, the state in VMEM, transposed so that the decay runs
  along the lanes); elsewhere ``jax.lax.scan`` over the same steps.

**Decays without overflow.** ``exp(g_t - g_i)`` is never formed from two large
exponentials: a chunk is cut into sub-chunks of 16, row ``t`` of sub-chunk
``I`` carries ``exp(g_t - r_I)`` (``r_I``: ``g`` at the sub-chunk's first
position, so the exponent is at most 0) and column ``i`` carries ``exp(r_I -
g_i)``, at most 0 for every earlier sub-chunk and at most ``15 * max|a|`` inside
``I``; with ``a >= -5`` (the layer's bounded gate) that is 75, inside float32.
Columns of later sub-chunks are masked anyway and are zeroed before the
exponential.

**Documents.** A document start inside a chunk cuts both parts: ``A`` and ``P``
keep a pair only where both positions lie in one document, the carried state
reaches only the positions before the chunk's first start (``exp(g)`` there is
a sum of ``a`` inside that document), and the state that leaves the chunk is
built from its last document alone. No decay is ever set to minus infinity.

The backward is a rule of its own (``custom_vjp``): the chunk states are made
again from the inputs, the recurrence runs in reverse for the cotangents of
``_intra``'s results, and ``_intra``'s own backward is JAX's. The forward's
output is named (``KDA_RESIDUALS``, as ``FLASH_RESIDUALS`` are) so that a layer
under ``nn.remat`` keeps it and its replay runs no recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
SUB_CHUNK = 16
# the exponent a column may carry inside its own sub-chunk: 15 positions of the layer's bound of 5
MAX_EXPONENT = 80.0
# what the forward keeps for the backward beside its inputs: the output, which the layer's
# replay then need not make again
KDA_RESIDUALS = ("kda_o",)

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_COMPILER_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _rows_inverse(a):
    """``(I + a)^-1`` of strictly lower ``a`` [..., n, n] row by row: row ``i``
    is ``e_i - a[i] @ T`` (the rows of ``T`` from ``i`` on are still the
    identity's, and ``a[i]`` is zero there)."""
    n = a.shape[-1]

    def row(i, t):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=a.ndim - 2, keepdims=False)
        new = jnp.einsum("...j,...jk->...k", a_i, t, precision=_HIGHEST)
        return jax.lax.dynamic_update_index_in_dim(t, jax.lax.dynamic_index_in_dim(t, i, a.ndim - 2, False) - new, i, a.ndim - 2)

    return jax.lax.fori_loop(1, n, row, jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape))


def _blocks_inverse(a, base: int):
    n = a.shape[-1]
    if n <= base:
        return _rows_inverse(a)
    h = n // 2
    t11, t22 = _blocks_inverse(a[..., :h, :h], base), _blocks_inverse(a[..., h:, h:], base)
    t21 = -jnp.einsum("...ij,...jk,...kl->...il", t22, a[..., h:, :h], t11, precision=_HIGHEST)
    top = jnp.concatenate([t11, jnp.zeros_like(a[..., :h, h:])], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([t21, t22], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., n, n] in
    float32, exact to rounding: diagonal blocks of ``SUB_CHUNK`` by forward
    substitution, the blocks below them by ``T21 = -T22 a21 T11``. No power of
    ``a`` is formed (identical keys make those as large as binomials)."""
    return _blocks_inverse(a, SUB_CHUNK)


def _inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _inverse_bwd(t, g):
    return (-jnp.einsum("...ji,...jk,...lk->...il", t, g, t, precision=_HIGHEST),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _mm(spec, x, y):
    return jnp.einsum(spec, x, y, preferred_element_type=_F32)


def _intra(q, k, v, a, beta, doc, prev):
    """What a chunk computes without the state, for all chunks: q, k, v
    [B, H, N, C, d] (products run in their type), ``a`` [B, H, N, C, d]
    float32, ``beta`` [B, H, N, C], ``doc`` [B, N, C] (a number a document,
    rising along the row) and ``prev`` [B, N] (the document of the position
    before the chunk; -1 before the row). ``(W, U~, Qg, P, Kd)`` in q's type
    and ``dec`` [B, H, N, 1, d] float32, the decay of the entering state over
    the whole chunk (0 where the chunk's last document starts inside it)."""
    dt = q.dtype
    c, d = q.shape[-2:]
    sub = SUB_CHUNK if c % SUB_CHUNK == 0 else c
    n_sub = c // sub
    q32, k32, beta = q.astype(_F32), k.astype(_F32), beta.astype(_F32)
    # the running sum as a product with a triangle of ones, float32 to the last bit the MXU's six
    # passes give: ``jnp.cumsum`` is a windowed reduction there, 3.8 ms a pass and layer at the
    # Ling cell's size against the product's 0.2 (tools/profile_kda.py)
    lower = jnp.tril(jnp.ones((c, c), _F32))
    g = jnp.einsum("ts,...sd->...td", lower, a.astype(_F32), precision=_HIGHEST)
    ref = g[..., ::sub, :]  # [.., n_sub, d]: g at each sub-chunk's first position
    row = jnp.exp(g - jnp.repeat(ref, sub, axis=-2))
    blocks = lambda x: x.reshape(*x.shape[:-2], n_sub, sub, d)
    kr, qr = blocks((k32 * row).astype(dt)), blocks((q32 * row).astype(dt))
    at = jnp.arange(c)
    reach = at[None, :] < (jnp.arange(n_sub)[:, None] + 1) * sub  # [n_sub, C]: columns up to the sub-chunk's end
    expo = jnp.where(reach[..., None], jnp.minimum(ref[..., :, None, :] - g[..., None, :, :], MAX_EXPONENT), 0.0)
    kc = jnp.where(reach[..., None], k32[..., None, :, :] * jnp.exp(expo), 0.0).astype(dt)  # [.., n_sub, C, d]
    gram = lambda rows: _mm("...itd,...isd->...its", rows, kc).reshape(*q.shape[:-2], c, c)
    same = (doc[..., :, None] == doc[..., None, :])[:, None]  # [B, 1, N, C, C]
    below = at[:, None] > at[None, :]
    a_mat = jnp.where(same & below, beta[..., None] * gram(kr), 0.0)
    p = jnp.where(same & (below | (at[:, None] == at[None, :])), gram(qr), 0.0).astype(dt)
    t = unit_lower_inverse(a_mat).astype(dt)
    carried = (doc == prev[..., None])[:, None, ..., None]  # [B, 1, N, C, 1]: positions the entering state reaches
    decay = jnp.exp(g)
    kg = jnp.where(carried, k32 * decay, 0.0)
    qg = jnp.where(carried, q32 * decay, 0.0).astype(dt)
    w = _mm("...ts,...sd->...td", t, (beta[..., None] * kg).astype(dt)).astype(dt)
    ut = _mm("...ts,...sd->...td", t, (beta[..., None] * v.astype(_F32)).astype(dt)).astype(dt)
    last = g[..., -1:, :]
    leaves = (doc == doc[..., -1:])[:, None, ..., None]  # positions of the chunk's last document
    kd = jnp.where(leaves, k32 * jnp.exp(jnp.where(leaves, last - g, 0.0)), 0.0).astype(dt)
    through = (doc[..., -1] == prev)[:, None, :, None, None]  # the entering state outlives the chunk
    dec = jnp.where(through, jnp.exp(last), 0.0)
    return w, ut, qg, p, kd, dec


# ---------------------------------------------------------------- the recurrence across chunks


def _chunks_first(x):
    return jnp.moveaxis(x, 2, 0)


def _scan_fwd(w, ut, qg, p, kd, dec, with_states: bool):
    """The recurrence in ``jax.numpy``: ``(O [B, H, N, C, d_v], states [B, H,
    N, d_k, d_v] float32 or None)``, ``states[n]`` the state entering chunk ``n``."""
    dt = w.dtype
    b, h, _, _, dk = w.shape

    def step(s, xs):
        w_, ut_, qg_, p_, kd_, dec_ = xs
        sb = s.astype(dt)
        u = (ut_.astype(_F32) - _mm("bhtk,bhkv->bhtv", w_, sb)).astype(dt)
        o = _mm("bhtk,bhkv->bhtv", qg_, sb) + _mm("bhts,bhsv->bhtv", p_, u)
        s_out = jnp.swapaxes(dec_, -1, -2) * s + _mm("bhtk,bhtv->bhkv", kd_, u)
        return s_out, (o.astype(dt), s if with_states else None)

    s0 = jnp.zeros((b, h, dk, ut.shape[-1]), _F32)
    _, (o, states) = jax.lax.scan(step, s0, tuple(map(_chunks_first, (w, ut, qg, p, kd, dec))))
    return jnp.moveaxis(o, 0, 2), None if states is None else jnp.moveaxis(states, 0, 2)


def _scan_bwd(do, w, ut, qg, p, kd, dec, states):
    """The recurrence in reverse: the cotangents of ``_intra``'s six results
    from ``do`` and the states entering the chunks."""
    dt = w.dtype

    def step(ds, xs):
        do_, w_, ut_, qg_, p_, kd_, dec_, s = xs
        sb, dsb = s.astype(dt), ds.astype(dt)
        u = (ut_.astype(_F32) - _mm("bhtk,bhkv->bhtv", w_, sb)).astype(dt)
        du = _mm("bhts,bhtv->bhsv", p_, do_) + _mm("bhtk,bhkv->bhtv", kd_, dsb)
        dub = du.astype(dt)
        dqg = _mm("bhtv,bhkv->bhtk", do_, sb)
        dp = _mm("bhtv,bhsv->bhts", do_, u)
        dw = -_mm("bhtv,bhkv->bhtk", dub, sb)
        dkd = _mm("bhtv,bhkv->bhtk", u, dsb)
        ddec = jnp.sum(ds * s, axis=-1)[..., None, :]
        ds_in = _mm("bhtk,bhtv->bhkv", qg_, do_) + jnp.swapaxes(dec_, -1, -2) * ds - _mm("bhtk,bhtv->bhkv", w_, dub)
        return ds_in, (dw.astype(dt), dub, dqg.astype(dt), dp.astype(dt), dkd.astype(dt), ddec)

    ds0 = jnp.zeros(states.shape[:2] + states.shape[3:], _F32)
    xs = tuple(map(_chunks_first, (do, w, ut, qg, p, kd, dec, states)))
    _, out = jax.lax.scan(step, ds0, xs, reverse=True)
    return tuple(jnp.moveaxis(x, 0, 2) for x in out)


def _fwd_kernel(w_ref, ut_ref, qg_ref, p_ref, kd_ref, dec_ref, o_ref, *rest, with_states: bool):
    """One chunk of one head. The state lives in VMEM as ``S^T`` [d_v, d_k]:
    the decay then scales its lanes."""
    st = rest[-1]
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        st[...] = jnp.zeros_like(st)

    s = st[...]
    if with_states:
        rest[0][...] = s
    sb = s.astype(dt)
    nt = (((1,), (1,)), ((), ()))  # x [C, k] by S^T [v, k] over k
    u = (ut_ref[...].astype(_F32) - jax.lax.dot_general(w_ref[...], sb, nt, preferred_element_type=_F32)).astype(dt)
    o = jax.lax.dot_general(qg_ref[...], sb, nt, preferred_element_type=_F32)
    o = o + jnp.dot(p_ref[...], u, preferred_element_type=_F32)
    o_ref[...] = o.astype(o_ref.dtype)
    tn = (((0,), (0,)), ((), ()))  # U^T Kd [v, k]
    st[...] = s * dec_ref[...] + jax.lax.dot_general(u, kd_ref[...], tn, preferred_element_type=_F32)


def _bwd_kernel(do_ref, w_ref, ut_ref, qg_ref, p_ref, kd_ref, dec_ref, s_ref,
                dw_ref, du_ref, dqg_ref, dp_ref, dkd_ref, ddec_ref, dst):
    """One chunk of one head, chunks in reverse: ``dst`` is the cotangent of
    the state leaving the chunk, transposed as the state is."""
    dt = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        dst[...] = jnp.zeros_like(dst)

    s, ds = s_ref[...], dst[...]
    sb, dsb = s.astype(dt), ds.astype(dt)
    do = do_ref[...]
    nt = (((1,), (1,)), ((), ()))
    tn = (((0,), (0,)), ((), ()))
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=_F32)
    u = (ut_ref[...].astype(_F32) - dot(w_ref[...], sb, nt)).astype(dt)
    du = dot(p_ref[...], do, tn) + dot(kd_ref[...], dsb, nt)  # P^T dO + Kd dS
    dub = du.astype(dt)
    du_ref[...] = dub
    dqg_ref[...] = jnp.dot(do, sb, preferred_element_type=_F32).astype(dt)  # dO S^T
    dp_ref[...] = dot(do, u, nt).astype(dt)  # dO U^T
    dw_ref[...] = (-jnp.dot(dub, sb, preferred_element_type=_F32)).astype(dt)
    dkd_ref[...] = jnp.dot(u, dsb, preferred_element_type=_F32).astype(dt)  # U dS^T
    ddec_ref[...] = jnp.sum(ds * s, axis=0, keepdims=True)
    dst[...] = dot(do, qg_ref[...], tn) + ds * dec_ref[...] - dot(dub, w_ref[...], tn)


def _flat(x):
    return x.reshape(-1, *x.shape[2:])


def _spec(x, reverse: int = 0):
    """A chunk of one head of ``x`` [BH, N, r, c]; ``reverse``: the number of chunks, read last first."""
    at = (lambda i, n: (i, reverse - 1 - n, 0, 0)) if reverse else (lambda i, n: (i, n, 0, 0))
    return pl.BlockSpec((None, None, *x.shape[2:]), at)


def _pallas_fwd(w, ut, qg, p, kd, dec, with_states: bool, interpret: bool = False):
    """``_scan_fwd`` as the kernel ``kda_fwd``."""
    b, h, n, c, dk = w.shape
    dv = ut.shape[-1]
    ins = tuple(map(_flat, (w, ut, qg, p, kd, dec)))
    out_shape = [jax.ShapeDtypeStruct((b * h, n, c, dv), w.dtype)]
    if with_states:
        out_shape.append(jax.ShapeDtypeStruct((b * h, n, dv, dk), _F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, with_states=with_states),
        grid=(b * h, n),
        in_specs=[_spec(x) for x in ins],
        out_specs=[_spec(x) for x in out_shape],
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        name="kda_fwd",
        interpret=interpret,
    )(*ins)
    o = out[0].reshape(b, h, n, c, dv)
    # the kernel's states are S^T; the scan's are S
    return o, jnp.swapaxes(out[1].reshape(b, h, n, dv, dk), -1, -2) if with_states else None


def _pallas_bwd(do, w, ut, qg, p, kd, dec, states, interpret: bool = False):
    """``_scan_bwd`` as the kernel ``kda_bwd``."""
    b, h, n, c, dk = w.shape
    dv = ut.shape[-1]
    ins = tuple(map(_flat, (do, w, ut, qg, p, kd, dec, jnp.swapaxes(states, -1, -2))))
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in ins[1:6]]
    outs.append(jax.ShapeDtypeStruct((b * h, n, 1, dk), _F32))
    out = pl.pallas_call(
        _bwd_kernel,
        grid=(b * h, n),
        in_specs=[_spec(x, n) for x in ins],
        out_specs=[_spec(x, n) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_COMPILER_PARAMS,
        name="kda_bwd",
        interpret=interpret,
    )(*ins)
    return tuple(x.reshape(b, h, *x.shape[1:]) for x in out)


def kernel_form(c: int, dk: int, dv: int, dtype) -> str:
    """``pallas`` where the recurrence runs as the kernels (a TPU, chunks and
    widths that fill the tiles), else ``xla``: the same steps under ``lax.scan``."""
    tiles = c % 16 == 0 and dk % 128 == 0 and dv % 128 == 0 and dtype in (jnp.bfloat16, jnp.float32)
    return "pallas" if jax.default_backend() == "tpu" and tiles else "xla"


@functools.lru_cache(maxsize=None)
def _core(form: str, interpret: bool):
    pallas = form == "pallas"
    fwd_pass = functools.partial(_pallas_fwd, interpret=interpret) if pallas else _scan_fwd
    bwd_pass = functools.partial(_pallas_bwd, interpret=interpret) if pallas else _scan_bwd

    @jax.custom_vjp
    def core(q, k, v, a, beta, doc, prev):
        return fwd_pass(*_intra(q, k, v, a, beta, doc, prev), False)[0]

    def core_fwd(q, k, v, a, beta, doc, prev):
        o = fwd_pass(*_intra(q, k, v, a, beta, doc, prev), False)[0]
        return checkpoint_name(o, KDA_RESIDUALS[0]), (q, k, v, a, beta, doc, prev)

    def core_bwd(res, do):
        q, k, v, a, beta, doc, prev = res
        intra, back = jax.vjp(lambda *xs: _intra(*xs, doc, prev), q, k, v, a, beta)
        d_intra = bwd_pass(do.astype(q.dtype), *intra, fwd_pass(*intra, True)[1])
        return (*back(d_intra), None, None)

    core.defvjp(core_fwd, core_bwd)
    return core


def documents(segment_ids, length: int, batch: int):
    """A number a document, rising along the row ([B, S] int32), from segment
    ids of any numbering: a new one wherever the id changes (padding, id 0, is
    a document of its own and touches no other)."""
    if segment_ids is None:
        return jnp.zeros((batch, length), jnp.int32)
    starts = segment_ids[:, 1:] != segment_ids[:, :-1]
    return jnp.pad(jnp.cumsum(starts.astype(jnp.int32), axis=1), ((0, 0), (1, 0)))


def chunks_cut(segment_ids, chunk: int = CHUNK):
    """``[chunks with a document start inside, chunks]`` of a batch, int32: a
    start on a chunk's first position cuts nothing."""
    b, s = segment_ids.shape
    n = -(-s // chunk)
    starts = jnp.pad(segment_ids[:, 1:] != segment_ids[:, :-1], ((0, 0), (1, n * chunk - s)))
    inside = starts.reshape(b, n, chunk)[..., 1:].any(-1)
    return jnp.stack([inside.sum(dtype=jnp.int32), jnp.int32(b * n)])


def kda(q, k, v, a, beta, segment_ids=None, chunk: int = CHUNK, *, form: Optional[str] = None,
        interpret: Optional[bool] = None):
    """The recurrence of the module docstring on q, k [B, S, H, d_k], v [B, S,
    H, d_v], ``a`` [B, S, H, d_k] (the log decays, at most 0 and at least
    ``-MAX_EXPONENT / 15``), ``beta`` [B, S, H] and ``segment_ids`` [B, S]
    (None: one document a row): ``o`` [B, S, H, d_v] in q's type. The products
    run in q's type with float32 accumulation; ``a``, its running sums, the
    triangular inverse and the carried state are float32. ``S`` need not be a
    multiple of ``chunk``: the row is padded with a document of its own. The
    backward makes the state entering every chunk again. ``form``: ``pallas``
    or ``xla`` (None: ``kernel_form``)."""
    b, s, h, dk = q.shape
    if chunk > SUB_CHUNK and chunk % SUB_CHUNK:
        raise ValueError(f"a chunk over {SUB_CHUNK} positions is a multiple of it, got {chunk}")
    form = form or kernel_form(chunk, dk, v.shape[-1], q.dtype)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    doc = documents(segment_ids, s, b)
    pad = -s % chunk
    if pad:
        doc = jnp.concatenate([doc, jnp.broadcast_to(doc[:, -1:] + 1, (b, pad))], axis=1)
        q, k, v, a, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2)) for x in (q, k, v, a, beta))
    n = (s + pad) // chunk
    split = lambda x: jnp.moveaxis(x.reshape(b, n, chunk, *x.shape[2:]), 3, 1)  # [B, H, N, C, ...]
    doc = doc.reshape(b, n, chunk)
    prev = jnp.concatenate([jnp.full((b, 1), -1, jnp.int32), doc[:, :-1, -1]], axis=1)
    o = _core(form, interpret)(split(q), split(k), split(v), split(a.astype(_F32)), split(beta), doc, prev)
    return jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, -1)[:, :s]
