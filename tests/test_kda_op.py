"""The chunked delta rule with a decay a channel (``maggy_tpu/ops/kda.py``)
against the recurrence token by token (the plain reference's
``delta_rule``: ``lax.scan`` over positions): forward and every cotangent, at chunks
that do and do not divide the row, with documents that start on chunk edges
and inside chunks, the recurrence across chunks as ``lax.scan`` and as the
Pallas kernels ``kda_fwd`` / ``kda_bwd`` in the interpreter, the chunk states
kept or made again; a document gives what it gives alone; the triangular
inverse; the count of chunks a document's start cuts. Float32 throughout."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.references import kda_mla_moe as reference  # noqa: E402
from maggy_tpu.ops import kda as ops_kda  # noqa: E402
from test_laguna_window import packed  # noqa: E402

S = 128
DOCS = [[16, 5, 43, 2, 37], [32, 1, 63, 32]]  # starts on chunk edges (16, 32, 96) and inside chunks; row 0 ends in padding


@pytest.fixture(scope="module")
def batch():
    return packed(DOCS, np.random.default_rng(5))


def kda_inputs(key, b, s, h, d, floor=-5.0):
    ks = jax.random.split(key, 5)
    unit = lambda z: z / jnp.linalg.norm(z, axis=-1, keepdims=True)
    q, k, v = (jax.random.normal(ks[i], (b, s, h, d), jnp.float32) for i in range(3))
    a = floor * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (b, s, h, d), jnp.float32))
    return unit(q) * d**-0.5, unit(k), v, a, jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h), jnp.float32))



@pytest.mark.parametrize("n", [5, 16, 32, 48, 64])
def test_the_unit_lower_inverse_and_its_gradient(n):
    a = 0.15 * jnp.tril(jax.random.normal(jax.random.key(n), (3, n, n), jnp.float32), -1)
    t = ops_kda.unit_lower_inverse(a)
    eye = jnp.eye(n)
    np.testing.assert_allclose(jnp.einsum("bij,bjk->bik", eye + a, t, precision="highest"), jnp.broadcast_to(eye, a.shape), atol=2e-4)
    # identical keys with no decay: powers of ``a`` are binomials up to 1e17, the inverse is the bidiagonal [1, -1]
    ones = jnp.tril(jnp.ones((n, n), jnp.float32), -1)
    np.testing.assert_array_equal(ops_kda.unit_lower_inverse(ones), eye - jnp.eye(n, k=-1))
    probe = jax.random.normal(jax.random.key(1), a.shape, jnp.float32)
    got = jax.grad(lambda a: (ops_kda.unit_lower_inverse(a) * probe).sum())(a)
    want = jax.grad(lambda a: (jnp.linalg.inv(eye + a) * probe).sum())(a)
    np.testing.assert_allclose(jnp.tril(got, -1), jnp.tril(want, -1), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("chunk,form", [
    (16, "xla"), (32, "xla"), (64, "xla"), (12, "xla"), (48, "xla"), (32, "pallas"), (16, "pallas"),
], ids=str)
def test_the_chunked_delta_rule_against_the_recurrence_token_by_token(batch, chunk, form):
    """Forward and every cotangent, with documents starting on chunk edges and
    inside chunks, at chunks that divide the row of 128 and at 12 and 48, which
    do not; both forms of the recurrence across chunks (``pallas``: the kernels
    ``kda_fwd`` / ``kda_bwd`` in the interpreter)."""
    seg = batch["segment_ids"]
    q, k, v, a, beta = kda_inputs(jax.random.key(3), 2, S, 2, 8)
    probe = jax.random.normal(jax.random.key(4), v.shape, jnp.float32)
    run = lambda *x: ops_kda.kda(*x, seg, chunk, form=form, interpret=True)
    both = lambda f: jax.jit(lambda *x: (lambda out, back: (out, back(probe)))(*jax.vjp(f, *x)))(q, k, v, a, beta)
    want, grads_want = both(lambda *x: reference.delta_rule(*x, reference.starts(seg)))
    got, grads = both(run)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-6)
    for name, g, w in zip("q k v a beta".split(), grads, grads_want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5 * float(jnp.abs(w).max()), err_msg=name)


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_a_document_gives_what_it_gives_alone(batch, form):
    """Packed behind other documents, in the middle of a chunk, a document's
    outputs and its inputs' gradients are those of the document alone in a row;
    and the strongest decays the layer allows (-5 a position over a sub-chunk
    of 16) stay finite."""
    seg = batch["segment_ids"]
    q, k, v, a, beta = kda_inputs(jax.random.key(6), 2, S, 2, 8)
    a = a.at[:, :, 0].set(-5.0)
    f = lambda seg, *x: ops_kda.kda(*x, seg, 32, form=form, interpret=True)
    probe = jax.random.normal(jax.random.key(7), v.shape, jnp.float32)
    both = lambda seg, probe, *x: jax.jit(lambda *x: (lambda out, back: (out, back(probe)))(*jax.vjp(lambda *y: f(seg, *y), *x)))(*x)
    whole, grads = both(seg, probe, q, k, v, a, beta)
    assert bool(jnp.isfinite(whole).all()) and all(bool(jnp.isfinite(g).all()) for g in grads)
    for at, n in ((21, 43), (66, 37)):  # row 0's third document, cut by two chunk edges, and its last, which starts inside a chunk
        cut = lambda x: x[:1, at:at + n]
        alone, grads_alone = both(None, cut(probe), *map(cut, (q, k, v, a, beta)))
        np.testing.assert_allclose(whole[:1, at:at + n], alone, rtol=2e-4, atol=2e-6)
        for g, w in zip(grads, grads_alone):
            np.testing.assert_allclose(cut(g), w, rtol=2e-3, atol=2e-5)


def test_chunks_cut_counts_starts_inside_chunks_only(batch):
    seg = batch["segment_ids"]
    # row 0 starts at 16, 21, 64, 66, 103 (padding); row 1 at 32, 33, 96: chunks of 16 are cut at 21, 66, 103 and 33
    assert ops_kda.chunks_cut(seg, 16).tolist() == [4, 16]
    assert ops_kda.chunks_cut(seg, 64).tolist() == [4, 4]
    assert ops_kda.chunks_cut(seg, 48).tolist() == [4, 6]  # the grid of a row padded to 144; the padding starts no document
    for chunk in (16, 64, 48):
        np.testing.assert_array_equal(ops_kda.chunks_cut(seg, chunk), reference.chunks_cut(seg, chunk).astype(jnp.int32))


@pytest.mark.parametrize("chunk", [24, 40])
def test_a_chunk_over_a_sub_chunk_is_a_multiple_of_it(chunk):
    """The decays' exponents are bounded a sub-chunk of 16 at a time, so a
    longer chunk that is no multiple of 16 is refused, not computed wrongly."""
    q, k, v, a, beta = kda_inputs(jax.random.key(5), 1, 48, 1, 8)
    with pytest.raises(ValueError, match="multiple"):
        ops_kda.kda(q, k, v, a, beta, None, chunk)
