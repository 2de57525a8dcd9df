"""Pallas ring attention (in-kernel RDMA rotation) vs the ppermute ring and
the dense reference, on the CPU mesh via the TPU interpret machine (remote
DMAs and semaphores are simulated faithfully; VERDICT r1 item 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from maggy_tpu.models.transformer import default_attention
from maggy_tpu.ops.ring_flash import ring_flash_attention
from maggy_tpu.parallel.ringattention import ring_attention

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs the 8-device CPU mesh"
)


def _mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def _qkv(B=2, S=128, H=4, KH=2, D=16):
    q = jax.random.normal(jax.random.key(1), (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (B, S, KH, D), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (B, S, KH, D), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.slow
def test_ring_flash_matches_dense(causal):
    mesh = _mesh(4)
    q, k, v = _qkv()
    ref = default_attention(q, k, v, causal=causal)
    with jax.set_mesh(mesh):
        out = ring_flash_attention(
            q, k, v, mesh=mesh, causal=causal, q_tile=16, interpret=True
        )
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_ring_flash_gqa_matches_xla_ring():
    """sp=4 mesh, grouped KV heads: the RDMA kernel and the ppermute ring are
    the same computation distributed two different ways."""
    mesh = _mesh(4)
    q, k, v = _qkv(B=1, S=64, H=4, KH=1, D=8)
    with jax.set_mesh(mesh):
        xla = ring_attention(q, k, v, mesh=mesh, causal=True, impl="xla")
        pallas = ring_attention(
            q, k, v, mesh=mesh, causal=True, impl="pallas", interpret=True
        )
    assert float(jnp.abs(pallas - xla).max()) < 2e-5


def test_ring_flash_backward_kernel_parity():
    """The RDMA backward ring (rotating dk/dv accumulators, probabilities
    recomputed from the saved LSE) must give the same gradients as the
    differentiable XLA ppermute ring. Kept in the fast tier (small 2-device
    S=32 case) so 'not slow' still catches backward-kernel regressions."""
    mesh = _mesh(2)
    q, k, v = _qkv(B=1, S=32, H=2, KH=2, D=8)

    def loss_pallas(q, k, v):
        out = ring_attention(
            q, k, v, mesh=mesh, causal=True, impl="pallas", interpret=True
        )
        return (out**2).sum()

    def loss_xla(q, k, v):
        out = ring_attention(q, k, v, mesh=mesh, causal=True, impl="xla")
        return (out**2).sum()

    with jax.set_mesh(mesh):
        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        assert float(jnp.abs(a - b).max()) < 5e-5


def test_auto_impl_gates_pallas_off_tpu(monkeypatch):
    """impl='auto' must resolve from the mesh's device platform and the
    MAGGY_TPU_RING_PALLAS opt-in: on a CPU mesh it always takes the XLA ring,
    even with the opt-in set (ADVICE r3; VERDICT r3 item 6)."""
    from maggy_tpu.parallel import ringattention as ra

    def boom(*a, **k):
        raise AssertionError("pallas path selected on a CPU mesh")

    monkeypatch.setattr(ra, "_pallas_ring", boom)
    monkeypatch.setenv("MAGGY_TPU_RING_PALLAS", "1")
    mesh = _mesh(2)
    q, k, v = _qkv(B=1, S=32, H=2, KH=2, D=8)
    with jax.set_mesh(mesh):
        out = ring_attention(q, k, v, mesh=mesh, causal=True, impl="auto")
    assert out.shape == q.shape


@pytest.mark.slow
def test_ring_flash_backward_gqa_four_ring():
    """4-device ring, grouped KV heads, several q tiles per chunk — the dK/dV
    group-sum and multi-tile dQ read-modify-write paths."""
    mesh = _mesh(4)
    q, k, v = _qkv(B=2, S=128, H=4, KH=2, D=16)

    def loss_pallas(q, k, v):
        out = ring_attention(
            q, k, v, mesh=mesh, causal=True, impl="pallas", interpret=True
        )
        return (out * jnp.cos(out)).sum()

    def loss_xla(q, k, v):
        out = ring_attention(q, k, v, mesh=mesh, causal=True, impl="xla")
        return (out * jnp.cos(out)).sum()

    with jax.set_mesh(mesh):
        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        assert float(jnp.abs(a - b).max()) < 5e-5
