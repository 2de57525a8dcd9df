"""The Pallas kernels of the main paths compiled for a described v5e (the
TPU's compiler is installed here; no chip is attached and nothing runs): what
Mosaic refuses at the real widths (tiles that do not fit VMEM, slices off the
tiling) fails here and not on the chip. One file, and the topology described
inside a fixture: only the worker that runs this file loads the TPU's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from maggy_tpu.models import moe


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("k,n", [(2048, 1536), (1536, 2048)], ids=["gate_and_up", "down"])
def test_grouped_kernels_compile_at_the_glm_widths(one_chip, k, n):
    """``grouped_kernel`` forward and both gradients (``gmm``, ``gmm`` with
    the weights transposed, ``tgmm``) at ``GROUPED_TILES``, a capacity tier
    of 8,192 rows, 8 held experts, glm-4.7-flash's widths, bfloat16."""
    rows, held = 8192, 8

    def step(x, w, sizes):
        return jax.value_and_grad(
            lambda x, w: moe.grouped_kernel(x, w, sizes).astype(jnp.float32).sum(), argnums=(0, 1)
        )(x, w)

    args = (
        jax.ShapeDtypeStruct((rows, k), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((held, k, n), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((held,), jnp.int32, sharding=one_chip),
    )
    text = jax.jit(step).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
