"""The Pallas kernels of the main paths compiled for a described v5e (the
TPU's compiler is installed here; no chip is attached and nothing runs): what
Mosaic refuses at the real widths (tiles that do not fit VMEM, slices off the
tiling) fails here and not on the chip. One file, and the topology described
inside a fixture: only the worker that runs this file loads the TPU's library.
"""

import collections
import functools
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from maggy_tpu.models import moe
from maggy_tpu.models.transformer import REMAT_POLICIES, DecoderConfig, LatentAttention
from maggy_tpu.ops.flash import backward_form, flash_attention


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def kernel_calls(text, *names):
    """How often a compiled program calls each Pallas kernel of these names."""
    calls = [line for line in text.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    return {name: sum(name in line for line in calls) for name in names}


def flash_calls(text):
    """The flash kernels a compiled program calls, by name."""
    return kernel_calls(text, "flash_fwd", "flash_bwd", "flash_dq", "flash_dkv")


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


def test_recomputed_latent_attention_compiles_one_flash_forward(one_chip):
    """A recomputed ``LatentAttention`` at glm-4.7-flash's widths, the GLM
    cell's rows (2 x 8,192, packed), its loss and gradient: Mosaic takes the
    forward and the fused backward kernel at width 256, and the policy keeps
    the forward kernel's results, so the compiled program calls ``flash_fwd``
    once (a replay would be a second call: ``nn.remat`` holds XLA back from
    merging the two)."""
    cfg = DecoderConfig(
        d_model=2048, n_heads=20, n_kv_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
        qk_rope_head_dim=64, v_head_dim=256, max_seq_len=8192, partition_params=False,
        attention_fn=functools.partial(flash_attention, interpret=False),
    )
    layer = nn.remat(LatentAttention, policy=REMAT_POLICIES["nothing"])(cfg)
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.key(0), x, ids, ids),
    )

    def step(params, x, positions, segment_ids):
        return jax.value_and_grad(
            lambda p, x: layer.apply(p, x, positions, segment_ids).astype(jnp.float32).sum(), argnums=(0, 1)
        )(params, x)

    text = jax.jit(step).lower(params, x, ids, ids).compile().as_text()
    assert flash_calls(text) == {"flash_fwd": 1, "flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}


@pytest.mark.parametrize(
    "b,s,h,kh,d",
    [(2, 8192, 20, 20, 256), (2, 4096, 32, 8, 128), (4, 8192, 32, 8, 64)],
    ids=["glm-40x8192x256", "mistral-64x4096x128", "lfm2-128x8192x64"],
)
def test_fused_flash_backward_compiles_at_the_cells_shapes(one_chip, b, s, h, kh, d):
    """``flash_bwd`` at the three training cells' rows, heads and widths,
    segmented, bfloat16, at the tiles ``_auto_blocks`` gives: a head's whole
    dq in VMEM beside the tiles needs more than Mosaic's default 16 MiB, so a
    ``vmem_limit_bytes`` counted too low is refused here, not on the chip."""
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kh, d), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)

    def step(q, k, v, segment_ids):
        return jax.grad(
            lambda q, k, v: flash_attention(q, k, v, segment_ids=segment_ids, interpret=False)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    text = jax.jit(step).lower(q, kv, kv, ids).compile().as_text()
    assert backward_form(s, d) == "fused"
    assert flash_calls(text) == {"flash_fwd": 1, "flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}


@pytest.mark.parametrize("h,window", [(72, 512), (48, 512), (72, 200)], ids=["72-heads", "48-heads", "window-off-the-tiles"])
def test_windowed_flash_kernels_compile_at_the_laguna_cells_shape(one_chip, h, window):
    """``flash_fwd`` and ``flash_bwd`` under a window at the laguna-s-2.1
    cell's row: one packed row of 8,192, 72 or 48 query heads over 8 of 128,
    bfloat16, the tiles ``_auto_blocks`` gives; the window is static, so it is
    part of the kernel Mosaic compiles."""
    s, kh, d = 8192, 8, 128
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, s, kh, d), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)

    def step(q, k, v, segment_ids):
        return jax.grad(
            lambda q, k, v: flash_attention(q, k, v, segment_ids=segment_ids, window=window, interpret=False)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    text = jax.jit(step).lower(q, kv, kv, ids).compile().as_text()
    assert flash_calls(text) == {"flash_fwd": 1, "flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}


def test_selected_key_kernels_compile_at_the_keye_cells_shape(one_chip):
    """The three kernels that keye-vl-2.0-30b-a3b's cell adds, at its row (1 x
    32,768, one document, 32 query heads over 4 of width 128, 16 index heads of
    64, 2,048 keys a query): ``index_scores`` for a block of 1,024 queries from
    a traced offset, ``sparse_select`` holding 128 x 32,768 scores in VMEM, and
    the flash pair with a selection's int8 tile as an operand, the backward
    fused with a head's whole dq resident (the longest row that keeps it)."""
    from maggy_tpu.ops import sparse_select

    s, h, kh, d, heads, width, rows = 32768, 32, 4, 128, 16, 64, 1024

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def select(qi, ki, w, seg, at):
        scores = sparse_select.index_scores(qi, ki, w, seg, at, rows, interpret=False)
        return scores, sparse_select.topk_thresholds(scores, at, 2048, interpret=False)

    text = jax.jit(select).lower(
        sds((1, heads, s, width), jnp.bfloat16), sds((1, s, width), jnp.bfloat16), sds((1, s, heads), jnp.float32),
        sds((1, 1, s), jnp.int32), sds((), jnp.int32),
    ).compile().as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    made = [line.split("=")[0].strip().lstrip("%") for line in calls]  # the result's name leads with the kernel's
    assert sorted(name.split(".")[0] for name in made) == ["index_scores", "sparse_select"]

    def step(q, k, v, segment_ids, selected):
        return jax.grad(
            lambda q, k, v: flash_attention(q, k, v, segment_ids=segment_ids, selected=selected, interpret=False)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    text = jax.jit(step).lower(
        sds((1, s, h, d), jnp.bfloat16), sds((1, s, kh, d), jnp.bfloat16), sds((1, s, kh, d), jnp.bfloat16),
        sds((1, s), jnp.int32), sds((1, s, s), jnp.int8),
    ).compile().as_text()
    assert backward_form(s, d) == "fused"
    assert flash_calls(text) == {"flash_fwd": 1, "flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}


def test_recomputed_selected_key_layer_compiles_two_index_passes(one_chip, monkeypatch):
    """A recomputed ``Attention`` with keye-vl-2.0-30b-a3b's widths at its
    cell's row (1 x 32,768, one document), its output's sum and the indexer's
    loss and their gradient: the compiled program calls ``index_scores`` twice
    (the forward's block of scores gives thresholds and mask; the flash
    kernels' backward makes the mask again from the kept thresholds) and every
    other kernel once: the replay neither selects nor scores. The dispatch
    asks the backend for its kernels; here it is told "tpu"."""
    from maggy_tpu.models.transformer import Attention

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    s = 32768
    cfg = DecoderConfig(
        d_model=2048, n_heads=32, n_kv_heads=4, head_width=128, qk_norm=True, rope_theta=1e7, max_seq_len=s,
        sparse_topk=2048, index_heads=16, index_head_dim=64, partition_params=False,
    )
    layer = nn.remat(Attention, policy=REMAT_POLICIES["nothing"])(cfg)
    x = jax.ShapeDtypeStruct((1, s, cfg.d_model), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.key(0), x, ids, ids),
    )

    def step(params, x, positions, segment_ids):
        def objective(p, x):
            out, mods = layer.apply(p, x, positions, segment_ids, mutable=["intermediates"])
            return out.astype(jnp.float32).sum() + sum(jax.tree.leaves(mods["intermediates"]["index_aux_loss"]))

        return jax.value_and_grad(objective, argnums=(0, 1))(params, x)

    text = jax.jit(step).lower(params, x, ids, ids).compile().as_text()
    calls = [line.split("=")[0] for line in text.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
    names = ("index_scores", "sparse_select", "index_loss", "flash_fwd", "flash_bwd")
    assert {name: sum(name in call for call in calls) for name in names} == dict(
        index_scores=2, sparse_select=1, index_loss=1, flash_fwd=1, flash_bwd=1
    ) and len(calls) == 6


def test_index_loss_kernel_compiles_at_the_keye_cells_shape(one_chip):
    """``index_loss`` at the cell's row (1 x 32,768, 32 heads over 4 of 128, 16
    index heads of 64), under a selection's int8 mask and, as a row of at most
    2,048 positions takes it, over every visible key of a packed row: the 16
    index heads' products of a 512 x 1,024 tile and ``d ki`` for the whole row
    resident, past Mosaic's default 16 MiB of VMEM."""
    from maggy_tpu.ops import sparse_select

    h, kh, d, heads, width = 32, 4, 128, 16, 64

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    for s, masked in ((32768, True), (2048, False)):
        def loss(q, k, lse, qi, ki, w, lse_i, mask, segs, weight):
            return sparse_select._loss_kernel_pass(
                q, k, lse, qi, ki, w, lse_i, mask if masked else None, None if masked else segs, weight, interpret=False
            )

        text = jax.jit(loss).lower(
            sds((1, s, h, d), jnp.bfloat16), sds((1, s, kh, d), jnp.bfloat16), sds((1, h, s), jnp.float32),
            sds((1, heads, s, width), jnp.bfloat16), sds((1, s, width), jnp.bfloat16), sds((1, s, heads), jnp.float32),
            sds((1, s), jnp.float32), sds((1, s, s), jnp.int8), sds((1, 1, s), jnp.int32), sds((1, s), jnp.float32),
        ).compile().as_text()
        calls = [line for line in text.splitlines() if "custom-call(" in line and "tpu_custom_call" in line]
        assert len(calls) == 1 and "index_loss" in calls[0].split("=")[0]


@pytest.mark.parametrize(
    "s,d,heads,kv_heads,gate,scanned",
    [(8192, 3072, 72, 8, True, True), (16384, 4096, 32, 32, False, False)],
    ids=["laguna-sliding", "evabyte-square"],
)
def test_attention_projections_backward_is_plain_products(one_chip, monkeypatch, s, d, heads, kv_heads, gate, scanned):
    """A recomputed ``Attention`` behind its ``RMSNorm``, its gradient and
    AdamW's update of float32 parameters, at laguna-s-2.1's sliding layer's
    widths (72 heads of 128 over 8 from 3,072, head norms, the gate; one
    packed row of 8,192, scanned once as the cell's period is) and at
    evabyte's (32 heads of 128 over 32 from 4,096: every projection square;
    one row of 16,384, unrolled as the cell's layers are). No convolution
    under ``attn/wq``, ``wk``, ``wv`` or ``wo`` in the backward writes a
    kernel-shaped result (``[d, heads, 128]``, ``[heads, 128, d]``: XLA's form
    of ``dot_general``'s own transpose, a window over the heads and a
    head-major result): the four weight gradients are products of matrices,
    ``[d, heads x 128]`` and ``wo``'s ``[heads x 128, d]``; no ``copy``
    transposes a float32 array of a kernel's shape (parameter, ``mu``,
    ``nu``) into a product's layout or back; and no fusion holds both such a
    product and a float32 result of a kernel's shape, which is AdamW's update
    written through the product's output tile. With ``DenseGeneral``'s own
    ``dot_general`` (the positive control, at the square widths) all four
    weight gradients of the unrolled layer do."""
    import optax

    from maggy_tpu.models import transformer
    from maggy_tpu.models.transformer import Attention, RMSNorm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = DecoderConfig(
        d_model=d, n_heads=heads, n_kv_heads=kv_heads, head_width=128, qk_norm=gate, attn_gate=gate, max_seq_len=s,
        partition_params=False,
    )

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x, positions, segment_ids):
            return x + Attention(cfg, name="attn")(RMSNorm(cfg, name="attn_norm")(x), positions, segment_ids), None

    kept = nn.remat(Layer, policy=REMAT_POLICIES["nothing"], prevent_cse=False)
    layer = kept() if not scanned else nn.scan(
        kept, variable_axes={"params": 0}, split_rngs={"params": True}, in_axes=nn.broadcast, length=1
    )()
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    x = jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)
    described = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip))
    params = described(jax.eval_shape(layer.init, jax.random.key(0), x, ids, ids))
    opt_state = described(jax.eval_shape(tx.init, params))
    kernels = "|".join(f"{a},{b},{c}" for a, b, c in ((d, heads, 128), (d, kv_heads, 128), (heads, 128, d)))

    def compiled():
        def step(params, opt_state, x, positions, segment_ids):  # a function of its own each time: jit's cache is keyed by it
            grads = jax.grad(lambda p: jnp.square(layer.apply(p, x, positions, segment_ids)[0].astype(jnp.float32)).mean())(params)
            updates, new_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_state

        return jax.jit(step, donate_argnums=(0, 1)).lower(params, opt_state, x, ids, ids).compile().as_text()

    backward = r'op_name="[^"]*transpose\(jvp[^"]*attn/(w[qkvo])/dot_general'

    def updates_inside_products(text):
        held = []
        for computation in fused_computations(text):
            product = re.search(r" convolution\([^\n]*" + backward, computation)
            root = re.search(r"\n\s*ROOT [^\n]*", computation)
            if product and root and re.search(rf"f32\[(?:1,)?(?:{kernels})\]", root.group(0).split(" = ")[1]):
                held.append(product.group(1))
        return sorted(held)

    text = compiled()
    products = [
        re.match(r"\s*(?:ROOT )?%\S+ = (\w+\[[\d,]*\])", line).group(1)
        for line in text.splitlines()
        if " convolution(" in line and re.search(backward, line)
    ]
    assert not re.findall(rf"bf16\[(?:{kernels})\]", " ".join(products)), products
    matrices = collections.Counter(  # wq, wk and wv, wo; at the square widths the four are one shape
        f"bf16[{a},{b}]" for a, b in ((d, heads * 128), (d, kv_heads * 128), (d, kv_heads * 128), (heads * 128, d))
    )
    assert {shape: products.count(shape) for shape in matrices} == dict(matrices), products
    assert not re.findall(rf"= f32\[(?:1,)?(?:{kernels})\]\S* copy\(", text)
    assert updates_inside_products(text) == []
    if not scanned:
        for form in ("head", "merge"):
            monkeypatch.setattr(transformer, f"{form}_dot_general", None)
        assert updates_inside_products(compiled()) == ["wk", "wo", "wq", "wv"]


def evabyte_feed_forward(one_chip):
    """An unrolled, recomputed feed-forward behind its ``RMSNorm`` at evabyte's
    widths (4,096 by 11,008) on one packed row of 16,384, described for the
    chip: the layer, its input, its parameters and the three sizes."""
    from maggy_tpu.models import transformer

    s, d, d_ff = 16384, 4096, 11008
    cfg = DecoderConfig(d_model=d, n_heads=32, n_kv_heads=32, d_ff=d_ff, max_seq_len=s, partition_params=False)

    class Layer(nn.Module):
        @nn.compact
        def __call__(self, x):
            return x + transformer.MLPBlock(cfg, name="mlp")(transformer.RMSNorm(cfg, name="mlp_norm")(x))

    layer = nn.remat(Layer, policy=REMAT_POLICIES["nothing"])()
    x = jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=one_chip)
    described = functools.partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip))
    return layer, x, described(jax.eval_shape(layer.init, jax.random.key(0), x)), described, (s, d, d_ff)


def fused_computations(text):
    """The computations of a compiled program's text, one string each."""
    return re.split(r"\n(?=\S[^\n]*\{\n)", text)


def test_evabyte_width_feed_forward_weight_gradients_are_plain_products(one_chip, monkeypatch):
    """An unrolled, recomputed feed-forward at evabyte's widths (4,096 by
    11,008) on one packed row of 16,384, its gradient and AdamW's update of
    float32 parameters: no fusion holds both a weight gradient's product (a
    convolution under ``mlp/w_*/dot_general`` in the backward) and a float32
    result of the kernel's shape, which is AdamW's update of the leaf written
    through the product's output tile. With ``DenseGeneral``'s own
    ``dot_general`` (the positive control) every one of the three does."""
    import optax

    from maggy_tpu.models import transformer

    layer, x, params, described, (_, d, d_ff) = evabyte_feed_forward(one_chip)
    tx = optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    opt_state = described(jax.eval_shape(tx.init, params))

    def updates_inside_products():
        def step(params, opt_state, x):  # a function of its own each time: jit's cache is keyed by it
            grads = jax.grad(lambda p: jnp.square(layer.apply(p, x).astype(jnp.float32)).mean())(params)
            updates, new_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_state

        text = jax.jit(step, donate_argnums=(0, 1)).lower(params, opt_state, x).compile().as_text()
        held = []
        for computation in fused_computations(text):
            product = re.search(
                r' convolution\([^\n]*op_name="[^"]*transpose\(jvp[^"]*mlp/(w_\w+)/dot_general', computation
            )
            root = re.search(r"\n\s*ROOT [^\n]*", computation)
            if product and root and re.search(rf"f32\[(?:{d},{d_ff}|{d_ff},{d})\]", root.group(0).split(" = ")[1]):
                held.append(product.group(1))
        return sorted(held)

    assert updates_inside_products() == []
    monkeypatch.setattr(transformer, "matrix_dot_general", None)
    assert updates_inside_products() == ["w_down", "w_gate", "w_up"]


def test_evabyte_width_feed_forward_input_gradient_is_a_plain_product(one_chip, monkeypatch):
    """The same feed-forward, the gradient of its parameters and of its input:
    no fusion holds both an input gradient's narrowing product (``[16384,
    11008]`` by ``[11008, 4096]``, a convolution under ``mlp/w_gate`` or
    ``mlp/w_up`` in the backward) and a reduction to ``f32[4096]``, which is
    the norm's scale gradient computed through the product's output tile.
    With ``DenseGeneral``'s own ``dot_general`` (the positive control) one
    does: ``w_gate``'s, with the sum of the two input gradients inside."""
    from maggy_tpu.models import transformer

    layer, x, params, _, (s, d, _) = evabyte_feed_forward(one_chip)

    def reductions_inside_products():
        def step(params, x):  # a function of its own each time: jit's cache is keyed by it
            return jax.grad(lambda p, x: jnp.square(layer.apply(p, x).astype(jnp.float32)).mean(), argnums=(0, 1))(params, x)

        text = jax.jit(step).lower(params, x).compile().as_text()
        held = []
        for computation in fused_computations(text):
            product = re.search(
                rf' bf16\[{s},{d}\]\S* convolution\([^\n]*op_name="[^"]*transpose\(jvp[^"]*mlp/(w_\w+)/dot_general',
                computation,
            )
            if product and re.search(rf" f32\[{d}\]\S* reduce\(", computation):
                held.append(product.group(1))
        return sorted(held)

    assert reductions_inside_products() == []
    monkeypatch.setattr(transformer, "matrix_dot_general", None)
    assert reductions_inside_products() == ["w_gate"]


def test_chunk_summary_attention_compiles_at_the_evabyte_cells_shape(one_chip):
    """A layer of chunk summaries (``ops/eva.py``) at the evabyte cell's row:
    one packed row of 16,384, 32 heads of 128, windows of 2,048 and chunks of
    16, bfloat16, the summaries in XLA and the two parts on the flash kernels
    with its loss and gradient, ``phi`` and ``mu`` among them: a local and a
    remote ``flash_fwd``, a local and a remote ``flash_bwd`` (the remote one
    fused, a head's whole dq of 16,384 rows resident beside the selection's
    int8 tile), and under the recompute policy no third forward."""
    from maggy_tpu.ops import eva

    s, h, d, window, chunk = 16384, 32, 128, 2048, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, phi, mu, segment_ids):
        ks, vs = eva.summaries(k, v, phi, mu, segment_ids, chunk)
        out = eva.eva_attention(q, k, v, ks, vs, segment_ids, window=window, chunk=chunk, interpret=False)
        return out.astype(jnp.float32).sum()

    def step(q, k, v, phi, mu, segment_ids):
        kept = jax.checkpoint(functools.partial(loss, segment_ids=segment_ids), policy=REMAT_POLICIES["nothing"])
        return jax.grad(kept, argnums=(0, 1, 2, 3, 4))(q, k, v, phi, mu)

    head = sds((1, s, h, d), jnp.bfloat16)
    text = jax.jit(step).lower(head, head, head, sds((h, d), jnp.float32), sds((h, d), jnp.float32), sds((1, s), jnp.int32)).compile().as_text()
    assert eva.untileable(s, window, chunk, d, compiled=True) is None and backward_form(s, d) == "fused"
    assert flash_calls(text) == {"flash_fwd": 2, "flash_bwd": 2, "flash_dq": 0, "flash_dkv": 0}


def test_two_stream_attention_compiles_at_the_sdar_cells_shape(one_chip):
    """A two-stream layer's attention (``ops/blockdiff.py``) at the
    sdar-30b-a3b-chat cell's rows: two packed rows of 8,192 a stream, 32 query
    heads over 4 of 128, bfloat16, block 4: the clean stream's ``flash_fwd`` /
    ``flash_bwd`` under its block-causal bound and the noised stream's under
    the bound before its block (the bounds are data: an int32 block beside the
    segment ids in every kernel), the own block in its band kernels, once
    each, and under the recompute policy no third forward."""
    from maggy_tpu.ops import blockdiff

    b, l, h, kh, d, block = 2, 8192, 32, 4, 128, 4

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(q, k, v, positions, segment_ids):
        lay = blockdiff.layout(positions, segment_ids, block)
        (q_c, q_n), (k_c, k_n), (v_c, v_n) = ((a[:, :l], a[:, l:]) for a in (q, k, v))
        clean = flash_attention(q_c, k_c, v_c, segment_ids=segment_ids, bound=lay.hi_clean, interpret=False)
        noised = blockdiff.noised_attention(q_n, k_c, v_c, k_n, v_n, segment_ids, lay, block=block, interpret=False)
        return clean.astype(jnp.float32).sum() + noised.astype(jnp.float32).sum()

    def step(q, k, v, positions, segment_ids):
        kept = jax.checkpoint(
            functools.partial(loss, positions=positions, segment_ids=segment_ids), policy=REMAT_POLICIES["nothing"]
        )
        return jax.grad(kept, argnums=(0, 1, 2))(q, k, v)

    ids = sds((b, l), jnp.int32)
    text = jax.jit(step).lower(
        sds((b, 2 * l, h, d), jnp.bfloat16), sds((b, 2 * l, kh, d), jnp.bfloat16), sds((b, 2 * l, kh, d), jnp.bfloat16), ids, ids
    ).compile().as_text()
    assert blockdiff.untileable(l, d, block, compiled=True) is None and backward_form(l, d) == "fused"
    assert flash_calls(text) == {"flash_fwd": 2, "flash_bwd": 2, "flash_dq": 0, "flash_dkv": 0}
    assert kernel_calls(text, "own_block_fwd", "own_block_bwd") == {"own_block_fwd": 1, "own_block_bwd": 1}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_the_band_kernels_compile_at_the_sdar_cells_shape(one_chip, dtype):
    """``own_block_fwd`` and ``own_block_bwd`` (``ops/blockdiff.py``) alone at
    the sdar-30b-a3b-chat cell's rows, 2 x 8,192 noised queries of 32 heads
    over 4 of 128, block 4, at the tiles ``band_tiles`` gives that shape: the
    windows of a tile are slices Mosaic has to take (128 queries, 144 keys from
    a multiple of 128), the group's heads an axis whose key tile stays, dq
    written over the flash call's; all inside Mosaic's default VMEM, which
    the calls do not raise. In float32 a tile is twice the bytes."""
    from maggy_tpu.ops import blockdiff

    b, l, h, kh, d, block = 2, 8192, 32, 4, 128, 4
    tq, sub, halo = band = blockdiff.band_tiles(l, block)
    assert (tq, sub, halo) == (1024, 128, 8)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows, keys = sds((b * h, l, d), dtype), sds((b * kh, l // tq, tq + 2 * halo, d), dtype)
    row, kid, qid = sds((b * h, 1, l), jnp.float32), sds((b, l // tq, tq + 2 * halo, 1), jnp.int32), sds((b, 1, l), jnp.int32)
    kw = dict(band=band, group=h // kh, kv_heads=kh, interpret=False)

    def forward(q, k, v, kid, qid, o_c, lse_c):
        ops = [("q", q), ("kv", k), ("kv", v), ("kid", kid), ("qid", qid), ("q", o_c), ("row", lse_c)]
        return blockdiff._own_call(blockdiff._own_fwd_kernel, "own_block_fwd", ops, [("q", rows), ("row", row)], **kw)

    def backward(q, g, k, v, kid, qid, lse, delta, dq_c):
        ops = [("q", q), ("q", g), ("kv", k), ("kv", v), ("kid", kid), ("qid", qid), ("row", lse), ("row", delta), ("q", dq_c)]
        edges = jax.ShapeDtypeStruct(keys.shape, jnp.float32)
        return blockdiff._own_call(
            blockdiff._own_bwd_kernel, "own_block_bwd", ops, [("q", rows), ("kv", edges), ("kv", edges)], aliases={8: 0}, **kw
        )

    text = jax.jit(forward).lower(rows, keys, keys, kid, qid, rows, row).compile().as_text()
    text += jax.jit(backward).lower(rows, rows, keys, keys, kid, qid, row, row, rows).compile().as_text()
    assert kernel_calls(text, "own_block_fwd", "own_block_bwd") == {"own_block_fwd": 1, "own_block_bwd": 1}


@pytest.mark.parametrize("window", [4096, 0], ids=["windowed", "global"])
def test_flash_kernels_compile_at_the_smallthinker_cells_shape(one_chip, window):
    """``flash_fwd`` and the backward at the smallthinker-21ba3b-instruct
    cell's row: one document of 16,384, 28 query heads over 4 of 128 (seven a
    key-value head), bfloat16, under the window of 4,096 (a band eight
    512-tiles wide) and with none (a global layer, whose queries and keys
    come unrotated: the kernel does not know)."""
    s, h, kh, d = 16384, 28, 4, 128
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, s, kh, d), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)

    def step(q, k, v, segment_ids):
        return jax.grad(
            lambda q, k, v: flash_attention(q, k, v, segment_ids=segment_ids, window=window, interpret=False)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    calls = flash_calls(jax.jit(step).lower(q, kv, kv, ids).compile().as_text())
    fused = backward_form(s, d) == "fused"
    assert calls == {"flash_fwd": 1, "flash_bwd": int(fused), "flash_dq": int(not fused), "flash_dkv": int(not fused)}


def test_relu_gated_chunk_compiles_with_its_count_at_the_smallthinker_widths(one_chip, monkeypatch):
    """One chunk of the share layer with ReLU-gated experts
    (``_chunk_experts(..., "relu")`` and its backward) at the
    smallthinker-21ba3b-instruct cell's widths: 18,432 rows (three quarters of
    the expected load), 16 held experts of 2,560 x 768, bfloat16: the grouped
    products stay the Pallas kernels, three forward, and the count of zero
    hidden activations comes out as one int32 beside them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows, held, d, f = 18432, 16, 2560, 768

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(a, sizes, w_gate, w_up, w_down, g_rows, scale):
        y, zeros = moe._chunk_experts(a, sizes, w_gate, w_up, w_down, "relu")
        return y, zeros, moe._chunk_experts_back(a, sizes, w_gate, w_up, w_down, g_rows, scale, "relu")

    args = (sds((rows, d)), sds((held,), jnp.int32), sds((held, d, f)), sds((held, d, f)), sds((held, f, d)),
            sds((rows, d)), sds((rows,)))
    lowered = jax.jit(step).lower(*args)
    assert lowered.out_info[1].shape == () and lowered.out_info[1].dtype == jnp.int32
    calls = kernel_calls(lowered.compile().as_text(), "gmm", "tgmm")  # "gmm" counts the "tgmm" lines too
    assert calls["tgmm"] == 3 and calls["gmm"] - calls["tgmm"] >= 6


@pytest.mark.parametrize(
    "s,d,vocab", [(16384, 2560, 37984), (32768, 2048, 18992)], ids=["smallthinker-16384x37984", "keye-32768x18992"]
)
def test_head_and_loss_in_blocks_keep_no_whole_float32_logits(one_chip, s, d, vocab):
    """``head.loss`` at the two cells' shapes whose float32 logits are 2.32 GiB,
    value and gradients, at the rows ``head.block_rows`` gives: one loop in the
    compiled program, the three products a block in it, and no float32 array
    of the whole logits' size anywhere (PR 46's step held three)."""
    from maggy_tpu.models import head

    rows = head.block_rows(1, s, vocab)
    assert rows < s and rows * vocab * 4 <= head.BLOCK_LOGITS_BYTES

    def step(hidden, kernel, ids, weights):
        return jax.value_and_grad(
            lambda h, w: head.loss(h, w, ids, weights, rows=rows, dtype=jnp.bfloat16), argnums=(0, 1)
        )(hidden, kernel)

    args = (
        jax.ShapeDtypeStruct((1, s, d), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((d, vocab), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((1, s), jnp.float32, sharding=one_chip),
    )
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" convolution\(", text)) == 3
    whole = [m for m in re.findall(r"f32\[([\d,]+)\]", text) if {str(vocab)} <= set(m.split(",")) and {str(s), str(s - 1)} & set(m.split(","))]
    assert not whole
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * rows * vocab * 4 + 3 * d * vocab * 4


@pytest.mark.parametrize("weighted", [True, False], ids=["combine", "dispatch_backward"])
@pytest.mark.parametrize(
    "t,k,d,held,rows", [(32768, 8, 2048, 16, 11 * 24576), (8192, 10, 3072, 8, 8 * 10240)], ids=["sdar-keye", "laguna"]
)
def test_token_sum_kernel_compiles_at_the_cells_shapes(one_chip, t, k, d, held, rows, weighted):
    """``slots_to_tokens`` at ``TOKEN_TILES``, with the weights (the combine)
    and without (``d_tokens``), at the SDAR and Keye cells' layer (32,768
    tokens x 8 choices of width 2,048, 16 experts held, a buffer of eleven
    chunks of 24,576 rows) and the Laguna cell's (8,192 x 10 of 3,072, 8
    held): the buffer's halves, the sum and the placement fit VMEM, and the
    DMAs' slices lie on the tiling."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(buffer, runs, total, inv, is_held, weights):
        return moe.slots_to_tokens(buffer, runs, total, inv, is_held, weights if weighted else None)

    blocks = -(-t // moe.TOKEN_TILES[0])
    lowered = jax.jit(step).lower(
        sds((rows, d), jnp.bfloat16), sds((2, blocks, held), jnp.int32), sds((), jnp.int32), sds((t, k), jnp.int32),
        sds((t, k), jnp.bool_), sds((t, k), jnp.bfloat16),
    )
    assert lowered.out_info.shape == (t, d)
    assert kernel_calls(lowered.compile().as_text(), "slots_to_tokens") == {"slots_to_tokens": 1}


def test_sdar_size_share_layer_gathers_no_row_a_token(one_chip, monkeypatch):
    """One ``ExpertShareBlock`` at the SDAR cell's size (32,768 positions, 8
    of 128 experts a token, 16 held, chunks of three quarters of the expected
    load), value and gradient, steered onto the TPU's branches: the token-side
    sums are the kernel, once in the forward and once in the backward, and no
    gather under ``moe.combine`` or ``moe.dispatch`` gives ``[T, d_model]``
    (``_by_token`` gave eight each way)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, d = 32768, 2048
    cfg = moe.MoEConfig(
        vocab_size=1024, d_model=d, n_layers=1, n_heads=32, n_kv_heads=4, d_ff=768, moe_d_ff=768, n_experts=128,
        top_k=8, experts_held=16, router="softmax", chunk_of_load=0.75, dtype=jnp.bfloat16, max_seq_len=t,
    )
    block = moe.ExpertShareBlock(cfg)
    x = jax.ShapeDtypeStruct((1, t, d), jnp.bfloat16, sharding=one_chip)
    params = jax.eval_shape(lambda: block.init(jax.random.key(0), jnp.zeros((1, 8, d), jnp.bfloat16)))["params"]
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), nn.meta.unbox(params))

    def step(params, x):
        return jax.value_and_grad(lambda p, x: block.apply({"params": p}, x).astype(jnp.float32).sum(), argnums=(0, 1))(params, x)

    text = jax.jit(step).lower(params, x).compile().as_text()
    assert kernel_calls(text, "slots_to_tokens") == {"slots_to_tokens": 2}
    by_token = [
        line for line in text.splitlines()
        if re.search(r"= \w+\[%d,%d\]\S* gather\(" % (t, d), line) and re.search(r"moe\.(combine|dispatch)", line)
    ]
    assert not by_token


def test_the_kda_kernels_compile_at_the_ling_cells_shape(one_chip):
    """``ops.kda.kda`` and its gradient at the ling-3.0-flash cell's row: one
    packed row of 8,192, 32 heads of 128, bfloat16, chunks of 64: Mosaic takes
    ``kda_fwd`` (a chunk's four products against the state in VMEM) and
    ``kda_bwd``; the backward runs ``kda_fwd`` once more to make the chunk
    states again."""
    from maggy_tpu.ops import kda as ops_kda

    b, s, h, d = 1, 8192, 32, 128
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(q, k, v, a, beta, segment_ids):
        return jax.grad(
            lambda *x: jnp.square(ops_kda.kda(*x, segment_ids, form="pallas", interpret=False).astype(jnp.float32)).sum(),
            argnums=(0, 1, 2, 3, 4),
        )(q, k, v, a, beta)

    args = (sds((b, s, h, d)),) * 3 + (sds((b, s, h, d), jnp.float32), sds((b, s, h), jnp.float32), sds((b, s), jnp.int32))
    calls = kernel_calls(jax.jit(step).lower(*args).compile().as_text(), "kda_fwd", "kda_bwd")
    assert calls == {"kda_fwd": 2, "kda_bwd": 1}


def test_the_padded_latent_call_compiles_the_flash_kernels_at_192_and_128(one_chip):
    """A gated ``LatentAttention`` with a full-rank query at the ling-3.0-flash
    cell's widths (32 heads, queries and keys of 128 + 64 beside values of
    128) and row (1 x 8,192, packed), its loss and gradient: the heads go
    through the flash kernels padded to 256, forward and the fused backward."""
    cfg = DecoderConfig(
        d_model=2560, n_heads=32, n_kv_heads=32, q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, attn_gate=True, max_seq_len=8192, partition_params=False,
        rope_theta=6e6, attention_fn=functools.partial(flash_attention, interpret=False),
    )
    layer = LatentAttention(cfg)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.d_model), jnp.bfloat16, sharding=one_chip)
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.key(0), x, ids, ids),
    )
    assert params["params"]["wq"]["kernel"].shape == (2560, 32, 192)

    def step(params, x, positions, segment_ids):
        return jax.value_and_grad(
            lambda p, x: layer.apply(p, x, positions, segment_ids).astype(jnp.float32).sum(), argnums=(0, 1)
        )(params, x)

    calls = flash_calls(jax.jit(step).lower(params, x, ids, ids).compile().as_text())
    assert calls == {"flash_fwd": 1, "flash_bwd": 1, "flash_dq": 0, "flash_dkv": 0}
