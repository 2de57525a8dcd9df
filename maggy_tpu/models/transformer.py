"""Flagship model family: LLaMA-style decoder-only transformer.

TPU-first design (none of this exists in the reference, which orchestrates
user-supplied torch/keras models — §2.10; this model family is what the
BASELINE Llama-3-8B config trains):

* bf16 compute / fp32 params via ``dtype``/``param_dtype`` — MXU-native.
* RMSNorm + RoPE + SwiGLU + grouped-query attention (GQA).
* ``scan_layers=True`` folds the layer stack into one ``nn.scan`` — O(1)
  compile time in depth, the standard XLA-friendly layout.
* ``remat=True`` wraps each layer in ``jax.checkpoint`` to trade FLOPs for HBM.
* Every parameter carries logical axis names (via ``nn.with_partitioning``)
  consumed by :mod:`maggy_tpu.parallel.sharding` — the same module runs
  replicated, FSDP, tensor-parallel, or any mesh combination unchanged.
* ``attention_fn`` hook: defaults to an einsum soft-max attention; the Pallas
  flash/ring kernels in :mod:`maggy_tpu.ops` slot in here for long sequences.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from maggy_tpu.models import head
from maggy_tpu.ops import attention as ops_attn
from maggy_tpu.ops import blockdiff, eva, sparse_select
from maggy_tpu.ops import kda as ops_kda
from maggy_tpu.ops.flash import (
    FLASH_RESIDUALS,
    flash_attention,
    lane_fill,
    sharded_flash_attention,
    tiles_visited_share,
)

Dtype = Any

# Recompute policies by name, so configs stay JSON-friendly and hashable: what
# a layer under ``nn.remat`` keeps from its forward pass for its backward.
# Every policy that recomputes keeps the flash kernel's two results
# (``ops.flash.FLASH_RESIDUALS``: the output and the per-row log-sum-exp, which
# the kernel's backward rule reads), so a recomputed layer runs ``flash_fwd``
# once a step and its replay rebuilds only q, k and v around it.
# - "nothing": no XLA intermediate is kept; the attention kernel's two results
#   are. A layer costs its input plus 2 * n_heads * head_dim_v + 4 * n_heads
#   bytes a token (as much again as the input where heads * width = d_model).
# - "dots": the same, plus the outputs of matmuls with no batch dimension (the
#   projections and the feed-forward), so the replay is elementwise work.
# - "everything": nothing is recomputed.
# A selected-key attention layer (``sparse_topk``) keeps two things more
# (``ops.sparse_select.SPARSE_RESIDUALS``): each query's threshold, from which
# the flash kernels' backward makes the selection's mask again (the replay
# makes no index score and selects nothing), and the indexer's gradients,
# which its loss's one pass already gave.
# There is no policy that replays the kernel: a step that does not fit with
# its results kept is one the autotuner (``tune/static.py``) prunes by its
# compiled footprint, and the remedy is the one it proposes, a smaller batch.
# Close to the device's memory the compiler makes the room itself, by
# computing other values twice (PERF.md section 6, PR 29: three matmuls, 14 ms
# of the 30 the kernel's replay had cost in the GLM cell).
# A "kda" layer keeps its operator's output (``ops.kda.KDA_RESIDUALS``): its
# replay then runs no recurrence.
KEPT_RESIDUALS = (*FLASH_RESIDUALS, *sparse_select.SPARSE_RESIDUALS, *ops_kda.KDA_RESIDUALS)
REMAT_POLICIES = {
    "nothing": jax.checkpoint_policies.save_only_these_names(*KEPT_RESIDUALS),
    "dots": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(*KEPT_RESIDUALS),
    ),
    "everything": jax.checkpoint_policies.everything_saveable,
}


LAYER_KINDS = ("full_attention", "sliding_attention", "conv", "eva_attention", "kda", "latent_attention")
# the kinds whose operator is no softmax attention: no window, no tile of a flash grid
STATE_KINDS = ("conv", "kda")


def _parse_ablated(ablated, n_layers: int):
    """Component-name grammar for factory-free LOCO ablation: "attn" / "mlp" (that sublayer in every layer), "layers.<i>"
    (layer i entirely), "layers.<i>.attn" / "layers.<i>.mlp". Returns a
    [n_layers, 2] float gate array (attn, mlp) or None when nothing is
    ablated. Raises on unknown names so typos never silently train the full
    model."""
    if not ablated:
        return None
    import numpy as np

    gates = np.ones((n_layers, 2), np.float32)
    for comp in sorted(ablated):
        parts = str(comp).split(".")
        ok = True
        if comp == "attn":
            gates[:, 0] = 0.0
        elif comp == "mlp":
            gates[:, 1] = 0.0
        elif parts[0] == "layers" and len(parts) in (2, 3) and parts[1].isdigit():
            i = int(parts[1])
            if not 0 <= i < n_layers:
                raise ValueError(
                    f"Ablated component {comp!r}: layer index out of range "
                    f"(n_layers={n_layers})"
                )
            if len(parts) == 2:
                gates[i] = 0.0
            elif parts[2] == "attn":
                gates[i, 0] = 0.0
            elif parts[2] == "mlp":
                gates[i, 1] = 0.0
            else:
                ok = False
        else:
            ok = False
        if not ok:
            raise ValueError(
                f"Unknown ablated component {comp!r}; expected 'attn', 'mlp', "
                "'layers.<i>', 'layers.<i>.attn' or 'layers.<i>.mlp'"
            )
    return gates


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: int = 8
    d_ff: int = 1376
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scan_layers: bool = True
    remat: bool = False
    # what a recomputed layer keeps (``REMAT_POLICIES``): "dots" the matmul
    # outputs and the attention kernel's results, so the backward recomputes
    # elementwise work only; "nothing" the kernel's results alone (least
    # HBM). Both benchmark cells run "nothing", because their step fits one
    # v5e no other way (PERF.md section 4), and read what is replayed as
    # train.recompute_share; "dots" is not measured on the chip.
    remat_policy: str = "dots"
    logits_softcap: float = 0.0
    tie_embeddings: bool = False
    attention_fn: Optional[Callable] = None
    # decode=True switches attention to the KV-cache incremental path
    # (build via `dataclasses.replace(cfg, decode=True)`; params are identical)
    decode: bool = False
    # paged=True (decode only) stores K/V in a flat pool of `num_pages`
    # fixed-size pages instead of [B, max_seq_len] rows; a per-row page
    # table (cache variable "pages", [B, max_seq_len/page_size] int32,
    # host-managed by the serve engine's block allocator) maps logical
    # positions to physical pages. Decouples batch width from sequence
    # reservation — the enabler for paged serving (docs/serving.md "Paged
    # KV cache"). The dense decode path is unchanged when False.
    paged: bool = False
    page_size: int = 64
    num_pages: int = 0
    # KV-cache read chunk: decode attends over ceil(written/chunk) chunks of
    # the cache instead of all max_seq_len slots — HBM traffic (the decode
    # bottleneck) tracks the ACTUAL prefix length. Rounded down to a divisor
    # of max_seq_len at use
    decode_chunk: int = 256
    # False drops the nn.with_partitioning logical-axis annotations from every
    # param (identical values/tree). Used where params are placed manually —
    # e.g. per-stage modules inside the pipeline shard_map, where flax would
    # otherwise try to resolve logical names against the physical mesh
    partition_params: bool = True
    # components gated to zero for LOCO ablation (param tree unchanged —
    # ablated sublayers contribute nothing and receive zero gradients);
    # grammar in _parse_ablated, usually set via cfg.without(...)
    ablated: Any = frozenset()
    # latent attention (DeepSeek-V2 MLA, as glm4_moe_lite keeps it): with
    # kv_lora_rank > 0 the layers' attention is LatentAttention — low-rank
    # query and key-value paths with an inner norm each, rope on a
    # qk_rope_head_dim-wide part whose key is one head shared by all, and
    # heads of qk_nope_head_dim + qk_rope_head_dim, whatever d_model / n_heads
    # is. n_kv_heads is n_heads there. q_lora_rank 0: the query is one
    # full-rank product ``wq`` with no inner norm. A v_head_dim other than the
    # query's width goes through the kernels padded with zeros to one width
    # (``padded_attention``). In ``layer_types`` the
    # kind "latent_attention" names such a layer beside layers of other kinds
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # the operator each layer takes before its feed-forward, one name a layer
    # (``LAYER_KINDS``); empty: attention everywhere. "conv" is the gated
    # short convolution (:class:`ShortConv`, ``lfm2``'s) with ``conv_kernel``
    # taps; it has no decode state yet (its tail of conv_kernel - 1 positions
    # would live beside the KV pages: ROADMAP M4)
    layer_types: tuple = ()
    conv_kernel: int = 3
    # a "kda" layer (``layer_types``; :class:`KDA`, ``ops/kda.py``): Kimi delta
    # attention, ``n_heads`` heads of ``kda_head_dim`` (keys and values alike)
    # behind causal depthwise convolutions of ``kda_conv_kernel`` taps, a log
    # decay a channel bounded below by ``kda_decay_floor`` (``floor *
    # sigmoid(...)``), the chunked delta rule at ``kda_chunk`` positions a
    # chunk (its backward makes the chunk states again). Training and
    # scoring only: its decode state (a
    # ``[d_k, d_v]`` matrix a head and the convolutions' tails, a snapshot a
    # sequence and not pages) is not written (ROADMAP M4)
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_decay_floor: float = -5.0
    kda_chunk: int = ops_kda.CHUNK
    # an RMSNorm over the head's width on every query and key head before the
    # rotary embedding (:class:`Attention` only)
    qk_norm: bool = False
    # the width of a head where it is not d_model / n_heads (``qwen3_moe``'s
    # family: 32 heads of 128 over a model of 2,048); 0: d_model / n_heads
    head_width: int = 0
    # attention over the keys an indexer selects (:class:`Attention` only,
    # ``ops/sparse_select.py``): each query attends its ``sparse_topk`` keys of
    # largest index score, ``index_heads`` heads of ``index_head_dim`` over one
    # key head; 0: every key (today's path, bit for bit). A row of at most
    # ``sparse_topk`` positions selects everything and takes that path too.
    # Training and scoring only: the serve engine has no indexer cache
    sparse_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    # a "sliding_attention" layer (``layer_types``; :class:`Attention` only):
    # a query sees the ``sliding_window`` keys up to its own (``t - s <
    # sliding_window``, inside its document), through ``sliding_heads`` query
    # heads over the same ``n_kv_heads`` (0: ``n_heads``), under the default
    # rotary embedding on the whole head at ``sliding_rope_theta`` (0:
    # ``rope_theta``). Training and scoring only: the page allocator does not
    # release what a window leaves behind yet (ROADMAP M2)
    sliding_window: int = 0
    sliding_heads: int = 0
    sliding_rope_theta: float = 0.0
    # the rotary form of a "full_attention" layer: ``rope_share`` of the head's
    # width is rotated (its first dimensions; the rest pass as they are), and
    # ``rope_yarn`` = (factor, original positions, beta_fast, beta_slow,
    # attention factor) scales the frequencies as YaRN does and cos and sin by
    # the attention factor (``yarn_inv_freq``); (): the default form
    rope_share: float = 1.0
    rope_yarn: tuple = ()
    # False: a "full_attention" layer has no positional embedding at all (its
    # queries and keys go to the kernel as the projections give them:
    # ``Attention`` skips :func:`rope`, it does not multiply by a table of
    # ones), while the "sliding_attention" layers keep theirs; a model of such
    # global layers beside windowed ones. :class:`Attention` only
    full_rope: bool = True
    # a sigmoid gate a head and token on the heads' outputs before ``wo``, from
    # a bias-free projection of the layer's normed input (:class:`Attention`)
    attn_gate: bool = False
    # an "eva_attention" layer (``layer_types``; :class:`Attention` only,
    # ``ops/eva.py``): a query sees the exact keys of its own window of
    # ``eva_window`` positions on the row's grid, up to itself and inside its
    # document, and one learned summary for every ``eva_chunk`` positions of
    # the earlier windows that end in its document, under one softmax. A row
    # of at most ``eva_window`` positions is plain causal attention. Training
    # and scoring only: the summaries have no decode state
    eva_window: int = 0
    eva_chunk: int = 0
    # the heads of the final product: head ``i`` predicts the token ``i + 1``
    # ahead from one untied ``[d_model, pred_heads x vocab_size]`` kernel. The
    # model's output is head 0's logits; the others' are sown (``mtp_logits``)
    # and the trainer adds the mean of their losses ``mtp_weight`` times, which
    # is their sum. 1: the one next-token head
    pred_heads: int = 1
    # every RMSNorm multiplies by ``1 + scale`` (a scale that starts at zero)
    norm_unit_offset: bool = False
    # the residual stream and a layer's two sums into it in float32
    residual_f32: bool = False

    @property
    def mtp_weight(self) -> float:
        """What the trainer weighs the further heads' mean loss by: their number."""
        return float(self.pred_heads - 1)

    def head_aheads(self) -> Tuple[int, ...]:
        """How far ahead of its position each head predicts, head by head
        (``models/head.py`` ``step_targets``: a model with this method returns
        its heads' losses where it is handed ``targets``)."""
        return tuple(range(1, self.pred_heads + 1))

    @property
    def stream_block(self) -> int:
        """The block length of a two-stream layout, where the model's
        objective runs a clean and a noised copy of every row through its
        layers (``MoEConfig.block_diffusion``: rows of ``2L`` positions, the
        clean stream first; :class:`Attention` then masks block-wise between
        them, ``ops/blockdiff.py``); 0: one stream, causal."""
        return 0

    @property
    def head_dim(self) -> int:
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_width or self.d_model // self.n_heads

    def layer_kinds(self) -> tuple:
        """The operator of every layer, ``n_layers`` names."""
        return self.layer_types or ("full_attention",) * self.n_layers

    def attention_form(self, kind: str) -> tuple:
        """``(query heads, window, rotary)`` of an attention layer of ``kind``;
        ``rotary`` = (base, rotated width, YaRN's constants or (), scale of cos
        and sin) as :func:`rope` takes them; a rotated width of 0 says that the
        layer has no rotary embedding (``full_rope`` False: ``rope`` is not
        called, where its own ``width=0`` would rotate the whole head).
        Without the fields above every layer reads
        ``(n_heads, 0, (rope_theta, head_dim, (), 1.0))``."""
        if kind == "sliding_attention":
            return (
                self.sliding_heads or self.n_heads, self.sliding_window,
                (self.sliding_rope_theta or self.rope_theta, self.head_dim, (), 1.0),
            )
        if not self.full_rope and kind == "full_attention":
            return self.n_heads, 0, (self.rope_theta, 0, (), 1.0)
        yarn = tuple(self.rope_yarn)
        return (
            self.n_heads, 0,
            (self.rope_theta, int(self.head_dim * self.rope_share), yarn[:4], yarn[4] if yarn else 1.0),
        )

    def attention_windows(self) -> tuple:
        """The window of every attention layer (0: none), in order."""
        return tuple(
            self.attention_form(kind)[1] for kind in self.layer_kinds() if kind not in STATE_KINDS
        )

    def tiles_visited_share(self, segment_ids) -> Optional[float]:
        """Of the tiles in the attention layers' forward grids for a packed
        host batch (``segment_ids`` [B, S], numpy), the share the kernels
        visit: a mean over the attention layers, each in its form. A plain
        layer's grid is ``ops.flash.tiles_visited_share``, a windowed one's
        the same with the window's tiles counted out, and a layer of chunk
        summaries on a row longer than its window counts the tiles of its two
        grids (``ops.eva.tiles_visited_share``), a two-stream layer those of
        its two bounded grids (``ops.blockdiff.tiles_visited_share``). None
        where any form's tiles do not divide the row."""
        if self.stream_block:  # every layer the two grids of the block-wise mask
            return blockdiff.tiles_visited_share(segment_ids, block=self.stream_block, head_dim=self.head_dim)
        kinds = [kind for kind in self.layer_kinds() if kind not in STATE_KINDS]
        forms = [  # (window, chunk) a layer; chunk 0: no summaries
            (self.eva_window, self.eva_chunk) if kind == "eva_attention" else (window, 0)
            for kind, window in zip(kinds, self.attention_windows())
        ]
        if not any(map(any, forms)):  # every layer plain: one form
            forms = [(0, 0)]

        def visited(window, chunk):
            if chunk and segment_ids.shape[1] > window:
                return eva.tiles_visited_share(segment_ids, window=window, chunk=chunk, head_dim=self.head_dim)
            return tiles_visited_share(segment_ids, head_dim=self.head_dim, window=0 if chunk else window)

        shares = {form: visited(*form) for form in set(forms)}
        if None in shares.values():
            return None
        return sum(shares[form] for form in forms) / len(forms)

    def __post_init__(self):
        if self.kv_lora_rank:
            if self.q_lora_rank < 0 or self.n_kv_heads != self.n_heads:
                raise ValueError("latent attention needs n_kv_heads == n_heads (q_lora_rank 0: a full-rank query)")
            if self.v_head_dim < 1 or self.qk_rope_head_dim < 2 or self.qk_rope_head_dim % 2:
                raise ValueError("latent attention takes a v_head_dim and an even qk_rope_head_dim")
            if self.decode:
                raise ValueError("latent attention has no decode cache yet")
        elif not self.head_width and self.d_model % self.n_heads:
            raise ValueError("d_model must be divisible by n_heads (or give head_width)")
        if self.n_heads % self.n_kv_heads or self.sliding_heads % self.n_kv_heads:
            raise ValueError("n_heads (and sliding_heads) must be divisible by n_kv_heads")
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_yarn", tuple(self.rope_yarn))
        if self.layer_types:
            if len(self.layer_types) != self.n_layers or set(self.layer_types) - set(LAYER_KINDS):
                raise ValueError(
                    f"layer_types names each of the {self.n_layers} layers one of {LAYER_KINDS}"
                )
            if self.decode and "conv" in self.layer_types:
                raise ValueError(
                    "a conv layer has no decode state yet (the convolution's tail "
                    "beside the KV cache): this model trains and scores, it does not serve"
                )
            if "latent_attention" in self.layer_types and not self.kv_lora_rank:
                raise ValueError("a latent_attention layer needs kv_lora_rank and the head's three widths")
            if "kda" in self.layer_types:
                if self.kda_head_dim < 1 or self.kda_conv_kernel < 1 or self.kda_decay_floor >= 0:
                    raise ValueError("a kda layer needs kda_head_dim, kda_conv_kernel >= 1 and a kda_decay_floor below 0")
                chunk = self.kda_chunk
                if chunk < 1 or (chunk > ops_kda.SUB_CHUNK and chunk % ops_kda.SUB_CHUNK):
                    raise ValueError(f"kda_chunk is at most {ops_kda.SUB_CHUNK} or a multiple of it")
                if -self.kda_decay_floor * (min(chunk, ops_kda.SUB_CHUNK) - 1) > ops_kda.MAX_EXPONENT:
                    raise ValueError("kda_decay_floor: a sub-chunk's decays must stay inside float32 (ops/kda.py)")
                if self.decode:
                    raise ValueError(
                        "a kda layer has a training form only: its state a head and the convolutions' "
                        "tails have no decode cache yet (ROADMAP M4)"
                    )
            if "sliding_attention" in self.layer_types:
                if self.sliding_window < 1:
                    raise ValueError("a sliding_attention layer needs sliding_window")
                if self.kv_lora_rank or self.sparse_topk or self.attention_fn is not None:
                    raise ValueError("a window is Attention's, through the automatic dispatch")
                if self.decode:
                    raise ValueError(
                        "a sliding_attention layer has a training form only: the page "
                        "allocator keeps every page of a row, so decode=True with a window "
                        "would hold what the window has left behind (ROADMAP M2)"
                    )
            if "eva_attention" in self.layer_types:
                if self.eva_chunk < 1 or self.eva_window < 1 or self.eva_window % self.eva_chunk:
                    raise ValueError("an eva_attention layer needs eva_window and an eva_chunk that divides it")
                if self.kv_lora_rank or self.sparse_topk or self.attention_fn is not None or self.attn_gate:
                    raise ValueError("chunk summaries are Attention's, through the automatic dispatch, ungated")
                if self.n_kv_heads != self.n_heads:
                    raise ValueError("an eva_attention layer takes n_kv_heads == n_heads: a summary a head")
                if self.decode:
                    raise ValueError(
                        "an eva_attention layer has a training form only: the chunk "
                        "summaries have no decode state beside the KV cache"
                    )
        if self.pred_heads < 1 or (self.pred_heads > 1 and self.tie_embeddings):
            raise ValueError("pred_heads >= 1, and more than one head takes an untied kernel")
        if self.rope_yarn and len(self.rope_yarn) != 5:
            raise ValueError(
                "rope_yarn is (factor, original positions, beta_fast, beta_slow, attention factor)"
            )
        rotated = self.head_dim * self.rope_share
        if self.rope_share != 1.0 and (not 0 < self.rope_share < 1 or rotated != int(rotated) or int(rotated) % 2):
            raise ValueError("rope_share of the head's width is an even number of dimensions")
        if (self.rope_share != 1.0 or self.rope_yarn) and self.kv_lora_rank:
            raise ValueError("latent attention rotates its own narrow part: no rope_share, no rope_yarn")
        if not self.full_rope and (self.rope_share != 1.0 or self.rope_yarn or self.kv_lora_rank or self.sparse_topk):
            raise ValueError(
                "full_rope=False is Attention's: a full layer with no rotary embedding has no rope_share "
                "and no rope_yarn, and neither the latent form nor the indexer, which rotate parts of "
                "their own, is written without positions"
            )
        if self.remat_policy not in REMAT_POLICIES:
            raise ValueError(
                f"remat_policy must be one of {sorted(REMAT_POLICIES)}"
            )
        if self.sparse_topk:
            if not (self.index_heads and self.index_head_dim) or self.index_head_dim % 2:
                raise ValueError("sparse_topk needs index_heads and an even index_head_dim")
            if self.kv_lora_rank or self.attention_fn is not None:
                raise ValueError("selected-key attention is Attention's, through the automatic dispatch")
            if self.decode:
                raise ValueError(
                    "selected-key attention has a training form only: no indexer "
                    "cache beside the KV cache, no selection inside decode"
                )
        if self.paged:
            if not self.decode:
                raise ValueError("paged=True requires decode=True")
            p = self.page_size
            if p < 1 or (p & (p - 1)):
                raise ValueError(f"page_size must be a power of two, got {p}")
            if self.max_seq_len % p:
                raise ValueError(
                    f"page_size ({p}) must divide max_seq_len "
                    f"({self.max_seq_len})"
                )
            if self.num_pages < 2:
                raise ValueError(
                    "paged=True needs num_pages >= 2 (page 0 is the "
                    f"reserved scratch page), got {self.num_pages}"
                )
        object.__setattr__(self, "ablated", frozenset(self.ablated))
        _parse_ablated(self.ablated, self.n_layers)  # validate eagerly

    def without(self, components) -> "DecoderConfig":
        """Factory-free model ablation (the flax-idiomatic counterpart of the
        reference's Keras-JSON layer surgery, loco.py:82-136): returns a
        config whose named components are gated out of the forward pass.
        ``components`` is a str or iterable of strs in the
        :func:`_parse_ablated` grammar. Param shapes are unchanged, so
        checkpoints/shardings transfer between variants."""
        if isinstance(components, str):
            components = (components,)
        return dataclasses.replace(
            self, ablated=self.ablated | frozenset(components)
        )

    @classmethod
    def llama3_8b(cls, **overrides) -> "DecoderConfig":
        """Llama-3-8B geometry (BASELINE config 3)."""
        return cls(
            **{
                **dict(
                    vocab_size=128_256,
                    d_model=4096,
                    n_layers=32,
                    n_heads=32,
                    n_kv_heads=8,
                    d_ff=14_336,
                    rope_theta=500_000.0,
                    max_seq_len=8192,
                    remat=True,
                    # 8k-context: minimum-HBM remat (dots would save
                    # ~50KB/token/layer of matmul outputs)
                    remat_policy="nothing",
                ),
                **overrides,
            }
        )

    @classmethod
    def tiny(cls, **overrides) -> "DecoderConfig":
        """Test/debug geometry: fits any host, compiles in seconds."""
        return cls(
            **{
                **dict(
                    vocab_size=256,
                    d_model=64,
                    n_layers=2,
                    n_heads=4,
                    n_kv_heads=2,
                    d_ff=128,
                    max_seq_len=128,
                ),
                **overrides,
            }
        )


def _partitioned(init, logical_axes, cfg):
    # getattr: _dense/RMSNorm are shared by model configs (Bert, MoE, ...)
    # that don't carry the pipeline-only partition_params switch.
    # logical_partitioning (not nn.with_partitioning): the names are LOGICAL
    # axes the trainer's rule tables resolve — flax must never apply them as
    # a raw sharding constraint (parallel/sharding.py LogicalPartitioned)
    if getattr(cfg, "partition_params", True):
        from maggy_tpu.parallel.sharding import logical_partitioning

        return logical_partitioning(init, logical_axes)
    return init


def _dense(features, logical_axes, cfg: DecoderConfig, name: str, dot_general=None):
    return nn.DenseGeneral(
        features=features,
        use_bias=False,
        dtype=cfg.dtype,
        param_dtype=cfg.param_dtype,
        kernel_init=_partitioned(nn.initializers.normal(stddev=0.02), logical_axes, cfg),
        dot_general=dot_general,
        name=name,
    )


class HeadKernel(nn.Module):
    """An untied head's kernel without its product, for a head that runs
    inside the loss (``models/head.py``): the leaf ``lm_head/kernel`` as
    ``_dense`` makes it."""

    cfg: DecoderConfig
    features: int

    @nn.compact
    def __call__(self):
        init = _partitioned(nn.initializers.normal(stddev=0.02), ("embed", "vocab"), self.cfg)
        return self.param("kernel", init, (self.cfg.d_model, self.features), self.cfg.param_dtype)


def _last_with_first(ndim, contracted):
    """``dot_general``'s dimension numbers: the last ``contracted`` dimensions
    of an lhs of ``ndim`` with the first of rhs."""
    return ((tuple(range(ndim - contracted, ndim)), tuple(range(contracted))), ((), ()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _projection(contracted, x, w):
    return jax.lax.dot_general(x, w, _last_with_first(x.ndim, contracted))


def _projection_fwd(contracted, x, w):
    return _projection(contracted, x, w), (x, w)


def _projection_bwd(contracted, res, g):
    # The operands as matrices: x [.., d_in] and w [d_in, features], where w is
    # [d, heads, width] (d_in = d), the matrix [d, features], or wo's [heads,
    # width, d] (d_in = heads x width, contracted as one: x is [.., heads,
    # width]). For a matrix the reshapes are the identity.
    x_shape, w_shape = res[0].shape, res[1].shape
    x = res[0].reshape(*x_shape[: len(x_shape) - contracted], -1)
    w = res[1].reshape(x.shape[-1], -1)
    lead = tuple(range(x.ndim - 1))  # batch and sequence stay apart: each may be sharded
    g2 = g.reshape(*x.shape[:-1], -1)
    # The barrier holds the weight's gradient as the bfloat16 matrix [d,
    # features], a product of its own. Without it XLA fuses what consumes the
    # gradient into the product: AdamW's update of an unrolled layer's leaf
    # (parameter, mu, nu read and written through the product's output tile,
    # which halves the product's rate) or the cast and write into a scanned
    # layer's stacked float32 gradient; and for a head-shaped kernel it folds the reshape in
    # and, since the cotangent comes back head-major (the flash kernels'
    # [heads, S, width], through rotary embedding and head norm), writes a
    # convolution whose window is the heads, with a head-major result that
    # AdamW's update then reads parameter, mu and nu into through transposing
    # copies (wq, wk and wv; for wo the heads are a spatial dimension of the
    # operand, and the result is head-major the same way). A matrix has no
    # head-major layout: the product is a plain one, in the state's layout, and
    # its consumer a memory-bound fusion of its own. The price is one
    # transposing pass of the head-major array a projection (the cotangent, for
    # wo the attention's output) where XLA does not fuse it into what made it:
    # 0.4-0.9 ms for bf16[16384,32,128] and bf16[8192,72,128] on a v5e.
    dw = jax.lax.optimization_barrier(jax.lax.dot_general(x, g2, ((lead, lead), ((), ()))))
    dx = jax.lax.dot_general(g2, w, (((x.ndim - 1,), (1,)), ((), ())))
    # The input's gradient is held the same way where the product narrows
    # ([tokens, features] by [features, d] with features > d: w_gate's and
    # w_up's in a feed-forward wider than the model, a wide wq's). What
    # consumes such a dx is a sum with its siblings' and the row and column
    # reductions of the norm's backward before it; fused into the product they
    # tile it for their small float32 results and it runs at half its rate.
    # Held, the product is a plain one and the sum and the reductions one
    # memory-bound pass over [tokens, d]. Where the product widens (w_down's
    # [tokens, d] by [d, d_ff] in such a feed-forward) dx keeps autodiff's
    # form: its consumer is elementwise on [tokens, d_ff] (the SwiGLU's
    # backward) and runs as the product's epilogue, which a barrier would turn
    # into a pass over the wider array and one more live copy of it. (A shared
    # expert narrower than the model has the shapes the other way round; its
    # products are small and either form runs them alike. wo's product widens
    # or keeps the width: its dx goes to the attention's backward as it is.)
    # A product that keeps its width (evabyte's square wq, wk, wv, wo) is not
    # held either: with the weight gradients out of their way those dx run at
    # 80-83% of the peak with their consumers inside, and held they gained
    # nothing (3.34 for 3.36 ms) while what they fed became passes of their own,
    # 6.9 ms a step more (PERF.md section 6, PR 45).
    if g2.shape[-1] > x.shape[-1]:
        dx = jax.lax.optimization_barrier(dx)
    return dx.reshape(x_shape).astype(x.dtype), dw.reshape(w_shape).astype(w.dtype)


_projection.defvjp(_projection_fwd, _projection_bwd)


def _checked_projection(
    name, kernel_dims, contracted, lhs, rhs, dimension_numbers, precision=None, preferred_element_type=None
):
    """``_projection`` as a ``dot_general`` for ``nn.DenseGeneral``: ``name``
    takes a kernel of the dimensions ``kernel_dims``, whose first
    ``contracted`` are contracted with the last of ``lhs`` at the default
    precision, and refuses anything else. The forward is ``dot_general``'s
    on the operands as they are, bit for bit; the backward reads them as
    matrices (two contracted dimensions as one: ``[.., heads, width]`` as
    ``[.., heads x width]``, the kernel as ``[heads x width, d]``) and makes
    the two gradients as products of matrices, the weight's held apart from
    what consumes it and the input's too where it is narrower than the
    cotangent (``_projection_bwd``), each handed back in its operand's
    shape."""
    if (
        rhs.ndim != len(kernel_dims) or dimension_numbers != _last_with_first(lhs.ndim, contracted)
        or precision is not None or preferred_element_type is not None
    ):
        raise ValueError(
            f"{name} contracts the last {contracted} of lhs with the first of a [{', '.join(kernel_dims)}] kernel at "
            f"the default precision; got {lhs.shape} by {rhs.shape}, {dimension_numbers}, {precision}, "
            f"{preferred_element_type}"
        )
    return _projection(contracted, lhs, rhs)


# The rule's three forms: a projection to heads (``wq``/``wk``/``wv``,
# ``_head_dense``), whose cotangent the backward flattens to ``[tokens, heads x
# width]``; one from heads (``wo``), whose operand and kernel it flattens; and
# ``MLPBlock``'s three matrices.
head_dot_general = functools.partial(_checked_projection, "head_dot_general", ("d", "heads", "width"), 1)
merge_dot_general = functools.partial(_checked_projection, "merge_dot_general", ("heads", "width", "d"), 2)
matrix_dot_general = functools.partial(_checked_projection, "matrix_dot_general", ("d_in", "d_out"), 1)


def _head_dense(heads, logical_axes, cfg: DecoderConfig, name: str, stacked: bool = False):
    """A projection of ``Attention`` to ``heads`` heads. Its backward is
    ``head_dot_general``'s whatever its shape: the weight's gradient is a plain
    product in the state's layout (95-97% of a v5e's peak at evabyte's square
    widths, where the head-major fusion with AdamW inside ran at 33-58%) and
    AdamW's update of the leaf a pass of its own. One exception, by structure
    and not by a model's name: in a layer that is one of a ``stacked`` scan
    (a scan over several layers, whose gradients land in the scan's stacked
    buffer: there is no update to take out of the product) only a projection
    wider than the model takes the rule, as since PR 38 (Keye +0.58% with
    ``wq``, -0.30% with ``wk`` and ``wv`` too). Tried without the exception
    (PERF.md section 6, PR 45): the Keye step, compiled at the memory limit,
    then plans 14.71 GiB for 14.45 and computes the head's input gradient, a
    product of 2.55 T, twice to fit, with ``wk``/``wv`` or with ``wo`` alone
    under the rule: 2,255.5 -> 2,292.4 ms busy a step on the chip (+1.6%);
    the Mistral step read 258.13 -> 257.98 (nothing to win); the compiler
    puts the SDAR step 1.4% slower. The same holds for ``wo``
    (``Attention``)."""
    rule = not stacked or heads * cfg.head_dim > cfg.d_model
    return _dense((heads, cfg.head_dim), logical_axes, cfg, name, head_dot_general if rule else None)


class RMSNorm(nn.Module):
    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x):
        # getattr: the norm is shared by config classes without the field
        offset = getattr(self.cfg, "norm_unit_offset", False)
        scale = self.param(
            "scale",
            _partitioned(nn.initializers.zeros_init() if offset else nn.initializers.ones_init(), ("norm",), self.cfg),
            (x.shape[-1],),
            self.cfg.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.cfg.norm_eps)
        return (y * (1.0 + scale) if offset else y * scale).astype(self.cfg.dtype)


class LayerNorm(nn.Module):
    """Mean and variance over the last dimension, a scale and a bias (the
    indexer's key norm); float32 inside."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x):
        width, cfg = x.shape[-1], self.cfg
        scale = self.param("scale", _partitioned(nn.initializers.ones_init(), ("norm",), cfg), (width,), cfg.param_dtype)
        bias = self.param("bias", _partitioned(nn.initializers.zeros_init(), ("norm",), cfg), (width,), cfg.param_dtype)
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * jax.lax.rsqrt(var + cfg.norm_eps) * scale + bias).astype(cfg.dtype)


def yarn_inv_freq(theta: float, width: int, factor: float, original: int, beta_fast: float, beta_slow: float):
    """YaRN's frequency table for a rotated width of ``width`` (float32
    [width / 2]): ``f_i = theta ** (-2i / width)``; the dimension whose
    wavelength turns ``r`` times in ``original`` positions is ``c(r) = width
    ln(original / (2 pi r)) / (2 ln theta)``; ``lo = floor(c(beta_fast))``,
    ``hi = ceil(c(beta_slow))`` (truncated, clipped to the table);
    ``m_i = 1 - clip((i - lo) / (hi - lo), 0, 1)`` and
    ``inv_freq_i = (1 - m_i) f_i / factor + m_i f_i``: fast dimensions keep
    their frequency, slow ones are interpolated by ``factor``."""
    import math

    def turns(r):
        return width * math.log(original / (r * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(turns(beta_fast)), 0)
    hi = min(math.ceil(turns(beta_slow)), width - 1)
    hi = hi + 0.001 if hi == lo else hi
    half = width // 2
    f = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    m = 1.0 - jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo), 0.0, 1.0)
    return (1.0 - m) * f / factor + m * f


def rope(
    x: jax.Array, positions: jax.Array, theta: float, *, width: int = 0, yarn: tuple = (), scale: float = 1.0,
) -> jax.Array:
    """Rotary position embedding over the last dim of [B, S, H, D] arrays.

    fp32 internally: sin/cos of large position*inv_freq products lose too much
    precision in bf16. ``width`` (0: all of D) is the rotary width: the first
    ``width`` dimensions are rotated, halves of them paired, and the others
    pass as they are. ``yarn`` = (factor, original positions, beta_fast,
    beta_slow) takes the frequencies from :func:`yarn_inv_freq` and not from
    ``theta ** (-2i / width)``; ``scale`` multiplies cos and sin (YaRN's
    attention factor: the rotated part of a query-key product grows by its
    square, the part that passes does not). Table and scale are constants of
    the trace, float32.
    """
    d = x.shape[-1]
    width = width or d
    half = width // 2
    if yarn:
        inv_freq = yarn_inv_freq(theta, width, *yarn)
    else:
        freq = jnp.arange(half, dtype=jnp.float32) / half
        inv_freq = theta ** (-freq)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, S, half]
    angles = angles[:, :, None, :]  # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if scale != 1.0:
        sin, cos = sin * scale, cos * scale
    x32 = x.astype(jnp.float32)
    # the forks here (``scale != 1.0``, ``width == d``), ``Attention``'s empty ``rotary`` and
    # ``softmax_route``'s ``scaling == 1.0`` keep the whole-head unscaled form's operations as they
    # were: the four older cells' steps then compile to the parent's programs (equal hashes from
    # ``benchmark/tools/compile_step.py``: PR 37's acceptance test), which one general form would not
    if width == d:
        x1, x2 = jnp.split(x32, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    else:
        x1, x2, rest = x32[..., :half], x32[..., half:width], x32[..., width:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.astype(x.dtype)


def flash_tileable(sq: int, sk: int, d: int) -> Optional[str]:
    """Why the automatic dispatch keeps a shape off the Pallas flash kernel
    (None: it tiles). head_dim must fill the 128 lanes, or half of them, and
    both sequence lengths be multiples of the 128 block, which is what
    guarantees that ``ops/flash.py``'s auto-chosen tiles are ones Mosaic can
    compile. Any multiple of 128 is admitted; 128 (every dense model) and 256
    (latent attention's 192 + 64, PR 26: B 2, S 8,192, 20 heads, compiled and
    run on one v5e, where 1,024 x 1,024 tiles do not fit VMEM and
    ``_auto_blocks`` chooses others) have run; wider heads have not. Width 64
    (PR 30: 32 query heads over 8 key-value heads, B 4, S 8,192) runs the same
    kernels with half-filled lanes (``ops/flash.py`` ``lane_fill``). A window
    changes nothing here: it is a mask inside a tile and a bound of the visit
    table, at every width the kernels take and at any tile size."""
    if jax.default_backend() != "tpu":
        return f"backend is {jax.default_backend()}"
    if lane_fill(d) is None:
        return f"head_dim {d} is not a multiple of 128 (nor 64)"
    if sq % 128 or sk % 128:
        return f"sequence lengths ({sq}, {sk}) are not multiples of 128"
    return None


def record_attention_kernel(
    kernel: str, q, k, segment_ids, reason: str = "", selected: int = 0, window: int = 0, chunk: int = 0,
    block: int = 0, true_widths: tuple = (),
):
    """Journal which kernel the automatic dispatch chose for this shape as
    one ``attention.kernel`` event; for the flash kernels also the tiles they
    run at (forward q, k, backward q, k), and always the head width with, for
    the flash kernels, how it fills the 128 lanes and which backward the row
    and the width take (``backward``: ``fused``, one kernel, or ``split``,
    two: ``ops.flash.backward_form``, which the kernels' call asks too), and
    ``selected``, the keys a query keeps where a selection masks the call,
    with the form the indexer's loss then takes (``index_loss``: ``kernel``
    where this call returns the heads' log-sum-exp, which only the one-chip
    flash kernels do, else ``blockwise``: ``ops.sparse_select.index_loss``)
    and ``index_passes``, the ``index_scores`` launches a block of queries,
    layer and training step as this call builds them: the forward's, from
    which thresholds and mask both come, and one more where the kernels'
    backward makes the mask again (``reselect``: 2); the XLA attention keeps
    the mask (1, and what a recomputed layer's replay runs again), and
    ``window``, the keys up to its own that a query of a sliding layer sees
    (0: every causal key). A layer of chunk summaries (``chunk`` > 0:
    ``ops/eva.py``) says so as ``form`` ``eva`` beside its ``window`` (there
    the window of the row's grid) and ``chunk``; the four tile sizes are then
    its local calls', on rows of one window, and ``remote_blocks`` the four of
    the calls on the summaries. A two-stream layer (``block`` > 0:
    ``ops/blockdiff.py``) says so as ``form`` ``blockdiff`` beside its ``block``
    and ``calls``, the flash calls a layer (2: one a stream, each under its
    causal bound a query), and ``own_block``, how the noised queries' own
    block is computed (``kernel``: the band kernels beside the flash calls;
    ``xla``: inside the explicit mask); ``q`` and ``kv`` are then one stream's.
    A call whose heads were padded with zeros to a width the kernels take
    (``padded_attention``; ``true_widths``: the query's and the value's own)
    says ``lanes`` ``padded`` with ``qk_width`` and ``v_width`` beside
    ``head_dim``, the width the kernels run at.
    The dispatch runs at trace time, so events count traces (init, forward, a
    rematerialized backward), never steps."""
    from maggy_tpu import telemetry

    attrs = {"head_dim": int(q.shape[3]), "window": int(window)}
    if selected:
        attrs["selected"] = int(selected)
        attrs["index_loss"] = "kernel" if kernel == "flash" else "blockwise"
        attrs["index_passes"] = 2 if kernel.startswith("flash") else 1
    if chunk:
        attrs.update(form="eva", chunk=int(chunk))
    if block:
        attrs.update(form="blockdiff", block=int(block), calls=2, own_block="kernel" if kernel == "flash" else "xla")
    if kernel.startswith("flash"):
        from maggy_tpu.ops.flash import _auto_blocks, backward_form

        attrs["lanes"] = lane_fill(q.shape[3])
        attrs["backward"] = backward_form(q.shape[1], q.shape[3])
        blocks = _auto_blocks(q.shape[1], k.shape[1], segment_ids is not None, q.shape[3])
        if chunk:
            tiles = eva.tiles(q.shape[1], window, chunk, q.shape[3])
            blocks, attrs["remote_blocks"] = tiles["local"], list(tiles["remote"])
        attrs.update(zip(("block_q", "block_k", "bwd_block_q", "bwd_block_k"), blocks))
    if true_widths:
        attrs.update(lanes="padded", qk_width=int(true_widths[0]), v_width=int(true_widths[1]))
    telemetry.get().event(
        "attention.kernel", kernel=kernel, reason=reason,
        q=list(q.shape), kv=list(k.shape), segmented=segment_ids is not None,
        **attrs,
    )


def auto_attention(
    q, k, v, *, causal: bool = True, segment_ids=None, selected=None, reselect=None, topk: int = 0,
    return_lse: bool = False, window: int = 0, true_widths: tuple = (),
):
    """Pick the fastest correct kernel for the backend/shape: the Pallas flash
    kernel (fwd+bwd) on TPU when the geometry tiles onto the MXU
    (:func:`flash_tileable`), otherwise the XLA dense path. Tile size is the
    whole game: with MXU-sized blocks (ops/flash.py ``_auto_blocks``, 512-row
    q tiles and up) the kernel won the full train step at every length
    measured on one v5e in round 2 (2026-07-29: 66.9k vs 60.7k tok/s at
    S=1024, 44.0k vs 22.8k at S=8192 against the dense path), where the old
    128x128 blocks lost to dense everywhere. With ``segment_ids`` the kernels
    also leave out the tiles a packed row masks wholly and choose their tiles
    again (PR 25: forward plus backward 10.7 ms against 14.1 at B 2, S 4,096
    on the packed4k rows; PERF.md section 6). Heads of width 256 (PR 26,
    latent attention at B 2, S 8,192, 20 query and key heads, the packed8k
    rows) take the same kernels at tiles chosen for the width: forward 7.5 ms
    and backward 20.1 ms a call on one v5e, a quarter of that model's train
    step. Since PR 31 the backward is one kernel that visits a score tile
    once (``ops/flash.py`` ``flash_bwd``; a row too long to keep a head's dq
    in VMEM, past S 32,768 at width 128, keeps the two-kernel split,
    ``backward_form``): 13.4 ms a call at that shape, 4.9 against 7.8 at B 2,
    S 4,096, 32/8 heads of 128. On a multi-device mesh the
    kernel runs per-shard under shard_map (a pallas_call has no GSPMD
    partitioning rule), each shard making its visit table from its own rows;
    incompatible layouts (sp/pp axes, non-divisible batch/heads) take the XLA
    path. The choice is recorded (:func:`record_attention_kernel`), never
    silent. ``selected`` (int8 [B, Sq, Sk], the ``topk`` keys a query keeps:
    ``ops/sparse_select.py``) masks every path the same way; the kernels take
    a tile of it as an operand, and their backward makes it again by
    ``reselect`` where the XLA path keeps the array. ``return_lse``: ``(out, lse)`` with the rows'
    log-sum-exp [B, H, Sq] where the one-chip kernels ran, which keep it
    anyway, and None on every other path (the caller normalises by itself).
    ``window`` (a sliding layer's; 0: none) masks the same pairs on every
    path: ``t - s < window`` beside the causal and the segment masks, and in
    the kernels the visit table's second bound (``ops/flash.py``).
    ``true_widths``: the event's only (``record_attention_kernel``)."""
    from maggy_tpu.parallel.mesh import ambient_mesh

    record = functools.partial(record_attention_kernel, true_widths=true_widths)
    masks = {} if selected is None else {"selected": selected}  # beside the causal and the segment masks
    if window:
        masks["window"] = window
    why = flash_tileable(q.shape[1], k.shape[1], q.shape[3])
    if why is None:
        mesh = ambient_mesh()
        if mesh is None or mesh.size == 1:
            record("flash", q, k, segment_ids, selected=topk, window=window)
            return flash_attention(
                q, k, v, causal=causal, segment_ids=segment_ids, return_lse=return_lse, reselect=reselect, **masks
            )
        out = sharded_flash_attention(
            q, k, v, mesh=mesh, causal=causal, segment_ids=segment_ids, reselect=reselect, **masks
        )
        if out is not None:
            record("flash_sharded", q, k, segment_ids, selected=topk, window=window)
            return (out, None) if return_lse else out
        why = f"mesh {dict(mesh.shape)} does not divide batch/heads or uses seq/stage axes"
    record("xla_dense", q, k, segment_ids, why, selected=topk, window=window)
    out = default_attention(q, k, v, causal=causal, segment_ids=segment_ids, **masks)
    return (out, None) if return_lse else out


def auto_eva_attention(q, k, v, ks, vs, *, segment_ids=None, window: int, chunk: int):
    """The dispatch of a layer of chunk summaries (``ops/eva.py``): on one TPU
    chip, where the two parts' shapes tile, the flash kernels on the windows'
    rows and on the summaries under their selection, joined by the rows'
    log-sum-exp (recorded as ``flash``, ``form`` ``eva``); anywhere else the
    same mathematics in XLA (``xla_dense``, with the reason)."""
    from maggy_tpu.parallel.mesh import ambient_mesh

    s, d = q.shape[1], q.shape[3]
    mesh = ambient_mesh()
    if jax.default_backend() != "tpu":
        why = f"backend is {jax.default_backend()}"
    elif mesh is not None and mesh.size > 1:
        why = f"mesh {dict(mesh.shape)}: the windows' rows and the summaries run on one chip"
    else:
        why = eva.untileable(s, window, chunk, d, compiled=True)
    if why is None:
        record_attention_kernel("flash", q, k, segment_ids, window=window, chunk=chunk)
        return eva.eva_attention(q, k, v, ks, vs, segment_ids, window=window, chunk=chunk)
    record_attention_kernel("xla_dense", q, k, segment_ids, why, window=window, chunk=chunk)
    return eva.eva_attention_xla(q, k, v, ks, vs, segment_ids, window=window, chunk=chunk)


def auto_blockdiff_attention(q, k, v, positions, segment_ids, lay, *, block: int):
    """The dispatch of a two-stream layer (``ops/blockdiff.py``): q [B, 2L, H,
    D], k, v [B, 2L, Kh, D], the clean stream first; ``positions``,
    ``segment_ids`` [B, L] and ``lay`` (``blockdiff.layout``) one stream's. On
    one TPU chip, where a stream's shape tiles, two calls of the flash kernels
    under their bounds (the clean queries block-causal on the clean keys; the
    noised queries on the clean keys before their block, whose softmax the
    band kernels continue over their own block's noised keys), recorded as
    ``flash``, ``form`` ``blockdiff``, ``own_block`` ``kernel``; anywhere else
    both streams on explicit masks in XLA (``xla_dense``, with the reason,
    ``own_block`` ``xla``): one choice for the two calls."""
    from maggy_tpu.parallel.mesh import ambient_mesh

    l, d = q.shape[1] // 2, q.shape[3]
    (q_c, q_n), (k_c, k_n), (v_c, v_n) = ((a[:, :l], a[:, l:]) for a in (q, k, v))
    mesh = ambient_mesh()
    if jax.default_backend() != "tpu":
        why = f"backend is {jax.default_backend()}"
    elif mesh is not None and mesh.size > 1:
        why = f"mesh {dict(mesh.shape)}: the two streams' calls run on one chip"
    else:
        why = blockdiff.untileable(l, d, block, compiled=True)
    if why is None:
        record_attention_kernel("flash", q_c, k_c, segment_ids, block=block)
        out_c = flash_attention(q_c, k_c, v_c, causal=True, segment_ids=segment_ids, bound=lay.hi_clean)
        out_n = blockdiff.noised_attention(q_n, k_c, v_c, k_n, v_n, segment_ids, lay, block=block)
    else:
        record_attention_kernel("xla_dense", q_c, k_c, segment_ids, why, block=block)
        out_c = default_attention(q_c, k_c, v_c, causal=True, segment_ids=segment_ids, bound=lay.hi_clean)
        out_n = default_attention(  # one softmax over the clean keys before the block and the block's noised ones
            q_n, jnp.concatenate([k_c, k_n], axis=1), jnp.concatenate([v_c, v_n], axis=1), causal=False,
            selected=blockdiff.noised_mask(positions, segment_ids, lay, block),
        )
    return jnp.concatenate([out_c, out_n], axis=1)


def padded_attention(attn, q, k, v, segment_ids=None):
    """Causal attention for heads the flash kernels do not take as they are
    (a query and key width that is no multiple of 128, or a value width of its
    own: 192 beside 128): q, k and v padded with zeros to one width that fills
    the lanes, the result cut to the value's. Zeros add nothing to a score and
    give zero output columns; the kernels divide the scores by the root of the
    width they see, so q is scaled by ``sqrt(padded / true)`` first. What the
    padding costs is the kernels' time over the true pairs (the benchmark
    books the pairs at the true widths). ``attn``: ``auto_attention`` or a
    function of its signature."""
    dq, dv = q.shape[-1], v.shape[-1]
    width = -(-max(dq, dv) // 128) * 128
    pad = lambda a: jnp.pad(a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))
    q = (q.astype(jnp.float32) * (width / dq) ** 0.5).astype(q.dtype)
    said = {"true_widths": (dq, dv)} if attn is auto_attention else {}
    return attn(pad(q), pad(k), pad(v), causal=True, segment_ids=segment_ids, **said)[..., :dv]


def default_attention(q, k, v, *, causal: bool = True, segment_ids=None, selected=None, window: int = 0, bound=None):
    """Reference soft-max attention: q [B,S,H,D], k/v [B,S,Kh,D] with GQA
    head-group broadcast. fp32 logits/softmax for stability. ``selected``
    [B, Sq, Sk]: the pairs a selection keeps (nonzero). ``window`` (with
    ``causal``): a query sees the ``window`` keys up to its own. ``bound``
    ([B, Sq] int32, with ``causal``): the query at row index ``t`` sees the
    keys at ``s <= bound[t]`` in place of ``s <= t``."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    group = h // kh
    q = q.reshape(b, sq, kh, group, d)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32)
    logits = logits / jnp.sqrt(d).astype(jnp.float32)
    if causal and bound is not None:
        mask = jnp.arange(k.shape[1])[None, None, :] <= bound[:, :, None]
        logits = jnp.where(mask[:, None, None], logits, -1e30)
    elif causal:
        sk = k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool))
        if window:
            mask = mask & ~jnp.tril(jnp.ones((sq, sk), dtype=bool), -window)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    if segment_ids is not None:
        seg_mask = segment_ids[:, None, None, :, None] == segment_ids[:, None, None, None, :]
        logits = jnp.where(seg_mask, logits, -1e30)
    if selected is not None:
        logits = jnp.where((selected != 0)[:, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(b, sq, h, d)


def head_gate(cfg: DecoderConfig, x, out, n_heads: int):
    """``out`` [B, S, H, D] times one sigmoid a head and token, from a
    bias-free projection ``w_head_gate`` of the layer's normed input ``x``
    (scope ``attn.gate``). Called inside the operator's ``nn.compact`` method."""
    with jax.named_scope("attn.gate"):  # one scalar a head and token, on the head's output before wo
        gate = _dense(n_heads, ("embed", "heads"), cfg, "w_head_gate")(x)
        return (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]).astype(cfg.dtype)


class Attention(nn.Module):
    """Grouped-query attention of a layer of ``kind`` (``LAYER_KINDS``): the
    kind gives the query heads, the window and the rotary form
    (``DecoderConfig.attention_form``); a "sliding_attention" layer sows
    ``window_pairs`` ([2]: the pairs inside window, document and causal order,
    the causal pairs inside documents) for the trainer's step metrics. Under a
    two-stream layout (``cfg.stream_block``) the row holds a clean and a
    noised stream, which the four projections, the head norms and the rotary
    embedding read as one row of ``2L`` positions (``_stream_attention``).
    The four projections take ``_projection``'s backward rule, ``wq``, ``wk``
    and ``wv`` to heads (``_head_dense``) and ``wo`` from them
    (``merge_dot_general``): the forward, the parameters' names, shapes and
    axes are ``nn.DenseGeneral``'s own. ``stacked`` says that the layer is
    one of a scan over several, whose gradients land in the scan's stacked
    buffer and not in a leaf AdamW reads: the code that builds the stack sets
    it (``Decoder``, ``MoEDecoder``, ``_ScannedPeriod``; no configuration
    does), and such a layer keeps ``dot_general``'s own transpose but for a
    ``wq`` wider than the model (``_head_dense`` has the measured reasons)."""

    cfg: DecoderConfig
    kind: str = "full_attention"
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        hd = cfg.head_dim
        n_heads, window, (theta, width, yarn, scale) = cfg.attention_form(self.kind)
        rotary = {} if (width, yarn, scale) == (hd, (), 1.0) else dict(width=width, yarn=yarn, scale=scale)
        q = _head_dense(n_heads, ("embed", "heads", None), cfg, "wq", self.stacked)(x)
        k = _head_dense(cfg.n_kv_heads, ("embed", "kv", None), cfg, "wk", self.stacked)(x)
        v = _head_dense(cfg.n_kv_heads, ("embed", "kv", None), cfg, "wv", self.stacked)(x)
        if cfg.qk_norm:  # over the head's width, one scale for all heads
            q = RMSNorm(cfg, name="q_norm")(q)
            k = RMSNorm(cfg, name="k_norm")(k)
        if width:  # 0: a layer with no positional embedding (``full_rope`` False)
            q = rope(q, positions, theta, **rotary)
            k = rope(k, positions, theta, **rotary)
        if self.kind == "eva_attention":
            out = self._summary_attention(q, k, v, positions, segment_ids)
        elif cfg.decode:
            out = self._cached_attention(q, k, v, positions, segment_ids)
        elif cfg.stream_block:
            out = self._stream_attention(q, k, v, positions, segment_ids)
        elif cfg.sparse_topk:
            out = self._selected_attention(x, q, k, v, positions, segment_ids)
        elif window:
            out = auto_attention(q, k, v, causal=True, segment_ids=segment_ids, window=window)
            # a query at position p of its document sees min(p + 1, window) of its p + 1 causal keys
            seen = (positions.astype(jnp.float32) + 1.0) * (1.0 if segment_ids is None else segment_ids > 0)
            self.sow(
                "intermediates", "window_pairs",
                jnp.stack([jnp.minimum(seen, float(window)).sum(), seen.sum()]),
            )
        else:
            attn = cfg.attention_fn or auto_attention
            out = attn(q, k, v, causal=True, segment_ids=segment_ids)
        if cfg.attn_gate:
            out = head_gate(cfg, x, out, n_heads)
        out = nn.DenseGeneral(
            features=cfg.d_model,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=_partitioned(
                nn.initializers.normal(stddev=0.02), ("heads", None, "embed"), cfg
            ),
            dot_general=None if self.stacked else merge_dot_general,
            name="wo",
        )(out)
        return out

    def _stream_attention(self, q, k, v, positions, segment_ids):
        """Two streams of one row (``ops/blockdiff.py``): the first ``L`` of the
        ``2L`` positions are the clean stream, the others the noised one, with
        the same positions and segment ids in both halves. A clean query sees
        the clean keys of its document up to the end of its block of
        ``cfg.stream_block`` positions; a noised one the noised keys of its
        own block and the clean keys before it, under one softmax. The bounds
        come from positions and segment ids under the scope
        ``diffusion.noise``. Sows ``blockdiff_pairs`` ([2]: the pairs the mask
        keeps for the real queries, all three forms; one causal stream's pairs
        inside documents) for the trainer's step metrics."""
        block, l = self.cfg.stream_block, q.shape[1] // 2
        pos = positions[:, :l]
        seg = jnp.ones(pos.shape, jnp.int32) if segment_ids is None else segment_ids[:, :l]
        with jax.named_scope("diffusion.noise"):
            lay = blockdiff.layout(pos, seg, block)
            self.sow("intermediates", "blockdiff_pairs", blockdiff.pairs(pos, seg, lay, block))
        return auto_blockdiff_attention(q, k, v, pos, seg, lay, block=block)

    def _summary_attention(self, q, k, v, positions, segment_ids):
        """A layer of chunk summaries (``ops/eva.py``): two learned vectors a
        head, ``eva_phi`` (what a chunk's keys are weighed by) and ``eva_mu``
        (added to the summary key), make one key and value of every
        ``eva_chunk`` positions under the scope ``eva.prep``; a query then
        sees its own window's keys and the earlier windows' summaries under
        one softmax. A row of at most ``eva_window`` positions has no earlier
        window: plain causal attention, the two vectors unused. Sows
        ``eva_counts`` ([4]: summaries the step's real queries see, all the
        entries they see, chunks in which two documents meet, chunks) for the
        trainer's step metrics, counted from positions and segment ids."""
        cfg = self.cfg
        b, s, h, hd = q.shape
        window, chunk = cfg.eva_window, cfg.eva_chunk
        vector = lambda name: self.param(
            name, _partitioned(nn.initializers.normal(stddev=0.02), ("heads", None), cfg), (h, hd), cfg.param_dtype
        )
        phi, mu = vector("eva_phi"), vector("eva_mu")
        if s <= window:
            return auto_attention(q, k, v, causal=True, segment_ids=segment_ids)
        eva.check_grid(s, window, chunk)
        with jax.named_scope("eva.prep"):
            ks, vs = eva.summaries(k, v, phi, mu, segment_ids, chunk)
        out = auto_eva_attention(q, k, v, ks, vs, segment_ids=segment_ids, window=window, chunk=chunk)
        # a query at row index t, position p of its document: min(p, t mod window) + 1 exact keys, and the
        # chunks from the one that ends first inside its document to the last before its window
        at = jnp.arange(s, dtype=jnp.int32)[None, :]
        seg = jnp.ones((b, s), jnp.int32) if segment_ids is None else segment_ids
        real = (seg > 0).astype(jnp.float32)
        local = jnp.minimum(positions, at % window) + 1
        remote = jnp.maximum(at // window * (window // chunk) - (at - positions) // chunk, 0)
        cut = seg[:, ::chunk] != seg[:, chunk - 1::chunk]
        self.sow(
            "intermediates", "eva_counts",
            jnp.stack([
                (remote * real).sum(), ((remote + local) * real).sum(),
                cut.sum().astype(jnp.float32), jnp.float32(b * (s // chunk)),
            ]),
        )
        return out

    def _selected_attention(self, x, q, k, v, positions, segment_ids):
        """Attention over each query's ``sparse_topk`` keys of largest index
        score (``ops/sparse_select.py``). The indexer reads the layer's normed
        input behind a stop-gradient: ``index_heads`` query heads and one key
        head (a LayerNorm on it) of ``index_head_dim``, the rotary embedding
        on their whole width, and a float32 weight a head; it learns from
        ``index_aux_loss`` alone (sown: the KL from the heads' mean
        probabilities on the selected keys to the index's softmax there), and
        the rest of the model from the cross entropy alone. Sows
        ``sparse_counts`` ([3]: pairs selected, pairs visible, queries whose
        set is not ``min(sparse_topk, visible)`` keys) where it selects: a row
        of at most ``sparse_topk`` positions takes the dense path whole."""
        cfg = self.cfg
        heads, width, topk = cfg.index_heads, cfg.index_head_dim, cfg.sparse_topk
        s = x.shape[1]
        with jax.named_scope("sparse.index"):
            u = jax.lax.stop_gradient(x)
            qi = rope(_dense((heads, width), ("embed", None, None), cfg, "index_q")(u), positions, cfg.rope_theta)
            ki = LayerNorm(cfg, name="index_k_norm")(_dense(width, ("embed", None), cfg, "index_k")(u))
            ki = rope(ki[:, :, None], positions, cfg.rope_theta)[:, :, 0]
            w = nn.DenseGeneral(
                features=heads, use_bias=False, dtype=jnp.float32, param_dtype=cfg.param_dtype,
                precision=jax.lax.Precision.HIGHEST,
                kernel_init=_partitioned(nn.initializers.normal(0.02), ("embed", None), cfg),
                name="index_w",
            )(u.astype(jnp.float32)) * (heads**-0.5 * width**-0.5)
            qi = qi.transpose(0, 2, 1, 3)  # [B, J, S, Dj]: a head's rows together
        segs = None if segment_ids is None else segment_ids.astype(jnp.int32)[:, None]
        mask = index_lse = reselect = None
        if s > topk:
            mask, counts, index_lse, thresholds = sparse_select.select(qi, ki, w, segs, topk)
            reselect = jax.tree_util.Partial(sparse_select.selection_mask, qi, ki, w, segs, thresholds)
            self.sow("intermediates", "sparse_counts", counts)
        out, lse = auto_attention(
            q, k, v, causal=True, segment_ids=segment_ids, selected=mask, reselect=reselect,
            topk=topk if s > topk else 0, return_lse=True,
        )
        with jax.named_scope("sparse.index_loss"):
            real = jnp.ones(x.shape[:2], bool) if segment_ids is None else segment_ids > 0
            self.sow(
                "intermediates", "index_aux_loss",
                sparse_select.index_loss(qi, ki, w, q, k, lse, mask, segs, real, index_lse),
            )
        return out

    def _cached_attention(self, q, k, v, positions, segment_ids=None):
        """Incremental decoding: append this chunk's K/V to a cache of
        ``max_seq_len`` and attend the chunk's queries over everything cached
        so far (the KV-cache path the recompute-based generate() lacks).

        Length-adaptive reads: the cache is consumed in
        ``decode_chunk``-sized blocks under a dynamic-trip-count loop that
        stops after the last WRITTEN chunk, so per-step HBM traffic — the
        decode bottleneck — is proportional to the actual prefix, not
        ``max_seq_len``. Online-softmax across chunks (same recurrence as
        ops.attention) keeps the math exact.

        Packed batches: with ``segment_ids`` a packed
        prompt prefills in ONE pass — the ids are cached alongside K/V and
        every read is masked to the query's segment, so segments cannot
        attend across their boundaries. Later single-token steps may omit
        ``segment_ids``; once the ``seg`` track exists the new token extends
        the row's most recent segment. Unpacked flows never create the track
        and keep the exact original compute.

        The write index is PER ROW (``[B]`` int32, not a scalar): each batch
        row carries its own cache length, so rows may sit at different
        sequence positions — the enabler for slot-based continuous batching
        (maggy_tpu/serve), where one compiled step decodes requests admitted
        at different times. Lockstep callers (generate_cached, prefill) keep
        identical values in every row and reproduce the old scalar
        semantics exactly.

        Paged mode (``cfg.paged``; docs/serving.md "Paged KV cache")
        replaces the ``[B, max_seq_len]`` row reservation with a flat page
        pool plus per-row page-table indirection — same math, same masks,
        storage decoupled from batch width. The packed ``segment_ids``
        track is a dense-path feature (the serve engine never packs)."""
        cfg = self.cfg
        if cfg.paged:
            if segment_ids is not None or self.has_variable("cache", "seg"):
                raise NotImplementedError(
                    "paged decode does not support packed segment_ids"
                )
            return self._paged_cached_attention(q, k, v, positions)
        b, t, kh, hd = k.shape
        k_cache = self.variable(
            "cache", "k",
            lambda: jnp.zeros((b, cfg.max_seq_len, kh, hd), cfg.dtype),
        )
        v_cache = self.variable(
            "cache", "v",
            lambda: jnp.zeros((b, cfg.max_seq_len, kh, hd), cfg.dtype),
        )
        index = self.variable(
            "cache", "index", lambda: jnp.zeros((b,), jnp.int32)
        )
        idx = index.value  # [B] per-row write offsets

        def _row_write(cache_row, update_row, start):
            return jax.lax.dynamic_update_slice(
                cache_row, update_row, (start, 0, 0)
            )

        # device-side scopes (telemetry/metrics.py SCOPES): metadata only
        with jax.named_scope("kv_write"):
            k_all = jax.vmap(_row_write)(k_cache.value, k.astype(cfg.dtype), idx)
            v_all = jax.vmap(_row_write)(v_cache.value, v.astype(cfg.dtype), idx)
        k_cache.value = k_all
        v_cache.value = v_all
        index.value = idx + t

        # packed-segment track: static trace-time decision (flax variable
        # presence), so unpacked decode pays nothing
        seg_all = seg_q = None
        if segment_ids is not None or self.has_variable("cache", "seg"):
            seg_cache = self.variable(
                "cache", "seg",
                lambda: jnp.zeros((b, cfg.max_seq_len), jnp.int32),
            )
            if segment_ids is None:
                # continuation: the new token(s) extend the most recent
                # segment written for the row
                last = jax.vmap(
                    lambda row, i: jax.lax.dynamic_slice_in_dim(
                        row, jnp.maximum(i - 1, 0), 1
                    )
                )(seg_cache.value, idx)
                seg_q = jnp.broadcast_to(last, (b, t))
            else:
                seg_q = segment_ids.astype(jnp.int32)
            seg_all = jax.vmap(
                lambda row, upd, i: jax.lax.dynamic_update_slice(row, upd, (i,))
            )(seg_cache.value, seg_q, idx)
            seg_cache.value = seg_all

        S = cfg.max_seq_len
        chunk = min(cfg.decode_chunk, S)
        while S % chunk:  # dynamic_slice must never clamp past the end
            chunk //= 2
        if chunk < 16:
            chunk = S  # pathological lengths: one full-cache chunk
        h = q.shape[2]
        scale = 1.0 / (hd**0.5)
        written = idx + t  # [B] per-row cache lengths after this write
        # chunks covering the LONGEST row's prefix (the loop bound must be a
        # scalar; shorter rows mask out the excess), clamped so the final
        # dynamic_slice can never be position-shifted by end-clamping
        # (over-long prompt buffers)
        n_valid = jnp.minimum(
            (jnp.max(written) + chunk - 1) // chunk, S // chunk
        )

        # a query's own write location in the cache; for packed rows this is
        # the causal clock (``positions`` restart per segment there, so they
        # cannot order keys across the whole cache)
        qslot = idx[:, None] + jnp.arange(t)[None, :]  # [B, t]

        def body(ci, carry):
            k_c = jax.lax.dynamic_slice_in_dim(k_all, ci * chunk, chunk, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v_all, ci * chunk, chunk, axis=1)
            kpos = ci * chunk + jnp.arange(chunk)
            w_row = written[:, None, None, None]  # per-row valid-key bound
            if seg_all is None:
                # causal over the cache: a query at position p sees keys at
                # <= p that have actually been written (positions == cache
                # slots on this path)
                mask = (
                    kpos[None, None, None, :] <= positions[:, None, :, None]
                ) & (kpos[None, None, None, :] < w_row)
            else:
                # packed: causal in CACHE ORDER (packing preserves a row's
                # temporal order) and restricted to the query's own segment
                seg_c = jax.lax.dynamic_slice_in_dim(
                    seg_all, ci * chunk, chunk, axis=1
                )
                mask = (
                    (kpos[None, None, None, :] <= qslot[:, None, :, None])
                    & (kpos[None, None, None, :] < w_row)
                    & (seg_c[:, None, None, :] == seg_q[:, None, :, None])
                )
            return ops_attn.online_block_update(
                carry,
                q,
                ops_attn.repeat_kv(k_c, h),
                ops_attn.repeat_kv(v_c, h),
                mask,
                scale,
            )

        with jax.named_scope("decode_attn"):
            carry = ops_attn.init_carry(b, h, t, hd)
            acc, _, l = jax.lax.fori_loop(0, n_valid, body, carry)
            return ops_attn.finalize(acc, l, q.dtype)

    def _paged_cached_attention(self, q, k, v, positions):
        """Paged KV cache: K/V live in a flat pool of ``num_pages`` pages of
        ``page_size`` slots (``[N, P, Kh, Dh]``) and each batch row maps its
        logical positions to physical pages through a ``[B, max_seq_len/P]``
        int32 page-table row — the vLLM/Pallas paged-attention layout
        expressed at the XLA level. The table is a cache variable this
        module only READS; the serve engine's host-side block allocator
        owns it (allocation, prefix aliasing, release all happen by editing
        table rows, never by moving K/V bytes).

        Writes scatter each new token to ``(table[b, pos // P], pos % P)``.
        A released/inactive row's table is zeroed and its index clamped, so
        masked lockstep writes land on the reserved scratch page 0 —
        garbage by design, never read as valid.

        Reads run the SAME chunked online-softmax loop as the dense path,
        except each chunk is materialized by gathering ``chunk/P`` pages
        into a contiguous block (one gather per chunk — the XLA analogue of
        the paged-attention kernel's per-page DMA batch) instead of a
        contiguous ``dynamic_slice``. Chunk token count, masks and update
        order are identical to the dense path whenever ``page_size``
        divides the effective chunk, so paged decode output is
        BIT-identical to dense decode — the byte-parity contract
        tests/test_paged_kv.py enforces."""
        cfg = self.cfg
        b, t, kh, hd = k.shape
        P = cfg.page_size
        S = cfg.max_seq_len
        max_pages = S // P
        k_pool = self.variable(
            "cache", "k",
            lambda: jnp.zeros((cfg.num_pages, P, kh, hd), cfg.dtype),
        )
        v_pool = self.variable(
            "cache", "v",
            lambda: jnp.zeros((cfg.num_pages, P, kh, hd), cfg.dtype),
        )
        pages = self.variable(
            "cache", "pages", lambda: jnp.zeros((b, max_pages), jnp.int32)
        )
        index = self.variable(
            "cache", "index", lambda: jnp.zeros((b,), jnp.int32)
        )
        idx = index.value  # [B] per-row write offsets (logical positions)
        pt = pages.value  # [B, max_pages] logical page -> physical page

        # scatter this chunk's K/V through the page table: token j of row b
        # lands at (pt[b, (idx+j)//P], (idx+j)%P). Distinct live rows own
        # distinct pages, so scatter indices never collide except on the
        # scratch page (masked rows), whose content is garbage by contract.
        with jax.named_scope("kv_write"):
            pos_w = idx[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
            page_slot = jnp.clip(pos_w // P, 0, max_pages - 1)
            phys = jnp.take_along_axis(pt, page_slot, axis=1)  # [B, t]
            off = pos_w % P
            k_all = k_pool.value.at[phys, off].set(k.astype(cfg.dtype))
            v_all = v_pool.value.at[phys, off].set(v.astype(cfg.dtype))
        k_pool.value = k_all
        v_pool.value = v_all
        index.value = idx + t

        # identical chunk geometry to the dense path (bit parity): token
        # chunks of the dense size, materialized as cpp-page gathers
        chunk = min(cfg.decode_chunk, S)
        while S % chunk:
            chunk //= 2
        if chunk < 16:
            chunk = S
        cpp = max(1, chunk // P)  # pages per chunk
        tok_chunk = cpp * P
        n_chunks = max_pages // cpp
        h = q.shape[2]
        scale = 1.0 / (hd**0.5)
        written = idx + t  # [B] per-row logical lengths after this write
        n_valid = jnp.minimum(
            (jnp.max(written) + tok_chunk - 1) // tok_chunk, n_chunks
        )

        def body(ci, carry):
            pt_c = jax.lax.dynamic_slice(
                pt, (jnp.int32(0), ci * cpp), (b, cpp)
            )  # [B, cpp] physical page ids for this chunk
            k_c = k_all[pt_c].reshape(b, tok_chunk, kh, hd)
            v_c = v_all[pt_c].reshape(b, tok_chunk, kh, hd)
            kpos = ci * tok_chunk + jnp.arange(tok_chunk)
            w_row = written[:, None, None, None]  # per-row valid-key bound
            # causal over logical positions + written bound: exactly the
            # dense unpacked mask (unallocated table entries point at the
            # scratch page; their kpos >= written, so they are masked)
            mask = (
                kpos[None, None, None, :] <= positions[:, None, :, None]
            ) & (kpos[None, None, None, :] < w_row)
            return ops_attn.online_block_update(
                carry,
                q,
                ops_attn.repeat_kv(k_c, h),
                ops_attn.repeat_kv(v_c, h),
                mask,
                scale,
            )

        with jax.named_scope("decode_attn"):
            carry = ops_attn.init_carry(b, h, t, hd)
            acc, _, l = jax.lax.fori_loop(0, n_valid, body, carry)
            return ops_attn.finalize(acc, l, q.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention, training form (module docstring of
    :class:`DecoderConfig`'s ``kv_lora_rank``): ``c_q = norm(x W_qa)``,
    ``q = c_q W_qb``; ``[c_kv ; k_r] = x W_kva``, ``[k_nope ; v] =
    norm(c_kv) W_kvb``; each head's query is ``[q_nope ; rope(q_rope)]`` and
    its key ``[k_nope ; rope(k_r)]`` with the one rope key broadcast over the
    heads. The heads then go through the same dispatch as :class:`Attention`'s
    (``auto_attention``: the flash kernels at the head's full width; padded to
    it where the value's width is its own, ``padded_attention``). With
    ``q_lora_rank`` 0 the query is one product ``wq`` and has no inner norm;
    with ``attn_gate`` a sigmoid gate a head and token on the heads' outputs
    before ``wo``, as :class:`Attention`'s."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        h, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        # device-side scopes (telemetry/metrics.py SCOPES): metadata only
        with jax.named_scope("mla.q"):
            if cfg.q_lora_rank:
                c_q = RMSNorm(cfg, name="q_norm")(
                    _dense(cfg.q_lora_rank, ("embed", None), cfg, "wq_a")(x)
                )
                q = _dense((h, dn + dr), (None, "heads", None), cfg, "wq_b")(c_q)
            else:
                q = _dense((h, dn + dr), ("embed", "heads", None), cfg, "wq")(x)
        with jax.named_scope("mla.kv"):
            c_kv = _dense(cfg.kv_lora_rank + dr, ("embed", None), cfg, "wkv_a")(x)
            kv = _dense(
                (h, dn + cfg.v_head_dim), (None, "heads", None), cfg, "wkv_b"
            )(RMSNorm(cfg, name="kv_norm")(c_kv[..., : cfg.kv_lora_rank]))
        with jax.named_scope("mla.rope"):
            k_rope = rope(
                c_kv[..., None, cfg.kv_lora_rank:], positions, cfg.rope_theta
            )
            q = jnp.concatenate(
                [q[..., :dn], rope(q[..., dn:], positions, cfg.rope_theta)], axis=-1
            )
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(k_rope, (*kv.shape[:-1], dr))],
                axis=-1,
            )
        attn = cfg.attention_fn or auto_attention
        if cfg.v_head_dim == dn + dr:  # one width: the call as it always was
            out = attn(q, k, kv[..., dn:], causal=True, segment_ids=segment_ids)
        else:
            out = padded_attention(attn, q, k, kv[..., dn:], segment_ids)
        if cfg.attn_gate:
            out = head_gate(cfg, x, out, h)
        return nn.DenseGeneral(
            features=cfg.d_model,
            axis=(-2, -1),
            use_bias=False,
            dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=_partitioned(
                nn.initializers.normal(stddev=0.02), ("heads", None, "embed"), cfg
            ),
            name="wo",
        )(out)


def causal_taps(z, w, segment_ids=None):
    """A depthwise causal convolution of ``z`` [B, S, C] (float32) with
    ``w`` [K, C]: ``c_t = sum over j < K of w[K-1-j] * z[t-j]``, the last tap
    on the current position. A tap that would reach before the row's start, or
    with ``segment_ids`` into the previous document of a packed row, is zero.
    ``(c, taps zeroed)``, the count over ``B * S * K`` taps (int32)."""
    taps, s = w.shape[0], z.shape[1]
    c = w[taps - 1] * z
    masked = jnp.int32(0)
    for j in range(1, min(taps, s)):
        back = jnp.pad(z[:, : s - j], ((0, 0), (j, 0), (0, 0)))
        inside = jnp.arange(s)[None, :] >= j
        if segment_ids is not None:
            inside = inside & (
                jnp.pad(segment_ids[:, : s - j], ((0, 0), (j, 0))) == segment_ids
            )
            back = jnp.where(inside[..., None], back, 0.0)
        masked = masked + jnp.sum(~jnp.broadcast_to(inside, z.shape[:2]))
        c = c + w[taps - 1 - j] * back
    return c, masked


class ShortConv(nn.Module):
    """The gated short convolution of ``lfm2``: ``[B, C, x] = split3(u W_in)``,
    ``z = B * x``, ``c_t = sum over j < K of w[K-1-j] * z[t-j]`` (depthwise and
    causal, one weight a channel and tap, the last tap on the current
    position as a left-padded ``Conv1d`` has it; no bias, no activation),
    ``y = (C * c) W_out``. No positions enter it. With ``segment_ids`` a tap
    that would reach into the previous document of a packed row is zero, as
    the padding before a row's start is: a document gives what it gives alone.
    Plain ``jax.numpy`` under three named scopes; ``conv.mix`` holds the two
    gates and the taps (float32 inside the fusion, no product). Sows
    ``taps_masked`` ([2]: taps zeroed at row and document starts, of all
    ``B * S * K``) for the trainer's step metrics."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        if cfg.decode:
            raise NotImplementedError("ShortConv has no decode state (DecoderConfig refuses it)")
        d, taps = cfg.d_model, cfg.conv_kernel
        s = x.shape[1]
        with jax.named_scope("conv.in_proj"):
            bcx = _dense((3, d), ("embed", None, "channels"), cfg, "in_proj")(x)
        w = self.param(
            "conv",
            _partitioned(nn.initializers.normal(stddev=0.02), (None, "channels"), cfg),
            (taps, d),
            cfg.param_dtype,
        )
        with jax.named_scope("conv.mix"):
            gate_in, gate_out, u = (bcx[..., i, :].astype(jnp.float32) for i in range(3))
            c, masked = causal_taps(gate_in * u, w.astype(jnp.float32), segment_ids)
            y = (gate_out * c).astype(cfg.dtype)
        self.sow(
            "intermediates", "taps_masked",
            jnp.stack([masked, jnp.int32(x.shape[0] * s * taps)]),
        )
        with jax.named_scope("conv.out_proj"):
            return _dense(d, ("channels", "embed"), cfg, "out_proj")(y)


def _kda_rate_init(key, shape, dtype=jnp.float32):
    """``A_log`` as the flash-linear-attention layer draws it: the log of U(1, 16)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _kda_dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` as that layer draws it: the inverse softplus of a step log-uniform in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class KDA(nn.Module):
    """Kimi delta attention (Kimi Linear, arXiv:2510.26692; ``ops/kda.py``),
    the operator of a "kda" layer, on the normed input ``u``: ``q~, k~, v~ =
    u W_q, u W_k, u W_v`` in ``n_heads`` heads of ``kda_head_dim``; each goes
    through a depthwise causal convolution of ``kda_conv_kernel`` taps
    (``causal_taps``: a tap that would reach into the previous document is
    zero) and a SiLU; ``q = l2norm(q) / sqrt(d)``, ``k = l2norm(k)`` over the
    head's width; ``a = kda_decay_floor * sigmoid(exp(A_log_h) * (u W_f +
    dt_bias))`` a channel and ``beta = sigmoid(u W_beta)`` a head, float32;
    ``o = kda(q, k, v, a, beta)``, the delta rule with a state a head that
    starts at zero with every document; ``y = W_o(sigmoid(u W_g) *
    RMSNorm_head(o))``, one gate a head. No positions enter it. Five named
    scopes: ``kda.in_proj`` (the six products), ``kda.conv`` (taps, SiLU, the
    norms of q and k), ``kda.gate`` (``a``, ``beta``), ``kda.scan`` (all of
    ``ops/kda.py``) and ``kda.out`` (norm, gate, ``W_o``). Sows ``taps_masked``
    as :class:`ShortConv` does ([2]: taps zeroed at row and document starts of
    one stream's ``B * S * K``) and ``kda_counts`` ([4] float32: chunks with a
    document start inside, chunks, the sum of ``a`` and its count) for the
    trainer's step metrics; journals ``kda.kernel`` once a trace. The four
    wide projections and ``wo`` take ``_projection``'s backward rule as
    :class:`Attention`'s do (``stacked``: see there)."""

    cfg: DecoderConfig
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        cfg = self.cfg
        if cfg.decode:
            raise NotImplementedError("KDA has no decode state (DecoderConfig refuses it)")
        h, d, taps = cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
        b, s = x.shape[:2]
        with jax.named_scope("kda.in_proj"):
            q, k, v, f = (
                _dense((h, d), ("embed", "heads", None), cfg, name, head_dot_general)(x) for name in ("wq", "wk", "wv", "wf")
            )
            beta = _dense(h, ("embed", "heads"), cfg, "w_beta")(x)
            gate = _dense(h, ("embed", "heads"), cfg, "w_head_gate")(x)
        conv = {
            name: self.param(
                name, _partitioned(nn.initializers.normal(stddev=0.02), (None, "channels"), cfg), (taps, h * d),
                cfg.param_dtype,
            )
            for name in ("q_conv", "k_conv", "v_conv")
        }
        with jax.named_scope("kda.conv"):
            def stream(z, name):
                c, masked = causal_taps(z.reshape(b, s, h * d).astype(jnp.float32), conv[name].astype(jnp.float32), segment_ids)
                return nn.silu(c).reshape(b, s, h, d), masked

            unit = lambda z: z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)
            (q, masked), (k, _), (v, _) = stream(q, "q_conv"), stream(k, "k_conv"), stream(v, "v_conv")
            q, k, v = (unit(q) * d**-0.5).astype(cfg.dtype), unit(k).astype(cfg.dtype), v.astype(cfg.dtype)
        self.sow("intermediates", "taps_masked", jnp.stack([masked, jnp.int32(b * s * taps)]))
        a_log = self.param("A_log", _partitioned(_kda_rate_init, ("heads",), cfg), (h,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", _partitioned(_kda_dt_bias_init, ("heads", None), cfg), (h, d), cfg.param_dtype)
        with jax.named_scope("kda.gate"):
            rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
            a = cfg.kda_decay_floor * jax.nn.sigmoid(rate * (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)))
            beta = jax.nn.sigmoid(beta.astype(jnp.float32))
        with jax.named_scope("kda.scan"):
            form = ops_kda.kernel_form(cfg.kda_chunk, d, d, q.dtype)
            o = ops_kda.kda(q, k, v, a, beta, segment_ids, cfg.kda_chunk, form=form)
        ids = jnp.ones((b, s), jnp.int32) if segment_ids is None else segment_ids
        self.sow(
            "intermediates", "kda_counts",
            jnp.concatenate([ops_kda.chunks_cut(ids, cfg.kda_chunk).astype(jnp.float32), jnp.stack([a.sum(), jnp.float32(a.size)])]),
        )
        from maggy_tpu import telemetry

        telemetry.get().event("kda.kernel", chunk=int(cfg.kda_chunk), head_dim=int(d), form=form)
        with jax.named_scope("kda.out"):
            o = RMSNorm(cfg, name="o_norm")(o)
            o = (o.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]).astype(cfg.dtype)
            return nn.DenseGeneral(
                features=cfg.d_model,
                axis=(-2, -1),
                use_bias=False,
                dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=_partitioned(nn.initializers.normal(stddev=0.02), ("heads", None, "embed"), cfg),
                dot_general=None if self.stacked else merge_dot_general,
                name="wo",
            )(o)


def attention_module(cfg: DecoderConfig):
    """The attention class an attention layer of this configuration takes."""
    return LatentAttention if cfg.kv_lora_rank else Attention


def layer_operator(cfg: DecoderConfig, kind: str, x, positions, segment_ids, stacked: bool = False):
    """The operator a layer of ``kind`` takes (``LAYER_KINDS``) on the normed
    residual: ``attn`` under ``attn_norm``, ``conv`` under ``conv_norm`` or
    ``kda`` under ``kda_norm``. Called inside the layer's ``nn.compact`` method."""
    if kind == "conv":
        return ShortConv(cfg, name="conv")(RMSNorm(cfg, name="conv_norm")(x), positions, segment_ids)
    if kind == "kda":
        return KDA(cfg, stacked, name="kda")(RMSNorm(cfg, name="kda_norm")(x), positions, segment_ids)
    if kind == "latent_attention":
        return LatentAttention(cfg, name="attn")(RMSNorm(cfg, name="attn_norm")(x), positions, segment_ids)
    of_kind = {} if kind == "full_attention" else {"kind": kind}
    if stacked and not cfg.kv_lora_rank:
        of_kind["stacked"] = True
    return attention_module(cfg)(cfg, name="attn", **of_kind)(
        RMSNorm(cfg, name="attn_norm")(x), positions, segment_ids
    )


class MLPBlock(nn.Module):
    """SwiGLU. Its three matrices take ``matrix_dot_general``: each weight
    gradient is a plain product, and AdamW's update of the leaf (or the write
    into a scanned layer's stacked gradient) a fusion of its own; an input
    gradient narrower than its cotangent (``w_gate``'s and ``w_up``'s,
    ``[tokens, d_ff]`` by ``[d_ff, d_model]``, where ``d_ff > d_model``) is a
    plain product too, and the sum of the two with the norm's backward before
    this block one pass over ``[tokens, d_model]``; a wider one (``w_down``'s
    there) keeps the SwiGLU's elementwise backward as its epilogue (PERF.md
    section 5 has them at their measured shares of the peak)."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg.d_ff, ("embed", "mlp"), cfg, "w_gate", matrix_dot_general)(x)
        up = _dense(cfg.d_ff, ("embed", "mlp"), cfg, "w_up", matrix_dot_general)(x)
        return _dense(cfg.d_model, ("mlp", "embed"), cfg, "w_down", matrix_dot_general)(
            nn.silu(gate) * up
        )


def _constrain_residual(x):
    """Pin the residual stream's layout: batch over (data, fsdp), seq over sp,
    embed replicated. Settled behavior: every DecoderLayer exit re-asserts
    this one canonical placement, because on deep tp/fsdp/sp meshes GSPMD
    propagation from the tensor-sharded projections can otherwise drift the
    residual into an embed-sharded (or gathered) layout mid-stack and pay an
    all-gather per layer. The embed dim stays deliberately REPLICATED — a
    per-layer reduce-scatter/all-gather pair costs more than it saves at the
    d_models this family targets — and inside manual (shard_map) regions the
    constraint is a no-op by construction (constrain_activation degrades
    there), so the pipeline stage adapter composes with it unchanged."""
    from maggy_tpu.parallel.sharding import constrain_activation

    return constrain_activation(x, ("batch", "activation_seq", None))


class DecoderLayer(nn.Module):
    cfg: DecoderConfig
    kind: str = "full_attention"
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, gates=None, segment_ids=None):
        """``gates`` — optional [2] float (attn, mlp) LOCO ablation gates: a
        zero gate removes that sublayer's contribution (residual becomes
        identity) and cuts its gradients, with an unchanged param tree.
        ``segment_ids`` — optional [B, S] packed-sequence ids."""
        # the stream's type: float32 under ``residual_f32`` (the sums then run in it), else the layers' own
        into = (lambda a: a.astype(jnp.float32)) if self.cfg.residual_f32 else (lambda a: a)
        a = layer_operator(self.cfg, self.kind, x, positions, segment_ids, self.stacked)
        x = into(x) + into(a if gates is None else a * gates[0].astype(a.dtype))
        m = MLPBlock(self.cfg, name="mlp")(RMSNorm(self.cfg, name="mlp_norm")(x))
        x = x + into(m if gates is None else m * gates[1].astype(m.dtype))
        return _constrain_residual(x)


class _ScannedLayer(nn.Module):
    cfg: DecoderConfig
    kind: str = "full_attention"
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        return DecoderLayer(self.cfg, self.kind, self.stacked, name="layer")(
            x, positions, None, segment_ids
        ), None


class _ScannedGatedLayer(nn.Module):
    """Scan body when LOCO gates are active: gates ride the scan's in_axes=0
    so each layer sees its own (attn, mlp) pair."""

    cfg: DecoderConfig
    kind: str = "full_attention"
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, gates, segment_ids=None):
        return DecoderLayer(self.cfg, self.kind, self.stacked, name="layer")(
            x, positions, gates, segment_ids
        ), None


class Decoder(nn.Module):
    """LLaMA-style causal LM. ``__call__(tokens [B,S]) -> logits [B,S,V]``."""

    cfg: DecoderConfig

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None, targets=None):
        """``positions`` default to per-row arange; packed batches pass both
        ``positions`` (restarting per segment) and ``segment_ids`` [B, S]
        (attention masks across segment boundaries, SURVEY §5.7). With
        ``targets`` (``models/head.py`` ``Targets``, a row a head) the head
        runs inside the loss, in blocks of the sequence, and the model returns
        its heads' losses ``[pred_heads]`` float32 in the place of logits."""
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
            )
        embed = self.param(
            "embedding",
            _partitioned(
                nn.initializers.normal(stddev=1.0), ("vocab", "embed"), cfg
            ),
            (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype,
        )
        x = _constrain_residual(jnp.asarray(embed, cfg.dtype)[tokens])
        if cfg.residual_f32:
            x = x.astype(jnp.float32)

        gates = _parse_ablated(cfg.ablated, cfg.n_layers)
        kinds = cfg.layer_kinds()
        if cfg.scan_layers and len(set(kinds)) > 1:
            raise ValueError(
                "Decoder scans layers of one kind: a mixed layer_types takes "
                "scan_layers=False here, or MoEDecoder, which scans whole periods"
            )
        layer_cls = _ScannedLayer if gates is None else _ScannedGatedLayer
        if cfg.remat and not cfg.decode:  # no gradients (hence no remat) in decode
            layer_cls = nn.remat(
                layer_cls,
                prevent_cse=not cfg.scan_layers,
                policy=REMAT_POLICIES[cfg.remat_policy],
            )
        if cfg.scan_layers:
            scanned = nn.scan(
                layer_cls,
                variable_axes={"params": 0, "cache": 0, "intermediates": 0},
                split_rngs={"params": True},
                # positions/segment_ids are the same for every layer; LOCO
                # gates are per-layer
                in_axes=(
                    (nn.broadcast, nn.broadcast)
                    if gates is None
                    else (nn.broadcast, 0, nn.broadcast)
                ),
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: None},
            )(cfg, kinds[0], cfg.n_layers > 1, name="layers")
            if gates is None:
                x, _ = scanned(x, positions, segment_ids)
            else:
                x, _ = scanned(x, positions, jnp.asarray(gates), segment_ids)
        else:
            for i in range(cfg.n_layers):
                if gates is None:
                    x, _ = layer_cls(cfg, kinds[i], name=f"layers_{i}")(
                        x, positions, segment_ids
                    )
                else:
                    x, _ = layer_cls(cfg, kinds[i], name=f"layers_{i}")(
                        x, positions, jnp.asarray(gates[i]), segment_ids
                    )

        x = RMSNorm(cfg, name="final_norm")(x)
        if targets is not None:
            width = cfg.vocab_size
            kernel = embed if cfg.tie_embeddings else HeadKernel(cfg, width * cfg.pred_heads, name="lm_head")()
            return jnp.stack([
                head.loss(
                    x, kernel if cfg.pred_heads == 1 else kernel[:, i * width:(i + 1) * width], targets.ids[i],
                    targets.weights[i], rows=targets.rows, dtype=cfg.dtype, tied=cfg.tie_embeddings,
                    softcap=cfg.logits_softcap,
                )
                for i in range(cfg.pred_heads)
            ])
        if cfg.tie_embeddings:
            with jax.named_scope("lm_head"):  # the scope the untied head's module gives
                logits = jnp.einsum("bsd,vd->bsv", x, jnp.asarray(embed, cfg.dtype))
        else:
            logits = _dense(cfg.vocab_size * cfg.pred_heads, ("embed", "vocab"), cfg, "lm_head")(x)
        if cfg.logits_softcap:
            logits = jnp.tanh(logits / cfg.logits_softcap) * cfg.logits_softcap
        logits = logits.astype(jnp.float32)
        if cfg.pred_heads > 1:  # head i's columns are i * vocab_size onward; head 0 is the model's output
            heads = logits.reshape(*logits.shape[:-1], cfg.pred_heads, cfg.vocab_size)
            self.sow("intermediates", "mtp_logits", heads[..., 1:, :])
            return heads[..., 0, :]
        return logits
