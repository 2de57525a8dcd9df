"""Mixtral-style sparse Mixture-of-Experts decoder.

Expert parallelism is absent from the reference (§2.10) and required by the
BASELINE Mixtral config. TPU-first design: GShard-style dense dispatch —
top-k routing builds one-hot dispatch/combine tensors with a static per-expert
capacity, expert FFNs are a single batched einsum over parameters laid out
[experts, ...] and sharded on the ``expert`` mesh axis, so XLA inserts the
token all-to-alls and the whole layer stays static-shaped for the MXU.

The second form (``MoEConfig.experts_held`` > 0; DeepSeek-V3's layer as
``glm4_moe_lite`` keeps it) is :class:`ExpertShareBlock`: a sigmoid router
over all the published experts, a shared expert beside them, and the chip's
share of the routed ones computed dropless by grouped matrix products over the
slots that exist. The stack takes a layer pattern: ``n_dense_layers`` leading
dense layers, then the expert layers under the scan, then (``mtp_depth``) the
multi-token-prediction module. Where the layers' operators differ
(``DecoderConfig.layer_types``: attention or the gated short convolution) the
scan's body is one period of the pattern (``layer_plan``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from maggy_tpu.models import head
from maggy_tpu.models.transformer import (
    REMAT_POLICIES,
    DecoderConfig,
    HeadKernel,
    MLPBlock,
    RMSNorm,
    _dense,
    _parse_ablated,
    _partitioned,
    _ScannedGatedLayer,
    _ScannedLayer,
    layer_operator,
)


# the routed experts' gate activation by ``MoEConfig.expert_act``
EXPERT_ACTS = {"silu": nn.silu, "relu": nn.relu}


@dataclasses.dataclass(frozen=True)
class MoEConfig(DecoderConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # tokens are routed in fixed-size groups so the dispatch one-hot is
    # O(tokens * group_size), not O(tokens^2) — the GShard group axis
    group_size: int = 512
    # the layer pattern: n_layers counts n_dense_layers leading dense layers
    # (d_ff wide) and then the expert layers
    n_dense_layers: int = 0
    # the dropless share form (ExpertShareBlock) when > 0: of n_experts this
    # chip holds experts_held, the share numbered expert_offset (experts
    # [expert_offset * experts_held, (expert_offset + 1) * experts_held)).
    # Under an ``expert`` mesh axis the two numbers would be the axis's size
    # and index; the exchange across chips is not written yet
    experts_held: int = 0
    expert_offset: int = 0
    moe_d_ff: int = 0  # width of one routed or shared expert
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    # what the sum of the chosen scores gets before it divides them (the
    # published modelling codes differ: 1e-20 glm4_moe_lite, 1e-6 lfm2_moe)
    route_norm_eps: float = 1e-20
    # how the share form's router scores the experts: "sigmoid"
    # (``sigmoid_route``: DeepSeek-V3's, with the selection bias) or "softmax"
    # (``softmax_route``: ``qwen3_moe``'s, the chosen probabilities
    # renormalised over themselves and times ``routed_scaling``; no bias).
    # Either takes a shared expert beside it (``n_shared_experts``)
    router: str = "sigmoid"
    # the sigmoid router's group limit (DeepSeek-V3's ``n_group`` and
    # ``topk_group``): the experts in ``n_group`` groups of neighbours, of which
    # a token keeps the ``topk_group`` best (by the sum of a group's two largest
    # biased scores) and chooses its ``top_k`` inside them; 1: no limit. Where
    # a group is a node the limit bounds a token's exchange; on one chip it
    # only shapes the selection (the exchange is ROADMAP M1)
    n_group: int = 1
    topk_group: int = 1
    # what the share form's router reads: "mlp_norm", the layer's residual
    # after attention under ``mlp_norm`` (what the experts read), or
    # "layer_input", the residual as it enters the layer, before ``attn_norm``
    # and un-normed: route, counting sort and slot order are then computed
    # ahead of the attention operator (scope ``moe.preroute``) and the experts
    # take them from there, so that the router's cotangent reaches the layer's
    # input directly, a second path beside the residual's
    route_from: str = "mlp_norm"
    # the routed experts' gate activation (``EXPERT_ACTS``): "silu" (SwiGLU) or
    # "relu" (ReGLU; the layer then sows ``hidden_zeros``, the hidden
    # activations that are exactly zero: ``moe_hidden_zero_share``)
    expert_act: str = "silu"
    # a chunk of the share layer's buffer as a fraction of the expected load
    # ``T * top_k * experts_held / n_experts`` (``chunk_rows``); 0: an eighth
    # of the buffer
    chunk_of_load: float = 0.0
    # the router's selection bias (``noaux_tc``): enters top-k only, gets no
    # gradient, and is a constant here — N(0, select_bias_std) from
    # select_bias_seed, a row a layer (its update between steps is per-step
    # state outside the optimizer, which the program does not have)
    select_bias_std: float = 0.0
    select_bias_seed: int = 0
    # multi-token prediction (DeepSeek-V3 section 2.2), depth 0 or 1: one more
    # expert layer predicts the token two ahead through the shared embedding
    # and head; Trainer adds mtp_weight times its loss
    mtp_depth: int = 0
    mtp_weight: float = 0.3
    # the objective "generation by diffusion over blocks" (SDAR's training
    # step; ``block_noise``, ``ops/blockdiff.py``): every row goes through the
    # layers twice in one program, clean and noised. A token at position ``p``
    # of its document lies in block ``p // block``; each block draws a noise
    # level ``t = noise_eps + (1 - noise_eps) u`` and each of its tokens
    # becomes ``mask_token_id`` with probability ``t``, from a key that is a
    # pure function of ``noise_seed`` and the optimizer step (``noise_key``);
    # attention masks block-wise between the streams; the head reads the
    # noised stream and a masked position predicts its own token, weighted
    # ``1 / t`` (the model sows the weights, ``models/sown.py``). Training and
    # scoring only: a decode step that yields a block is not written.
    # ``[MASK]`` is a learned vector of its own (``mask_embedding`` [d_model],
    # drawn at 0.02), not a row of the table: where the vocabulary is sliced
    # over chips the published id lies in one chip's slice, and it is a token
    # the checkpoint being adapted never trained, so its vector starts at the
    # initializer's scale; ``mask_token_id`` is the id the noised row shows
    # at a masked position
    block_diffusion: bool = False
    block: int = 4
    mask_token_id: int = 0
    noise_seed: int = 0
    noise_eps: float = 1e-3

    @property
    def stream_block(self) -> int:
        return self.block if self.block_diffusion else 0

    def noise_key(self, step):
        """The noise key of optimizer step ``step`` (a traced count or an int):
        the noise is drawn in the jitted step, and a resumed job continues it."""
        return jax.random.fold_in(jax.random.key(self.noise_seed), step)

    def step_inputs(self, step) -> dict:
        """What ``apply`` takes in a train step beside the batch
        (``models.sown.step_inputs``): the step's noise key under this
        objective, nothing else."""
        return {"noise_key": self.noise_key(step)} if self.block_diffusion else {}

    def head_aheads(self) -> Tuple[int, ...]:
        """How far ahead of its position each head predicts: the main head 1
        and the ``mtp`` pass 2, or 0 where the objective is the model's own
        (``block_diffusion``: a position predicts its own token and the model
        weighs the targets)."""
        if self.block_diffusion:
            return (0,)
        return (1, 2) if self.mtp_depth else (1,)

    def __post_init__(self):
        super().__post_init__()
        if self.block_diffusion:
            if self.block < 1 or not 0 <= self.mask_token_id < self.vocab_size or not 0 <= self.noise_eps < 1:
                raise ValueError("block_diffusion needs block >= 1, a mask_token_id of the vocabulary and 0 <= noise_eps < 1")
            if self.decode:
                raise ValueError(
                    "block diffusion has a training form only: a decode step that yields a block "
                    "after several denoising passes is not written (ROADMAP M7)"
                )
            if self.sparse_topk or self.kv_lora_rank or self.attention_fn is not None or self.mtp_depth:
                raise ValueError(
                    "the two streams' block-wise mask is Attention's, through the automatic dispatch: "
                    "no selected keys, no latent attention, no attention_fn, no multi-token-prediction module"
                )
            if set(self.layer_types) - {"full_attention"}:
                raise ValueError("the two streams' block-wise mask takes full_attention layers: no window, no summaries, no conv")
        if self.experts_held:
            if self.n_experts % self.experts_held or not (
                0 <= self.expert_offset < self.n_experts // self.experts_held
            ):
                raise ValueError(
                    "experts_held must divide n_experts and expert_offset "
                    "number one of the shares"
                )
            if not self.moe_d_ff:
                raise ValueError("the share form needs moe_d_ff")
            if self.ablated:
                raise ValueError("the share form has no LOCO gates")
            if self.router not in ("sigmoid", "softmax"):
                raise ValueError("router is 'sigmoid' or 'softmax'")
            if self.router == "softmax" and self.select_bias_std:
                raise ValueError("the softmax router takes no selection bias")
            if self.n_group > 1 and (
                self.router != "sigmoid" or self.n_experts % self.n_group or not 1 <= self.topk_group <= self.n_group
                or self.n_experts // self.n_group < 2 or self.topk_group * (self.n_experts // self.n_group) < self.top_k
            ):
                raise ValueError(
                    "a group limit is the sigmoid router's: n_group divides n_experts into groups of at least two, "
                    "and the topk_group groups kept hold at least top_k experts"
                )
        if self.n_group < 1 or (self.n_group > 1 and not self.experts_held):
            raise ValueError("n_group >= 1, and a group limit is the share form's")
        if self.route_from not in ("mlp_norm", "layer_input") or self.expert_act not in EXPERT_ACTS:
            raise ValueError(f"route_from is 'mlp_norm' or 'layer_input', expert_act one of {sorted(EXPERT_ACTS)}")
        if self.route_from == "layer_input" and (not self.experts_held or self.decode):
            raise ValueError(
                "a router that reads the layer's input is the share form's (experts_held), training and "
                "scoring only: a decode step that routes ahead of attention is not written"
            )
        if self.expert_act != "silu" and (not self.experts_held or self.n_shared_experts):
            raise ValueError(
                "expert_act is the share form's routed experts': the capacity form and the shared "
                "expert (MLPBlock) are SwiGLU"
            )
        if self.chunk_of_load < 0 or (self.chunk_of_load and not self.experts_held):
            raise ValueError("chunk_of_load is a fraction of the share form's expected load")
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError("n_dense_layers must leave an expert layer")
        if self.mtp_depth not in (0, 1):
            raise ValueError("mtp_depth is 0 or 1")

    def select_bias(self) -> np.ndarray:
        """[expert layers (+ the MTP module's), n_experts] float32."""
        rows = self.n_layers - self.n_dense_layers + self.mtp_depth
        rng = np.random.default_rng(self.select_bias_seed)
        return (self.select_bias_std * rng.standard_normal((rows, self.n_experts))).astype(np.float32)

    @classmethod
    def mixtral_8x7b(cls, **overrides) -> "MoEConfig":
        """Mixtral-8x7B geometry (BASELINE config 5)."""
        return cls(
            **{
                **dict(
                    vocab_size=32_000,
                    d_model=4096,
                    n_layers=32,
                    n_heads=32,
                    n_kv_heads=8,
                    d_ff=14_336,
                    n_experts=8,
                    top_k=2,
                    max_seq_len=8192,
                    remat=True,
                ),
                **overrides,
            }
        )

    @classmethod
    def tiny_moe(cls, **overrides) -> "MoEConfig":
        return cls(
            **{
                **dict(
                    vocab_size=256,
                    d_model=64,
                    n_layers=2,
                    n_heads=4,
                    n_kv_heads=2,
                    d_ff=96,
                    n_experts=4,
                    top_k=2,
                ),
                **overrides,
            }
        )


class MoEBlock(nn.Module):
    """Top-k routed SwiGLU experts with static capacity."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, aux_gate=None):
        cfg = self.cfg
        b, s, d = x.shape
        t = b * s
        e = cfg.n_experts
        g = min(cfg.group_size, t)  # group axis keeps dispatch memory O(t * g)
        n_groups = (t + g - 1) // g
        pad = n_groups * g - t
        capacity = max(
            cfg.top_k,
            int(math.ceil(g / e * cfg.top_k * cfg.capacity_factor)),
        )

        tokens = x.reshape(t, d)
        if pad:
            tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
        grouped = tokens.reshape(n_groups, g, d)

        # device-side scopes (telemetry/metrics.py SCOPES): metadata only
        with jax.named_scope("moe.route"):
            router_logits = _dense(e, ("embed", None), cfg, "router")(grouped)
            router_probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)

            gate_vals, expert_idx = jax.lax.top_k(router_probs, cfg.top_k)  # [n,g,k]
            gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

            # GShard dispatch per group: position of each (token, k) in its expert
            # queue; top-1 assignments win capacity slots over top-2
            onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # [n,g,k,e]
            flat = onehot.transpose(0, 2, 1, 3).reshape(n_groups, cfg.top_k * g, e)
            pos_flat = jnp.cumsum(flat, axis=1) - flat
            pos = pos_flat.reshape(n_groups, cfg.top_k, g, e).transpose(0, 2, 1, 3)
            pos_in_expert = (pos * onehot).sum(-1)  # [n,g,k]
            within = pos_in_expert < capacity

        with jax.named_scope("moe.dispatch"):
            disp = (
                jax.nn.one_hot(expert_idx, e, dtype=x.dtype)[..., None]
                * jax.nn.one_hot(pos_in_expert, capacity, dtype=x.dtype)[..., None, :]
                * within[..., None, None].astype(x.dtype)
            )  # [n,g,k,e,c]
            combine = (disp * gate_vals[..., None, None].astype(x.dtype)).sum(2)
            dispatch = disp.sum(2)  # [n,g,e,c]

            expert_in = jnp.einsum("ngec,ngd->necd", dispatch, grouped)
            expert_in = expert_in.reshape(n_groups, e, capacity, d)
            # fold groups into the expert batch: experts see [e, n*c, d]
            expert_in = expert_in.transpose(1, 0, 2, 3).reshape(e, n_groups * capacity, d)

        w_gate = self.param(
            "w_gate",
            _partitioned(nn.initializers.normal(0.02), ("expert", "embed", "mlp"), cfg),
            (e, d, cfg.d_ff),
            cfg.param_dtype,
        )
        w_up = self.param(
            "w_up",
            _partitioned(nn.initializers.normal(0.02), ("expert", "embed", "mlp"), cfg),
            (e, d, cfg.d_ff),
            cfg.param_dtype,
        )
        w_down = self.param(
            "w_down",
            _partitioned(nn.initializers.normal(0.02), ("expert", "mlp", "embed"), cfg),
            (e, cfg.d_ff, d),
            cfg.param_dtype,
        )
        with jax.named_scope("moe.experts"):
            w_gate, w_up, w_down = (
                jnp.asarray(w_gate, cfg.dtype),
                jnp.asarray(w_up, cfg.dtype),
                jnp.asarray(w_down, cfg.dtype),
            )
            hidden = nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate)) * jnp.einsum(
                "ecd,edf->ecf", expert_in, w_up
            )
            expert_out = jnp.einsum("ecf,efd->ecd", hidden, w_down)
            expert_out = expert_out.reshape(e, n_groups, capacity, d).transpose(1, 0, 2, 3)

        with jax.named_scope("moe.combine"):
            y = jnp.einsum("ngec,necd->ngd", combine, expert_out).reshape(-1, d)
        if pad:
            y = y[:t]
        y = y.reshape(b, s, d)

        with jax.named_scope("moe.route"):
            # load-balancing auxiliary loss (Switch/Mixtral style); a LOCO gate
            # scales it too, so ablated blocks add no balancing gradients
            me = router_probs.reshape(-1, e).mean(0)  # [e] mean router prob
            ce = jax.nn.one_hot(expert_idx[..., 0], e).reshape(-1, e).mean(0)
            aux = (me * ce).sum() * e * cfg.router_aux_weight
            if aux_gate is not None:
                aux = aux * aux_gate.astype(aux.dtype)
        self.sow("intermediates", "router_aux_loss", aux)
        return y


def counting_sort(key, n_keys: int, every: int = 0):
    """A stable sort of ``key`` [n] (whole numbers below ``n_keys``, a small
    static count) without sorting: ``(inv, load)`` with ``load[v]`` the
    entries of value ``v`` and ``inv[i]`` the place of entry ``i`` in sorted
    order, its value's offset (the exclusive running sum of ``load``) plus
    its rank among the entries of that value. ``order`` with
    ``order[inv[i]] = i`` is ``jnp.argsort(key, stable=True)``. With
    ``every`` a third result, ``ends`` [ceil(n / every), n_keys]: the entries
    of each value up to the end of each run of ``every`` entries (the last
    run may be short), rows of the running sum that the places come from. The
    sort is stable, so the entries of one value in one such run stand
    together in sorted order, at ``start[v] + [ends[b - 1, v], ends[b, v])``."""
    onehot = (key[:, None] == jnp.arange(n_keys, dtype=key.dtype)).astype(jnp.int32)
    upto = jnp.cumsum(onehot, axis=0)  # [n, n_keys]: scalars a slot, never rows of width d
    load = upto[-1] if key.shape[0] else jnp.zeros(n_keys, jnp.int32)
    start = jnp.cumsum(load) - load
    inv = ((upto - 1 + start) * onehot).sum(-1).astype(jnp.int32)
    if not every:
        return inv, load
    last = np.minimum(np.arange(1, -(-key.shape[0] // every) + 1) * every, key.shape[0]) - 1
    return inv, load, upto[last]


def chunk_rows(slots: int, held: int, n_experts: int, of_load: float = 0.0) -> int:
    """Rows of one chunk of a share layer's buffer (``slots`` = ``T * top_k``
    rows, which no load exceeds): the routed part runs chunk by chunk over as
    many as the counted slots fill. A chip that holds every expert sees every
    slot: one chunk. Otherwise the load is about ``slots * held / n_experts``
    and wanders severalfold above that with the router, so an eighth of the
    buffer: on one v5e at the GLM cell's size a quarter lost 1.2% of the
    step to rows that hold no slot and a sixteenth won 0.4% (PERF.md section
    6, PR 27). ``of_load`` (``MoEConfig.chunk_of_load``) above 0 asks for
    that fraction of the expected load instead: where a chip holds an eighth
    of the experts an eighth of the buffer is the expected load itself, and
    a layer lands on one chunk or on two by a few hundred slots."""
    if held >= n_experts:
        return slots
    if of_load > 0:
        return math.ceil(of_load * slots * held / n_experts)
    return -(-slots // 8)


def _by_token(rows, inv, held, weights=None):
    """``sum over the held choices j of weights[:, j] * rows[inv[:, j]]``
    [T, d] in float32 from rows in slot order (``inv`` [T, k]: the place of
    token ``t``'s choice ``j``; ``held`` [T, k]: whether its expert is held,
    and then the place lies below the load): a token's choices gathered one
    at a time, so nothing of ``T * k`` rows is built, and selected, not
    multiplied by zero (a row past the load may hold anything)."""
    at = jnp.minimum(inv, rows.shape[0] - 1)
    total = 0.0
    for j in range(inv.shape[1]):
        row = rows[at[:, j]].astype(jnp.float32)
        if weights is not None:
            row = weights[:, j, None].astype(jnp.float32) * row
        total = total + jnp.where(held[:, j, None], row, 0)
    return total


# the token-side sum's kernel (``slots_to_tokens``): tokens a block, rows a tile (one DMA), and the
# rows of tiles a block's buffer holds before it is multiplied; the best of seven on one v5e at
# the expert cells' shapes taken together (``slots_to_tokens``; PERF.md section 6, PR 49)
TOKEN_TILES = (512, 16, 1024)


# the columns of the placement the kernel builds and multiplies at a time: tiles of the buffer side by side
PLACE_COLS = 256


def token_tiles(ends, load, tile: int = TOKEN_TILES[1]):
    """Which tiles of ``tile`` rows of the buffer hold the slots of each block
    of tokens, from ``counting_sort``'s ``ends`` [blocks, held] and ``load``
    [held]: int32 ``[2, blocks, held]``, for block ``b`` and held expert
    ``e`` the first tile and the number of tiles that cover the rows
    ``start[e] + [ends[b - 1, e], ends[b, e])`` (the block's slots on that
    expert, which stand together and in token order). A tile that two
    experts' runs of one block share is the first one's alone: the tiles of a
    block ascend and each is read once."""
    start = jnp.cumsum(load) - load
    hi = start + ends
    lo = start + jnp.concatenate([jnp.zeros_like(ends[:1]), ends[:-1]])
    last = jnp.where(hi > lo, (hi - 1) // tile, -1)
    read = jax.lax.cummax(last, axis=1)  # the last tile the experts up to this one read
    first = jnp.maximum(lo // tile, jnp.concatenate([jnp.full_like(read[:, :1], -1), read[:, :-1]], axis=1) + 1)
    return jnp.stack([first, jnp.maximum(last - first + 1, 0)]).astype(jnp.int32)


def _token_sum_kernel(first_ref, count_ref, total_ref, inv_ref, w_ref, rows_ref, out_ref, buf, sem, at, state, acc, *,
                      held: int, tile: int, weighted: bool, precision):
    """One block of tokens a grid step. ``fill`` walks the block's tiles
    (``first_ref``/``count_ref``, [blocks * held] in SMEM) from a cursor
    (expert, tile of its run) and starts one DMA a tile, HBM to the next free
    tile of the buffer ``buf[half]``, until the buffer is full or the block
    done; ``flush`` waits for them and adds ``place @ buffer`` to the block's
    float32 sum, ``place[c, t]`` the weight of the token's choice whose slot
    is the buffer's row ``c``, contracted over ``c``. The next block's first fill is started before
    this block's product, into the other half. Rows from ``total`` on hold
    anything and are selected to zero; the tiles of a block ascend, so those
    are the buffer's rows from ``live`` on."""
    b, blocks = pl.program_id(0), pl.num_programs(0)
    slots = buf.shape[1] // tile
    total = total_ref[0]

    def copy(half, row0, slot):
        return pltpu.make_async_copy(
            rows_ref.at[pl.ds(pl.multiple_of(row0, tile), tile)],
            buf.at[half, pl.ds(pl.multiple_of(slot * tile, tile), tile)], sem.at[half],
        )

    def fill(block, half, e, i):
        """-> the cursor it stopped at, the tiles started and the rows among them that hold a slot"""
        def step(c):
            e, i, s, live = c
            more = i < count_ref[block * held + e]
            row0 = (first_ref[block * held + e] + i) * tile

            @pl.when(more)
            def _():
                copy(half, row0, s).start()
                at[half, s] = row0

            return (
                jnp.where(more, e, e + 1), jnp.where(more, i + 1, 0), jnp.where(more, s + 1, s),
                jnp.where(more, live + jnp.clip(total - row0, 0, tile), live),
            )

        return jax.lax.while_loop(lambda c: (c[0] < held) & (c[2] < slots), step, (e, i, jnp.int32(0), jnp.int32(0)))

    def flush(half, s, live):
        """Adds the product with the ``s`` tiles in the buffer's half, ``cols`` columns at a time."""
        jax.lax.fori_loop(0, s, lambda j, c: (copy(half, 0, j).wait(), c)[1], 0)
        cols = min(PLACE_COLS, buf.shape[1])
        inv = inv_ref[...]

        def part(c, carry):
            base = pl.multiple_of(c * cols, cols)
            col = jax.lax.broadcasted_iota(jnp.int32, (cols, 1), 0)
            row = jnp.full((cols, 1), -2, jnp.int32)  # the buffer's row each row of this part holds; -2: none
            for j in range(cols // tile):
                row = jnp.where(col // tile == j, at[half, c * (cols // tile) + j] + col % tile, row)
            row = jnp.where(base + col < live, row, -2)
            # the placement with the tokens along the lanes, as ``inv`` and the weights come ([k, B]): a
            # choice's places are one row, and the only broadcast along lanes is ``row``'s, once a part
            place = jnp.zeros((cols, inv.shape[1]), jnp.float32)
            for j in range(inv.shape[0]):
                place = jnp.where(inv[j:j + 1, :] == row, w_ref[j:j + 1, :] if weighted else 1.0, place)
            data = jnp.where(base + col < live, buf[half, pl.ds(base, cols)], jnp.zeros((), buf.dtype))
            acc[...] += jax.lax.dot_general(
                place.astype(buf.dtype), data, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
                precision=precision,
            )
            return carry

        jax.lax.fori_loop(0, (s * tile + cols - 1) // cols, part, 0)

    def started(half, cursor):
        for j, v in enumerate(cursor):
            state[half, j] = v

    @pl.when(b == 0)
    def _():
        started(0, fill(0, 0, jnp.int32(0), jnp.int32(0)))

    @pl.when(b + 1 < blocks)
    def _():
        started((b + 1) % 2, fill(b + 1, (b + 1) % 2, jnp.int32(0), jnp.int32(0)))

    half = b % 2
    acc[...] = jnp.zeros_like(acc)
    flush(half, state[half, 2], state[half, 3])

    def rest(cursor):  # a block whose tiles outnumber the buffer's: fill and multiply again, nothing in flight meanwhile
        e, i, s, live = fill(b, half, *cursor)
        flush(half, s, live)
        return e, i

    jax.lax.while_loop(lambda c: c[0] < held, rest, (state[half, 0], state[half, 1]))
    out_ref[...] = acc[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def slots_to_tokens(rows, runs, total, inv, held, weights=None, *, tiles=TOKEN_TILES, interpret=False):
    """``_by_token`` as one Pallas kernel that reads each tile of ``rows``
    [N, d] that holds a slot of a block of tokens once (``runs``:
    ``token_tiles`` at ``tiles``' block and tile; ``total``: the slots on held
    experts, ``load.sum()``), where ``_by_token`` gathers ``T`` rows for each
    of the ``top_k`` choices and throws away those whose expert is not held.
    The placement of a tile's rows onto the block's tokens is a one-hot
    product (at most one choice of a token is a given row, so a weight is not
    rounded; float32 sum, cast once: ``_by_token``'s arithmetic; float32 rows
    are multiplied at ``HIGHEST``). ``tiles``: ``TOKEN_TILES``, one triple for
    every shape: on one v5e (PERF.md section 6, PR 49: the kernel alone against
    ``_by_token`` alone, both forms, the six expert cells' shapes and loads)
    tiles of 16 rows beat 32 and 64 everywhere (the tiles start on multiples
    of their size, so a larger one reads more rows that hold no slot), and
    blocks of 256 and of 512 tokens lie within a quarter of each other, 512
    ahead at the low loads and 256 at the high ones: at 32,768 tokens x 8 of
    2,048 with 16 of 128 experts held 0.99 ms (512) and 0.93 (256) against the
    gathers' 10.5, at 8,192 x 10 of 3,072 with 8 of 256 held 0.27 against 4.6;
    512 reads 0.19 of ``T * top_k`` rows there where 256 reads 0.25. -> [T, d]
    in ``rows``' type."""
    block, tile, width = tiles
    (t, k), (n, d) = inv.shape, rows.shape
    blocks, n_held = runs.shape[1:]
    if blocks != -(-t // block) or n % tile or width % tile or width % min(PLACE_COLS, width) or PLACE_COLS % tile:
        raise ValueError(f"runs {runs.shape} for {t} tokens in blocks of {block}, {n} rows in tiles of {tile}")
    # [k, T] for the kernel, tokens along the lanes; the weights as float32 (exact), whose tile takes any ``k``
    inv = jnp.pad(jnp.where(held, inv, -1), ((0, blocks * block - t), (0, 0)), constant_values=-1).T
    weighted = weights is not None
    weights = (
        jnp.pad(weights.astype(jnp.float32), ((0, blocks * block - t), (0, 0))).T if weighted
        else jnp.zeros((k, block), jnp.float32)
    )
    by_block = pl.BlockSpec((k, block), lambda b, *_: (0, b))
    out = pl.pallas_call(
        functools.partial(
            _token_sum_kernel, held=n_held, tile=tile, weighted=weighted,
            precision=jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(blocks,),
            in_specs=[
                by_block,
                by_block if weighted else pl.BlockSpec((k, block), lambda b, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((block, d), lambda b, *_: (b, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, width, d), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2, width // tile), jnp.int32),  # the first row of each tile in the buffer
                pltpu.SMEM((2, 4), jnp.int32),  # where each half's first fill stopped: ``fill``'s result
                pltpu.VMEM((block, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((blocks * block, d), rows.dtype),
        # the halves are filled a block ahead: the blocks run in order
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=96 * 2**20),
        name="slots_to_tokens",
        interpret=interpret,
    )(runs[0].reshape(-1), runs[1].reshape(-1), total.reshape(1).astype(jnp.int32), inv, weights, rows)
    return out[:t]


def token_sum(rows, runs, total, inv, held, weights=None):
    """``_by_token`` [T, d] in ``rows``' type. On a TPU, where the rows fill
    the kernel's tiles and lanes, ``slots_to_tokens``, whose reads follow the
    load; elsewhere the gathers."""
    if (
        jax.default_backend() == "tpu"
        and rows.shape[0] % TOKEN_TILES[1] == 0
        and rows.shape[1] % 128 == 0
        and rows.dtype in (jnp.bfloat16, jnp.float32)
    ):
        return slots_to_tokens(rows, runs, total, inv, held, weights)
    return _by_token(rows, inv, held, weights).astype(rows.dtype)


# tiles (rows, reduction, columns) of the grouped product's kernel: the best of
# seven on one v5e at the GLM cell's shapes (PERF.md section 6, PR 27)
GROUPED_TILES = (512, 2048, 512)


def grouped_kernel(lhs, rhs, group_sizes, interpret: bool = False):
    """``grouped_dot`` as the Pallas kernels of ``megablox`` (``gmm`` forward
    and for the rows' gradient, ``tgmm`` for the weights'; float32
    accumulation, results in the operands' type), which visit the row tiles
    the group sizes cover. Rows have to fill whole tiles."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    return megablox.gmm(
        lhs, rhs, group_sizes, preferred_element_type=lhs.dtype, tiling=GROUPED_TILES,
        interpret=interpret,
    )


def grouped_dot(lhs, rhs, group_sizes):
    """``lhs`` [C, k] by runs of rows, run ``e`` (``group_sizes[e]`` rows,
    in order from row 0) times ``rhs[e]`` [k, n]; rows past the runs hold
    anything. On a TPU, where the rows fill the kernel's tiles, the Pallas
    kernel: ``jax.lax.ragged_dot`` becomes a kernel of the compiler's own
    there, as fast to within a tenth, but one that drops the program's scope
    names, so that a trace cannot put its time down to ``moe.experts``
    (PERF.md section 6, PR 27). Elsewhere ``jax.lax.ragged_dot``."""
    if (
        jax.default_backend() == "tpu"
        and lhs.shape[0] % GROUPED_TILES[0] == 0
        and lhs.dtype == rhs.dtype == jnp.bfloat16
    ):
        return grouped_kernel(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _chunk_sizes(load, first_row, rows: int):
    """Of the runs of rows that ``load`` gives (run ``e``: ``load[e]`` rows, in
    order from row 0), what lies in ``[first_row, first_row + rows)``."""
    ends = jnp.cumsum(load)
    return jnp.clip(jnp.minimum(ends, first_row + rows) - jnp.maximum(ends - load, first_row), 0, rows)


@functools.partial(jax.jit, static_argnames="act")
def _chunk_experts(a, sizes, w_gate, w_up, w_down, act="silu"):
    """The three grouped products over one chunk's rows ``a`` [rows, d] with
    the gate's activation ``act`` (``EXPERT_ACTS``): ``(the chunk's result,
    zeros)``, where ``zeros`` under "relu" counts the hidden activations
    ``relu(a W_gate)`` that are exactly zero in the rows that hold a slot
    (the first ``sizes.sum()``), in the pass that multiplies the two halves,
    and is None otherwise ("silu" is the program it was).
    Under ``jax.jit`` for its cache alone: every expert layer of a model and
    every pass calls it at the same shapes, and tracing the kernels anew each
    time took longer than loading the compiled step."""
    with jax.named_scope("moe.experts"):
        gate = EXPERT_ACTS[act](grouped_dot(a, w_gate, sizes))
        y = grouped_dot(gate * grouped_dot(a, w_up, sizes), w_down, sizes)
        if act != "relu":
            return y, None
        live = jnp.arange(a.shape[0], dtype=jnp.int32) < sizes.sum()
        return y, jnp.sum((gate == 0) & live[:, None], dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames="act")
def _chunk_experts_back(a, sizes, w_gate, w_up, w_down, g_rows, scale, act="silu"):
    """One chunk again and backward, from the rows ``g_rows`` of the result's
    cotangent that belong to its slots and the slots' weights ``scale`` (zero
    where the slot's expert is not held): the cotangent of ``a``, each slot's
    dot product of its result with ``g_rows`` (the weights' gradient), and
    the three expert weights' gradients."""
    y, vjp = jax.vjp(
        lambda a, w_gate, w_up, w_down: _chunk_experts(a, sizes, w_gate, w_up, w_down, act)[0],
        a, w_gate, w_up, w_down,
    )
    with jax.named_scope("moe.combine"):
        dot = (y.astype(jnp.float32) * g_rows.astype(jnp.float32)).sum(-1)
        d_y = scale[:, None] * g_rows
    return (*vjp(d_y), dot)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(rows: int, act: str, tokens, weights, order, inv, held, load, runs, w_gate, w_up, w_down):
    """The routed part of the share layer: ``out[t] = sum over the held
    choices j of weights[t, j] * expert(tokens[t])``, the buffer (slot order:
    ``order`` [chunks * rows] names the slot of each row, ``inv`` [T, k] the
    row of each slot) worked through in chunks of ``rows`` rows, as many as
    the counted slots ``load.sum()`` fill and no more: a loop whose length is
    read in the step, so one traced and compiled body serves every load. A
    chunk gathers its rows of ``tokens``, runs the three grouped products and
    writes its part of the buffer; then every token takes the sum of its held
    choices' rows, in float32 (``token_sum``; ``runs``: ``token_tiles``, where
    each block of tokens has its slots in the buffer). Returns ``(out,
    zeros)``: ``zeros`` is the chunks' count of ``_chunk_experts`` summed
    (None unless ``act`` is "relu"), taken in these forward chunks and not in
    the backward pass's. Its own VJP (a loop of unknown length has no
    transpose): the forward pass keeps the arguments only; the backward pass
    is a second loop whose chunk runs forward again and then backward, the
    expert weights' gradients summed over the chunks in float32, and both
    directions sum by token where a scatter-add would stand: the forward the
    weighted results, the backward the cotangents of the chunks' inputs,
    unweighted. On a TPU that sum reads the tiles of the buffer that hold a
    slot, once (``slots_to_tokens``); elsewhere it is ``top_k`` gathers of
    ``T`` rows each (``_by_token``)."""
    k = inv.shape[1]

    def chunk(i, carry):
        y, zeros = carry
        first = i * rows
        with jax.named_scope("moe.dispatch"):
            a = tokens[jax.lax.dynamic_slice(order, (first,), (rows,)) // k]
        y_c, zeros_c = _chunk_experts(a, _chunk_sizes(load, first, rows), w_gate, w_up, w_down, act)
        return jax.lax.dynamic_update_slice(y, y_c, (first, 0)), None if zeros is None else zeros + zeros_c

    y, zeros = jax.lax.fori_loop(
        0, (load.sum() + rows - 1) // rows, chunk,
        (
            jnp.zeros((order.shape[0], tokens.shape[1]), tokens.dtype),
            jnp.zeros((), jnp.int32) if act == "relu" else None,
        ),
    )
    with jax.named_scope("moe.combine"):
        return token_sum(y, runs, load.sum(), inv, held, weights), zeros


def _routed_fwd(rows, act, *args):
    return _routed(rows, act, *args), args


def _routed_bwd(rows, act, res, g):
    tokens, weights, order, inv, held, load, runs, w_gate, w_up, w_down = res
    g, _ = g  # the count has no cotangent
    k = inv.shape[1]
    scale = jnp.where(held, weights, 0).reshape(-1)

    def chunk(i, carry):
        d_a, dot, *d_experts = carry
        first = i * rows
        slots = jax.lax.dynamic_slice(order, (first,), (rows,))
        with jax.named_scope("moe.dispatch"):
            a = tokens[slots // k]
        with jax.named_scope("moe.combine"):
            g_rows, scale_rows = g[slots // k], scale[slots]
        d_a_c, *d_experts_c, dot_c = _chunk_experts_back(
            a, _chunk_sizes(load, first, rows), w_gate, w_up, w_down, g_rows, scale_rows, act
        )
        return (
            jax.lax.dynamic_update_slice(d_a, d_a_c, (first, 0)),
            jax.lax.dynamic_update_slice(dot, dot_c, (first,)),
            *(total + part.astype(jnp.float32) for total, part in zip(d_experts, d_experts_c)),
        )

    d_a, dot, *d_experts = jax.lax.fori_loop(
        0, (load.sum() + rows - 1) // rows, chunk,
        (
            jnp.zeros((order.shape[0], tokens.shape[1]), tokens.dtype),
            jnp.zeros(order.shape[0], jnp.float32),
            *(jnp.zeros(w.shape, jnp.float32) for w in (w_gate, w_up, w_down)),
        ),
    )
    with jax.named_scope("moe.dispatch"):
        d_tokens = token_sum(d_a, runs, load.sum(), inv, held)
    with jax.named_scope("moe.combine"):
        d_weights = jnp.where(held, dot[jnp.minimum(inv, dot.shape[0] - 1)], 0).astype(weights.dtype)
    d_experts = (d.astype(w.dtype) for d, w in zip(d_experts, (w_gate, w_up, w_down)))
    return (d_tokens, d_weights, None, None, None, None, None, *d_experts)


_routed.defvjp(_routed_fwd, _routed_bwd)


def sigmoid_route(
    logits, select_bias, top_k: int, scaling: float, norm_eps: float = 1e-20, n_group: int = 1, topk_group: int = 1,
):
    """DeepSeek-V3's router (``noaux_tc``) from float32 logits [..., n_experts]:
    ``(sel [..., k] expert numbers, weights [..., k])`` with ``s =
    sigmoid(logits)``, ``sel = top_k(s + select_bias)`` (the bias enters the
    selection only and gets no gradient) and the chosen scores normalised over
    themselves (their sum plus ``norm_eps``) and scaled. With ``n_group`` > 1
    the selection is group-limited: the experts stand in ``n_group`` groups of
    neighbours, a group's score is the sum of its two largest biased scores,
    the ``topk_group`` best groups stay and the ``top_k`` are chosen inside
    them. ``n_group`` 1 is the selection over all the experts, as it was."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    biased = scores if select_bias is None else scores + select_bias
    biased = jax.lax.stop_gradient(biased)
    if n_group > 1:
        grouped = biased.reshape(*biased.shape[:-1], n_group, -1)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)  # [..., n_group]
        _, kept = jax.lax.top_k(group_score, topk_group)
        stays = (kept[..., None] == jnp.arange(n_group)).any(-2)  # [..., n_group]
        biased = jnp.where(stays[..., None], grouped, -jnp.inf).reshape(biased.shape)
    _, sel = jax.lax.top_k(biased, top_k)
    chosen = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, scaling * chosen / (chosen.sum(-1, keepdims=True) + norm_eps)


def softmax_route(logits, top_k: int, scaling: float = 1.0):
    """``qwen3_moe``'s router (``norm_topk_prob``) from float32 logits
    [..., n_experts]: ``(sel [..., k] expert numbers, weights [..., k])`` with
    ``p = softmax(logits)`` over all the experts, ``sel = top_k(p)`` and the
    chosen probabilities divided by their sum, times ``scaling`` where the
    model scales its routed sum (``moe_routed_scaling_factor``)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(probs), top_k)
    chosen = jnp.take_along_axis(probs, sel, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True)
    # no multiply by 1.0: an unscaled route's program stays the parent's (``transformer.rope``'s note)
    return sel, weights if scaling == 1.0 else scaling * weights


class Route(NamedTuple):
    """What ``ExpertShareBlock.route`` hands its experts: each token's
    ``weights`` [T, k] in float32, and the slots' order by held expert
    (``_routed``'s ``order``, ``inv``, ``held``, ``load``, ``runs``) with
    the rows of the buffer that the chunks which run will visit."""

    weights: jax.Array
    order: jax.Array
    inv: jax.Array
    held: jax.Array
    load: jax.Array
    runs: jax.Array
    visited: jax.Array


class ExpertShareBlock(nn.Module):
    """One chip's share of a sigmoid-routed expert layer, dropless
    (``router="softmax"``: of a softmax-routed one, ``softmax_route``, with
    ``routed_scaling`` on the renormalised probabilities and the shared
    expert beside them where the model has one).

    The router scores all ``n_experts`` in float32: ``s = sigmoid(x W_r)``,
    ``sel = top_k(s + b)`` (``b`` the selection bias: no gradient),
    ``w_e = routed_scaling * s_e / (sum of the chosen s + route_norm_eps)``. The result
    is ``shared(x)`` (where there is a shared expert) ``+ sum over chosen experts held here of w_e * expert_e(x)``:
    what the absent experts would add is another chip's part. The (token,
    choice) slots are put in order of held expert by a counting sort (those
    on absent experts last) and counted (:meth:`route`), and the routed part (``_routed``:
    gather into slot order, three grouped products, the weighted sum back by
    token) works through the buffer chunk by chunk (``chunk_rows``), as many
    chunks as the counted slots fill, in a loop whose length is read in the
    step: the rows of width ``d_model`` that move follow the load, not
    ``T * top_k``, on the way back too: the sort is stable, so a block of
    tokens has its slots on one expert in one run of rows, and the sum by
    token reads those runs' tiles (``token_tiles``, ``slots_to_tokens``) and
    not a row a choice. The buffer has a row for every slot, so none on a held
    expert is ever cut: ``slots_dropped`` counts what the chunks that ran
    left out, and reads 0. The router reads what the experts read, or, given
    ``route`` from outside, whatever :meth:`route` was called on
    (``MoELayer`` under ``route_from="layer_input"``: the layer's input,
    before attention). Sows ``expert_load`` ([experts_held] slots an
    expert), ``slots_dropped``, ``rows_visited`` ([2]: the rows of the
    chunks that ran, of ``T * top_k``) and ``combine_rows`` ([2]: the rows in
    the tiles one sum by token reads, of the ``T * top_k`` that a gather a
    choice fetches) for the trainer's step metrics, and
    under ``expert_act="relu"`` ``hidden_zeros`` ([2]: the hidden activations
    ``relu(x W_gate)`` of the slots on held experts that are exactly zero, of
    all of them: the sparsity a down product could skip)."""

    cfg: MoEConfig

    def setup(self):
        cfg = self.cfg
        d, held, f = cfg.d_model, cfg.experts_held, cfg.moe_d_ff
        self.router = nn.DenseGeneral(
            features=cfg.n_experts, use_bias=False, dtype=jnp.float32,
            param_dtype=cfg.param_dtype, precision=jax.lax.Precision.HIGHEST,
            kernel_init=_partitioned(nn.initializers.normal(0.02), ("embed", None), cfg),
        )

        def experts(name, axes, shape):
            return self.param(
                name, _partitioned(nn.initializers.normal(0.02), axes, cfg), shape, cfg.param_dtype,
            )

        self.w_gate = experts("w_gate", ("expert", "embed", "mlp"), (held, d, f))
        self.w_up = experts("w_up", ("expert", "embed", "mlp"), (held, d, f))
        self.w_down = experts("w_down", ("expert", "mlp", "embed"), (held, f, d))
        if cfg.n_shared_experts:
            self.shared = MLPBlock(dataclasses.replace(cfg, d_ff=f * cfg.n_shared_experts))

    def _rows(self, t: int) -> int:
        cfg = self.cfg
        return chunk_rows(t * cfg.top_k, cfg.experts_held, cfg.n_experts, cfg.chunk_of_load)

    def route(self, tokens, select_bias=None) -> Route:
        """Router, selection, weights and the slots' counting sort from
        ``tokens`` [T, d_model] (scopes ``moe.route`` and ``moe.dispatch``)."""
        cfg = self.cfg
        t, k, held = tokens.shape[0], cfg.top_k, cfg.experts_held
        with jax.named_scope("moe.route"):
            logits = self.router(tokens.astype(jnp.float32))
            if cfg.router == "softmax":
                sel, weights = softmax_route(logits, k, cfg.routed_scaling)
            else:
                sel, weights = sigmoid_route(
                    logits, select_bias, k, cfg.routed_scaling, cfg.route_norm_eps, cfg.n_group, cfg.topk_group
                )  # [t, k]

        with jax.named_scope("moe.dispatch"):
            local = sel - cfg.expert_offset * held
            is_held = (local >= 0) & (local < held)  # [t, k]
            key = jnp.where(is_held, local, held).reshape(t * k)
            inv, load, ends = counting_sort(key, held + 1, every=TOKEN_TILES[0] * k)
            load = load[:held]
            runs = token_tiles(ends[:, :held], load)
            # a row of the buffer for every slot, in chunks: the last chunk may overhang
            rows = self._rows(t)
            chunks = -(-t * k // rows)
            order = jnp.zeros(chunks * rows, jnp.int32).at[inv].set(
                jnp.arange(t * k, dtype=jnp.int32), unique_indices=True
            )
            visited = jnp.minimum((load.sum() + rows - 1) // rows * rows, t * k)
        return Route(weights, order, inv.reshape(t, k), is_held, load, runs, visited)

    def __call__(self, x, select_bias=None, route: Optional[Route] = None):
        cfg = self.cfg
        b, s, d = x.shape
        t, k = b * s, cfg.top_k
        tokens = x.reshape(t, d)
        if route is None:
            route = self.route(tokens, select_bias)
        load, visited = route.load, route.visited

        w_gate, w_up, w_down = (jnp.asarray(w, cfg.dtype) for w in (self.w_gate, self.w_up, self.w_down))
        y, zeros = _routed(
            self._rows(t), cfg.expert_act, tokens, route.weights.astype(tokens.dtype), route.order, route.inv,
            route.held, load, route.runs, w_gate, w_up, w_down,
        )

        if cfg.n_shared_experts:
            with jax.named_scope("moe.shared"):
                y = y + self.shared(tokens)
        self.sow("intermediates", "expert_load", load)
        self.sow("intermediates", "slots_dropped", jnp.maximum(load.sum() - visited, 0))
        self.sow("intermediates", "rows_visited", jnp.stack([visited, jnp.int32(t * k)]))
        self.sow("intermediates", "combine_rows", jnp.stack([route.runs[1].sum() * TOKEN_TILES[1], jnp.int32(t * k)]))
        if zeros is not None:
            self.sow("intermediates", "hidden_zeros", jnp.stack([zeros, load.sum() * cfg.moe_d_ff]))
        return y.reshape(b, s, d)


class MoELayer(nn.Module):
    cfg: MoEConfig
    kind: str = "full_attention"
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, gates=None, select_bias=None):
        """``gates`` — optional [2] float (attn, moe) LOCO ablation gates,
        same semantics as DecoderLayer (zero gate = identity residual,
        zero grads, unchanged param tree). The gate also scales the sown
        router aux loss — an ablated expert block must not keep pushing
        balancing gradients into its router. ``select_bias`` — the share
        form's [n_experts] selection bias of this layer. Under
        ``route_from="layer_input"`` the share form's route is computed here,
        from ``x`` as it arrives and ahead of the operator (scope
        ``moe.preroute`` around the router's own scopes)."""
        cfg = self.cfg
        share = ExpertShareBlock(cfg, name="moe") if cfg.experts_held else None
        route = None
        if cfg.route_from == "layer_input":
            with jax.named_scope("moe.preroute"):
                route = share.route(x.reshape(-1, x.shape[-1]), select_bias)
        a = layer_operator(cfg, self.kind, x, positions, segment_ids, self.stacked)
        x = x + (a if gates is None else a * gates[0].astype(a.dtype))
        xn = RMSNorm(cfg, name="mlp_norm")(x)
        if share is not None:
            return x + share(xn, select_bias, route)
        m = MoEBlock(cfg, name="moe")(
            xn, aux_gate=None if gates is None else gates[1]
        )
        x = x + (m if gates is None else m * gates[1].astype(m.dtype))
        return x


def _remat(cls, cfg, prevent_cse=None):
    """``cls`` recomputed in the backward pass where the configuration asks
    for it (no gradients, hence no remat, in decode). ``prevent_cse`` None:
    ``not cfg.scan_layers``, right for the body of a scan over many layers,
    where no replay can be merged with a forward in another loop. A layer
    outside the scan, or in a scan of one period, which XLA unrolls, takes
    True: merged with its forward a replay keeps every intermediate, and at
    the hybrid cell's size XLA then plans 14.54 GiB and computes nine large
    products again to stay under its limit, against 8.93 GiB with the replays
    kept apart (compiled for a v5e, PERF.md section 6, PR 30). The stacks of
    one kind of layer keep PR 26's merged replays of ``dense_<i>`` and ``mtp``
    until a ``perf_opt`` issue measures the other way (PERF.md section 7)."""
    if cfg.remat and not cfg.decode:
        return nn.remat(
            cls, prevent_cse=(not cfg.scan_layers) if prevent_cse is None else prevent_cse,
            policy=REMAT_POLICIES[cfg.remat_policy],
        )
    return cls


class _ScannedMoELayer(nn.Module):
    """Scan body. ``per_layer`` holds what rides the scan's in_axes=0: the
    LOCO ``gates`` and the share form's ``select_bias``, each where there is
    one."""

    cfg: MoEConfig
    kind: str = "full_attention"
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, per_layer, segment_ids=None):
        return MoELayer(self.cfg, self.kind, self.stacked, name="layer")(
            x, positions, segment_ids, **per_layer
        ), None


class _ScannedPeriod(nn.Module):
    """Scan body of a stack whose layers' operators differ: one period of the
    pattern, ``layer_<j>`` of ``kinds[j]``, each recomputed on its own (the
    flash kernel's kept results matter in the attention layers only). The
    layers of a period are modules of their own, so they may differ in their
    leaves' shapes too (a sliding layer's query heads against a full one's).
    ``per_layer`` arrives with a leading axis over the period's layers."""

    cfg: MoEConfig
    kinds: tuple
    stacked: bool = False

    @nn.compact
    def __call__(self, x, positions, per_layer, segment_ids=None):
        for j, kind in enumerate(self.kinds):
            x, _ = _remat(_ScannedMoELayer, self.cfg, True)(self.cfg, kind, self.stacked, name=f"layer_{j}")(
                x, positions, {k: v[j] for k, v in per_layer.items()}, segment_ids
            )
        return x, None


def layer_plan(kinds, n_dense: int):
    """``(period, n_periods, tail)`` of the layers after the ``n_dense``
    leading ones: the shortest run of kinds whose repetition gives them, how
    many whole runs there are, and the layers over (a prefix of the run)."""
    rest = tuple(kinds[n_dense:])
    for p in range(1, len(rest) + 1):
        if all(rest[i] == rest[i % p] for i in range(len(rest))):
            return rest[:p], len(rest) // p, rest[len(rest) // p * p:]
    return (), 0, ()


class MTPModule(nn.Module):
    """Multi-token prediction, depth 1 (DeepSeek-V3 section 2.2): position
    ``i``'s last hidden state (before the final norm) and the embedding of
    token ``i+1``, each normed, concatenated and projected back to the model's
    width, go through one more expert layer and a final norm of its own. The
    caller applies the shared head: the logits predict token ``i+2``."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, h, next_embed, positions, segment_ids, per_layer):
        cfg = self.cfg
        x = jnp.concatenate(
            [RMSNorm(cfg, name="enorm")(next_embed), RMSNorm(cfg, name="hnorm")(h)], axis=-1
        )
        x = _dense(cfg.d_model, (None, "embed"), cfg, "eh_proj")(x)
        x, _ = _remat(_ScannedMoELayer, cfg)(cfg, name="block")(x, positions, per_layer, segment_ids)
        return RMSNorm(cfg, name="final_norm")(x)


def block_noise(cfg: MoEConfig, tokens, positions, segment_ids, key):
    """One step's noise (``MoEConfig.block_diffusion``): ``(noised tokens
    [B, L], target weights [B, L] float32, [masked, real] float32)``. Each
    block of ``cfg.block`` positions of a document draws one level ``t =
    noise_eps + (1 - noise_eps) u``, ``u ~ U[0, 1)`` (the draw at the row index
    of its first token, of ``uniform(fold_in(key, 0), [B, L])``); a real token
    is masked where ``uniform(fold_in(key, 1), [B, L]) < t`` and then reads
    ``mask_token_id`` (``MoEDecoder`` embeds it as the vector
    ``mask_embedding``); a masked token's weight in the objective is ``1 / t``
    (the masked-diffusion bound under a linear schedule), every other's 0.
    Float32 throughout."""
    b, l = tokens.shape
    real = jnp.ones((b, l), bool) if segment_ids is None else segment_ids > 0
    first = jnp.arange(l, dtype=jnp.int32)[None, :] - positions.astype(jnp.int32) % cfg.block
    u = jax.random.uniform(jax.random.fold_in(key, 0), (b, l), jnp.float32)
    t = cfg.noise_eps + (1.0 - cfg.noise_eps) * jnp.take_along_axis(u, first, axis=1)
    masked = (jax.random.uniform(jax.random.fold_in(key, 1), (b, l), jnp.float32) < t) & real
    noised = jnp.where(masked, jnp.asarray(cfg.mask_token_id, tokens.dtype), tokens)
    counts = jnp.stack([masked.sum(dtype=jnp.float32), real.sum(dtype=jnp.float32)])
    return noised, jnp.where(masked, 1.0 / t, 0.0), counts


class MoEDecoder(nn.Module):
    """Sparse-MoE causal LM; same interface as
    :class:`maggy_tpu.models.transformer.Decoder`. With ``mtp_depth`` it also
    sows ``mtp_logits`` (float32, predicting the token two ahead) for the
    trainer's loss. ``layer_types`` gives every layer its operator: the
    leading dense layers and the layers over after the last whole period are
    unrolled (``dense_<i>``, ``tail_<i>``), the whole periods scanned
    (``layers``: one kind of layer as ``layer``, several as ``layer_<j>``).
    Under ``block_diffusion`` the objective is the model's own: it draws the
    step's noise (``block_noise``), runs the clean and the noised row through
    the layers as one row of ``2L`` positions, returns the noised stream's
    logits and sows ``target_weights`` (what the train step's loss weighs each
    position's own token by) and ``diffusion_masked``."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None, noise_key=None, targets=None):
        """``noise_key``: the step's noise key under ``block_diffusion``
        (``MoEConfig.step_inputs``; None: step 0's). The layers then run on
        rows of ``2L`` positions, the clean stream and the noised one, and
        the logits ``[B, L, vocab]`` are the noised stream's. With ``targets``
        (``models/head.py`` ``Targets``, a row of it for each of
        ``cfg.head_aheads()``) the head runs inside the loss, in blocks of the
        sequence, and the model returns the losses ``[heads]`` float32 in the
        place of logits: the main head's, then the ``mtp`` pass's; under
        ``block_diffusion`` its own weights times the row's."""
        cfg = self.cfg
        length = tokens.shape[1]
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape
            )
        embed = self.param(
            "embedding",
            _partitioned(nn.initializers.normal(1.0), ("vocab", "embed"), cfg),
            (cfg.vocab_size, cfg.d_model),
            cfg.param_dtype,
        )
        embed = jnp.asarray(embed, cfg.dtype)
        if cfg.block_diffusion:
            with jax.named_scope("diffusion.noise"):
                noised, weights, counts = block_noise(
                    cfg, tokens, positions, segment_ids, cfg.noise_key(0) if noise_key is None else noise_key
                )
                tokens, positions = (jnp.concatenate([a, b], axis=1) for a, b in ((tokens, noised), (positions, positions)))
                if segment_ids is not None:
                    segment_ids = jnp.concatenate([segment_ids, segment_ids], axis=1)
            self.sow("intermediates", "target_weights", weights)
            self.sow("intermediates", "diffusion_masked", counts)
        x = embed[tokens]
        if cfg.block_diffusion:
            mask = self.param(
                "mask_embedding", _partitioned(nn.initializers.normal(0.02), ("norm",), cfg),
                (cfg.d_model,), cfg.param_dtype,
            )
            with jax.named_scope("diffusion.noise"):
                masked = jnp.concatenate([jnp.zeros_like(weights, bool), weights > 0], axis=1)
                x = jnp.where(masked[..., None], jnp.asarray(mask, cfg.dtype), x)

        n_dense, n_moe = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
        kinds = cfg.layer_kinds()
        period, n_periods, tail = layer_plan(kinds, n_dense)
        n_scanned = n_periods * len(period)
        apart = True if cfg.layer_types else None  # ``_remat``: replays kept from their forwards
        gates = _parse_ablated(cfg.ablated, cfg.n_layers)
        bias = cfg.select_bias() if cfg.experts_held and cfg.select_bias_std else None

        def per_layer(rows):
            """What a run of expert layers takes from the scan's leading axis."""
            out = {}
            if gates is not None:
                out["gates"] = jnp.asarray(gates[n_dense:][rows])
            if bias is not None:
                out["select_bias"] = jnp.asarray(bias[rows])
            return out

        # the leading dense layers, unrolled: there are few of them
        for i in range(n_dense):
            if gates is None:
                x, _ = _remat(_ScannedLayer, cfg, apart)(cfg, kinds[i], name=f"dense_{i}")(x, positions, segment_ids)
            else:
                x, _ = _remat(_ScannedGatedLayer, cfg)(cfg, kinds[i], name=f"dense_{i}")(
                    x, positions, jnp.asarray(gates[i]), segment_ids
                )
        layer_cls = _remat(_ScannedMoELayer, cfg)
        if cfg.scan_layers:
            scanned = per_layer(slice(0, n_scanned))
            body, kind = layer_cls, period[0]
            if len(period) > 1:  # the period's layers are recomputed one by one inside the body
                body, kind = _ScannedPeriod, period
                scanned = {
                    k: v.reshape(n_periods, len(period), *v.shape[1:]) for k, v in scanned.items()
                }
            x, _ = nn.scan(
                body,
                variable_axes={"params": 0, "intermediates": 0, "cache": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, 0, nn.broadcast),
                length=n_periods,
                metadata_params={nn.PARTITION_NAME: None},
            )(cfg, kind, n_periods > 1, name="layers")(x, positions, scanned, segment_ids)
            for i, kind in enumerate(tail):
                x, _ = _remat(_ScannedMoELayer, cfg, apart)(cfg, kind, name=f"tail_{i}")(
                    x, positions, per_layer(n_scanned + i), segment_ids
                )
        else:
            for i in range(n_moe):
                x, _ = layer_cls(cfg, kinds[n_dense + i], name=f"layers_{i}")(
                    x, positions, per_layer(i), segment_ids
                )

        if cfg.block_diffusion:  # the head reads the noised stream; the clean one fed the keys and values below
            x = x[:, length:]
        x_norm = RMSNorm(cfg, name="final_norm")(x)
        if cfg.tie_embeddings and cfg.mtp_depth:
            raise ValueError("the multi-token-prediction module takes an untied head")

        def mtp_hidden():
            # token i+1's embedding beside position i; the row's last position
            # wraps and is never a target's predictor (its target is masked)
            return MTPModule(cfg, name="mtp")(
                x, embed[jnp.roll(tokens, -1, axis=1)], positions, segment_ids,
                per_layer(n_moe),
            )

        if targets is not None:
            kernel = embed if cfg.tie_embeddings else HeadKernel(cfg, cfg.vocab_size, name="lm_head")()
            own = weights if cfg.block_diffusion else 1.0
            return jnp.stack([
                head.loss(
                    h, kernel, targets.ids[i], targets.weights[i] * own, rows=targets.rows, dtype=cfg.dtype,
                    tied=cfg.tie_embeddings,
                )
                for i, h in enumerate((x_norm, mtp_hidden()) if cfg.mtp_depth else (x_norm,))
            ])
        if cfg.tie_embeddings:
            with jax.named_scope("lm_head"):  # the scope the untied head's module gives
                return jnp.einsum("bsd,vd->bsv", x_norm, embed).astype(jnp.float32)
        lm_head = _dense(cfg.vocab_size, ("embed", "vocab"), cfg, "lm_head")
        logits = lm_head(x_norm)
        if cfg.mtp_depth:
            self.sow("intermediates", "mtp_logits", lm_head(mtp_hidden()).astype(jnp.float32))
        return logits.astype(jnp.float32)
