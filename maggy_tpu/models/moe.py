"""Mixtral-style sparse Mixture-of-Experts decoder.

Expert parallelism is absent from the reference (§2.10) and required by the
BASELINE Mixtral config. TPU-first design: GShard-style dense dispatch —
top-k routing builds one-hot dispatch/combine tensors with a static per-expert
capacity, expert FFNs are a single batched einsum over parameters laid out
[experts, ...] and sharded on the ``expert`` mesh axis, so XLA inserts the
token all-to-alls and the whole layer stays static-shaped for the MXU.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from maggy_tpu.models.transformer import (
    REMAT_POLICIES,
    Attention,
    DecoderConfig,
    RMSNorm,
    _dense,
    _parse_ablated,
    _partitioned,
)


@dataclasses.dataclass(frozen=True)
class MoEConfig(DecoderConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01
    # tokens are routed in fixed-size groups so the dispatch one-hot is
    # O(tokens * group_size), not O(tokens^2) — the GShard group axis
    group_size: int = 512

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


class MoELayer(nn.Module):
    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None, gates=None):
        """``gates`` — optional [2] float (attn, moe) LOCO ablation gates,
        same semantics as DecoderLayer (zero gate = identity residual,
        zero grads, unchanged param tree). The gate also scales the sown
        router aux loss — an ablated expert block must not keep pushing
        balancing gradients into its router."""
        a = Attention(self.cfg, name="attn")(
            RMSNorm(self.cfg, name="attn_norm")(x), positions, segment_ids
        )
        x = x + (a if gates is None else a * gates[0].astype(a.dtype))
        m = MoEBlock(self.cfg, name="moe")(
            RMSNorm(self.cfg, name="mlp_norm")(x),
            aux_gate=None if gates is None else gates[1],
        )
        x = x + (m if gates is None else m * gates[1].astype(m.dtype))
        return x


class _ScannedMoELayer(nn.Module):
    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, positions, segment_ids=None):
        return MoELayer(self.cfg, name="layer")(x, positions, segment_ids), None


class _ScannedGatedMoELayer(nn.Module):
    """Scan body when LOCO gates are active (gates ride in_axes=0)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, positions, gates, segment_ids=None):
        return MoELayer(self.cfg, name="layer")(
            x, positions, segment_ids, gates
        ), None


class MoEDecoder(nn.Module):
    """Sparse-MoE causal LM; same interface as
    :class:`maggy_tpu.models.transformer.Decoder`."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None):
        cfg = self.cfg
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
        x = jnp.asarray(embed, cfg.dtype)[tokens]

        gates = _parse_ablated(cfg.ablated, cfg.n_layers)
        layer_cls = _ScannedMoELayer if gates is None else _ScannedGatedMoELayer
        if cfg.remat and not cfg.decode:  # no gradients (hence no remat) in decode
            layer_cls = nn.remat(
                layer_cls,
                prevent_cse=not cfg.scan_layers,
                policy=REMAT_POLICIES[cfg.remat_policy],
            )
        if cfg.scan_layers:
            scanned = nn.scan(
                layer_cls,
                variable_axes={"params": 0, "intermediates": 0, "cache": 0},
                split_rngs={"params": True},
                in_axes=(
                    (nn.broadcast, nn.broadcast)
                    if gates is None
                    else (nn.broadcast, 0, nn.broadcast)
                ),
                length=cfg.n_layers,
                metadata_params={nn.PARTITION_NAME: None},
            )(cfg, name="layers")
            if gates is None:
                x, _ = scanned(x, positions, segment_ids)
            else:
                x, _ = scanned(x, positions, jnp.asarray(gates), segment_ids)
        else:
            for i in range(cfg.n_layers):
                if gates is None:
                    x, _ = layer_cls(cfg, name=f"layers_{i}")(x, positions, segment_ids)
                else:
                    x, _ = layer_cls(cfg, name=f"layers_{i}")(
                        x, positions, jnp.asarray(gates[i]), segment_ids
                    )

        x = RMSNorm(cfg, name="final_norm")(x)
        logits = _dense(cfg.vocab_size, ("embed", "vocab"), cfg, "lm_head")(x)
        return logits.astype(jnp.float32)
