"""Logical-axis sharding rules (GSPMD layer).

This file is the TPU-native successor to the reference's entire patching layer
(§2.7: DDP/FSDP/DeepSpeed wrappers, ZeRO optimizer monkey-patches): models
annotate parameters with *logical* axis names via ``flax.linen.with_partitioning``
and the rules below map them to mesh axes. Replication, ZeRO-style state
sharding, tensor parallelism and sequence parallelism are all just different
rule tables — no engine wrappers, no monkey-patching. Optimizer state shards
with its parameters for free (optax state mirrors the param pytree).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

from maggy_tpu.parallel.spec import (
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_SLICE,
    AXIS_TENSOR,
)

# Logical axis name -> mesh axis (or tuple of mesh axes, or None = replicate).
# Matches the MaxText-style convention: the same model code serves pure-DP
# (everything replicated), ZeRO-3/FSDP ("embed" sharded over fsdp), TP
# ("mlp"/"heads"/"vocab" over tensor) and any 2D/3D combination, depending only
# on the mesh shape — axes of size 1 shard trivially.
DEFAULT_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", (AXIS_DATA, AXIS_FSDP)),
    ("activation_seq", AXIS_SEQ),
    ("embed", AXIS_FSDP),
    ("mlp", AXIS_TENSOR),
    ("heads", AXIS_TENSOR),
    # a short convolution's channels (models/transformer.py ShortConv): its
    # three weights lie as the attention projections beside them do, the
    # model's width over fsdp ("embed") and the operator's own over tensor
    ("channels", AXIS_TENSOR),
    ("kv", None),
    ("vocab", AXIS_TENSOR),
    ("expert", AXIS_EXPERT),
    ("norm", None),
    ("conv_spatial", None),
    ("conv_in", None),
    ("conv_out", AXIS_FSDP),
)


def slice_rules(rules=DEFAULT_RULES) -> Tuple[Tuple[str, Any], ...]:
    """The rule table for a slice-topology mesh: ``batch`` additionally
    spans the outer ``slice`` axis, so the per-step gradient sync
    decomposes hierarchically — reduce-scatter/all-gather over ``fsdp``
    inside a slice (ICI), one all-reduce over ``slice`` across slices
    (DCN-tolerant). Every other rule is unchanged: params never shard over
    ``slice``, which is what keeps a membership reshape a pure
    re-placement."""
    out = []
    for name, axis in rules:
        if name == "batch":
            cur = (
                tuple(axis)
                if isinstance(axis, (tuple, list))
                else ((axis,) if axis is not None else ())
            )
            axis = (AXIS_SLICE,) + cur
        out.append((name, axis))
    return tuple(out)


def logical_to_mesh_axes(
    logical_axes: Sequence[Optional[str]], rules=DEFAULT_RULES
) -> Tuple:
    table = dict(rules)
    out = []
    for name in logical_axes:
        out.append(table.get(name) if name is not None else None)
    return tuple(out)


def mesh_extent(mesh, axis) -> int:
    """Total device count behind a mesh-axis assignment (None / name / tuple).

    Axes the mesh does not define count as 1 — an ambient user mesh without
    the framework axis names must degrade (downstream NamedSharding
    construction then decides), never KeyError at trace time."""
    if axis is None:
        return 1
    shape = dict(mesh.shape)
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= shape.get(a, 1)
        return n
    return shape.get(axis, 1)


def partition_spec(logical_axes: Sequence[Optional[str]], rules=DEFAULT_RULES):
    from jax.sharding import PartitionSpec

    return PartitionSpec(*logical_to_mesh_axes(logical_axes, rules))


def named_sharding(mesh, logical_axes: Sequence[Optional[str]], rules=DEFAULT_RULES):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, partition_spec(logical_axes, rules))


def _resolve_axis(name, table, mesh_axes):
    """One logical name -> mesh axis assignment. Names already naming mesh
    axes pass through unchanged (boxes rewritten by
    :func:`resolve_boxed_names` re-enter here idempotently); unknown names
    replicate."""
    if name is None:
        return None
    if name in table:
        return table[name]
    if name in mesh_axes:
        return name
    if isinstance(name, (tuple, list)) and all(a in mesh_axes for a in name):
        return tuple(name)
    return None


def _divisible_axes(names, shape, mesh, rules, warn_context=None):
    """Mesh axes for a leaf's dims with the divisibility fallback: axes whose
    extent does not divide the dim replicate (a layout downgrade, never a
    crash) — e.g. 4 attention heads on a tensor=8 mesh."""
    import logging

    table = dict(rules)
    mesh_axes = set(dict(mesh.shape))
    axes = []
    for i, name in enumerate(names):
        axis = _resolve_axis(name, table, mesh_axes)
        ext = mesh_extent(mesh, axis)
        if ext > 1 and shape[i] % ext != 0:
            logging.getLogger(__name__).warning(
                "Axis %d of param (shape %s, logical %s) is not divisible by "
                "mesh axis %r (size %d); replicating that dimension.",
                i, shape, names, axis, ext,
            )
            axis = None
        axes.append(axis)
    return axes


def params_shardings(mesh, abstract_params, rules=DEFAULT_RULES):
    """Map a pytree of (possibly flax-partitioned) abstract leaves to NamedShardings.

    Leaves carrying flax ``nn.Partitioned`` metadata use their logical names;
    plain leaves replicate. Axes whose size does not divide the assigned mesh
    extent fall back to replication with a warning (e.g. 4 attention heads on a
    tensor=8 mesh) — a layout downgrade, never a crash. This is what makes user
    models "obliviously" shardable: annotate once, run under any mesh.
    """
    import flax.linen as nn
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    def leaf_sharding(leaf):
        if not isinstance(leaf, nn.Partitioned):
            return NamedSharding(mesh, PartitionSpec())
        axes = _divisible_axes(leaf.names, leaf.value.shape, mesh, rules)
        return NamedSharding(mesh, PartitionSpec(*axes))

    return jax.tree.map(
        leaf_sharding, abstract_params, is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )


_logical_partitioned_cls = None


def _logical_partitioned_class():
    global _logical_partitioned_cls
    if _logical_partitioned_cls is None:
        import flax.linen as nn
        from flax import struct

        @struct.dataclass
        class LogicalPartitioned(nn.Partitioned):
            """``nn.Partitioned`` whose unboxing NEVER self-constrains.

            The names here are LOGICAL axes ('embed', 'vocab', ...), resolved
            to mesh axes only by this module's rule tables. Stock flax applies
            the names directly as a ``with_sharding_constraint`` whenever an
            ambient mesh is active (``Partitioned.unbox``) — and raw logical
            names are not mesh axes, so every ``model.init``/``apply`` under
            ``with mesh:`` would be rejected by jax. Placement in this
            framework is decided once, by ``Trainer.make_state``'s
            out_shardings (from :func:`params_shardings`), and GSPMD
            propagates it — the boxes are pure metadata carriers.
            """

            def unbox(self, apply_constraint=True):
                return self.value

        _logical_partitioned_cls = LogicalPartitioned
    return _logical_partitioned_cls


def logical_partitioning(fn, names):
    """Like ``nn.with_partitioning(fn, names)``, but producing
    :class:`LogicalPartitioned` boxes (metadata-only, no unbox-time
    constraint). Every framework model annotates through this."""
    import functools

    cls = _logical_partitioned_class()

    @functools.wraps(fn)
    def init(*args, **kwargs):
        return cls(fn(*args, **kwargs), names)

    return init


def unbox(tree):
    """Strip flax Partitioned boxes, returning raw arrays."""
    import flax.linen as nn
    import jax

    return jax.tree.map(
        lambda x: x.value if isinstance(x, nn.Partitioned) else x,
        tree,
        is_leaf=lambda x: isinstance(x, nn.Partitioned),
    )


def batch_sharding(mesh, rules=DEFAULT_RULES):
    """Sharding for [batch, ...] host data: batch over (data, fsdp)."""
    return named_sharding(mesh, ("batch",), rules)


def constrain_activation(x, logical_axes, rules=DEFAULT_RULES):
    """Pin an activation's layout inside jit via ``with_sharding_constraint``.

    GSPMD propagates shardings from parameters, but on deep mixed meshes
    (tp x fsdp x sp) the residual stream between layers is where propagation
    can drift into accidental all-gathers; pinning it (batch over
    (data, fsdp), seq over sp, embed replicated) keeps collectives where the
    design wants them. No-op without an ambient mesh, on single-device
    meshes, and for axes that do not divide (GSPMD would insert padding —
    a silent layout downgrade is better than a padded one).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from maggy_tpu.parallel.mesh import ambient_mesh

    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return x
    # inside a shard_map body (Manual axes) placement is already manual;
    # a constraint built from the Auto physical mesh would trace without
    # raising but poison the region's vjp with a mesh-mismatched op
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and jax.sharding.AxisType.Manual in set(am.axis_types):
        return x
    axes = list(logical_to_mesh_axes(logical_axes, rules))
    # slice-topology meshes: models pin activations with the DEFAULT rule
    # table, whose batch rule knows nothing of the outer slice axis — a
    # (data, fsdp)-only constraint there would force a cross-slice row
    # gather every layer. Widen batch constraints to include slice so the
    # pin agrees with the input placement.
    if dict(mesh.shape).get(AXIS_SLICE, 1) > 1:
        for i, (name, axis) in enumerate(zip(logical_axes, axes)):
            if name == "batch" and axis is not None:
                cur = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
                if AXIS_SLICE not in cur:
                    axes[i] = (AXIS_SLICE,) + cur
    for i, axis in enumerate(axes):
        ext = mesh_extent(mesh, axis)
        if ext > 1 and x.shape[i] % ext:
            axes[i] = None
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec(*axes))
        )
    except Exception:  # manual (shard_map) regions reject constraints
        return x
