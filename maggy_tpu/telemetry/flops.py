"""FLOPs/MFU estimation for telemetry gauges.

Training FLOPs/token ≈ 6·params (fwd+bwd matmul estimate: it counts
embedding rows and every expert and leaves attention out, so the monitor's
``mfu_est`` is an estimate; the benchmark's ``train.mfu`` counts from
``benchmark/counts.py``) against the chip's published peak, looked up by
``device.device_kind`` in :data:`PEAK_BF16_FLOPS`. A device that is not in
the table has no peak here — an "MFU" against a guessed peak would be noise,
so the gauge is omitted (CPU test meshes, GPU hosts) and an unknown TPU kind
is logged.
"""

from __future__ import annotations

import logging
from typing import Any, Optional

# Peak dense bf16 FLOP/s of one chip, keyed by the ``device_kind`` jax
# reports (both spellings jax's own pallas tpu_info accepts for each chip).
# Source: Google Cloud TPU documentation, "TPU v5e" (197 TFLOP/s) and
# "TPU v5p" (459 TFLOP/s) system architecture pages.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
}


def param_count(tree) -> int:
    """Total parameter count of a (possibly nn.Partitioned-boxed) param tree."""
    import jax

    try:
        import flax.linen as nn

        boxed = (nn.Partitioned,)
    except Exception:  # flax absent: plain arrays only
        boxed = ()

    total = 0
    for leaf in jax.tree.leaves(tree, is_leaf=lambda x: isinstance(x, boxed) if boxed else None):
        val = leaf.value if boxed and isinstance(leaf, boxed) else leaf
        total += getattr(val, "size", 0)
    return int(total)


def flops_per_token(n_params: int) -> float:
    """Training (fwd+bwd) matmul FLOPs per token, the standard 6N estimate."""
    return 6.0 * float(n_params)


def device_peak_flops(device: Any) -> Optional[float]:
    """Peak bf16 FLOP/s of ``device``'s chip, or None when its kind is not in
    :data:`PEAK_BF16_FLOPS` (never a default)."""
    kind = getattr(device, "device_kind", None)
    peak = PEAK_BF16_FLOPS.get(kind)
    if peak is None and getattr(device, "platform", None) == "tpu":
        logging.getLogger(__name__).warning(
            "no published peak for TPU device_kind %r in "
            "telemetry.flops.PEAK_BF16_FLOPS: MFU is not reported", kind,
        )
    return peak


def estimate_mfu(tok_per_sec: float, n_params: int, devices) -> Optional[float]:
    """Achieved/peak FLOPs fraction for a whole device set, or None when the
    chip's peak is not known."""
    if not devices or tok_per_sec <= 0 or n_params <= 0:
        return None
    peak = device_peak_flops(devices[0])
    if peak is None:
        return None
    achieved = tok_per_sec * flops_per_token(n_params)
    return achieved / (peak * len(devices))
