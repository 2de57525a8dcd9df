"""Plain reference of mistral-7b-v0.3: the dense decoder of
``benchmark/references/decoder.py`` (RMSNorm, split-half rotary embedding,
grouped-query causal attention, SwiGLU), float32 at ``Precision.HIGHEST``."""

from benchmark.references.decoder import GRAD_SAMPLE, train_steps  # noqa: F401
