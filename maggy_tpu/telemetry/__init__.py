"""Unified telemetry: spans, step metrics, and durable trace export.

The reference framework's only observability is log shipping plus one scalar
metric per heartbeat (SURVEY §2.4 LOG/METRIC verbs). This package adds the
structured layer every tier threads through:

* :mod:`maggy_tpu.telemetry.recorder` — a process-local :class:`Telemetry`
  recorder with ``span(name)`` context managers and typed counters/gauges,
  buffered lock-free per worker; a span names its ``parent``, and the compile
  pipeline's stages (``jax.monitoring``) are journaled as ``compile.*`` spans
  under the program span whose call compiled. ``MAGGY_TPU_TELEMETRY=0`` swaps
  in a no-op recorder so the hot path carries zero instrumentation cost.
* :mod:`maggy_tpu.telemetry.sink` — a JSONL sink on the env storage seam, so
  records land under ``<exp_dir>/telemetry/worker_<pid>.jsonl`` identically on
  a local disk or ``gs://``.
* :mod:`maggy_tpu.telemetry.export` — merges every worker's JSONL into one
  Chrome-trace (Perfetto-loadable) JSON on the shared wall-clock base —
  including one lane per traced request — and mirrors gauge series into
  TensorBoard scalars via the tensorboard.py seam.
* :mod:`maggy_tpu.telemetry.tracing` — request-scoped trace ids, minted at
  the edge and propagated on every RPC frame; records tagged automatically.
* :mod:`maggy_tpu.telemetry.histogram` — fixed-log-bucket latency
  histograms (TTFT/TPOT/queue-wait/e2e), mergeable across replicas.
* :mod:`maggy_tpu.telemetry.flightrec` — stall watchdog + flight recorder:
  bounded event rings plus thread-stack dumps when a progress loop wedges.
* :mod:`maggy_tpu.telemetry.metrics` — the checked-in metric-name registry
  ``tools/check_telemetry_names.py`` enforces.
* :mod:`maggy_tpu.telemetry.timeseries` — bounded ring-buffer series sampled
  from the recorder on a fixed tick, with windowed ``rate``/``delta``/
  percentile queries and a versioned snapshot form (the ``METRICS`` RPC
  payload and the autoscaler's input substrate).
* :mod:`maggy_tpu.telemetry.alerts` — the checked-in alert-rule registry
  (threshold + for-duration, multi-window SLO burn rate) evaluated per
  worker and at fleet scope, plus the recompile sentinel.

Wiring: executors build a worker recorder (:func:`worker_telemetry`), install
it as the thread-ambient recorder (``Trainer.fit`` and ``Checkpointer`` pick
it up via :func:`get`), and hand it to the RPC client so per-verb latencies
and heartbeat RTTs record too; every heartbeat attaches a snapshot that the
driver folds into STATUS for the live monitor panel.
"""

from __future__ import annotations

from maggy_tpu.telemetry import alerts, flightrec, timeseries, tracing  # noqa: F401
from maggy_tpu.telemetry.alerts import AlertEvaluator, RecompileSentinel  # noqa: F401
from maggy_tpu.telemetry.histogram import LatencyHistogram  # noqa: F401
from maggy_tpu.telemetry.timeseries import Series, SeriesStore  # noqa: F401
from maggy_tpu.telemetry.recorder import (  # noqa: F401
    NULL,
    NullTelemetry,
    Telemetry,
    current,
    enabled,
    get,
    set_current,
)
from maggy_tpu.telemetry.sink import JsonlSink, telemetry_dir, worker_telemetry  # noqa: F401

__all__ = [
    "Telemetry",
    "NullTelemetry",
    "NULL",
    "enabled",
    "get",
    "set_current",
    "current",
    "JsonlSink",
    "telemetry_dir",
    "worker_telemetry",
    "LatencyHistogram",
    "Series",
    "SeriesStore",
    "AlertEvaluator",
    "RecompileSentinel",
    "tracing",
    "flightrec",
    "timeseries",
    "alerts",
]
