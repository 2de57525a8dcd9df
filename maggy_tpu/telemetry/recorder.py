"""Process-local telemetry recorder.

One :class:`Telemetry` instance per worker (executor thread, pod process, or
the driver itself). The hot path — ``span`` enter/exit, ``gauge``,
``event``, ``histogram`` — touches only ``deque.append``s and dict stores,
each a single GIL-atomic operation, so per-worker recording is lock-free;
the only lock in the class guards the RPC latency accumulators, which sit
on network-bound paths where a ~100ns uncontended acquire is noise.

Every record is tagged with the thread-ambient trace id
(:mod:`maggy_tpu.telemetry.tracing`) when one is in scope, and teed into a
bounded flight ring the stall watchdog
(:mod:`maggy_tpu.telemetry.flightrec`) dumps when a progress loop wedges.
``histogram`` aggregates latency samples into fixed-log-bucket
distributions (:mod:`maggy_tpu.telemetry.histogram`) that ride in
snapshots — mergeable across workers, percentile-ready.

Two clocks, deliberately: every record carries a wall-clock ``ts``
(``time.time()``, the common base that lets the exporter merge spans from
many workers/hosts into one Chrome trace) while durations come from
``time.perf_counter()`` (monotonic, immune to NTP steps). A span also opens a
``jax.profiler.TraceAnnotation`` of the same name and attributes: while a
profiler session runs (``Trainer.fit(profile_dir=...)``, ``profcap``, the
benchmark's ``--trace 1``) the span lies on the host plane of the
``.xplane.pb``, on the device events' clock; outside one it is a flag check.

A span record names the span that caused it: ``parent`` is the innermost
span open on the same thread when this one was opened (absent at the top), so
a span's self time is its duration less its children's. ``record_span``
journals the same record from a start and an end that something else measured
on ``time.time()``; the compile pipeline's stages come that way, from
``jax.monitoring`` (:func:`install_compile_listeners`): ``compile.trace``,
``compile.lower`` and ``compile.backend`` under the program span whose call
compiled, with the persistent cache's verdict on the last.

``MAGGY_TPU_TELEMETRY=0`` disables recording globally: :func:`get` then
returns the shared :data:`NULL` no-op recorder, whose ``span`` hands back one
reusable null context manager — the instrumented code paths stay in place at
zero cost.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

from maggy_tpu.core import lockdebug
from maggy_tpu.telemetry import tracing
from maggy_tpu.telemetry.histogram import LatencyHistogram

ENV_FLAG = "MAGGY_TPU_TELEMETRY"

# span/gauge events kept in memory between sink flushes; oldest dropped first
# (a worker with an attached sink flushes every heartbeat, so the cap only
# matters for unflushed standalone use)
DEFAULT_CAPACITY = 100_000

# flight-recorder ring: the last records this worker produced, always in
# memory, dumped by the stall watchdog (telemetry/flightrec.py)
FLIGHT_CAPACITY = 512


def enabled() -> bool:
    """Telemetry is on unless explicitly disabled (``MAGGY_TPU_TELEMETRY=0``)."""
    return os.environ.get(ENV_FLAG, "1").lower() not in ("0", "false", "off")


class Telemetry:
    """Recorder for one worker: spans, gauges, counters, RPC latencies."""

    active = True

    def __init__(self, worker: Any = 0, role: str = "worker", capacity: int = DEFAULT_CAPACITY):
        self.worker = str(worker)
        self.role = role
        self._events: deque = deque(maxlen=capacity)
        # bounded tee of the same records for the stall flight recorder —
        # never drained, so a dump always has the recent past
        self.flight: deque = deque(maxlen=FLIGHT_CAPACITY)
        self._gauges: Dict[str, float] = {}  # race: ok — GIL-atomic dict stores, latest-value-wins semantics; snapshot copies are best-effort
        self._counters: Dict[str, int] = {}  # race: ok — single-writer per key by design (module docstring); rpc_errors.* keys are written only under _rpc_lock
        # name -> fixed-log-bucket latency distribution (single-writer per
        # worker, like counters; snapshot copies under no lock by the same
        # GIL-atomicity argument)
        self._hists: Dict[str, LatencyHistogram] = {}
        # verb -> [n, total_ms, max_ms]; the single locked structure (see
        # module docstring) because two threads (worker + heartbeat) write it
        self._rpc: Dict[str, List[float]] = {}
        self._rpc_lock = lockdebug.lock("telemetry._rpc_lock")
        self._sink = None
        # flush is called from both the worker thread (trial boundaries) and
        # the heartbeat thread (per beat); serialize so JSONL lines never tear
        self._flush_lock = lockdebug.lock("telemetry._flush_lock")
        _instances.add(self)
        install_compile_listeners()

    # ------------------------------------------------------------------ spans

    def _append(self, rec: Dict[str, Any]) -> None:
        """Journal one record (sink buffer + flight ring), tagging it with
        the thread-ambient trace id when one is in scope — the whole
        cross-worker correlation story is this one optional field."""
        trace = tracing.current()
        if trace is not None:
            rec["trace"] = trace
        self._events.append(rec)
        self.flight.append(rec)

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[None]:
        """Time a block; records wall-clock start + duration on exit, and
        annotates the profiler's trace with it when one is being taken."""
        stack = _open_spans()
        parent = stack[-1] if stack else None
        stack.append(name)
        ts = time.time()
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name, **attrs):
                yield
        finally:
            dur_ms = (time.perf_counter() - t0) * 1e3
            stack.pop()
            self._append(self._span_record(name, ts, dur_ms, parent, attrs))

    def _span_record(self, name, ts, dur_ms, parent, attrs) -> Dict[str, Any]:
        rec = {
            "kind": "span",
            "name": name,
            "ts": ts,
            "dur_ms": dur_ms,
            "worker": self.worker,
            "tid": threading.get_ident() & 0xFFFF,
        }
        if parent is not None:
            rec["parent"] = parent
        if attrs:
            rec["attrs"] = attrs
        return rec

    def record_span(self, name: str, start: float, end: float, **attrs) -> None:
        """Journal a span after the fact, from a start and an end on
        ``time.time()``'s clock: the record ``span`` writes, with the calling
        thread's innermost open span as ``parent``, and no annotation of the
        profiler's trace (the interval is over)."""
        stack = _open_spans()
        self._append(
            self._span_record(name, start, (end - start) * 1e3, stack[-1] if stack else None, attrs)
        )

    # ------------------------------------------------------- gauges / counters

    def gauge(self, name: str, value: float) -> None:  # thread-entry — heartbeat + scheduler threads record gauges
        """Set a gauge to its latest value (also journaled as an event)."""
        value = float(value)
        self._gauges[name] = value
        self._append(
            {
                "kind": "gauge",
                "name": name,
                "ts": time.time(),
                "value": value,
                "worker": self.worker,
            }
        )

    def count(self, name: str, n: int = 1) -> None:  # thread-entry — scheduler/router threads count from their loops
        """Increment a counter (single-writer per worker by design)."""
        self._counters[name] = self._counters.get(name, 0) + n

    def event(self, name: str, trace: Optional[str] = None, **attrs) -> None:
        """Journal one lifecycle milestone (request/run state transition),
        correlated by ``trace`` (explicit, else the thread-ambient id)."""
        rec: Dict[str, Any] = {
            "kind": "event",
            "name": name,
            "ts": time.time(),
            "worker": self.worker,
        }
        if attrs:
            rec["attrs"] = attrs
        if trace is not None:
            rec["trace"] = trace
            self._events.append(rec)
            self.flight.append(rec)
        else:
            self._append(rec)

    def histogram(self, name: str, value_ms: float) -> None:
        """Observe one latency sample into the named fixed-log-bucket
        histogram (created on first use; serialized into snapshots)."""
        h = self._hists.get(name)
        if h is None:
            h = self._hists.setdefault(name, LatencyHistogram())
        h.observe(value_ms)

    def rpc(self, verb: str, ms: Optional[float] = None, ok: bool = True) -> None:  # thread-entry — worker + heartbeat threads both record RPCs
        """Record one RPC round-trip for ``verb`` (thread-safe)."""
        with self._rpc_lock:
            rec = self._rpc.setdefault(verb, [0, 0.0, 0.0])
            rec[0] += 1
            if ms is not None:
                rec[1] += ms
                if ms > rec[2]:
                    rec[2] = ms
            if not ok:
                self._counters[f"rpc_errors.{verb}"] = (
                    self._counters.get(f"rpc_errors.{verb}", 0) + 1
                )

    # ------------------------------------------------------------------ export

    def snapshot(self) -> Dict[str, Any]:  # thread-entry — the heartbeat thread attaches snapshots to beats
        """Compact aggregate state for heartbeat attachment: latest gauges,
        counters, and per-verb RPC stats — no event history."""
        out: Dict[str, Any] = {"worker": self.worker, "role": self.role, "ts": time.time()}
        if self._gauges:
            out["gauges"] = dict(self._gauges)
        if self._counters:
            out["counters"] = dict(self._counters)
        if self._hists:
            out["hist"] = {name: h.to_dict() for name, h in self._hists.items()}
        with self._rpc_lock:
            if self._rpc:
                out["rpc"] = {
                    verb: {
                        "n": int(n),
                        "mean_ms": round(total / n, 3) if n else None,
                        "max_ms": round(mx, 3),
                    }
                    for verb, (n, total, mx) in self._rpc.items()
                }
        return out

    def drain_events(self) -> List[Dict[str, Any]]:
        """Pop and return all buffered events (safe against concurrent appends)."""
        out = []
        try:
            while True:
                out.append(self._events.popleft())
        except IndexError:
            pass
        return out

    # ------------------------------------------------------------------- sink

    def attach_sink(self, sink) -> None:
        self._sink = sink

    def flush(self) -> None:  # thread-entry — the heartbeat thread flushes every beat
        """Drain buffered events into the attached sink (no-op without one)."""
        if self._sink is None:
            return
        with self._flush_lock:
            if self._sink is None:
                return
            events = self.drain_events()
            if events:
                self._sink.write(events)

    def close(self) -> None:
        """Final flush + snapshot record, then close the sink."""
        if self._sink is None:
            return
        snap = self.snapshot()
        snap["kind"] = "snapshot"
        self._events.append(snap)
        self.flush()
        with self._flush_lock:
            if self._sink is not None:
                self._sink.close()
                self._sink = None


class NullTelemetry:
    """No-op recorder installed when telemetry is disabled."""

    active = False
    worker = "null"
    role = "null"
    flight = ()  # the ring a reader of ``Telemetry.flight`` finds: empty

    _NULL_CTX = contextlib.nullcontext()

    def span(self, name: str, **attrs):
        return self._NULL_CTX

    def record_span(self, name: str, start: float, end: float, **attrs) -> None:
        pass

    def gauge(self, name: str, value: float) -> None:
        pass

    def count(self, name: str, n: int = 1) -> None:
        pass

    def event(self, name: str, trace: Optional[str] = None, **attrs) -> None:
        pass

    def histogram(self, name: str, value_ms: float) -> None:
        pass

    def rpc(self, verb: str, ms: Optional[float] = None, ok: bool = True) -> None:
        pass

    def snapshot(self) -> Dict[str, Any]:
        return {}

    def drain_events(self) -> List[Dict[str, Any]]:
        return []

    def attach_sink(self, sink) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL = NullTelemetry()

# every live recorder, for the stall watchdog's dump (weak: a recorder dies
# with its owner, the registry must not keep it alive)
_instances: "weakref.WeakSet[Telemetry]" = weakref.WeakSet()


def flight_snapshots() -> List[Dict[str, Any]]:
    """Every live recorder's flight ring (most recent records last), for
    the watchdog dump. Rings are copied, never drained."""
    out = []
    for tel in list(_instances):
        ring = list(tel.flight)
        if ring:
            out.append({"worker": tel.worker, "role": tel.role, "events": ring})
    return out


# per thread: the ambient recorder (``get``), the names of the spans open on
# the thread, outermost first (whichever recorder opened them: the enclosing
# span is a fact about the thread), and what the persistent compile cache said
# of the compile in progress on it
_tls = threading.local()


def _open_spans() -> List[str]:
    stack = getattr(_tls, "spans", None)
    if stack is None:
        stack = _tls.spans = []
    return stack


# ------------------------------------------------------- the compile pipeline
# jax.monitoring reports each stage of a program's compilation on the thread
# that called the program, with the start and end it measured on time.time().
# A listener compares the event's name and records: an event not named here is
# dropped at the comparison, and listeners fire on compile events only, never
# per step.

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
# JAX also reports a trace for every jitted function called inside another's
# trace and for every eager operation's lookup of a program it has: tens of
# microseconds each, a thousand and more a set-up. Journaled they would push
# everything else out of the flight ring and tell nothing (an inner trace lies
# inside its caller's), so a trace shorter than this is not recorded
_MIN_TRACE_S = 1e-3
# inside the backend stage, in this order: the cache is asked, and on a hit
# the hit and the retrieval time are reported before the stage ends. JAX asks
# with no directory to ask in too; that, like a disabled cache, reads "off"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def _on_compile_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _tls.cache = "hit"
    elif event == _CACHE_ASKED and jax.config.jax_compilation_cache_dir:
        _tls.cache = "miss"


def _on_compile_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _CACHE_LOAD:
        _tls.cache_load_ms = duration_secs * 1e3


def _on_compile_stage(event: str, start: float, end: float, fun_name: str = "", **_kw) -> None:
    if event == _TRACE:
        if end - start >= _MIN_TRACE_S:
            tel = get()
            tel.record_span("compile.trace", start, end, fun_name=fun_name)
    elif event == _LOWER:
        tel = get()
        tel.record_span("compile.lower", start, end, fun_name=fun_name)
    elif event == _BACKEND:
        tel = get()
        cache = getattr(_tls, "cache", "off")
        _tls.cache = "off"
        attrs = {"fun_name": fun_name, "cache": cache}
        if cache == "hit":
            tel.count("compile.cache_hits")
            attrs["cache_load_ms"] = getattr(_tls, "cache_load_ms", 0.0)
        elif cache == "miss":
            tel.count("compile.cache_misses")
        tel.record_span("compile.backend", start, end, **attrs)


_listening = False
_listening_lock = threading.Lock()


def install_compile_listeners() -> None:
    """Register the three listeners with ``jax.monitoring``, once a process
    and never with telemetry disabled. Called where a real recorder is built
    (``Telemetry.__init__``)."""
    global _listening
    if _listening or not enabled():
        return
    with _listening_lock:
        if _listening:
            return
        jax.monitoring.register_event_listener(_on_compile_event)
        jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
        jax.monitoring.register_event_time_span_listener(_on_compile_stage)
        _listening = True


# thread-ambient recorder: executors are THREADS in one process (like the
# Reporter print tee), so the current recorder is thread-local, with one lazy
# process-wide default for standalone Trainer.fit use outside any experiment
_default_lock = threading.Lock()
_default: Optional[Telemetry] = None


def get():
    """The ambient recorder for this thread; :data:`NULL` when disabled."""
    if not enabled():
        return NULL
    tel = getattr(_tls, "telemetry", None)
    if tel is not None:
        return tel
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Telemetry(worker="main", role="standalone")
    return _default


def set_current(tel) -> None:
    """Install ``tel`` as this thread's ambient recorder (None to clear)."""
    _tls.telemetry = tel


@contextlib.contextmanager
def current(tel) -> Iterator[None]:
    """Scope ``tel`` as the ambient recorder for this thread."""
    prev = getattr(_tls, "telemetry", None)
    _tls.telemetry = tel
    try:
        yield
    finally:
        _tls.telemetry = prev
