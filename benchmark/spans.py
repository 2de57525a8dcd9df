"""One timeline from the profiler's trace: device operations grouped by the
scope the program gave them, device idle time put down to the program span
the loop thread was in.

Both names come from the program. A span of ``Telemetry.span`` is a
``TraceAnnotation`` on the host plane of the ``.xplane.pb``; a scope is a flax
module name or a ``jax.named_scope`` in the ``op_name`` of the compiled HLO,
which the trace carries as the ``tf_op`` stat of each device operation's event
metadata. ``jax.profiler.ProfileData`` shows an event's own stats only, so the
metadata's are read from the file's wire format here (``read_op_names``).
Against a program without the spans or scopes the readers find nothing and
return ``None``.

The file is loaded once per process (``load``); the reductions are plain
functions over event lists (``checks/test_span_metrics.py``).
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

from benchmark import trace

# innermost first match decides; a flax module's own name is its scope
TRAIN_GROUPS = {
    "attn": "attn", "attn_norm": "attn",
    "mlp": "mlp", "mlp_norm": "mlp", "moe": "mlp",
    "moe.route": "mlp", "moe.dispatch": "mlp", "moe.experts": "mlp", "moe.combine": "mlp",
    "loss": "loss", "final_norm": "loss", "lm_head": "loss",
    "optimizer": "optimizer",
    "grad_sync": "grad_sync",
}
# what lies under the layer scan and under no module of a layer: the scan's
# own slicing and stacked writes (``while/body/dynamic_update_slice``), what
# XLA hoists out of it (``.../while:``), the residual adds (``layers/layer/add``)
SCAN_GROUPS = {"layers": "scan", "while": "scan"}
REMAT_MARKER = "rematted_computation"

TRAIN_SPANS = ("train.fit_setup", "train.input_wait", "shard_batch", "train_step",
               "train.drain", "train.checkpoint")

_WORD = re.compile(r"[A-Za-z_][\w.]*")


# ------------------------------------------------------------ the wire format


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane message")
        yield key >> 3, value


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), None)


def read_op_names(path: str) -> dict:
    """``{device plane: {event name: op_name}}`` from the ``tf_op`` stat of
    the planes' event metadata (``XSpace.planes[].event_metadata[].stats``)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, stat_names, events = "", {}, []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                meta = dict(_fields(_map_value(v)))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        table = {}
        for entry in events:
            ev_name, op_name = "", None
            for f, v in _fields(_map_value(entry)):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        op_name = bytes(stat[5]).decode() if 5 in stat else stat_names.get(stat.get(7))
            if op_name:
                table[ev_name] = op_name
        out[name] = table
    return out


# ------------------------------------------------------------------ reductions


def scope_of(op_name, groups, fallback=None):
    """The group of the innermost component of ``op_name`` that names one
    (``jit(step)/jvp(Decoder)/layers/layer/attn/wq/dot_general:`` is
    ``attn``; the last component is the primitive, not a scope); else the
    group of the first word of ``fallback`` among the components, where the
    primitive counts if it is the word (``.../while:``); else ``None``."""
    if not op_name:
        return None
    *scopes, primitive = op_name.rstrip(":").split("/")
    parts = [_WORD.findall(part) for part in scopes]
    for part in reversed(parts):
        for word in reversed(part):
            if word in groups:
                return groups[word]
    for word in [w for part in parts for w in part] + ([primitive] if scopes else []):
        if fallback and word in fallback:
            return fallback[word]
    return None


def time_by_scope(ops, groups, fallback=None) -> dict:
    """Summed duration per group over ``(start, end, name, op_name)``
    operations (containers left out by the caller); ``None`` holds the rest."""
    out = defaultdict(int)
    for start, end, _name, op_name in ops:
        out[scope_of(op_name, groups, fallback)] += end - start
    return dict(out)


def marked_time(ops, marker=REMAT_MARKER) -> int:
    return sum(e - s for s, e, _n, op_name in ops if op_name and marker in op_name)


def innermost(spans):
    """Disjoint ``(start, end, name)`` segments from one thread's spans,
    which nest: every instant belongs to the innermost span open at it."""
    out, stack, t = [], [], None

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            emit(t, end, top)
            t = max(t, end)
        if stack:
            emit(t, s, stack[-1][1])
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    while stack:
        end, top = stack.pop()
        emit(t, end, top)
        t = max(t, end)
    return out


def gaps_of(busy, lo, hi):
    """The idle intervals of ``[lo, hi]`` around merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def idle_by_span(gaps, spans) -> dict:
    """Idle time per span name: each instant of a gap goes to the innermost
    span open at it; ``None`` holds idle time inside no span."""
    segments = innermost(spans)
    starts = [s for s, _e, _n in segments]
    out = defaultdict(int)
    for g0, g1 in gaps:
        named = 0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                out[name] += overlap
                named += overlap
            i += 1
        out[None] += (g1 - g0) - named
    return dict(out)


# -------------------------------------------------------------------- the file


class Timeline:
    """What the readers share: per device the operations with their
    ``op_name``, busy time and the window as ``trace.reduce`` has them, the
    first device's idle gaps, and the program's spans per host thread."""

    def __init__(self, path: str, chips: int = 1, span_names=TRAIN_SPANS):
        devices, hosts = trace.read_planes(path)
        devices = devices[:chips]
        if not devices:
            raise RuntimeError("the trace holds no /device:TPU plane")
        op_names = read_op_names(path)
        self.ops, self.busy_ns, merged0 = [], [], None
        lo = hi = None
        for plane in devices:
            table = op_names.get(plane.name, {})
            events = trace.line_events(plane, "XLA Ops")
            for s, e, _ in events + trace.line_events(plane, "XLA Modules"):
                lo = s if lo is None or s < lo else lo
                hi = e if hi is None or e > hi else hi
            merged = trace.union([(s, e) for s, e, _ in events])
            merged0 = merged if merged0 is None else merged0
            self.busy_ns.append(trace.covered(merged))
            self.ops.append([(s, e, n, table.get(n)) for s, e, n in events if not trace.is_container(n)])
        self.lo, self.hi = lo, hi
        self.gaps = gaps_of(merged0, lo, hi)
        self._scope_times = {}
        names = set(span_names)
        self.threads = []  # one list of (start, end, name) per host thread that recorded a span
        for plane in hosts:
            for line in plane.lines:
                found = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name) for ev in line.events if ev.name in names]
                if found:
                    self.threads.append(found)

    @property
    def busy(self) -> float:
        return sum(self.busy_ns) / len(self.busy_ns)

    @property
    def window(self) -> float:
        return self.hi - self.lo

    def scope_time(self, groups, fallback=None) -> dict:
        """Device time per group, a mean over the devices (kept: several
        readers ask for the same grouping)."""
        key = (id(groups), id(fallback))
        if key not in self._scope_times:
            out = defaultdict(float)
            for ops in self.ops:
                for group, t in time_by_scope(ops, groups, fallback).items():
                    out[group] += t / len(self.ops)
            self._scope_times[key] = dict(out)
        return self._scope_times[key]

    def remat_time(self) -> float:
        return sum(marked_time(ops) for ops in self.ops) / len(self.ops)

    def thread_of(self, span_name: str):
        """The spans of the thread that recorded ``span_name`` most often
        (the loop thread, for the span of its step)."""
        best = max(self.threads, key=lambda t: sum(n == span_name for _s, _e, n in t), default=None)
        if best is None or not any(n == span_name for _s, _e, n in best):
            return None
        return best


_LOADED = {}


def load(obs, span_names=TRAIN_SPANS):
    """The timeline of this run's trace, or ``None`` without one."""
    cell = obs.get("cell")
    if obs.get("trace") is None or cell is None:
        return None
    if cell.trace_dir not in _LOADED:
        try:
            path = trace.find_xplane(cell.trace_dir)
        except FileNotFoundError:
            return None
        _LOADED[cell.trace_dir] = Timeline(path, chips=cell.chips, span_names=span_names)
    return _LOADED[cell.trace_dir]


def train_scope_share(obs, group):
    """Busy time under ``group`` of the train step's scopes over busy time,
    in percent (``"unscoped"``: under none of them)."""
    tl = load(obs)
    if tl is None or "needed_flops" not in obs:
        return None
    times = tl.scope_time(TRAIN_GROUPS, SCAN_GROUPS)
    if not any(k for k in times):
        return None  # a program that names nothing
    if group == "unscoped":
        named = sum(t for k, t in times.items() if k in ("attn", "mlp", "loss", "optimizer", "scan"))
        return (tl.busy - named) / tl.busy * 100.0
    return times.get(group, 0.0) / tl.busy * 100.0


def train_idle_share(obs, names):
    """Device idle time inside the loop thread's spans ``names`` (``None``:
    inside none of its spans) over the traced window, in percent."""
    tl = load(obs)
    if tl is None or "needed_flops" not in obs:
        return None
    loop = tl.thread_of("train_step")
    if loop is None:
        return None
    idle = idle_by_span(tl.gaps, loop)
    return sum(idle.get(n, 0) for n in names) / tl.window * 100.0
