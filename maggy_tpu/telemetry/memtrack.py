"""Per-worker device memory ledger: named accounts reconciled against HBM.

The fleet can see latency (tracing, time-series, burn-rate alerts) but not
*capacity*: nothing says how much HBM is spoken for, by what, or how much
headroom a replica has before the next admission preempts. This module is
that measurement layer — the substrate ROADMAP's autoscaler (cost-normalized
scaling needs headroom) and KV-tiering (eviction needs occupancy) items
consume as-is.

Mechanics: allocation sites register **named accounts** — ``params``,
``optimizer`` (ZeRO shards), ``kv_pages``, ``prefetch``, ``workspace`` —
each a byte figure the owner computes from its own arrays (``Trainer`` for
params/optimizer, ``Engine`` for the KV page pool, ``DevicePrefetcher`` for
its staging queue). A 1 Hz :meth:`MemoryLedger.tick` from the owning metrics
loop reconciles the account sum against what the runtime actually reports
(``jax.local_devices()[*].memory_stats()``), and exports:

* ``mem.hbm_used`` / ``mem.hbm_free`` / ``mem.headroom_pct`` gauges,
* one ``mem.account.<name>`` gauge per account,
* ``mem.unattributed`` — reported-used minus the account sum (a growing
  value here means an allocation site forgot to register),
* cumulative ``mem.headroom_ok`` / ``mem.headroom_miss`` counters — the
  pair the ``alert.hbm_headroom`` multi-window burn rule reads: a tick with
  headroom under the low-water mark is a miss.

**CPU-sim fallback.** On hosts whose devices expose no ``memory_stats``
(the CPU backend tier-1 runs on), reconciliation stays fully exercised
against a deterministic simulation: reported-used is the account sum plus a
fixed :data:`SIM_UNATTRIBUTED_FRAC` runtime overhead, against a pool of
:attr:`MemoryLedger.sim_limit_bytes` (settable; defaults to 4x used so the
sim reports healthy headroom). Tests assert the account sum lands within
10% of reported-used on this path — the same contract the device path is
expected to hold.

Reconciliation must *never* crash the metrics loop: :meth:`MemoryLedger.tick`
logs a failed pass and carries on, and a mismatch is a gauge
(``mem.unattributed``), not an error.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Tuple

from maggy_tpu.core import lockdebug

# CPU-sim runtime overhead: the deterministic stand-in for what a real
# runtime allocates beyond the registered accounts (XLA workspace, runtime
# scratch). 5% keeps the account sum within the 10% reconciliation contract.
SIM_UNATTRIBUTED_FRAC = 0.05

# headroom below this fraction of the pool counts the tick as a miss for
# the alert.hbm_headroom burn rule
DEFAULT_LOW_HEADROOM_PCT = 0.10


def device_memory() -> Optional[Tuple[int, int]]:
    """``(bytes_in_use, bytes_limit)`` summed over local devices, or None on
    a backend whose devices report no memory stats (the CPU backend). A TPU
    that reports none is an error: its numbers must never be simulated."""
    import jax

    used = limit = 0
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        b_used = stats.get("bytes_in_use")
        b_limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        if b_used is None or not b_limit:
            if dev.platform == "tpu":
                raise RuntimeError(
                    f"{dev} reports no memory_stats (got {stats!r}); refusing "
                    "to simulate device memory on a TPU backend"
                )
            return None
        used += int(b_used)
        limit += int(b_limit)
    return used, limit


class MemoryLedger:
    """Named byte accounts + reconciliation against reported device memory.

    Registration (``register``/``adjust``/``unregister``) happens from
    whatever thread owns the allocation (trainer setup, engine admit,
    prefetcher construction); :meth:`tick` runs on the owner's metrics
    thread — so the account table is lock-guarded.
    """

    def __init__(self, low_headroom_pct: float = DEFAULT_LOW_HEADROOM_PCT):
        self._lock = lockdebug.lock("memtrack._lock")
        self._accounts: Dict[str, int] = {}  # guarded-by: _lock
        self.low_headroom_pct = float(low_headroom_pct)
        # cumulative low-water tick counters (the burn-rule pair); written
        # only by the tick thread, read via snapshots
        self._headroom_ok = 0  # guarded-by: _lock
        self._headroom_miss = 0  # guarded-by: _lock
        # CPU-sim pool size; None = 4x reported-used (healthy headroom).
        # Pressure tests shrink this to drive headroom under the low-water
        # mark deterministically.
        self.sim_limit_bytes: Optional[int] = None

    # -------------------------------------------------------------- accounts

    def register(self, name: str, nbytes: int) -> None:
        """Set account ``name`` to ``nbytes`` (idempotent — re-registering
        an account replaces its figure; allocation sites call this on every
        (re)build so a reconfigure never double-counts)."""
        with self._lock:
            self._accounts[str(name)] = max(0, int(nbytes))

    def adjust(self, name: str, delta: int) -> None:
        """Add ``delta`` bytes to an account (clamped at zero)."""
        with self._lock:
            cur = self._accounts.get(str(name), 0)
            self._accounts[str(name)] = max(0, cur + int(delta))

    def unregister(self, name: str) -> None:
        with self._lock:
            self._accounts.pop(str(name), None)

    def accounts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._accounts)

    def accounted_bytes(self) -> int:
        with self._lock:
            return sum(self._accounts.values())

    # ----------------------------------------------------------- reconcile

    def reconcile(self) -> Dict[str, Any]:
        """One reconciliation pass: account sum vs reported device memory
        (or the deterministic CPU-sim). Pure read — no counters move."""
        accounted = self.accounted_bytes()
        reported = device_memory()
        if reported is not None:
            used, limit = reported
            source = "device"
        else:
            used = int(accounted * (1.0 + SIM_UNATTRIBUTED_FRAC))
            limit = self.sim_limit_bytes
            if limit is None:
                limit = max(1, used) * 4
            source = "sim"
        limit = max(int(limit), 1)
        used = min(int(used), limit)
        free = limit - used
        return {
            "source": source,
            "hbm_used": used,
            "hbm_free": free,
            "hbm_limit": limit,
            "headroom_pct": round(free / limit, 4),
            "accounted": accounted,
            "unattributed": max(0, used - accounted),
            "accounts": self.accounts(),
        }

    def tick(self, store=None, telemetry=None, now: Optional[float] = None) -> Dict[str, Any]:  # thread-entry — ticked from the owning scheduler/trainer metrics loop
        """Reconcile and export: gauges into the time-series ``store`` and
        the ``telemetry`` recorder, plus the cumulative headroom ok/miss
        counter pair the ``alert.hbm_headroom`` burn rule reads. Never
        raises — capacity observability must not sink the loop it rides."""
        try:
            rec = self.reconcile()
        except Exception:  # noqa: BLE001 - reconcile must never kill the tick
            logging.getLogger(__name__).exception("memory reconciliation failed")
            return {}
        with self._lock:
            if rec["headroom_pct"] < self.low_headroom_pct:
                self._headroom_miss += 1
            else:
                self._headroom_ok += 1
            ok, miss = self._headroom_ok, self._headroom_miss
        rec["headroom_ok"] = ok
        rec["headroom_miss"] = miss
        try:
            if telemetry is not None:
                telemetry.gauge("mem.hbm_used", float(rec["hbm_used"]))
                telemetry.gauge("mem.hbm_free", float(rec["hbm_free"]))
                telemetry.gauge("mem.headroom_pct", rec["headroom_pct"])
                telemetry.gauge("mem.unattributed", float(rec["unattributed"]))
            if store is not None and now is not None:
                gauges = {
                    "mem.hbm_used": float(rec["hbm_used"]),
                    "mem.hbm_free": float(rec["hbm_free"]),
                    "mem.headroom_pct": rec["headroom_pct"],
                    "mem.unattributed": float(rec["unattributed"]),
                }
                for name, nbytes in rec["accounts"].items():
                    gauges[f"mem.account.{name}"] = float(nbytes)
                store.ingest(
                    now,
                    gauges=gauges,
                    counters={"mem.headroom_ok": ok, "mem.headroom_miss": miss},
                )
        except Exception:  # noqa: BLE001 - export must never kill the tick
            pass
        return rec

    def snapshot(self) -> Dict[str, Any]:
        """SSTATS-ready view (no counter movement)."""
        rec = self.reconcile()
        with self._lock:
            rec["headroom_ok"] = self._headroom_ok
            rec["headroom_miss"] = self._headroom_miss
        return rec


def array_bytes(tree: Any) -> int:
    """Total bytes of every array-like leaf in a (possibly nested) pytree —
    the helper allocation sites use to size an account. Works without jax
    (plain dicts/lists of numpy arrays) so tests stay backend-free."""
    total = 0
    try:
        import jax

        for leaf in jax.tree_util.tree_leaves(tree):
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
        return total
    except Exception:  # noqa: BLE001 - fall through to the stdlib walk
        pass

    def walk(node) -> int:
        nbytes = getattr(node, "nbytes", None)
        if nbytes is not None:
            return int(nbytes)
        if isinstance(node, dict):
            return sum(walk(v) for v in node.values())
        if isinstance(node, (list, tuple)):
            return sum(walk(v) for v in node)
        return 0

    return walk(tree)
