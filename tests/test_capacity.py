"""Capacity observability (ISSUE 16): the HBM memory ledger's CPU-sim
reconciliation contract, KV page heat / fragmentation / eviction ordering,
prefix residency, the fleet capacity view reproduced offline through
``tools/metrics_query.py --merge``, alert-triggered profile capture, the
trace-attribution v2 back-compat guarantee, and the capacity-rule lint."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from maggy_tpu.serve.paging.allocator import BlockAllocator
from maggy_tpu.serve.prefix import PrefixIndex
from maggy_tpu.telemetry import memtrack
from maggy_tpu.telemetry.alerts import ALERT_FIRING, AlertEvaluator
from maggy_tpu.telemetry.histogram import LatencyHistogram
from maggy_tpu.telemetry.memtrack import MemoryLedger, array_bytes
from maggy_tpu.telemetry.profcap import ProfileCapture
from maggy_tpu.telemetry.recorder import Telemetry
from maggy_tpu.telemetry.timeseries import SeriesStore


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------- allocator heat & eviction


def test_heat_buckets_and_coldest_eviction_ordering():
    a = BlockAllocator(num_pages=17, page_size=4)
    pages = a.alloc(8)
    a.touch(pages[:4], gen=100)  # hot at gen 104 (age 4)
    a.touch(pages[4:6], gen=40)  # warm at gen 104 (age 64, boundary)
    # pages[6:8] never touched -> cold
    heat = a.heat_buckets(104)
    assert heat == {"hot": 4, "warm": 2, "cold": 2}
    # eviction ordering: never-touched pages first, then oldest stamps —
    # the known-cold pages are selected before anything recently read
    cold = a.coldest()
    assert cold[:2] == sorted(pages[6:8])
    assert set(cold[2:4]) == set(pages[4:6])
    assert set(cold[4:]) == set(pages[:4])
    assert a.coldest(3) == cold[:3]
    # touching a freed page is ignored (stale caller lists race release)
    a.release(pages[:1])
    a.touch(pages[:1], gen=200)
    a.check_invariants()
    assert pages[0] not in a.coldest()


def test_fragmentation_empty_full_and_fragmented_pools():
    a = BlockAllocator(num_pages=9, page_size=4)
    # all-free pool: one contiguous run, no fragmentation
    f = a.fragmentation()
    assert (f["free_runs"], f["largest_run"], f["frag_ratio"]) == (1, 8, 0.0)
    assert f["pages_pinned_shared"] == 0 and f["pages_reclaimable"] == 0
    # full pool: nothing free, ratio pinned at 0 (nothing to fragment)
    pages = a.alloc(8)
    f = a.fragmentation()
    assert (f["free_runs"], f["largest_run"], f["frag_ratio"]) == (0, 0, 0.0)
    assert f["pages_reclaimable"] == 8
    # checkerboard release: every free page is its own run
    a.release(pages[::2])
    f = a.fragmentation()
    assert f["free_runs"] == 4 and f["largest_run"] == 1
    assert f["frag_ratio"] == pytest.approx(0.75)
    a.check_invariants()
    # releasing the rest re-coalesces into one run
    a.release(pages[1::2])
    assert a.fragmentation()["frag_ratio"] == 0.0
    a.check_invariants()


# ------------------------------------------------------------- memory ledger


def test_ledger_sim_reconciliation_within_10pct(monkeypatch):
    monkeypatch.setattr(memtrack, "device_memory", lambda: None)
    ledger = MemoryLedger()
    ledger.register("params", 512 << 20)
    ledger.register("optimizer", 1 << 30)
    ledger.register("kv_pages", 256 << 20)
    ledger.register("prefetch", 32 << 20)
    rec = ledger.reconcile()
    assert rec["source"] == "sim"
    # the reconciliation contract: account sum within 10% of reported-used,
    # the gap surfaced as unattributed — never an error
    assert abs(rec["hbm_used"] - rec["accounted"]) <= 0.10 * rec["hbm_used"]
    assert rec["unattributed"] == rec["hbm_used"] - rec["accounted"]
    assert rec["hbm_used"] + rec["hbm_free"] == rec["hbm_limit"]
    assert rec["accounts"]["optimizer"] == 1 << 30
    # idempotent re-register replaces (reconfigure never double-counts)
    ledger.register("kv_pages", 128 << 20)
    assert ledger.accounts()["kv_pages"] == 128 << 20
    ledger.unregister("prefetch")
    assert "prefetch" not in ledger.accounts()


def test_ledger_tick_exports_and_headroom_counters(monkeypatch):
    monkeypatch.setattr(memtrack, "device_memory", lambda: None)
    ledger = MemoryLedger()
    ledger.register("kv_pages", 1000)
    store = SeriesStore()
    tel = Telemetry(worker="ledger-test")
    rec = ledger.tick(store=store, telemetry=tel, now=100.0)
    assert rec["headroom_ok"] == 1 and rec["headroom_miss"] == 0
    # shrink the sim pool: headroom collapses under the 10% low-water mark
    ledger.sim_limit_bytes = 1100
    rec = ledger.tick(store=store, telemetry=tel, now=101.0)
    assert rec["headroom_miss"] == 1 and rec["headroom_pct"] < 0.10
    # gauges + per-account series + the burn-rule counter pair all landed
    assert store.get("mem.headroom_pct").latest()[1] == rec["headroom_pct"]
    assert store.get("mem.account.kv_pages").latest()[1] == 1000.0
    assert store.get("mem.unattributed").latest()[1] == float(rec["unattributed"])
    assert store.get("mem.headroom_ok").kind == "counter"
    assert store.get("mem.headroom_miss").latest()[1] == 1
    snap = ledger.snapshot()
    assert snap["headroom_ok"] == 1 and snap["headroom_miss"] == 1


def test_ledger_tick_never_raises(monkeypatch):
    ledger = MemoryLedger()
    ledger.register("params", 100)

    class _BoomStore:
        def ingest(self, *a, **k):
            raise RuntimeError("boom")

    # a broken export sink is swallowed; the reconcile still returns
    rec = ledger.tick(store=_BoomStore(), telemetry=None, now=1.0)
    assert rec["accounted"] == 100
    # a broken device probe inside reconcile degrades to {} — never a raise
    def _boom():
        raise RuntimeError("probe died")

    monkeypatch.setattr(memtrack, "device_memory", _boom)
    assert ledger.tick(store=None, telemetry=None, now=2.0) == {}


def test_array_bytes_walks_plain_trees():
    tree = {
        "a": np.zeros((4, 8), np.float32),
        "b": [np.zeros(16, np.int32), (np.zeros(2, np.float64),)],
        "c": "not-an-array",
    }
    assert array_bytes(tree) == 4 * 8 * 4 + 16 * 4 + 2 * 8
    assert array_bytes(None) == 0


# ----------------------------------------------------------- prefix residency


def test_prefix_residency_stats_rank_by_hits():
    idx = PrefixIndex()
    idx.bytes_per_token = 100
    p1 = list(range(1, 17))
    p2 = list(range(40, 52))
    idx.insert(0, p1, gen=0)
    idx.insert(1, p2, gen=2)
    for g in (5, 6, 7):
        assert idx.match(p1, gen=g) is not None
    res = idx.residency_stats(gen=10, top=4)
    assert res["resident_prefixes"] == 2
    assert res["resident_tokens"] == len(p1) + len(p2)
    assert res["resident_bytes"] == (len(p1) + len(p2)) * 100
    top = res["top"]
    assert top[0]["slot"] == 0 and top[0]["hits"] == 3
    assert top[0]["bytes"] == len(p1) * 100
    # digests are content-stable: same tokens, same digest, cross-process
    assert top[0]["digest"] == PrefixIndex.digest(tuple(p1))
    assert len(top[0]["digest"]) == 8


# --------------------------------------------------- alert-triggered profcap


def test_profcap_fires_once_on_injected_pressure(tmp_path, monkeypatch):
    """Acceptance: injected HBM pressure drives the real burn rule; the
    controller arms exactly ONE bounded capture whose dump carries the
    alerted series tails."""
    monkeypatch.delenv("MAGGY_TPU_PROFCAP", raising=False)
    monkeypatch.setattr(memtrack, "device_memory", lambda: None)
    store = SeriesStore()
    tel = Telemetry(worker="profcap-pressure-test")
    ledger = MemoryLedger()
    ledger.register("params", 900 << 20)
    ledger.sim_limit_bytes = 1 << 30  # ~7.7% headroom: every tick a miss
    ev = AlertEvaluator(store, tel, scope="worker")
    pc = ProfileCapture(dump_dir=str(tmp_path))
    t0 = 50_000.0
    fired = []
    for tick in range(60):
        now = t0 + tick
        ledger.tick(store=store, telemetry=tel, now=now)
        path = pc.tick(ev.evaluate(now), now=now)
        if path:
            fired.append(path)
    assert len(fired) == 1  # fires once; the still-firing alert never re-arms
    with open(os.path.join(fired[0], "capture.json"), encoding="utf-8") as f:
        payload = json.load(f)
    assert payload["reason"] == "alert:alert.hbm_headroom"
    assert payload["trigger"]["alert"] == "alert.hbm_headroom"
    assert payload["profiler"] in ("fallback", "jax.profiler")
    assert any(a["alert"] == "alert.hbm_headroom" for a in payload["alerts"])
    # the dump is self-describing: tails of the series that tripped the rule
    assert any("mem.headroom_miss" in k for k in payload["alert_series"])
    assert payload["threads"]
    snap = pc.snapshot()
    assert snap["captures"] == 1 and snap["paths"] == fired


def test_profcap_cooldown_and_capture_cap(tmp_path, monkeypatch):
    monkeypatch.delenv("MAGGY_TPU_PROFCAP", raising=False)
    trans = [{"event": ALERT_FIRING, "alert": "alert.fragmentation"}]
    pc = ProfileCapture(dump_dir=str(tmp_path), cooldown_s=100.0, max_captures=2)
    assert pc.tick(trans, now=1000.0) is not None
    assert pc.tick(trans, now=1050.0) is None  # inside cooldown
    assert pc.tick(trans, now=1200.0) is not None  # cooldown elapsed
    assert pc.tick(trans, now=2000.0) is None  # over the per-process cap
    assert pc.snapshot()["captures"] == 2
    # unwatched alerts and resolve transitions never arm
    assert pc.tick([{"event": ALERT_FIRING, "alert": "alert.queue_depth_high"}],
                   now=3000.0) is None
    assert pc.tick([{"event": "alert.resolved", "alert": "alert.fragmentation"}],
                   now=4000.0) is None


def test_profcap_env_flag_disables(tmp_path, monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_PROFCAP", "0")
    pc = ProfileCapture(dump_dir=str(tmp_path))
    trans = [{"event": ALERT_FIRING, "alert": "alert.hbm_headroom"}]
    assert pc.tick(trans, now=1.0) is None
    snap = pc.snapshot()
    assert snap["captures"] == 0 and snap["enabled"] is False
    assert os.listdir(str(tmp_path)) == []


# -------------------------------------------- fleet capacity view & offline


def _capacity_replica_stats(h, resid_bytes, resid_count, headroom, heat,
                            frag_ratio, done):
    return {
        "num_slots": 4, "active_slots": 2, "queue_depth": 1,
        "tokens_per_sec": 120.0, "requests_done": done,
        "ttft_ms_p50": h.percentile(0.5), "ttft_ms_p95": h.percentile(0.95),
        "latency": {"ttft_ms": h.to_dict()},
        "slo_ok": 10, "slo_miss": 0,
        "paging": {
            "paged": True, "pages_total": 32, "pages_free": 10,
            "pages_shared": 0,
            "heat": dict(heat),
            "fragmentation": {
                "free_runs": 2, "largest_run": 5, "frag_ratio": frag_ratio,
            },
        },
        "memory": {"headroom_pct": headroom},
        "prefix_residency": {
            "resident_prefixes": resid_count,
            "resident_tokens": resid_bytes // 100,
            "resident_bytes": resid_bytes,
            "top": [{
                "digest": "abcd1234", "slot": 0,
                "tokens": resid_bytes // 100, "bytes": resid_bytes, "hits": 3,
            }],
        },
    }


def test_fleet_capacity_view_and_offline_merge(tmp_path, capsys):
    """Acceptance: a 2-replica fleet's residency/headroom view is reproduced
    EXACTLY from per-replica METRICS exports via metrics_query --merge."""
    from maggy_tpu.serve.fleet import Router, RouterConfig
    from tests.test_serve_fleet import fake_replica

    mq = load_tool("metrics_query")
    tel = Telemetry(worker="fleet-capacity-test")
    router = Router(
        [fake_replica(0), fake_replica(1)],
        config=RouterConfig(),
        telemetry_recorder=tel,
    )
    hists = [LatencyHistogram(), LatencyHistogram()]
    resid = [4096, 6144]
    headroom = [0.42, 0.17]
    frags = [0.25, 0.6]
    t0 = 42_000.0
    for tick in range(12):
        for r in range(2):
            hists[r].observe(20.0)
            router._stats_cache[r] = _capacity_replica_stats(
                hists[r], resid[r], r + 1, headroom[r],
                {"hot": 3 + r, "warm": 2, "cold": 1}, frags[r], tick * 2,
            )
        router._sample_metrics(t0 + tick)

    # FSTATS capacity view: sums / fleet-min headroom / fleet-max frag
    cap = router._fleet_stats()["capacity"]
    assert cap["resident_bytes"] == sum(resid)
    assert cap["resident_prefixes"] == 3
    assert cap["headroom_pct"] == pytest.approx(min(headroom))
    assert cap["fragmentation"] == pytest.approx(max(frags))
    assert (cap["pages_hot"], cap["pages_warm"], cap["pages_cold"]) == (7, 4, 2)
    # same digest on both replicas -> ONE anchor, bytes/hits summed
    tops = cap["top_prefixes"]
    assert len(tops) == 1
    assert tops[0]["bytes"] == sum(resid) and tops[0]["hits"] == 6
    assert sorted(tops[0]["replicas"]) == [0, 1]

    # offline reproduction from the exported per-replica stores
    body = router._metrics_body()
    paths = []
    for k in sorted(body["replicas"]):
        p = os.path.join(str(tmp_path), f"r{k}.json")
        with open(p, "w") as f:
            json.dump(body["replicas"][k], f)
        paths.append(p)
    fleet_store = SeriesStore.from_snapshot(body["metrics"])
    now = t0 + 11
    assert mq.main(["--merge", *paths, "--name", "serve.prefix_resident_bytes",
                    "--window", "30", "--now", str(now)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["kind"] == "gauge" and out["sum"] == float(sum(resid))
    assert fleet_store.get("serve.prefix_resident_bytes").latest()[1] == float(
        sum(resid)
    )
    assert mq.main(["--merge", *paths, "--name", "mem.headroom_pct",
                    "--window", "30", "--now", str(now)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["min"] == pytest.approx(min(headroom))
    assert fleet_store.get("mem.headroom_pct").latest()[1] == pytest.approx(
        min(headroom)
    )


# ------------------------------------------------- attribution v2 back-compat


def test_attribution_v2_reads_v1_jsonl(tmp_path):
    from maggy_tpu.telemetry import attribution

    tdir = os.path.join(str(tmp_path), "telemetry")
    os.makedirs(tdir)

    def ev(name, ts, trace, **attrs):
        return {"kind": "event", "name": name, "ts": ts, "worker": "serve",
                "trace": trace, "attrs": attrs}

    records = [
        # v1-era request: no capacity attrs anywhere
        ev("req.queued", 100.0, "t1", rid="r1"),
        ev("req.admitted", 100.1, "t1", rid="r1"),
        ev("req.finished", 100.5, "t1", rid="r1", state="done"),
        # v2 request: headroom stamped at admit, page peak at finish
        ev("req.queued", 200.0, "t2", rid="r2"),
        ev("req.admitted", 200.1, "t2", rid="r2", headroom_at_admit=0.33),
        ev("req.finished", 200.6, "t2", rid="r2", state="done",
           pages_held_peak=5),
    ]
    with open(os.path.join(tdir, "worker_1.jsonl"), "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")

    out = attribution.analyze(str(tmp_path))
    assert out["schema"] == "maggy-tpu.trace-attribution.v2"
    by = {r["trace"]: r for r in out["requests"]}
    # back-compat: v1 rows parse cleanly, new fields read as None
    assert by["t1"]["state"] == "done"
    assert by["t1"]["pages_held_peak"] is None
    assert by["t1"]["headroom_at_admit"] is None
    assert by["t2"]["pages_held_peak"] == 5
    assert by["t2"]["headroom_at_admit"] == 0.33


# ------------------------------------------------------- capacity-rule lint


def test_capacity_rules_lint_catches_miswiring():
    import types

    ctn = load_tool("check_telemetry_names")

    class R:
        windows = ((30.0, 2.0), (5.0, 2.0))

        def __init__(self, **kw):
            self.__dict__.update(kw)

    good_rules = (
        R(name="alert.hbm_headroom", kind="burn_rate",
          ok_metric="mem.headroom_ok", miss_metric="mem.headroom_miss"),
        R(name="alert.fragmentation", kind="threshold",
          metric="serve.fragmentation"),
    )
    assert ctn.check_capacity_rules(types.SimpleNamespace(RULES=good_rules)) == []
    # deleting a rule silently disarms profcap -> the lint names it
    missing = types.SimpleNamespace(RULES=good_rules[:1])
    assert any("alert.fragmentation" in v
               for v in ctn.check_capacity_rules(missing))
    # re-pointing the burn pair at another series is flagged field-by-field
    repointed = types.SimpleNamespace(RULES=(
        R(name="alert.hbm_headroom", kind="burn_rate",
          ok_metric="serve.slo_ok", miss_metric="mem.headroom_miss"),
        good_rules[1],
    ))
    assert any("ok_metric" in v for v in ctn.check_capacity_rules(repointed))
    # a single-window burn rule loses the fast-resolve property
    slow = R(name="alert.hbm_headroom", kind="burn_rate",
             ok_metric="mem.headroom_ok", miss_metric="mem.headroom_miss")
    slow.windows = ((30.0, 2.0),)
    one_window = types.SimpleNamespace(RULES=(slow, good_rules[1]))
    assert any("2 windows" in v for v in ctn.check_capacity_rules(one_window))
    # and the checked-in registry itself is clean
    assert ctn.check_capacity_rules(ctn.load_alerts(REPO)) == []


# -------------------------------------------------- engine capacity surfaces


def test_engine_registers_accounts_and_capacity_surfaces():
    import jax
    import jax.numpy as jnp

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.sharding import unbox
    from maggy_tpu.serve import Engine, Request, SamplingParams

    cfg = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = unbox(
        Decoder(cfg).init(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    engine = Engine(cfg, params, num_slots=2)
    acc = engine.memory.accounts()
    assert acc["params"] > 0 and acc["kv_pages"] > 0 and acc["workspace"] > 0
    assert engine.prefix_index.bytes_per_token >= 1
    rec = engine.memory.reconcile()
    if rec["source"] == "sim":  # the CPU tier-1 path
        assert rec["unattributed"] <= 0.10 * rec["hbm_used"]
    slot, _ = engine.admit(
        Request(prompt=[3, 1, 4, 1, 5, 9, 2, 6], params=SamplingParams(max_new=4))
    )
    assert engine.pages_held_peak(slot) >= 1
    ps = engine.paging_stats
    assert ps["heat"]["hot"] >= 1
    assert 0.0 <= ps["fragmentation"]["frag_ratio"] <= 1.0
    res = engine.prefix_stats["prefix_residency"]
    assert res["resident_prefixes"] == 1 and res["resident_bytes"] > 0
    engine.release(slot)
    assert engine.pages_held_peak(slot) == 0
    assert engine.prefix_stats["prefix_residency"]["resident_prefixes"] == 0
    engine.allocator.check_invariants()
