"""Time-series store, alert rules, and the recompile sentinel (ISSUE 13).

Covers the windowed ring-buffer series (`telemetry/timeseries.py`), the
declarative alert registry + evaluator (`telemetry/alerts.py`), the
fleet-merge reproducibility contract (`tools/metrics_query.py` equals the
router's fleet store), the chaos acceptance (degraded replica -> fleet
burn-rate alert -> monitor ALERTS line -> resolve; out-of-band reconfigure
trips the sentinel), the registry lints, the flight-recorder alert
enrichment, and concurrent sink rotation.
"""

import importlib.util
import json
import os
import sys
import threading
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from maggy_tpu.telemetry import timeseries
from maggy_tpu.telemetry.alerts import (
    ALERT_FIRING,
    ALERT_RESOLVED,
    BY_NAME,
    AlertEvaluator,
    RecompileSentinel,
)
from maggy_tpu.telemetry.histogram import LatencyHistogram
from maggy_tpu.telemetry.recorder import Telemetry
from maggy_tpu.telemetry.timeseries import (
    Series,
    SeriesStore,
    merge_windowed_percentile,
)


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


class _EventTap:
    """Minimal recorder stand-in capturing alert transition events."""

    def __init__(self):
        self.events = []

    def event(self, name, trace=None, **attrs):
        self.events.append((name, attrs))

    def gauge(self, *a, **k):
        pass

    def count(self, *a, **k):
        pass

    def names(self, kind):
        return [n for n, _ in self.events if n == kind]


# ------------------------------------------------------------ series queries


def test_counter_delta_rate_and_reset_clamp():
    s = Series("c", "counter")
    for i in range(20):
        s.append(1000.0 + i, float(i * 5))
    # window of 10s back from ts=1019: base is the point at ts<=1009 (45)
    assert s.delta(10.0, 1019.0) == 95.0 - 45.0
    assert s.rate(10.0, 1019.0) == pytest.approx(5.0)
    # ring shorter than the window: difference against the oldest point
    assert s.delta(1e6, 1019.0) == 95.0
    # counter reset (process restart) clamps to zero, never negative
    s.append(1020.0, 0.0)
    assert s.delta(5.0, 1020.0) == 0.0


def test_hist_series_windowed_percentile_is_window_only():
    s = Series("h", "hist")
    h = LatencyHistogram()
    # old regime: 100 fast observations, then 10 slow ones recently
    for _ in range(100):
        h.observe(5.0)
    s.append(1000.0, h.to_dict())
    for i in range(10):
        h.observe(500.0)
        s.append(1010.0 + i, h.to_dict())
    # lifetime view is dominated by the fast old samples ...
    assert LatencyHistogram.from_dict(s.latest()[1]).percentile(0.5) < 10.0
    # ... the windowed view sees only the recent slow ones
    p50 = s.percentile(0.5, 8.0, 1019.0)
    assert p50 is not None and p50 > 100.0
    att = s.attainment(100.0, 8.0, 1019.0)
    assert att == pytest.approx(0.0, abs=0.01)


def test_store_sample_snapshot_roundtrip_and_version_guard():
    tel = Telemetry(worker="ts-test")
    tel.gauge("serve.queue_depth", 3.0)
    tel.count("serve.requests_done", 7)
    tel.histogram("serve.ttft_ms", 12.5)
    store = SeriesStore()
    store.sample(tel, 2000.0)
    tel.gauge("serve.queue_depth", 5.0)
    tel.count("serve.requests_done", 2)
    store.sample(tel, 2001.0)

    snap = store.snapshot()
    back = SeriesStore.from_snapshot(snap)
    assert back.names() == store.names()
    assert back.get("serve.queue_depth").latest()[1] == 5.0
    assert back.get("serve.requests_done").delta(10.0, 2001.0) == 2.0
    assert back.get("serve.ttft_ms").percentile(0.5, 10.0, 2001.0) is not None
    # versioned form: a future schema refuses rather than misreads
    with pytest.raises(ValueError, match="newer"):
        SeriesStore.from_snapshot(dict(snap, v=timeseries.SCHEMA_VERSION + 1))
    # tick gating: same second -> no second sample
    assert store.maybe_sample(tel, 2001.2) is False
    assert store.maybe_sample(tel, 2002.5) is True


def test_merge_of_windowed_equals_windowed_of_merge():
    """The reproducibility contract: per-replica windowed distributions
    merged == the fleet-aggregate series (merged-then-appended) windowed,
    when every append shares the tick timestamp."""
    replica_stores = [SeriesStore(), SeriesStore()]
    fleet = SeriesStore()
    hists = [LatencyHistogram(), LatencyHistogram()]
    t0 = 3000.0
    for tick in range(40):
        now = t0 + tick
        for r, h in enumerate(hists):
            for _ in range(3):
                h.observe(4.0 * (r + 1) + tick * 0.3)
            replica_stores[r].ingest(now, hists={"serve.ttft_ms": h.to_dict()})
        merged = hists[0].merge(hists[1])
        fleet.ingest(now, hists={"serve.ttft_ms": merged.to_dict()})
    now = t0 + 39
    for window in (5.0, 15.0, 30.0):
        for q in (0.5, 0.95):
            via_merge = merge_windowed_percentile(
                replica_stores, "serve.ttft_ms", q, window, now
            )
            via_fleet = fleet.get("serve.ttft_ms").percentile(q, window, now)
            assert via_merge == pytest.approx(via_fleet), (window, q)


# ------------------------------------------------------------------- alerts


def test_threshold_rule_for_duration_and_transitions():
    tap = _EventTap()
    store = SeriesStore()
    ev = AlertEvaluator(
        store, tap, scope="worker", rules=(BY_NAME["alert.queue_depth_high"],)
    )
    t0 = 5000.0
    s = store.series("serve.queue_depth", "gauge")
    # over threshold but shorter than for_s=3 -> pending, not firing
    for i in range(3):
        s.append(t0 + i, 100.0)
        ev.evaluate(t0 + i)
    assert ev.firing() == []
    s.append(t0 + 3, 100.0)
    fired = ev.evaluate(t0 + 3)
    assert [t["alert"] for t in fired] == ["alert.queue_depth_high"]
    assert ev.firing()[0]["severity"] == "warning"
    assert tap.names(ALERT_FIRING)
    # a one-tick dip resets the for-duration clock AND resolves
    s.append(t0 + 4, 1.0)
    resolved = ev.evaluate(t0 + 4)
    assert resolved and resolved[0]["event"] == ALERT_RESOLVED
    assert ev.firing() == [] and tap.names(ALERT_RESOLVED)
    # stale series (no samples within stale_s) never fires
    ev2 = AlertEvaluator(
        store, None, scope="worker", rules=(BY_NAME["alert.queue_depth_high"],)
    )
    s.append(t0 + 5, 100.0)
    for dt in (5, 6, 7, 8):
        ev2.evaluate(t0 + 100 + dt)
    assert ev2.firing() == []


def test_burn_rate_multiwindow_fire_and_resolve():
    tap = _EventTap()
    store = SeriesStore()
    ev = AlertEvaluator(
        store, tap, scope="worker", rules=(BY_NAME["alert.ttft_slo_burn"],)
    )
    t0 = 6000.0
    ok, miss = 0, 0
    tick = 0
    # healthy: 35 ticks of pure attainment -> never fires
    for _ in range(35):
        ok += 10
        store.ingest(t0 + tick, counters={"serve.slo_ok": ok, "serve.slo_miss": miss})
        assert ev.evaluate(t0 + tick) == []
        tick += 1
    # degrade: 40% miss rate; both the 30s and 5s windows blow their
    # 2x-budget factor within a couple of evaluation ticks
    fired_at = None
    for i in range(6):
        ok += 6
        miss += 4
        store.ingest(t0 + tick, counters={"serve.slo_ok": ok, "serve.slo_miss": miss})
        if ev.evaluate(t0 + tick) and fired_at is None:
            fired_at = i
        tick += 1
    assert fired_at is not None and fired_at <= 5
    assert ev.firing()[0]["alert"] == "alert.ttft_slo_burn"
    assert ev.firing()[0]["severity"] == "critical"
    # recover: the short window drains within ~5 ticks and resolves the page
    resolved_at = None
    for i in range(12):
        ok += 10
        store.ingest(t0 + tick, counters={"serve.slo_ok": ok, "serve.slo_miss": miss})
        trans = ev.evaluate(t0 + tick)
        if any(t["event"] == ALERT_RESOLVED for t in trans):
            resolved_at = i
        tick += 1
    assert resolved_at is not None
    assert ev.firing() == []
    assert tap.names(ALERT_FIRING) and tap.names(ALERT_RESOLVED)


def test_recompile_sentinel_warm_expected_and_trip():
    tap = _EventTap()
    store = SeriesStore()
    dumps = []
    wd = types.SimpleNamespace(dump=lambda reason: dumps.append(reason))
    sent = RecompileSentinel(store, tap, steady=("decode", "admit"))
    t0 = 7000.0
    # first observation baselines silently (even at a nonzero count)
    assert sent.observe({"decode": 0, "prefill": 1}, t0, wd) == []
    # the warm first compile (0 -> 1) is silent
    assert sent.observe({"decode": 1, "prefill": 1}, t0 + 1, wd) == []
    # a declared reconfigure re-baselines silently
    sent.expect()
    assert sent.observe({"decode": 2, "prefill": 1}, t0 + 2, wd) == []
    # prefill is a bucketed ladder: new buckets compile by design, no alert
    assert sent.observe({"decode": 2, "prefill": 5}, t0 + 3, wd) == []
    assert not dumps and not tap.names(ALERT_FIRING)
    # the unexplained retrace past a warm baseline trips, dumps, emits
    assert sent.observe({"decode": 3, "prefill": 5}, t0 + 4, wd) == ["decode"]
    firing = sent.firing(t0 + 5)
    assert firing and firing[0]["alert"] == "alert.recompile"
    assert firing[0]["program"] == "decode"
    assert dumps == ["alert:alert.recompile:decode"]
    assert tap.names(ALERT_FIRING)
    # every count landed as a compile.<prog> series
    assert store.get("compile.decode").latest()[1] == 3.0
    assert store.get("compile.prefill").latest()[1] == 5.0
    # the hold window expires -> auto-resolve with an event
    assert sent.firing(t0 + 4 + sent.HOLD_S + 1) == []
    assert tap.names(ALERT_RESOLVED)


def test_flightrec_dump_embeds_firing_alerts_and_series_tails():
    from maggy_tpu.telemetry import flightrec

    store = SeriesStore()
    ev = AlertEvaluator(
        store, None, scope="worker", rules=(BY_NAME["alert.queue_depth_high"],)
    )
    t0 = 8000.0
    s = store.series("serve.queue_depth", "gauge")
    for i in range(5):
        s.append(t0 + i, 200.0)
        ev.evaluate(t0 + i)
    assert ev.firing()
    wd = flightrec.Watchdog(stall_s=60.0, dump_dir=None)
    wd.dump("unit-test")
    payload = wd.last_dump
    assert any(a["alert"] == "alert.queue_depth_high" for a in payload["alerts"])
    tail = payload["alert_series"]["worker/serve.queue_depth"]
    assert tail and tail[-1] == [t0 + 4, 200.0]


# --------------------------------------------------- registry + lint checks


def test_every_metric_has_a_unit():
    from maggy_tpu.telemetry import metrics as M

    assert set(M.UNITS) >= set(M.ALL)
    assert {u for u in M.UNITS.values()} <= set(M.VALID_UNITS)


@pytest.mark.parametrize("name,unit", [
    ("moe.slots", "count"), ("moe.slots_dropped", "count"), ("moe.load_max_over_mean", "ratio"),
    ("moe.rows_visited_share", "ratio"), ("moe.combine_rows_share", "ratio"),
])
def test_expert_share_gauges_are_registered_with_their_units(name, unit):
    """What ``Trainer.fit`` publishes for a model with expert share layers."""
    from maggy_tpu.telemetry import metrics as M

    assert name in M.GAUGES and M.UNITS[name] == unit


def test_lint_units_and_alert_registry_self_checks():
    mod = load_tool("check_telemetry_names")
    registry = mod.load_registry(REPO)
    alerts = mod.load_alerts(REPO)
    assert mod.check_units(registry) == []
    assert mod.check_alert_registry(alerts, registry) == []

    # a registered metric without a unit is flagged
    broken = types.SimpleNamespace(
        ALL=registry.ALL | {"serve.mystery"},
        UNITS=dict(registry.UNITS, bogus="ms"),
        VALID_UNITS=registry.VALID_UNITS,
    )
    out = mod.check_units(broken)
    assert any("serve.mystery" in v for v in out)
    assert any("bogus" in v for v in out)

    # malformed rules are flagged structurally
    bad_rules = types.SimpleNamespace(
        RULES=(
            alerts.Rule(name="no_prefix", summary="x", kind="threshold"),
            alerts.Rule(
                name="alert.bad_burn", summary="x", kind="burn_rate", objective=2.0
            ),
            alerts.Rule(
                name="alert.ghost_metric",
                summary="x",
                kind="threshold",
                metric="serve.not_registered_anywhere",
            ),
        ),
        KINDS=alerts.KINDS,
        SEVERITIES=alerts.SEVERITIES,
        SCOPES=alerts.SCOPES,
        ALERT_FIRING=alerts.ALERT_FIRING,
        ALERT_RESOLVED=alerts.ALERT_RESOLVED,
    )
    out = mod.check_alert_registry(bad_rules, registry)
    assert any("must start with 'alert.'" in v for v in out)
    assert any("objective" in v for v in out)
    assert any("needs a metric" in v or "ok/miss" in v for v in out)
    assert any("unregistered metric" in v for v in out)

    # a typo'd alert literal in source is caught; registered names pass
    names = {r.name for r in alerts.RULES} | {alerts.ALERT_FIRING}
    bad_src = 'tel.event("alert.firing", alert="alert.definitely_a_typo")\n'
    hits = mod.check_source(bad_src, "x.py", registry, names)
    assert any("definitely_a_typo" in msg for _, msg in hits)
    ok_src = 'tel.event("alert.firing", alert="alert.recompile")\n'
    names |= {"alert.recompile"}
    assert mod.check_source(ok_src, "x.py", registry, names) == []
    # 3-arg form (no alert validation) stays supported
    assert mod.check_source(ok_src, "x.py", registry) == []


def test_telemetry_names_lint_clean():
    mod = load_tool("check_telemetry_names")
    assert mod.main([]) == 0


# ------------------------------------------------- sink rotation concurrency


def test_sink_rotation_with_concurrent_writers(tmp_env, tmp_path):
    """N writer threads through one rotating sink: no dropped, duplicated,
    or torn records, and per-thread order survives rotation + the
    oldest-first segment fold."""
    from maggy_tpu.telemetry.export import load_records
    from maggy_tpu.telemetry.sink import JsonlSink

    tdir = os.path.join(str(tmp_path), "exp", "telemetry")
    os.makedirs(tdir)
    path = os.path.join(tdir, "worker_cc.jsonl")
    n_threads, n_records = 4, 150
    # small segments force many rotations mid-traffic; enough segment slots
    # that nothing ages out, so every record must survive
    sink = JsonlSink(path, env=tmp_env, max_bytes=2048, max_segments=64)

    def writer(t):
        for i in range(n_records):
            sink.write(
                [{"kind": "event", "name": "e", "ts": float(i), "worker": str(t),
                  "attrs": {"thread": t, "seq": i}}]
            )

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    sink.close()

    recs = load_records(tmp_env, os.path.join(str(tmp_path), "exp"))["worker_cc"]
    assert len(recs) == n_threads * n_records
    by_thread = {}
    for r in recs:
        by_thread.setdefault(r["attrs"]["thread"], []).append(r["attrs"]["seq"])
    for t in range(n_threads):
        assert by_thread[t] == list(range(n_records)), f"thread {t} order broken"


# -------------------------------------------- chaos acceptance: fleet alert


def _replica_stats(h, ok, miss, done, qd=1):
    return {
        "num_slots": 4, "active_slots": 2, "queue_depth": qd,
        "tokens_per_sec": 120.0, "requests_done": done,
        "ttft_ms_p50": h.percentile(0.5), "ttft_ms_p95": h.percentile(0.95),
        "latency": {"ttft_ms": h.to_dict()},
        "slo_ok": ok, "slo_miss": miss,
    }


def test_fleet_burn_alert_fires_on_degraded_replica_and_resolves():
    """Chaos acceptance: one replica of two degrades its TTFT -> the
    fleet-scope burn-rate alert fires within an evaluation window, lands in
    alert.* events, renders on the monitor ALERTS line, and resolves once
    the replica recovers."""
    from maggy_tpu.monitor import _alert_lines, render_status
    from maggy_tpu.serve.fleet import Router, RouterConfig
    from tests.test_serve_fleet import fake_replica

    tel = Telemetry(worker="fleet-alert-test")
    router = Router(
        [fake_replica(0), fake_replica(1)],
        config=RouterConfig(),
        telemetry_recorder=tel,
    )
    hists = [LatencyHistogram(), LatencyHistogram()]
    ok = [0, 0]
    miss = [0, 0]
    done = [0, 0]
    t0 = 9000.0
    tick = 0

    def advance(degraded=None):
        nonlocal tick
        for r in range(2):
            if r == degraded:
                hists[r].observe(900.0)  # injected TTFT degradation
                ok[r] += 2
                miss[r] += 8
            else:
                hists[r].observe(20.0)
                ok[r] += 10
            done[r] += 5
            router._stats_cache[r] = _replica_stats(
                hists[r], ok[r], miss[r], done[r]
            )
        router._sample_metrics(t0 + tick)
        tick += 1

    # healthy steady state: no alert
    for _ in range(35):
        advance()
    assert router.alerts.firing() == []
    # degrade replica 1; fleet-scope burn fires within a handful of ticks
    fired_after = None
    for i in range(6):
        advance(degraded=1)
        if router.alerts.firing() and fired_after is None:
            fired_after = i
    assert fired_after is not None and fired_after <= 5
    names = [a["alert"] for a in router.alerts.firing()]
    assert "alert.ttft_slo_burn" in names
    assert all(a["scope"] == "fleet" for a in router.alerts.firing())
    # the transition landed in the telemetry journal as an alert.* event
    flight = [r.get("name") for r in list(tel.flight)]
    assert ALERT_FIRING in flight

    # SSTATS carries the firing set + trends; the monitor renders both
    stats = router._fleet_stats()
    assert any(a["alert"] == "alert.ttft_slo_burn" for a in stats["alerts"])
    assert stats["trends"].get("serve.queue_depth")
    lines = _alert_lines(stats, 78)
    assert lines and "ALERTS[" in lines[0] and "ttft_slo_burn(!)" in lines[0]
    panel = render_status(router._on_status({}))
    assert "ALERTS[" in panel and "ttft_slo_burn(!)" in panel

    # recovery: the short window drains, the burn alert resolves, and the
    # brownout ladder (stepped up while the burn fired) walks back to 0 one
    # level per recover_s — only then does alert.brownout clear too
    for _ in range(30):
        advance()
    assert router.brownout.level() == 0
    assert router.alerts.firing() == []
    flight = [r.get("name") for r in list(tel.flight)]
    assert ALERT_RESOLVED in flight

    # the exported snapshots reproduce the fleet percentile offline
    body = router._metrics_body()
    stores = [
        SeriesStore.from_snapshot(body["replicas"][k]) for k in sorted(body["replicas"])
    ]
    fleet_store = SeriesStore.from_snapshot(body["metrics"])
    now = t0 + tick - 1
    reproduced = merge_windowed_percentile(stores, "serve.ttft_ms", 0.95, 30.0, now)
    direct = fleet_store.get("serve.ttft_ms").percentile(0.95, 30.0, now)
    assert reproduced == pytest.approx(direct)


def test_metrics_query_cli_reproduces_fleet_percentile(tmp_path, capsys):
    mq = load_tool("metrics_query")
    stores = [SeriesStore(), SeriesStore()]
    fleet = SeriesStore()
    hists = [LatencyHistogram(), LatencyHistogram()]
    t0 = 10_000.0
    for tick in range(40):
        now = t0 + tick
        for r, h in enumerate(hists):
            h.observe(10.0 * (r + 1) + tick)
            stores[r].ingest(now, hists={"serve.ttft_ms": h.to_dict()},
                             counters={"serve.requests_done": tick * 2})
        fleet.ingest(now, hists={"serve.ttft_ms": hists[0].merge(hists[1]).to_dict()})
    paths = []
    for r, st in enumerate(stores):
        p = os.path.join(str(tmp_path), f"r{r}.json")
        with open(p, "w") as f:
            json.dump(st.snapshot(), f)
        paths.append(p)
    now = t0 + 39
    expected = fleet.get("serve.ttft_ms").percentile(0.95, 30.0, now)

    assert mq.main(["--merge", *paths, "--name", "serve.ttft_ms",
                    "--q", "0.95", "--window", "30", "--now", str(now)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["merged_from"] == 2
    assert out["p95"] == pytest.approx(expected)

    # METRICS-reply unwrapping + counter rate on a single store
    reply = os.path.join(str(tmp_path), "reply.json")
    with open(reply, "w") as f:
        json.dump({"scope": "worker", "metrics": stores[0].snapshot()}, f)
    assert mq.main([reply, "--name", "serve.requests_done",
                    "--window", "30", "--now", str(now)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["kind"] == "counter" and out["delta"] == 60.0
    assert mq.main([reply, "--list"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert {s["name"] for s in out["series"]} == {
        "serve.ttft_ms", "serve.requests_done"
    }


# ----------------------------------- chaos acceptance: out-of-band retrace


@pytest.mark.slow
def test_scheduler_sentinel_trips_on_out_of_band_reconfigure():
    """An engine reconfigure through the scheduler seam re-baselines the
    sentinel; the same geometry change injected OUTSIDE the seam (the
    chaos case: something recompiles decode behind the scheduler's back)
    trips alert.recompile onto SSTATS and the monitor ALERTS line."""
    import jax
    import jax.numpy as jnp

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.monitor import _alert_lines
    from maggy_tpu.parallel.sharding import unbox
    from maggy_tpu.serve import Engine, Request, SamplingParams, Scheduler

    cfg = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = unbox(
        Decoder(cfg).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    )
    engine = Engine(cfg, params, num_slots=2)
    sched = Scheduler(engine)  # not started: tick driven by hand
    # warm decode so the sentinel has a nonzero baseline
    slot, _ = engine.admit(Request(prompt=[1, 2, 3], params=SamplingParams(max_new=4)))
    engine.step()
    engine.release(slot)
    assert engine.compile_counts["decode"] >= 1

    import time as _time

    # wall-clock ticks: stats()/firing() judge the sentinel hold window
    # against real time
    t0 = _time.time()
    sched._metrics_tick(t0)
    assert sched.sentinel.firing() == []

    # legit path: reconfigure through the scheduler seam -> expect() -> quiet
    sched._pending_slots = 3
    sched._maybe_reconfigure()
    before = engine.compile_counts["decode"]
    sched._metrics_tick(t0 + 1)
    assert sched.sentinel.firing() == [], "declared reconfigure must not alert"

    # chaos: the same change outside the seam trips the sentinel
    engine.reconfigure(4)
    assert engine.compile_counts["decode"] > before
    sched._metrics_tick(t0 + 2)
    firing = sched.sentinel.firing()
    assert firing and firing[0]["alert"] == "alert.recompile"
    stats = sched.stats()
    assert any(a["alert"] == "alert.recompile" for a in stats["alerts"])
    lines = _alert_lines(stats, 78)
    assert lines and "recompile" in lines[0] and "(!)" in lines[0]
