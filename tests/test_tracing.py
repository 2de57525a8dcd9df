"""Request-scoped tracing, latency histograms, stall flight recorder
(ISSUE 7): histogram/tracing/watchdog units, RPC trace propagation, JSONL
rotation, scheduler lifecycle events, the 2-replica fleet acceptance run
(one correlated Chrome-trace lane per request + merged histograms + SLO
attainment + analyze_trace attribution), trace continuity across a chaos
replica kill, the flight recorder firing on an injected rpc_stall, and the
telemetry-name lint."""

import importlib.util
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from maggy_tpu.resilience import chaos
from maggy_tpu.telemetry import flightrec, tracing
from maggy_tpu.telemetry import recorder as rec_mod
from maggy_tpu.telemetry.histogram import LatencyHistogram, merge_dicts
from maggy_tpu.telemetry.recorder import Telemetry
from maggy_tpu.telemetry.sink import JsonlSink

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -------------------------------------------------------------- histograms


def test_histogram_observe_percentiles_merge():
    h = LatencyHistogram()
    for v in (1.0, 2.0, 4.0, 8.0, 100.0, 100.0, 100.0, 100.0):
        h.observe(v)
    assert h.n == 8
    assert h.mean_ms == pytest.approx(sum((1, 2, 4, 8, 100, 100, 100, 100)) / 8)
    # bucket-resolution approximations: within the ~7% bucket width
    assert h.percentile(0.5) == pytest.approx(8.0, rel=0.20)
    assert h.percentile(0.99) == pytest.approx(100.0, rel=0.10)
    # negative / NaN dropped, never recorded
    h.observe(-5.0)
    h.observe(float("nan"))
    assert h.n == 8

    a, b = LatencyHistogram(), LatencyHistogram()
    for v in (10.0,) * 50:
        a.observe(v)
    for v in (1000.0,) * 50:
        b.observe(v)
    merged = merge_dicts([a.to_dict(), b.to_dict(), None, {"junk": 1}])
    assert merged.n == 100
    # true merged percentiles: median straddles the two populations,
    # p99 comes from the slow replica — what max-of-p50s could never say
    assert merged.percentile(0.25) == pytest.approx(10.0, rel=0.10)
    assert merged.percentile(0.99) == pytest.approx(1000.0, rel=0.10)
    with pytest.raises(ValueError, match="geometry"):
        LatencyHistogram(growth=2.0).merge(a)


def test_histogram_attainment_and_serialization():
    h = LatencyHistogram()
    for _ in range(90):
        h.observe(10.0)
    for _ in range(10):
        h.observe(500.0)
    assert h.attainment(100.0) == pytest.approx(0.9, abs=0.02)
    assert h.attainment(1e9) == pytest.approx(1.0)
    assert LatencyHistogram().attainment(10.0) is None
    rt = LatencyHistogram.from_dict(json.loads(json.dumps(h.to_dict())))
    assert rt.n == h.n
    assert rt.percentile(0.5) == h.percentile(0.5)
    assert rt.total_ms == pytest.approx(h.total_ms)


# ----------------------------------------------------------------- tracing


def test_tracing_scope_ensure_and_isolation():
    assert tracing.current() is None
    with tracing.scope("t-outer"):
        assert tracing.current() == "t-outer"
        assert tracing.ensure() == "t-outer"
        with tracing.scope(None):  # handlers mask the outer trace
            assert tracing.current() is None
        with tracing.scope("t-inner"):
            assert tracing.current() == "t-inner"
        assert tracing.current() == "t-outer"
    assert tracing.current() is None
    minted = tracing.ensure()
    assert minted and tracing.current() is None  # ensure() does not install
    seen = {}
    t = threading.Thread(target=lambda: seen.update(t=tracing.current()))
    with tracing.scope("t-main"):
        t.start()
        t.join()
    assert seen["t"] is None  # thread-local: other threads see nothing


def test_recorder_trace_tag_event_histogram_flight():
    tel = Telemetry(worker=3)
    with tracing.scope("tr1"):
        with tel.span("work", step=1):
            pass
        tel.gauge("step_time_ms", 5.0)
        tel.event("req.queued", rid="r1")
    tel.event("req.finished", trace="tr1", rid="r1", state="done")
    tel.histogram("serve.ttft_ms", 25.0)
    tel.histogram("serve.ttft_ms", 30.0)

    events = tel.drain_events()
    assert [e["kind"] for e in events] == ["span", "gauge", "event", "event"]
    assert all(e["trace"] == "tr1" for e in events)
    ev = events[2]
    assert ev["name"] == "req.queued" and ev["attrs"] == {"rid": "r1"}
    # flight ring keeps its own copy after the drain
    assert len(tel.flight) == 4
    snap = tel.snapshot()
    assert snap["hist"]["serve.ttft_ms"]["n"] == 2
    # the registry includes this recorder's ring for watchdog dumps
    rings = {r["worker"]: r for r in rec_mod.flight_snapshots()}
    assert len(rings["3"]["events"]) == 4


# ----------------------------------------------------- rpc trace propagation


def test_rpc_propagates_trace_to_handler_scope():
    from maggy_tpu.core import rpc

    server = rpc.Server(num_executors=0)
    seen = []
    server.register_callback(
        "PING", lambda msg: seen.append((msg.get("trace"), tracing.current()))
        or {"type": "PING"}
    )
    host, port = server.start(host="127.0.0.1")
    try:
        client = rpc.Client((host, port), partition_id=-1, secret=server.secret)
        try:
            with tracing.scope("wire-1"):
                client.request({"type": "PING"})  # ambient id rides the frame
            client.request({"type": "PING", "trace": "wire-2"})  # explicit wins
            client.request({"type": "PING"})  # no scope: no trace field
        finally:
            client.stop()
    finally:
        server.stop()
    assert seen[0] == ("wire-1", "wire-1")
    assert seen[1] == ("wire-2", "wire-2")
    assert seen[2] == (None, None)


# ------------------------------------------------------------ sink rotation


def test_jsonl_sink_rotation_and_rotated_read(tmp_env, tmp_path):
    from maggy_tpu.telemetry.export import load_records

    tdir = os.path.join(str(tmp_path), "exp", "telemetry")
    os.makedirs(tdir)
    path = os.path.join(tdir, "worker_9.jsonl")
    sink = JsonlSink(path, env=tmp_env, max_bytes=400, max_segments=2)
    for i in range(30):
        sink.write(
            [{"kind": "gauge", "name": "g", "ts": float(i), "value": float(i),
              "worker": "9"}]
        )
    sink.close()
    names = sorted(os.listdir(tdir))
    # live file + bounded rotated segments, never more
    assert names[0] == "worker_9.jsonl"
    assert set(names[1:]) <= {"worker_9.jsonl.1", "worker_9.jsonl.2"}
    assert len(names) == 3
    recs = load_records(tmp_env, os.path.join(str(tmp_path), "exp"))
    vals = [r["value"] for r in recs["worker_9"]]
    # rotation dropped the oldest, kept order, and the reader folds the
    # surviving segments oldest-first under ONE stem
    assert vals == sorted(vals)
    assert vals[-1] == 29.0
    assert len(vals) < 30


# ------------------------------------------------- watchdog / flight recorder


def test_watchdog_fires_on_stall_not_on_beats(tmp_path):
    wd = flightrec.Watchdog(stall_s=0.15, interval_s=0.03, dump_dir=str(tmp_path))
    try:
        wd.begin("loop.a")
        deadline = time.time() + 0.6
        while time.time() < deadline and not wd.dumps:
            wd.beat("loop.b")  # beating a DIFFERENT mark must not help a
            time.sleep(0.02)
        assert wd.dumps, "armed mark with no beats never dumped"
        dump = json.load(open(wd.dumps[0]))
        assert dump["reason"].startswith("stall")
        assert "loop.a" in dump["marks"]
        assert dump["threads"]  # every thread's stack is in the payload
        assert any("MainThread" in k for k in dump["threads"])
        # one dump per stall episode: no second dump while still stalled
        n = len(wd.dumps)
        time.sleep(0.3)
        assert len(wd.dumps) == n
        # a beat re-arms; a healthy beating mark never dumps again
        wd.beat("loop.a")
        t0 = time.time()
        while time.time() - t0 < 0.3:
            wd.beat("loop.a")
            time.sleep(0.02)
        assert len(wd.dumps) == n
        wd.end("loop.a")
    finally:
        wd.stop()


def test_flight_recorder_fires_on_rpc_stall(tmp_path):
    """Acceptance seam: an injected rpc_stall wedges the server event loop;
    the watchdog dumps the event ring + thread stacks mid-stall."""
    from maggy_tpu.core import rpc

    wd = flightrec.Watchdog(stall_s=0.2, interval_s=0.05, dump_dir=str(tmp_path))
    flightrec.install(wd)
    chaos.install(chaos.Chaos.parse("rpc_stall:verb=PING,secs=1.0"))
    tel = Telemetry(worker="stalled")
    tel.event("req.queued", trace="stall-trace", rid="r-stall")
    server = rpc.Server(num_executors=0)
    server.register_callback("PING", lambda msg: {"type": "PING"})
    host, port = server.start(host="127.0.0.1")
    try:
        client = rpc.Client((host, port), partition_id=-1, secret=server.secret)
        try:
            client.request({"type": "PING"})  # blocks ~1s in the chaos stall
        finally:
            client.stop()
    finally:
        server.stop()
        chaos.reset()
        flightrec.reset()
    assert wd.dumps, "watchdog never fired during the stall"
    dump = json.load(open(wd.dumps[0]))
    assert "rpc.PING" in dump["reason"] or "rpc.PING" in dump["marks"]
    # the stalled thread's stack shows where it was wedged
    stacks = "".join("".join(frames) for frames in dump["threads"].values())
    assert "sleep" in stacks
    # the flight ring carried the recent lifecycle events into the dump
    rings = {r["worker"]: r["events"] for r in dump["events"]}
    assert any(
        e.get("name") == "req.queued" and e.get("trace") == "stall-trace"
        for e in rings.get("stalled", [])
    )


def test_watchdog_disabled_env(monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_FLIGHTREC", "0")
    flightrec.reset()
    wd = flightrec.get()
    assert isinstance(wd, flightrec.NullWatchdog)
    wd.begin("x")
    wd.beat("x")
    wd.end("x")
    assert wd.dump("r") is None
    monkeypatch.delenv("MAGGY_TPU_FLIGHTREC")
    flightrec.reset()


# --------------------------------------------------------------- CI lint


def test_check_telemetry_names_lint():
    """tools/check_telemetry_names.py runs clean over maggy_tpu/ (wired
    into tier-1 here) and its detector catches typos without flagging
    non-telemetry .count() calls."""
    mod = load_tool("check_telemetry_names")
    assert mod.main([]) == 0

    registry = mod.load_registry(REPO)
    flag = lambda src: mod.check_source(src, "<s>", registry)  # noqa: E731
    # a typo'd gauge is flagged; the registered name is not
    assert flag("tel.gauge('serve.ttft_m', 1)") != []
    assert flag("tel.gauge('serve.ttft_ms', 1)") == []
    # kind mix-up: histogram-only name used as a counter
    assert flag("self.telemetry.count('serve.tpot_ms')") != []
    # dynamic prefixes: registered head passes, unknown head fails
    assert flag("tel.count(f'serve.requests_{k}')") == []
    assert flag("tel.count(f'serve.requestz_{k}')") != []
    # non-telemetry receivers are out of scope (str/list .count)
    assert flag("'abc'.count('serve.nope')") == []
    assert flag("mylist.count(x)") == []
    # variables cannot be checked statically: skipped, not flagged
    assert flag("tel.gauge(name, 1)") == []


def test_trace_overhead_recorder_hot_path():
    """The full per-record observability cost — span + gauge + event +
    histogram, trace-tagged, flight-teed — stays far under any realistic
    step budget (2% of even a 5 ms step is 100 us, asserted loosely here;
    a host cost, no device number)."""
    tel = Telemetry(worker="bench")
    n = 2000
    with tracing.scope("hot"):
        t0 = time.perf_counter()
        for i in range(n):
            with tel.span("serve.decode_step", active=4):
                pass
            tel.gauge("serve.drain_ms", 0.1)
            tel.histogram("serve.drain_ms", 0.1)
            tel.event("req.first_token", rid="r", ttft_ms=1.0)
        per_iter_us = (time.perf_counter() - t0) / n * 1e6
    assert per_iter_us < 100.0, per_iter_us


# ------------------------------------------------------- analyze_trace units


def test_analyze_trace_attribution_synthetic(tmp_path):
    analyze = load_tool("analyze_trace")
    tdir = os.path.join(str(tmp_path), "telemetry")
    os.makedirs(tdir)
    base = 100.0
    router = [
        ("req.accepted", 0.000, {"rid": "r1"}),
        ("req.dispatched", 0.004, {"replica": 0}),
        ("req.requeued", 0.060, {"replica": 0, "resubmits": 1}),
        ("req.dispatched", 0.062, {"replica": 1}),
        ("req.completed", 0.200, {"state": "done"}),
    ]
    replica = [
        ("req.queued", 0.005, {}),
        ("req.admitted", 0.006, {}),
        ("req.first_token", 0.030, {"ttft_ms": 30.0}),
        ("req.queued", 0.063, {}),
        ("req.admitted", 0.064, {}),
        ("req.first_token", 0.090, {"ttft_ms": 90.0}),
        ("req.finished", 0.190, {"state": "done", "n_tokens": 8}),
    ]
    for stem, events in (("router", router), ("worker_1", replica)):
        with open(os.path.join(tdir, f"{stem}.jsonl"), "w") as f:
            for name, dt, attrs in events:
                f.write(json.dumps({
                    "kind": "event", "name": name, "ts": base + dt,
                    "worker": stem, "trace": "tr-99", "attrs": attrs,
                }) + "\n")
    # per-step gauges ride in the same dir
    with open(os.path.join(tdir, "worker_0.jsonl"), "w") as f:
        for v in (10.0, 12.0):
            f.write(json.dumps({"kind": "gauge", "name": "step_time_ms",
                                "ts": base, "value": v, "worker": "0"}) + "\n")
        f.write(json.dumps({"kind": "gauge", "name": "input_wait_ms",
                            "ts": base, "value": 2.0, "worker": "0"}) + "\n")

    result = analyze.analyze(str(tmp_path))
    rows = result["requests"]
    assert len(rows) == 1
    row = rows[0]
    assert row["trace"] == "tr-99" and row["rid"] == "r1" and row["hops"] == 1
    comp = row["components"]
    # attribution covers the whole span: components sum to measured e2e
    assert sum(comp.values()) == pytest.approx(row["e2e_ms"], rel=0.05)
    assert row["e2e_ms"] == pytest.approx(200.0, rel=0.01)
    assert comp["prefill"] == pytest.approx(24.0 + 26.0, rel=0.05)
    assert comp["decode"] == pytest.approx(100.0, rel=0.05)
    assert comp["lost"] == pytest.approx(30.0, rel=0.05)  # first_token→requeued
    assert comp["route"] > 0 and comp["queue"] > 0
    steps = result["step_summary"]
    assert steps["steps"] == 2
    assert steps["step_ms_mean"] == pytest.approx(11.0)
    assert steps["compute_ms_est"] == pytest.approx(9.0)
    report = analyze.render_report(rows, result["request_summary"], steps)
    assert "per-request attribution" in report
    assert "per-step attribution" in report


# --------------------------------------------- engine-backed lifecycle tests

CFG = None  # built lazily so collection stays fast


def _cfg():
    global CFG
    if CFG is None:
        from maggy_tpu.models import DecoderConfig

        CFG = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    return CFG


@pytest.fixture(scope="module")
def params():
    from maggy_tpu.models import Decoder
    from maggy_tpu.parallel.sharding import unbox

    return unbox(
        Decoder(_cfg()).init(jax.random.key(7), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
    )


def test_scheduler_lifecycle_events_histograms_slo(params):
    """One engine, three requests: the full queued→admitted→first_token→
    finished event chain per trace, scheduler histograms feeding SSTATS
    percentiles, and exact SLO counters."""
    from maggy_tpu.serve import Engine, SamplingParams, Scheduler

    tel = Telemetry(worker="sched")
    engine = Engine(_cfg(), params, num_slots=2, telemetry_recorder=tel)
    scheduler = Scheduler(engine, slo_ttft_ms=60_000.0)
    scheduler.start()
    try:
        reqs = [
            scheduler.submit(
                [1 + i, 2, 3], SamplingParams(max_new=4), trace=f"life-{i}"
            )
            for i in range(3)
        ]
        deadline = time.time() + 120
        while time.time() < deadline and any(r.state != "done" for r in reqs):
            time.sleep(0.01)
        assert all(r.state == "done" for r in reqs)
    finally:
        scheduler.stop()

    by_trace = {}
    for e in tel.drain_events():
        if e["kind"] == "event":
            by_trace.setdefault(e.get("trace"), []).append(e["name"])
    for i, req in enumerate(reqs):
        assert req.trace == f"life-{i}"
        names = by_trace[f"life-{i}"]
        admitted = (
            "req.admitted" if "req.admitted" in names else "req.prefix_admitted"
        )
        order = [
            names.index("req.queued"), names.index(admitted),
            names.index("req.first_token"), names.index("req.finished"),
        ]
        assert order == sorted(order), names

    stats = scheduler.stats()
    for key in ("ttft_ms_p50", "ttft_ms_p90", "ttft_ms_p95", "ttft_ms_p99",
                "tpot_ms_p50", "queue_wait_ms_p50", "e2e_ms_p50"):
        assert stats[key] is not None, key
    assert stats["latency"]["ttft_ms"]["n"] == 3
    assert stats["latency"]["e2e_ms"]["n"] == 3
    # tiny decoder on CPU: everything lands inside a 60s TTFT budget
    assert stats["slo_ok"] == 3 and stats["slo_miss"] == 0
    assert stats["slo_attainment"] == 1.0
    # recorder-side mirrors for JSONL/monitor snapshots
    snap = tel.snapshot()
    assert snap["hist"]["serve.ttft_ms"]["n"] == 3
    # POLL wire carries the trace id
    assert scheduler.poll(reqs[0].id)["trace"] == "life-0"


def test_fit_emits_run_trace_events():
    """Trainer.fit mints one trace per run: start/end events share it and
    every train_step span inside carries it."""
    import optax

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    cfg = DecoderConfig.tiny()
    ctx = TrainContext.create("dp")
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 8, 32, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))
    tel = Telemetry(worker=0)
    with rec_mod.current(tel):
        trainer.fit(state, data, num_steps=3)
    events = tel.drain_events()
    lifecycle = [
        e for e in events if e["kind"] == "event" and e["name"].startswith("train.")
    ]
    assert [e["name"] for e in lifecycle] == ["train.run_start", "train.run_end"]
    run_trace = lifecycle[0]["trace"]
    assert run_trace and lifecycle[1]["trace"] == run_trace
    assert lifecycle[0]["attrs"]["num_steps"] == 3
    steps = [e for e in events if e["kind"] == "span" and e["name"] == "train_step"]
    assert len(steps) == 3
    assert all(s.get("trace") == run_trace for s in steps)
    # the ambient trace did not leak out of fit
    assert tracing.current() is None


# ------------------------------------------------------- fleet acceptance


def test_fleet_tracing_acceptance(params, tmp_env):
    """ISSUE 7 acceptance: a staggered 2-replica fleet run yields (a) a
    merged Chrome trace where each request is ONE lane correlated across
    router + replica workers, (b) SSTATS with merged-histogram TTFT
    p50/p95/p99 and SLO attainment, and (c) analyze_trace attribution whose
    components sum to within 5% of the measured e2e."""
    from maggy_tpu.serve import ServeClient
    from maggy_tpu.serve.fleet import ReplicaSpec, RouterConfig, launch_fleet
    from maggy_tpu.telemetry import worker_telemetry
    from maggy_tpu.telemetry.export import REQUESTS_PID, export_chrome_trace

    exp_dir = tmp_env.experiment_dir("app_trace", 1)
    recorders = {}

    def factory(i):
        recorders[i] = worker_telemetry(f"replica{i}", exp_dir, role="serve",
                                        env=tmp_env)
        return recorders[i]

    router_tel = worker_telemetry("router", exp_dir, role="router", env=tmp_env)
    router = launch_fleet(
        ReplicaSpec(_cfg(), params, num_slots=2, telemetry_factory=factory),
        replicas=2,
        config=RouterConfig(slo_ttft_ms=120_000.0, admission="queue"),
        telemetry_recorder=router_tel,
    )
    host, port = router.start(host="127.0.0.1")
    prompts = [[1, 2, 3, 4], [5, 6, 7], [9, 10, 11], [2, 4, 6], [7, 3],
               [20, 21, 22]]
    traces = [f"accept-{i:02d}" for i in range(len(prompts))]
    max_new = 5
    results, errors = {}, []

    def drive(i, prompt, delay):
        try:
            time.sleep(delay)
            with ServeClient((host, port), router.secret) as client:
                rid = client.submit(prompt, max_new=max_new, trace=traces[i])
                snap = client.result(rid, timeout=120)
                assert snap["trace"] == traces[i]  # POLL echoes the trace
                results[i] = snap["tokens"]
        except Exception as e:  # noqa: BLE001 - surfaced via the errors list
            errors.append((i, repr(e)))

    try:
        threads = [
            threading.Thread(target=drive, args=(i, p, 0.04 * i))
            for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not errors, errors
        assert len(results) == len(prompts)

        with ServeClient((host, port), router.secret) as client:
            deadline = time.time() + 30
            while time.time() < deadline:
                stats = client.stats()
                if stats["routing"]["completed"] == len(prompts):
                    break
                time.sleep(0.05)
        # (b) merged-histogram percentiles + SLO attainment over the fleet
        assert stats["routing"]["completed"] == len(prompts)
        assert stats["latency"]["ttft_ms"]["n"] == len(prompts)
        for key in ("ttft_ms_p50", "ttft_ms_p95", "ttft_ms_p99"):
            assert stats[key] is not None and stats[key] > 0
        assert stats["ttft_ms_p50"] <= stats["ttft_ms_p99"]
        assert stats["slo_ttft_ms"] == 120_000.0
        assert stats["slo_ok"] == len(prompts) and stats["slo_miss"] == 0
        assert stats["slo_attainment"] == 1.0
        # monitor renders the latency/SLO line from the same stats
        from maggy_tpu.monitor import render_status

        status = None
        with ServeClient((host, port), router.secret) as client:
            status = client._client.request({"type": "STATUS"})
        panel = render_status(status)
        assert "p99" in panel and "slo 100.0%" in panel
    finally:
        router.stop()
        router_tel.close()
        for tel in recorders.values():
            tel.close()

    # (a) one correlated lane per request in the merged Chrome trace
    out = export_chrome_trace(tmp_env, exp_dir)
    trace_json = json.load(open(out))
    lanes = [e for e in trace_json["traceEvents"] if e.get("pid") == REQUESTS_PID]
    lane_traces = {
        e["args"]["trace"] for e in lanes if e.get("ph") in ("i", "X")
    }
    assert lane_traces == set(traces)
    # every lane shows the full journey: route span + prefill + decode
    for tr in traces:
        phases = {e["name"] for e in lanes
                  if e.get("ph") == "X" and e["args"]["trace"] == tr}
        assert {"route", "queue", "prefill", "decode"} <= phases, (tr, phases)

    # cross-worker correlation: each trace's raw events span the router
    # JSONL AND a replica JSONL
    from maggy_tpu.telemetry.export import load_records

    by_stem = load_records(tmp_env, exp_dir)
    for tr in traces:
        stems = {
            stem
            for stem, records in by_stem.items()
            for r in records
            if r.get("kind") == "event" and r.get("trace") == tr
        }
        assert "worker_router" in stems
        assert any(s.startswith("worker_replica") for s in stems), (tr, stems)

    # (c) analyze_trace attribution sums to the measured e2e within 5%
    analyze = load_tool("analyze_trace")
    result = analyze.analyze(exp_dir)
    rows = {row["trace"]: row for row in result["requests"]}
    assert set(rows) == set(traces)
    for tr, row in rows.items():
        total = sum(row["components"].values())
        assert total == pytest.approx(row["e2e_ms"], rel=0.05), (tr, row)
        assert row["components"].get("decode", 0) > 0
        assert row["components"].get("prefill", 0) > 0
    summary = result["request_summary"]
    assert summary["requests"] == len(prompts)


def test_trace_continuity_across_replica_kill(params):
    """Satellite: a replica_kill mid-stream keeps ONE trace id across the
    requeue — an explicit req.requeued hop on the router, then a second
    queued→admitted→…→finished cycle on the survivor under the same id."""
    from maggy_tpu.serve import ServeClient
    from maggy_tpu.serve.fleet import ReplicaSpec, RouterConfig, launch_fleet

    chaos.install(chaos.Chaos.parse("replica_kill:replica=1"))
    recorders = {}

    def factory(i):
        # respawns reuse the index: keep ONE recorder per replica index
        if i not in recorders:
            recorders[i] = Telemetry(worker=f"replica{i}")
        return recorders[i]

    router_tel = Telemetry(worker="router")
    router = launch_fleet(
        ReplicaSpec(_cfg(), params, num_slots=2, telemetry_factory=factory),
        replicas=2,
        config=RouterConfig(max_restarts=0, quarantine_threshold=2),
        telemetry_recorder=router_tel,
    )
    host, port = router.start(host="127.0.0.1")
    prompts = [[1, 2, 3, 4], [5, 6, 7], [9, 10, 11, 12], [2, 4, 6, 8]]
    traces = [f"chaos-{i:02d}" for i in range(len(prompts))]
    results, errors = {}, []

    def drive(i, prompt, delay):
        try:
            time.sleep(delay)
            with ServeClient((host, port), router.secret) as client:
                rid = client.submit(prompt, max_new=30, trace=traces[i])
                results[i] = client.result(rid, timeout=240)
        except Exception as e:  # noqa: BLE001 - surfaced via the errors list
            errors.append((i, repr(e)))

    try:
        threads = [
            threading.Thread(target=drive, args=(i, p, 0.04 * i))
            for i, p in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert chaos.get().fired, "chaos rule never fired"
        assert all(s["state"] == "done" for s in results.values())
    finally:
        router.stop()
        chaos.reset()

    router_events = [
        e for e in router_tel.drain_events() if e["kind"] == "event"
    ]
    requeued = [e for e in router_events if e["name"] == "req.requeued"]
    assert requeued, "no requeue hop event despite the chaos kill"
    # every hop kept a submitted trace id — the binding is durable
    assert {e["trace"] for e in requeued} <= set(traces)

    replica_events = [
        e
        for tel in recorders.values()
        for e in tel.drain_events()
        if e["kind"] == "event"
    ]
    for hop in requeued:
        tr = hop["trace"]
        names = [e["name"] for e in replica_events if e.get("trace") == tr]
        # the SAME trace ran (at least) two admission cycles: one on the
        # killed replica, one on the survivor
        assert names.count("req.queued") >= 2, (tr, names)
        assert names.count("req.finished") >= 1, (tr, names)
        # and the router saw it through to completion under that id
        assert any(
            e["name"] == "req.completed" and e["trace"] == tr
            for e in router_events
        ), tr
