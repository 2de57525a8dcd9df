"""Unified telemetry subsystem (ISSUE 1): recorder semantics, JSONL sink +
Chrome-trace export, Trainer.fit step metrics, the profiler hook, the STATUS
panel, the <1% overhead budget, and the no-bare-print lint."""

import json
import os
import socket
import threading
import time

import jax
import optax
import pytest

from maggy_tpu.telemetry import recorder as rec_mod
from maggy_tpu.telemetry.export import export_chrome_trace
from maggy_tpu.telemetry.recorder import NullTelemetry, Telemetry
from maggy_tpu.telemetry.sink import worker_telemetry


def _tiny_trainer(seed=0):
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    cfg = DecoderConfig.tiny()
    ctx = TrainContext.create("dp")
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 8, 32, seed=seed)
    state = trainer.make_state(jax.random.key(0), next(data))
    return trainer, state, data


# ------------------------------------------------------------------- recorder


def test_recorder_spans_gauges_counters_rpc():
    tel = Telemetry(worker=7, role="trial")
    with tel.span("outer", step=3):
        time.sleep(0.002)
    tel.gauge("step_time_ms", 4.2)
    tel.gauge("step_time_ms", 5.0)  # gauges keep the latest value
    tel.count("trials_done")
    tel.rpc("GET", 1.0)
    tel.rpc("GET", 3.0)
    tel.rpc("METRIC", None, ok=False)

    snap = tel.snapshot()
    assert snap["worker"] == "7" and snap["role"] == "trial"
    assert snap["gauges"]["step_time_ms"] == 5.0
    assert snap["counters"]["trials_done"] == 1
    assert snap["counters"]["rpc_errors.METRIC"] == 1
    assert snap["rpc"]["GET"]["n"] == 2
    assert snap["rpc"]["GET"]["mean_ms"] == pytest.approx(2.0)
    assert snap["rpc"]["GET"]["max_ms"] == pytest.approx(3.0)

    events = tel.drain_events()
    span = next(e for e in events if e["kind"] == "span")
    assert span["name"] == "outer" and span["dur_ms"] >= 1.0
    assert span["attrs"] == {"step": 3}
    assert "ts" in span and "tid" in span
    assert not tel.drain_events()  # drained


def test_recorder_span_records_on_exception():
    tel = Telemetry(worker=0)
    with pytest.raises(ValueError):
        with tel.span("boom"):
            raise ValueError("x")
    events = tel.drain_events()
    assert events and events[0]["name"] == "boom"


def test_disabled_env_flag_returns_null(monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_TELEMETRY", "0")
    assert not rec_mod.enabled()
    tel = rec_mod.get()
    assert isinstance(tel, NullTelemetry) and not tel.active
    with tel.span("x"):
        pass
    tel.gauge("g", 1.0)
    assert tel.snapshot() == {} and tel.drain_events() == []
    # sink factory also degrades to the shared null recorder
    assert isinstance(worker_telemetry(0, "/tmp/x"), NullTelemetry)


def test_thread_ambient_recorder():
    tel = Telemetry(worker=1)
    seen = {}

    def other_thread():
        seen["other"] = rec_mod.get()

    with rec_mod.current(tel):
        assert rec_mod.get() is tel
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    # thread-local: another thread never sees this thread's recorder
    assert seen["other"] is not tel
    assert rec_mod.get() is not tel


# -------------------------------------------------------- sink + chrome trace


def test_sink_and_chrome_trace_export(tmp_env):
    exp_dir = tmp_env.experiment_dir("app_tel", 1)
    for pid in (0, 1):
        tel = worker_telemetry(pid, exp_dir, role="trial", env=tmp_env)
        with tel.span("trial", trial_id=f"t{pid}"):
            with tel.span("train_step", step=0):
                time.sleep(0.001)
        tel.gauge("step_time_ms", 2.5 + pid)
        tel.close()
        path = os.path.join(exp_dir, "telemetry", f"worker_{pid}.jsonl")
        assert os.path.exists(path)
        lines = [json.loads(l) for l in open(path) if l.strip()]
        kinds = {l["kind"] for l in lines}
        assert {"span", "gauge", "snapshot"} <= kinds

    out = export_chrome_trace(tmp_env, exp_dir)
    assert out and out.endswith("trace.json")
    trace = json.load(open(out))
    events = trace["traceEvents"]
    assert events
    # structural validity: required fields present, timestamps sorted
    for e in events:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e)
    xs = [e for e in events if e["ph"] == "X"]
    cs = [e for e in events if e["ph"] == "C"]
    assert xs and cs
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    assert {e["pid"] for e in xs} == {0, 1}


def test_chrome_trace_skips_torn_lines(tmp_env):
    exp_dir = tmp_env.experiment_dir("app_torn", 1)
    tdir = os.path.join(exp_dir, "telemetry")
    os.makedirs(tdir)
    with open(os.path.join(tdir, "worker_0.jsonl"), "w") as f:
        f.write(
            json.dumps(
                {"kind": "span", "name": "s", "ts": 1.0, "dur_ms": 2.0, "worker": "0"}
            )
            + "\n"
        )
        f.write('{"kind": "span", "name": "torn"')  # crashed-worker tail
    out = export_chrome_trace(tmp_env, exp_dir)
    trace = json.load(open(out))
    assert sum(e["ph"] == "X" for e in trace["traceEvents"]) == 1


# --------------------------------------------------------------- Trainer.fit


def test_fit_exposes_steps_per_sec_and_gauges():
    trainer, state, data = _tiny_trainer()
    tel = Telemetry(worker=0)
    with rec_mod.current(tel):
        state, metrics = trainer.fit(state, data, num_steps=4)
    assert metrics["steps_per_sec"] > 0
    g = tel.snapshot()["gauges"]
    assert g["compile_time_ms"] > 0
    assert g["step_time_ms"] > 0
    assert g["steps_per_sec"] == pytest.approx(metrics["steps_per_sec"])
    assert g["tokens_per_sec"] > 0  # LM batch: 8*32 tokens/step
    assert "mfu_est" not in g  # unknown peak FLOPs on the CPU mesh
    names = [e["name"] for e in tel.drain_events() if e["kind"] == "span"]
    assert names.count("train_step") == 4
    assert names.count("shard_batch") == 4


def test_peak_flops_table_is_keyed_by_device_kind(caplog):
    """A known kind reads its published peak; a TPU kind the table does not
    hold yields no MFU (and a logged warning), never a default; a CPU device
    is silently without one."""
    import logging
    import types

    from maggy_tpu.telemetry import flops

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert flops.device_peak_flops(v5e) == 197e12
    # 6 * 1e9 params * 1e4 tok/s over 4 chips of 197 TFLOP/s
    assert flops.estimate_mfu(1e4, 10**9, [v5e] * 4) == pytest.approx(
        6e13 / (4 * 197e12)
    )
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")
    with caplog.at_level(logging.WARNING, logger="maggy_tpu.telemetry.flops"):
        assert flops.estimate_mfu(1e4, 10**9, [unknown]) is None
    assert "TPU v9 imaginary" in caplog.text
    caplog.clear()
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    with caplog.at_level(logging.WARNING, logger="maggy_tpu.telemetry.flops"):
        assert flops.estimate_mfu(1e4, 10**9, [cpu]) is None
    assert not caplog.text


def test_fit_steps_per_sec_with_telemetry_disabled(monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_TELEMETRY", "0")
    trainer, state, data = _tiny_trainer()
    state, metrics = trainer.fit(state, data, num_steps=2)
    # the metrics-dict contract holds even with the recorder off
    assert metrics["steps_per_sec"] > 0


# ------------------------------------------------------------- profiler hook


class _FakeProfiler:
    def __init__(self, counter):
        self.counter = counter  # shared data-iterator call counter
        self.starts = []
        self.stops = 0

    def start_trace(self, logdir):
        self.starts.append((logdir, self.counter["n"]))

    def stop_trace(self):
        self.stops += 1


def _counting(data, counter):
    for batch in data:
        counter["n"] += 1
        yield batch


def test_profiler_hook_starts_and_stops_at_bounds(monkeypatch, tmp_path):
    trainer, state, data = _tiny_trainer()
    counter = {"n": 0}
    fake = _FakeProfiler(counter)
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    # prefetch=0: the draw-count assertion below pins when batches leave the
    # iterator, which only the synchronous input path makes deterministic
    trainer.fit(
        state, _counting(data, counter), num_steps=6,
        profile_dir=str(tmp_path), profile_steps=(1, 3), prefetch=0,
    )
    # started before step profile_steps[0]'s batch was drawn...
    assert fake.starts == [(str(tmp_path), 1)]
    # ...and stopped exactly once, at profile_steps[1]
    assert fake.stops == 1


def test_profiler_finally_stops_active_trace_on_error(monkeypatch, tmp_path):
    trainer, state, data = _tiny_trainer()
    counter = {"n": 0}
    fake = _FakeProfiler(counter)
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)

    def exploding(data):
        for i, batch in enumerate(data):
            if i == 2:  # mid-capture: trace started at step 1, stops at 3
                raise RuntimeError("data loader died")
            counter["n"] += 1
            yield batch

    with pytest.raises(RuntimeError, match="data loader died"):
        trainer.fit(
            state, exploding(data), num_steps=6,
            profile_dir=str(tmp_path), profile_steps=(1, 3),
        )
    assert len(fake.starts) == 1
    assert fake.stops == 1  # the finally path closed the dangling trace


def test_profiler_not_started_without_profile_dir(monkeypatch):
    trainer, state, data = _tiny_trainer()
    fake = _FakeProfiler({"n": 0})
    monkeypatch.setattr(jax.profiler, "start_trace", fake.start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace", fake.stop_trace)
    trainer.fit(state, data, num_steps=3)
    assert fake.starts == [] and fake.stops == 0


# ------------------------------------------------------------ overhead budget


def test_telemetry_overhead_within_budget():
    """The per-step recorder cost (what Trainer.fit adds: 2 spans + ~2
    gauges) must be far under the 1% step-time budget. Asserted loosely at
    5% against the real compiled step to stay robust to CI noise; on the
    chip, telemetry on against off read -0.013% (PERF.md section 6, PR 24)."""
    trainer, state, data = _tiny_trainer()
    batch = trainer.shard_batch(next(data))
    state, m = trainer.step(state, batch)  # compile
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(5):
        state, m = trainer.step(state, batch)
    float(m["loss"])
    step_ms = (time.perf_counter() - t0) / 5 * 1e3

    tel = Telemetry(worker=0)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with tel.span("shard_batch", step=i):
            pass
        with tel.span("train_step", step=i):
            pass
        tel.gauge("step_time_ms", 1.0)
        tel.gauge("steps_per_sec", 1.0)
    cost_ms = (time.perf_counter() - t0) / n * 1e3
    assert cost_ms < step_ms * 0.05, (cost_ms, step_ms)

    # the disabled path must be cheaper still — it is pure no-op dispatch
    null = NullTelemetry()
    t0 = time.perf_counter()
    for i in range(n):
        with null.span("train_step", step=i):
            pass
        null.gauge("step_time_ms", 1.0)
    null_ms = (time.perf_counter() - t0) / n * 1e3
    assert null_ms < step_ms * 0.05, (null_ms, step_ms)


# ------------------------------------------------- e2e dryrun + STATUS panel


def test_distributed_dryrun_telemetry_e2e(tmp_env):
    """A distributed dryrun on the CPU mesh produces per-worker JSONL + a
    structurally valid merged Chrome trace, and STATUS carries the worker
    telemetry snapshots the monitor panel renders."""
    from maggy_tpu import experiment
    from maggy_tpu.config import DistributedConfig
    from maggy_tpu.core import rpc
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.monitor import render_status
    from maggy_tpu.train.data import synthetic_lm_batches

    cfg = DecoderConfig.tiny()
    release = threading.Event()

    def train(model, dataset, hparams, reporter, ctx):
        trainer = ctx.trainer(model, optax.adamw(hparams["lr"]))
        state = trainer.make_state(jax.random.key(0), next(dataset))
        state, metrics = trainer.fit(state, dataset, num_steps=4)
        # hold until the main thread has read STATUS with telemetry attached
        release.wait(timeout=30)
        return {"metric": -metrics["loss"], **metrics}

    dconf = DistributedConfig(
        module=Decoder(cfg),
        dataset=synthetic_lm_batches(cfg.vocab_size, 8, 32, seed=5),
        hparams={"lr": 1e-3},
        sharding="dp",
        hb_interval=0.05,
        name="telemetry-e2e",
    )
    holder = {}
    t = threading.Thread(target=lambda: holder.update(r=experiment.lagom(train, dconf)))
    t.start()
    status = None
    try:
        deadline = time.time() + 60
        driver = None
        while time.time() < deadline:
            driver = experiment.CURRENT_DRIVER
            if driver is not None and driver.server is not None and driver.server.port:
                break
            time.sleep(0.05)
        assert driver is not None
        client = rpc.Client(
            ("127.0.0.1", driver.server.port), partition_id=-1,
            secret=driver.server.secret,
        )
        try:
            while time.time() < deadline:
                s = client._request({"type": "STATUS"})
                gauges = (s.get("telemetry") or {}).get("0", {}).get("gauges") or {}
                # early beats carry only connection gauges; wait for fit's
                if "step_time_ms" in gauges and "steps_per_sec" in gauges:
                    status = s
                    break
                time.sleep(0.05)
        finally:
            client.stop()
    finally:
        release.set()
        t.join(timeout=120)

    # live STATUS carried the heartbeat-attached snapshot...
    assert status is not None, "no STATUS with telemetry arrived"
    gauges = status["telemetry"]["0"]["gauges"]
    assert gauges["step_time_ms"] > 0 and gauges["steps_per_sec"] > 0
    # ...which the monitor renders as the throughput/step-time panel
    panel = render_status(status)
    assert "-- telemetry --" in panel
    assert "ms/step" in panel and "tok/s" in panel

    # returned metrics expose steps/sec (averaged into the dist result)
    assert holder["r"]["steps_per_sec"] > 0

    # durable artifacts: per-worker JSONL + structurally valid merged trace
    exp_dir = tmp_env.experiment_dir(experiment.APP_ID, experiment.RUN_ID)
    tdir = os.path.join(exp_dir, "telemetry")
    worker_file = os.path.join(tdir, "worker_0.jsonl")
    assert os.path.exists(worker_file)
    records = [json.loads(l) for l in open(worker_file) if l.strip()]
    assert any(r.get("name") == "train_step" for r in records)
    trace_path = os.path.join(tdir, "trace.json")
    assert os.path.exists(trace_path)
    trace = json.load(open(trace_path))
    events = trace["traceEvents"]
    assert events
    for e in events:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e)
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)
    assert any(e["ph"] == "X" and e["name"] == "train_step" for e in events)


# ------------------------------------------------------- monitor satellites


def test_resolve_target_skips_and_prunes_stale_records(tmp_env, capsys):
    from maggy_tpu.monitor import resolve_target

    # live driver: a real listening socket
    live = socket.socket()
    live.bind(("127.0.0.1", 0))
    live.listen(1)
    live_port = live.getsockname()[1]
    # a port that refuses connections
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()

    try:
        tmp_env.register_driver("app_live", 1, "127.0.0.1", live_port,
                                secret="s1", scope="local")
        time.sleep(0.01)  # registry orders by ts: make the dead record newest
        tmp_env.register_driver("app_dead", 1, "127.0.0.1", dead_port,
                                secret="s2", scope="local")
        host, port, secret = resolve_target(tmp_env)
        assert (host, port, secret) == ("127.0.0.1", live_port, "s1")
        # the stale record was pruned from the registry
        assert tmp_env.lookup_driver("app_dead") is None
        assert tmp_env.lookup_driver("app_live") is not None

        # nothing live left -> LookupError naming the pruned count
        tmp_env.unregister_driver("app_live")
        tmp_env.register_driver("app_dead2", 1, "127.0.0.1", dead_port,
                                secret="s3", scope="local")
        with pytest.raises(LookupError, match="stale"):
            resolve_target(tmp_env)
    finally:
        live.close()


# ----------------------------------------------------------------- CI lint


def test_no_bare_print_lint():
    """tools/check_no_bare_print.py runs clean over maggy_tpu/ (wired into
    tier-1 here so regressions fail the suite)."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_no_bare_print", os.path.join(repo, "tools", "check_no_bare_print.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([]) == 0

    # the detector itself: bare print flagged, file=-routed print allowed
    assert mod.find_bare_prints("print('x')", "<s>") != []
    assert mod.find_bare_prints("import sys\nprint('x', file=sys.stderr)", "<s>") == []
    assert mod.find_bare_prints("obj.print('x')", "<s>") == []


def _docs_nav_lint():
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_docs_nav", os.path.join(repo, "tools", "check_docs_nav.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return repo, mod


def test_docs_nav_lint(tmp_path):
    """tools/check_docs_nav.py: every docs/*.md is reachable from the mkdocs
    nav (wired into tier-1 here, alongside the bare-print lint)."""
    repo, mod = _docs_nav_lint()
    assert mod.main([repo]) == 0

    # the detector itself: an orphaned page is flagged, a referenced one not
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "linked.md").write_text("# linked")
    (tmp_path / "docs" / "orphan.md").write_text("# orphan")
    (tmp_path / "mkdocs.yml").write_text(
        "site_name: x\nnav:\n  - Linked: linked.md\ntheme:\n  name: mkdocs\n"
    )
    assert mod.orphaned_docs(str(tmp_path)) == [os.path.join("docs", "orphan.md")]
    assert mod.main([str(tmp_path)]) == 1


def test_docs_nav_lint_dangling_path(tmp_path):
    """tools/check_docs_nav.py: a backticked ``.py`` path in README.md or a
    docs page must name a file of the tree, from the root, from maggy_tpu/ or
    by its last components; commands and line suffixes are understood."""
    _, mod = _docs_nav_lint()
    (tmp_path / "maggy_tpu" / "ops").mkdir(parents=True)
    (tmp_path / "maggy_tpu" / "ops" / "flash.py").write_text("")
    (tmp_path / "README.md").write_text(
        "see `maggy_tpu/ops/flash.py`, `ops/flash.py:12-30` and `flash.py`\n"
        "run `python gone.py` (a command, not a path)\n"
        "`ops/gone.py` was deleted\n"
    )
    assert mod.dangling_paths(str(tmp_path)) == [("README.md", 3, "ops/gone.py")]
    assert mod.main([str(tmp_path)]) == 1
    (tmp_path / "maggy_tpu" / "ops" / "gone.py").write_text("")
    assert mod.main([str(tmp_path)]) == 0
