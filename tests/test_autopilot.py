"""Autopilot acceptance (ISSUE 8): diagnosis verdicts + evidence, the
planner's registry-bounded moves, workload-fingerprint decision sharing,
the tune-cache alias scoping fix, the knob-registry lint (wired into
tier-1 here), live knob application (prefetcher depth, engine slot
reconfigure), the workload-shift re-tune + forced-regression rollback
state machine, fit integration, and the end-to-end serve demo: a workload
shift triggers an online re-tune whose measured after-window beats the
before-window, an injected regression rolls back automatically, and both
decisions are visible as ``autopilot.*`` telemetry and on the monitor
panel."""

import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from maggy_tpu import telemetry
from maggy_tpu.autopilot import (
    AutopilotConfig,
    Controller,
    DecisionStore,
    Move,
    Planner,
    diagnose_requests,
    diagnose_serve,
    diagnose_steps,
    diagnose_train,
    traffic_shape,
    workload_fingerprint,
)
from maggy_tpu.autopilot.knobs import KNOBS
from maggy_tpu.telemetry import attribution
from maggy_tpu.telemetry.recorder import Telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def autopilot_events(tel):
    return [
        e
        for e in tel.drain_events()
        if str(e.get("name", "")).startswith("autopilot.")
        and e.get("kind") == "event"
    ]


# ---------------------------------------------------------------- diagnoser


def test_diagnose_train_verdicts_with_evidence():
    d = diagnose_train(
        {"step_time_ms": 100.0, "input_wait_ms": 40.0, "metrics_drain_ms": 2.0}
    )
    assert d.bottleneck == "input_bound" and d.scope == "train"
    # the evidence struct names the metrics behind the verdict
    assert d.evidence["input_wait_ms"] == 40.0
    assert d.shares["input"] == pytest.approx(0.4)
    assert "input_wait_ms" in d.reason

    d = diagnose_train(
        {"step_time_ms": 100.0, "input_wait_ms": 2.0, "metrics_drain_ms": 30.0}
    )
    assert d.bottleneck == "drain_bound"

    d = diagnose_train(
        {"step_time_ms": 100.0, "input_wait_ms": 2.0, "metrics_drain_ms": 1.0}
    )
    assert d.bottleneck == "compute_bound"

    d = diagnose_train(
        {"step_time_ms": 100.0, "input_wait_ms": 90.0, "memory_headroom_frac": 0.01}
    )
    assert d.bottleneck == "memory_bound"  # memory outranks everything
    assert json.loads(json.dumps(d.to_dict()))["bottleneck"] == "memory_bound"


def test_diagnose_serve_verdicts():
    flood = {
        "queue_depth": 10, "active_slots": 2, "num_slots": 2,
        "tpot_ms_p50": 5.0, "drain_ms": 0.2,
    }
    assert diagnose_serve(flood).bottleneck == "queue_bound"
    drainy = {
        "queue_depth": 0, "active_slots": 2, "num_slots": 4,
        "tpot_ms_p50": 5.0, "drain_ms": 3.0,
    }
    assert diagnose_serve(drainy).bottleneck == "drain_bound"
    assert (
        diagnose_serve(
            {"queue_depth": 0, "active_slots": 0, "num_slots": 4}
        ).bottleneck
        == "idle"
    )
    healthy = {
        "queue_depth": 1, "active_slots": 2, "num_slots": 4,
        "tpot_ms_p50": 5.0, "drain_ms": 0.1,
    }
    assert diagnose_serve(healthy).bottleneck == "compute_bound"


def test_diagnoser_and_cli_share_the_attribution_code_path(tmp_path):
    """Satellite: ``analyze_trace --json`` and the Diagnoser consume the
    SAME module — the tool's analyze() IS attribution.analyze, the JSON is
    schema-stamped, and diagnose_steps reads its step_summary verbatim."""
    tool = load_tool("analyze_trace")
    assert tool.analyze is attribution.analyze

    tdir = os.path.join(str(tmp_path), "telemetry")
    os.makedirs(tdir)
    with open(os.path.join(tdir, "worker_0.jsonl"), "w") as f:
        for step, wait in ((20.0, 9.0), (22.0, 11.0)):
            f.write(json.dumps({"kind": "gauge", "name": "step_time_ms",
                                "ts": 1.0, "value": step, "worker": "0"}) + "\n")
            f.write(json.dumps({"kind": "gauge", "name": "input_wait_ms",
                                "ts": 1.0, "value": wait, "worker": "0"}) + "\n")
    result = attribution.analyze(str(tmp_path))
    assert result["schema"] == attribution.SCHEMA
    # machine-readable output round-trips and diagnoses input-bound
    back = json.loads(json.dumps(result, sort_keys=True, default=str))
    d = diagnose_steps(back["step_summary"])
    assert d.bottleneck == "input_bound"
    assert d.evidence["step_time_ms"] == pytest.approx(21.0)

    # request-side: queue-dominated attribution diagnoses queue_bound
    d = diagnose_requests(
        {
            "requests": 4,
            "components_ms_mean": {"queue": 80.0, "decode": 20.0},
            "components_share": {"queue": 0.8, "decode": 0.2},
        }
    )
    assert d.bottleneck == "queue_bound"

    # the CLI prints the same object under --json
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert tool.main([str(tmp_path), "--json"]) == 0
    printed = json.loads(buf.getvalue())
    assert printed["schema"] == attribution.SCHEMA
    assert printed["step_summary"] == back["step_summary"]


# ------------------------------------------------------------------ planner


def test_planner_respects_registry_bounds_and_liveness():
    p = Planner()
    d = diagnose_train(
        {"step_time_ms": 100.0, "input_wait_ms": 40.0, "metrics_drain_ms": 0.0}
    )
    (move,) = p.plan(d, {"train.prefetch_depth": 2, "train.metrics_window": 2})
    assert move.knob == "train.prefetch_depth" and move.value == 4

    # at the registry ceiling the doubling clamps; the no-op is dropped
    hi = int(KNOBS["train.prefetch_depth"].hi)
    assert p.plan(d, {"train.prefetch_depth": hi}) == []

    # memory_bound plans only startup knobs -> nothing survives live_only
    dm = diagnose_train(
        {"step_time_ms": 100.0, "input_wait_ms": 0.0, "memory_headroom_frac": 0.0}
    )
    current = {"train.batch_size": 32, "train.remat_policy": None}
    assert p.plan(dm, current, live_only=True) == []
    offline = {m.knob: m.value for m in p.plan(dm, current, live_only=False)}
    assert offline["train.batch_size"] == 16
    assert offline["train.remat_policy"] == "nothing"

    # feasibility hook prunes exactly like the startup tuner would
    p2 = Planner(feasible=lambda m: m.knob != "train.batch_size")
    offline2 = {m.knob for m in p2.plan(dm, current, live_only=False)}
    assert "train.batch_size" not in offline2 and "train.remat_policy" in offline2

    # a move can never target an unregistered knob
    with pytest.raises(ValueError):
        Move("train.nonexistent_knob", 1)


def test_planner_serve_queue_bound_escalates_to_admission():
    p = Planner()
    d = diagnose_serve(
        {"queue_depth": 99, "active_slots": 2, "num_slots": 2, "tpot_ms_p50": 5.0}
    )
    (move,) = p.plan(d, {"serve.num_slots": 2})
    assert move.knob == "serve.num_slots" and move.value == 4
    # slot geometry already at its registry ceiling: shed instead
    hi = int(KNOBS["serve.num_slots"].hi)
    (move,) = p.plan(
        d, {"serve.num_slots": hi, "fleet.admission": "queue"}
    )
    assert move.knob == "fleet.admission" and move.value == "shed"


# ----------------------------------------------------------------- CI lint


def test_check_knob_registry_lint():
    """tools/check_knob_registry.py runs clean over maggy_tpu/ (wired into
    tier-1 here); its detector catches unregistered Move targets, KNOBS
    subscripts, and knob-shaped literals in the autopilot package; and the
    registry self-check catches structurally bad entries."""
    mod = load_tool("check_knob_registry")
    assert mod.main([]) == 0

    registry = mod.load_registry(REPO)
    flag = lambda src, ap=False: mod.check_source(  # noqa: E731
        src, "<s>", registry, in_autopilot_pkg=ap
    )
    assert flag("Move('serve.num_slots', 4)") == []
    assert flag("Move('serve.num_slotz', 4)") != []
    assert flag("plan.Move(knob='train.prefetch_depht', value=2)") != []
    assert flag("KNOBS['fleet.admission']") == []
    assert flag("KNOBS['fleet.admision']") != []
    # knob-shaped literals are references inside the autopilot package only
    assert flag("x = 'serve.not_a_knob'", ap=True) != []
    assert flag("x = 'serve.not_a_knob'", ap=False) == []
    assert flag("tel.gauge('autopilot.tick_ms', 1)", ap=True) == []

    # registry structural self-check
    bad = dict(registry.KNOBS)
    bad["train.broken"] = registry.Knob(
        "train.broken", "int", "train", True, "missing bounds"
    )
    errs = registry.validate_registry(bad)
    assert any("lo <= hi" in e for e in errs)
    assert registry.validate_registry() == []


# ------------------------------------------- workload fingerprint + sharing


def test_workload_fingerprint_and_traffic_buckets():
    topo = {"n_devices": 8, "platform": "cpu", "n_processes": 1}
    t1 = traffic_shape("serve", prompt_len=100, offered_rps=20)
    t2 = traffic_shape("serve", prompt_len=120, offered_rps=17)
    assert t1 == t2  # power-of-two buckets: near-identical traffic shares
    a = workload_fingerprint("model-a", topo, t1)
    assert a == workload_fingerprint("model-a", topo, t2)
    assert a != workload_fingerprint("model-b", topo, t1)
    assert a != workload_fingerprint("model-a", {**topo, "n_processes": 2}, t1)
    assert a != workload_fingerprint("model-a", topo, traffic_shape("train"))


class KnobTarget:
    """Synthetic push-mode target: knobs apply instantly, samples are
    whatever the test scripts."""

    def __init__(self, scope="train", guard="steps_per_sec", knobs=None):
        self.scope = scope
        self.guard_metric = guard
        self.knobs = dict(knobs or {})
        self.applied = []

    def sample(self):
        return {}

    def pending(self):
        return False

    def current(self):
        return dict(self.knobs)

    def apply(self, knob, value):
        self.applied.append((knob, value))
        self.knobs[knob] = value
        return True


def test_decision_store_fleet_sharing(tmp_env):
    """A committed decision under a workload fingerprint seeds the next
    controller for the same workload — the fleet-shared cache."""
    wfp = workload_fingerprint("m", {"n_devices": 8}, traffic_shape("train"))
    store = DecisionStore()
    store.record(
        wfp, Move("train.prefetch_depth", 8, "test"),
        outcome="committed", before=1.0, after=2.0,
    )
    assert store.load(wfp) == {"train.prefetch_depth": 8}
    # a different workload reads nothing (scoped, not last-writer-wins)
    assert store.load("someone-else") == {}

    tel = Telemetry(worker="seed-test")
    target = KnobTarget(knobs={"train.prefetch_depth": 2, "train.metrics_window": 2})
    Controller(target, AutopilotConfig(window=4), telemetry_recorder=tel, workload=wfp)
    assert target.knobs["train.prefetch_depth"] == 8
    evs = autopilot_events(tel)
    assert any(
        e["name"] == "autopilot.applied"
        and e["attrs"]["reason"] == "decision cache"
        for e in evs
    )


def test_tune_cache_alias_scoped_per_workload(tmp_env):
    """Satellite: the tune-cache 'latest' alias is scoped per workload
    fingerprint — distinct topologies get distinct alias keys (process
    layout included), and a record stamped for another workload reads as
    a miss, never as this job's winner."""
    from maggy_tpu.tune.cache import (
        TuneCache,
        alias_cache_key,
        alias_workload,
        topology_key,
    )

    topo_a = {"n_devices": 8, "platform": "cpu", "device_kind": "cpu", "n_processes": 1}
    topo_b = {**topo_a, "n_processes": 2}
    assert alias_cache_key("fp", topo_a, "bf16") != alias_cache_key("fp", topo_b, "bf16")
    assert "n_processes" in topology_key()  # live topologies carry the layout

    cache = TuneCache()
    key = alias_cache_key("fp", topo_a, "bf16")
    wl_a = alias_workload("fp", topo_a, "bf16")
    record = {"best": {"x": 1}, "workload": wl_a}
    cache.put(key, record)
    assert cache.get_alias(key, wl_a) == record
    # another workload's stamp at the same key is a MISS (anti-clobber)
    assert cache.get_alias(key, alias_workload("fp", topo_b, "bf16")) is None
    # a clobber by a different-workload writer poisons nobody
    cache.put(key, {"best": {"x": 2}, "workload": "other"})
    assert cache.get_alias(key, wl_a) is None


# -------------------------------------------------- controller state machine


def feed(controller, sample, n):
    for _ in range(n):
        controller.observe(dict(sample))


def test_workload_shift_retunes_and_journals(tmp_env):
    """Satellite scenario: an input-bound run flips to drain-bound
    mid-run; the controller re-diagnoses, applies the planned move each
    time, and every decision lands in telemetry."""
    tel = Telemetry(worker="shift-test")
    target = KnobTarget(knobs={"train.prefetch_depth": 1, "train.metrics_window": 1})
    c = Controller(
        target,
        AutopilotConfig(window=4, cooldown_windows=0, store=False),
        telemetry_recorder=tel,
    )
    # phase A: input-bound at 5 steps/sec
    input_bound = {
        "step_time_ms": 200.0, "input_wait_ms": 120.0,
        "metrics_drain_ms": 1.0, "steps_per_sec": 5.0,
    }
    feed(c, input_bound, 4)  # baseline window -> diagnose + apply
    assert target.knobs["train.prefetch_depth"] == 2
    # trial window: the move helped (input wait gone, faster)
    feed(
        c,
        {"step_time_ms": 90.0, "input_wait_ms": 5.0,
         "metrics_drain_ms": 1.0, "steps_per_sec": 11.0},
        4,
    )
    assert c.retunes == 1 and c.rollbacks == 0

    # phase B: the workload shifts — now drain-bound
    drain_bound = {
        "step_time_ms": 100.0, "input_wait_ms": 2.0,
        "metrics_drain_ms": 40.0, "steps_per_sec": 10.0,
    }
    feed(c, drain_bound, 4)  # re-diagnose -> metrics_window move
    assert target.knobs["train.metrics_window"] == 2
    feed(
        c,
        {"step_time_ms": 70.0, "input_wait_ms": 2.0,
         "metrics_drain_ms": 5.0, "steps_per_sec": 14.0},
        4,
    )
    assert c.retunes == 2

    evs = autopilot_events(tel)
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e["attrs"])
    diags = [a["bottleneck"] for a in by_name["autopilot.diagnosis"]]
    assert "input_bound" in diags and "drain_bound" in diags
    # evidence rides in the journal
    assert all("evidence" in a for a in by_name["autopilot.diagnosis"])
    applied = [(a["knob"], a["value"]) for a in by_name["autopilot.applied"]]
    assert ("train.prefetch_depth", 2) in applied
    assert ("train.metrics_window", 2) in applied
    commits = [(a["knob"], a["guard_before"], a["guard_after"])
               for a in by_name["autopilot.committed"]]
    assert len(commits) == 2
    assert all(after > before for _, before, after in commits)


def test_forced_regression_rolls_back(tmp_env):
    """Satellite scenario: a move whose after-window regresses the guard
    is rolled back automatically and journaled."""
    tel = Telemetry(worker="rb-test")
    target = KnobTarget(knobs={"train.prefetch_depth": 1, "train.metrics_window": 1})
    c = Controller(
        target,
        AutopilotConfig(window=4, cooldown_windows=0, store=False),
        telemetry_recorder=tel,
    )
    input_bound = {
        "step_time_ms": 200.0, "input_wait_ms": 120.0,
        "metrics_drain_ms": 1.0, "steps_per_sec": 5.0,
    }
    feed(c, input_bound, 4)
    assert target.knobs["train.prefetch_depth"] == 2
    # trial window REGRESSES (guard 5 -> 2): automatic rollback
    feed(c, {**input_bound, "steps_per_sec": 2.0}, 4)
    assert c.rollbacks == 1 and c.retunes == 0
    assert target.knobs["train.prefetch_depth"] == 1  # restored
    evs = autopilot_events(tel)
    rb = [e["attrs"] for e in evs if e["name"] == "autopilot.rollback"]
    assert rb and rb[0]["restored"] == 1 and rb[0]["guard_after"] < rb[0]["guard_before"]


def test_controller_observe_overhead_budget():
    """The per-step controller cost (window append + amortized
    diagnose/plan) stays far under 2% of any realistic step (a host
    cost, asserted loosely; no device number)."""
    target = KnobTarget(knobs={"train.prefetch_depth": 2, "train.metrics_window": 2})
    c = Controller(
        target,
        AutopilotConfig(window=16, cooldown_windows=0, store=False),
        telemetry_recorder=Telemetry(worker="ovh"),
    )
    sample = {
        "step_time_ms": 5.0, "input_wait_ms": 0.1,
        "metrics_drain_ms": 0.05, "steps_per_sec": 200.0,
    }
    n = 4000
    t0 = time.perf_counter()
    for _ in range(n):
        c.observe(dict(sample))
    per_obs_us = (time.perf_counter() - t0) / n * 1e6
    # 2% of even a 5 ms step is 100 us
    assert per_obs_us < 100.0, per_obs_us


# ------------------------------------------------------- live knob plumbing


def test_prefetcher_set_depth_live():
    from maggy_tpu.train.prefetch import DevicePrefetcher

    src = iter(range(100))
    pf = DevicePrefetcher(src, put=lambda x: x, depth=1)
    try:
        assert next(pf) == 0
        time.sleep(0.1)  # producer tops up the depth-1 queue and blocks
        assert pf._queue.qsize() == 1
        pf.set_depth(4)
        deadline = time.time() + 2.0
        while pf._queue.qsize() < 4 and time.time() < deadline:
            time.sleep(0.01)
        assert pf._queue.qsize() == 4  # the larger lookahead filled live
        assert [next(pf) for _ in range(6)] == [1, 2, 3, 4, 5, 6]  # order kept
    finally:
        pf.close()


# --------------------------------------------------------- engine/scheduler

CFG = None


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


def _run_engine(engine, prompts, max_new=8):
    from maggy_tpu.serve import Request, SamplingParams
    from maggy_tpu.serve.slots import SlotOccupiedError

    out = {}
    todo = list(enumerate(prompts))
    streams = {}
    while todo or streams:
        while todo and engine.slots.free_slots():
            idx, p = todo.pop(0)
            try:
                slot, first = engine.admit(
                    Request(prompt=p, params=SamplingParams(max_new=max_new))
                )
            except SlotOccupiedError:
                todo.insert(0, (idx, p))
                break
            streams[slot] = (idx, [first])
        step = engine.step()
        done = []
        for slot, tok in step.tokens.items():
            idx, toks = streams[slot]
            toks.append(tok)
            if len(toks) >= max_new:
                done.append(slot)
        for slot in done:
            idx, toks = streams.pop(slot)
            out[idx] = toks
            engine.release(slot)
    engine.flush()
    return [out[i] for i in range(len(prompts))]


def test_engine_reconfigure_drain_and_byte_parity(params):
    """The drain-and-reconfigure seam: slot geometry changes between
    waves, refuses while occupied, and the reconfigured engine produces
    byte-identical streams to a fresh engine of the same geometry."""
    from maggy_tpu.serve import Engine, Request, SamplingParams
    from maggy_tpu.serve.slots import SlotOccupiedError

    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11]]
    eng = Engine(_cfg(), params, num_slots=2, telemetry_recorder=telemetry.NULL)
    first_wave = _run_engine(eng, prompts[:2])

    # refuses mid-wave
    slot, _ = eng.admit(Request(prompt=[9, 9], params=SamplingParams(max_new=4)))
    with pytest.raises(SlotOccupiedError):
        eng.reconfigure(4)
    eng.release(slot)

    eng.reconfigure(4)
    assert eng.slots.num_slots == 4
    after = _run_engine(eng, prompts)

    fresh = Engine(_cfg(), params, num_slots=4, telemetry_recorder=telemetry.NULL)
    expect = _run_engine(fresh, prompts)
    assert after == expect  # engine output = f(params, prompt, seed) only
    assert first_wave == expect[:2]


def test_serve_autopilot_e2e_demo(params, tmp_env):
    """End-to-end acceptance: a serve workload shift (trickle -> flood)
    makes the controller diagnose queue_bound, grow ``serve.num_slots``
    via drain-and-reconfigure, and COMMIT because the measured after-window
    beats the before-window; an injected regression (slots slashed to 1)
    then triggers automatic rollback to the prior geometry. Both decisions
    are `autopilot.*` telemetry events and visible on the monitor panel."""
    from maggy_tpu.monitor import render_status
    from maggy_tpu.serve import Engine, SamplingParams, Scheduler

    tel = Telemetry(worker="e2e")
    eng = Engine(_cfg(), params, num_slots=2, telemetry_recorder=tel)
    sched = Scheduler(
        eng,
        autopilot=AutopilotConfig(
            window=4, interval_s=0.05, cooldown_windows=0, store=False
        ),
    )
    sched.start()
    try:
        # phase 1 — trickle: a couple of requests, no queue pressure
        for _ in range(2):
            r = sched.submit([1, 2, 3], SamplingParams(max_new=4))
            deadline = time.time() + 60
            while r.state != "done" and time.time() < deadline:
                time.sleep(0.01)
        assert eng.slots.num_slots == 2

        # phase 2 — flood: sustained backlog until the re-tune commits
        committed_evs = []
        deadline = time.time() + 150
        i = 0
        while time.time() < deadline and sched.autopilot.retunes == 0:
            with sched._lock:
                depth = len(sched._queue)
            if depth < 24:
                sched.submit(
                    [1 + (i % 13), 2, 3 + (i % 5)], SamplingParams(max_new=24)
                )
                i += 1
            time.sleep(0.005)
        assert sched.autopilot.retunes >= 1, "flood never committed a re-tune"
        assert eng.slots.num_slots == 4  # the planned move, live

        evs = autopilot_events(tel)
        applied = [e["attrs"] for e in evs if e["name"] == "autopilot.applied"]
        committed_evs = [
            e["attrs"] for e in evs if e["name"] == "autopilot.committed"
        ]
        assert any(
            a["knob"] == "serve.num_slots" and a["value"] == 4 for a in applied
        )
        commit = next(
            a for a in committed_evs if a["knob"] == "serve.num_slots"
        )
        # the measured after-window beats the before-window
        assert commit["guard_after"] > commit["guard_before"]
        diags = [e["attrs"] for e in evs if e["name"] == "autopilot.diagnosis"]
        assert any(d["bottleneck"] == "queue_bound" for d in diags)

        # phase 3 — injected regression: slash the geometry, keep flooding
        # (rollbacks counted from here: under load a noisy window reverts the
        # flood's first trial of 4 slots before a later one commits)
        rolled = sched.autopilot.rollbacks
        assert sched.autopilot.inject(
            Move("serve.num_slots", 1, reason="chaos: forced regression")
        )
        deadline = time.time() + 150
        while time.time() < deadline and sched.autopilot.rollbacks == rolled:
            with sched._lock:
                depth = len(sched._queue)
            if depth < 24:
                sched.submit(
                    [2 + (i % 11), 3, 4 + (i % 7)], SamplingParams(max_new=24)
                )
                i += 1
            time.sleep(0.005)
        assert sched.autopilot.rollbacks > rolled, "regression never rolled back"
        # wait out the rollback's own drain-and-reconfigure
        deadline = time.time() + 60
        while eng.slots.num_slots != 4 and time.time() < deadline:
            time.sleep(0.02)
        assert eng.slots.num_slots == 4  # restored to the prior config
        evs = autopilot_events(tel)
        rb = [e["attrs"] for e in evs if e["name"] == "autopilot.rollback"]
        assert any(
            a["knob"] == "serve.num_slots" and a["restored"] == 4 for a in rb
        )

        # monitor panel shows the decision trail
        status = {
            "name": "serve-demo", "kind": "serve", "state": "serving",
            "app_id": "serve-demo", "run_id": 0, "elapsed_s": 1.0,
            "serve": sched.stats(),
        }
        panel = render_status(status)
        assert "autopilot[" in panel
        assert "serve.num_slots" in panel
    finally:
        sched.stop()


def test_fit_autopilot_integration(tmp_env):
    """``Trainer.fit(autopilot=...)`` on an input-starved run: the
    controller diagnoses input_bound from the live gauges, applies the
    prefetch-depth move to the RUNNING loop, and journals the decision."""
    import optax

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    cfg = DecoderConfig.tiny(n_layers=2, d_model=64, n_heads=2, d_ff=128)
    ctx = TrainContext.create("dp")
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(1e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 8, 16, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))

    def starved(src):
        while True:
            time.sleep(0.1)  # loader far slower than the tiny step, under six test workers too
            yield next(src)

    tel = Telemetry(worker="fit-ap")
    telemetry.set_current(tel)
    try:
        state, metrics = trainer.fit(
            state,
            starved(data),
            num_steps=14,
            prefetch=1,
            autopilot=AutopilotConfig(window=4, cooldown_windows=0),
        )
    finally:
        telemetry.set_current(None)
    assert metrics["steps_per_sec"] > 0
    evs = autopilot_events(tel)
    diags = [e["attrs"] for e in evs if e["name"] == "autopilot.diagnosis"]
    assert diags and any(d["bottleneck"] == "input_bound" for d in diags)
    applied = [e["attrs"] for e in evs if e["name"] == "autopilot.applied"]
    assert any(
        a["knob"] == "train.prefetch_depth" and a["value"] > 1 for a in applied
    )
    # the fit-side workload fingerprint names the decision-cache scope
    assert all(a.get("workload") for a in applied)


def test_monitor_renders_autopilot_counters():
    from maggy_tpu.monitor import _telemetry_lines

    status = {
        "telemetry": {
            "0": {
                "counters": {
                    "autopilot.diagnoses": 7,
                    "autopilot.retunes": 2,
                    "autopilot.rollbacks": 1,
                }
            }
        }
    }
    lines = "\n".join(_telemetry_lines(status, width=78))
    assert "autopilot diag=7 retune=2 rb=1" in lines
