"""Set-up from the inside (ISSUE 34): a span names the span that caused it,
``record_span`` journals one after the fact, and the compile pipeline's stages
reach the thread-ambient recorder as ``compile.trace`` / ``compile.lower`` /
``compile.backend`` under the program span whose call compiled."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from maggy_tpu.telemetry import recorder as rec_mod
from maggy_tpu.telemetry.recorder import Telemetry
from maggy_tpu.telemetry.sink import worker_telemetry
from tests.test_tracing import REPO, load_tool

STAGES = ("compile.trace", "compile.lower", "compile.backend")


def spans_of(tel):
    return [e for e in tel.drain_events() if e["kind"] == "span"]


def inside(child, parent, slack_ms=5.0):
    """``child``'s interval lies in ``parent``'s (both on ``time.time()``; the
    parent's duration is on the monotonic clock, hence the slack)."""
    start = child["ts"] >= parent["ts"] - slack_ms / 1e3
    end = child["ts"] + child["dur_ms"] / 1e3 <= parent["ts"] + (parent["dur_ms"] + slack_ms) / 1e3
    return start and end


# ------------------------------------------------------------ parent, record_span


def test_nested_spans_on_two_threads_get_their_own_parents():
    tel = Telemetry(worker=0)
    gate = threading.Barrier(2, timeout=10)

    def work(outer, inner):
        with tel.span(outer):
            gate.wait()  # both outers are open before either inner opens
            with tel.span(inner):
                gate.wait()

    threads = [threading.Thread(target=work, args=pair) for pair in (("trial", "train_fn"), ("serve.admit", "serve.prefill"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    by_name = {s["name"]: s for s in spans_of(tel)}
    assert by_name["train_fn"]["parent"] == "trial"
    assert by_name["serve.prefill"]["parent"] == "serve.admit"
    assert "parent" not in by_name["trial"] and "parent" not in by_name["serve.admit"]
    assert by_name["train_fn"]["tid"] != by_name["serve.prefill"]["tid"]


def test_parent_is_restored_after_an_exception():
    tel = Telemetry(worker=0)
    with tel.span("trial"):
        with pytest.raises(ValueError):
            with tel.span("train_fn"):
                raise ValueError("x")
        with tel.span("build_context"):
            pass
    with tel.span("await_reservations"):
        pass
    parents = {s["name"]: s.get("parent") for s in spans_of(tel)}
    assert parents == {"train_fn": "trial", "build_context": "trial", "trial": None, "await_reservations": None}


def test_record_span_journals_what_span_journals():
    from maggy_tpu.telemetry import tracing

    tel = Telemetry(worker=3)
    with tracing.scope("t-1"), tel.span("trial"):
        with tel.span("train_fn", partition=0):
            pass
        start = time.time()
        tel.record_span("compile.lower", start, start + 0.25, fun_name="jit(f)")
    live, late, _outer = spans_of(tel)
    assert set(live) == set(late)  # kind, name, ts, dur_ms, worker, tid, parent, attrs, trace
    assert late["parent"] == "trial" and late["ts"] == start and late["dur_ms"] == pytest.approx(250.0)
    assert late["attrs"] == {"fun_name": "jit(f)"} and late["trace"] == live["trace"] == "t-1"
    assert late["tid"] == live["tid"] and late["worker"] == "3"
    assert list(tel.flight)[-2] is late  # teed into the flight ring like any record
    assert rec_mod.NULL.record_span("compile.lower", 0.0, 1.0) is None


# ------------------------------------------------------- the compile pipeline


@pytest.fixture(scope="module")
def first_and_second_call():
    """A fresh jitted program called twice, each call inside a span."""
    tel = Telemetry(worker=0)

    def scaled_sum_for_setup_spans(x):
        for i in range(40):  # milliseconds to trace: over the recorder's floor
            x = jnp.sin(x) * 1.01 + i
        return (x * 3.0).sum()

    program = jax.jit(scaled_sum_for_setup_spans)
    x = jnp.ones((5, 7))
    jax.block_until_ready(x)  # the input's own programs compile out here
    with rec_mod.current(tel):
        tel.drain_events()
        with tel.span("train_step", step=0):
            program(x)
        first = spans_of(tel)
        with tel.span("train_step", step=1):
            program(x)
        second = spans_of(tel)
    return first, second


@pytest.mark.parametrize("stage", STAGES)
def test_first_call_in_a_span_records_the_stage_under_it(first_and_second_call, stage):
    first, _ = first_and_second_call
    outer = first[-1]
    assert outer["name"] == "train_step"
    mine = [s for s in first if s["name"] == stage and "scaled_sum_for_setup_spans" in s["attrs"]["fun_name"]]
    assert len(mine) == 1
    assert mine[0]["parent"] == "train_step" and inside(mine[0], outer)
    assert mine[0]["tid"] == outer["tid"] and mine[0]["dur_ms"] > 0
    if stage == "compile.backend":
        assert mine[0]["attrs"]["cache"] in ("off", "miss")


def test_the_stages_come_in_order_and_a_second_call_records_none(first_and_second_call):
    first, second = first_and_second_call
    of_program = [s for s in first if "scaled_sum_for_setup_spans" in s.get("attrs", {}).get("fun_name", "")]
    assert [s["name"] for s in sorted(of_program, key=lambda s: s["ts"])] == list(STAGES)
    assert [s["name"] for s in second] == ["train_step"]


def test_a_program_called_outside_any_span_has_no_parent():
    tel = Telemetry(worker=0)
    with rec_mod.current(tel):
        jax.jit(lambda x: x - 11.0)(jnp.ones(3))
    stages = [s for s in spans_of(tel) if s["name"] in STAGES]
    assert stages and all("parent" not in s for s in stages)


@pytest.fixture()
def persistent_cache(tmp_path):
    """JAX's persistent compile cache in a directory of the test's own, every
    program admitted; the process's settings come back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    before = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        cc.reset_cache()
        yield tmp_path
    finally:
        for n, v in before.items():
            jax.config.update(n, v)
        cc.reset_cache()


def compile_once(tel, fn, x):
    with rec_mod.current(tel), tel.span("train.make_state"):
        jax.jit(fn)(x)
    return [s for s in spans_of(tel) if s["name"] == "compile.backend"]


@pytest.mark.parametrize("what", ["verdicts", "counters", "load_time"])
def test_persistent_cache_verdict_rides_on_the_backend_span(persistent_cache, what):
    def cached_for_setup_spans(x):
        return jnp.tanh(x) @ x.T

    def mine(spans):
        (span,) = [s for s in spans if "cached_for_setup_spans" in s["attrs"]["fun_name"]]
        return span

    tel = Telemetry(worker=0)
    x = jnp.ones((4, 4))
    cold_all = compile_once(tel, cached_for_setup_spans, x)
    jax.clear_caches()  # the in-memory executables go; the directory stays
    warm_all = compile_once(tel, cached_for_setup_spans, x)
    cold, warm = mine(cold_all), mine(warm_all)
    if what == "verdicts":
        assert cold["attrs"]["cache"] == "miss" and warm["attrs"]["cache"] == "hit"
        assert cold["parent"] == warm["parent"] == "train.make_state"
    elif what == "counters":
        verdicts = [s["attrs"]["cache"] for s in cold_all + warm_all]
        assert tel.snapshot()["counters"] == {
            "compile.cache_misses": verdicts.count("miss"), "compile.cache_hits": verdicts.count("hit"),
        }
        assert verdicts.count("hit") >= 1 and verdicts.count("miss") >= 1
    else:
        assert "cache_load_ms" not in cold["attrs"]
        assert 0 < warm["attrs"]["cache_load_ms"] <= warm["dur_ms"]


def test_without_a_cache_directory_the_verdict_is_off():
    assert not jax.config.jax_compilation_cache_dir  # the tests run with none
    tel = Telemetry(worker=0)
    with rec_mod.current(tel):
        jax.jit(lambda x: x * 17.0 + 1.0)(jnp.ones(3))
    backends = [s for s in spans_of(tel) if s["name"] == "compile.backend"]
    assert backends and all(s["attrs"]["cache"] == "off" for s in backends)
    assert "counters" not in tel.snapshot()


# ------------------------------------------------------------- registration


class Registrar:
    """Stands in for ``jax.monitoring``'s three ``register_*`` functions."""

    def __init__(self, monkeypatch):
        self.calls = []
        monkeypatch.setattr(rec_mod, "_listening", False)
        for name in ("register_event_listener", "register_event_duration_secs_listener",
                     "register_event_time_span_listener"):
            monkeypatch.setattr(jax.monitoring, name, lambda fn, name=name: self.calls.append((name, fn)))


def test_listeners_register_once_with_the_first_real_recorder(monkeypatch, tmp_env):
    reg = Registrar(monkeypatch)
    worker_telemetry(0, tmp_env.experiment_dir("app_setup", 1), env=tmp_env).close()
    Telemetry(worker=1)
    assert sorted(name for name, _fn in reg.calls) == [
        "register_event_duration_secs_listener", "register_event_listener", "register_event_time_span_listener",
    ]


def test_disabled_telemetry_registers_and_records_nothing(monkeypatch, tmp_env):
    reg = Registrar(monkeypatch)
    monkeypatch.setenv("MAGGY_TPU_TELEMETRY", "0")
    tel = worker_telemetry(0, tmp_env.experiment_dir("app_setup", 2), env=tmp_env)
    bystander = Telemetry(worker=9)  # built by hand: still registers nothing
    assert reg.calls == [] and tel is rec_mod.NULL
    # listeners an enabled process registered earlier find the null recorder
    with tel.span("train_step"):
        rec_mod._on_compile_stage("/jax/core/compile/jaxpr_trace_duration", 1.0, 2.0, fun_name="f")
        jax.jit(lambda x: x / 19.0)(jnp.ones(3))
    assert tel.drain_events() == [] and bystander.drain_events() == []


def test_an_unknown_event_is_dropped_at_the_lookup():
    tel = Telemetry(worker=0)
    with rec_mod.current(tel):
        rec_mod._on_compile_stage("/jax/core/compile/some_new_duration", 1.0, 2.0, fun_name="f")
        rec_mod._on_compile_duration("/jax/compilation_cache/compile_time_saved_sec", 3.0)
        rec_mod._on_compile_event("/jax/compilation_cache/tasks_using_cache")
    assert tel.drain_events() == [] and "counters" not in tel.snapshot()


@pytest.mark.parametrize("stage,seconds,kept", [
    ("jaxpr_trace_duration", 0.0002, False),  # an inner jit's or an eager op's lookup
    ("jaxpr_trace_duration", 0.02, True),
    ("jaxpr_to_mlir_module_duration", 0.0002, True),  # only traces come by the thousand
    ("backend_compile_duration", 0.0002, True),
])
def test_a_trace_of_microseconds_is_not_journaled(stage, seconds, kept):
    tel = Telemetry(worker=0)
    with rec_mod.current(tel):
        rec_mod._on_compile_stage(f"/jax/core/compile/{stage}", 100.0, 100.0 + seconds, fun_name="add")
    assert len(spans_of(tel)) == int(kept)


# ------------------------------------------------------------------ the trainer


@pytest.fixture(scope="module")
def cold_trainer_spans():
    """``make_state`` and a cold two-step ``fit`` on the tiny decoder."""
    import optax

    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    cfg = DecoderConfig.tiny()
    trainer = TrainContext.create("dp").trainer(Decoder(cfg), optax.adamw(1e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 8, 32, seed=0)
    tel = Telemetry(worker=0)
    with rec_mod.current(tel):
        state = trainer.make_state(jax.random.key(0), next(data))
        trainer.fit(state, data, num_steps=2)
    return spans_of(tel)


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("outer,program", [("train.make_state", "init_fn"), ("train_step", "step")])
def test_trainer_set_up_is_a_tree(cold_trainer_spans, outer, program, stage):
    spans = cold_trainer_spans
    outers = [s for s in spans if s["name"] == outer]
    children = [s for s in spans if s["name"] == stage and s.get("parent") == outer]
    assert children and all(any(inside(c, o) for o in outers) for c in children)
    # the first one is the one that compiled the program
    assert any(program in c["attrs"]["fun_name"] and inside(c, outers[0]) for c in children)


def test_only_the_first_step_lowers_and_compiles(cold_trainer_spans):
    steps = [s for s in cold_trainer_spans if s["name"] == "train_step"]
    assert [s["attrs"]["step"] for s in steps] == [0, 1]
    # (the second call's arguments are the first's outputs: jit looks its
    # trace up again, which JAX reports as a trace of microseconds)
    late = [s for s in cold_trainer_spans if s["name"] in STAGES[1:] and s["ts"] >= steps[1]["ts"]]
    assert late == []
    drains = [s for s in cold_trainer_spans if s["name"] == "train.drain" and s["attrs"].get("why") == "compile"]
    assert len(drains) == 1 and drains[0]["attrs"]["step"] == 0


# ----------------------------------------------------------------------- lint


@pytest.mark.parametrize("source,clean", [
    ("tel.record_span('compile.lower', a, b, fun_name=f)", True),
    ("tel.record_span('compile.lowr', a, b)", False),
    ("telemetry.get().record_span('compile.cache_hits', a, b)", False),  # a counter's name
    ("tel.record_span(name, a, b)", True),  # a variable: not checkable
    ("tel.span('train.make_state')", True),
    ("tel.count('compile.cache_misses')", True),
    ("tel.gauge('step_time_ms_mean', 1.0)", False),  # removed with its reader
    ("tel.gauge('data_plane_init_ms', 1.0)", False),
])
def test_lint_knows_record_span_and_the_new_names(source, clean):
    mod = load_tool("check_telemetry_names")
    violations = mod.check_source(source, "<s>", mod.load_registry(REPO))
    assert (violations == []) is clean
