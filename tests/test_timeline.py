"""One timeline (ISSUE 24): the program's spans inside the profiler's trace,
the model's scopes inside the compiled programs, per-token timestamps, the
compile-time repair of ``Trainer.fit``, the span-name lint, and the two alert
repairs (``admit`` no longer trips the recompile sentinel; the TPOT burn rule
waits for the engine's warm-up)."""

import functools
import glob
import os
import re
import signal
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from maggy_tpu.telemetry import metrics as registry
from maggy_tpu.telemetry import recorder as rec_mod
from maggy_tpu.telemetry.recorder import Telemetry
from tests.test_tracing import REPO, load_tool


def limit(seconds):
    """A test's own time limit (the suite has no timeout plugin)."""

    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            def expired(*_):
                raise TimeoutError(f"{fn.__name__} ran over {seconds} s")

            old = signal.signal(signal.SIGALRM, expired)
            signal.alarm(seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, old)

        return run

    return wrap


def tiny_trainer(**cfg):
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.data import synthetic_lm_batches

    config = DecoderConfig.tiny(**cfg)
    trainer = TrainContext.create("dp").trainer(Decoder(config), optax.adamw(1e-3))
    data = synthetic_lm_batches(config.vocab_size, 8, 32, seed=0)
    state = trainer.make_state(jax.random.key(0), next(data))
    return trainer, state, data


# ------------------------------------------- (a) spans in the profiler's trace

LOOP_SPANS = ("train.fit_setup", "train.input_wait", "train_step", "train.drain")


def host_events(trace_dir):
    """``(name, start_ns, end_ns)`` of every host-plane event, and the
    trace's length in ns."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events, bounds = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            bounds = dict(plane.stats)
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
    return events, bounds["profile_stop_time"] - bounds["profile_start_time"]


@pytest.mark.parametrize("flag", ["1", "0"])
@limit(120)
def test_fit_spans_lie_in_the_profilers_trace(flag, tmp_path, monkeypatch):
    monkeypatch.setenv("MAGGY_TPU_TELEMETRY", flag)
    trainer, state, data = tiny_trainer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec_mod.current(None):
            trainer.fit(state, data, num_steps=3)
    finally:
        jax.profiler.stop_trace()
    events, length_ns = host_events(str(tmp_path))
    assert events, "the profiler recorded no host event"
    found = {name: [(s, e) for n, s, e in events if n == name] for name in registry.SPANS}
    if flag == "0":
        assert not any(found.values()), {n: len(v) for n, v in found.items() if v}
        return
    for name in LOOP_SPANS:
        assert found[name], f"no {name} event on the host plane"
        for s, e in found[name]:
            assert 0 <= s <= e <= length_ns, (name, s, e, length_ns)
    assert len(found["train_step"]) == 3 and len(found["train.input_wait"]) == 3
    assert len(found["train.fit_setup"]) == 1
    # the prefetcher's thread keeps its own span
    assert len(found["shard_batch"]) == 3


# ------------------------------------------------ (b) scopes in the compiled HLO


def op_names(compiled_text):
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def has_scope(names, scope):
    word = re.compile(r"(?<![\w.])" + re.escape(scope) + r"(?![\w.])")
    return any(word.search(n.rsplit("/", 1)[0]) for n in names if "/" in n)


def train_step_names(model_kind, **cfg):
    from maggy_tpu import models
    from maggy_tpu.train import TrainContext

    config_class = {"Decoder": models.DecoderConfig, "MoEDecoder": models.MoEConfig}[model_kind]
    model = getattr(models, model_kind)(config_class.tiny(**cfg))
    trainer = TrainContext.create("dp").trainer(model, optax.adamw(1e-3))
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
    state = trainer.make_state(jax.random.key(0), batch)
    sharded = trainer.shard_batch(batch)
    with trainer.mesh:
        step = trainer._build_train_step()
        return op_names(step.lower(state, sharded).compile().as_text())


@pytest.fixture(scope="module")
def dense_names():
    return {remat: train_step_names("Decoder", remat=remat, remat_policy="nothing") for remat in (True, False)}


# flax gives the module scopes; the closed list gives the rest
DENSE_SCOPES = ("attn", "mlp", "attn_norm", "mlp_norm", "final_norm", "lm_head", "layers", "loss", "optimizer")


@pytest.mark.parametrize("scope", DENSE_SCOPES)
@limit(120)
def test_dense_train_step_carries_scope(dense_names, scope):
    for remat in (True, False):
        assert has_scope(dense_names[remat], scope), scope


@limit(120)
def test_remat_marker_only_with_remat(dense_names):
    marker = registry.REMAT_MARKER
    assert any(marker in n for n in dense_names[True])
    assert not any(marker in n for n in dense_names[False])
    # what the marker marks is the model's own scopes, recomputed
    assert any(marker in n and "/attn/" in n for n in dense_names[True])


@pytest.mark.parametrize("scope", ["moe.route", "moe.dispatch", "moe.experts", "moe.combine"])
@limit(180)
def test_moe_train_step_carries_scope(scope):
    names = _moe_names()
    assert scope in registry.SCOPES and has_scope(names, scope), scope
    assert has_scope(names, "moe") and has_scope(names, "loss")


@functools.lru_cache(maxsize=None)
def _moe_names():
    return frozenset(train_step_names("MoEDecoder"))


@functools.lru_cache(maxsize=None)
def _decode_names(paged):
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.sharding import unbox
    from maggy_tpu.serve import Engine

    cfg = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = unbox(Decoder(cfg).init(jax.random.key(7), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = Engine(cfg, params, num_slots=2, paged=paged)
    b = engine.slots.num_slots
    i32, f32, flag = (np.zeros((b,), t) for t in (np.int32, np.float32, bool))
    with engine._ctx():
        lowered = engine._decode_jit.lower(
            engine.params, engine.cache, engine.key_data, i32, i32, flag, i32, flag, f32, i32, i32
        )
    return frozenset(op_names(lowered.compile().as_text()))


@pytest.mark.parametrize("paged", [True, False])
@pytest.mark.parametrize("scope", ["decode_attn", "kv_write", "sample"])
@limit(120)
def test_decode_program_carries_scope(scope, paged):
    names = _decode_names(paged)
    assert scope in registry.SCOPES and has_scope(names, scope), scope
    assert has_scope(names, "mlp") and has_scope(names, "attn")


@limit(120)
def test_grad_sync_scope_in_the_bucketed_step():
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train import TrainContext

    ctx = TrainContext.create("dp")
    trainer = ctx.trainer(Decoder(DecoderConfig.tiny()), optax.adamw(1e-3), zero_stage=1, bucket_mb=0.05)
    batch = {"tokens": jnp.zeros((8, 16), jnp.int32)}
    state = trainer.make_state(jax.random.key(0), batch)
    with trainer.mesh:
        names = op_names(trainer._build_train_step().lower(state, trainer.shard_batch(batch)).compile().as_text())
    assert trainer._overlap_mode()[0] == "zero"
    for scope in ("grad_sync", "optimizer", "loss"):
        assert has_scope(names, scope), scope


# ------------------------------------- (c) per-token timestamps, scheduler phases

LOOP_PHASES = ("serve.sweep", "serve.admit", "serve.preempt", "serve.decode_step",
               "serve.emit", "serve.idle_wait", "serve.tick", "serve.prefill", "serve.spill")


@pytest.fixture(scope="module")
def served():
    """A scheduler run on the toy engine (host tier on, prompts of a page or
    more so a leaving prompt is spilled): its requests and its records."""
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.sharding import unbox
    from maggy_tpu.serve import Engine, SamplingParams, Scheduler

    cfg = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = unbox(Decoder(cfg).init(jax.random.key(7), jnp.zeros((1, 8), jnp.int32))["params"])
    tel = Telemetry(worker="timeline")
    engine = Engine(cfg, params, num_slots=2, num_pages=24, tier=True, telemetry_recorder=tel)
    sched = Scheduler(engine, telemetry_recorder=tel)
    sched.start()
    rng = np.random.default_rng(0)
    try:
        requests = [
            sched.submit(
                [int(t) for t in rng.integers(1, cfg.vocab_size, size=18 + i)],
                SamplingParams(max_new=3 + i),
            )
            for i in range(5)
        ]
        deadline = time.time() + 90
        seen = []

        def spans():
            seen.extend(r for r in tel.drain_events() if r.get("kind") == "span")
            return {r["name"] for r in seen}

        while time.time() < deadline:
            if all(r.state == "done" for r in requests) and "serve.tick" in spans():
                break
            time.sleep(0.05)
        assert all(r.state == "done" for r in requests), [(r.id, r.state, r.error) for r in requests]
    finally:
        sched.stop()
    spans()
    return requests, seen, tel


@limit(150)
def test_token_timestamps(served):
    requests, _spans, tel = served
    for r in requests:
        assert len(r.token_ts) == len(r.tokens) == r.params.max_new
        assert r.token_ts == sorted(r.token_ts)
        assert r.token_ts[0] == r.first_token_ts
    itl = tel.snapshot()["hist"]["serve.itl_ms"]
    assert itl["n"] == sum(len(r.tokens) for r in requests) - len(requests)


@pytest.mark.parametrize("phase", LOOP_PHASES)
@limit(150)
def test_scheduler_phase_span_recorded(served, phase):
    _requests, spans, _tel = served
    assert phase in registry.SPANS
    mine = [r for r in spans if r["name"] == phase]
    assert mine, f"{phase} never recorded"
    assert all(r["dur_ms"] >= 0 for r in mine)


@limit(150)
def test_phase_spans_never_nest_under_their_own_name(served):
    _requests, spans, _tel = served
    by_thread = {}
    for r in spans:
        by_thread.setdefault((r["tid"], r["name"]), []).append((r["ts"], r["ts"] + r["dur_ms"] / 1e3))
    for (_tid, name), ivs in by_thread.items():
        ivs.sort()
        for (a0, a1), (b0, _b1) in zip(ivs, ivs[1:]):
            assert b0 >= a1 - 1e-3, f"{name} opened inside {name}"


# ------------------------------------------------------ (d) the compile repair


@limit(120)
def test_only_a_step_that_compiled_is_synced_and_gauged(monkeypatch):
    trainer, state, data = tiny_trainer()
    syncs, watched = [], []  # the loop thread's syncs; the ``fit-steps`` thread waits for every step
    real = jax.block_until_ready

    def counted(x):
        (watched if threading.current_thread().name == "fit-steps" else syncs).append(1)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", counted)

    def fit():
        tel = Telemetry(worker=0)
        with rec_mod.current(tel):
            out = trainer.fit(state_box[0], data, num_steps=3)
        state_box[0] = out[0]
        records = tel.drain_events()
        gauges = [r["name"] for r in records if r["kind"] == "gauge"]
        drains = [r.get("attrs", {}).get("why") for r in records if r["kind"] == "span" and r["name"] == "train.drain"]
        return gauges, drains

    state_box = [state]
    gauges, drains = fit()
    assert gauges.count("compile_time_ms") == 1 and gauges.count("step_time_ms") == 2
    assert drains == ["compile", "return"] and len(syncs) == 1 and len(watched) == 3
    # a warm trainer: step 0 of the next fit is an ordinary step
    del syncs[:]
    gauges, drains = fit()
    assert "compile_time_ms" not in gauges and gauges.count("step_time_ms") == 3
    assert drains == ["return"] and not syncs
    assert trainer.compile_counts["train_step"] == 1


# ------------------------------------------------------------- (e) the name lint


@pytest.mark.parametrize(
    "source, clean",
    [
        ("with tel.span('train.drain', step=1): pass", True),
        ("with tel.span('train.drian'): pass", False),
        ("with self.telemetry.span('serve.emit', tokens=3): pass", True),
        ("with self.telemetry.span('serve.emitt'): pass", False),
        ("with pf._tel.span('shard_batch', step=i): pass", True),
        # a gauge's name is not a span's
        ("with tel.span('step_time_ms'): pass", False),
        # not a recorder: out of scope
        ("m.span('whatever')", True),
    ],
)
def test_span_names_are_linted(source, clean):
    mod = load_tool("check_telemetry_names")
    found = mod.check_source(source, "<s>", mod.load_registry(REPO))
    assert (found == []) is clean, found


def test_every_span_call_in_the_package_is_registered():
    """``grep -rn "span(" maggy_tpu``: every literal name is in the registry,
    and every registered span has a call site (``record_span`` is one)."""
    called = set()
    for root, _dirs, files in os.walk(os.path.join(REPO, "maggy_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    called |= set(re.findall(r"\.(?:record_)?span\(\s*[\"']([\w.]+)[\"']", f.read()))
    assert called - registry.SPANS == set()
    assert registry.SPANS - called == set()
    assert load_tool("check_telemetry_names").main([]) == 0


def test_scopes_registry_is_closed_and_used():
    text = ""
    for path in ("models/transformer.py", "models/moe.py", "ops/sparse_select.py", "ops/eva.py", "ops/blockdiff.py", "train/trainer.py", "serve/engine.py"):
        with open(os.path.join(REPO, "maggy_tpu", path)) as f:
            text += f.read()
    used = set(re.findall(r"named_scope\(\"([\w.]+)\"\)", text))
    assert used - {"lm_head"} == set(registry.SCOPES)


def test_a_two_stream_layers_event_says_how_its_own_block_ran():
    """``attention.kernel`` of a block-diffusion layer names the form of the
    noised queries' own block beside the flash calls: the band kernels where
    the flash kernels run, the explicit mask where the dispatch fell to XLA (as
    it does here, off the chip); a layer of another kind does not carry it."""
    from maggy_tpu import telemetry
    from maggy_tpu.models import transformer
    from maggy_tpu.ops import blockdiff

    tel = Telemetry(worker="t")
    big, kv = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16), jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16)
    pos = jnp.tile(jnp.arange(32, dtype=jnp.int32), (1, 2))
    seg = jnp.repeat(jnp.asarray([[1, 2]], jnp.int32), 32, axis=1)
    q, k = jnp.ones((1, 128, 2, 64), jnp.float32), jnp.ones((1, 128, 1, 64), jnp.float32)
    with telemetry.current(tel):
        transformer.record_attention_kernel("flash", big, kv, seg, block=4)
        transformer.record_attention_kernel("flash", big, kv, seg)
        transformer.auto_blockdiff_attention(q, k, k, pos, seg, blockdiff.layout(pos, seg, 4), block=4)
    on_chip, plain, here = [e["attrs"] for e in tel.drain_events() if e["name"] == "attention.kernel"]
    assert (on_chip["kernel"], on_chip["form"], on_chip["calls"], on_chip["own_block"]) == ("flash", "blockdiff", 2, "kernel")
    assert (here["kernel"], here["form"], here["own_block"]) == ("xla_dense", "blockdiff", "xla") and "cpu" in here["reason"]
    assert "own_block" not in plain and "form" not in plain
    assert blockdiff.untileable(8192, 128, 4, compiled=True) is None


# --------------------------------------------------------- the two alert repairs


@limit(120)
def test_prefix_admit_does_not_count_as_an_admit_compile():
    """The admit body traced inside the prefix-admit program counts towards
    that program: ``admit`` compiles once and the sentinel stays quiet."""
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.sharding import unbox
    from maggy_tpu.serve import Engine, Request, SamplingParams, Scheduler

    cfg = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = unbox(Decoder(cfg).init(jax.random.key(7), jnp.zeros((1, 8), jnp.int32))["params"])
    for paged in (True, False):
        engine = Engine(cfg, params, num_slots=4, paged=paged, prefix_reuse=True)
        sched = Scheduler(engine)  # not started: ticks driven by hand
        shared = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2]
        now = time.time()
        engine.admit(Request(prompt=shared + [11], params=SamplingParams(max_new=4)))
        engine.step()
        sched._metrics_tick(now)
        # a prefix hit, then one with a longer suffix (another bucket of the
        # prefix-admit ladder): both trace the admit body inside their program
        engine.admit(Request(prompt=shared + [12, 13], params=SamplingParams(max_new=4)))
        engine.admit(Request(prompt=shared + list(range(20, 40)), params=SamplingParams(max_new=4)))
        engine.step()
        sched._metrics_tick(now + 1)
        counts = engine.compile_counts
        assert engine.prefix_hits == 2 and counts["prefix_admit"] >= 2
        assert counts["admit"] == 1 and counts["decode"] == 1
        assert sched.sentinel.firing() == []


@limit(60)
def test_tpot_burn_waits_for_the_engines_warm_up():
    from maggy_tpu.telemetry import timeseries
    from maggy_tpu.telemetry.alerts import BY_NAME, AlertEvaluator
    from maggy_tpu.telemetry.histogram import LatencyHistogram

    assert BY_NAME["alert.tpot_slo_burn"].after_warmup
    assert not BY_NAME["alert.ttft_slo_burn"].after_warmup
    store = timeseries.SeriesStore()
    ev = AlertEvaluator(store, None, scope="worker")
    hist = LatencyHistogram()
    t0 = 1000.0
    for i in range(40):  # every token slower than the 200 ms bound
        hist.observe(900.0)
        store.series("serve.tpot_ms", "hist").append(t0 + i, hist.to_dict())
        ev.evaluate(t0 + i, warmed_up=False)
        assert ev.firing() == [], "evaluated while the engine was still compiling"
    ev.evaluate(t0 + 40, warmed_up=True)
    assert [a["alert"] for a in ev.firing()] == ["alert.tpot_slo_burn"]
    # a new compile re-opens the window: the alert resolves instead of sticking
    ev.evaluate(t0 + 41, warmed_up=False)
    assert ev.firing() == []


@limit(60)
def test_engine_warm_up_follows_its_compiles():
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.sharding import unbox
    from maggy_tpu.serve import Engine, Request, SamplingParams
    from maggy_tpu.serve.engine import WARMUP_QUIET_S

    cfg = DecoderConfig.tiny(max_seq_len=64, dtype=jnp.float32)
    params = unbox(Decoder(cfg).init(jax.random.key(7), jnp.zeros((1, 8), jnp.int32))["params"])
    engine = Engine(cfg, params, num_slots=2)
    assert not engine.warmed_up(time.time() + 10 * WARMUP_QUIET_S)  # decode never compiled
    engine.admit(Request(prompt=[1, 2, 3], params=SamplingParams(max_new=4)))
    engine.step()
    last = engine.last_compile_ts
    assert not engine.warmed_up(last + WARMUP_QUIET_S - 1)
    assert engine.warmed_up(last + WARMUP_QUIET_S)
    engine.step()  # no compile: the clock does not move
    assert engine.last_compile_ts == last
    engine.admit(Request(prompt=list(range(1, 30)), params=SamplingParams(max_new=4)))  # a new prefill bucket
    assert engine.last_compile_ts > last
