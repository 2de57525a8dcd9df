"""Host-overlap subsystem (ISSUE 5, docs/performance.md): DevicePrefetcher
semantics + the fit overlap win, skip(n) resume fast paths, the lagged
metrics drain's broadcast contract, evaluate's single host sync, and the
check_host_sync lint."""

import textwrap
import threading
import time

import jax
import numpy as np
import optax
import pytest

from maggy_tpu import telemetry
from maggy_tpu.exceptions import EarlyStopException
from maggy_tpu.models import Decoder, DecoderConfig
from maggy_tpu.reporter import Reporter
from maggy_tpu.train import DevicePrefetcher, TrainContext, skip_batches
from maggy_tpu.train.data import batch_iterator, synthetic_lm_batches


def _tiny_trainer(seed=0):
    cfg = DecoderConfig.tiny()
    ctx = TrainContext.create("dp")
    trainer = ctx.trainer(Decoder(cfg), optax.adamw(3e-3))
    data = synthetic_lm_batches(cfg.vocab_size, 8, 16, seed=seed)
    state = trainer.make_state(jax.random.key(0), next(
        synthetic_lm_batches(cfg.vocab_size, 8, 16, seed=seed)
    ))
    return trainer, state, data


# ------------------------------------------------------------ DevicePrefetcher


def test_prefetcher_preserves_order_and_caps_consumption():
    pulled = {"n": 0}

    def src():
        i = 0
        while True:
            pulled["n"] += 1
            yield i
            i += 1

    pf = DevicePrefetcher(src(), put=lambda x: x * 10, depth=2, max_items=5)
    out = [next(pf) for _ in range(5)]
    assert out == [0, 10, 20, 30, 40]
    with pytest.raises(StopIteration):
        next(pf)
    pf.close()
    # max_items bounds SOURCE consumption exactly: a shared iterator keeps
    # its position across consecutive fit calls
    assert pulled["n"] == 5


def test_prefetcher_relays_source_and_put_errors():
    def exploding():
        yield 1
        raise RuntimeError("loader died")

    pf = DevicePrefetcher(exploding(), put=lambda x: x, depth=2)
    assert next(pf) == 1
    with pytest.raises(RuntimeError, match="loader died"):
        next(pf)
    with pytest.raises(RuntimeError, match="loader died"):
        next(pf)  # terminal: the error sticks, no hang on an empty queue
    pf.close()

    pf2 = DevicePrefetcher(iter([1, 2]), put=lambda x: 1 / 0, depth=2)
    with pytest.raises(ZeroDivisionError):
        next(pf2)
    pf2.close()


def test_prefetcher_skip_delegates_before_start():
    it = batch_iterator({"x": np.arange(120).reshape(30, 4)}, 5, seed=2)
    pf = DevicePrefetcher(it, put=lambda b: b, depth=2)
    assert pf.skip(7) == 7
    assert it.batches_materialized == 0  # index advance, nothing gathered
    ref = batch_iterator({"x": np.arange(120).reshape(30, 4)}, 5, seed=2)
    skip_batches(ref, 7)
    np.testing.assert_array_equal(next(pf)["x"], next(ref)["x"])
    pf.close()


def test_prefetcher_records_telemetry():
    tel = telemetry.Telemetry(worker="t")
    pf = DevicePrefetcher(
        iter(range(4)), put=lambda x: x, depth=2, telemetry_recorder=tel
    )
    for _ in range(4):
        next(pf)
    pf.close()
    g = tel.snapshot()["gauges"]
    assert "input_wait_ms" in g and "prefetch_depth" in g
    spans = [e["name"] for e in tel.drain_events() if e["kind"] == "span"]
    assert spans.count("shard_batch") == 4


# --------------------------------------------------------------- skip(n) paths


def test_skip_batches_falls_back_to_next_for_generators():
    def gen():
        yield from range(10)

    g = gen()
    assert skip_batches(g, 3) == 3
    assert next(g) == 3
    assert skip_batches(g, 100) == 6  # short on exhaustion


def test_batch_iterator_skip_matches_next_across_epochs():
    arrays = {"x": np.arange(80).reshape(20, 4)}
    a = batch_iterator(arrays, 8, seed=7)  # 2 batches/epoch
    b = batch_iterator(arrays, 8, seed=7)
    for _ in range(11):
        next(a)
    assert b.skip(11) == 11
    assert b.batches_materialized == 0
    for _ in range(4):
        np.testing.assert_array_equal(next(a)["x"], next(b)["x"])


def test_native_loader_skip_avoids_gathers():
    from maggy_tpu.train.native_loader import NativeBatchLoader

    arrays = {"x": np.arange(4000).reshape(1000, 4)}
    a = NativeBatchLoader(arrays, 10, seed=3)
    b = NativeBatchLoader(arrays, 10, seed=3)
    try:
        for _ in range(250):
            next(a)
        assert b.skip(250) == 250
        for _ in range(3):
            np.testing.assert_array_equal(next(a)["x"], next(b)["x"])
        time.sleep(0.2)  # let the producer run ahead to its bound
        # only the pre-skip in-flight/queued batches plus the 3 consumed and
        # the refilled prefetch window were ever gathered — not 250
        assert b.gathers <= 12, b.gathers
    finally:
        a.close()
        b.close()


def test_fit_resume_skips_without_materializing(tmp_path):
    """ACCEPTANCE (satellite): fit(resume="auto") fast-forwards a skip()-
    capable loader by index — the skipped range is never gathered."""
    from maggy_tpu.train.checkpoint import Checkpointer

    cfg = DecoderConfig.tiny()
    ctx = TrainContext.create("dp")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (64, 16)).astype(np.int32)

    trainer, state, _ = _tiny_trainer()
    ckpt = Checkpointer(str(tmp_path / "ck"), async_save=False)
    loader = batch_iterator({"tokens": toks}, 8, seed=1)
    state, _ = trainer.fit(
        state, loader, num_steps=4, checkpointer=ckpt, checkpoint_every=2
    )
    assert ckpt.latest_step() == 4

    trainer2, state2, _ = _tiny_trainer()
    fresh = batch_iterator({"tokens": toks}, 8, seed=1)
    state2, out = trainer2.fit(
        state2, fresh, num_steps=10, checkpointer=ckpt, resume="auto"
    )
    ckpt.close()
    assert out["resumed_from"] == 4.0
    assert int(state2.step) == 10
    # 6 remaining steps materialized; the 4 skipped batches never were
    assert fresh.batches_materialized == 6, fresh.batches_materialized


# ---------------------------------------------------------- fit overlap order


def _logged_fit(trainer, state, data, n, prefetch):
    """fit() with every pull from the loader and every dispatch of a step
    logged into one list. With a prefetcher, a dispatch first waits (bounded)
    for the next batch's pull: the producer thread owes it without the loop's
    help. Returns (state, log, whether every such wait was met)."""
    log, cond = [], threading.Condition()

    def loader(src):
        k = 0
        while True:
            with cond:
                log.append(("pull", k))
                cond.notify_all()
            yield next(src)
            k += 1

    step = trainer.step
    met = []

    def logged_step(state, batch):
        with cond:
            i = sum(1 for kind, _ in log if kind == "step")
            if prefetch and i + 1 < n:
                met.append(cond.wait_for(lambda: ("pull", i + 1) in log, timeout=30))
            log.append(("step", i))
        return step(state, batch)

    trainer.step = logged_step
    try:
        state, _ = trainer.fit(state, loader(data), num_steps=n, prefetch=prefetch)
    finally:
        del trainer.step
    return state, log, all(met)


def test_fit_prefetch_pulls_next_batch_before_dispatch():
    """ACCEPTANCE: through the prefetcher batch i+1 is pulled (and placed, by
    the producer thread) before step i is dispatched, so the loader's time
    hides behind the step's; without it the loop pulls batch i+1 only after
    it dispatched step i. Either way exactly ``num_steps`` batches are
    consumed. No clock: what the overlap buys on the chip is the benchmark's
    ``train.idle_in_input_share``."""
    trainer, state, data = _tiny_trainer()
    n = 6
    pulls = [("pull", k) for k in range(n)]

    state, log, _ = _logged_fit(trainer, state, data, n, prefetch=0)
    assert log == [e for k in range(n) for e in (("pull", k), ("step", k))]

    state, log, met = _logged_fit(trainer, state, data, n, prefetch=2)
    assert met, log
    assert [e for e in log if e[0] == "pull"] == pulls  # no more than n
    assert [e for e in log if e[0] == "step"] == [("step", k) for k in range(n)]
    for i in range(n - 1):
        assert log.index(("pull", i + 1)) < log.index(("step", i)), (i, log)


# -------------------------------------------------------- lagged metrics drain


class _RecordingReporter:
    def __init__(self):
        self.calls = []

    def broadcast(self, value, step=None):
        self.calls.append((value, step))


def test_fit_broadcasts_lag_bounded_and_monotonic():
    trainer, state, data = _tiny_trainer()
    rep = _RecordingReporter()
    tel = telemetry.Telemetry(worker="t")
    with telemetry.current(tel):
        state, _ = trainer.fit(
            state, data, num_steps=12, reporter=rep,
            report_every=2, metrics_window=2,
        )
    steps = [s for _, s in rep.calls]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    assert all(np.isfinite(v) for v, _ in rep.calls)
    # every broadcast carries the step its metric was measured at, at most
    # `window` behind the boundary it was emitted from
    boundaries = [i + 1 for i in range(12) if (i + 1) % 2 == 0]
    assert len(rep.calls) >= len(boundaries) - 1  # first may defer (priming)
    lag = tel.snapshot()["gauges"]["metrics_lag"]
    assert 0 <= lag <= 2


def test_fit_window_zero_restores_synchronous_broadcasts():
    trainer, state, data = _tiny_trainer()
    rep = _RecordingReporter()
    state, _ = trainer.fit(
        state, data, num_steps=6, reporter=rep,
        report_every=2, metrics_window=0,
    )
    # fresh value at every boundary: steps are exactly the boundary steps
    assert [s for _, s in rep.calls] == [2, 4, 6]


def test_fit_early_stop_fires_through_lagged_drain():
    """ACCEPTANCE: the driver's early-stop flag still interrupts fit at a
    broadcast boundary with the lagged drain (the flag is what HPO
    executors set via heartbeat; EarlyStopException is the interrupt)."""
    trainer, state, data = _tiny_trainer()
    reporter = Reporter()
    reporter.early_stop()
    with pytest.raises(EarlyStopException):
        trainer.fit(
            state, data, num_steps=30, reporter=reporter,
            report_every=1, metrics_window=2,
        )
    # the interrupt landed within the lag bound of the first boundary that
    # had an aged ref: a 30-step run never completes
    _, metric, step, _ = reporter.get_data()
    assert step <= 2 + 2  # first primed boundary + window


# ----------------------------------------------------- evaluate's single sync


class _CountingScalar:
    """Device-scalar stand-in whose float() conversions are counted —
    on-device adds must NOT sync."""

    def __init__(self, val, counter):
        self.val = val
        self.counter = counter

    def __add__(self, other):
        return _CountingScalar(
            self.val + getattr(other, "val", other), self.counter
        )

    __radd__ = __add__

    def __float__(self):
        self.counter["n"] += 1
        return float(self.val)


def test_evaluate_accumulates_on_device_single_conversion():
    trainer, state, data = _tiny_trainer()
    trainer.evaluate(state, data, 1)  # compile
    real_step = trainer._eval_loss_step
    counter = {"n": 0}

    def wrapped(s, b):
        return _CountingScalar(np.asarray(real_step(s, b)), counter)

    trainer._eval_loss_step = wrapped
    try:
        res = trainer.evaluate(state, data, 5)
    finally:
        trainer._eval_loss_step = real_step
    assert np.isfinite(res["loss"])
    # regression guard: the old loop float()ed every batch (5 syncs)
    assert counter["n"] == 1, counter


# -------------------------------------------------------- check_host_sync lint


def _lint():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_host_sync", os.path.join(repo, "tools", "check_host_sync.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_host_sync_lint_flags_and_allowlists():
    lint = _lint()
    bad = textwrap.dedent(
        """
        def f(xs, m):
            for x in xs:  # hot-loop
                a = float(x)
                b = int(x)
                c = np.asarray(x)
                d = x.item()
        """
    )
    hits = lint.find_violations(bad, "<bad>")
    assert len(hits) == 4, hits

    ok = textwrap.dedent(
        """
        def f(xs):  # hot-loop
            for x in xs:
                a = float(x)  # sync: ok — lagged ref
            return np.asarray(xs)  # sync: ok — outside-loop epilogue
        """
    )
    assert lint.find_violations(ok, "<ok>") == []

    unmarked = "def f(xs):\n    return [float(x) for x in xs]\n"
    assert lint.find_violations(unmarked, "<unmarked>") == []

    assert lint.has_hot_region(ok, "<ok>", "f")
    assert not lint.has_hot_region(unmarked, "<unmarked>", "f")


def test_host_sync_lint_tree_clean():
    """tools/check_host_sync.py runs clean over maggy_tpu/ (wired into
    tier-1, beside the exception-hygiene / bare-print / docs-nav lints) —
    and the required hot-loop regions are present."""
    import os

    lint = _lint()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    violations = lint.check_tree(os.path.join(repo, "maggy_tpu"))
    assert violations == [], violations


def test_host_sync_lint_detects_missing_required_region(tmp_path):
    lint = _lint()
    fake = tmp_path / "maggy_tpu" / "serve"
    fake.mkdir(parents=True)
    (fake / "engine.py").write_text("def step(self):\n    return 1\n")
    violations = lint.check_tree(str(tmp_path / "maggy_tpu"))
    assert any("required hot-loop marker" in what for _, _, what in violations)
