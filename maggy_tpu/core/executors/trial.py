"""Trial executor — the worker-side loop for HPO/ablation/single-run experiments.

Capability parity with the reference ``trial_executor_fn``
(core/executors/trial_executor.py:35-213): register → heartbeat → loop
{blocking get_suggestion → per-trial logdir + .hparams.json → signature-based
kwarg injection → train_fn → normalize return value → finalize_metric} until
GSTOP. Early stops arrive as EarlyStopException out of ``reporter.broadcast``
and keep the last metric (trial_executor.py:194-196).

TPU-native differences: the worker holds a lease on a disjoint device group;
train_fn errors are reported to the driver as errored trials instead of
killing a Spark task.

The lease reaches the train_fn only through what it asks for: ``ctx`` (a
``TrainContext`` whose mesh spans exactly the leased devices — ``ctx.trainer``
and ``ctx.shard`` place everything there) or ``devices`` (the raw list, for a
``jax.device_put``/sub-mesh of its own). A train_fn that asks for neither
computes on JAX's default device — chip 0 on a TPU host — whatever its lease
says, so concurrent trials would all share that one chip.
"""

from __future__ import annotations

import os
import socket as socket_mod
import traceback
from typing import Any, Callable, Dict, Optional

from maggy_tpu import constants, util
from maggy_tpu.core import rpc
from maggy_tpu.core.env import EnvSing
from maggy_tpu.exceptions import EarlyStopException, WorkerLost
from maggy_tpu.reporter import Reporter, capture_prints

# keys stripped from trial params before they reach the train_fn as hparams
# ("budget" stays available via the dedicated kwarg and in hparams for ASHA-style
# train_fns; "run"/"rep" are pure bookkeeping nonces)
_CONTROL_KEYS = ("run", "rep")


def trial_executor_fn(
    train_fn: Callable,
    config,
    app_id: str,
    run_id: int,
    partition_id: int,
    server_addr,
    secret: str,
    devices: Optional[list] = None,
    resolve: Optional[Callable[[Dict[str, Any], Dict[str, Any]], Dict[str, Any]]] = None,
) -> Callable[[], None]:
    # one lease-wide TrainContext shared by every trial this worker runs
    # (same devices -> same mesh; built only if the train_fn asks for it,
    # so metric-only train_fns never touch jax). ``devices`` may be a
    # zero-arg callable (pod workers' env-spec lease, core/pod.py
    # _worker_devices) resolved here — also lazily, same reason
    _ctx_cache: Dict[str, Any] = {}

    def _lease_devices():
        if "devices" not in _ctx_cache:
            _ctx_cache["devices"] = devices() if callable(devices) else devices
        return _ctx_cache["devices"]

    def _lease_ctx():
        if "ctx" not in _ctx_cache:
            from maggy_tpu.train.trainer import TrainContext

            # honor a sharding preset configured on the experiment; default dp
            preset = getattr(config, "sharding", None) or "dp"
            _ctx_cache["ctx"] = TrainContext.create(
                preset, devices=_lease_devices() or None
            )
        return _ctx_cache["ctx"]

    def _executor() -> None:
        from maggy_tpu import telemetry

        env = EnvSing.get_instance()
        exp_dir = env.experiment_dir(app_id, run_id)
        log_file = os.path.join(exp_dir, f"executor_{partition_id}.log")
        reporter = Reporter(log_file=log_file, partition_id=partition_id)
        # per-worker recorder, ambient for this thread: Trainer.fit and the
        # Checkpointer inside the train_fn record into it; the heartbeat
        # attaches snapshots and flushes it to the JSONL sink every beat
        tel = telemetry.worker_telemetry(partition_id, exp_dir, role="trial", env=env)
        telemetry.set_current(tel)
        client = rpc.Client(
            server_addr, partition_id, secret, hb_interval=config.hb_interval,
            telemetry=tel,
        )
        try:
            client.register(
                meta={
                    "host": socket_mod.gethostname(),
                    # a callable lease is deliberately NOT resolved here —
                    # registration must never touch the jax backend
                    "devices": (
                        [f"lease:{os.environ.get('MAGGY_TPU_WORKER_DEVICES', '?')}"]
                        if callable(devices)
                        else [str(d) for d in (devices or [])]
                    ),
                }
            )
            client.start_heartbeat(reporter)
            while True:
                reply = client.get_suggestion()
                if reply["type"] == "GSTOP":
                    break
                _run_trial(reply, client, reporter, env)
        finally:
            client.stop()
            reporter.close()
            telemetry.set_current(None)
            tel.close()

    def _run_trial(reply: Dict[str, Any], client: rpc.Client, reporter: Reporter, env) -> None:
        from maggy_tpu import tensorboard as tb

        trial_id, params = reply["trial_id"], dict(reply["params"])
        reporter.reset(trial_id)
        trial_dir = env.trial_dir(app_id, run_id, trial_id)
        tb._register(trial_dir)  # registry only; persistence is the line below
        try:
            env.dump(util._jsonify(params), os.path.join(trial_dir, constants.HPARAMS_FILE))
        except OSError:
            pass

        hparams = {
            **dict(getattr(config, "hparams", None) or {}),
            **{k: v for k, v in params.items() if k not in _CONTROL_KEYS},
        }
        import inspect as _inspect

        fn_params = _inspect.signature(train_fn).parameters
        available = {
            "hparams": hparams,
            "reporter": reporter,
            "model": getattr(config, "model", None),
            "dataset": getattr(config, "dataset", None),
            # resolved only when asked for: a callable (env-spec) lease
            # touches the jax backend, and metric-only train_fns never do
            "devices": _lease_devices() if "devices" in fn_params else None,
            "trial_dir": trial_dir,
            "budget": params.get("budget"),
        }
        if resolve is not None:
            # experiment-kind hook: ablation swaps in per-trial model/dataset
            available = resolve(params, available)
        if "ctx" in fn_params:
            # lease-wide TrainContext, built only when the train_fn asks for
            # it so metric-only train_fns never touch jax
            available["ctx"] = _lease_ctx()
        kwargs = util.inject_kwargs(train_fn, available)

        from maggy_tpu import telemetry

        tel = telemetry.get()
        metric: Optional[float] = None
        outputs: Dict[str, Any] = {}
        error: Optional[str] = None
        early = False
        try:
            # train_fn prints ship to the driver with the heartbeat logs
            # (reference trial_executor.py:93-103)
            with tel.span("trial", trial_id=trial_id), capture_prints(reporter):
                retval = train_fn(**kwargs)
            metric = util.handle_return_val(
                retval, trial_dir, config.optimization_key
            )
            outputs = retval if isinstance(retval, dict) else {config.optimization_key: metric}
        except EarlyStopException as e:
            early = True
            metric = e.metric if e.metric is not None else reporter.get_metric()
            outputs = {config.optimization_key: metric}
            reporter.log(f"Trial {trial_id} early-stopped at metric {metric}")
        except WorkerLost:
            # worker death (preemption / chaos kill), not a trial error: no
            # FINAL goes out — the executor dies with it and the driver
            # requeues the in-flight trial and respawns/quarantines the slot
            tb._unregister()
            raise
        except Exception as e:  # noqa: BLE001 - errored trial, not a dead worker
            error = f"{type(e).__name__}: {e}"
            reporter.log(f"Trial {trial_id} failed:\n{traceback.format_exc()}")

        tb._unregister()
        tel.count("trials_errored" if error else "trials_done")
        tel.flush()  # trial boundary: events are durable before FINAL ships
        client.finalize_metric(
            trial_id,
            metric,
            outputs=util._jsonify(outputs),
            error=error,
            early_stopped=early,
        )

    return _executor
