"""TPU-VM pod execution: every host runs the same user script.

The reference ships pickled closures to Spark executors over the JVM
(spark_driver.py:136-145). On a TPU pod that machinery is unnecessary — the
standard JAX SPMD launch already starts one identical Python process per host,
so the train_fn exists everywhere by construction. ``lagom(train_fn,
DistributedConfig(...))`` therefore behaves per role:

* **process 0** (or ``MAGGY_TPU_ROLE=driver``): full driver + its own worker.
* **worker hosts** (``MAGGY_TPU_ROLE=worker``, or a non-zero
  ``jax.process_index()``): skip the driver, connect a worker to the process-0
  driver over the host network, run the executor, return the local outputs.

Bootstrap contract: on a pod with ``data_plane="auto"`` the launcher (or the
top of the user script) calls ``jax.distributed.initialize()`` — standard JAX
practice — *before* ``lagom``. The framework never initializes it late (the
backend is already up by the time executors run) and fails loudly instead of
silently training unsynchronized replicas. The driver address travels
out-of-band: ``MAGGY_TPU_DRIVER=host:port`` + ``MAGGY_TPU_SECRET=...`` env
vars, or ``DistributedConfig(driver_addr=...)``; the driver logs its reachable
address at startup for launcher tooling.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, NamedTuple, Optional, Tuple


def initialize_data_plane(
    coordinator: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Form the global JAX data plane — call at the very top of a pod script,
    before any other JAX use (the reference's MASTER_ADDR/NCCL rendezvous,
    torch_dist_executor.py:121-140, as one explicit bootstrap call).

    Arguments default from the launcher environment (MAGGY_TPU_COORDINATOR /
    NUM_EXECUTORS / PARTITION, exported by ``python -m maggy_tpu.run
    --global-mesh``); returns False (no-op) when no coordinator is configured,
    so the same script runs single-process unchanged. On a CPU fleet (tests,
    dev boxes) cross-process collectives go through gloo automatically.
    """
    coordinator = coordinator or os.environ.get("MAGGY_TPU_COORDINATOR")
    if not coordinator:
        return False
    num_processes = int(
        num_processes
        if num_processes is not None
        else os.environ.get("MAGGY_TPU_NUM_EXECUTORS", "1")
    )
    process_id = int(
        process_id
        if process_id is not None
        else os.environ.get("MAGGY_TPU_PARTITION", "0")
    )
    if jax_backend_initialized():
        raise RuntimeError(
            "initialize_data_plane() must run before any JAX backend use "
            "(move it to the top of the script, before model/data imports "
            "that touch jax)."
        )
    import jax

    from maggy_tpu import telemetry

    tel = telemetry.get()
    # multi-process CPU collectives need the gloo transport; harmless when the
    # resolved platform is TPU (the knob only affects the CPU backend), and the
    # platform cannot be resolved before initialize without starting a backend
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    with tel.span("data_plane_init", coordinator=coordinator, rank=process_id):
        jax.distributed.initialize(
            coordinator, num_processes=num_processes, process_id=process_id
        )
        # Create the backend NOW: backend creation runs a global device-exchange
        # barrier across all processes, so every rank must reach it at the same
        # program point. Deferring it lets rank roles diverge — e.g. the driver
        # touching jax before its RPC server is up while workers wait on that
        # server before touching jax — a circular wait only broken by a timeout.
        jax.devices()
    return True


def jax_backend_initialized() -> bool:
    """True if XLA backends already exist (without creating them). jax has no
    public probe for this; if the private one moves, this raises instead of
    guessing an answer."""
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def driver_address(config) -> Optional[str]:
    """The single source of pod-mode detection for driver AND workers."""
    return os.environ.get("MAGGY_TPU_DRIVER") or getattr(config, "driver_addr", None)


def discover_driver(app_id: str) -> Optional[dict]:
    """Look up a running driver's {host, port, secret} by app id in the Env's
    driver registry (shared storage) — the fallback when MAGGY_TPU_DRIVER /
    MAGGY_TPU_SECRET are not set. Mirrors the reference's Hopsworks REST
    driver discovery (environment/hopsworks.py:136-190).

    Only scope="pod" records qualify for worker bootstrap: "local" records
    advertise a loopback address for same-host monitor attach and would
    misdirect a remote worker to its own machine.

    Staleness: a SIGKILLed driver cannot unregister, so a record can outlive
    its driver. A restarted driver overwrites the record at init; a worker
    that discovered a dead record fails at the connect deadline with an error
    naming the registry path (``_connect_with_deadline`` below)."""
    from maggy_tpu.core.env import EnvSing

    rec = EnvSing.get_instance().lookup_driver(app_id)
    if rec is not None and rec.get("scope", "pod") != "pod":
        return None
    return rec


def _parse_addr(addr: str) -> Tuple[str, int]:
    host, sep, port = addr.rpartition(":")
    if not sep or not port.isdigit():
        raise ValueError(
            f"MAGGY_TPU_DRIVER/driver_addr must be 'host:port', got {addr!r}"
        )
    return host or "127.0.0.1", int(port)


class WorkerRole(NamedTuple):
    host: str
    port: int
    secret: str
    via_registry: bool = False


def worker_role(config) -> Optional[WorkerRole]:
    """Return a :class:`WorkerRole` if this process should run as a pod
    worker, else None (run the driver)."""
    explicit_role = os.environ.get("MAGGY_TPU_ROLE")
    if explicit_role == "driver":
        return None
    addr = driver_address(config)
    discovered = None
    app_id = os.environ.get("MAGGY_TPU_APP_ID")
    if not addr and app_id:
        # No explicit address: poll the shared-storage driver registry. An
        # explicit worker waits out the driver's JAX bring-up (the record is
        # written only once the RPC server is up) — without the wait, a
        # worker that checks early would silently become a second driver and
        # deadlock the reservation barrier.
        deadline = time.time() + (
            float(os.environ.get("MAGGY_TPU_CONNECT_TIMEOUT", "120"))
            if explicit_role == "worker"
            else 0.0
        )
        while True:
            discovered = discover_driver(app_id)
            if discovered or time.time() >= deadline:
                break
            time.sleep(0.5)
        if discovered:
            addr = f"{discovered['host']}:{discovered['port']}"
    if not addr:
        if explicit_role == "worker":
            raise RuntimeError(
                "MAGGY_TPU_ROLE=worker but no driver address: set "
                "MAGGY_TPU_DRIVER=host:port, or make the driver's registry "
                "record reachable (MAGGY_TPU_APP_ID + the driver's "
                "MAGGY_TPU_LOG_ROOT on shared storage)."
            )
        return None
    if explicit_role != "worker":
        # Infer from the JAX process index. Meaningful only when
        # jax.distributed is already up; a single-process backend (dev box,
        # driver host in tests) infers "driver". A real pod must therefore
        # either initialize jax.distributed before lagom() or set
        # MAGGY_TPU_ROLE per host — otherwise every host becomes a driver and
        # the run fails loudly at the reservation barrier.
        import jax

        if jax.process_index() == 0:
            return None
    # via_registry marks the ADDRESS as registry-sourced (drives the
    # stale-record hint on connect timeout) — a registry-sourced secret with
    # an env-var address must not blame the registry for a bad address
    addr_from_registry = discovered is not None
    secret = os.environ.get("MAGGY_TPU_SECRET", "")
    if not secret:
        # the registry can supply the secret even when the address came from
        # MAGGY_TPU_DRIVER/driver_addr
        if discovered is None and app_id:
            discovered = discover_driver(app_id)
        if discovered:
            secret = discovered.get("secret", "")
    if not secret:
        raise RuntimeError(
            "Pod worker role needs MAGGY_TPU_SECRET (printed by the driver) "
            "or a driver-registry record reachable via MAGGY_TPU_APP_ID."
        )
    host, port = _parse_addr(addr)
    return WorkerRole(host, port, secret, via_registry=addr_from_registry)


def partition_id() -> int:
    if "MAGGY_TPU_PARTITION" in os.environ:
        return int(os.environ["MAGGY_TPU_PARTITION"])
    import jax

    return jax.process_index()


def _connect_with_deadline(
    host: str,
    port: int,
    pid: int,
    secret: str,
    deadline_s: float,
    hb_interval: float = 1.0,  # rpc.Client's own default
    via_registry: bool = False,
):
    """Pod hosts start simultaneously; the driver may need many seconds of JAX
    bring-up before it listens — retry well past Client's own 3 attempts.
    ``via_registry`` marks an address that came from the discovery registry so
    the timeout error can point at a possibly-stale record."""
    from maggy_tpu.core import rpc
    from maggy_tpu.exceptions import RpcError

    deadline = time.time() + deadline_s
    delay = 0.2
    while True:
        try:
            return rpc.Client((host, port), pid, secret, hb_interval)
        except RpcError as e:
            if time.time() > deadline:
                hint = ""
                if via_registry:
                    from maggy_tpu.core.env import EnvSing

                    app_id = os.environ.get("MAGGY_TPU_APP_ID", "<app>")
                    hint = (
                        f" (address came from the driver registry "
                        f"{EnvSing.get_instance().driver_registry_path(app_id)};"
                        f" the record may be stale — a SIGKILLed driver cannot"
                        f" unregister)"
                    )
                raise RpcError(
                    f"Could not reach driver at {host}:{port} within "
                    f"{deadline_s:.0f}s{hint}: {e}"
                ) from e
            time.sleep(delay)
            delay = min(delay * 1.5, 5.0)


def _bootstrap_ids(
    host: str, port: int, pid: int, secret: str, via_registry: bool
) -> Tuple[str, int]:
    """Fetch the driver's app/run ids so this worker's artifacts land in the
    driver's experiment directory (env vars override)."""
    from maggy_tpu import util

    connect_timeout = float(os.environ.get("MAGGY_TPU_CONNECT_TIMEOUT", "120"))
    app_id = os.environ.get("MAGGY_TPU_APP_ID")
    run_id = os.environ.get("MAGGY_TPU_RUN_ID")
    if app_id is None or run_id is None:
        probe = _connect_with_deadline(host, port, pid, secret, connect_timeout,
                                       via_registry=via_registry)
        try:
            cfg_reply = probe._request({"type": "EXEC_CONFIG"})
            app_id = app_id or cfg_reply.get("app_id") or util.new_app_id()
            run_id = run_id or cfg_reply.get("run_id") or 1
        finally:
            probe.stop()
    return app_id, int(run_id)


def run_worker(
    train_fn: Callable, config, host: str, port: int, secret: str,
    via_registry: bool = False,
) -> Any:
    """Run this process as one pod worker; returns the worker's outputs."""
    from maggy_tpu.core.executors.distributed import dist_executor_fn

    pid = partition_id()
    app_id, run_id = _bootstrap_ids(host, port, pid, secret, via_registry)
    executor = dist_executor_fn(
        train_fn=train_fn,
        config=config,
        app_id=app_id,
        run_id=run_id,
        partition_id=pid,
        server_addr=(host, port),
        secret=secret,
        devices=None,  # pod worker spans its host's devices
        via_registry=via_registry,
    )
    executor()
    return {"role": "worker", "partition_id": pid}


def _worker_devices():
    """This trial worker's device lease. Default (None): span the host (one
    worker per host). MAGGY_TPU_WORKER_DEVICES="0,1" leases a subset of
    jax.local_devices() so several worker processes can share one host, each
    trial training on its own sub-slice — the trial ↔ device-lease model the
    local (thread) executors get from devices_per_trial, extended to pod
    workers. CPU/GPU hosts only, or TPU processes already chip-partitioned
    by the platform (TPU_VISIBLE_CHIPS etc.): a plain TPU runtime is
    host-exclusive, so two unpartitioned processes cannot both initialize it
    (``python -m maggy_tpu.run`` refuses that launch; on one TPU host the
    supported shape is one process whose thread executors lease the chips).

    Returns None (no lease) or a zero-arg CALLABLE resolving to the device
    list — deferred so the worker never touches the jax backend before it
    registers with the driver (executors keep jax lazy by design,
    core/executors/trial.py)."""
    spec = os.environ.get("MAGGY_TPU_WORKER_DEVICES", "").strip()
    if not spec:
        return None
    # everything that needs no jax validates EAGERLY: a typo'd env var must
    # fail at worker startup, not after the worker has registered and been
    # handed a trial (which would strand that trial until worker_timeout —
    # and loop forever under --respawn)
    try:
        idxs = [int(i) for i in spec.split(",")]
    except ValueError as e:
        raise RuntimeError(
            f"MAGGY_TPU_WORKER_DEVICES={spec!r} is not a comma-separated "
            f"list of local device indices: {e}"
        ) from e
    if len(set(idxs)) != len(idxs) or any(i < 0 for i in idxs):
        raise RuntimeError(
            f"MAGGY_TPU_WORKER_DEVICES={spec!r} must name distinct "
            "non-negative indices — duplicate or negative leases would "
            "silently alias devices instead of a disjoint sub-slice"
        )

    def resolve():
        import jax

        local = jax.local_devices()
        if any(i >= len(local) for i in idxs):
            raise RuntimeError(
                f"MAGGY_TPU_WORKER_DEVICES={spec!r} indexes past this "
                f"host's {len(local)} local device(s)"
            )
        return [local[i] for i in idxs]

    return resolve


def run_trial_worker(
    train_fn: Callable, config, host: str, port: int, secret: str,
    via_registry: bool = False,
) -> Any:
    """Run this process as one remote TRIAL executor for an HPO/ablation
    experiment (reference parity: Spark runs trial executors on cluster
    hosts, spark_driver.py:136-145 + trial_executor.py:35-213; here any host
    running the same script with MAGGY_TPU_ROLE=worker adds trial capacity).
    Loops {register → GET → run trial → FINAL} until the driver answers
    GSTOP. A driver that has already finished and torn down its server reads
    as a graceful stop, not a crash."""
    from maggy_tpu.core.executors.trial import trial_executor_fn
    from maggy_tpu.exceptions import RpcError

    pid = partition_id()
    app_id, run_id = _bootstrap_ids(host, port, pid, secret, via_registry)
    resolve = None
    study = getattr(config, "ablation_study", None)
    if study is not None:
        # the worker holds the same AblationConfig the driver does, so the
        # model/dataset variant resolver is rebuilt host-side
        from maggy_tpu.core.driver.ablation import make_ablation_resolver

        resolve = make_ablation_resolver(study)
    executor = trial_executor_fn(
        train_fn=train_fn,
        config=config,
        app_id=app_id,
        run_id=run_id,
        partition_id=pid,
        server_addr=(host, port),
        secret=secret,
        devices=_worker_devices(),
        resolve=resolve,
    )
    try:
        executor()
    except RpcError as e:
        # the driver is unreachable mid-loop. Normal completion is NOT this
        # path (the driver answers GSTOP before tearing its server down), so
        # propagate: the process exits nonzero and a supervisor
        # (maggy_tpu.run --respawn) can put the capacity back — swallowing
        # here would read as a clean exit and defeat the respawn.
        import sys

        print(
            f"[maggy_tpu pod worker {pid}] driver unreachable ({e}); exiting "
            "for the supervisor to respawn",
            file=sys.stderr,
        )
        raise
    return {"role": "trial_worker", "partition_id": pid}
