"""Distributed-training driver.

Capability parity with the reference's ``TorchDistributedTrainingDriver`` /
``TfDistributedTrainingDriver`` (core/experiment_driver/
torch_distributed_training_driver.py:28-146, tf_distributed_training_driver.py:
37-271): one registration barrier, an EXEC_CONFIG exchange that tells every
worker the cluster layout, per-worker final metrics averaged into the result.

Topology note: a "worker" here is one JAX *process* (one host of a pod), not
one device — SPMD over each host's chips happens inside pjit. Locally that
means exactly one worker spanning all visible devices.
"""

from __future__ import annotations

import os
import statistics
import time
from typing import Any, Callable, Dict, List

from maggy_tpu.core import rpc
from maggy_tpu.core.driver.base import Driver
from maggy_tpu.core.executors.distributed import dist_executor_fn


class DistributedTrainingDriver(Driver):
    def __init__(self, config, app_id: str, run_id: int):
        super().__init__(config, app_id, run_id)
        try:
            import jax

            default_workers = jax.process_count()
        except Exception:
            default_workers = 1
        self.num_executors = config.num_executors or default_workers
        # last worker becomes a dedicated evaluator (reference
        # tf_dist_executor.py:138-144); it shares the control plane but not
        # the training group
        self.evaluator_partition: Any = None
        if getattr(config, "evaluator", False):
            if self.num_executors < 2:
                raise ValueError(
                    "evaluator=True needs num_executors >= 2 (one training "
                    "worker plus the evaluator)"
                )
            if config.data_plane == "auto" and default_workers > 1:
                raise ValueError(
                    "evaluator=True requires data_plane='local': in a global "
                    "jax.distributed mesh every process is part of the "
                    "training collective and none can be carved out."
                )
            self.evaluator_partition = self.num_executors - 1
        self._finals: List[Dict[str, Any]] = []
        self._coordinator = None  # host:port of worker 0, filled at registration
        self._last_seen: Dict[int, float] = {}  # partition -> last contact ts
        self._final_pids: set = set()
        # elastic restart (docs/resilience.md): a TRANSIENT worker death
        # consumes one restart slot and relaunches that partition — the
        # replacement re-runs registration + EXEC_CONFIG and its train_fn
        # resumes from the latest checkpoint via fit(resume="auto")
        self.max_restarts = int(getattr(config, "max_restarts", 0))
        self._restarts = 0
        # restart serialization: every processed restart is one membership
        # transition — _RESTART messages carry the epoch their death was
        # observed at, and a partition restarts at most once per epoch, so
        # the thread-death and liveness-sweep paths double-reporting one
        # loss can never double-respawn a partition or double-charge the
        # budget (the double-fault window fix)
        self._restart_epoch = 0
        self._restarted_at: Dict[int, int] = {}  # partition -> epoch of last restart
        # elastic membership (docs/resilience.md "Elastic membership"):
        # epoch-numbered views of the active slice set; on slice loss or
        # rejoin the mesh RESHAPES instead of relaunching at fixed width
        self.elastic = bool(getattr(config, "elastic", False))
        self.membership = None
        self._member_acks: Dict[int, int] = {}  # partition -> last acked epoch
        self._reshape_t0: float = 0.0  # perf_counter at the last epoch bump
        self._reshape_epoch_timed = -1  # epoch whose barrier was already gauged
        if self.elastic:
            from maggy_tpu.resilience.membership import MembershipView

            total = int(getattr(config, "num_slices", None) or self.num_executors)
            min_slices = int(getattr(config, "min_slices", 1))
            if min_slices > total:
                raise ValueError(
                    f"min_slices={min_slices} exceeds the launch width "
                    f"({total} slice(s))"
                )
            # one executor hosting several slices = simulated partitions of
            # the local device mesh; several executors = one slice each
            mode = "sim" if (self.num_executors == 1 and total > 1) else "workers"
            self.membership = MembershipView.full(total, min_slices, mode=mode)
            self.telemetry.gauge("resilience.membership_epoch", 0)
            self.telemetry.gauge("resilience.active_slices", total)
        # pod mode: remote hosts run their own copy of the script and connect
        # as workers (core/pod.py); this driver launches only partition 0
        from maggy_tpu.core.pod import driver_address

        self.pod_mode = bool(driver_address(config))

    # ------------------------------------------------------------------ server

    def _make_server(self) -> rpc.Server:
        # a launcher distributes one secret to every pod process via env
        return rpc.Server(
            self.num_executors, secret=os.environ.get("MAGGY_TPU_SECRET") or None
        )

    def _register_msg_callbacks(self) -> None:
        s = self.server
        s.register_callback("REG", self._reg_callback)
        s.register_callback(
            "QUERY", lambda m: {"type": "QUERY", "ready": s.reservations.done()}
        )
        s.register_callback("EXEC_CONFIG", self._exec_config_callback)
        # full cluster spec (reference TensorflowServer RESERVATIONS verb,
        # rpc.py:614-620)
        s.register_callback(
            "RESERVATIONS",
            lambda m: {
                "type": "RESERVATIONS",
                "cluster": s.reservations.cluster_spec(),
            },
        )
        s.register_callback("METRIC", self._metric_callback)
        s.register_callback("FINAL", self._final_callback)
        if self.elastic:
            # membership protocol (docs/resilience.md): SLICE_EVENT reports
            # a drop/rejoin for digestion; MEMBERSHIP is the reshape
            # barrier poll — it records the caller's acked epoch and
            # reports whether every active member has converged
            s.register_callback("SLICE_EVENT", self._slice_event_callback)
            s.register_callback("MEMBERSHIP", self._membership_callback)
        s.register_callback("GET", lambda m: {"type": "GSTOP"})
        s.register_callback(
            "LOG", lambda m: {"type": "LOG", "logs": self.drain_logs(), "progress": ""}
        )

    def _touch(self, pid: int) -> None:
        with self.lock:
            self._last_seen[pid] = time.time()

    def _reg_callback(self, msg) -> Dict[str, Any]:
        restarted = self.server.reservations.register(
            msg["partition_id"], msg.get("meta", {})
        )
        self._touch(msg["partition_id"])
        if (
            restarted
            and self.elastic
            and self.membership.mode == "workers"
            and msg["partition_id"] in self.membership.inactive
        ):
            # a dropped slice's worker came back (supervisor respawn):
            # re-admit it through the membership protocol — the rejoin
            # epoch reshapes every survivor back to the wider mesh
            self.server.enqueue(
                {
                    "type": "_SLICE_EVENT",
                    "kind": "rejoin",
                    "slice": msg["partition_id"],
                    "partition_id": msg["partition_id"],
                }
            )
        return {"type": "OK"}

    def _exec_config_callback(self, msg) -> Dict[str, Any]:
        # worker 0's host becomes the jax.distributed coordinator
        # (the reference's MASTER_ADDR selection, rpc.py:544-553); app/run ids
        # ride along so pod workers land their artifacts in the driver's
        # experiment directory
        spec = self.server.reservations.cluster_spec()
        coordinator = None
        # advertised only on pods — a plain local multi-worker run must not
        # look like a multi-host cluster to the executors
        if self.pod_mode and self.num_executors > 1 and spec:
            host = spec[0].get("host") or "127.0.0.1"
            # derive from the experiment's RPC port unless pinned on the
            # config: concurrent experiments on one host get distinct ports
            port = getattr(self.config, "coordinator_port", None) or (
                1024 + (self.server.port + 1000) % 64000
            )
            coordinator = f"{host}:{port}"
        num_processes = self.num_executors - (
            1 if self.evaluator_partition is not None else 0
        )
        out = {
            "type": "EXEC_CONFIG",
            # the evaluator is outside the training group (reference: the TF
            # evaluator is not in the TF_CONFIG worker list)
            "num_processes": num_processes,
            "coordinator": coordinator,
            "cluster": spec,
            "evaluator_partition": self.evaluator_partition,
            "app_id": self.app_id,
            "run_id": self.run_id,
        }
        if self.elastic:
            # membership rides the config exchange: a reshape re-runs
            # EXEC_CONFIG, so the layout a worker builds is always the one
            # the current epoch's view describes
            view = self.membership
            out["membership"] = view.as_dict()
            if view.mode == "workers":
                out["num_processes"] = view.n_active
        return out

    def _metric_callback(self, msg) -> Dict[str, Any]:
        self._touch(msg["partition_id"])
        self.note_worker_telemetry(msg)
        self.server.enqueue(msg)
        if self.abort.is_set():
            return {"type": "STOP"}
        if self.elastic and msg.get("epoch") is not None:
            view = self.membership  # atomic read; digestion swaps whole views
            if int(msg["epoch"]) < view.epoch:
                # this worker runs a stale layout: tell it to reshape — its
                # fit raises MembershipChanged at the next step boundary
                return {"type": "RESHAPE", "epoch": view.epoch}
        return {"type": "OK"}

    # ------------------------------------------------------- membership verbs

    def _slice_event_callback(self, msg) -> Dict[str, Any]:
        """A worker observed a slice drop/rejoin (chaos or real): enqueue
        for digestion — the epoch bump and all accounting happen there."""
        self.server.enqueue(
            {
                "type": "_SLICE_EVENT",
                "kind": msg.get("kind"),
                "slice": msg.get("slice"),
                "partition_id": msg.get("partition_id"),
                "step": msg.get("step"),
            }
        )
        return {"type": "OK"}

    def _membership_callback(self, msg) -> Dict[str, Any]:
        """Reshape-barrier poll: record the caller's acked epoch; ready once
        every member expected at the barrier has acked the current epoch.
        The barrier is what makes the reshape *checkpoint-consistent*: no
        member rebuilds its mesh until all of them have converged on the
        view (and therefore on the checkpoint the transition saved)."""
        import time as _time

        view = self.membership
        pid = msg.get("partition_id")
        acked = msg.get("epoch")
        with self.lock:
            if pid is not None and acked is not None:
                self._member_acks[int(pid)] = int(acked)
            members = self._barrier_members()
            ready = all(
                self._member_acks.get(p, -1) >= view.epoch for p in members
            )
            if ready and view.epoch > 0 and self._reshape_epoch_timed < view.epoch:
                self._reshape_epoch_timed = view.epoch
                self.telemetry.gauge(
                    "resilience.reshape_ms",
                    (_time.perf_counter() - self._reshape_t0) * 1e3,
                )
        return {
            "type": "MEMBERSHIP",
            "view": view.as_dict(),
            "ready": ready,
            "aborted": self.abort.is_set(),
        }

    def _barrier_members(self) -> List[int]:
        """Partitions whose ack the reshape barrier waits for (call under
        ``self.lock``): the single hosting executor in sim mode, the active
        slices' workers otherwise — minus workers that already FINALed
        (they will never poll again, and their result is already in)."""
        if self.membership.mode == "sim":
            return [p for p in (0,) if p not in self._final_pids]
        return [
            p
            for p in self.membership.active
            if p < self.num_executors and p not in self._final_pids
        ]

    def _final_callback(self, msg) -> Dict[str, Any]:
        with self.lock:
            self._final_pids.add(msg["partition_id"])
        self._touch(msg["partition_id"])
        self.server.enqueue(msg)
        return {"type": "OK"}

    # ------------------------------------------------------------------ digestion

    def _on_worker_death(self, partition_id: int, exc: BaseException) -> bool:
        """Local worker-thread death: absorb TRANSIENT failures while restart
        budget remains — or, under elastic membership, reshape the mesh
        around the lost slice (runs on the dying thread — only enqueues)."""
        from maggy_tpu.resilience import TRANSIENT, classify_failure

        if self.experiment_done.is_set() or classify_failure(exc) != TRANSIENT:
            return False
        if self.elastic and self.membership.mode == "workers":
            # slice == worker process: the death IS a membership drop —
            # digestion bumps the epoch, survivors reshape, and no restart
            # slot is charged. A min_slices violation aborts cleanly from
            # digestion (the death still reads as absorbed here: the
            # violation is the authoritative error, not the thread's).
            self.telemetry.count("resilience.worker_deaths")
            self.server.enqueue(
                {
                    "type": "_SLICE_EVENT",
                    "kind": "drop",
                    "slice": partition_id,
                    "partition_id": partition_id,
                    "error": f"{type(exc).__name__}: {exc}",
                }
            )
            return True
        with self.lock:
            if self._restarts >= self.max_restarts:
                return False
            self._restarts += 1
            nth = self._restarts
            # serialize behind the restart epoch: the relaunch for THIS
            # death is valid only while no other restart of the same
            # partition lands first (double-fault window fix)
            observed_epoch = self._restart_epoch
        self.telemetry.count("resilience.dist_restarts")
        self.server.enqueue(
            {
                "type": "_RESTART",
                "partition_id": partition_id,
                "error": f"{type(exc).__name__}: {exc}",
                "restart": nth,
                "epoch": observed_epoch,
            }
        )
        return True

    def _digest_restart(self, msg: Dict[str, Any]) -> None:
        pid = msg["partition_id"]
        with self.lock:
            # double-fault window: the thread-death and liveness-sweep paths
            # can both report one loss, and a relaunch may already be in
            # flight for this partition. A restart observed BEFORE the
            # partition's last processed restart epoch is that duplicate —
            # refund the slot it charged and keep the one relaunch instead
            # of spawning a second executor for the partition (which would
            # double-FINAL and corrupt completion accounting). A death
            # observed at or after it is the relaunched worker genuinely
            # dying again and restarts normally.
            if self._restarted_at.get(pid, -1) > msg.get("epoch", 0):
                self._restarts = max(0, self._restarts - 1)
                self.log(
                    f"Worker {pid} death report superseded by an in-flight "
                    f"restart (epoch {self._restarted_at[pid]}); restart slot "
                    "refunded"
                )
                return
            self._restart_epoch += 1
            self._restarted_at[pid] = self._restart_epoch
            # the partition's previous FINAL (if any) is void — its rerun
            # reports the authoritative one
            self._finals = [m for m in self._finals if m["partition_id"] != pid]
            self._final_pids.discard(pid)
            self._last_seen.pop(pid, None)
        self.log(
            f"Worker {pid} died ({msg['error']}); elastic restart "
            f"{msg['restart']}/{self.max_restarts}: re-running registration "
            f"+ EXEC_CONFIG for partition {pid} and relaunching its train_fn "
            "from the latest checkpoint"
        )
        self._respawn_executor(pid)

    def _digest_slice_event(self, msg: Dict[str, Any]) -> None:
        """Apply a membership transition (digestion thread): bump the epoch,
        start the reshape clock, and let the heartbeat/barrier paths carry
        the new view to every member. A min_slices violation aborts the run
        with the violation as the experiment error — deterministic, never a
        hang on a barrier that cannot complete."""
        from maggy_tpu.resilience.membership import MembershipViolation

        kind, slice_id = msg.get("kind"), msg.get("slice")
        view = self.membership
        try:
            new = view.drop(slice_id) if kind == "drop" else view.rejoin(slice_id)
        except (MembershipViolation, ValueError) as e:
            self.log(f"Membership {kind} of slice {slice_id} rejected: {e}")
            with self.lock:
                if self.exception is None:
                    self.exception = e
            self.abort.set()
            self.experiment_done.set()
            return
        if new.epoch == view.epoch:
            self.log(
                f"Membership {kind} of slice {slice_id} ignored "
                f"(duplicate report at epoch {view.epoch})"
            )
            return
        import time as _time

        with self.lock:
            self.membership = new
            self._reshape_t0 = _time.perf_counter()
            if kind == "drop":
                self._last_seen.pop(slice_id, None)
        self.telemetry.count(
            "resilience.slice_drops" if kind == "drop" else "resilience.slice_rejoins"
        )
        self.telemetry.gauge("resilience.membership_epoch", new.epoch)
        self.telemetry.gauge("resilience.active_slices", new.n_active)
        self.log(
            f"Membership epoch {new.epoch}: slice {slice_id} "
            f"{'left' if kind == 'drop' else 'rejoined'}"
            + (f" ({msg['error']})" if msg.get("error") else "")
            + f"; active slices {list(new.active)}/{new.total_slices} — "
            "reshape barrier open, survivors converge on the latest "
            "complete checkpoint"
        )
        # a drop can complete the experiment retroactively: every REMAINING
        # member may already have FINALed at full width
        self._check_elastic_completion()

    def _needed_finals(self) -> int:
        if self.elastic and self.membership.mode == "workers":
            return self.membership.n_active
        return self.num_executors

    def _check_elastic_completion(self) -> None:
        with self.lock:
            done = len(self._finals)
        if done >= self._needed_finals() and not self.experiment_done.is_set():
            self._aggregate()
            self.experiment_done.set()

    def _handle_message(self, msg: Dict[str, Any]) -> None:
        verb = msg.get("type")
        if verb == "_RESTART":
            self._digest_restart(msg)
        elif verb == "_SLICE_EVENT":
            self._digest_slice_event(msg)
        elif verb == "METRIC":
            logs = msg.get("logs") or []
            if logs:
                self.add_executor_logs(logs)
        elif verb == "FINAL":
            if msg.get("error"):
                raise RuntimeError(
                    f"Distributed worker {msg['partition_id']} failed: {msg['error']}"
                )
            with self.lock:
                # a re-admitted (restarted) worker may FINAL twice for one
                # partition — keep only its latest result
                self._finals = [
                    m
                    for m in self._finals
                    if m["partition_id"] != msg["partition_id"]
                ]
                self._finals.append(msg)
                done = len(self._finals)
            needed = self._needed_finals()
            self.log(f"Worker {msg['partition_id']} finished ({done}/{needed})")
            if done >= needed:
                self._aggregate()
                self.experiment_done.set()

    def _aggregate(self) -> None:
        """Average per-worker numeric test metrics (reference
        torch_distributed_training_driver.py:49-69, 137-146). The evaluator's
        outputs are reported separately, never averaged into the training
        mean (reference: the TF evaluator lives outside the worker list)."""
        finals = self._finals
        evaluator = None
        if self.evaluator_partition is not None:
            ev = [m for m in finals if m["partition_id"] == self.evaluator_partition]
            finals = [m for m in finals if m["partition_id"] != self.evaluator_partition]
            if ev:
                evaluator = ev[0].get("outputs") or {}
                if ev[0].get("metric") is not None:
                    evaluator.setdefault("metric", ev[0]["metric"])
        outputs = [m.get("outputs") or {} for m in finals]
        metrics = [m.get("metric") for m in finals if m.get("metric") is not None]
        result: Dict[str, Any] = {"num_workers": len(finals)}
        if metrics:
            result["metric"] = statistics.mean(metrics)
        keys = set().union(*outputs) if outputs else set()
        for k in keys:
            vals = [o[k] for o in outputs if isinstance(o.get(k), (int, float))]
            if vals:
                result.setdefault("outputs", {})[k] = statistics.mean(vals)
        if evaluator is not None:
            result["evaluator"] = evaluator
        self.result = result

    def _status(self) -> Dict[str, Any]:
        base = super()._status()
        with self.lock:
            base.update(
                workers_done=len(self._final_pids),
                evaluator_partition=self.evaluator_partition,
                restarts=self._restarts,
                max_restarts=self.max_restarts,
                last_seen={
                    str(pid): round(time.time() - ts, 1)
                    for pid, ts in self._last_seen.items()
                },
            )
            if self.elastic:
                view = self.membership
                base.update(
                    membership_epoch=view.epoch,
                    active_slices=list(view.active),
                    num_slices=view.total_slices,
                    min_slices=view.min_slices,
                    membership_mode=view.mode,
                )
        return base

    def _exp_final_callback(self) -> None:
        if self.result and "outputs" in self.result:
            flat = dict(self.result["outputs"])
            flat.update({k: v for k, v in self.result.items() if k != "outputs"})
            self.result = flat

    # ------------------------------------------------------------------ executor

    def _local_partitions(self) -> List[int]:
        if not self.pod_mode:
            return super()._local_partitions()
        import socket as socket_mod

        # reachable hostname, not the loopback the Server records for 0.0.0.0
        # binds — launcher tooling copies this into MAGGY_TPU_DRIVER
        self.log(
            f"Pod mode: driver at {socket_mod.gethostname()}:{self.server.port} "
            f"(secret via MAGGY_TPU_SECRET), running local partition 0, "
            f"awaiting {self.num_executors - 1} remote workers"
        )
        return [0]

    def _await_completion(self) -> None:
        super()._await_completion()
        # workers exit right after FINAL is *enqueued*; wait for the digestion
        # thread to actually aggregate before run_experiment reads self.result
        if self.exception is not None or self.abort.is_set():
            return
        if self.pod_mode:
            # remote workers may train for hours: wait for every FINAL, but a
            # registered worker that goes silent past worker_timeout (its
            # heartbeat beats every hb_interval) fails the run loudly instead
            # of hanging the driver forever
            timeout = getattr(self.config, "worker_timeout", 1800.0)
            while not self.experiment_done.wait(timeout=1.0):
                if self.abort.is_set():
                    return
                now = time.time()
                with self.lock:
                    stale = [
                        pid
                        for pid, ts in self._last_seen.items()
                        if now - ts > timeout and pid not in self._final_pids
                    ]
                if stale:
                    if self.elastic and self.membership.mode == "workers":
                        # heartbeat-silent slices leave the membership: the
                        # mesh reshapes around them (min_slices violations
                        # abort from digestion) — no restart budget burned,
                        # and a later re-registration rejoins them
                        for pid in stale:
                            with self.lock:
                                self._last_seen.pop(pid, None)
                            self.telemetry.count("resilience.worker_deaths")
                            self.server.enqueue(
                                {
                                    "type": "_SLICE_EVENT",
                                    "kind": "drop",
                                    "slice": pid,
                                    "partition_id": pid,
                                    "error": f"silent > {timeout:.0f}s",
                                }
                            )
                        continue
                    with self.lock:
                        budget_left = self.max_restarts - self._restarts
                        if budget_left >= len(stale):
                            # elastic window: charge the budget, forget the
                            # dead registrations, and keep waiting — the
                            # respawned hosts (supervisor/launcher) re-register
                            # and resume from the latest checkpoint
                            self._restarts += len(stale)
                            for pid in stale:
                                self._last_seen.pop(pid, None)
                            restarts = self._restarts
                        else:
                            restarts = None
                    if restarts is not None:
                        self.telemetry.count(
                            "resilience.dist_restarts", len(stale)
                        )
                        self.log(
                            f"Pod worker(s) {stale} silent > {timeout:.0f}s; "
                            f"elastic restart window open "
                            f"({restarts}/{self.max_restarts} restarts used) "
                            "— awaiting re-registration"
                        )
                        continue
                    with self.lock:
                        if self.exception is None:
                            self.exception = RuntimeError(
                                f"Pod worker(s) {stale} silent for more than "
                                f"{timeout:.0f}s; aborting experiment."
                            )
                    self.abort.set()
                    self.experiment_done.set()
                    return
        else:
            # local mode: wait for digestion to aggregate the finals. A dead
            # executor may be about to come back via elastic restart, so the
            # 60s grace clock only runs while NO executor thread is alive —
            # a respawned worker (which may train for minutes) resets it.
            grace_deadline = None
            while not self.experiment_done.wait(timeout=0.5):
                if self.abort.is_set():
                    return
                if any(t.is_alive() for t in self._worker_threads):
                    grace_deadline = None
                    continue
                if grace_deadline is None:
                    grace_deadline = time.time() + 60
                elif time.time() > grace_deadline:
                    return

    def _device_groups(self) -> List[list]:
        # one worker per process; with several local workers each leases a
        # disjoint device group, with one worker it spans every local device
        import jax

        devices = jax.local_devices()
        if self.pod_mode:
            # remote pod workers span their whole host; the driver's local
            # partition must match, not take a 1/num_executors lease
            return [devices]
        n = self.num_executors
        if n <= 1 or len(devices) < n:
            return [devices]
        per = len(devices) // n
        return [devices[i * per : (i + 1) * per] for i in range(n)]

    def _executor_fn(self, train_fn: Callable, partition_id: int, devices: list) -> Callable:
        return dist_executor_fn(
            train_fn=train_fn,
            config=self.config,
            app_id=self.app_id,
            run_id=self.run_id,
            partition_id=partition_id,
            server_addr=(self.server.host, self.server.port),
            secret=self.server.secret,
            devices=devices,
        )
