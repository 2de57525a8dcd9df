"""python -m maggy_tpu.run: the multi-process launcher forms one experiment
from N copies of an unmodified user script."""

import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess/multi-process tier

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

SCRIPT = textwrap.dedent(
    """
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    from maggy_tpu import experiment
    from maggy_tpu.config import DistributedConfig

    def train(hparams, reporter, ctx):
        reporter.broadcast(1.0, step=0)
        return {{"metric": 2.5}}

    result = experiment.lagom(
        train,
        DistributedConfig(
            num_executors=3,
            sharding="dp",
            data_plane="local",
            hb_interval=0.05,
        ),
    )
    print("RESULT", result, flush=True)
    """
).format(repo=REPO)


def test_run_launcher_three_processes(tmp_path):
    script = tmp_path / "user_script.py"
    script.write_text(SCRIPT)
    env = dict(os.environ)
    env["MAGGY_TPU_LOG_ROOT"] = str(tmp_path / "logs")
    proc = subprocess.run(
        [sys.executable, "-m", "maggy_tpu.run", "--workers", "3", str(script)],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=180,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # driver's result aggregates all three workers
    driver_lines = [l for l in proc.stdout.splitlines() if "num_workers" in l]
    assert driver_lines, proc.stdout[-2000:]
    assert "'num_workers': 3" in driver_lines[0]
    assert "'metric': 2.5" in driver_lines[0]
    # worker ranks report their role
    assert proc.stdout.count("'role': 'worker'") == 2


GLOBAL_MESH_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import maggy_tpu
    formed = maggy_tpu.initialize_data_plane()
    assert formed, "launcher should have exported MAGGY_TPU_COORDINATOR"
    assert jax.process_count() == int(os.environ["MAGGY_TPU_NUM_EXECUTORS"]), (
        jax.process_count()
    )

    import optax
    from maggy_tpu import experiment
    from maggy_tpu.config import DistributedConfig
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train.data import synthetic_lm_batches

    CFG = DecoderConfig.tiny()

    def train(model, dataset, hparams, reporter, ctx):
        assert ctx.num_processes == 2 and len(ctx.mesh.devices.flat) == 2
        trainer = ctx.trainer(model, optax.adamw(3e-3))
        state = trainer.make_state(jax.random.key(0), next(dataset))
        last = None
        for _ in range(5):
            # every process sees the same global batch; shard_batch slices
            state, m = trainer.step(state, trainer.shard_batch(next(dataset)))
            last = float(m["loss"])
        return {{"metric": last, "loss": last}}

    result = experiment.lagom(
        train,
        DistributedConfig(
            module=Decoder(CFG),
            dataset=synthetic_lm_batches(CFG.vocab_size, 8, 32, seed=7),
            sharding="dp",
            data_plane="auto",
            hb_interval=0.05,
        ),
    )
    if jax.process_index() == 0:
        with open(os.environ["MT_RESULT_FILE"], "w") as f:
            json.dump(result, f)
    print("GLOBAL_MESH_OK", flush=True)
    """
).format(repo=REPO)


def test_run_launcher_global_mesh(tmp_path):
    """Two launcher processes form ONE jax.distributed mesh (process_count==2)
    and train with the same loss as a single-process run over the same data —
    the multi-host data-plane proof (NCCL/MASTER_ADDR rendezvous parity)."""
    script = tmp_path / "global_mesh_script.py"
    script.write_text(GLOBAL_MESH_SCRIPT)
    result_file = tmp_path / "result.json"
    env = dict(os.environ)
    env["MAGGY_TPU_LOG_ROOT"] = str(tmp_path / "logs")
    env["MT_RESULT_FILE"] = str(result_file)
    # conftest's 8-device flag must not leak: 1 local device per process
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "maggy_tpu.run",
            "--workers", "2", "--global-mesh", str(script),
        ],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-2500:])
    assert proc.stdout.count("GLOBAL_MESH_OK") == 2
    import json

    multi = json.load(result_file.open())

    # same training single-process on a 1-device mesh with the same global batch
    single = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(
            f"""
            import sys; sys.path.insert(0, {REPO!r})
            import os; os.environ["JAX_PLATFORMS"] = "cpu"
            import jax; jax.config.update("jax_platforms", "cpu")
            import optax
            from maggy_tpu.models import Decoder, DecoderConfig
            from maggy_tpu.train import TrainContext
            from maggy_tpu.train.data import synthetic_lm_batches
            CFG = DecoderConfig.tiny()
            ctx = TrainContext.create("dp")
            trainer = ctx.trainer(Decoder(CFG), optax.adamw(3e-3))
            data = synthetic_lm_batches(CFG.vocab_size, 8, 32, seed=7)
            state = trainer.make_state(jax.random.key(0), next(data))
            for _ in range(5):
                state, m = trainer.step(state, trainer.shard_batch(next(data)))
            print("SINGLE_LOSS", float(m["loss"]))
            """
        )],
        env={
            **{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
            "MAGGY_TPU_LOG_ROOT": str(tmp_path / "logs1"),
        },
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=180,
    )
    assert single.returncode == 0, single.stderr[-2000:]
    single_loss = float(single.stdout.split("SINGLE_LOSS")[1].strip().split()[0])
    assert abs(multi["loss"] - single_loss) < 2e-4, (multi["loss"], single_loss)


def test_run_launcher_arg_validation():
    proc = subprocess.run(
        [sys.executable, "-m", "maggy_tpu.run", "--workers", "0", "nope.py"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode != 0
    assert "--workers" in proc.stderr


def test_run_launcher_refuses_to_share_a_tpu_host(monkeypatch):
    """Two ranks on one unpartitioned TPU host: the second cannot open the
    device, so the launcher names the problem before it starts anything.
    CPU runs, single ranks and chip-partitioned environments pass."""
    from maggy_tpu import run

    monkeypatch.setattr(run, "_local_tpu_chips", lambda: 4)
    with pytest.raises(SystemExit, match="one TPU host"):
        run.check_tpu_host_not_shared(2, {})
    run.check_tpu_host_not_shared(1, {})
    run.check_tpu_host_not_shared(2, {"JAX_PLATFORMS": "cpu"})
    run.check_tpu_host_not_shared(2, {"TPU_VISIBLE_CHIPS": "0,1"})
    monkeypatch.setattr(run, "_local_tpu_chips", lambda: 0)
    run.check_tpu_host_not_shared(2, {})


ELASTIC_SCRIPT = textwrap.dedent(
    """
    import os, signal, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import maggy_tpu
    assert maggy_tpu.initialize_data_plane()

    import optax
    from maggy_tpu import experiment
    from maggy_tpu.config import DistributedConfig
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train.checkpoint import Checkpointer
    from maggy_tpu.train.data import synthetic_lm_batches

    CFG = DecoderConfig.tiny()
    GEN = int(os.environ["MAGGY_TPU_GENERATION"])
    RANK = int(os.environ["MAGGY_TPU_PARTITION"])
    TOTAL = 24

    def train(model, dataset, reporter, ctx):
        trainer = ctx.trainer(model, optax.adamw(3e-3))
        state = trainer.make_state(jax.random.key(0), next(dataset))
        ckpt = Checkpointer(os.environ["MT_CKPT_DIR"], async_save=False)
        start = ckpt.latest_step()
        if start is not None:
            state = ckpt.restore(state, step=start)
            for _ in range(start):  # realign the deterministic batch stream
                next(dataset)
        else:
            start = 0
        with open(os.environ["MT_TRACE_FILE"] + f".g{{GEN}}.r{{RANK}}", "w") as f:
            f.write(str(start))
        last = None
        for i in range(start, TOTAL):
            state, m = trainer.step(state, trainer.shard_batch(next(dataset)))
            last = float(m["loss"])
            if (i + 1) % 4 == 0:
                ckpt.save(i + 1, state)
                ckpt.wait()
            if GEN == 0 and RANK == 2 and i + 1 == 10:
                os.kill(os.getpid(), signal.SIGKILL)  # simulated host loss
        ckpt.close()
        return {{"metric": last, "loss": last, "end_step": int(state.step)}}

    result = experiment.lagom(
        train,
        DistributedConfig(
            module=Decoder(CFG),
            dataset=synthetic_lm_batches(CFG.vocab_size, 12, 32, seed=7),
            sharding="dp",
            data_plane="auto",
            hb_interval=0.05,
        ),
    )
    if jax.process_index() == 0:
        import json
        with open(os.environ["MT_RESULT_FILE"], "w") as f:
            json.dump(result, f)
    print("ELASTIC_OK", flush=True)
    """
).format(repo=REPO)


def test_run_launcher_elastic_restart(tmp_path):
    """Kill one of three global-mesh workers mid-run: the launcher restarts the
    generation, the experiment dir is pinned, training resumes from the latest
    checkpoint (not step 0), and the run still completes and converges."""
    script = tmp_path / "elastic_script.py"
    script.write_text(ELASTIC_SCRIPT)
    result_file = tmp_path / "result.json"
    trace = tmp_path / "trace"
    env = dict(os.environ)
    env["MAGGY_TPU_LOG_ROOT"] = str(tmp_path / "logs")
    env["MT_RESULT_FILE"] = str(result_file)
    env["MT_TRACE_FILE"] = str(trace)
    env["MT_CKPT_DIR"] = str(tmp_path / "ckpt")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [
            sys.executable, "-m", "maggy_tpu.run",
            "--workers", "3", "--global-mesh", "--elastic", "2", str(script),
        ],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-2500:])
    assert "restarting generation 0 -> 1" in proc.stderr, proc.stderr[-2000:]

    # generation 0 started cold, generation 1 resumed from a checkpoint
    g0 = int((tmp_path / "trace.g0.r0").read_text())
    g1 = int((tmp_path / "trace.g1.r0").read_text())
    assert g0 == 0
    assert 0 < g1 < 24, g1

    import json

    result = json.load(result_file.open())
    assert result["num_workers"] == 3
    assert result["end_step"] == 24.0


PACKED_SP_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import maggy_tpu
    formed = maggy_tpu.initialize_data_plane()
    assert formed and jax.process_count() == 2
    assert len(jax.devices()) == 8, len(jax.devices())

    import numpy as np
    import optax
    from maggy_tpu import experiment
    from maggy_tpu.config import DistributedConfig
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.parallel.ringattention import make_ring_attention
    from maggy_tpu.parallel.spec import ShardingSpec

    B, S = 4, 128

    def make_batch():
        rng = np.random.default_rng(5)
        seg = np.zeros((B, S), np.int32); seg[:, S // 2:] = 1
        pos = np.concatenate([np.arange(S // 2), np.arange(S - S // 2)])
        return {{
            "tokens": rng.integers(0, 256, (B, S)).astype(np.int32),
            "positions": pos[None].repeat(B, 0).astype(np.int32),
            "segment_ids": seg,
        }}

    def train(hparams, reporter, ctx):
        cfg = DecoderConfig.tiny(attention_fn=make_ring_attention(ctx.mesh))
        trainer = ctx.trainer(Decoder(cfg), optax.adamw(3e-3))
        batch = make_batch()
        state = trainer.make_state(jax.random.key(0), batch)
        sb = trainer.shard_batch(batch)
        # the seq mesh axis SPANS the two processes: each process must carve
        # its own seq chunk out of the global side inputs
        from jax.sharding import PartitionSpec as P
        assert sb["segment_ids"].sharding.spec == P(("data", "fsdp"), "seq")
        last = None
        for _ in range(4):
            state, m = trainer.step(state, sb)
            last = float(m["loss"])
        return {{"metric": last, "loss": last}}

    result = experiment.lagom(
        train,
        DistributedConfig(
            sharding=ShardingSpec(sp=8),
            data_plane="auto",
            hb_interval=0.05,
        ),
    )
    if jax.process_index() == 0:
        with open(os.environ["MT_RESULT_FILE"], "w") as f:
            json.dump(result, f)
    print("PACKED_SP_OK", flush=True)
    """
).format(repo=REPO)


def test_run_launcher_packed_sp_spans_processes(tmp_path):
    """Packed side inputs, the multi-process arm: they stay
    seq-sharded when the seq mesh axis SPANS processes (2 procs x 4 local
    devices, sp=8) — shard_batch slices each process's seq chunk from the
    sharding's index map — and the loss matches a single-process sp=8 run
    of the same data."""
    script = tmp_path / "packed_sp_script.py"
    script.write_text(PACKED_SP_SCRIPT)
    result_file = tmp_path / "result.json"
    env = dict(os.environ)
    env["MAGGY_TPU_LOG_ROOT"] = str(tmp_path / "logs")
    env["MT_RESULT_FILE"] = str(result_file)
    env.pop("XLA_FLAGS", None)  # the script pins its own 4-device count
    proc = subprocess.run(
        [
            sys.executable, "-m", "maggy_tpu.run",
            "--workers", "2", "--global-mesh", str(script),
        ],
        env=env,
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-2500:])
    assert proc.stdout.count("PACKED_SP_OK") == 2
    import json

    multi = json.load(result_file.open())

    single = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(
            f"""
            import sys; sys.path.insert(0, {REPO!r})
            import os; os.environ["JAX_PLATFORMS"] = "cpu"
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax; jax.config.update("jax_platforms", "cpu")
            import numpy as np
            import optax
            from maggy_tpu.models import Decoder, DecoderConfig
            from maggy_tpu.parallel.ringattention import make_ring_attention
            from maggy_tpu.parallel.spec import ShardingSpec
            from maggy_tpu.train import TrainContext

            B, S = 4, 128
            rng = np.random.default_rng(5)
            seg = np.zeros((B, S), np.int32); seg[:, S // 2:] = 1
            pos = np.concatenate([np.arange(S // 2), np.arange(S - S // 2)])
            batch = {{
                "tokens": rng.integers(0, 256, (B, S)).astype(np.int32),
                "positions": pos[None].repeat(B, 0).astype(np.int32),
                "segment_ids": seg,
            }}
            ctx = TrainContext.create(ShardingSpec(sp=8))
            cfg = DecoderConfig.tiny(attention_fn=make_ring_attention(ctx.mesh))
            trainer = ctx.trainer(Decoder(cfg), optax.adamw(3e-3))
            state = trainer.make_state(jax.random.key(0), batch)
            sb = trainer.shard_batch(batch)
            for _ in range(4):
                state, m = trainer.step(state, sb)
            print("SINGLE_LOSS", float(m["loss"]))
            """
        )],
        env={
            **{k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
            "MAGGY_TPU_LOG_ROOT": str(tmp_path / "logs1"),
        },
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert single.returncode == 0, single.stderr[-2000:]
    single_loss = float(single.stdout.split("SINGLE_LOSS")[1].strip().split()[0])
    assert abs(multi["loss"] - single_loss) < 1e-3, (multi["loss"], single_loss)
