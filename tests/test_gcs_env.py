"""GcsEnv exercised for real over fsspec ``memory://``:
dump/load, directory layout, the driver-registry round-trip in both secret
modes, remote sharded-dataset streaming through the env seam, and a full
lagom experiment writing every artifact into the object store."""

import importlib
import uuid

import numpy as np
import pytest

fsspec = pytest.importorskip("fsspec")

from maggy_tpu.core.env.gcs import GcsEnv


def _env():
    # unique root per test: the fsspec memory filesystem is process-global
    return GcsEnv(f"memory://maggy-{uuid.uuid4().hex[:8]}")


def test_dump_load_roundtrip_and_layout():
    env = _env()
    assert env.protocol == "memory"
    d = env.experiment_dir("app_1", 0)
    assert env.exists(d)
    t = env.trial_dir("app_1", 0, "trial_a")
    assert t.endswith("app_1/0/trial_a")

    env.dump({"metric": 0.5, "name": "x"}, f"{t}/result.json")
    assert env.load_json(f"{t}/result.json") == {"metric": 0.5, "name": "x"}
    env.dump("plain text", f"{t}/log.txt")
    with env.open_file(f"{t}/log.txt") as f:
        assert f.read() == "plain text"

    assert sorted(env.listdir(t)) == ["log.txt", "result.json"]
    with pytest.raises(OSError):
        env.listdir(f"{env.root}/nope")
    env.delete(f"{t}/log.txt")
    assert not env.exists(f"{t}/log.txt")


@pytest.mark.parametrize("omit_secret", [False, True])
def test_driver_registry_roundtrip(omit_secret):
    env = _env()
    env.register_driver(
        "app_reg", 3, "worker-host", 4242,
        secret=None if omit_secret else "s3cret", scope="pod",
    )
    rec = env.lookup_driver("app_reg")
    assert rec["host"] == "worker-host" and rec["port"] == 4242
    assert rec["scope"] == "pod" and rec["run_id"] == 3
    assert ("secret" in rec) == (not omit_secret)
    if not omit_secret:
        assert rec["secret"] == "s3cret"

    assert env.list_drivers()[0]["app_id"] == "app_reg"
    env.unregister_driver("app_reg")
    assert env.lookup_driver("app_reg") is None
    assert env.list_drivers() == []


def test_remote_sharded_dataset_streams_through_env(tmp_path):
    """ShardedDataset reads non-local shards through the ambient env's
    open_file/listdir — the GCS streaming path, on memory://."""
    from maggy_tpu.core import env as env_mod
    from maggy_tpu.train.sharded_dataset import ShardedDataset

    env = _env()
    env_mod.set_instance(env)
    try:
        data = np.arange(64 * 4, dtype=np.int32).reshape(64, 4)
        root = f"{env.root}/ds/tokens"
        bounds = np.linspace(0, 64, 5, dtype=np.int64)
        for s in range(4):
            import io

            buf = io.BytesIO()
            np.save(buf, data[bounds[s]:bounds[s + 1]])
            with env.open_file(f"{root}/shard-{s:05d}.npy", "wb") as f:
                f.write(buf.getvalue())

        ds = ShardedDataset(f"{env.root}/ds")
        assert ds.num_shards == 4 and ds.fields == ["tokens"]
        rows = [r for s in range(4) for r in np.asarray(ds.open_shard("tokens", s)).tolist()]
        assert sorted(map(tuple, rows)) == sorted(map(tuple, data.tolist()))

        loader = ds.loader(batch_size=16, loop=False, shuffle=True)
        batches = list(loader)
        assert len(batches) == 4 and all(b["tokens"].shape == (16, 4) for b in batches)
    finally:
        env_mod.set_instance(None)


def test_checkpoint_save_restore_with_remote_env(tmp_path):
    """Checkpointer under an ambient GcsEnv: orbax speaks gs:// natively via
    tensorstore (not through the env seam), so the env must not interfere
    with checkpoint save/restore — exercised with the memory:// env ambient
    and a real orbax round-trip."""
    import jax
    import optax

    from maggy_tpu.core import env as env_mod
    from maggy_tpu.models import Decoder, DecoderConfig
    from maggy_tpu.train import TrainContext
    from maggy_tpu.train.checkpoint import Checkpointer

    env_mod.set_instance(_env())
    try:
        cfg = DecoderConfig.tiny()
        ctx = TrainContext.create("dp")
        trainer = ctx.trainer(Decoder(cfg), optax.sgd(1e-2))
        batch = {"tokens": np.zeros((2, 16), np.int32)}
        state = trainer.make_state(jax.random.key(0), batch)
        ckpt = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
        ckpt.save(0, state)
        ckpt.wait()
        restored = ckpt.restore(state)
        a = jax.tree.leaves(state.params)[0]
        b = jax.tree.leaves(restored.params)[0]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        env_mod.set_instance(None)


def test_lagom_experiment_on_memory_env():
    """Full HPO run with GcsEnv ambient: experiment/trial dirs, hparams,
    result.json, executor logs and the registry record all land in the
    object store (the reference Hopsworks-env seam, hopsworks.py:136-190)."""
    experiment = importlib.import_module("maggy_tpu.experiment")
    from maggy_tpu import Searchspace
    from maggy_tpu.config import HyperparameterOptConfig
    from maggy_tpu.core import env as env_mod

    env = _env()
    env_mod.set_instance(env)
    try:
        def train(hparams, reporter):
            reporter.log(f"training with x={hparams['x']:.3f}")
            reporter.broadcast(hparams["x"], step=0)
            return hparams["x"]

        result = experiment.lagom(train, HyperparameterOptConfig(
            num_trials=3, optimizer="randomsearch",
            searchspace=Searchspace(x=("DOUBLE", [0.0, 1.0])),
            direction="max", num_executors=2, es_policy="none",
            hb_interval=0.05, seed=0,
        ))
        assert result["num_trials"] == 3
        app_dirs = env.listdir(env.root)
        app_id = next(a for a in app_dirs if a != ".drivers")
        run_id = sorted(env.listdir(f"{env.root}/{app_id}"))[0]
        exp = f"{env.root}/{app_id}/{run_id}"
        names = env.listdir(exp)
        assert "result.json" in names
        # executor logs publish at close through the env seam (no appends)
        assert any(n.startswith("executor_") and n.endswith(".log") for n in names)
        persisted = env.load_json(f"{exp}/result.json")
        assert persisted["best"]["metric"] == pytest.approx(result["best"]["metric"])
        # per-trial artifacts, INCLUDING the persist_outputs seam (a local
        # os.makedirs here would create a literal 'memory:/' dir in cwd)
        trial_dir = f"{exp}/{result['best']['trial_id']}"
        trial_names = env.listdir(trial_dir)
        assert "trial.json" in trial_names
        assert ".outputs.json" in trial_names
        import os as _os

        assert not _os.path.exists("memory:"), "artifacts leaked to local cwd"
    finally:
        env_mod.set_instance(None)
