"""BASELINE config 2 (scaled down): ResNet/CIFAR-style ASHA HPO.

ASHA allocates epochs as budget; swap the synthetic data for CIFAR-10 arrays
and ResNetConfig.resnet50() to reproduce the baseline on a v5e-8.

    python examples/resnet_asha.py
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from maggy_tpu import Searchspace, experiment
from maggy_tpu.config import HyperparameterOptConfig
from maggy_tpu.models import ResNet, ResNetConfig
from maggy_tpu.train.native_loader import NativeBatchLoader


def make_data(n=512, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 16, 16, 3)).astype(np.float32)
    y = (x.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    return {"inputs": x, "labels": y}


DATA = make_data()


def train(hparams, budget, reporter):
    cfg = ResNetConfig(
        stage_sizes=(1, 1),
        width=hparams["width"],
        num_classes=2,
        dtype=jnp.float32,
    )
    model = ResNet(cfg)
    loader = NativeBatchLoader(DATA, batch_size=64, seed=1)
    variables = model.init(jax.random.key(0), DATA["inputs"][:1])
    tx = optax.adam(hparams["lr"])
    opt_state = tx.init(variables["params"])

    @jax.jit
    def step(params, opt_state, batch):
        def loss_fn(p):
            logits = model.apply({"params": p}, batch["inputs"])
            logp = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(logp, batch["labels"][:, None], 1).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state2, loss

    params = variables["params"]
    steps_per_epoch = 8
    for epoch in range(int(budget)):
        for _ in range(steps_per_epoch):
            params, opt_state, loss = step(params, opt_state, next(loader))
        logits = model.apply({"params": params}, DATA["inputs"])
        acc = float((jnp.argmax(logits, -1) == DATA["labels"]).mean())
        reporter.broadcast(acc, step=epoch)
    loader.close()
    return {"metric": acc}


if __name__ == "__main__":
    sp = Searchspace(lr=("DOUBLE", [1e-4, 3e-2]), width=("DISCRETE", [8, 16, 32]))
    config = HyperparameterOptConfig(
        num_trials=8,
        optimizer="asha",
        searchspace=sp,
        direction="max",
        es_policy="none",
        hb_interval=0.2,
        seed=0,
    )
    result = experiment.lagom(train, config)
    print("best:", result["best"]["params"], "acc:", result["best"]["metric"])
    print("total trials (incl. promotions):", result["num_trials"])
