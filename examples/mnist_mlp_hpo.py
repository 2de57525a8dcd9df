"""BASELINE config 1: MNIST-style MLP HPO with lagom() (reference README parity).

Runs anywhere (CPU/TPU). Uses synthetic MNIST-shaped data so the example is
hermetic; swap in real MNIST arrays to reproduce the baseline.

    python examples/mnist_mlp_hpo.py
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
from maggy_tpu.models import MLP
from maggy_tpu.train.native_loader import NativeBatchLoader


def make_data(n=2048, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 28, 28)).astype(np.float32)
    w = rng.normal(size=(28 * 28, 10)).astype(np.float32)
    y = (x.reshape(n, -1) @ w).argmax(-1).astype(np.int32)
    return {"inputs": x, "labels": y}


DATA = make_data()


def train(hparams, reporter):
    model = MLP(features=(hparams["width"],) * hparams["depth"], num_classes=10)
    loader = NativeBatchLoader(DATA, batch_size=128, seed=0)
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

    def accuracy(params):
        logits = model.apply({"params": params}, DATA["inputs"])
        return float((jnp.argmax(logits, -1) == DATA["labels"]).mean())

    params = variables["params"]
    for i in range(150):
        params, opt_state, loss = step(params, opt_state, next(loader))
        if i % 25 == 24:
            # broadcast the same quantity the trial returns, so early-stopped
            # trials are comparable with finished ones
            reporter.broadcast(accuracy(params), step=i)
    loader.close()
    return {"metric": accuracy(params), "final_loss": float(loss)}


if __name__ == "__main__":
    sp = Searchspace(
        lr=("DOUBLE", [1e-4, 1e-1]),
        width=("DISCRETE", [64, 128, 256]),
        depth=("INTEGER", [1, 3]),
    )
    config = HyperparameterOptConfig(
        num_trials=8,
        optimizer="randomsearch",
        searchspace=sp,
        direction="max",
        es_policy="median",
        es_min=3,
        hb_interval=0.2,
        seed=0,
    )
    result = experiment.lagom(train, config)
    print("best:", result["best"])
    print("avg accuracy:", round(result["avg"], 4))
