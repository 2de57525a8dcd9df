"""BASELINE config 4 (scaled down): BERT component ablation study.

LOCO over encoder layers + the pooler: one baseline trial, one trial per
ablated component, ranked by downstream accuracy. ZERO factories (reference
parity, loco.py:82-136): the driver derives each ablated variant from the
config model automatically — BertConfig carries an ``ablated`` field, so the
model is rebuilt with the component dropped; models without one get generic
param-subtree masking.

    python examples/bert_ablation.py
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import jax
import jax.numpy as jnp
import numpy as np

from maggy_tpu import experiment
from maggy_tpu.ablation import AblationStudy
from maggy_tpu.config import AblationConfig
from maggy_tpu.models import Bert, BertConfig

CFG = BertConfig.tiny()
rng = np.random.default_rng(0)
TOKENS = rng.integers(1, CFG.vocab_size, (128, 16)).astype(np.int32)
LABELS = (TOKENS[:, 0] % 2).astype(np.int32)


def train(model, reporter):
    variables = model.init(jax.random.key(0), TOKENS)

    @jax.jit
    def step(params):
        def loss_fn(p):
            logits, _ = model.apply(p, TOKENS)
            logp = jax.nn.log_softmax(logits)
            return -jnp.take_along_axis(logp, LABELS[:, None], 1).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return jax.tree.map(lambda a, b: a - 0.3 * b, params, grads), loss

    for i in range(30):
        variables, loss = step(variables)
    logits, _ = model.apply(variables, TOKENS)
    acc = float((jnp.argmax(logits, -1) == LABELS).mean())
    reporter.broadcast(acc, step=0)
    return acc


if __name__ == "__main__":
    study = AblationStudy()
    study.model.layers.include("layer_0", "layer_1", "pooler")
    result = experiment.lagom(
        train,
        AblationConfig(
            ablation_study=study,
            model=Bert(CFG),  # no set_factory: variants derived automatically
            direction="max",
            hb_interval=0.2,
        ),
    )
    print("trials:", result["num_trials"])
    print("best variant:", result["best"]["params"], result["best"]["metric"])
