"""Compile a training cell's real step for a described v5e, with no chip: what
the compiler plans (``memory_analysis()``), what it recomputes by itself
(``.remat`` instructions), how many Pallas kernels it calls, a hash of the
optimized program without names of files and lines (two trees whose hashes
agree run one program but for the kernels' embedded source locations), and,
with ``--cycles REGEX``, the compiler's own ``estimated_cycles`` summed over
the fusions whose ``op_name`` matches. Nothing runs; numbers from here are the
compiler's plan, never a device metric.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_step.py --workload W [--out PREFIX] [--cycles 'conv\\.mix']

``Trainer._build_train_step`` of the cell's model on a one-device mesh of the
described topology, the state and batch abstract. ``jax.default_backend`` is
made to answer "tpu" so that the program takes the kernels it takes on the
chip (``flash_tileable``, ``grouped_dot``).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", default=None, help="write the optimized HLO to <out>.hlo.txt")
    ap.add_argument("--cycles", default=None, help="sum estimated_cycles over fusions whose op_name matches")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"

    import jax
    import numpy as np
    import optax
    from jax.experimental import topologies

    from benchmark import configs, traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    workload = next(w for w in bench["workloads"] if w["name"] == args.workload)
    entry = next(c for c in bench["configs"] if c["name"] == workload["config"])
    cfg, mix = configs.load(entry["file"]), traffic.load_mix(workload["traffic"])
    kind, section = mix["kind"], cfg[mix["kind"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"

    from maggy_tpu import models
    from maggy_tpu.parallel.mesh import mesh_for
    from maggy_tpu.train.trainer import Trainer, _model_inputs

    if kind == "train_packed_ref":
        ref = configs.load_reference(cfg)
        model = getattr(models, section["model"])(
            getattr(models, section["config_class"])(**ref.program_fields(cfg, kind))
        )
    else:
        model = models.Decoder(models.DecoderConfig(**configs.program_fields(cfg, kind)))
    hp = section["optimizer"]
    mesh, _spec = mesh_for(1, section["sharding"], devices=topo.devices[:1])
    trainer = Trainer(
        model, optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], weight_decay=hp["weight_decay"]), mesh
    )
    batch = {
        k: np.zeros((mix["rows_per_chip"], mix["seq_len"]), np.int32)
        for k in ("tokens", "positions", "segment_ids", "loss_mask")
    }

    def abstract(tree, shardings):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return jax.tree_util.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
            for a, s in zip(leaves, jax.tree_util.tree_leaves(shardings))
        ])

    state = abstract(
        jax.eval_shape(trainer._init_fn(), jax.random.key(0), *_model_inputs(batch)),
        trainer.state_shardings_for(batch),
    )
    with mesh:
        compiled = trainer._build_train_step().lower(state, abstract(batch, trainer.batch_shardings(batch))).compile()
    text, ma = compiled.as_text(), compiled.memory_analysis()
    if args.out:
        with open(args.out + ".hlo.txt", "w") as f:
            f.write(text)
    body = "\n".join(
        re.sub(r"backend_config=\{.*$", "", re.sub(r",? ?metadata=\{[^}]*\}", "", line))
        for line in text.splitlines()
        if not re.match(r"^(\d+ |FileNames|FunctionNames|FileLocations|StackFrames)", line)
    )
    gib = 2.0**30
    report = {
        "workload": args.workload,
        "peak_GiB": getattr(ma, "peak_memory_in_bytes", 0) / gib, "temp_GiB": ma.temp_size_in_bytes / gib,
        "arguments_GiB": ma.argument_size_in_bytes / gib,
        "kernels": {k: len(re.findall(rf"%{k}[.\d]* = ", text)) for k in ("flash_fwd", "flash_dq", "flash_dkv", "gmm", "tgmm")},
        "remat_instructions": len(re.findall(r"\.remat\d* = ", text)),
        "program_sha256": hashlib.sha256(body.encode()).hexdigest()[:16],
    }
    if args.cycles:
        report["estimated_cycles"] = sum(
            int(m.group(1)) for line in text.splitlines()
            if re.search(r'op_name="[^"]*' + args.cycles, line)
            for m in [re.search(r'estimated_cycles":"(\d+)"', line)] if m
        )
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
