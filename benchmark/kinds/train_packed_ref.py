"""Packed-document training through ``lagom(train_fn, DistributedConfig)``,
for a configuration whose ``.reference.py`` says what the model is.

The same run as ``train_packed`` (the train_fn is a user's: it asks the context
for a trainer, makes the state and calls ``Trainer.fit``; the benchmark's part
is the fixed pool of packed batches, the seeded weights it puts into the
state, the clock and the readings the comparison needs; the first optimizer
steps of set-up go through the same ``fit`` call, trainer and state as the
measured window; the same ``obs`` keys, so the same readers read it). What
differs: everything that knows the architecture — the reference's sizes, the
program's config fields, the leaf specification and names, the needed
operations — is asked of the configuration's ``.reference.py``, not of
``benchmark/configs.py``, ``weights.leaf_spec`` and ``counts.py``, which are
written for the dense decoder. A model with expert share layers also reports
its step counters (``moe_slots``, ``moe_slots_dropped``,
``moe_load_max_over_mean`` of ``Trainer.fit``'s result, read at each chunk's
last step): the slots on held experts are held against the reference's count
in the verify steps, give the routed experts' needed operations, and a slot
dropped anywhere makes the run not correct.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import time

import numpy as np

from benchmark import compare, configs, traffic, weights

KIND = "train_packed_ref"
COUNTERS = ("moe_slots", "moe_slots_dropped", "moe_load_max_over_mean")


def run(cell) -> dict:
    import jax
    import jax.numpy as jnp
    import optax

    from maggy_tpu import experiment, telemetry
    from maggy_tpu import models
    from maggy_tpu.config import DistributedConfig

    cfg, mix = cell.config, cell.mix
    section = cfg[KIND]
    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    fields = ref.program_fields(cfg, KIND)
    spec = ref.leaf_spec(sizes)
    hp = section["optimizer"]
    chips = cell.chips
    model = getattr(models, section["model"])(
        getattr(models, section["config_class"])(**fields)
    )
    cell.lap("imports")
    pool, rows = traffic.packed_pool(mix, cell.seed, sizes["vocab"], chips)
    cell.lap("pool")
    real = [int(b["loss_mask"].sum()) for b in pool]
    key = weights.base_key(cell.seed)
    k_chunk, n_verify = int(mix["steps_per_chunk"]), int(mix["verify_steps"])
    obs = {"chunks": [], "input_wait_ms": 0.0, "steps": 0, "untraced_s": 0.0, "untraced_steps": [],
           "traced_steps": [], "counters": []}

    def make(name, shape, k):
        return weights.stacked(k, name, spec, spec[name][1]).reshape(shape)

    def train_fn(ctx):
        trainer = ctx.trainer(
            model,
            optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"], weight_decay=hp["weight_decay"]),
        )
        cell.lap("lagom to train_fn")
        state = trainer.make_state(jax.random.key(0), pool[0])
        cell.lap("make_state")
        paths = [p for p, _ in jax.tree_util.tree_flatten_with_path(state.params)[0]]

        def reseed(state, k):
            leaves, treedef = jax.tree_util.tree_flatten(state.params)
            new = [
                make(ref.ref_name(p), a.shape, k).astype(a.dtype) for p, a in zip(paths, leaves)
            ]
            return state.replace(params=jax.tree_util.tree_unflatten(treedef, new))

        with ctx.mesh:
            state = jax.jit(
                reseed, donate_argnums=(0,),
                out_shardings=jax.tree.map(lambda a: a.sharding, state),
            )(state, key)
            jax.block_until_ready(state.params)
        cell.lap("seeded weights")
        cell.mark("built")

        tel = telemetry.get()
        feed = itertools.cycle(pool)
        n_s = ref.GRAD_SAMPLE

        def l2(a):
            return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))

        @jax.jit
        def first_gradient(mu):
            """Per leaf: the norm of the first gradient as the optimizer got it
            (AdamW's first moment after one step is (1 - b1) times it) and
            ``GRAD_SAMPLE`` evenly strided elements of it."""
            g = {n: a.astype(jnp.float32) / (1 - hp["b1"]) for n, a in ref.named_leaves(mu).items()}
            return (
                {n: l2(a) for n, a in g.items()},
                {n: a.reshape(-1)[:: max(1, a.size // n_s)][:n_s] for n, a in g.items()},
            )

        @jax.jit
        def change(params, k):
            """Per leaf: the norm of the parameters' change from the seeded weights."""
            return {n: l2(a.astype(jnp.float32) - make(n, a.shape, k)) for n, a in ref.named_leaves(params).items()}

        program = {"loss": [], "mtp_loss": [], "slots": []}
        for i in range(n_verify):
            state, out = trainer.fit(state, feed, num_steps=1)
            program["loss"].append(out["loss"])
            program["mtp_loss"].append(out.get("mtp_loss", 0.0))
            program["slots"].append(int(out.get("moe_slots", 0)))
            if i == 0:
                cell.mark("compiled")
                obs["kernels"] = sorted({
                    r.get("attrs", {}).get("kernel") for r in list(tel.flight)
                    if r.get("name") == "attention.kernel"
                })
                with ctx.mesh:
                    norms, samples = first_gradient(state.opt_state[0].mu)
                program["grad_norm"] = {n: float(v) for n, v in norms.items()}
                program["grad_sample"] = {n: np.asarray(v) for n, v in samples.items()}
                cell.lap("first step and its readings")
        with ctx.mesh:
            program["delta_norm"] = {n: float(v) for n, v in change(state.params, key).items()}
        cell.lap("further steps and the change's norms")
        obs["program"] = program

        traces0 = trainer.compile_counts["train_step"]
        cell.start_window()
        t0 = time.perf_counter()
        steps = 0
        while True:
            if cell.trace and steps == 0:
                cell.trace_start()
            traced_chunk = cell.tracing
            c0, w0 = time.perf_counter(), time.time()
            state, out = trainer.fit(state, feed, num_steps=k_chunk)
            c1 = time.perf_counter()
            steps += k_chunk
            obs["chunks"].append((c1 - c0) / k_chunk)
            obs["counters"].append({n: out[n] for n in COUNTERS if n in out})
            if not traced_chunk:
                obs["untraced_s"] += c1 - c0
                obs["untraced_steps"].append(steps - k_chunk)
            else:
                obs["traced_steps"].append(steps - k_chunk)
            obs["input_wait_ms"] += sum(
                r["value"] for r in list(tel.flight)
                if r.get("kind") == "gauge" and r.get("name") == "input_wait_ms" and r["ts"] >= w0
            )
            if cell.tracing and steps >= int(mix["trace_steps"]):
                cell.trace_stop()
            if c1 - t0 >= cell.seconds:
                break
        obs["window_s"] = time.perf_counter() - t0
        obs["steps"] = steps
        obs["last_loss"] = out["loss"]
        obs["train_step_traces_in_window"] = trainer.compile_counts["train_step"] - traces0
        cell.end_window()
        del state
        gc.collect()
        return {"metric": -obs["last_loss"]}

    experiment.lagom(
        train_fn,
        DistributedConfig(
            module=model, hparams={}, sharding=section["sharding"],
            name=cell.workload.replace(".", "_"), log_dir=os.path.join(cell.out_dir, "lagom"),
        ),
    )
    gc.collect()

    # real tokens trained in the window: the pool is cycled in order
    per = len(pool[0]["tokens"])
    docs = [[n for row in rows[b * per:(b + 1) * per] for n in row] for b in range(len(pool))]
    trained = [(n_verify + i) % len(pool) for i in range(obs["steps"])]
    tokens = sum(real[b] for b in trained)
    # a step's slots on held experts: fit brings one count back with the loss,
    # its last step's, so the steps between two readings get the line between
    # them (the router trains: the count drifts by tens of per cent a window)
    at = [-1] + [(c + 1) * k_chunk - 1 for c in range(len(obs["counters"]))]
    read = [obs["program"]["slots"][-1]] + [c.get("moe_slots", 0) for c in obs["counters"]]
    slots = [int(round(float(np.interp(i, at, read)))) for i in range(obs["steps"])]
    # for the utilization, only the chunks the profiler did not slow
    quiet = [i + j for i in obs["untraced_steps"] for j in range(k_chunk)]
    traced = [i + j for i in obs["traced_steps"] for j in range(k_chunk)]
    cell.note(f"window from {cell.window[0]:.3f} (time.time), chunks ms/step "
              + json.dumps([round(c * 1e3, 1) for c in obs["chunks"]]))
    cell.note("counters at each chunk's last step " + json.dumps(obs["counters"]))
    obs.update(
        needed_flops=sum(ref.train_flops(sizes, docs[trained[i]], slots[i]) for i in quiet),
        traced_slots=sum(slots[i] for i in traced), sizes=sizes, chips=chips,
    )
    dropped = sum(c.get("moe_slots_dropped", 0) for c in obs["counters"])
    if not cell.rehearsal and "flash" not in obs["kernels"]:
        raise RuntimeError(f"attention took {obs['kernels']}, not the flash kernel this cell measures")

    # the program's state is freed: the reference has the chip to itself
    verdict = judge(section["limits"], obs["program"], follow(cfg, pool[:n_verify], cell.seed, None, cell.note))
    verdict.add("slots_dropped_in_window", dropped, 0, "the expert share layer is dropless")
    steps_ok = obs["steps"] if np.isfinite(obs["last_loss"]) else 0
    return {
        "end_to_end": {"train_tok_s_chip": tokens / obs["window_s"] / chips},
        "attempted": obs["steps"],
        "failed": obs["steps"] - steps_ok,
        "correct": verdict.correct and steps_ok == obs["steps"],
        "obs": obs,
    }


def follow(cfg, batches, seed, low=None, note=lambda text: None):
    """The plain reference (``low``: one of its controls) over the same first
    steps, from weights it makes itself from the seed."""
    import jax
    import jax.numpy as jnp

    ref = configs.load_reference(cfg)
    sizes = ref.sizes(cfg, KIND)
    spec, key = ref.leaf_spec(sizes), weights.base_key(seed)
    made = {n: jax.jit(lambda k, n=n: weights.stacked(k, n, spec, spec[n][1])) for n in spec}
    feed = [{k: jnp.asarray(v) for k, v in b.items()} for b in batches]
    t0 = time.perf_counter()
    out = ref.train_steps(
        lambda n: made[n](key), list(spec), feed, sizes, cfg[KIND]["optimizer"], low, note
    )
    note(f"reference ({low or 'float32'}) followed {len(batches)} steps in {time.perf_counter() - t0:.1f} s")
    return out


def judge(limits, program, reference, prefix=""):
    verdict = compare.Comparison()
    for i, (p, r) in enumerate(zip(program["loss"], reference["loss"])):
        verdict.add(f"{prefix}loss_step{i + 1}_abs_gap", abs(p - r), limits["loss_abs"],
                    f"program {p:.6f} reference {r:.6f}")
    for i, (p, r) in enumerate(zip(program["mtp_loss"], reference["mtp_loss"])):
        verdict.add(f"{prefix}mtp_loss_step{i + 1}_abs_gap", abs(p - r), limits["mtp_loss_abs"],
                    f"program {p:.6f} reference {r:.6f}")
    for i, (p, r) in enumerate(zip(program["slots"], reference["slots"])):
        verdict.add(f"{prefix}slots_step{i + 1}_rel_gap", abs(p - r) / max(r, 1), limits["slots_rel"],
                    f"program {p} reference {r} slots on held experts")
    for what in ("grad_norm", "delta_norm"):
        gap, where = compare.worst_leaf_gap(program[what], reference[what])
        verdict.add(f"{prefix}{what}_worst_leaf_gap", gap, limits[f"{what}_worst_leaf"], f"at {where}")
    # the selection is discontinuous: a near-tie that bfloat16 activations flip moves a token's
    # whole contribution between experts, so the leaves on the routed path are held apart
    routed = {n for n in reference["grad_sample"] if n.endswith("router") or ".experts_" in n}
    for what, names in (("grad_sample", set(reference["grad_sample"]) - routed), ("grad_sample_routed", routed)):
        gap, where = compare.worst_leaf_difference(
            {n: program["grad_sample"][n] for n in names}, {n: reference["grad_sample"][n] for n in names}
        )
        verdict.add(f"{prefix}{what}_worst_leaf_difference", gap, limits[f"{what}_worst_leaf"], f"at {where}")
    return verdict


def controls(cfg, mix, seed, note=lambda text: None):
    """Each variant of the configuration's control file put in the program's
    place and judged against the reference at the cell's limits: every one has
    to come out as not correct. Not part of a run (``tools/control.py`` at the
    cell's size, ``checks/test_train_packed_ref.py`` at a toy size)."""
    pool, _ = traffic.packed_pool(mix, seed, configs.load_reference(cfg).sizes(cfg, KIND)["vocab"])
    batches = pool[:int(mix["verify_steps"])]
    with open(os.path.join(configs.ROOT, cfg["control"])) as f:
        variants = json.load(f)["variants"]
    reference = follow(cfg, batches, seed, None, note)
    return {
        name: judge(cfg[KIND]["limits"], follow(cfg, batches, seed, {k: v for k, v in low.items() if k != "why"}, note),
                    reference, f"control.{name}.")
        for name, low in variants.items()
    }
