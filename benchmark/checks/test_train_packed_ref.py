"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-glm47flash-packed8k``. ``run.py --rehearse-cpu`` looks a
kind's toy sizes up in ``checks/tiny.json``, which knows one configuration;
this check builds its ``Cell`` itself from ``checks/tiny.glm-4.7-flash.json``
with ``run.merge``: a sound run is judged correct with its counters read, both
controls and a step that returns its state unchanged are judged not correct."""

import argparse
import json
import os

import pytest

from benchmark import run
from benchmark.kinds import train_packed_ref

CELL = "train-glm47flash-packed8k"


def toy(seed, seconds=1.0):
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(run.HERE, "checks", "tiny.glm-4.7-flash.json")) as f:
        tiny = json.load(f)
    config = run.merge(config, tiny["config"])
    mix = run.merge(mix, tiny["traffic"][mix["kind"]])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse_cpu=True)
    return run.Cell(args, workload, config, mix), config, mix


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1
    assert config["name"] == "glm-4.7-flash" and train_packed_ref.KIND in config


def test_sound_run_is_correct_and_reads_its_counters(capsys):
    cell, _config, mix = toy(2**31 + 11)
    result = train_packed_ref.run(cell)
    out = capsys.readouterr().out.splitlines()
    comparisons = [json.loads(l[len("comparison "):]) for l in out if l.startswith("comparison ")]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["end_to_end"]["train_tok_s_chip"] > 0
    names = {c["name"] for c in comparisons}
    assert {"loss_step1_abs_gap", "mtp_loss_step2_abs_gap", "slots_step1_rel_gap",
            "grad_sample_worst_leaf_difference", "slots_dropped_in_window"} <= names
    obs = result["obs"]
    assert len(obs["counters"]) == obs["steps"] // mix["steps_per_chunk"]
    assert all(c["moe_slots"] > 0 and c["moe_slots_dropped"] == 0 for c in obs["counters"])
    assert obs["needed_flops"] > 0 and obs["kernels"] == ["xla_dense"]  # the CPU's dispatch


def test_every_control_is_judged_not_correct():
    _cell, config, mix = toy(5)
    verdicts = train_packed_ref.controls(config, mix, 5)
    assert set(verdicts) == {"float8_operands", "bfloat16_state"}
    assert not any(v.correct for v in verdicts.values())
    failed = {name: {r["name"].split(".")[-1] for r in v.rows if not r["ok"]} for name, v in verdicts.items()}
    assert "grad_sample_worst_leaf_difference" in failed["float8_operands"]
    assert "delta_norm_worst_leaf_gap" in failed["bfloat16_state"]


def test_training_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    import jax

    from maggy_tpu.train import trainer

    real = trainer.Trainer.step

    def frozen(self, state, batch):
        _, metrics = real(self, jax.tree.map(lambda a: a.copy() if hasattr(a, "copy") else a, state), batch)
        return state, metrics

    monkeypatch.setattr(trainer.Trainer, "step", frozen)
    cell, _config, _mix = toy(6)
    result = train_packed_ref.run(cell)
    out = capsys.readouterr().out.splitlines()
    comparisons = {json.loads(l[len("comparison "):])["name"]: json.loads(l[len("comparison "):])["ok"]
                   for l in out if l.startswith("comparison ")}
    assert result["correct"] is False and not comparisons["delta_norm_worst_leaf_gap"]
