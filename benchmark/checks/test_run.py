"""Drives ``run.py`` end to end at toy sizes on the CPU (``--rehearse-cpu``
skips the look for a chip): the last line key for key, every control judged as
not correct, and ``correct`` coming out false when the timed path is broken
underneath."""

import json

from benchmark import run
from benchmark.kinds import train_packed

CELL = "train-mistral7b-packed4k"


def last_line(capsys, argv):
    assert run.main(argv) == 0
    out = capsys.readouterr().out.strip().splitlines()
    comparisons = [json.loads(l[len("comparison "):]) for l in out if l.startswith("comparison ")]
    return json.loads(out[-1]), comparisons


def argv(seed):
    return ["--workload", CELL, "--seed", str(seed), "--seconds", "2", "--trace", "0", "--rehearse-cpu"]


def test_last_line_keys_and_no_device_metric(capsys):
    line, comparisons = last_line(capsys, argv(2**31 + 11))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device", "rehearsal"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and set(line["device"]) == {"platform", "kind", "count"}
    assert comparisons and all(set(c) == {"name", "value", "limit", "ok", "note"} for c in comparisons)


def test_every_control_is_judged_not_correct():
    _, _, config, mix = run.load_cell(CELL, True)
    verdicts = train_packed.controls(config, mix, 5)
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
    line, comparisons = last_line(capsys, argv(6))
    assert line["correct"] is False
    assert not {c["name"]: c["ok"] for c in comparisons}["delta_norm_worst_leaf_gap"]
