"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-lfm2moe-packed8k``, as ``test_train_packed_ref.py`` does for
the GLM cell: the ``Cell`` is built from ``checks/tiny.lfm2-24b-a2b.json`` with
``run.merge``; a sound run is judged correct with its counters read, both
controls are judged not correct."""

import argparse
import json
import os

from benchmark import run
from benchmark.kinds import train_packed_ref

CELL = "train-lfm2moe-packed8k"


def toy(seed, seconds=1.0):
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(run.HERE, "checks", "tiny.lfm2-24b-a2b.json")) as f:
        tiny = json.load(f)
    config = run.merge(config, tiny["config"])
    mix = run.merge(mix, tiny["traffic"][mix["kind"]])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse_cpu=True)
    return run.Cell(args, workload, config, mix), config, mix


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1
    assert config["name"] == "lfm2-24b-a2b" and train_packed_ref.KIND in config
    assert mix["rows_per_chip"] * mix["pool_batches"] == 32 and mix["seq_len"] == 8192
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert {"train.conv_op_share", "train.conv_mix_share", "train.conv_mix_roofline", "train.flash_roofline"} <= listed
    assert not {"train.mla_proj_share", "train.moe_shared_share", "train.mtp_share", "train.scope_scan_share"} & listed


def test_sound_run_is_correct_and_reads_its_counters(capsys):
    cell, _config, mix = toy(2**31 + 13)
    result = train_packed_ref.run(cell)
    out = capsys.readouterr().out.splitlines()
    comparisons = [json.loads(l[len("comparison "):]) for l in out if l.startswith("comparison ")]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["end_to_end"]["train_tok_s_chip"] > 0
    names = {c["name"] for c in comparisons}
    assert {"loss_step1_abs_gap", "slots_step1_rel_gap", "grad_sample_worst_leaf_difference",
            "grad_sample_routed_worst_leaf_difference", "slots_dropped_in_window"} <= names
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
