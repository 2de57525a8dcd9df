"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-evabyte-packed16k``, as ``test_train_laguna.py`` does for the
Laguna cell: the ``Cell`` is built from ``checks/tiny.evabyte.json`` with
``run.merge``; a sound run is judged correct with both losses read, both
controls and the three planted faults are judged not correct;
``counts_evabyte.py`` is held against the entries counted one by one from the
reference's masks; the six readers this cell brings read a synthetic timeline,
and find nothing (and do not raise) in the recorded trace of a program that
has none of their scopes."""

import argparse
import json
import math
import os
import types

import numpy as np
import pytest

from benchmark import counts_evabyte, run, spans, trace
from benchmark.kinds import train_packed_ref

CELL = "train-evabyte-packed16k"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("train.eva_attn_share", "train.eva_prep_share", "train.eva_kernel_share", "train.eva_prep_roofline",
       "train.eva_attend_roofline", "train.multibyte_head_share")


def toy(seed, seconds=1.0):
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(run.HERE, "checks", "tiny.evabyte.json")) as f:
        tiny = json.load(f)
    config = run.merge(config, tiny["config"])
    mix = run.merge(mix, tiny["traffic"][mix["kind"]])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse_cpu=True)
    return run.Cell(args, workload, config, mix), config, mix


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1 and workload["traffic"] == "packedbytes16k"
    assert config["name"] == "evabyte" and train_packed_ref.KIND in config
    assert (mix["rows_per_chip"], mix["seq_len"], mix["pool_batches"], mix["steps_per_chunk"]) == (1, 16384, 32, 4)
    assert mix["documents"] == {"distribution": "lognormal", "median": 2400, "sigma": 1.0, "min": 64, "max": 16384}
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | {"train.mfu", "train.attn_kernel_share", "train.attn_qkv_proj_share", "train.scope_scan_share",
                       "train.step_ms_p50", "train.peak_hbm_gib"} <= listed
    assert not {"train.flash_roofline", "train.mtp_share", "train.moe_experts_share", "train.window_attn_share",
                "train.sparse_index_share", "train.conv_op_share"} & listed
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s_chip" for m in bench["per_layer"] if m["name"] in NEW)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_tok_s_chip")["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "evabyte")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "max_position_embeddings", "max_seq_length"]


def test_the_configuration_states_its_cut_and_keeps_every_width():
    _bench, _workload, config, _mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import configs

    ref = configs.load_reference(config)
    s = ref.sizes(config, train_packed_ref.KIND)
    assert (s["d_model"], s["d_ff"], s["n_heads"], s["head_dim"], s["vocab"]) == (4096, 11008, 32, 128, 320)
    assert (s["window"], s["chunk"], s["pred_heads"], s["rope_theta"], s["norm_eps"]) == (2048, 16, 8, 1e5, 1e-5)
    assert (s["n_layers"], s["max_positions"]) == (4, 16384)
    assert set(config["reduced"]) == set(config["why_reduced"]) == {k for k, v in config.items() if isinstance(v, dict) and "published" in v}
    assert {"rotary", "summaries", "windows", "head", "loss", "optimizer"} <= set(config["assumed"])
    spec = ref.leaf_spec(s)
    total = sum(math.prod(shape) * max(stacked, 1) for shape, stacked, _std, _mean in spec.values())
    assert total == config["parameters"]["total"] == 821366784
    fields = ref.program_fields(config, train_packed_ref.KIND)
    assert fields["layer_types"] == ("eva_attention",) * 4 and (fields["eva_window"], fields["eva_chunk"]) == (2048, 16)
    assert (fields["pred_heads"], fields["norm_unit_offset"], fields["residual_f32"], fields["scan_layers"]) == (8, True, True, False)
    assert {n for n in spec if n.endswith("router")} == {f"l{i}.phi.router" for i in range(4)}  # the module docstring says why


def brute_entries(rows, window, chunk, s):
    """Count the entries one by one from the reference's masks over the documents laid from each row's start."""
    import jax.numpy as jnp

    from benchmark.references import eva_dense

    seg = np.zeros((len(rows), s), np.int32)
    for r, row in enumerate(rows):
        at = 0
        for j, n in enumerate(row):
            seg[r, at:at + n] = j + 1
            at += n
    (remote, local), _cut = eva_dense.seen_entries({"segment_ids": jnp.asarray(seg)}, {"window": window, "chunk": chunk})
    return remote, local


@pytest.mark.parametrize("rows,window,chunk,s", [
    ([[5, 40, 1, 33]], 16, 4, 96), ([[200]], 64, 8, 256), ([[3, 3, 3]], 8, 2, 16), ([[64, 65, 63]], 64, 16, 192),
    ([[100, 20], [30, 90]], 32, 4, 128), ([[128]], 128, 4, 128),
])
def test_counts_against_the_masks_on_small_documents(rows, window, chunk, s):
    docs = [n for row in rows for n in row]
    sizes = {"window": window, "chunk": chunk, "max_positions": s, "head_dim": 16, "n_heads": 4, "n_layers": 2}
    assert counts_evabyte.rows_of(docs, s) == rows
    remote, local = brute_entries(rows, window, chunk, s)
    assert counts_evabyte.entries(docs, sizes) == (remote, local)
    assert counts_evabyte.attend_flops(sizes, docs) == 3 * 2 * 2 * 16 * 4 * 2 * (remote + local)
    assert (remote == 0) == (max(sum(row) for row in rows) <= window or window == s)


def test_the_mix_sends_its_queries_summaries_and_cuts_its_chunks():
    from benchmark import configs, traffic

    _bench, _workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    sizes = configs.load_reference(config).sizes(config, train_packed_ref.KIND)
    rows = traffic.packed_rows(mix)
    docs = [n for row in rows for n in row]
    assert len(rows) == 32 and len(docs) == 148 and sum(docs) == 520295  # 99.24% of 32 x 16,384 are real bytes
    assert 0.85 < sum(n for n in docs if n > 2048) / sum(docs) < 0.86
    assert min(sum(row) for row in rows) == 16035  # the fullest 32 of the 38 rows that 190 documents pack into: no row under 97.8%
    seen = [counts_evabyte.entries(row, sizes) for row in rows]
    remote, local = sum(r for r, _ in seen), sum(l for _, l in seen)
    assert 0.175 < remote / (remote + local) < 0.176  # what the step's eva_remote_share reads
    assert 872 < local / sum(docs) < 873 and 185 < remote / sum(docs) < 186  # entries a byte
    starts = [sum(row[:j]) for row in rows for j in range(1, len(row))]
    assert len(starts) == 116 and all(at % 2048 for at in starts)  # every later document starts inside a window
    assert sum(1 for at in starts if at % 16) == 110  # and nineteen in twenty inside a chunk
    # the needed products: 83 TFLOP a step, the attention a twenty-fifth of them
    steps = [counts_evabyte.train_flops(sizes, row) for row in rows]
    assert 83.3e12 < sum(steps) / 32 < 83.4e12
    assert 0.040 < sum(counts_evabyte.attend_flops(sizes, row) for row in rows) / sum(steps) < 0.041


def test_sound_run_is_correct_and_reads_both_losses(capsys):
    cell, _config, mix = toy(2**31 + 13)
    result = train_packed_ref.run(cell)
    out = capsys.readouterr().out.splitlines()
    comparisons = [json.loads(l[len("comparison "):]) for l in out if l.startswith("comparison ")]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["end_to_end"]["train_tok_s_chip"] > 0
    names = {c["name"] for c in comparisons}
    assert {"loss_step1_abs_gap", "mtp_loss_step2_abs_gap", "grad_sample_worst_leaf_difference",
            "grad_sample_routed_worst_leaf_difference", "delta_norm_worst_leaf_gap"} <= names
    obs = result["obs"]
    assert len(obs["counters"]) == obs["steps"] // mix["steps_per_chunk"] and obs["counters"][0] == {}
    assert obs["needed_flops"] > 0 and obs["kernels"] == ["xla_dense"]  # the CPU's dispatch
    assert all(v > 0 for v in obs["program"]["mtp_loss"]) and obs["program"]["slots"] == [0, 0]
    assert obs["program"]["grad_norm"]["l0.phi.router"] > 0 and obs["program"]["grad_norm"]["l1.mu"] > 0


def test_every_control_is_judged_not_correct():
    _cell, config, mix = toy(5)
    verdicts = train_packed_ref.controls(config, mix, 5)
    assert set(verdicts) == {"float8_operands", "bfloat16_state", "no_summaries", "summaries_cross_documents", "one_head"}
    assert not any(v.correct for v in verdicts.values())
    failed = {name: {r["name"].split(".")[-1] for r in v.rows if not r["ok"]} for name, v in verdicts.items()}
    assert "grad_sample_worst_leaf_difference" in failed["float8_operands"]
    assert "delta_norm_worst_leaf_gap" in failed["bfloat16_state"]
    # the planted faults, by the limits held against them: phi and mu get no gradient without summaries; a
    # summary over two documents moves what the leaves below it get; seven blocks of the head get nothing
    assert {"grad_norm_worst_leaf_gap", "grad_sample_routed_worst_leaf_difference"} <= failed["no_summaries"]
    assert "grad_sample_worst_leaf_difference" in failed["summaries_cross_documents"]
    assert {"grad_norm_worst_leaf_gap", "grad_sample_worst_leaf_difference"} <= failed["one_head"]


def fake_obs(ops, busy, device_ops):
    tl = spans.Timeline.__new__(spans.Timeline)
    tl.ops, tl.busy_ns, tl.lo, tl.hi = [ops], [busy], 0, busy
    tl.gaps, tl.threads, tl._scope_times = [], [], {}
    cell, _config, mix = toy(7)
    cell.trace_dir = f"synthetic-{id(tl)}"
    spans._LOADED[cell.trace_dir] = tl
    from benchmark import configs

    sizes = configs.load_reference(cell.config).sizes(cell.config, train_packed_ref.KIND)
    return {"cell": cell, "trace": {"device_ops": device_ops, "busy_s": busy / 1e9}, "needed_flops": 1.0,
            "sizes": sizes, "chips": 1, "traced_steps": [0], "device_kind": "TPU v5 lite"}, mix


def test_the_new_readers_on_a_synthetic_timeline():
    """Names as the step compiled for a v5e carries them (``compile_step.py --out``)."""
    fwd = "jit(train_step)/jvp(Decoder)/layers_1/layer/attn/"
    bwd = "jit(train_step)/transpose(jvp(Decoder))/layers_2/checkpoint/layer/attn/"
    ops = [
        (0, 100, "%flash_fwd.3", fwd + "jit(eva_attention)/eva.local/flash_fwd/pallas_call:"),
        (100, 150, "%flash_fwd.4", fwd + "jit(eva_attention)/eva.remote/flash_fwd/pallas_call:"),
        (150, 350, "%flash_bwd.2", bwd + "jit(eva_attention)/eva.local/flash_bwd/pallas_call:"),
        (350, 370, "%fusion.9", fwd + "jit(eva_attention)/eva.merge/mul:"),
        (370, 400, "%fusion.8", fwd + "jit(eva_attention)/eva.remote/ne:"),
        (400, 440, "%fusion.1", fwd + "eva.prep/reduce_sum:"),
        (440, 460, "%fusion.2", bwd + "eva.prep/mul:"),
        (460, 520, "%fusion.3", fwd + "wq/dot_general:"),
        (520, 700, "%fusion.4", "jit(train_step)/jvp(Decoder)/layers_0/layer/mlp/w_up/dot_general:"),
        (700, 760, "%fusion.5", "jit(train_step)/jvp(Decoder)/lm_head/dot_general:"),
        (760, 800, "%fusion.6", "jit(train_step)/jvp(loss)/reduce_max:"),
        (800, 1000, "%fusion.7", "jit(train_step)/optimizer/add:"),
    ]
    obs, mix = fake_obs(ops, 1000, [])
    read = lambda name: run.reader(name).read(obs)
    assert read("train.eva_attn_share") == pytest.approx(52.0)
    assert read("train.eva_prep_share") == pytest.approx(6.0)
    assert read("train.eva_kernel_share") == pytest.approx(35.0)  # the kernels alone: not the mask's compare under eva.remote
    assert read("train.multibyte_head_share") == pytest.approx(10.0)
    from benchmark.peaks import peaks_for

    docs = counts_evabyte.traced_documents(obs)
    assert len(docs) == mix["steps_per_chunk"] and all(sum(d) <= mix["seq_len"] * mix["rows_per_chip"] for d in docs)
    peak = peaks_for("TPU v5 lite")
    want = sum(counts_evabyte.attend_flops(obs["sizes"], d) for d in docs) / 350e-9 / peak["bf16_flops_per_s"] * 100
    assert read("train.eva_attend_roofline") == pytest.approx(want)
    positions = mix["steps_per_chunk"] * mix["rows_per_chip"] * mix["seq_len"]
    want = counts_evabyte.prep_bytes(obs["sizes"], positions) / 60e-9 / peak["hbm_bytes_per_s"] * 100
    assert read("train.eva_prep_roofline") == pytest.approx(want)
    # a position's share of the bytes: k and v twice forward, once backward, their cotangents; bfloat16, two layers
    assert counts_evabyte.prep_bytes(obs["sizes"], 1) == 2 * (4 * 16 * 2) * (2 * (2 + 2 / 4) + (4 + 2 / 4))


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(tmp_path):
    """The recorded trace of PR 23's dense program (under another
    configuration's sizes and under this one's), and a run with no trace:
    every reader returns None and none raises (the parent's side of a traced
    run of another cell)."""
    import shutil

    recorded = str(tmp_path)
    os.makedirs(os.path.join(recorded, "plugins", "profile", "recorded"))
    shutil.copy(os.path.join(HERE, "recorded", "train.xplane.pb"),
                os.path.join(recorded, "plugins", "profile", "recorded", "host.xplane.pb"))
    summary = trace.reduce(recorded)
    cell = types.SimpleNamespace(trace_dir=recorded, chips=1, mix={}, seed=1)
    obs = {"cell": cell, "trace": summary, "needed_flops": 1.0, "sizes": {"vocab": 32768}, "chips": 1,
           "traced_steps": [0], "device_kind": "TPU v5 lite"}
    assert [run.reader(n).read(obs) for n in NEW] == [None] * len(NEW)
    assert [run.reader(n).read({"sizes": {}}) for n in NEW] == [None] * len(NEW)
    from benchmark import configs

    _cell, config, _mix = toy(3)
    obs["sizes"] = configs.load_reference(config).sizes(config, train_packed_ref.KIND)
    found = {n: run.reader(n).read(obs) for n in NEW}  # a dense program has attn, lm_head and loss, and no eva.* scope
    assert [found[n] for n in NEW if "prep" in n or "kernel" in n or "attend" in n] == [None] * 4
