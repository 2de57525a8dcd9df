"""Drives the ``train_packed_ref`` kind end to end at toy sizes on the CPU for
the cell ``train-sdar30b-packed8k``, as ``test_train_keye.py`` does for the
Keye cell: the ``Cell`` is built from ``checks/tiny.sdar-30b-a3b-chat.json``
with ``run.merge``; a sound run is judged correct with its counters read,
both controls are judged not correct; ``counts_sdar.py`` is held against the
pairs counted one by one from the reference's mask; the three readers this
cell brings read a synthetic timeline, and find nothing (and do not raise) in
the recorded trace of a program that has none of their scopes."""

import argparse
import json
import math
import os
import types

import numpy as np
import pytest

from benchmark import counts_sdar, run, spans, trace
from benchmark.kinds import train_packed_ref

CELL = "train-sdar30b-packed8k"
HERE = os.path.dirname(os.path.abspath(__file__))
NEW = ("train.blockdiff_attn_share", "train.blockdiff_noise_share", "train.blockdiff_flash_roofline")


def toy(seed, seconds=1.0):
    os.environ["MAGGY_TPU_COMPILE_CACHE"] = "0"
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    with open(os.path.join(run.HERE, "checks", "tiny.sdar-30b-a3b-chat.json")) as f:
        tiny = json.load(f)
    config = run.merge(config, tiny["config"])
    mix = run.merge(mix, tiny["traffic"][mix["kind"]])
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0, rehearse_cpu=True)
    return run.Cell(args, workload, config, mix), config, mix


def test_the_cell_names_this_kind_and_configuration():
    bench, workload, config, mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    assert mix["kind"] == train_packed_ref.KIND and workload["chips"] == 1 and workload["traffic"] == "packed8k"
    assert config["name"] == "sdar-30b-a3b-chat" and train_packed_ref.KIND in config
    assert (mix["rows_per_chip"], mix["seq_len"], mix["pool_batches"], mix["steps_per_chunk"]) == (2, 8192, 16, 8)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert set(NEW) | {"train.mfu", "train.attn_kernel_share", "train.attn_qkv_proj_share", "train.scope_scan_share",
                       "train.moe_experts_roofline", "train.moe_slots_dropped", "train.moe_route_share",
                       "train.compile_ms_in_window", "train.step_ms_p50", "train.peak_hbm_gib"} <= listed
    assert not {"train.flash_roofline", "train.moe_shared_share", "train.mtp_share", "train.sparse_index_share",
                "train.window_attn_share", "train.eva_attn_share", "train.conv_op_share"} & listed
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tok_s_chip" for m in bench["per_layer"] if m["name"] in NEW)
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "train_tok_s_chip")["workloads"]
    entry = next(c for c in bench["configs"] if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "max_position_embeddings"]
    assert entry["source"] == config["source"]


def test_the_configuration_states_its_cut_and_keeps_every_width():
    _bench, _workload, config, _mix = run.load_cell(CELL, False)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    from benchmark import configs

    ref = configs.load_reference(config)
    s = ref.sizes(config, train_packed_ref.KIND)
    assert (s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"], s["moe_d_ff"]) == (2048, 32, 4, 128, 768)
    assert (s["n_experts"], s["top_k"], s["held"], s["rope_theta"], s["norm_eps"]) == (128, 8, 16, 1e6, 1e-6)
    assert (s["vocab"], s["max_positions"]) == (18992, 8192) and s["n_layers"] >= 4  # the guide's floor
    assert (s["block"], s["noise_eps"], s["mask_token_id"]) == (4, 1e-3, 0)
    assert set(config["reduced"]) == set(config["why_reduced"]) == {k for k, v in config.items() if isinstance(v, dict) and "published" in v}
    assert {"a_block_length", "b_noise_key", "c_schedule", "d_mask_token_id", "e_qk_norm", "f_no_shift", "optimizer"} <= set(config["assumed"])
    spec = ref.leaf_spec(s)
    total = sum(math.prod(shape) * max(stacked, 1) for shape, stacked, _std, _mean in spec.values())
    layer = 18_874_368 + 262_144 + 75_497_472 + 4_352  # attention, router, 16 experts, the four norms
    assert total == config["parameters"]["total"] == s["n_layers"] * layer + 2 * 18992 * 2048 + 2048 + 2048  # final norm, [MASK]
    assert (spec["mask_embed"][2], spec["moe.router"][2], spec["embed"][2]) == (0.02, 0.06, 1.0)
    fields = ref.program_fields(config, train_packed_ref.KIND)
    assert fields["block_diffusion"] and (fields["block"], fields["router"], fields["experts_held"]) == (4, "softmax", 16)
    assert (fields["remat"], fields["remat_policy"], fields["chunk_of_load"]) == (True, "nothing", 0.75)
    assert config["train_packed_ref"]["optimizer"]["lr"] == 1e-5


def brute_pairs(docs, block):
    """The pairs of the reference's four cases, counted one by one on the
    explicit ``2L x 2L`` mask of one row holding these documents."""
    import jax.numpy as jnp

    from benchmark.references import blockdiff_gqa_moe as reference

    n = sum(docs)
    seg = np.concatenate([np.full(d, j + 1) for j, d in enumerate(docs)])
    pos = np.concatenate([np.arange(d) for d in docs])
    two = lambda a: jnp.asarray(np.concatenate([a, a])[None])
    stream = jnp.asarray((np.arange(2 * n) >= n)[None])
    mask = reference.sees(two(seg), two(pos // block), stream, two(seg), two(pos // block), stream)
    return int(mask.sum())


@pytest.mark.parametrize("docs,block", [([61, 70, 125], 4), ([1, 2, 3, 4, 5, 8, 16, 17], 4), ([13, 40], 3), ([9], 16)])
def test_counts_against_a_brute_force_count(docs, block):
    kept, causal = counts_sdar.pairs(docs, block)
    assert kept == brute_pairs(docs, block)
    assert causal == sum(n * (n + 1) // 2 for n in docs)
    s = {"n_heads": 4, "head_dim": 32, "n_layers": 3, "block": block, "d_model": 64, "n_kv_heads": 1, "n_experts": 16,
         "moe_d_ff": 48, "vocab": 512}
    assert counts_sdar.attention_flops_forward(s, docs) == 2 * 2 * 4 * 32 * 3 * kept
    assert counts_sdar.flash_flops(s, docs) == 3 * counts_sdar.attention_flops_forward(s, docs)
    per_position = 3 * (2 * 64 * 4 * 32 + 2 * 64 * 32 + 64 * 16)  # wq, wo; wk, wv; the router: three layers
    assert counts_sdar.matmul_params_per_position(s) == per_position
    tokens = sum(docs)
    assert counts_sdar.train_flops(s, docs, 7) == 3 * (
        2 * (per_position * 2 * tokens + 64 * 512 * tokens + 3 * 64 * 48 * 7) + counts_sdar.attention_flops_forward(s, docs)
    )


def test_sound_run_is_correct_and_reads_its_counters(capsys):
    cell, _config, mix = toy(2**31 + 13)
    result = train_packed_ref.run(cell)
    out = capsys.readouterr().out.splitlines()
    comparisons = [json.loads(l[len("comparison "):]) for l in out if l.startswith("comparison ")]
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["end_to_end"]["train_tok_s_chip"] > 0
    names = {c["name"] for c in comparisons}
    assert {"loss_step1_abs_gap", "loss_step2_abs_gap", "slots_step1_rel_gap", "grad_sample_worst_leaf_difference",
            "grad_sample_routed_worst_leaf_difference", "slots_dropped_in_window"} <= names
    obs = result["obs"]
    assert len(obs["counters"]) == obs["steps"] // mix["steps_per_chunk"]
    assert all(c["moe_slots"] > 0 and c["moe_slots_dropped"] == 0 for c in obs["counters"])
    assert obs["needed_flops"] > 0 and obs["kernels"] == ["xla_dense"]  # the CPU's dispatch, one choice for both streams
    # a real token counts once: the pool's loss_mask, not the 2L positions the layers see
    assert result["end_to_end"]["train_tok_s_chip"] * obs["window_s"] <= obs["steps"] * mix["seq_len"] * mix["rows_per_chip"]


def test_every_control_is_judged_not_correct():
    _cell, config, mix = toy(5)
    verdicts = train_packed_ref.controls(config, mix, 5)
    assert set(verdicts) == {"float8_operands", "bfloat16_state"}
    assert not any(v.correct for v in verdicts.values())
    failed = {name: {r["name"].split(".")[-1] for r in v.rows if not r["ok"]} for name, v in verdicts.items()}
    assert "grad_sample_worst_leaf_difference" in failed["float8_operands"]
    assert "delta_norm_worst_leaf_gap" in failed["bfloat16_state"]


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
    layer = "jit(train_step)/jvp(MoEDecoder)/while/body/closed_call/layers/layer/attn/"
    ops = [
        (0, 100, "%flash_fwd.1", layer + "jit(flash_attention)/flash_fwd/pallas_call:"),
        (100, 300, "%flash_bwd.2", "jit(train_step)/transpose(jvp(MoEDecoder))/while/body/layers/layer/attn/jit(noised_attention)/flash_bwd/pallas_call:"),
        (300, 340, "%fusion.1", layer + "jit(noised_attention)/diffusion.merge/exp:"),
        (340, 350, "%fusion.2", layer + "diffusion.noise/sub:"),
        (350, 360, "%fusion.3", "jit(train_step)/jvp(MoEDecoder)/diffusion.noise/threefry2x32:"),
        (360, 500, "%fusion.4", layer + "wq/dot_general:"),
        (500, 800, "%gmm.1", "jit(train_step)/jvp(MoEDecoder)/while/body/layers/layer/moe/moe.experts/gmm/pallas_call:"),
        (800, 1000, "%fusion.5", "jit(train_step)/optimizer/add:"),
    ]
    obs, mix = fake_obs(ops, 1000, [["%flash_fwd.1", 100e-9], ["%flash_bwd.2", 200e-9]])
    read = lambda name: run.reader(name).read(obs)
    assert read("train.blockdiff_attn_share") == pytest.approx(49.0)  # everything under attn: 0..350 and 360..500
    assert read("train.blockdiff_noise_share") == pytest.approx(6.0)  # the merge, a layer's bounds, the step's draw
    from benchmark.counts_keye import traced_documents
    from benchmark.peaks import peaks_for

    docs = traced_documents(obs)
    assert len(docs) == mix["steps_per_chunk"] and all(sum(d) <= mix["seq_len"] * mix["rows_per_chip"] for d in docs)
    peak = peaks_for("TPU v5 lite")["bf16_flops_per_s"]
    want = sum(counts_sdar.flash_flops(obs["sizes"], d) for d in docs) / 300e-9 / peak * 100
    assert read("train.blockdiff_flash_roofline") == pytest.approx(want)


def test_the_new_readers_find_nothing_in_a_program_without_the_scopes(tmp_path):
    """The recorded trace of PR 23's dense program, and a run with no trace:
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
