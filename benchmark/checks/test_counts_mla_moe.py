"""Operation counts of the latent-attention, expert-share decoder on shapes
worked by hand, and at the published sizes against ISSUE 26's arithmetic."""

from benchmark import configs, counts_mla_moe as counts

SMALL = {"vocab": 10, "d_model": 4, "d_ff": 6, "moe_d_ff": 3, "n_heads": 2, "q_rank": 3, "kv_rank": 2,
         "d_nope": 2, "d_rope": 2, "d_v": 4, "n_dense": 1, "n_moe": 2, "n_experts": 8, "n_shared": 1, "mtp": 1}


def test_attention_params_by_hand():
    # q down 4x3 + q up 3x(2 heads x 4) + kv down 4x(2+2) + kv up 2x(2 heads x (2+4)) + out (2x4)x4
    assert counts.attention_params(SMALL) == 12 + 24 + 16 + 24 + 32 == 108


def test_matmul_params_by_hand():
    # attention in 1 dense + 2 expert layers + the MTP module's layer: 4 x 108
    # dense feed-forward 3x4x6 = 72; an expert layer: router 4x8 + shared 3x4x3 = 68, three of them (MTP's too)
    # MTP projection 8x4 = 32; the head 4x10 twice (main and MTP)
    assert counts.expert_params(SMALL) == 36
    assert counts.matmul_params_per_token(SMALL) == 4 * 108 + 72 + 3 * 68 + 32 + 2 * 40


def test_train_flops_of_two_documents_and_five_slots():
    # attention: documents of 3 and 1 tokens give 6 + 1 pairs, each 2 operations x 2 heads
    # x (4 wide keys + 4 wide values) x 4 layers with attention = 128
    per_token = counts.matmul_params_per_token(SMALL)
    fwd = 2 * (per_token * 4 + 36 * 5) + 128 * 7
    assert counts.train_flops(SMALL, [3, 1], 5) == 3 * fwd
    assert counts.experts_flops(SMALL, 5) == 3 * 2 * 36 * 5


def test_published_sizes():
    cfg = configs.load("benchmark/configs/glm-4.7-flash.json")
    s = configs.load_reference(cfg).sizes(cfg, "train_packed_ref")
    assert counts.attention_params(s) == 21_757_952  # 1.573M + 3.932M + 1.180M + 4.588M + 10.486M
    assert counts.expert_params(s) == 9_437_184
    # 6 x attention 130.5M + dense 62.9M + 5 x (router 0.13M + shared 9.44M) + W_eh 8.39M + 2 x head 39.65M
    assert counts.matmul_params_per_token(s) == 328_990_720
    # with half a slot a token on held experts in each of 5 expert layers: ISSUE 26's 352.6M a token
    assert round((counts.matmul_params_per_token(s) + 2.5 * counts.expert_params(s)) / 1e5) == 3526
