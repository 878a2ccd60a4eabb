"""perf/work_moe.py gives the counts ISSUE 26 states for one chip's share of Trinity-Large-Preview, from the
configuration file alone, and its FLOP and byte arithmetic adds up."""

import json
import os

import pytest

from perf import work, work_moe

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(HERE, "configs", "trinity-large-preview-ep8.json")) as f:
        return json.load(f)


def test_share_counts(cfg):
    assert work_moe.expert_params(cfg) == 3 * 3072 * 3072 == 28_311_552  # one expert: 28.3 M
    # q 18.9 M + k, v 6.3 M + o 18.9 M + output gate 18.9 M
    assert work_moe.attention_params(cfg) == 3 * 18_874_368 + 6_291_456 == 62_914_560
    assert round(work_moe.expert_layer_fixed_params(cfg) / 1e6) == 92  # + shared expert 28.3 M + router 0.8 M
    assert round(work_moe.dense_layer_params(cfg) / 1e6) == 176  # 63 M attention + 3 x 3072 x 12288
    assert round(work_moe.expert_layer_params(cfg) / 1e6) == 998  # + 32 held experts (906 M): 2.0 GB
    assert cfg["hidden_size"] * cfg["vocab_size"] == 76_873_728  # 25,024 rows: embedding + head 154 M
    # 0.35 + 4 x 2.0 + 0.31 GB of bf16 weights (the issue's 8.66 adds its parts rounded up)
    assert round(work_moe.share_params(cfg) * 2 / 1e9, 2) == 8.64
    assert work_moe.kv_bytes_per_token(cfg) == 20 * 1024  # 2 x 8 heads x 128 x 2 B x 5 layers
    assert 3072 * 64 * work_moe.kv_bytes_per_token(cfg) == pytest.approx(4.03e9, rel=1e-2)  # the pool: 3.9 GiB
    assert work_moe.layer_counts(cfg) == (1, 4) and work_moe.sliding_layers(cfg) == 4
    assert cfg["router_experts"] == 256 and cfg["num_experts"] == 32 and cfg["num_experts_per_tok"] == 4


def test_flops_and_bytes_add_up(cfg):
    # a sliding layer's query sees at most the window; the full layer sees everything
    assert work_moe.keys_seen(cfg, 100) == 5 * 100 and work_moe.keys_seen(cfg, 5000) == 4 * 4096 + 5000
    for prompt in (1, 700, 4096, 4505):
        by_token = sum(work_moe.token_fixed_flops(cfg, p + 1, head=False) for p in range(prompt)) + 2.0 * 3072 * 25024
        assert work_moe.prefill_fixed_flops(cfg, prompt) == pytest.approx(by_token, rel=1e-12)
    assert work_moe.routed_flops(cfg, 80) == 2.0 * 28_311_552 * 80
    peak = work.peaks("TPU v5 lite")
    fixed = work_moe.fixed_weight_bytes_per_step(cfg)
    assert fixed == (176_160_768 + 4 * 92_012_544 + 76_873_728) * 2
    # 128 rows at 950 positions, 29 held experts hit in each of 4 layers: bound by the memory peak
    least, bound = work_moe.decode_least_seconds(cfg, peak, 1, [950] * 128, experts_hit=4 * 29, local_pairs=4 * 64)
    assert bound == "memory"
    kv = 128 * (950 + 1) * 20 * 1024
    assert least == pytest.approx((fixed + 4 * 29 * 28_311_552 * 2 + kv) / 819e9)
    # past the window the sliding layers' reads stop growing
    long_, _ = work_moe.decode_least_seconds(cfg, peak, 1, [5000], 0, 0)
    longer, _ = work_moe.decode_least_seconds(cfg, peak, 1, [5200], 0, 0)
    assert (longer - long_) * 819e9 == pytest.approx(200 * 4096)  # one full layer's 4 KiB a position
