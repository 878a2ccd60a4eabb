"""``perf/work_mla.py`` reproduces ISSUE 30's own arithmetic from the configuration file's keys alone."""

import json
import os

import pytest

from perf import work, work_mla, work_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perf", "configs", "glm-4.7-flash-ep8.json")) as f:
        return json.load(f)


def test_the_share_is_the_issues(cfg):
    million = lambda n: round(n / 1e6, 2)  # noqa: E731
    assert million(work_mla.attention_params(cfg)) == 21.76  # W_dq 1.573 + W_uq 3.932 + W_dkv 1.180 + W_ukv 4.588 + W_o 10.486
    assert million(work_mla.expert_params(cfg)) == 9.44
    assert million(work_mla.expert_layer_fixed_params(cfg)) == 31.33  # attention, the shared expert, the router at 64
    assert million(work_mla.expert_layer_params(cfg)) == 106.82  # with the 8 held experts
    assert million(work_mla.dense_layer_params(cfg)) == 84.67
    assert work_mla.layer_counts(cfg) == (1, 23) == work_moe.layer_counts(cfg)  # the accepted reader's name for it
    assert round(work_mla.share_params(cfg) / 1e6) == 2621  # 84.67 + 23 x 106.82 + 79.3: 5.24 GB in bfloat16
    assert work_mla.latent_bytes_per_token_layer(cfg) == 1152  # 512 + 64 values; expanded keys and values: 20,480
    assert 2 * cfg["num_attention_heads"] * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]) * 2 // 2 == 20480
    assert work_mla.absorbed_pair_flops(cfg) == 43520.0 and work_mla.expanded_pair_flops(cfg) == 20480.0
    # the pool as stored (whole lanes): 3,584 blocks of 64 at 640 values over 24 layers
    assert round(3584 * 64 * cfg["latent_cache_width"] * 2 * cfg["num_hidden_layers"] / 1e9, 2) == 7.05
    assert round(3584 * 64 * work_mla.latent_bytes_per_token_layer(cfg) * cfg["num_hidden_layers"] / 1e9, 2) == 6.34


def test_least_times_follow_the_issues_reckoning(cfg):
    peak = work.peaks("TPU v5 lite")
    # a decode step with 24 rows live at 3,920 positions, 78 % of the held experts hit: weights + latent, memory-bound
    contexts = [3920] * 24
    hit = 0.78 * 8 * 23
    least, bound = work_mla.decode_least_seconds(cfg, peak, 1, contexts, hit, 24 * 4 * 8 / 64 * 23)
    assert bound == "memory"
    moved = work_mla.fixed_weight_bytes(cfg) + hit * work_mla.expert_params(cfg) * 2 + 1152 * 24 * (sum(contexts) + 24)
    assert least == pytest.approx(moved / peak["hbm_bytes_per_s"]) and 0.006 < least < 0.0096  # under the issue's all-weights 9.6 ms
    # a whole 256-token chunk ending at position 2,048: its causal pairs from the program's count of positions needed
    pairs = work_mla.chunks_causal_pairs(256, 1, 24 * 2048, 24)
    assert pairs == 24 * (256 * 2048 - 256 * 255 / 2)
    assert work_mla.prompt_causal_pairs(cfg, 2048) == 24 * 2048 * 2049 / 2
    least, bound = work_mla.prefill_least_seconds(cfg, peak, 1, 256, 24 * 2048, 8 * 23, 256 * 4 * 8 / 64 * 23)
    assert bound == "memory" and 0.0045 < least < 0.0075  # a batch-1 chunk's floor is its weights, ~5 GB
    flops = work_mla.prefill_flops(cfg, 256, pairs, 0.0)
    assert flops == pytest.approx(2.0 * work_mla.fixed_params(cfg) * 256 + 20480.0 * pairs) and 0.4e12 < 2.0 * work_mla.fixed_params(cfg) * 256 < 0.45e12
