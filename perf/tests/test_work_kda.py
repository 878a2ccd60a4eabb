"""``perf/work_kda.py`` reproduces ISSUE 33's own arithmetic from the configuration file's keys alone."""

import json
import os

import pytest

from perf import work, work_kda, work_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perf", "configs", "ling-3.0-flash-ep8.json")) as f:
        return json.load(f)


def test_the_share_is_the_issues(cfg):
    million = lambda n: round(n / 1e6, 1)  # noqa: E731
    assert work_kda.kinds(cfg) == (6, 1) and work_kda.expert_layers(cfg) == 6 == work_moe.layer_counts(cfg)[1]
    # W_q, W_k, W_v 31.46 + W_f 10.49 + W_g 10.49 + W_o 10.49 + W_b, taps, A_log, dt_bias, the head norm 0.14
    assert million(work_kda.kda_attention_params(cfg)) == 63.0 and work_kda.kda_attention_params(cfg) == 6 * 2560 * 4096 + 81920 + 49152 + 32 + 4096 + 128
    assert million(work_kda.mla_attention_params(cfg)) == 32.0  # W_q 15.73 + W_dkv 1.47 + W_ukv 4.19 + W_o 10.49 + gate 0.08
    assert round(work_kda.expert_params(cfg) / 1e6, 3) == 5.898
    assert million(work_kda.layer_fixed_params(cfg, 0)) == 110.2  # the dense KDA layer: 63.05 + 3 x 2560 x 6144
    assert million(work_kda.layer_fixed_params(cfg, 1)) == 70.3 and million(work_kda.layer_fixed_params(cfg, 4)) == 39.2
    # one slot: six KDA layers of S (32 x 128 x 128 float32 = 2.10 MB) and 3 rows of three convolutions (74 KB)
    assert work_kda.state_bytes_per_layer(cfg) == 2097152 + 73728 and work_kda.slot_state_bytes(cfg) == 13025280
    # 110.2 + 5 x 447.7 + 416.6 + 100.6 (the issue rounds its parts up to 2,867)
    assert abs(work_kda.share_params(cfg) - 2867e6) < 1.5e6
    assert round(work_kda.fixed_weight_bytes(cfg) / 1e9, 2) == 1.10  # a decode step's fixed weights, the head slice among them
    assert work_kda.step_flops(cfg) == 7 * 32 * 128 * 128 and round(work_kda.chunk_form_flops(cfg) / 1e6, 2) == 5.24
    # the state and the pool at the cell's sizes (192 slots, 10,752 blocks of 64 at 640 values in one layer)
    assert round(192 * work_kda.slot_state_bytes(cfg) / 1e9, 2) == 2.50 and round(10752 * 64 * 640 * 2 / 1e9, 2) == 0.88


def test_least_times_follow_the_issues_reckoning(cfg):
    peak = work.peaks("TPU v5 lite")
    # a decode step with 192 rows live at 1,000 positions, 95 % of the 64 x 6 held experts hit
    contexts, hit, rows = [1000] * 192, 0.95 * 64 * 6, 192 * 6
    moved = work_kda.decode_bytes(cfg, 1, contexts, hit, rows)
    assert round(moved["state"] / 1e9, 2) == 5.00 and round(moved["experts"] / 1e9, 1) == 4.3 and round(moved["latent"] / 1e9, 2) == 0.22
    assert 0.43 < moved["state"] / sum(moved.values()) < 0.49  # the issue's 47 %
    least, bound = work_kda.decode_least_seconds(cfg, peak, 1, contexts, hit, 192.0 * 6, rows)
    assert bound == "memory" and least == pytest.approx(sum(moved.values()) / peak["hbm_bytes_per_s"]) and 0.0125 < least < 0.0135
    # a whole 256-token chunk ending at position 1,024, every held expert hit: its floor is its weights and experts
    least, bound = work_kda.prefill_least_seconds(cfg, peak, 1, 256, 1024, 64 * 6, 256 * 6, 256 * 6)
    assert bound == "memory" and 0.0065 < least < 0.0085
    flops = work_kda.prefill_flops(cfg, 256, 0.0, 0.0)
    assert flops == pytest.approx(256 * (2.0 * work_kda.fixed_params(cfg) + 6 * work_kda.step_flops(cfg)))
    assert work_kda.prompt_causal_pairs(cfg, 2048) == 1 * 2048 * 2049 / 2  # one MLA layer
