"""``perf/work_swa.py`` reproduces the share's arithmetic (PERF.md section 4) from the configuration file's keys alone, and the cell's
four readers read it from a hand-made record (and nothing from a program without the counters)."""

import importlib.util
import json
import os
import types

import pytest

from perf import work, work_moe, work_swa

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(ROOT, "perf", "configs", "smallthinker-21b-a3b-l8.json")) as f:
        return json.load(f)


def test_the_share_by_hand(cfg):
    million = lambda n: round(n / 1e6, 2)  # noqa: E731
    assert work_swa.kinds(cfg) == (6, 2) and work_moe.layer_counts(cfg) == (0, 8)
    assert million(work_swa.attention_params(cfg)) == 20.97  # q 2560 x 3584, k and v 2560 x 512, o 3584 x 2560
    assert cfg["hidden_size"] * cfg["router_experts"] == 163840 and round(work_swa.expert_params(cfg) / 1e6, 3) == 5.898
    assert million(64 * work_swa.expert_params(cfg)) == 377.49 and million(work_swa.layer_params(cfg)) == 398.62
    # 8 x 398.62 + 2 x 151,936 x 2,560 (embedding and untied head, 777.9 M): 3,966.9 M = 7.93 GB, the experts 76 %
    assert round(work_swa.share_params(cfg) / 1e6, 1) == 3966.9 and round(2 * work_swa.share_params(cfg) / 1e9, 2) == 7.93
    assert 0.76 < 8 * 64 * work_swa.expert_params(cfg) / work_swa.share_params(cfg) < 0.77
    # 4 KV heads x 128 x 2 planes x 2 B a position a layer; 16 KiB over the 8 layers; the pool 4.83 GB, 12.77 with the weights
    assert work_swa.kv_bytes_per_position_layer(cfg) == 2048 and work_swa.kv_bytes_per_position(cfg) == 16384
    pool = 4608 * 64 * work_swa.kv_bytes_per_position(cfg)
    assert round(pool / 1e9, 2) == 4.83 and round((pool + 2 * work_swa.share_params(cfg)) / 1e9, 2) == 12.77
    # a decode step's fixed bytes: attention 0.34 GB, the routers, the head 0.78 GB
    assert round(work_swa.fixed_weight_bytes(cfg) / 1e9, 2) == 1.12


def test_the_decode_floor_by_hand(cfg):
    """A step at 80 live rows hits all 64 experts of every layer: 6.04 GB of experts beside 1.12 GB of fixed weights
    and head = 7.16 GB, 8.7 ms at 819 GB/s; then 2,048 B a position a layer, the two global layers at the row's
    length and the six windowed ones at what their reads touched."""
    peak = work.peaks("TPU v5 lite")
    contexts, read = [6000] * 80, 80 * 6 * 4160
    moved = work_swa.decode_bytes(cfg, 1, contexts, 64 * 8, read)
    assert round(moved["experts"] / 1e9, 2) == 6.04 and round((moved["experts"] + moved["weights"]) / 1e9, 2) == 7.16
    assert moved["kv"] == 2048 * (2 * 6000 * 80 + read + 8 * 80)
    least, bound = work_swa.decode_least_seconds(cfg, peak, 1, contexts, 64 * 8, 80 * 6 * 8, read)
    assert bound == "memory" and least == pytest.approx(sum(moved.values()) / peak["hbm_bytes_per_s"])
    assert round((moved["experts"] + moved["weights"]) / peak["hbm_bytes_per_s"] * 1e3, 1) == 8.7
    # a 256-token chunk whose 1,536 pairs hit every expert streams them all too
    least, bound = work_swa.prefill_least_seconds(cfg, peak, 1, 256, 8 * 4096, 64 * 8, 1536 * 8)
    assert bound == "memory" and 0.0075 < least < 0.0090
    # a prompt's pairs: the windowed layers see at most 4,096 keys
    assert work_swa.prompt_pairs(cfg, 100) == 8 * 100 * 101 / 2
    assert work_swa.prompt_pairs(cfg, 5000) == 2 * 5000 * 5001 / 2 + 6 * (4096 * 4097 / 2 + 904 * 4096)


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name.replace('.', '_')}", os.path.join(ROOT, "perf", "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


NEW_READERS = ("step_mfu.serve_swa", "swa_decode_roofline", "swa_prefill_roofline", "window_read_share")


def test_new_readers_on_a_hand_made_record(cfg):
    peak = work.peaks("TPU v5 lite")
    # one request of 5,000 prompt tokens whose first token and 16 more arrived in the window, 8 of them in the slice
    record = types.SimpleNamespace(
        request=types.SimpleNamespace(prompt=[1] * 5000, index=0), first=10.5, arrivals=[(10.5, 1), (11.0, 8), (12.0, 8)],
    )

    def counters(dispatches, chunks, needed, pairs, hit):
        return {
            "decode_dispatches": dispatches, "prefill_chunks": chunks, "prefill_chunk_tokens": 250 * chunks,
            "moe_local_pairs": pairs + 1500 * chunks, "moe_decode_local_pairs": pairs, "moe_experts_hit": hit + 400 * chunks,
            "moe_decode_experts_hit": hit, "window_positions_live": 6 * 8 * 5005 * dispatches,
            "window_positions_read": 6 * 8 * 4160 * dispatches, "kv_positions_needed": needed, "kv_positions_attended": 2 * needed,
        }

    facts = {
        "kind": "serving", "config": cfg, "records": [record], "open_at": 10.0, "close_at": 20.0, "window_s": 10.0, "chips": 1,
        "decode_chunk": 8, "before": counters(0, 0, 0, 0, 0), "after": counters(2, 20, 90000, 96, 90),
        "slice": {"t0": 10.8, "t1": 11.5, "before": counters(0, 1, 4000, 0, 0), "after": counters(1, 3, 40000, 48, 45)},
    }
    trace = {"programs": {"decode_steps": {"calls": 1.0, "seconds": 0.2}, "prefill_chunk": {"calls": 2.0, "seconds": 0.03}}, "window_s": 1.0, "busy_s": 0.5}

    contexts = list(range(5000, 5017))
    flops = work_swa.decode_flops(cfg, contexts, 96 + 30000, 6 * 8 * 4160 * 2) + work_swa.prefill_flops(cfg, 5000)
    assert _reader("step_mfu.serve_swa")(facts, None, peak) == pytest.approx(100.0 * flops / (10.0 * 197e12))
    least, bound = work_swa.decode_least_seconds(cfg, peak, 8, range(5001, 5009), 45, 48, 6 * 8 * 4160)
    assert bound == "memory"
    assert _reader("swa_decode_roofline")(facts, trace, peak) == pytest.approx(100.0 * (least / 8) / (0.2 / 8))
    least, bound = work_swa.prefill_least_seconds(cfg, peak, 2, 500, 36000, 800, 3000)
    assert _reader("swa_prefill_roofline")(facts, trace, peak) == pytest.approx(100.0 * (least / 2) / (0.03 / 2))
    assert _reader("window_read_share")(facts, None, None) == pytest.approx(100.0 * 4160 / 5005)
    for name in NEW_READERS:
        assert 0.0 < _reader(name)(facts, trace, peak) < 100.0
    # no chip, a program without the counters (the parent commit), another kind of cell: nothing, and no exception
    for name in NEW_READERS[:3]:
        assert _reader(name)(facts, trace, None) is None
    plain = {"decode_dispatches": 0, "prefill_chunks": 0, "prefill_chunk_tokens": 0}
    bare = dict(facts, before=plain, after=dict(plain, decode_dispatches=2, prefill_chunks=4, prefill_chunk_tokens=1000))
    bare["slice"] = dict(facts["slice"], before=plain, after=dict(plain, decode_dispatches=1, prefill_chunks=2, prefill_chunk_tokens=500))
    for name in NEW_READERS:
        assert _reader(name)(bare, trace, peak) is None
        assert _reader(name)({"kind": "training"}, trace, peak) is None
    # a CPU run, whose decode read gathers: the window's counters stay 0 and the share reads nothing
    idle = dict(facts, after=dict(facts["after"], window_positions_live=0, window_positions_read=0))
    assert _reader("window_read_share")(idle, None, None) is None
