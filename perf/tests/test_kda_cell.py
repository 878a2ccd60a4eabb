"""The hybrid cell's own pieces: the rehearsal walks the flow on the CPU and reports the new counters' metric; the
int8 control, the altered token and the two faults planted in the state's path are caught; a program without the
model fails as the system file is imported; the four new readers on a hand-made record; the reference imports
nothing of the program and computes KDA as the recurrence; the cell is the issue's."""

import importlib.util
import json
import os
import re
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "ling3-flash.think_sat"


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def rehearse(*more):
    args = ["--workload", CELL, "--seconds", "3", "--rehearse", *more]
    done = subprocess.run([sys.executable, "-m", "perf.run", *args], cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    return lines[-2]["detail"], lines[-1]


def _over(line):
    return {n for n, e in line["compared"].items() if e["limit"] is not None and e["value"] > e["limit"]}


def test_traced_rehearsal_walks_the_flow_and_counts_the_state():
    detail, line = rehearse("--seed", "4000000033", "--trace", "1")
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) >= {"token_gap_max", "logprob_mse", "logprob_sq_median"}
    values = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert {"state_bytes_share", "moe_local_pairs_per_step", "rows_per_dispatch", "engine_iteration_ms", "engine_phase_ms.admit"} <= set(values)
    assert 0.0 < values["state_bytes_share"] < 100.0
    counters = detail["counters"]
    assert counters["state_rows_updated"] == 3 * 8 * counters["decoded_rows"]  # three KDA layers x 8 steps a live row
    assert counters["state_positions_run"] >= counters["state_positions_needed"] > 0
    assert counters["moe_decode_routed_pairs"] == 2 * 3 * 8 * counters["decoded_rows"]  # top-2 x 3 expert layers
    assert 0 < counters["moe_local_pairs"] < counters["moe_routed_pairs"]  # one of four groups held: most pairs live elsewhere
    assert detail["decode_attention_path"] == "latent_gather"  # the CPU's read; the chip's is the kernel


@pytest.mark.parametrize("how,over", [
    (("--control", "int8"), {"logprob_sq_median"}),
    (("--fault", "token_altered"), {"token_gap_max", "logprob_mse"}),
    (("--fault", "no_decay"), {"logprob_sq_median"}),
    (("--fault", "state_bf16"), {"logprob_sq_median"}),
], ids=["int8_control", "token_altered", "no_decay", "state_bf16"])
def test_the_control_and_every_planted_fault_read_not_correct(how, over):
    _, line = rehearse("--seed", "7", "--trace", "0", *how)
    assert line["correct"] is False and over <= _over(line), line["compared"]


def test_a_program_without_the_model_fails_as_the_system_file_is_imported():
    """The parent commit on this cell: no ``BailingHybridConfig`` in ``unionml_tpu.models``. The run ends non-zero
    at the import, within seconds, having made no weight."""
    code = (
        "import sys, unionml_tpu.models as m\n"
        "del m.BailingHybridConfig, m.BailingHybridTransformer\n"
        "import perf.reference.bailing_hybrid_decoder as r\n"
        "r.make_weights = lambda *a, **k: sys.exit('weights were made')\n"
        "from perf import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--rehearse', '--seconds', '1']))\n"
    )
    started = time.monotonic()
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert done.returncode != 0 and "ImportError" in done.stderr and "weights were made" not in done.stderr
    assert time.monotonic() - started < 60
    assert not [x for x in done.stdout.splitlines() if x.startswith('{"correct"')]


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name.replace('.', '_')}", os.path.join(ROOT, "perf", "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


NEW_READERS = ("step_mfu.serve_kda", "kda_decode_roofline", "kda_prefill_roofline", "state_bytes_share")


def test_new_readers_on_a_hand_made_record():
    from perf import work, work_kda

    with open(os.path.join(ROOT, "perf", "configs", "ling-3.0-flash-ep8.json")) as f:
        cfg = json.load(f)
    peak = work.peaks("TPU v5 lite")
    # one request of 300 prompt tokens whose first token and 16 more arrived in the window, 8 of them in the slice
    record = types.SimpleNamespace(
        request=types.SimpleNamespace(prompt=[1] * 300, index=0), first=10.5, arrivals=[(10.5, 1), (11.0, 8), (12.0, 8)],
    )

    def counters(dispatches, chunks, needed, pairs, hit):
        return {
            "decode_dispatches": dispatches, "prefill_chunks": chunks, "prefill_chunk_tokens": 150 * chunks,
            "moe_local_pairs": pairs + 40 * chunks, "moe_decode_local_pairs": pairs, "moe_experts_hit": hit + 100 * chunks,
            "moe_decode_experts_hit": hit, "latent_positions_read": 900 * dispatches, "latent_positions_needed": needed,
            "state_rows_updated": 6 * 8 * dispatches, "state_positions_run": 6 * 256 * chunks, "state_positions_needed": 6 * 150 * chunks,
        }

    facts = {
        "kind": "serving", "config": cfg, "records": [record], "open_at": 10.0, "close_at": 20.0, "window_s": 10.0, "chips": 1,
        "decode_chunk": 8, "before": counters(0, 0, 0, 0, 0), "after": counters(2, 4, 900, 64, 60),
        "slice": {"t0": 10.8, "t1": 11.5, "before": counters(0, 1, 150, 0, 0), "after": counters(1, 3, 750, 32, 30)},
    }
    trace = {"programs": {"decode_steps": {"calls": 1.0, "seconds": 0.16}, "prefill_chunk": {"calls": 2.0, "seconds": 0.07}}, "window_s": 1.0, "busy_s": 0.5}

    flops = work_kda.prefill_flops(cfg, 300, work_kda.prompt_causal_pairs(cfg, 300), 1.0)
    flops += sum(work_kda.decode_token_flops(cfg, c) for c in range(300, 317)) + work_kda.routed_flops(cfg, 64 + 160)
    assert _reader("step_mfu.serve_kda")(facts, None, peak) == pytest.approx(100.0 * flops / (10.0 * 197e12))
    least, bound = work_kda.decode_least_seconds(cfg, peak, 8, range(301, 309), 30, 32, 48)
    assert bound == "memory"
    assert _reader("kda_decode_roofline")(facts, trace, peak) == pytest.approx(100.0 * (least / 8) / (0.16 / 8))
    least, bound = work_kda.prefill_least_seconds(cfg, peak, 2, 300, 600, 200, 80, 6 * 300)
    assert _reader("kda_prefill_roofline")(facts, trace, peak) == pytest.approx(100.0 * (least / 2) / (0.07 / 2))
    moved = work_kda.decode_bytes(cfg, 16, list(range(300, 317)), 60, 96)
    assert _reader("state_bytes_share")(facts, None, None) == pytest.approx(100.0 * moved["state"] / sum(moved.values()))
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


def test_the_reference_is_plain_and_computes_kda_as_the_recurrence():
    text = open(os.path.join(ROOT, "perf", "reference", "bailing_hybrid_decoder.py")).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert not re.search(r"unionml_tpu|flax|optax|pallas|ragged_dot|delta_rule|solve_triangular|cumsum", code)
    assert 'default_matmul_precision("highest")' in code
    assert "jax.lax.scan(token," in code  # one position at a time, the state carried by the scan alone
    from perf.systems import hybrid_state_serving, mla_moe_serving

    source = open(os.path.join(ROOT, "perf", "systems", "hybrid_state_serving.py")).read()
    assert "base.run(" in source and "time.sleep" not in source and hybrid_state_serving.base is mla_moe_serving  # no copy of the window


def test_the_cell_is_the_one_the_issue_named():
    from perf.run import load_cell

    loaded = load_cell(CELL, rehearse=False)
    engine = {**loaded.config["engine"], **loaded.cell["engine"]}
    assert (loaded.mix["clients"], engine["slots"], engine["prefill_budget"], engine["pool_blocks"]) == (288, 192, 2048, 192 * 56)
    assert (engine["decode_chunk"], engine["block_size"], engine["admit_chunk"], engine["max_prompt_tokens"], engine["max_new_tokens"]) == (8, 64, 256, 2048, 1536)
    assert engine["prefix_cache"] is False and loaded.mix["stream_threads"] == 512 and loaded.mix["pool_per_s"] == 24
    assert loaded.mix["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32, "max": 2048}
    assert loaded.mix["output_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.6, "min": 64, "max": 1536}
    from perf.traffic import generate

    lengths = lambda seed: [(len(r.prompt), r.max_tokens) for r in generate.requests(loaded.mix, seed, 19648, 75.0)]  # noqa: E731
    assert lengths(1) == lengths(4000000033) and sorted(n for n, _ in lengths(1)[:16])[::15] == [40, 1649]
    assert all(1 <= t < 19648 for r in generate.requests(loaded.mix, 3, 19648, 5.0)[:8] for t in r.prompt)
    assert loaded.cell["check"] == {"requests": 6, "pad_to": 1792} and loaded.cell["trace_seconds"] == 4.0
    assert loaded.cell["serve"]["default_deadline_ms"] == 1e3 * loaded.mix["request_timeout_s"] == 120000
    cfg = loaded.config
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["num_experts"], cfg["router_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"]) == (7, 1, 64, 512, 19648, 0)
    assert cfg["published"] == {"num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512, "vocab_size": 157184,
                                "num_nextn_predict_layers": 1}
    assert cfg["layer_types"] == ["kda"] * 4 + ["mla"] + ["kda"] * 2 and len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    # published layers 1-7 of the pattern "layer i is MLA iff (i + 1) % 6 == 0"
    assert cfg["layer_types"] == ["mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda" for i in range(1, 8)]
    # every width as published
    widths = dict(hidden_size=2560, num_attention_heads=32, head_dim=128, short_conv_kernel_size=4, kv_lora_rank=512,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128, intermediate_size=6144, moe_intermediate_size=768,
                  moe_shared_expert_intermediate_size=768, num_experts_per_tok=8, n_group=8, topk_group=4, routed_scaling_factor=2.5,
                  rope_theta=6000000, rms_norm_eps=1e-06, kda_lower_bound=-5, q_lora_rank=None)
    assert {k: cfg[k] for k in widths} == widths and cfg["num_dense_layers"] == cfg["first_k_dense_replace"]
    assert {"use_qk_norm", "kda_rotary", "num_kv_heads_for_linear_attn", "max_window_layers"} <= set(cfg["assumed"])
    # every number of the catalog's config is here under its key, the five reduced ones apart
    catalog = os.path.join("/opt/skills/guides/model-configs", "architectures.jsonl")
    if os.path.exists(catalog):
        row = next(json.loads(x) for x in open(catalog) if json.loads(x)["name"] == "Ling-3.0-flash")
        assert cfg["source"] == row["source_url"]
        assert {k for k, v in row["config"].items() if cfg.get(k, "absent") != v} == set(cfg["reduced"])
