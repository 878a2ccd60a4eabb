"""The latent-attention cell's own pieces: the rehearsal walks the flow on the CPU and reports the new counters'
metric; the int8 control and a planted fault are caught; a program without the model fails as the system file is
imported; the four new readers on a hand-made record; the reference imports nothing of the program and never
absorbs; ``run`` takes its four parameters; the cell is the issue's."""

import importlib.util
import inspect
import json
import os
import re
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "glm47-flash.long_sat"


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


def test_traced_rehearsal_walks_the_flow_and_counts_the_latent_reads():
    detail, line = rehearse("--seed", "4000000021", "--trace", "1")
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) >= {"token_gap_max", "logprob_mse", "logprob_sq_median"}
    values = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert {"latent_attended_share", "moe_local_pairs_per_step", "rows_per_dispatch", "engine_iteration_ms", "engine_phase_ms.admit"} <= set(values)
    assert 0.0 < values["latent_attended_share"] < 100.0
    counters = detail["counters"]
    assert counters["latent_positions_attended"] > counters["latent_positions_needed"] > 0 and counters["latent_positions_read"] > 0
    assert counters["moe_decode_routed_pairs"] == 2 * 4 * 8 * counters["decoded_rows"]  # top-2 x 4 expert layers x 8 steps a live row
    assert detail["decode_attention_path"] == "latent_gather"  # the CPU's read; the chip's is the kernel


def test_the_int8_control_reads_not_correct():
    _, line = rehearse("--seed", "7", "--trace", "0", "--control", "int8")
    assert line["correct"] is False and "logprob_sq_median" in _over(line), line["compared"]


def test_an_altered_token_reads_not_correct():
    _, line = rehearse("--seed", "12", "--trace", "0", "--fault", "token_altered")
    assert line["correct"] is False and {"token_gap_max", "logprob_mse"} <= _over(line), line["compared"]


def test_a_program_without_the_model_fails_as_the_system_file_is_imported():
    """The parent commit on this cell: no ``Glm4MoeLiteConfig`` in ``unionml_tpu.models``. The run ends non-zero
    at the import, within seconds, having made no weight."""
    code = (
        "import sys, unionml_tpu.models as m\n"
        "del m.Glm4MoeLiteConfig, m.Glm4MoeLiteTransformer\n"
        "import perf.reference.glm4_moe_lite_decoder as r\n"
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


def test_new_readers_on_a_hand_made_record():
    from perf import work, work_mla

    with open(os.path.join(ROOT, "perf", "configs", "glm-4.7-flash-ep8.json")) as f:
        cfg = json.load(f)
    peak = work.peaks("TPU v5 lite")
    # one request of 1,000 prompt tokens whose first token and 16 more arrived in the window, 8 of them in the slice
    record = types.SimpleNamespace(
        request=types.SimpleNamespace(prompt=[1] * 1000, index=0), first=10.5, arrivals=[(10.5, 1), (11.0, 8), (12.0, 8)],
    )

    def counters(dispatches, chunks, needed, pairs, hit):
        return {
            "decode_dispatches": dispatches, "prefill_chunks": chunks, "prefill_chunk_tokens": 250 * chunks,
            "moe_local_pairs": pairs + 40 * chunks, "moe_decode_local_pairs": pairs, "moe_experts_hit": hit + 100 * chunks,
            "moe_decode_experts_hit": hit, "latent_positions_read": 9000 * dispatches,
            "latent_positions_attended": 24 * 8960 * chunks, "latent_positions_needed": needed,
        }

    facts = {
        "kind": "serving", "config": cfg, "records": [record], "open_at": 10.0, "close_at": 20.0, "window_s": 10.0, "chips": 1,
        "decode_chunk": 8, "before": counters(0, 0, 0, 0, 0), "after": counters(2, 4, 24 * 2500, 64, 60),
        "slice": {"t0": 10.8, "t1": 11.5, "before": counters(0, 1, 24 * 250, 0, 0), "after": counters(1, 3, 24 * 1500, 32, 30)},
    }
    trace = {"programs": {"decode_steps": {"calls": 1.0, "seconds": 0.16}, "prefill_chunk": {"calls": 2.0, "seconds": 0.07}}, "window_s": 1.0, "busy_s": 0.5}

    assert _reader("latent_attended_share")(facts, None, None) == pytest.approx(100.0 * 2500 / (4 * 8960))
    flops = work_mla.prefill_flops(cfg, 1000, work_mla.prompt_causal_pairs(cfg, 1000), 1.0)
    flops += sum(work_mla.decode_token_flops(cfg, c) for c in range(1000, 1017)) + work_mla.routed_flops(cfg, 64 + 160)
    assert _reader("step_mfu.serve_mla")(facts, None, peak) == pytest.approx(100.0 * flops / (10.0 * 197e12))
    least, bound = work_mla.decode_least_seconds(cfg, peak, 8, range(1001, 1009), 30, 32)
    assert bound == "memory"
    assert _reader("mla_decode_roofline")(facts, trace, peak) == pytest.approx(100.0 * (least / 8) / (0.16 / 8))
    least, bound = work_mla.prefill_least_seconds(cfg, peak, 2, 500, 24 * 1250, 200, 80)
    assert _reader("mla_prefill_roofline")(facts, trace, peak) == pytest.approx(100.0 * (least / 2) / (0.07 / 2))
    for name in ("mla_decode_roofline", "mla_prefill_roofline"):
        assert 0.0 < _reader(name)(facts, trace, peak) < 100.0
    # no chip, a program without the counters (the parent commit), another kind of cell: nothing, and no exception
    for name in ("step_mfu.serve_mla", "mla_decode_roofline", "mla_prefill_roofline"):
        assert _reader(name)(facts, trace, None) is None
    plain = {"decode_dispatches": 0, "prefill_chunks": 0, "prefill_chunk_tokens": 0}
    bare = dict(facts, before=plain, after=dict(plain, decode_dispatches=2, prefill_chunks=4, prefill_chunk_tokens=1000))
    bare["slice"] = dict(facts["slice"], before=plain, after=dict(plain, decode_dispatches=1, prefill_chunks=2, prefill_chunk_tokens=500))
    for name in ("step_mfu.serve_mla", "mla_decode_roofline", "mla_prefill_roofline", "latent_attended_share"):
        assert _reader(name)(bare, trace, peak) is None
        assert _reader(name)({"kind": "training"}, trace, peak) is None


def test_the_reference_is_plain_and_never_absorbs_and_run_takes_its_four_parameters():
    text = open(os.path.join(ROOT, "perf", "reference", "glm4_moe_lite_decoder.py")).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert not re.search(r"unionml_tpu|flax|optax|pallas|ragged_dot", code)
    assert 'default_matmul_precision("highest")' in code
    # expanded only: the up-projection is applied to the latent of every position, never to a query or an output
    assert code.count('attn["kv_up"]["kernel"]') == 1 and "c_kv[s : s + block] @ up" in code
    from perf.systems import decoder_serving, mla_moe_serving

    assert mla_moe_serving.plant_fault is decoder_serving.plant_fault and mla_moe_serving.Server is decoder_serving.Server
    assert mla_moe_serving.build_app is decoder_serving.build_app
    assert list(inspect.signature(mla_moe_serving.run).parameters) == ["ctx", "engine", "counters", "references", "extra_numbers"]
    assert 'cell.get("serve", {})' in inspect.getsource(mla_moe_serving.run)


def test_the_cell_is_the_one_the_issue_named():
    from perf.run import load_cell

    loaded = load_cell(CELL, rehearse=False)
    engine = {**loaded.config["engine"], **loaded.cell["engine"]}
    assert (loaded.mix["clients"], engine["slots"], engine["prefill_budget"], engine["pool_blocks"]) == (72, 48, 2048, 3584)
    assert (engine["decode_chunk"], engine["block_size"], engine["admit_chunk"], engine["max_prompt_tokens"], engine["max_new_tokens"]) == (8, 64, 256, 8192, 768)
    assert loaded.mix["ramp_s"] == 20.0 and loaded.mix["stream_threads"] == 256 and loaded.mix["pool_per_s"] == 8
    # the lengths' order is the file's, not the seed's (PERF.md, departure (o)): every seed offers the same load
    from perf.traffic import generate

    lengths = lambda seed: [(len(r.prompt), r.max_tokens) for r in generate.requests(loaded.mix, seed, 19360, 70.0)]  # noqa: E731
    assert lengths(1) == lengths(4000000021) and sorted(n for n, _ in lengths(1)[:16])[::15] == [834, 8192]
    assert loaded.cell["check"] == {"requests": 6, "pad_to": 2048} and loaded.cell["trace_seconds"] == 4.0
    assert loaded.cell["serve"]["default_deadline_ms"] == 1e3 * loaded.mix["request_timeout_s"]
    cfg = loaded.config
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["router_experts"], cfg["vocab_size"], cfg["num_nextn_predict_layers"]) == (24, 8, 64, 19360, 0)
    assert cfg["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64, "vocab_size": 154880, "num_nextn_predict_layers": 1}
    # every width as published
    widths = dict(hidden_size=2048, num_attention_heads=20, q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
                  v_head_dim=256, intermediate_size=10240, moe_intermediate_size=1536, num_experts_per_tok=4, n_shared_experts=1)
    assert {k: cfg[k] for k in widths} == widths and cfg["num_dense_layers"] == cfg["first_k_dense_replace"] == 1
