"""The window-and-global routed cell's own pieces: the rehearsal walks the flow on the CPU; the int8 control, the
altered token and the three faults planted in how the program is built are caught; a program without the model
fails as the system file is imported; the reference imports nothing of the program and routes from the block's
input; the cell holds the sizes it was specified with."""

import json
import os
import re
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "smallthinker.mixed_long_sat"


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


def test_traced_rehearsal_walks_the_flow_and_counts_the_routing():
    detail, line = rehearse("--seed", "4000000037", "--trace", "1")
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) >= {"token_gap_max", "logprob_mse", "logprob_sq_median"}
    assert {"moe_local_pairs_per_step", "rows_per_dispatch", "engine_iteration_ms", "engine_phase_ms.admit"} <= set(line["metrics"])
    counters = detail["counters"]
    assert counters["moe_local_pairs"] == counters["moe_routed_pairs"] > 0  # every expert held
    assert counters["moe_decode_routed_pairs"] == 3 * 4 * 8 * counters["decoded_rows"]  # top-3 x 4 layers x 8 steps a row
    assert counters["kv_positions_needed"] > 0
    # the CPU's read gathers: the windowed kernel's counters stay 0 and window_read_share reads nothing
    assert detail["decode_attention_path"] == "gather" and counters["window_positions_live"] == 0
    assert "window_read_share" not in line["metrics"]


@pytest.mark.parametrize("how,over", [
    (("--control", "int8"), {"logprob_sq_median"}),
    (("--fault", "token_altered"), {"token_gap_max", "logprob_mse"}),
    (("--fault", "router_after_attention"), {"logprob_sq_median"}),
    (("--fault", "silu_experts"), {"logprob_sq_median"}),
    (("--fault", "no_window"), {"logprob_sq_median"}),
], ids=["int8_control", "token_altered", "router_after_attention", "silu_experts", "no_window"])
def test_the_control_and_every_planted_fault_read_not_correct(how, over):
    _, line = rehearse("--seed", "7", "--trace", "0", *how)
    assert line["correct"] is False and over <= _over(line), line["compared"]


def test_a_program_without_the_model_fails_as_the_system_file_is_imported():
    """The parent commit on this cell: no ``SmallThinkerConfig`` in ``unionml_tpu.models``. The run ends non-zero
    at the import, within seconds, having made no weight."""
    code = (
        "import sys, unionml_tpu.models as m\n"
        "del m.SmallThinkerConfig, m.SmallThinkerTransformer\n"
        "import perf.reference.smallthinker_decoder as r\n"
        "r.make_weights = lambda *a, **k: sys.exit('weights were made')\n"
        "from perf import run\n"
        f"sys.exit(run.main(['--workload', '{CELL}', '--rehearse', '--seconds', '1']))\n"
    )
    started = time.monotonic()
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert done.returncode != 0 and "ImportError" in done.stderr and "weights were made" not in done.stderr
    assert time.monotonic() - started < 60
    assert not [x for x in done.stdout.splitlines() if x.startswith('{"correct"')]


def test_the_reference_is_plain_and_routes_from_the_blocks_input():
    text = open(os.path.join(ROOT, "perf", "reference", "smallthinker_decoder.py")).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert not re.search(r"unionml_tpu|flax|optax|pallas|ragged_dot|argsort", code)
    assert 'default_matmul_precision("highest")' in code and "jax.nn.relu(" in code and "jax.nn.silu" not in code
    assert "h_in = x" in code and "expert_layer(x, h_in, w, cfg" in code  # the router reads the block's input
    from perf.systems import mla_moe_serving, swa_moe_serving

    source = open(os.path.join(ROOT, "perf", "systems", "swa_moe_serving.py")).read()
    assert "base.run(" in source and "time.sleep" not in source and swa_moe_serving.base is mla_moe_serving  # no copy of the window


def test_the_cell_holds_its_specified_sizes():
    from perf.run import load_cell
    from perf.traffic import generate

    loaded = load_cell(CELL, rehearse=False)
    engine = {**loaded.config["engine"], **loaded.cell["engine"]}
    assert (loaded.mix["clients"], engine["slots"], engine["prefill_budget"], engine["pool_blocks"]) == (144, 96, 4096, 4608)
    assert (engine["block_size"], engine["max_prompt_tokens"], engine["max_new_tokens"]) == (64, 15360, 1024)
    assert loaded.mix["prompt_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 1.5, "min": 32, "max": 15360}
    assert loaded.mix["output_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32, "max": 1024}
    assert (loaded.mix["ramp_s"], loaded.mix["request_timeout_s"], loaded.mix["stream_threads"]) == (25.0, 120.0, 256)
    assert "schedule_seed" in loaded.mix and loaded.cell["serve"]["default_deadline_ms"] == 120000
    vocab = loaded.config["vocab_size"]
    lengths = lambda seed: [(len(r.prompt), r.max_tokens) for r in generate.requests(loaded.mix, seed, vocab, 75.0)]  # noqa: E731
    assert lengths(1) == lengths(4000000037)
    assert sorted(n for n, _ in lengths(1)[:16]) == [63, 142, 225, 320, 430, 560, 717, 910, 1152, 1462, 1872, 2441, 3282, 4659, 7394, 15360]
    assert all(1 <= t < vocab for r in generate.requests(loaded.mix, 3, vocab, 5.0)[:8] for t in r.prompt)
    assert loaded.cell["check"]["requests"] == 6
    cfg = loaded.config
    widths = dict(hidden_size=2560, num_attention_heads=28, num_key_value_heads=4, head_dim=128, moe_ffn_hidden_size=768,
                  moe_num_primary_experts=64, moe_num_active_primary_experts=6, sliding_window_size=4096, vocab_size=151936,
                  rope_theta=1500000, rms_norm_eps=1e-06, max_position_embeddings=16384)
    assert {k: cfg[k] for k in widths} == widths
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["router_experts"], cfg["experts_first"]) == (8, 64, 64, 0)
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [0, 1, 1, 1] * 2 == cfg["published"]["rope_layout"][:8]
    assert cfg["published"]["num_hidden_layers"] == 52 and set(cfg["reduced"]) == {"num_hidden_layers", "sliding_window_layout", "rope_layout"}
    assert {"router_input", "expert_activation"} <= set(cfg["assumed"])
