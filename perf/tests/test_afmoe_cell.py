"""The afmoe cell's own pieces: a wrong token underneath reads not correct; a traced rehearsal reports the routing
counter's metric beside the engine's; the three new readers on a hand-made record; the reference imports nothing
of the program; the system file's window is ``decoder_serving``'s, restated with its differences marked; the cell is the issue's."""

import difflib
import importlib.util
import json
import os
import re
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "trinity-large.chat_wide_sat"


def rehearse(*more):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    args = ["--workload", CELL, "--seconds", "3", "--rehearse", *more]
    done = subprocess.run([sys.executable, "-m", "perf.run", *args], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    return lines[-2]["detail"], lines[-1]


def test_an_altered_token_reads_not_correct():
    _, line = rehearse("--seed", "12", "--trace", "0", "--fault", "token_altered")
    assert line["correct"] is False
    over = {n for n, e in line["compared"].items() if e["limit"] is not None and e["value"] > e["limit"]}
    assert {"token_gap_max", "logprob_mse"} <= over, line["compared"]


def test_traced_rehearsal_reports_the_routing_metric_and_counts_every_pair():
    detail, line = rehearse("--seed", "4000000021", "--trace", "1")
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) >= {"token_gap_max", "logprob_mse", "logprob_sq_median"}
    values = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert {"moe_local_pairs_per_step", "rows_per_dispatch", "engine_iteration_ms", "engine_phase_ms.fetch"} <= set(values)
    # the rehearsal's share: 4 of 8 experts held, top-2: a live row puts one pair on a held expert on average
    assert 0.0 < values["moe_local_pairs_per_step"] <= 2 * values["rows_per_dispatch"]
    counters = detail["counters"]
    assert counters["moe_routed_pairs"] > counters["moe_local_pairs"] > 0
    assert counters["moe_decode_routed_pairs"] == 2 * 4 * 8 * counters["decoded_rows"]  # top-2 x 4 layers x 8 steps a live row
    assert counters["decode_window_pages_skipped"] == 0  # the CPU's gather read masks the window


def _reader(name):
    spec = importlib.util.spec_from_file_location(f"reader_{name.replace('.', '_')}", os.path.join(ROOT, "perf", "layer_metrics", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_new_readers_on_a_hand_made_record():
    from perf import work, work_moe

    with open(os.path.join(ROOT, "perf", "configs", "trinity-large-preview-ep8.json")) as f:
        cfg = json.load(f)
    peak = work.peaks("TPU v5 lite")
    # one request of 100 prompt tokens whose first token and 16 more arrived in the window, 8 of them in the slice
    record = types.SimpleNamespace(
        request=types.SimpleNamespace(prompt=[1] * 100, index=0), first=10.5,
        arrivals=[(10.5, 1), (11.0, 8), (12.0, 8)],
    )
    counters = lambda d, pairs, hit: {"decode_dispatches": d, "moe_local_pairs": pairs + 50, "moe_decode_local_pairs": pairs, "moe_decode_experts_hit": hit}  # noqa: E731
    facts = {
        "kind": "serving", "config": cfg, "records": [record], "open_at": 10.0, "close_at": 20.0, "window_s": 10.0, "chips": 1,
        "decode_chunk": 8, "before": counters(0, 0, 0), "after": counters(2, 64, 60),
        "slice": {"t0": 10.8, "t1": 11.5, "before": counters(0, 0, 0), "after": counters(1, 32, 30)},
    }
    trace = {"programs": {"decode_steps": {"calls": 1.0, "seconds": 0.16}}, "window_s": 1.0, "busy_s": 0.5}

    assert _reader("moe_local_pairs_per_step")(facts, None, None) == 64 / (2 * 8 * 4)  # pairs / (steps x expert layers)
    flops = work_moe.prefill_fixed_flops(cfg, 100) + sum(work_moe.token_fixed_flops(cfg, c, head=True) for c in range(100, 117))
    flops += work_moe.routed_flops(cfg, 64)  # the window's pairs: after - before
    assert _reader("step_mfu.serve_moe")(facts, None, peak) == pytest.approx(100.0 * flops / (10.0 * 197e12))
    least, bound = work_moe.decode_least_seconds(cfg, peak, 8, range(101, 109), 30, 32)
    assert bound == "memory"
    assert _reader("moe_decode_roofline")(facts, trace, peak) == pytest.approx(100.0 * (least / 8) / (0.16 / 8))
    assert _reader("moe_decode_roofline")(facts, trace, peak) < 100.0
    # no chip, a program without the counters (the parent commit), another kind of cell: nothing, and no exception
    for name in ("step_mfu.serve_moe", "moe_decode_roofline"):
        assert _reader(name)(facts, trace, None) is None
    bare = dict(facts, before={"decode_dispatches": 0}, after={"decode_dispatches": 2})
    bare["slice"] = dict(facts["slice"], before={"decode_dispatches": 0}, after={"decode_dispatches": 1})
    for name in ("step_mfu.serve_moe", "moe_decode_roofline", "moe_local_pairs_per_step"):
        assert _reader(name)(bare, trace, peak) is None
        assert _reader(name)({"kind": "training"}, trace, peak) is None


def test_the_reference_is_plain_and_the_window_is_a_marked_copy():
    text = open(os.path.join(ROOT, "perf", "reference", "afmoe_decoder.py")).read()
    code = re.sub(r'""".*?"""', "", text, flags=re.S)
    assert not re.search(r"unionml_tpu|flax|optax|pallas|ragged_dot", code)
    assert 'default_matmul_precision("highest")' in code
    from perf.systems import afmoe_serving, decoder_serving

    assert afmoe_serving.plant_fault is decoder_serving.plant_fault and afmoe_serving.Server is decoder_serving.Server
    assert afmoe_serving.build_app is decoder_serving.build_app and afmoe_serving.run is not decoder_serving.run
    # ``run`` restates ``decoder_serving.run``: every line that is not marked ``# differs:`` (or its docstring) is
    # that function's own, in order, so a change there shows here as a failure and not as a silent fork
    body = lambda module: open(module.__file__).read().split("\ndef run(ctx: Any)", 1)[1].splitlines()  # noqa: E731
    theirs, ours = body(decoder_serving), body(afmoe_serving)
    changed = [line for line in difflib.unified_diff(theirs, ours, lineterm="", n=0) if line[:1] in "+-" and line[:3] not in ("+++", "---")]
    added = [line[1:] for line in changed if line[0] == "+"]
    removed = [line[1:].strip() for line in changed if line[0] == "-"]
    assert removed == [
        "weights = reference.make_weights(cfg, args.seed)", "gen, batcher = build_engine(cfg, cell, weights, args.control)",
        'logits = reference.logits_at(weights, cfg, list(prompt) + list(served[:-1]), rows, pad_to=int(cell["check"].get("pad_to", 512)))',
    ], removed
    assert len(added) == 11 and sum("# differs:" in line for line in added) == 4, added


def test_the_cell_is_the_one_the_issue_named():
    from perf.run import load_cell

    loaded = load_cell(CELL, rehearse=False)
    engine = {**loaded.config["engine"], **loaded.cell["engine"]}
    assert (loaded.mix["clients"], engine["slots"], engine["prefill_budget"], engine["pool_blocks"]) == (240, 160, 2048, 3072)
    assert (engine["decode_chunk"], engine["block_size"], engine["admit_chunk"], engine["max_prompt_tokens"], engine["max_new_tokens"]) == (8, 64, 256, 4608, 768)
    assert loaded.mix["ramp_s"] == 20.0 and loaded.mix["stream_threads"] == 512 and loaded.cell["check"]["requests"] == 6
    # the server gives up on a waiting request when its caller does, not before
    assert loaded.cell["serve"]["default_deadline_ms"] == 1e3 * loaded.mix["request_timeout_s"]
