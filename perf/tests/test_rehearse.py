"""Each cell runs end to end at its rehearsal size on the CPU; a plain run without a TPU exits non-zero
and prints no result; with the timed path broken underneath, ``correct`` comes out false; the
lower-precision control comes out as not correct."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run([sys.executable, "-m", "perf.run", *args], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    lines = [x for x in done.stdout.splitlines() if x.strip()]
    return done, (json.loads(lines[-1]) if lines and lines[-1].startswith("{") else None)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace):
    done, line = run("--workload", cell, "--seed", "4000000007", "--seconds", "3", "--trace", str(trace), "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    assert KEYS <= set(line) and line["rehearsal"] is True and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert line["device"]["platform"] == "cpu"  # the device's real name: never mistaken for a measurement
    assert line["failed"] == 0 and line["attempted"] > 0
    for name in line["metrics"]:
        assert "roofline" not in name and "mfu" not in name and "idle" not in name  # no chip, no share of a peak
    assert "correct: True" in done.stderr.splitlines()[-1]
    if trace == 0:  # a traced rehearsal may have nothing to report: every device metric needs the chip
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2


@pytest.mark.parametrize("cell", CELLS)
def test_plain_run_without_a_tpu_fails(cell):
    done, line = run("--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0")
    assert done.returncode != 0 and line is None and "no TPU" in done.stderr


FAULTS = [
    ("mistral7b.chat_sat", "token_altered"), ("mistral7b.docs", "token_altered"),
    ("bert-base.finetune", "state_unchanged"), ("bert-base.finetune", "half_batch"),
]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_underneath_reads_not_correct(cell, fault):
    if cell not in CELLS:
        pytest.skip(f"{cell} is not a cell of this benchmark")
    done, line = run("--workload", cell, "--seed", "12", "--seconds", "2", "--trace", "0", "--rehearse", "--fault", fault)
    assert done.returncode == 0, done.stderr[-2000:]
    assert line["correct"] is False
    over = [n for n, e in line["compared"].items() if e["limit"] is not None and e["value"] > e["limit"]]
    assert over, line["compared"]


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_reads_not_correct(cell):
    done, line = run("--workload", cell, "--seed", "13", "--seconds", "2", "--trace", "0", "--rehearse", "--control", "int8")
    assert done.returncode == 0, done.stderr[-2000:]
    assert line["correct"] is False, line["compared"]


def test_four_chip_path_is_data():
    """A configuration with a ``mesh`` builds it and its partition rules from data: the serving system,
    tensor-parallel over four virtual devices, still agrees with the unsharded reference."""
    done, line = run(
        "--workload", "mistral7b.chat_sat", "--seed", "14", "--seconds", "2", "--trace", "0", "--rehearse",
        "--set", 'config.mesh={"model": 4}', "--set", 'config.partition_rules="llama_partition_rules"', devices=4,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert line["correct"] is True and line["device"]["count"] == 4
