"""The per-layer metrics that read the engine's own record (``perf/layer_metrics/_engine_log.py``): a traced
rehearsal of a serving cell reports all ten, the six phases sum to the iteration, the three parts of the time to the
first token sum to the client's mean; on a program without the record every reader returns nothing and none raises."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PHASES = ("schedule", "admit", "grow", "dispatch", "fetch", "emit")
ENGINE = ["engine_iteration_ms"] + [f"engine_phase_ms.{p}" for p in PHASES]
TTFT_PARTS = ["queue_wait_mean_ms", "admission_mean_ms", "front_ttft_gap_ms"]


def rehearse(cell, *more):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    args = ["--workload", cell, "--seed", "4000000021", "--seconds", "4", "--trace", "1", "--rehearse", *more]
    done = subprocess.run([sys.executable, "-m", "perf.run", *args], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines() if x.startswith("{")]
    return lines[-2]["detail"], lines[-1]


def test_traced_docs_rehearsal_reports_the_ten_engine_metrics():
    detail, line = rehearse("mistral7b.docs")
    assert line["correct"] is True and line["rehearsal"] is True
    values = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert set(ENGINE + TTFT_PARTS) <= set(values)
    assert all(line["metrics"][name]["unit"] == "ms" for name in ENGINE + TTFT_PARTS)
    assert all(values[name] >= 0.0 for name in ENGINE + TTFT_PARTS[:2])
    # the phases partition the iteration
    assert sum(values[f"engine_phase_ms.{p}"] for p in PHASES) == pytest.approx(values["engine_iteration_ms"], rel=0.01)
    assert values["engine_phase_ms.fetch"] > 0.0 and values["engine_phase_ms.emit"] > 0.0
    # queue wait + admission + the front's share is the client's mean time to the first token over the requests
    # whose first token arrived in the window; the detail line's mean is over those due in it (the sets differ
    # by the requests in flight at either edge, a few of the rehearsal's two dozen)
    assert sum(values[name] for name in TTFT_PARTS) == pytest.approx(detail["also"]["ttft_mean_ms"], rel=0.5)
    assert values["admission_mean_ms"] > 0.0


def test_traced_chat_sat_rehearsal_reports_the_engine_phases_and_names_them_in_the_gaps():
    spans = json.dumps([f"unionml_tpu.engine.{p}" for p in PHASES])
    detail, line = rehearse("mistral7b.chat_sat", "--set", f"cell.host_spans={spans}")
    values = {name: entry["value"] for name, entry in line["metrics"].items()}
    assert set(ENGINE) <= set(values) and not set(TTFT_PARTS) & set(values)  # the parts of TTFT are docs' alone
    assert sum(values[f"engine_phase_ms.{p}"] for p in PHASES) == pytest.approx(values["engine_iteration_ms"], rel=0.01)


def _reader(name):
    path = os.path.join(ROOT, "perf", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", ENGINE + TTFT_PARTS)
def test_reader_returns_nothing_without_the_record(name, monkeypatch):
    """A program that lacks the handle (the parent commit), another kind of cell, or a window in which no engine
    ran: nothing, and no exception (``perf/run.py`` catches none around a reader)."""
    import time
    import types

    import unionml_tpu.observability as package
    from unionml_tpu.observability.engine_log import EngineLog, RequestRecord, register_engine_log

    import collections

    from unionml_tpu.observability import engine_log as module

    monkeypatch.setattr(module, "_logs", collections.deque(maxlen=4))  # no other test's engine in this process
    read = _reader(name)
    log = EngineLog()
    log.begin()
    with log.phase("fetch"):
        time.sleep(0.002)
    log.end()
    now = time.monotonic()
    log.request(RequestRecord("r", now - 0.3, now - 0.2, now - 0.1, now, 8, 0, 4, "finish", 0))
    register_engine_log(log)
    seen = types.SimpleNamespace(request=types.SimpleNamespace(index=0), due=now - 0.4, first=now - 0.05)
    facts = {"kind": "serving", "open_at": now - 1.0, "close_at": now + 1.0, "records": [seen], "in_window": [seen]}
    assert read(facts, None, None) is not None  # the record is there: every reader reads it
    assert read(dict(facts, open_at=now + 5.0, close_at=now + 6.0), None, None) is None  # no engine ran in that window
    assert read({"kind": "training"}, None, None) is None
    # the import fails, as on a commit before the record existed
    monkeypatch.setitem(sys.modules, "unionml_tpu.observability.engine_log", None)
    monkeypatch.delattr(package, "engine_log", raising=False)
    assert read(facts, None, None) is None
