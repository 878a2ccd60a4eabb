"""The four per-layer metrics that read what the engine handed its device and waited for (PR 35:
``engine_wait_ms.decode``, ``engine_wait_ms.admission``, ``device_starved_share``, ``engine_iteration_max_ms``), on
hand-made records: each reads the fields it names over the iterations that start in the window, and nothing, without
raising, from records that lack the fields (the parent commit's), from another kind of cell, or from a window in which
no engine ran."""

import collections
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NEW = ["engine_wait_ms.decode", "engine_wait_ms.admission", "device_starved_share", "engine_iteration_max_ms"]


def _reader(name):
    path = os.path.join(ROOT, "perf", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class _Log:
    """What the readers ask of an engine log: its two rings."""

    def __init__(self, iterations):
        self._iterations = iterations

    def iteration_records(self):
        return list(self._iterations)

    def request_records(self):
        return []


@pytest.fixture
def logs(monkeypatch):
    from unionml_tpu.observability import engine_log as module

    held = collections.deque(maxlen=4)  # no other test's engine in this process
    monkeypatch.setattr(module, "_logs", held)
    return held


FACTS = {"kind": "serving", "open_at": 100.0, "close_at": 150.0}


def _records():
    from unionml_tpu.observability.engine_log import PHASES, WAITS, IterationRecord

    def make(index, start, fetch_by_kind, starved_by_phase, **phases):
        phase_s = tuple(phases.get(p, 0.0) for p in PHASES)
        wait_s = tuple(fetch_by_kind.get(k, 0.0) for k in WAITS)
        starved_s = tuple(starved_by_phase.get(p, 0.0) for p in PHASES)
        assert sum(wait_s) == pytest.approx(phases.get("fetch", 0.0))
        return IterationRecord(index, start, phase_s, 8, 0, 1, 0, 0, 1, 5, {"decode_steps": 1}, wait_s, (0.0,) * len(WAITS), starved_s)

    return [
        make(0, 99.0, {"decode": 9.0}, {"emit": 9.0}, fetch=9.0, emit=9.0),  # began before the window: not read
        make(1, 100.0, {"decode": 0.110, "first_logprob": 0.030}, {"emit": 0.004, "admit": 0.010}, fetch=0.140, admit=0.020, emit=0.010, schedule=0.030),
        make(2, 120.0, {"decode": 0.100, "first_token": 0.002, "first_logprob": 0.020, "export": 0.008, "spec": 0.070}, {"schedule": 0.006}, fetch=0.200, admit=0.100, emit=0.100),
        make(3, 150.0, {"decode": 7.0}, {"emit": 7.0}, fetch=7.0, emit=7.0),  # began at the window's close: not read
    ]


WANT = {
    "engine_wait_ms.decode": 1e3 * (0.110 + 0.100) / 2,
    "engine_wait_ms.admission": 1e3 * (0.030 + 0.002 + 0.020 + 0.008) / 2,  # the speculative round's wait is in neither half
    "device_starved_share": 100.0 * (0.004 + 0.010 + 0.006) / (0.200 + 0.400),
    "engine_iteration_max_ms": 400.0,
}


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_its_fields_over_the_iterations_that_start_in_the_window(name, logs):
    logs.append(_Log(_records()))
    assert _reader(name)(FACTS, None, None) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_nothing_where_the_record_lacks_the_fields(name, logs, monkeypatch):
    """The parent commit's record is the first ten fields; another kind of cell, an empty window and a program
    without the handle read as nothing too, and nothing raises (``perf/run.py`` catches none around a reader)."""
    import unionml_tpu.observability as package

    Old = collections.namedtuple("IterationRecord", "index start phase_s rows prefill_tokens admitted finished blocks_grown table_syncs admit_dispatches")
    logs.append(_Log([Old(*record[:10]) for record in _records()]))
    read = _reader(name)
    assert read(FACTS, None, None) is None
    logs.clear()
    logs.append(_Log(_records()))
    assert read(FACTS, None, None) is not None
    assert read(dict(FACTS, open_at=200.0, close_at=250.0), None, None) is None  # no engine ran in that window
    assert read({"kind": "training"}, None, None) is None
    monkeypatch.setitem(sys.modules, "unionml_tpu.observability.engine_log", None)
    monkeypatch.delattr(package, "engine_log", raising=False)
    assert read(FACTS, None, None) is None


def test_the_two_halves_of_the_fence_sum_to_the_fetch_phase_where_nothing_speculates(logs):
    from unionml_tpu.observability.engine_log import WAITS

    records = [r._replace(wait_s=tuple(0.0 if k == "spec" else s for k, s in zip(WAITS, r.wait_s))) for r in _records()]
    logs.append(_Log(records))
    halves = sum(_reader(name)(FACTS, None, None) for name in NEW[:2])
    assert halves == pytest.approx(1e3 * (0.140 + 0.200 - 0.070) / 2)
    assert _reader("device_starved_share")(FACTS, None, None) <= 100.0
