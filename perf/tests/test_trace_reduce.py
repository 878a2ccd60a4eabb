"""The reduction from a trace to busy time, program time, top operations and gaps, against hand-counted
values on the small traces kept in perf/testdata (see each file's ``about``)."""

import json
import os

import pytest

from perf import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "testdata")


def test_small_trace_matches_the_hand_count():
    events = trace_reduce.load(os.path.join(DATA, "small_trace.json"))
    assert trace_reduce.device_ids(events) == ["/device:TPU:0"]
    assert trace_reduce.busy_intervals(events, "/device:TPU:0") == [(0, 250), (400, 650), (1000, 1200)]
    assert trace_reduce.busy_seconds(events) == pytest.approx(700e-9)
    programs = trace_reduce.program_seconds(events)
    assert programs["decode_steps"] == {"calls": 2.0, "seconds": pytest.approx(500e-9)}
    assert programs["prefill_chunk"] == {"calls": 1.0, "seconds": pytest.approx(200e-9)}
    assert trace_reduce.top_operations(events, 2) == [["fusion.1", pytest.approx(400e-9)], ["copy.2", pytest.approx(300e-9)]]
    gaps = dict(trace_reduce.idle_gaps(events, ["engine.admit"]))
    assert gaps == {"host:engine.admit": pytest.approx(350e-9), "between:decode_steps>decode_steps": pytest.approx(150e-9)}
    unnamed = dict(trace_reduce.idle_gaps(events))
    assert unnamed["between:decode_steps>prefill_chunk"] == pytest.approx(350e-9)
    summary = trace_reduce.summarize(events, ["engine.admit"])
    assert summary["chips"] == 1 and summary["window_s"] == pytest.approx(1200e-9)
    assert 1 - summary["busy_s"] / summary["window_s"] == pytest.approx(1 - 700 / 1200)


def test_traced_window_is_the_devices_own():
    """The window is taken from the device's events, never from the host's clock around the profiler's calls: a
    profiler that starts recording late shortens busy time and window alike. Host events do not stretch it; on
    several chips it runs from the earliest start to the latest end, and busy time is the chips' average."""
    op = lambda chip, start, dur: trace_reduce.Event(f"/device:TPU:{chip}", trace_reduce.OPS_LINE, "fusion.1", start, dur)  # noqa: E731
    host = trace_reduce.Event("/host:CPU", "main", "unionml_tpu.train_step", -5000, 20000)
    one = [op(0, 1000, 400), op(0, 1500, 500), host]
    assert trace_reduce.traced_seconds(one) == pytest.approx(1000e-9)
    assert trace_reduce.summarize(one)["busy_s"] == pytest.approx(900e-9)
    four = one + [op(1, 900, 600), op(1, 1600, 500)]
    assert trace_reduce.traced_seconds(four) == pytest.approx(1200e-9)
    assert trace_reduce.summarize(four)["busy_s"] == pytest.approx((900e-9 + 1100e-9) / 2)
    assert trace_reduce.traced_seconds([host]) == 0.0


def test_executions_cut_by_the_trace_edges_are_left_out():
    mod = lambda name, start, dur: trace_reduce.Event("/device:TPU:0", trace_reduce.MODULES_LINE, name, start, dur)  # noqa: E731
    # the trace began 60 ns before a step ended and stopped 30 ns into another: 3 whole steps of 100 ns between
    events = [mod("jit_trainer(1)", 0, 60), mod("jit_trainer(1)", 70, 100), mod("jit_trainer(1)", 180, 100),
              mod("jit_trainer(1)", 290, 100), mod("jit_trainer(1)", 400, 30)]
    assert trace_reduce.program_seconds(events)["trainer"] == {"calls": 3.0, "seconds": pytest.approx(300e-9)}
    # two runs only: nothing to judge an edge by, both count
    assert trace_reduce.program_seconds(events[:2])["trainer"]["calls"] == 2.0


def test_program_names():
    assert trace_reduce.program_name("jit_decode_steps(1234567890)") == "decode_steps"
    assert trace_reduce.program_name("jit_trainer") == "trainer"
    assert trace_reduce.program_name("prefill_chunk(7)") == "prefill_chunk"


def test_recorded_chip_trace_matches_its_hand_count():
    path = os.path.join(DATA, "v5e_train_steps.json")
    if not os.path.isfile(path):
        pytest.skip("no recorded chip trace in this tree")
    with open(path) as f:
        counted = json.load(f)["hand_counted"]
    events = trace_reduce.load(path)
    programs = trace_reduce.program_seconds(events)
    assert programs[counted["program"]]["calls"] == counted["calls"]
    assert programs[counted["program"]]["seconds"] == pytest.approx(counted["program_seconds"], rel=1e-9)
    assert trace_reduce.busy_seconds(events) == pytest.approx(counted["busy_seconds"], rel=1e-9)
    assert trace_reduce.top_operations(events, 1)[0][0] == counted["top_operation"]
    assert trace_reduce.traced_seconds(events) == pytest.approx(counted["traced_seconds"], rel=1e-9)
