"""The engine's own record of where its time goes (observability/engine_log.py).

Contracts pinned here (docs/observability.md, "Where the engine's time goes"):

- the six phases partition every iteration — their sum is the iteration's wall
  time — and ``idle`` lies outside every iteration;
- both rings are bounded; the totals in ``stats()["loop"]`` are cumulative and
  render through the Prometheus exposition unchanged;
- every request that ends — finish, cancel, deadline shed — leaves one
  life-cycle record with ordered stamps, its request id, and the index of the
  iteration that emitted its first token;
- every phase enters a ``jax.profiler.TraceAnnotation`` of its documented name;
- the records outlive ``close()`` through the process-wide handle, and
  ``GET /debug/engine`` serves them.
"""

import asyncio
import dataclasses
import gc
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import DraftSpec, GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.observability import render_prometheus
from unionml_tpu.observability import engine_log as engine_log_mod
from unionml_tpu.observability import trace as trace_mod
from unionml_tpu.observability.engine_log import (
    PHASES,
    SPAN_PREFIX,
    EngineLog,
    RequestRecord,
    engine_logs,
)
from unionml_tpu.serving import ContinuousBatcher
from unionml_tpu.serving.overload import DeadlineExceeded


@pytest.fixture(scope="module")
def tiny_gen():
    config = LlamaConfig.tiny(
        vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _engine(tiny_gen, *, max_new=8, draft=False, **kwargs):
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=(16, 32))
    if draft:
        d_cfg = LlamaConfig.tiny(
            vocab_size=97, dim=32, n_layers=1, n_heads=4, n_kv_heads=2, hidden_dim=64,
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        d_module = Llama(d_cfg)
        d_params = d_module.init(jax.random.PRNGKey(9), jnp.zeros((1, 8), jnp.int32))["params"]
        cfg = dataclasses.replace(cfg, draft=DraftSpec(module=d_module, params=d_params, gamma=3))
    return ContinuousBatcher(Generator(module, params, cfg), **kwargs)


PAGED = dict(slots=3, decode_chunk=4, block_size=8, pool_blocks=48, admit_chunk=8, prefix_cache=True)


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


def _run(batcher, prompts, **submit_kwargs):
    threads = [
        threading.Thread(target=lambda p=p: _drain(batcher.submit(p, **submit_kwargs)))
        for p in prompts
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)


def _settled(log, iterations=1, requests=0, finished=0, timeout=30.0):
    """The engine thread records an iteration just after its consumers see the
    last token: wait for the log to hold what the test is about to read."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if (
            log.totals()["iterations"] >= iterations
            and len(log.request_records()) >= requests
            and sum(record.finished for record in log.iteration_records()) >= finished
        ):
            return
        time.sleep(0.005)
    raise AssertionError(f"engine log never reached {iterations} iterations / {requests} requests")


class _Clock:
    """A scripted ``time.monotonic`` for the log's own arithmetic."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


# ------------------------------------------------------------------ the phase clock


def test_phases_partition_the_iteration_and_idle_lies_outside(monkeypatch):
    """On a scripted clock: a nested phase suspends the one around it, the six
    durations sum to the pass's wall time exactly, and a wait is idle."""
    clock = _Clock()
    monkeypatch.setattr(engine_log_mod.time, "monotonic", clock)
    log = EngineLog()
    log.begin()  # schedule from 100
    clock.now = 101.0
    log.wait()  # nothing to do: idle from 101
    clock.now = 111.0
    log.begin()  # woke: the iteration starts at 111 — 11 s since the first begin were idle
    clock.now = 111.5
    with log.phase("admit"):  # 111.5 .. 114.5, less the nested fetch
        clock.now = 112.0
        with log.phase("fetch"):  # 112 .. 113
            clock.now = 113.0
        clock.now = 114.5
    clock.now = 115.0  # schedule again 114.5 .. 115
    with log.phase("grow"):
        clock.now = 115.25
    with log.phase("dispatch"):
        clock.now = 115.5
    with log.phase("fetch"):
        clock.now = 118.5
    with log.phase("emit"):
        clock.now = 119.0
    log.rows, log.prefill_tokens, log.admitted, log.finished, log.blocks_grown, log.table_syncs = 3, 16, 1, 2, 4, 2
    log.admit_dispatches = 7
    log.end()
    (record,) = log.iteration_records()
    assert record.index == 0 and record.start == 111.0
    assert dict(zip(PHASES, record.phase_s)) == {
        "schedule": 1.0, "admit": 2.0, "grow": 0.25, "dispatch": 0.25, "fetch": 4.0, "emit": 0.5,
    }
    assert sum(record.phase_s) == 119.0 - 111.0  # the pass's wall time, idle not in it
    assert (record.rows, record.prefill_tokens, record.admitted, record.finished, record.blocks_grown) == (3, 16, 1, 2, 4)
    assert record.table_syncs == 2 and record.admit_dispatches == 7
    assert record._fields[-2:] == ("table_syncs", "admit_dispatches")  # appended: readers by position keep theirs
    totals = log.totals()
    assert totals["iterations"] == 1 and totals["idle_s"] == 11.0
    assert totals["phase_s"]["fetch"] == 4.0 and totals["table_syncs"] == 2 and totals["admit_dispatches"] == 7
    assert log.rows == log.prefill_tokens == log.admitted == log.finished == log.blocks_grown == log.table_syncs == 0
    assert log.admit_dispatches == 0
    log.begin()
    log.admit_dispatches = 3
    log.end()
    assert log.totals()["admit_dispatches"] == 10  # summed over the iterations, like table_syncs
    log.clear()
    assert log.totals()["admit_dispatches"] == log.totals()["table_syncs"] == 0


def test_engine_iterations_sum_to_their_wall_time_within_one_percent(tiny_gen, monkeypatch):
    """On a real engine, against stamps taken around the log's own calls: the
    six phases of every iteration never exceed its wall time and over the run
    they sum to it within 1 %; a quiet stretch between two requests is idle and
    in no iteration."""
    walls = {}
    real_begin, real_end = EngineLog.begin, EngineLog.end

    def begin(self):
        walls["t0"] = time.monotonic()
        real_begin(self)

    def end(self):
        index = self.index
        real_end(self)
        walls[index] = time.monotonic() - walls["t0"]

    monkeypatch.setattr(EngineLog, "begin", begin)
    monkeypatch.setattr(EngineLog, "end", end)
    batcher = _engine(tiny_gen, max_new=16, **PAGED)
    try:
        batcher.warmup()
        inner = batcher.gen._decode

        def slow_decode(*args, **kwargs):  # iterations long enough that 1 % is not a scheduler hiccup
            time.sleep(0.02)
            return inner(*args, **kwargs)

        monkeypatch.setattr(batcher.gen, "_decode", slow_decode)
        log = batcher.engine_log
        _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1], [8, 2, 8]], logprobs=True)
        _settled(log, requests=3, finished=3)
        first_batch = log.totals()
        time.sleep(0.3)  # nothing to do: the engine waits
        _run(batcher, [[44, 9, 7]])
        _settled(log, requests=4, finished=4)
        records = log.iteration_records()
        assert len(records) >= 6
        for record in records:
            assert all(seconds >= 0.0 for seconds in record.phase_s)
            assert sum(record.phase_s) <= walls[record.index] + 1e-9
        phases = sum(sum(record.phase_s) for record in records)
        wall = sum(walls[record.index] for record in records)
        assert phases == pytest.approx(wall, rel=0.01)
        # the quiet stretch is idle, beside the phases: no iteration holds it
        totals = log.totals()
        assert totals["idle_s"] - first_batch["idle_s"] >= 0.25
        ends = [record.start + sum(record.phase_s) for record in records]
        assert max(start - end for start, end in zip([r.start for r in records][1:], ends)) >= 0.25
        assert 2 <= max(record.rows for record in records) <= 3
        assert sum(record.prefill_tokens for record in records) > 0
        assert sum(record.admitted for record in records) == 4
        assert sum(record.finished for record in records) == 4
        assert sum(record.blocks_grown for record in records) > 0
        starts = [record.start for record in records]
        assert starts == sorted(starts)
    finally:
        batcher.close()


def test_rings_stop_growing_at_capacity(tiny_gen):
    log = EngineLog(capacity=4)
    for i in range(10):
        log.begin()
        log.end()
        log.request(RequestRecord(f"r{i}", 0.0, None, None, 1.0, 3, 0, 0, "cancel", None))
    assert [r.index for r in log.iteration_records()] == [6, 7, 8, 9]
    assert [r.request_id for r in log.request_records()] == ["r6", "r7", "r8", "r9"]
    assert log.totals()["iterations"] == 10  # the totals keep counting
    with pytest.raises(ValueError):
        EngineLog(capacity=0)
    # and on an engine: five requests through a ring of three
    batcher = _engine(tiny_gen, slots=2, decode_chunk=4)
    batcher.engine_log = log = EngineLog(capacity=3)
    try:
        for i in range(5):
            _drain(batcher.submit([3 + i, 1, 4]))
        _settled(log, requests=3)
        assert len(log.request_records()) == 3
        assert len(log.iteration_records()) == 3 < log.totals()["iterations"]
    finally:
        batcher.close()


# ------------------------------------------------------------------ life-cycle records


def _end_by_finish(batcher):
    assert len(_drain(batcher.submit([3, 14, 15, 92, 6, 5, 3, 5, 9, 2]))) == 8
    return "finish", True


def _end_by_cancel(batcher):
    stream = batcher.submit([3, 14, 15, 92, 6])
    next(stream)  # the first token arrived: the request is resident
    stream.close()
    return "cancel", True


def _end_by_deadline_shed(batcher):
    """One slot, held by a long request: a second one whose deadline passes
    while it waits is shed without ever being admitted."""
    holder = batcher.submit([8, 2, 8, 1])
    next(holder)
    waiter = batcher.submit([27, 1], deadline=time.monotonic() + 0.05)
    with pytest.raises(DeadlineExceeded):
        _drain(waiter)
    holder.close()
    return "shed_deadline", False


@pytest.mark.parametrize("end", [_end_by_finish, _end_by_cancel, _end_by_deadline_shed], ids=["finish", "cancel", "shed_deadline"])
def test_lifecycle_record_written_for_every_end(tiny_gen, end):
    batcher = _engine(tiny_gen, slots=1, decode_chunk=2, block_size=8, pool_blocks=16, admit_chunk=8)
    tokens = trace_mod.bind("rid-lifecycle")  # tracing itself stays off: the id alone flows
    try:
        log = batcher.engine_log
        outcome, served = end(batcher)
        _settled(log, requests=1)
        record = next(r for r in log.request_records() if r.outcome == outcome)
        assert record.request_id == "rid-lifecycle"
        assert record.submitted <= record.finished <= time.monotonic()
        if served:
            assert record.submitted <= record.admission_started <= record.first_token <= record.finished
            assert record.prompt_tokens in (5, 10) and record.produced >= 1
            # the first token's iteration: the record is there and the stamp falls inside it
            _settled(log, iterations=record.first_iteration + 1)
            iteration = next(r for r in log.iteration_records() if r.index == record.first_iteration)
            assert iteration.start <= record.first_token <= iteration.start + sum(iteration.phase_s) + 1e-6
            assert iteration.admitted == 1
        else:
            assert record.admission_started is None and record.first_token is None
            assert record.first_iteration is None and record.produced == 0
        assert record.render()["outcome"] == outcome
    finally:
        trace_mod.unbind(tokens)
        batcher.close()


def test_lifecycle_record_counts_radix_cached_prompt_tokens(tiny_gen):
    batcher = _engine(tiny_gen, **PAGED)
    try:
        shared = list(range(3, 21))  # 18 tokens: two full blocks of 8
        _drain(batcher.submit(shared + [40]))
        _drain(batcher.submit(shared + [41]))
        _settled(batcher.engine_log, requests=2)
        cold, warm = batcher.engine_log.request_records()
        assert (cold.prompt_tokens, cold.cached_tokens) == (19, 0)
        assert warm.prompt_tokens == 19 and warm.cached_tokens >= 16
        assert warm.cached_tokens == batcher.stats()["prefix_cache"]["tokens_avoided"]
    finally:
        batcher.close()


# ------------------------------------------------------------------ the readers


def test_stats_loop_is_cumulative_and_renders_as_prometheus(tiny_gen):
    batcher = _engine(tiny_gen, slots=2, decode_chunk=4)
    try:
        batcher.warmup()
        zero = batcher.stats()["loop"]
        assert zero["iterations"] == 0 and set(zero["phase_s"]) == set(PHASES)  # warm-up passes are not traffic
        _drain(batcher.submit([3, 14, 15]))
        _settled(batcher.engine_log)
        first = batcher.stats()["loop"]
        _drain(batcher.submit([9, 2, 6]))
        _settled(batcher.engine_log, iterations=first["iterations"] + 1)
        second = batcher.stats()["loop"]
        assert second["iterations"] > first["iterations"] > 0
        assert second["idle_s"] >= first["idle_s"] >= 0.0
        for phase in PHASES:
            assert second["phase_s"][phase] >= first["phase_s"][phase] >= 0.0
        assert second["phase_s"]["fetch"] > first["phase_s"]["fetch"]
        text = render_prometheus({"generation": batcher.stats()})
        samples = dict(line.rsplit(" ", 1) for line in text.splitlines() if not line.startswith("#"))
        assert int(samples["unionml_tpu_generation_loop_iterations"]) >= second["iterations"]
        assert float(samples["unionml_tpu_generation_loop_idle_s"]) >= 0.0
        for phase in PHASES:
            assert float(samples[f"unionml_tpu_generation_loop_phase_s_{phase}"]) >= second["phase_s"][phase]
    finally:
        batcher.close()


@pytest.mark.parametrize("draft", [False, True], ids=["plain", "speculative"])
def test_every_phase_enters_a_trace_annotation_of_its_documented_name(tiny_gen, monkeypatch, draft):
    entered, left = [], []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc_info):
            left.append(self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    kwargs = dict(slots=2, decode_chunk=4) if draft else PAGED
    batcher = _engine(tiny_gen, draft=draft, **kwargs)
    try:
        _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1]], logprobs=not draft)
        _settled(batcher.engine_log, requests=2)
        deadline = time.monotonic() + 30
        while SPAN_PREFIX + "idle" not in entered and time.monotonic() < deadline:
            time.sleep(0.005)  # with nothing left to do the engine thread waits: the idle span
    finally:
        batcher.close()
    batcher._thread.join(timeout=30)
    assert not batcher._thread.is_alive()
    assert set(entered) == {SPAN_PREFIX + phase for phase in PHASES} | {SPAN_PREFIX + "idle"}
    assert sorted(entered) == sorted(left)  # every span that opened closed, the engine's exit included
    iterations = batcher.engine_log.totals()["iterations"]
    for phase in ("grow", "dispatch", "emit"):  # once a decode dispatch
        assert 1 <= entered.count(SPAN_PREFIX + phase) <= iterations
    assert entered.count(SPAN_PREFIX + "fetch") >= entered.count(SPAN_PREFIX + "dispatch")


def test_records_stay_readable_through_the_process_wide_handle_after_close(tiny_gen):
    batcher = _engine(tiny_gen, slots=2, decode_chunk=4)
    log = batcher.engine_log
    assert not any(known is log for known in engine_logs())  # registered when the engine thread starts
    _drain(batcher.submit([3, 14, 15]))
    batcher.close()
    assert batcher._thread is not None and not batcher._thread.is_alive()
    del batcher
    gc.collect()
    found = next(known for known in reversed(engine_logs()) if known is log)
    assert found.totals()["iterations"] >= 1
    assert found.iteration_records() and found.request_records()[-1].outcome == "finish"
    snapshot = found.snapshot(limit=1)
    assert len(snapshot["iterations_log"]) == 1 and len(snapshot["requests_log"]) == 1
    assert snapshot["iterations_log"][0]["index"] == snapshot["iterations"] - 1  # newest first
    assert set(snapshot["iterations_log"][0]["phase_s"]) == set(PHASES)
    assert len(engine_logs()) <= engine_log_mod._MAX_LOGS


def test_debug_engine_endpoint_answers(tiny_gen, sklearn_model):
    from unionml_tpu.serving.app import ServingApp

    sklearn_model.train(hyperparameters={"max_iter": 500})
    app = ServingApp(sklearn_model)

    def get(path):
        async def run():
            app.startup()
            return await app.server.dispatch_with_headers("GET", path, b"", None)

        return asyncio.run(run())

    batcher = _engine(tiny_gen, slots=2, decode_chunk=4)
    try:
        for prompt in ([3, 14, 15], [9, 2, 6], [5, 3]):
            _drain(batcher.submit(prompt))
        _settled(batcher.engine_log, iterations=3, requests=3)
        status, payload, content_type, _ = get("/debug/engine?limit=2")
        assert status == 200 and content_type == "application/json"
        mine = payload["engines"][-1]
        assert mine["iterations"] >= 3 and set(mine["phase_s"]) == set(PHASES)
        assert len(mine["iterations_log"]) == 2 and len(mine["requests_log"]) == 2
        assert mine["requests_log"][0]["outcome"] == "finish"
        assert mine["requests_log"][0]["first_iteration"] is not None
        status, payload, _, _ = get("/debug/engine?limit=many")
        assert status == 400
        assert ("GET", "/debug/engine") in app.server._drain_exempt  # answers while a drain is stuck
    finally:
        batcher.close()


def _read_iteration_records(batcher, get, field="table_syncs"):
    records = batcher.engine_log.iteration_records()
    return [getattr(r, field) for r in records], sum(getattr(r, field) for r in records)


def _read_stats_loop(batcher, get, field="table_syncs"):
    return None, batcher.stats()["loop"][field]


def _read_prometheus(batcher, get, field="table_syncs"):
    text = render_prometheus({"generation": batcher.stats()})
    samples = dict(line.rsplit(" ", 1) for line in text.splitlines() if not line.startswith("#"))
    return None, int(samples[f"unionml_tpu_generation_loop_{field}"])


def _read_debug_engine(batcher, get, field="table_syncs"):
    status, payload, _, _ = get("/debug/engine")
    assert status == 200
    mine = payload["engines"][-1]
    return [r[field] for r in reversed(mine["iterations_log"])], mine[field]


@pytest.mark.parametrize(
    "read", [_read_iteration_records, _read_stats_loop, _read_prometheus, _read_debug_engine],
    ids=["iteration_records", "stats_loop", "prometheus", "debug_engine"],
)
def test_table_syncs_is_served_wherever_the_loop_is_read(tiny_gen, sklearn_model, read):
    """How often the engine ran the program that carries its table growths and
    slot releases to the device: per iteration in the record (at most twice,
    0 where nothing grew and nothing was released), cumulative in
    ``stats()["loop"]``, ``/metrics`` and ``GET /debug/engine``; zeroed with
    the other totals."""
    from unionml_tpu.serving.app import ServingApp

    sklearn_model.train(hyperparameters={"max_iter": 500})
    app = ServingApp(sklearn_model)

    def get(path):
        async def run():
            app.startup()
            return await app.server.dispatch_with_headers("GET", path, b"", None)

        return asyncio.run(run())

    batcher = _engine(tiny_gen, max_new=16, **PAGED)
    try:
        _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1], [8, 2, 8]])
        _settled(batcher.engine_log, requests=3, finished=3)
        records = batcher.engine_log.iteration_records()
        per_iteration, total = read(batcher, get)
        assert total == sum(r.table_syncs for r in records) > 0
        if per_iteration is not None:
            assert per_iteration == [r.table_syncs for r in records]
        for r in records:
            assert r.table_syncs <= 2
            assert (r.table_syncs == 0) == (r.blocks_grown == 0 and r.finished == 0)
        batcher.engine_log.clear()
        assert read(batcher, get)[1] == 0
    finally:
        batcher.close()


@pytest.mark.parametrize(
    "read", [_read_iteration_records, _read_stats_loop, _read_prometheus, _read_debug_engine],
    ids=["iteration_records", "stats_loop", "prometheus", "debug_engine"],
)
def test_admit_dispatches_is_served_wherever_the_loop_is_read(tiny_gen, sklearn_model, read):
    """How many programs and transfers the admit phase handed the runtime: per
    iteration in the record (0 in an iteration that admitted nothing; an
    admission is its set-up, its chunks, its first token and its paste), cumulative in
    ``stats()["loop"]``, ``/metrics`` and ``GET /debug/engine``; zeroed with
    the other totals."""
    from unionml_tpu.serving.app import ServingApp

    sklearn_model.train(hyperparameters={"max_iter": 500})
    app = ServingApp(sklearn_model)

    def get(path):
        async def run():
            app.startup()
            return await app.server.dispatch_with_headers("GET", path, b"", None)

        return asyncio.run(run())

    batcher = _engine(tiny_gen, max_new=16, **PAGED)
    try:
        _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1], [8, 2, 8]])
        _settled(batcher.engine_log, requests=3, finished=3)
        records = batcher.engine_log.iteration_records()
        per_iteration, total = read(batcher, get, "admit_dispatches")
        assert total == sum(r.admit_dispatches for r in records) == 3 * 3 + batcher.stats()["prefill"]["chunks"]
        if per_iteration is not None:
            assert per_iteration == [r.admit_dispatches for r in records]
        for r in records:
            assert (r.admit_dispatches == 0) == (r.admitted == 0 and r.prefill_tokens == 0)
        batcher.engine_log.clear()
        assert read(batcher, get, "admit_dispatches")[1] == 0
    finally:
        batcher.close()


@pytest.mark.parametrize("kind,want", [(None, {"pairs": 12, "max_load": 7}), ("decode", {"pairs": 5, "max_load": 3}), ("absent", {"pairs": 0, "max_load": 0})])
def test_model_counters_add_up_by_kind_and_max_names_keep_the_largest(kind, want):
    """What a counting model reports per dispatch: sums, except ``max_*`` names; by kind of dispatch and over
    all; served by ``snapshot`` (``GET /debug/engine``) and zeroed with the totals."""
    from unionml_tpu.observability.engine_log import EngineLog

    log = EngineLog()
    assert "model_counters" not in log.snapshot(0)  # a model that counts nothing adds no key
    log.count("decode", ("pairs", "max_load"), [2, 3])
    log.count("decode", ("pairs", "max_load"), [3, 1])
    log.count("prefill", ("pairs", "max_load"), [7, 7])
    assert log.counted(("pairs", "max_load"), kind) == want
    assert log.snapshot(0)["model_counters"] == {"decode": {"pairs": 5, "max_load": 3}, "prefill": {"pairs": 7, "max_load": 7}}
    log.clear()
    assert log.counted(("pairs",)) == {"pairs": 0} and "model_counters" not in log.snapshot(0)
