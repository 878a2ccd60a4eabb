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
  ``GET /debug/engine`` serves them;
- every program the engine thread hands the runtime is tallied by name
  (``dispatched``; those inside ``admit`` are ``admit_dispatches``), every wait
  for a device result by what was waited for (``wait_s``, which sums to the
  ``fetch`` phase), and the seconds the device had nothing of the engine's to
  run are charged to the phase they fell in (``starved_s``);
- a pass of ``SLOW_ITERATION_S`` or more keeps its evidence in a ring of its
  own and is logged once; the log counts backend compiles.
"""

import asyncio
import dataclasses
import gc
import json
import logging
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
    SLOW_ITERATION_S,
    SPAN_PREFIX,
    WAITS,
    EngineLog,
    RequestRecord,
    engine_logs,
    register_engine_log,
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


def _settled(log, iterations=1, requests=0, finished=0, admitted=0, timeout=30.0):
    """The engine thread records an iteration just after its consumers see the
    last token: wait for the log to hold what the test is about to read."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if (
            log.totals()["iterations"] >= iterations
            and len(log.request_records()) >= requests
            and sum(record.finished for record in log.iteration_records()) >= finished
            and sum(record.admitted for record in log.iteration_records()) >= admitted
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
    log.prefill_call(8)
    log.prefill_call(8)
    log.prefill_call()  # a monolithic prefill: a call, no width
    with log.phase("admit"):
        for _ in range(7):
            log.dispatch("step", int)  # admit_dispatches is what was dispatched inside admit, nothing bumped by hand
    log.dispatch("_sync_impl", int)  # outside admit: in ``dispatched`` alone
    log.end()
    (record,) = log.iteration_records()
    assert record.index == 0 and record.start == 111.0
    assert dict(zip(PHASES, record.phase_s)) == {
        "schedule": 1.0, "admit": 2.0, "grow": 0.25, "dispatch": 0.25, "fetch": 4.0, "emit": 0.5,
    }
    assert sum(record.phase_s) == 119.0 - 111.0  # the pass's wall time, idle not in it
    assert (record.rows, record.prefill_tokens, record.admitted, record.finished, record.blocks_grown) == (3, 16, 1, 2, 4)
    assert record.table_syncs == 2 and record.admit_dispatches == 7
    assert dict(record.dispatched) == {"step": 7, "_sync_impl": 1}
    # appended, with defaults: readers that build a record by position keep their ten
    assert record._fields[8:10] == ("table_syncs", "admit_dispatches")
    assert record._fields[10:] == ("dispatched", "wait_s", "wait_copy_s", "starved_s", "prefill_calls")
    assert len(record._field_defaults) == 5
    assert record.prefill_calls == 3 and log.prefill_widths() == {8: 2} and log.prefill_calls == 0
    totals = log.totals()
    assert totals["iterations"] == 1 and totals["idle_s"] == 11.0
    assert totals["phase_s"]["fetch"] == 4.0 and totals["table_syncs"] == 2 and totals["admit_dispatches"] == 7
    assert log.rows == log.prefill_tokens == log.admitted == log.finished == log.blocks_grown == log.table_syncs == 0
    log.begin()
    with log.phase("admit"):
        for _ in range(3):
            log.dispatch("step", int)
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
    admission is its set-up, its chunks, its first token and its paste; the
    engine's first also builds the carry), cumulative in
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
        # + 1: this engine's first admission also builds the carry (``init_carry``, once a life; ``warmup()``
        # absorbs it in a served engine)
        assert total == sum(r.admit_dispatches for r in records) == 3 * 3 + batcher.stats()["prefill"]["chunks"] + 1
        assert sum(r.dispatched.get("init_carry", 0) for r in records) == 1
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


# ------------------------------------------------------------------ what the engine hands its device, and waits for


def _wait_plain(batcher):
    _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1]])
    return {"decode", "first_token"}, dict(requests=2, finished=2)


def _wait_logprobs(batcher):
    """The first token's log-probability is priced, and read, before the token:
    the admission's wait falls under ``first_logprob``."""
    _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1]], logprobs=True)
    return {"decode", "first_token", "first_logprob"}, dict(requests=2, finished=2)


def _wait_speculative(batcher):
    _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1]])
    return {"spec", "first_token"}, dict(requests=2, finished=2)


def _wait_export(batcher):
    stream = batcher.submit([3, 14, 15, 92, 6], export_handoff=True)
    assert len(_drain(stream)) == 1 and stream.handoff is not None
    return {"export"}, dict(requests=1, admitted=1)  # the record of the pass that exported, not only the request's


@pytest.mark.parametrize(
    "drive,kwargs",
    [(_wait_plain, PAGED), (_wait_logprobs, PAGED), (_wait_speculative, dict(slots=2, decode_chunk=4, draft=True)), (_wait_export, PAGED)],
    ids=["plain", "logprobs", "speculative", "export"],
)
def test_every_wait_is_named_and_the_waits_sum_to_the_fetch_phase(tiny_gen, drive, kwargs):
    batcher = _engine(tiny_gen, **kwargs)
    try:
        kinds, settled = drive(batcher)
        log = batcher.engine_log
        _settled(log, **settled)
        records = log.iteration_records()
        fetch = PHASES.index("fetch")
        for record in records:
            assert len(record.wait_s) == len(record.wait_copy_s) == len(WAITS)
            # a wait starts and ends on the phase's own stamps: the sum is the phase, to the float's last digits
            assert sum(record.wait_s) == pytest.approx(record.phase_s[fetch], abs=1e-9)
            assert all(0.0 <= copy <= wait for copy, wait in zip(record.wait_copy_s, record.wait_s))
        waited = {kind for record in records for kind, seconds in zip(WAITS, record.wait_s) if seconds > 0.0}
        assert waited == kinds
        totals = log.totals()
        assert set(totals["wait_s"]) == set(totals["wait_copy_s"]) == set(WAITS)
        assert sum(totals["wait_s"].values()) == pytest.approx(totals["phase_s"]["fetch"], abs=1e-6)
        rendered = records[-1].render()
        assert set(rendered["wait_s"]) == set(WAITS) and set(rendered["starved_s"]) == set(PHASES)
        json.dumps(rendered)  # what ``GET /debug/engine`` serves is plain data
    finally:
        batcher.close()


def test_dispatched_by_name_is_what_a_counting_stub_saw(tiny_gen, monkeypatch):
    """Every program the engine thread hands the runtime, under the name a device
    trace prints for it: the tally equals the calls counted around the jitted
    programs themselves, and ``admit_dispatches`` is what was dispatched inside
    ``admit``: nothing is bumped by hand."""
    batcher = _engine(tiny_gen, max_new=16, **PAGED)
    try:
        batcher.warmup()
        _run(batcher, [[5, 3, 1]], logprobs=True)  # builds the lazily jitted log-probability program
        _settled(batcher.engine_log, requests=1)
        seen = {}

        def counting(name, fn):
            def call(*args, **kwargs):
                seen[name] = seen.get(name, 0) + 1
                return fn(*args, **kwargs)

            return call

        gen = batcher.gen
        for owner, attr, name in [
            (gen, "_decode", "decode_steps"), (gen, "_prefill_chunk", "prefill_chunk"), (gen, "_first_token", "first_token"),
            (batcher, "_setup_fn", "admit_setup"), (batcher, "_cached_setup_fn", "admit_setup_cached"),
            (batcher, "_paged_admit_fn", "_paged_admit_impl"), (batcher, "_sync_fn", "_sync_impl"), (batcher, "_lp0_fn", "impl"),
        ]:
            monkeypatch.setattr(owner, attr, counting(name, getattr(owner, attr)))
        log = batcher.engine_log
        log.clear()
        before = batcher.stats()
        shared = list(range(3, 21))
        _run(batcher, [shared + [40], [27, 1], [8, 2, 8]], logprobs=True)
        _run(batcher, [shared + [41]], logprobs=True)  # a radix hit: its set-up gathers the row
        _settled(log, requests=4, finished=4)
        records = log.iteration_records()
        dispatched = {}
        for record in records:
            for name, n in record.dispatched.items():
                dispatched[name] = dispatched.get(name, 0) + n
        assert dispatched == seen == log.totals()["dispatched"]
        assert seen["admit_setup_cached"] == 1 and seen["impl"] == seen["first_token"] == seen["_paged_admit_impl"] == 4
        assert seen["prefill_chunk"] == batcher.stats()["prefill"]["chunks"] - before["prefill"]["chunks"]
        assert seen["decode_steps"] == batcher.stats()["decode_dispatches"] - before["decode_dispatches"]
        assert seen["_sync_impl"] == sum(r.table_syncs for r in records)
        outside_admit = ("decode_steps", "_sync_impl")
        for record in records:
            assert record.admit_dispatches == sum(n for name, n in record.dispatched.items() if name not in outside_admit)
        assert log.totals()["admit_dispatches"] == sum(n for name, n in seen.items() if name not in outside_admit) == 4 * 4 + seen["prefill_chunk"]
    finally:
        batcher.close()


class _Output:
    """What a dispatch returned, as the log sees it: ready when the test says so."""

    nbytes = 4

    def __init__(self):
        self.ready = False
        self.asked = 0

    def is_ready(self):
        self.asked += 1
        return self.ready


def test_starved_seconds_are_charged_to_the_phase_in_which_the_device_ran_dry(monkeypatch):
    """An ``EngineLog`` driven by hand on a scripted clock: nothing is charged
    while the newest output is in flight; from the switch at which it is seen
    ready to the next dispatch every second is charged to the phase it fell
    in; a wait for work is not starvation; the pass's starved seconds never
    exceed its wall time."""
    clock = _Clock()
    monkeypatch.setattr(engine_log_mod.time, "monotonic", clock)
    log = EngineLog()
    log.begin()  # 100: schedule
    first, pool = _Output(), _Output()
    pool.nbytes = 1 << 20
    with log.phase("dispatch"):
        assert log.dispatch("decode_steps", lambda: (pool, {"done": first})) == (pool, {"done": first})
        clock.now = 101.0
    assert first.asked >= 1 and pool.asked == 0  # the smallest leaf is the one asked
    with log.phase("emit"):  # 101 .. 104: still in flight
        clock.now = 104.0
    clock.now = 105.0
    first.ready = True  # the device ran dry some time before the host looks, at 105
    with log.phase("admit"):  # seen at 105: from here on, starved
        clock.now = 107.0
        with log.phase("fetch"):  # a nested phase takes its own share
            clock.now = 107.5
        clock.now = 108.0
        second = _Output()
        log.dispatch("prefill_chunk", lambda: second)  # 108: the device has work again
        asked = first.asked
        clock.now = 110.0
    assert first.asked == asked  # dropped at the next dispatch, never asked again
    with log.phase("grow"):  # 110 .. 112, in flight: nothing
        clock.now = 112.0
    second.ready = True
    with log.phase("emit"):  # seen at 112
        clock.now = 113.0
    log.end()  # schedule 113 .. 113
    (record,) = log.iteration_records()
    assert dict(zip(PHASES, record.starved_s)) == {
        "schedule": 0.0, "admit": 2.5, "grow": 0.0, "dispatch": 0.0, "fetch": 0.5, "emit": 1.0,
    }
    assert sum(record.starved_s) <= sum(record.phase_s) == 13.0
    assert all(starved <= spent for starved, spent in zip(record.starved_s, record.phase_s))
    assert dict(record.dispatched) == {"decode_steps": 1, "prefill_chunk": 1} and record.admit_dispatches == 1
    # the device is still dry when the engine finds nothing to do: the wait is idle, not starvation
    log.begin()  # 113
    clock.now = 114.0
    log.wait()
    clock.now = 124.0
    log.begin()  # 124: work again
    clock.now = 125.0
    log.dispatch("admit_setup", lambda: 7)  # an output with no array: the queue is not known to be empty after it
    clock.now = 130.0
    log.end()
    second_pass = log.iteration_records()[-1]
    assert dict(zip(PHASES, second_pass.starved_s))["schedule"] == 1.0 and sum(second_pass.starved_s) == 1.0
    assert log.totals()["starved_s"]["admit"] == 2.5 and log.totals()["starved_s"]["schedule"] == 1.0
    assert log.totals()["idle_s"] == 11.0
    log.clear()
    assert sum(log.totals()["starved_s"].values()) == 0.0 and log.totals()["dispatched"] == {}


class _Late:
    """A device result that arrives late: ``block_until_ready`` holds the caller."""

    def __init__(self, inner, seconds):
        self._inner, self._seconds = inner, seconds
        self.nbytes = inner.nbytes

    def is_ready(self):
        return self._inner.is_ready()

    def block_until_ready(self):
        seconds, self._seconds = self._seconds, 0.0
        time.sleep(seconds)
        self._inner.block_until_ready()
        return self

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self._inner)


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.records = []

    def emit(self, record):
        self.records.append(record)


def test_a_slow_iteration_keeps_its_evidence(tiny_gen, sklearn_model, monkeypatch):
    """A decode result that comes 1.1 s late: one ``SlowIteration`` with the wait
    named and split, the CPU seconds, the compiles and the iterations before it;
    one WARNING line; served by ``GET /debug/engine``; still there after 5,000
    further iterations turned the iteration ring over; emptied by ``clear()``."""
    from unionml_tpu._logging import logger
    from unionml_tpu.serving.app import ServingApp

    sklearn_model.train(hyperparameters={"max_iter": 500})
    app = ServingApp(sklearn_model)

    def get(path):
        async def run():
            app.startup()
            return await app.server.dispatch_with_headers("GET", path, b"", None)

        return asyncio.run(run())

    lines = _Lines()
    logger.addHandler(lines)
    batcher = _engine(tiny_gen, max_new=16, slots=2, decode_chunk=4)
    try:
        batcher.warmup()  # its compiles are not this test's; its clear() empties the ring as it empties the others
        assert batcher.engine_log.slow_iterations() == [] and batcher.stats()["loop"]["slow_iterations"] == 0
        lines.records.clear()
        inner, late = batcher.gen._decode, [1.1]

        def decode(*args, **kwargs):
            toks, lps, carry = inner(*args, **kwargs)
            return _Late(toks, late.pop() if late else 0.0), lps, carry

        monkeypatch.setattr(batcher.gen, "_decode", decode)
        log = batcher.engine_log
        _run(batcher, [[3, 14, 15, 92, 6], [27, 1]])
        _settled(log, requests=2, finished=2)
        (slow,) = log.slow_iterations()
        assert sum(slow.iteration.phase_s) >= SLOW_ITERATION_S
        assert slow.iteration.wait_s[WAITS.index("decode")] >= 1.1
        waits = [event for event in slow.events if event[3] is not None]
        dispatches = [event for event in slow.events if event[3] is None]
        assert [event[0] for event in waits if event[2] >= 1.1] == ["decode"]  # late, not slow to copy
        assert all(event[3] < 0.5 for event in waits)
        assert "decode_steps" in [event[0] for event in dispatches]
        assert [event[1] for event in slow.events] == sorted(event[1] for event in slow.events)  # in order, from the pass's start
        assert 0.0 <= slow.thread_cpu_s <= slow.process_cpu_s + 0.05 and slow.thread_cpu_s < 1.0  # it slept: blocked, not busy
        assert slow.compiles == 0 and slow.compile_s == 0.0
        assert slow.memory_before == slow.memory_after == {}  # a CPU's allocator reports nothing
        assert slow.recent["iterations"] == slow.iteration.index  # every iteration before it, fewer than 64
        assert batcher.stats()["loop"]["slow_iterations"] == 1
        warned = [r for r in lines.records if "slow_iteration" in r.getMessage()]
        assert [r.levelno for r in warned] == [logging.WARNING]
        said = json.loads(warned[0].getMessage())["slow_iteration"]
        assert said["iteration"]["index"] == slow.iteration.index
        assert max(said["events"], key=lambda e: e.get("ready_s", 0.0))["wait"] == "decode"
        batcher.close()
        batcher._thread.join(timeout=30)
        assert not batcher._thread.is_alive()
        for _ in range(5000):  # the engine thread is gone: the test drives its log
            log.begin()
            log.end()
        assert len(log.iteration_records()) == log.capacity < log.totals()["iterations"]
        assert slow.iteration.index not in [r.index for r in log.iteration_records()]  # the iteration ring has turned over
        assert log.slow_iterations() == [slow]
        status, payload, _, _ = get("/debug/engine?limit=1")
        assert status == 200
        mine = next(e for e in payload["engines"] if e["slow_iterations_log"] and e["slow_iterations_log"][0]["iteration"]["start"] == slow.iteration.start)
        assert mine["slow_iterations"] == 1 and mine["slow_iterations_log"][0]["iteration"]["wait_s"]["decode"] >= 1.1
        log.clear()
        assert log.slow_iterations() == [] and log.totals()["slow_iterations"] == 0
        assert len([r for r in lines.records if "slow_iteration" in r.getMessage()]) == 1  # once
    finally:
        logger.removeHandler(lines)
        batcher.close()


def test_a_slow_pass_that_compiled_is_said_not_warned_of(monkeypatch):
    """A cold ``warmup()`` compiles through the loop: a pass made slow by a
    compile is logged at INFO, and the compile is in its record."""
    from unionml_tpu._logging import logger

    clock = _Clock()
    lines = _Lines()
    logger.addHandler(lines)
    try:
        log = EngineLog()
        monkeypatch.setattr(engine_log_mod.time, "monotonic", clock)
        log.begin()
        log.dispatch("fresh", jax.jit(lambda x: x * 3 + 1), jnp.ones((3,)))
        clock.now += 2.0
        log.end()
        (slow,) = log.slow_iterations()
        assert slow.compiles >= 1 and slow.compile_s > 0.0
        (line,) = [r for r in lines.records if "slow_iteration" in r.getMessage()]
        assert line.levelno == logging.INFO
    finally:
        logger.removeHandler(lines)


def test_the_compile_counter_counts_a_fresh_jit_and_not_a_cached_call():
    log = EngineLog()
    fresh = jax.jit(lambda x: x * 5 - 2)
    log.begin()
    log.dispatch("fresh", fresh, jnp.ones((3,)))
    log.end()
    first = log.totals()
    assert first["compiles"] >= 1 and first["compile_s"] > 0.0
    log.begin()
    log.dispatch("fresh", fresh, jnp.ones((3,)))  # the same shapes: the program is there
    log.end()
    assert log.totals()["compiles"] == first["compiles"] and log.totals()["compile_s"] == first["compile_s"]
    fresh(jnp.ones((4,)))  # a compile outside every pass is nobody's iteration
    assert log.totals()["compiles"] == first["compiles"]
    log.clear()
    assert log.totals()["compiles"] == 0 and log.totals()["compile_s"] == 0.0


def test_the_new_loop_leaves_render_as_prometheus(tiny_gen):
    batcher = _engine(tiny_gen, **PAGED)
    try:
        _run(batcher, [[3, 14, 15, 92, 6, 5, 3, 5, 9], [27, 1]], logprobs=True)
        _settled(batcher.engine_log, requests=2, finished=2)
        loop = batcher.stats()["loop"]
        text = render_prometheus({"generation": batcher.stats()})
        samples = dict(line.rsplit(" ", 1) for line in text.splitlines() if not line.startswith("#"))
        prefix = "unionml_tpu_generation_loop_"
        for kind in WAITS:
            assert float(samples[f"{prefix}wait_s_{kind}"]) >= loop["wait_s"][kind] >= 0.0
            assert float(samples[f"{prefix}wait_copy_s_{kind}"]) >= 0.0
        for phase in PHASES:
            assert float(samples[f"{prefix}starved_s_{phase}"]) >= loop["starved_s"][phase] >= 0.0
        for name, n in loop["dispatched"].items():
            assert int(samples[f"{prefix}dispatched_{name}"]) >= n > 0
        assert {"decode_steps", "prefill_chunk", "_paged_admit_impl", "init_carry"} <= set(loop["dispatched"])
        assert int(samples[f"{prefix}compiles"]) >= loop["compiles"] > 0  # nothing warmed this engine up
        assert float(samples[f"{prefix}compile_s"]) > 0.0 and int(samples[f"{prefix}slow_iterations"]) >= 0
        assert float(samples[f"{prefix}wait_s_decode"]) > 0.0
    finally:
        batcher.close()
