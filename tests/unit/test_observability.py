"""Observability surface: request ids, traces, the flight recorder, Prometheus
exposition, the /debug endpoints, the profiler hook, and structured logging.

Contracts pinned here (docs/observability.md):

- the request id flows HTTP -> engine -> response and is echoed on EVERY
  response, including 404s, sheds (429/503), and streams;
- with tracing off the hot path allocates no RequestTrace at all (the
  zero-cost-off claim);
- flight-recorder eviction, in-flight -> completed transitions, and the
  /debug/requests filters;
- Prometheus rendering escapes labels and never emits a None-valued series;
- the profiler endpoint rejects overlapping captures (409).
"""

import asyncio
import json
import logging
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from unionml_tpu._logging import JsonFormatter, set_log_format
from unionml_tpu.observability import (
    FlightRecorder,
    Tracer,
    render_prometheus,
)
from unionml_tpu.observability import trace as trace_mod
from unionml_tpu.observability.trace import (
    RequestTrace,
    new_request_id,
    sanitize_request_id,
)
from unionml_tpu.serving.http import HTTPServer
from unionml_tpu.serving.metrics import ServingMetrics
from unionml_tpu.serving.overload import QueueFullError


def _server(enabled=True, capacity=8):
    srv = HTTPServer()
    recorder = FlightRecorder(capacity)
    srv.tracer = Tracer(enabled=enabled, recorder=recorder)
    return srv, recorder


def _dispatch(srv, method, path, body=b"", headers=None):
    return asyncio.run(srv.dispatch_with_headers(method, path, body, headers))


async def _ok(body):
    return 200, {"ok": True}, "application/json"


# ------------------------------------------------------------------ request ids


def test_sanitize_request_id_strips_header_injection():
    assert sanitize_request_id("abc\r\nX-Evil: 1") == "abcX-Evil1"
    assert sanitize_request_id("ok-id_1.2") == "ok-id_1.2"
    assert sanitize_request_id("\r\n") is None
    assert sanitize_request_id("") is None
    assert sanitize_request_id(None) is None
    assert len(sanitize_request_id("x" * 500)) == 128


def test_inbound_request_id_honored_and_echoed():
    srv, recorder = _server()
    srv.route("GET", "/x", _ok)
    status, _, _, extra = _dispatch(srv, "GET", "/x", headers={"x-request-id": "req-42"})
    assert status == 200
    assert extra["X-Request-Id"] == "req-42"
    assert recorder.get("req-42")["status"] == 200


def test_generated_request_id_when_header_missing():
    srv, _ = _server(enabled=False)
    srv.route("GET", "/x", _ok)
    _, _, _, extra = _dispatch(srv, "GET", "/x")
    assert re.fullmatch(r"[0-9a-f]{32}", extra["X-Request-Id"])


def test_request_id_echoed_on_404_and_405():
    srv, _ = _server(enabled=False)
    srv.route("GET", "/x", _ok)
    status, _, _, extra = _dispatch(srv, "GET", "/nope", headers={"x-request-id": "a1"})
    assert (status, extra["X-Request-Id"]) == (404, "a1")
    status, _, _, extra = _dispatch(srv, "POST", "/x", headers={"x-request-id": "a2"})
    assert (status, extra["X-Request-Id"]) == (405, "a2")


def test_request_id_echoed_on_shed_paths():
    """429 (inflight cap / queue full) and 503 (draining) must still echo the
    id — correlating a shed with its client is the whole point."""
    srv, recorder = _server()
    srv.route("GET", "/x", _ok)

    async def full(body):
        raise QueueFullError("downstream queue full")

    srv.route("POST", "/full", full)

    srv.max_inflight = 0
    status, _, _, extra = _dispatch(srv, "GET", "/x", headers={"x-request-id": "shed-1"})
    assert (status, extra["X-Request-Id"]) == (429, "shed-1")
    assert "Retry-After" in extra
    srv.max_inflight = None

    status, _, _, extra = _dispatch(srv, "POST", "/full", headers={"x-request-id": "shed-2"})
    assert (status, extra["X-Request-Id"]) == (429, "shed-2")

    srv.draining = True
    status, _, _, extra = _dispatch(srv, "GET", "/x", headers={"x-request-id": "shed-3"})
    assert (status, extra["X-Request-Id"]) == (503, "shed-3")

    # the sheds were traced, with the reason on the timeline
    for rid, reason in (("shed-1", "inflight_cap"), ("shed-2", "queue_full"), ("shed-3", "draining")):
        snap = recorder.get(rid)
        assert {"event": "http.shed", "reason": reason}.items() <= snap["events"][-1].items()


# ------------------------------------------------------------------ zero-cost off


def test_trace_off_allocates_no_request_traces(monkeypatch):
    """With tracing disabled no RequestTrace is ever constructed — not merely
    unused: the constructor is poisoned and dispatch must still succeed."""

    def boom(self, *a, **k):
        raise AssertionError("RequestTrace allocated with tracing off")

    monkeypatch.setattr(RequestTrace, "__init__", boom)
    srv, recorder = _server(enabled=False)
    srv.route("GET", "/x", _ok)
    status, _, _, extra = _dispatch(srv, "GET", "/x")
    assert status == 200
    assert extra["X-Request-Id"]  # ids still flow — only the timeline is off
    assert len(recorder) == 0 and recorder.inflight_count == 0


def test_engine_sessions_carry_no_trace_when_off():
    from unionml_tpu.serving.continuous import _Session

    assert _Session.__dataclass_fields__["trace"].default is None
    assert trace_mod.current_trace() is None  # no ambient trace outside dispatch


# ------------------------------------------------------------------ trace timelines


def test_trace_events_monotonic_nondecreasing_across_threads():
    trace = RequestTrace("rid", "GET", "/x")
    barrier = threading.Barrier(4)

    def worker(i):
        barrier.wait()
        for j in range(50):
            trace.event("tick", worker=i, j=j)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    offsets = [e["t_ms"] for e in trace.snapshot()["events"]]
    assert offsets == sorted(offsets)
    assert len(offsets) == 200


def test_trace_event_cap_counts_drops():
    trace = RequestTrace("rid", "GET", "/x")
    for i in range(trace_mod._MAX_EVENTS + 7):
        trace.event("e", i=i)
    snap = trace.snapshot()
    assert len(snap["events"]) == trace_mod._MAX_EVENTS
    assert snap["dropped_events"] == 7


def test_trace_finish_idempotent_first_wins():
    trace = RequestTrace("rid", "GET", "/x")
    trace.finish(200)
    trace.finish(500, "late abort")
    assert trace.status == 200 and trace.detail is None


def test_streaming_response_trace_finishes_at_stream_end():
    srv, recorder = _server()

    async def stream(body):
        async def gen():
            yield b"a"
            yield b"bb"

        return 200, gen(), "application/octet-stream"

    srv.route("GET", "/s", stream)

    async def scenario():
        status, payload, _, extra = await srv.dispatch_with_headers(
            "GET", "/s", b"", {"x-request-id": "stream-1"}
        )
        assert recorder.get("stream-1")["in_flight"]  # handler returned, stream open
        chunks = [c async for c in payload]
        return status, chunks

    status, chunks = asyncio.run(scenario())
    assert (status, chunks) == (200, [b"a", b"bb"])
    snap = recorder.get("stream-1")
    assert not snap["in_flight"] and snap["status"] == 200
    sizes = [e["bytes"] for e in snap["events"] if e["event"] == "http.stream_chunk"]
    assert sizes == [1, 2]


# ------------------------------------------------------------------ flight recorder


def _finished_trace(rid, status=200, path="/x"):
    trace = RequestTrace(rid, "GET", path)
    trace.finish(status)
    return trace


def test_flight_recorder_inflight_to_completed_transition():
    recorder = FlightRecorder(4)
    trace = RequestTrace("r1", "GET", "/x")
    recorder.start(trace)
    assert recorder.inflight_count == 1 and len(recorder) == 0
    assert recorder.get("r1")["in_flight"]
    trace.finish(200)
    recorder.complete(trace)
    assert recorder.inflight_count == 0 and len(recorder) == 1
    assert recorder.get("r1")["in_flight"] is False


def test_flight_recorder_evicts_oldest_beyond_capacity():
    recorder = FlightRecorder(3)
    for i in range(5):
        recorder.complete(_finished_trace(f"r{i}"))
    assert len(recorder) == 3
    snap = recorder.snapshot()
    assert [s["request_id"] for s in snap["completed"]] == ["r4", "r3", "r2"]
    assert recorder.get("r0") is None  # evicted


def test_flight_recorder_get_prefers_live_then_newest():
    recorder = FlightRecorder(4)
    recorder.complete(_finished_trace("dup", status=500))
    recorder.complete(_finished_trace("dup", status=200))
    assert recorder.get("dup")["status"] == 200  # newest completed wins
    live = RequestTrace("dup", "GET", "/x")
    recorder.start(live)
    assert recorder.get("dup")["in_flight"]  # the live view wins over the ring


def test_flight_recorder_snapshot_filters_route_status_limit():
    recorder = FlightRecorder(8)
    recorder.complete(_finished_trace("a", status=200, path="/predict"))
    recorder.complete(_finished_trace("b", status=503, path="/predict"))
    recorder.complete(_finished_trace("c", status=200, path="/health"))
    by_route = recorder.snapshot(route="/predict")
    assert {s["request_id"] for s in by_route["completed"]} == {"a", "b"}
    by_status = recorder.snapshot(status=503)
    assert [s["request_id"] for s in by_status["completed"]] == ["b"]
    both = recorder.snapshot(route="/predict", status=200)
    assert [s["request_id"] for s in both["completed"]] == ["a"]
    limited = recorder.snapshot(limit=1)
    assert len(limited["completed"]) == 1


def test_flight_recorder_dump_writes_timelines_to_log():
    # the package logger has propagate=False, so capture with our own handler
    from unionml_tpu._logging import logger

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture(level=logging.WARNING)
    logger.addHandler(handler)
    try:
        recorder = FlightRecorder(4)
        recorder.complete(_finished_trace("dumped"))
        recorder.dump("unit test")
    finally:
        logger.removeHandler(handler)
    text = "\n".join(records)
    assert "unit test" in text and "dumped" in text


# ------------------------------------------------------------------ prometheus

#: the text-exposition grammar: a sample line is name{labels} value, where the
#: value is a float/int literal (Prometheus also allows +Inf/-Inf/NaN)
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\\n])*"(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\\n])*")*\})?'
    r" (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]?Inf|NaN)$"
)
_TYPE_LINE = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|summary|histogram|untyped)$")


def _assert_parses(text):
    seen_sample = False
    for line in text.rstrip("\n").splitlines():
        if not line:
            continue
        assert _TYPE_LINE.match(line) or _SAMPLE.match(line), f"bad exposition line: {line!r}"
        seen_sample = seen_sample or bool(_SAMPLE.match(line))
    return seen_sample


def test_prometheus_renders_real_metrics_snapshot_under_grammar():
    metrics = ServingMetrics()
    for i in range(10):
        metrics.record("POST /predict", 200, 0.001 * (i + 1))
    metrics.record("GET /health", 500, 0.002)
    metrics.inc("shed_inflight")
    metrics.observe_queue_wait("batcher", 0.003)
    text = render_prometheus(metrics.snapshot())
    assert _assert_parses(text)
    assert 'unionml_tpu_route_requests_total{route="POST /predict"} 10' in text
    assert 'unionml_tpu_overload_total{counter="shed_inflight"} 1' in text
    assert 'quantile="0.99"' in text


def test_prometheus_escapes_label_values():
    metrics = ServingMetrics()
    metrics.record('GET /evil"\\\n', 200, 0.001)
    text = render_prometheus(metrics.snapshot())
    assert _assert_parses(text)
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    # no raw newline survives inside any label value
    for line in text.splitlines():
        assert _TYPE_LINE.match(line) or _SAMPLE.match(line)


def test_prometheus_skips_none_and_string_leaves():
    snapshot = {
        "requests_total": 3,
        "errors_total": 0,
        "gauges": {"replicas": None, "name": "llama", "active": True},
        "generation": {"ttft_ms": {"window": 0}},
    }
    text = render_prometheus(snapshot)
    assert _assert_parses(text)
    assert "None" not in text and "llama" not in text
    assert "unionml_tpu_gauges_active 1" in text
    assert "unionml_tpu_generation_ttft_count 0" in text


def test_prometheus_nested_sections_flatten_with_index_labels():
    snapshot = {
        "requests_total": 0,
        "errors_total": 0,
        "generation": {"per_replica": [{"resident": 1}, {"resident": 2}]},
    }
    text = render_prometheus(snapshot)
    assert 'unionml_tpu_generation_per_replica_resident{index="0"} 1' in text
    assert 'unionml_tpu_generation_per_replica_resident{index="1"} 2' in text


def test_prometheus_renders_prefix_cache_section_without_none_gauges():
    # the radix prefix cache's stats() section (serving/continuous.py) must
    # reach the exposition as plain numeric series — every value an int by
    # contract, never a None-valued sample; grammar-checked like the rest
    snapshot = {
        "requests_total": 0,
        "errors_total": 0,
        "generation": {
            "prefix_cache": {
                "hits": 4, "misses": 1, "tokens_avoided": 96, "cow_copies": 1,
                "evictions": 0, "evicted_blocks": 0, "cached_blocks": 7,
                "cached_tokens": 56, "pinned_blocks": 2, "nodes": 3,
            }
        },
    }
    text = render_prometheus(snapshot)
    assert _assert_parses(text)
    assert "None" not in text
    assert "unionml_tpu_generation_prefix_cache_hits 4" in text
    assert "unionml_tpu_generation_prefix_cache_tokens_avoided 96" in text
    assert "unionml_tpu_generation_prefix_cache_pinned_blocks 2" in text


def test_prometheus_renders_quantized_pool_gauges_without_none():
    # the int8-aware byte gauges (serving/continuous.py stats): kv_blocks
    # carries block_bytes/used_bytes plus a STRING dtype label (skipped by the
    # exposition, never rendered as a broken sample), and prefix_cache carries
    # cached_bytes — every numeric leaf an int, never None
    snapshot = {
        "requests_total": 0,
        "errors_total": 0,
        "generation": {
            "kv_blocks": {
                "total": 38, "used": 12, "shared_prefix": 0, "block_size": 16,
                "preemptions": 0, "block_bytes": 8704, "used_bytes": 104448,
                "kv_dtype": "int8",
            },
            "prefix_cache": {
                "hits": 4, "misses": 1, "tokens_avoided": 96, "cow_copies": 1,
                "evictions": 0, "evicted_blocks": 0, "cached_blocks": 7,
                "cached_tokens": 56, "cached_bytes": 60928, "pinned_blocks": 2,
                "nodes": 3,
            },
        },
    }
    text = render_prometheus(snapshot)
    assert _assert_parses(text)
    assert "None" not in text
    assert "unionml_tpu_generation_kv_blocks_block_bytes 8704" in text
    assert "unionml_tpu_generation_kv_blocks_used_bytes 104448" in text
    assert "unionml_tpu_generation_prefix_cache_cached_bytes 60928" in text
    # the dtype label is a string leaf: skipped, not emitted as a series
    assert "kv_dtype" not in text


# ------------------------------------------------------------------ serving app surface


@pytest.fixture
def traced_app(sklearn_model):
    sklearn_model.train(hyperparameters={"max_iter": 500})
    from unionml_tpu.serving.app import ServingApp

    app = ServingApp(sklearn_model)
    app.configure_observability(trace=True, flight_recorder_size=16, access_log=False)
    return app


def _app_dispatch(app, method, path, body=b"", headers=None):
    async def run():
        app.startup()
        return await app.server.dispatch_with_headers(method, path, body, headers)

    return asyncio.run(run())


def test_metrics_prometheus_format_negotiation(traced_app):
    status, payload, content_type, _ = _app_dispatch(traced_app, "GET", "/health")
    assert status == 200
    status, text, content_type, _ = _app_dispatch(traced_app, "GET", "/metrics?format=prometheus")
    assert status == 200
    assert content_type.startswith("text/plain")
    assert _assert_parses(text)
    status, payload, content_type, _ = _app_dispatch(traced_app, "GET", "/metrics")
    assert status == 200 and content_type == "application/json"
    status, payload, _, _ = _app_dispatch(traced_app, "GET", "/metrics?format=xml")
    assert status == 400 and "unknown metrics format" in payload["detail"]


def test_debug_requests_lists_and_filters(traced_app):
    _app_dispatch(traced_app, "GET", "/health", headers={"x-request-id": "h-1"})
    _app_dispatch(traced_app, "GET", "/nope", headers={"x-request-id": "n-1"})
    status, payload, _, _ = _app_dispatch(traced_app, "GET", "/debug/requests")
    assert status == 200 and payload["tracing"] is True
    ids = {s["request_id"] for s in payload["completed"]}
    assert {"h-1", "n-1"} <= ids
    status, payload, _, _ = _app_dispatch(traced_app, "GET", "/debug/requests?route=/health&status=200")
    assert {s["request_id"] for s in payload["completed"]} == {"h-1"}
    status, payload, _, _ = _app_dispatch(traced_app, "GET", "/debug/requests?status=potato")
    assert status == 400
    status, payload, _, _ = _app_dispatch(traced_app, "GET", "/debug/requests?limit=zero")
    assert status == 400


def test_debug_request_by_id_timeline_roundtrip(traced_app):
    _app_dispatch(traced_app, "GET", "/health", headers={"x-request-id": "find-me"})
    status, payload, _, _ = _app_dispatch(traced_app, "GET", "/debug/requests/find-me")
    assert status == 200
    assert payload["request_id"] == "find-me" and payload["route"] == "GET /health"
    assert payload["events"][0]["event"] == "http.accept"
    status, payload, _, _ = _app_dispatch(traced_app, "GET", "/debug/requests/who")
    assert status == 404


def test_debug_request_by_id_hints_when_tracing_off(sklearn_model):
    sklearn_model.train(hyperparameters={"max_iter": 500})
    from unionml_tpu.serving.app import ServingApp

    app = ServingApp(sklearn_model)
    app.configure_observability(trace=False)
    _app_dispatch(app, "GET", "/health", headers={"x-request-id": "gone"})
    status, payload, _, _ = _app_dispatch(app, "GET", "/debug/requests/gone")
    assert status == 404 and "tracing is off" in payload["detail"]


def test_profile_endpoint_requires_configuration(traced_app):
    traced_app.profile_dir = None
    status, payload, _, _ = _app_dispatch(traced_app, "POST", "/debug/profile")
    assert status == 400 and "--profile-dir" in payload["detail"]


def test_profile_endpoint_rejects_overlapping_captures(traced_app, tmp_path, monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: calls.append(("stop", None)))
    traced_app.profile_dir = str(tmp_path)

    async def overlap():
        traced_app.startup()
        body = json.dumps({"duration_ms": 150}).encode()
        return await asyncio.gather(
            traced_app.server.dispatch_with_headers("POST", "/debug/profile", body),
            traced_app.server.dispatch_with_headers("POST", "/debug/profile", body),
        )

    results = asyncio.run(overlap())
    statuses = sorted(r[0] for r in results)
    assert statuses == [200, 409]
    assert calls == [("start", str(tmp_path)), ("stop", None)]  # exactly one capture
    ok = next(r for r in results if r[0] == 200)
    assert ok[1]["duration_ms"] == 150.0

    status, payload, _, _ = _app_dispatch(
        traced_app, "POST", "/debug/profile", json.dumps({"duration_ms": -5}).encode()
    )
    assert status == 400
    status, payload, _, _ = _app_dispatch(
        traced_app, "POST", "/debug/profile", json.dumps({"duration_ms": "soon"}).encode()
    )
    assert status == 400


# ------------------------------------------------------------------ structured logging


def test_loglevel_garbage_falls_back_to_info():
    """The crash-at-import regression: UNIONML_TPU_LOGLEVEL=garbage must warn
    and degrade, never raise before app code runs."""
    code = (
        "from unionml_tpu._logging import logger; "
        "import logging; print(logger.level == logging.INFO)"
    )
    env = {**os.environ, "UNIONML_TPU_LOGLEVEL": "garbage"}
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=60
    )
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "True"
    assert "invalid UNIONML_TPU_LOGLEVEL" in proc.stderr


def test_json_formatter_carries_request_id():
    record = logging.LogRecord("unionml_tpu", logging.INFO, __file__, 1, "served %s", ("x",), None)
    line = json.loads(JsonFormatter().format(record))
    assert line["message"] == "served x" and "request_id" not in line

    tokens = trace_mod.bind("corr-1")
    try:
        line = json.loads(JsonFormatter().format(record))
        assert line["request_id"] == "corr-1"
    finally:
        trace_mod.unbind(tokens)


def test_log_format_env_selects_json(tmp_path):
    code = (
        "from unionml_tpu._logging import logger; logger.warning('hello json')"
    )
    env = {**os.environ, "UNIONML_TPU_LOG_FORMAT": "json"}
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=60
    )
    line = json.loads(proc.stderr.strip().splitlines()[-1])
    assert line["level"] == "WARNING" and line["message"] == "hello json"


def test_set_log_format_toggles_formatter():
    from unionml_tpu._logging import logger

    set_log_format("json")
    try:
        assert all(isinstance(h.formatter, JsonFormatter) for h in logger.handlers)
    finally:
        set_log_format("text")
    assert not any(isinstance(h.formatter, JsonFormatter) for h in logger.handlers)


# ------------------------------------------------- HTTP -> engine propagation


@pytest.fixture(scope="module")
def tiny_gen():
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import Llama, LlamaConfig

    config = LlamaConfig.tiny(
        vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _engine(tiny_gen, **kwargs):
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.serving import ContinuousBatcher

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    return ContinuousBatcher(Generator(module, params, cfg), **kwargs)


def _engine_server(batcher):
    """An HTTP server whose POST /gen submits the JSON prompt to the engine
    and drains the stream off-loop — the serving app's stream-predictor shape,
    minus the model plumbing."""
    srv, recorder = _server(enabled=True)

    async def gen_handler(body):
        prompt = json.loads(body or b"{}").get("prompt", [3, 1, 4])
        loop = asyncio.get_running_loop()
        stream = batcher.submit(prompt)  # handler context: trace is ambient here
        tokens = await loop.run_in_executor(
            None, lambda: [int(t) for c in stream for t in np.asarray(c).ravel()]
        )
        return 200, {"tokens": tokens}, "application/json"

    srv.route("POST", "/gen", gen_handler)
    return srv, recorder


def test_request_id_propagates_http_to_engine_timeline(tiny_gen):
    batcher = _engine(tiny_gen, slots=2, decode_chunk=4)
    try:
        srv, recorder = _engine_server(batcher)
        body = json.dumps({"prompt": [3, 14, 15, 92, 6]}).encode()
        status, payload, _, extra = _dispatch(
            srv, "POST", "/gen", body, {"x-request-id": "gen-1"}
        )
        assert status == 200 and extra["X-Request-Id"] == "gen-1"
        assert payload["tokens"]
        snap = recorder.get("gen-1")
        names = [e["event"] for e in snap["events"]]
        # the full lifecycle, in causal order, on ONE timeline
        for required in (
            "http.accept", "engine.submit", "engine.admission_start",
            "engine.prefill", "engine.first_token", "engine.emit", "engine.finish",
        ):
            assert required in names, f"missing {required} in {names}"
        assert names.index("engine.submit") < names.index("engine.admission_start")
        assert names.index("engine.first_token") <= names.index("engine.emit")
        offsets = [e["t_ms"] for e in snap["events"]]
        assert offsets == sorted(offsets)  # monotonic-clock offsets, one clock
        admission = next(e for e in snap["events"] if e["event"] == "engine.admission_start")
        assert admission["queue_wait_ms"] >= 0
        emitted = sum(e["tokens"] for e in snap["events"] if e["event"] == "engine.emit")
        assert emitted == len(payload["tokens"])
    finally:
        batcher.close()


def test_chunked_prefill_records_every_chunk(tiny_gen):
    batcher = _engine(tiny_gen, slots=1, decode_chunk=4, admit_chunk=8)
    try:
        srv, recorder = _engine_server(batcher)
        body = json.dumps({"prompt": list(range(1, 15))}).encode()  # aligned to 16 -> 2 chunks
        status, _, _, _ = _dispatch(srv, "POST", "/gen", body, {"x-request-id": "chunked"})
        assert status == 200
        chunks = [
            e for e in recorder.get("chunked")["events"] if e["event"] == "engine.prefill_chunk"
        ]
        assert [c["pos"] for c in chunks] == [8, 16]
        assert all(c["chunk"] == 8 and c["width"] == 16 for c in chunks)
    finally:
        batcher.close()


def test_engine_shed_paths_trace_and_echo_request_id(tiny_gen):
    batcher = _engine(tiny_gen, slots=1, max_waiting=1)
    try:
        srv, recorder = _engine_server(batcher)
        # occupy the only slot, then fill the 1-deep waiting queue: the HTTP
        # submit must shed 429 with the id echoed and both layers traced
        occupant = batcher.submit([5, 5, 5])
        next(iter(occupant))
        waiter = batcher.submit([6, 6])
        status, _, _, extra = _dispatch(srv, "POST", "/gen", b"{}", {"x-request-id": "shed-q"})
        assert (status, extra["X-Request-Id"]) == (429, "shed-q")
        events = recorder.get("shed-q")["events"]
        assert any(e["event"] == "engine.shed_queue_full" for e in events)
        assert any(
            e["event"] == "http.shed" and e["reason"] == "queue_full" for e in events
        )
        for stream in (occupant, waiter):
            for _ in stream:
                pass
    finally:
        batcher.close()


def test_engine_deadline_shed_traces_503(tiny_gen):
    import time as _time

    batcher = _engine(tiny_gen, slots=1)
    try:
        srv, recorder = _engine_server(batcher)

        async def expired_handler(body):
            batcher.submit([1, 2, 3], deadline=_time.monotonic() - 1.0)
            raise AssertionError("unreachable")

        srv.route("POST", "/expired", expired_handler)
        status, _, _, extra = _dispatch(srv, "POST", "/expired", b"", {"x-request-id": "late"})
        assert (status, extra["X-Request-Id"]) == (503, "late")
        events = recorder.get("late")["events"]
        shed = next(e for e in events if e["event"] == "engine.shed_deadline")
        assert shed["phase"] == "submit"
        assert any(e["event"] == "http.shed" and e["reason"] == "deadline" for e in events)
    finally:
        batcher.close()


def test_engine_trace_opt_out_even_with_ambient_trace(tiny_gen):
    """trace=False on the engine (the bench lane's control arm) must not
    touch an ambient request trace."""
    batcher = _engine(tiny_gen, slots=1, trace=False)
    try:
        trace = RequestTrace("ambient", "POST", "/gen")
        tokens = trace_mod.bind("ambient", trace)
        try:
            stream = batcher.submit([4, 2])
        finally:
            trace_mod.unbind(tokens)
        drained = [int(t) for c in stream for t in np.asarray(c).ravel()]
        assert drained
        assert [e["event"] for e in trace.snapshot()["events"]] == []
    finally:
        batcher.close()
