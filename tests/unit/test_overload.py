"""Overload-protection behavior: admission control, deadlines, load shedding,
and graceful drain (serving/overload.py + the bounded queues it feeds).

The oracle throughout: with admission cap Q and a wedged predictor, a 4xQ
flood leaves AT MOST Q requests queued-or-in-flight and sheds the rest
immediately with 429 + Retry-After; deadline-expired work is shed with 503
without spending a predictor dispatch; a draining server answers
503/ready=false while in-flight work finishes. Continuous-engine overload
tests (slot-wait bounds, disconnect-frees-slot) live in test_continuous.py,
next to the engine fixtures they reuse.
"""

import asyncio
import json
import threading
import time

import pytest

from unionml_tpu.serving import (
    DeadlineExceeded,
    MicroBatcher,
    QueueFullError,
    ServingConfig,
    serving_app,
)
from unionml_tpu.serving.http import _STATUS_PHRASES, HTTPError, HTTPServer


# ------------------------------------------------------------------ HTTP layer


def test_shed_status_phrases_exist():
    """429/503 responses must carry real reason phrases, not 'Unknown'."""
    assert _STATUS_PHRASES[429] == "Too Many Requests"
    assert _STATUS_PHRASES[503] == "Service Unavailable"
    assert _STATUS_PHRASES[408] == "Request Timeout"


def test_negative_content_length_is_a_clean_400():
    """A negative Content-Length must be rejected at the parser, not passed to
    readexactly (whose own ValueError message is about internals)."""
    server = HTTPServer()

    async def scenario():
        reader = asyncio.StreamReader()
        reader.feed_data(b"POST /predict HTTP/1.1\r\nContent-Length: -5\r\n\r\n")
        reader.feed_eof()
        with pytest.raises(ValueError, match="negative Content-Length"):
            await server._read_request(reader)
        reader = asyncio.StreamReader()
        reader.feed_data(b"POST /predict HTTP/1.1\r\nContent-Length: nope\r\n\r\n")
        reader.feed_eof()
        with pytest.raises(ValueError, match="malformed Content-Length"):
            await server._read_request(reader)

    asyncio.run(scenario())


def test_inflight_cap_sheds_excess_with_429_and_retry_after():
    """Admission control at the HTTP layer: cap Q, flood 4xQ against a blocked
    handler -> exactly Q admitted (in flight), 3xQ shed IMMEDIATELY with 429 +
    Retry-After; once unblocked, the admitted Q all complete."""
    Q = 4
    server = HTTPServer()
    server.max_inflight = Q
    release = asyncio.Event()

    async def handler(body):
        await release.wait()
        return 200, {"ok": True}, "application/json"

    server.route("POST", "/work", handler)

    async def scenario():
        tasks = [
            asyncio.create_task(server._dispatch_full("POST", "/work", b""))
            for _ in range(4 * Q)
        ]
        await asyncio.sleep(0.05)  # one scheduling tick: sheds are synchronous
        done = [t for t in tasks if t.done()]
        shed = [t.result() for t in done]
        assert len(shed) == 3 * Q, "excess requests must shed within one tick"
        assert all(r[0] == 429 for r in shed)
        assert all(r[3].get("Retry-After") for r in shed)
        assert server.inflight == Q  # bounded in-flight, nothing queued beyond
        release.set()
        results = await asyncio.gather(*tasks)
        assert sum(1 for r in results if r[0] == 200) == Q
        assert server.inflight == 0

    asyncio.run(scenario())


def test_deadline_header_cancels_slow_handler_with_503():
    server = HTTPServer()
    cancelled = asyncio.Event()

    async def slow(body):
        try:
            await asyncio.sleep(30)
        except asyncio.CancelledError:
            cancelled.set()  # resources reclaimed, not leaked
            raise
        return 200, {}, "application/json"

    server.route("POST", "/slow", slow)

    async def scenario():
        t0 = time.monotonic()
        status, payload, _ = await server.dispatch(
            "POST", "/slow", b"", {"x-request-deadline-ms": "50"}
        )
        assert status == 503
        assert time.monotonic() - t0 < 5.0  # the deadline fired, not the sleep
        await asyncio.wait_for(cancelled.wait(), 2.0)
        # born-expired: non-positive deadline sheds before the handler runs
        status, payload, _ = await server.dispatch(
            "POST", "/slow", b"", {"x-request-deadline-ms": "0"}
        )
        assert status == 503 and "deadline" in payload["detail"]
        # malformed header is the client's fault: 400, not a silent default
        status, payload, _ = await server.dispatch(
            "POST", "/slow", b"", {"x-request-deadline-ms": "soon"}
        )
        assert status == 400

    asyncio.run(scenario())


def test_server_default_deadline_applies_without_header():
    server = HTTPServer()
    server.default_deadline_ms = 50

    async def slow(body):
        await asyncio.sleep(30)
        return 200, {}, "application/json"

    server.route("POST", "/slow", slow)
    status, payload, _ = asyncio.run(server.dispatch("POST", "/slow", b""))
    assert status == 503


def test_client_deadline_is_clipped_to_server_max():
    server = HTTPServer()
    server.max_deadline_ms = 50  # a client cannot pin resources past this

    async def slow(body):
        await asyncio.sleep(30)
        return 200, {}, "application/json"

    server.route("POST", "/slow", slow)
    status, *_ = asyncio.run(
        server.dispatch("POST", "/slow", b"", {"x-request-deadline-ms": "600000"})
    )
    assert status == 503


def test_queue_full_error_from_handler_maps_to_429():
    server = HTTPServer()

    async def full(body):
        raise QueueFullError("engine queue full", retry_after_s=7)

    server.route("POST", "/gen", full)

    async def scenario():
        status, payload, _, extra, _ = await server._dispatch_full("POST", "/gen", b"")
        assert status == 429
        assert extra["Retry-After"] == "7"

    asyncio.run(scenario())


def test_http_error_headers_reach_the_wire_encoding():
    raw = HTTPServer._encode_response(
        429, {"detail": "full"}, keep_alive=False, extra_headers={"Retry-After": "3"}
    )
    head = raw.split(b"\r\n\r\n")[0].decode()
    assert "429 Too Many Requests" in head and "Retry-After: 3" in head
    assert isinstance(HTTPError(429, "x", headers={"Retry-After": "1"}).headers, dict)


# ------------------------------------------------------------------ drain


def test_drain_sheds_new_work_but_health_and_metrics_stay_up():
    server = HTTPServer()

    async def work(body):
        return 200, {"ok": True}, "application/json"

    async def health(body):
        if server.draining:
            return 503, {"ready": False}, "application/json"
        return 200, {"ready": True}, "application/json"

    async def metrics(body):
        return 200, {}, "application/json"

    server.route("POST", "/work", work)
    server.route("GET", "/health", health)
    server.route("GET", "/metrics", metrics)

    async def scenario():
        assert (await server.dispatch("POST", "/work", b""))[0] == 200
        server.begin_drain()
        status, payload, _, extra, _ = await server._dispatch_full("POST", "/work", b"")
        assert status == 503 and "draining" in payload["detail"]
        assert extra.get("Retry-After")
        # exempt probes keep answering so the LB sees ready=false, not a dead host
        status, payload, _ = await server.dispatch("GET", "/health", b"")
        assert status == 503 and payload["ready"] is False
        assert (await server.dispatch("GET", "/metrics", b""))[0] == 200

    asyncio.run(scenario())


def test_shutdown_waits_for_inflight_work_then_signals_stop():
    """The SIGTERM path (serve() wires SIGTERM -> shutdown()): in-flight work
    admitted before the drain completes normally; the drain returns only after
    it finishes (or the drain timeout expires)."""
    server = HTTPServer()
    drained = []
    server.on_drained = lambda: drained.append(True)

    async def slowish(body):
        await asyncio.sleep(0.2)
        return 200, {"ok": True}, "application/json"

    server.route("POST", "/work", slowish)

    async def scenario():
        inflight = asyncio.create_task(server.dispatch("POST", "/work", b""))
        await asyncio.sleep(0.02)  # the request is mid-handler when SIGTERM lands
        t0 = time.monotonic()
        await server.shutdown(drain_timeout_s=5.0)
        assert time.monotonic() - t0 >= 0.1  # waited for the in-flight request
        status, *_ = inflight.result()  # finished cleanly during the drain
        assert status == 200
        assert drained == [True]
        # late arrivals during/after the drain are shed
        assert (await server.dispatch("POST", "/work", b""))[0] == 503

    asyncio.run(scenario())


# ------------------------------------------------------------------ micro-batcher


def test_micro_batcher_full_queue_sheds_immediately():
    """Bounded admission queue: with the predictor wedged and max_queue=Q, a
    4xQ flood keeps at most Q queued (+ one dispatching batch) and sheds the
    rest synchronously with QueueFullError."""
    Q = 4
    release = threading.Event()

    def predict(batch):
        release.wait(timeout=30)
        return [x * 2 for x in batch]

    async def scenario():
        batcher = MicroBatcher(
            predict,
            ServingConfig(max_batch_size=2, max_wait_ms=1, pad_to_bucket=False, max_queue=Q),
        )
        tasks = [asyncio.create_task(batcher.submit([i])) for i in range(4 * Q)]
        await asyncio.sleep(0.05)
        shed = [
            t for t in tasks if t.done() and isinstance(t.exception(), QueueFullError)
        ]
        # worker absorbs at most one batch (max_batch_size=2); queue holds <= Q
        assert len(shed) >= 4 * Q - Q - 2
        assert batcher.queue_depth <= Q
        assert batcher.stats()["shed_queue_full"] == len(shed)
        release.set()
        results = await asyncio.gather(*tasks, return_exceptions=True)
        served = [r for r in results if isinstance(r, list)]
        assert len(served) == 4 * Q - len(shed)  # every admitted request answered
        await batcher.stop()

    asyncio.run(scenario())


def test_micro_batcher_sheds_expired_queued_request_without_dispatching_it():
    dispatched = []
    release = threading.Event()

    def predict(batch):
        dispatched.append(list(batch))
        release.wait(timeout=30)
        return [x * 2 for x in batch]

    async def scenario():
        batcher = MicroBatcher(
            predict, ServingConfig(max_batch_size=1, max_wait_ms=1, pad_to_bucket=False)
        )
        blocker = asyncio.create_task(batcher.submit([1]))
        await asyncio.sleep(0.05)  # the wedged dispatch now owns the worker
        doomed = asyncio.create_task(
            batcher.submit([2], deadline=time.monotonic() + 0.05)
        )
        await asyncio.sleep(0.15)  # expires while queued behind the wedge
        release.set()
        assert (await blocker) == [2]
        with pytest.raises(DeadlineExceeded):
            await doomed
        assert [1] in dispatched and [2] not in dispatched  # no wasted dispatch
        assert batcher.stats()["shed_deadline"] == 1
        await batcher.stop()

    asyncio.run(scenario())


def test_micro_batcher_reaps_cancelled_requests_before_dispatch():
    """A handler cancelled at the HTTP layer (client disconnect / deadline)
    leaves a done future in the queue; the worker must drop it instead of
    spending a predictor dispatch on it."""
    dispatched = []
    release = threading.Event()

    def predict(batch):
        dispatched.append(list(batch))
        release.wait(timeout=30)
        return [x * 2 for x in batch]

    async def scenario():
        batcher = MicroBatcher(
            predict, ServingConfig(max_batch_size=1, max_wait_ms=1, pad_to_bucket=False)
        )
        blocker = asyncio.create_task(batcher.submit([1]))
        await asyncio.sleep(0.05)
        abandoned = asyncio.create_task(batcher.submit([2]))
        await asyncio.sleep(0.02)
        abandoned.cancel()  # the disconnecting client
        await asyncio.sleep(0.02)
        release.set()
        assert (await blocker) == [2]
        with pytest.raises(asyncio.CancelledError):
            await abandoned
        # give the worker a tick to reap the cancelled item, then verify
        await asyncio.sleep(0.05)
        assert [2] not in dispatched
        assert batcher.stats()["cancelled"] == 1
        await batcher.stop()

    asyncio.run(scenario())


# ------------------------------------------------------------------ end to end


def test_app_flood_bounded_admission_and_drain(sklearn_model):
    """The acceptance scenario, in process: admission cap Q, wedged predictor,
    4xQ flood -> <=Q queued+in-flight, 3xQ shed with 429 + Retry-After within a
    tick; /metrics reports the sheds; a drain then flips /health readiness and
    sheds new predicts with 503 while admitted work completes."""
    Q = 4
    sklearn_model.train(hyperparameters={"max_iter": 500})
    app = serving_app(sklearn_model)
    app.configure_overload(max_inflight=Q)
    app.startup()

    release = threading.Event()
    fast_predict = app.batcher._predict_fn

    def wedged(features):
        release.wait(timeout=30)
        return fast_predict(features)

    app.batcher._predict_fn = wedged
    body = json.dumps({"features": [{"x1": 1.0, "x2": 1.0}]}).encode()

    async def scenario():
        tasks = [
            asyncio.create_task(app.server._dispatch_full("POST", "/predict", body))
            for _ in range(4 * Q)
        ]
        await asyncio.sleep(0.1)  # one tick: every shed is already resolved
        done = [t.result() for t in tasks if t.done()]
        assert len(done) == 3 * Q
        assert all(r[0] == 429 and r[3].get("Retry-After") for r in done)
        assert app.server.inflight == Q
        assert app.batcher.queue_depth <= Q  # bounded queue behind the cap
        release.set()
        results = await asyncio.gather(*tasks)
        assert sum(1 for r in results if r[0] == 200) == Q

        status, snapshot, _ = await app.dispatch("GET", "/metrics")
        assert snapshot["overload"]["shed_inflight"] == 3 * Q
        assert "inflight" in snapshot["gauges"]
        assert snapshot["micro_batcher"]["max_queue"] > 0

        # ---- graceful drain: readiness flips, new predicts shed, probes live
        status, payload, _ = await app.dispatch("GET", "/health")
        assert status == 200 and payload["ready"] is True
        app.server.begin_drain()
        status, payload, _ = await app.dispatch("GET", "/health")
        assert status == 503 and payload["ready"] is False
        status, payload, _, extra, _ = await app.server._dispatch_full(
            "POST", "/predict", body
        )
        assert status == 503 and extra.get("Retry-After")
        assert (await app.dispatch("GET", "/metrics"))[0] == 200
        await app.server.shutdown(drain_timeout_s=1.0)

    asyncio.run(scenario())


def test_app_request_deadline_propagates_to_batcher_shed(sklearn_model):
    """An explicit client deadline rides the contextvar into the micro-batcher:
    a request expiring while queued behind a wedge is answered 503 and its
    queued work is reaped, never dispatched."""
    sklearn_model.train(hyperparameters={"max_iter": 500})
    app = serving_app(sklearn_model)
    app.startup()

    release = threading.Event()
    fast_predict = app.batcher._predict_fn
    seen_x1 = []

    def wedged(features):
        seen_x1.extend(float(v) for v in features["x1"])
        release.wait(timeout=30)
        return fast_predict(features)

    app.batcher._predict_fn = wedged
    body = json.dumps({"features": [{"x1": 1.0, "x2": 1.0}]}).encode()
    doomed_body = json.dumps({"features": [{"x1": 99.0, "x2": 1.0}]}).encode()

    async def scenario():
        blocker = asyncio.create_task(app.dispatch("POST", "/predict", body))
        await asyncio.sleep(0.1)  # the wedge owns the dispatch loop
        status, payload, _ = await app.dispatch(
            "POST", "/predict", doomed_body, {"x-request-deadline-ms": "50"}
        )
        assert status == 503
        release.set()
        assert (await blocker)[0] == 200
        # the expired request's rows never reached the predictor: its queued
        # work was reaped (cancelled future / expired deadline) at dequeue
        await asyncio.sleep(0.05)
        assert 99.0 not in seen_x1

    asyncio.run(scenario())


@pytest.mark.parametrize(
    "slots, max_waiting, cap",
    [(64, 128, 256), (192, 256, 448), (None, None, 256)],
    ids=["engine-under-the-default", "engine-over-the-default", "no-engine"],
)
def test_the_fronts_default_cap_never_refuses_what_the_engine_would_queue(sklearn_model, slots, max_waiting, cap):
    """A stream holds its handler for its whole life, so the front's default cap is the engine's own
    ``slots + max_waiting`` where that is over ``SERVE_MAX_INFLIGHT``; an explicit ``configure_overload`` still wins."""
    import types

    if slots is not None:
        sklearn_model.generation_batcher = types.SimpleNamespace(slots=slots, max_waiting=max_waiting)
    app = serving_app(sklearn_model)
    assert app.server.max_inflight == cap
    assert app.configure_overload(max_inflight=8).server.max_inflight == 8
