"""Multi-tenant QoS (docs/serving.md "Multi-tenant QoS") + the OpenAI surface.

The pinned contracts:

- **buckets**: per-tenant req/s and generated-tokens/s token buckets shed 429
  with a ``Retry-After`` computed from the limiting bucket's actual refill
  time; anonymous traffic is never bucket-limited; the tenant state map is
  bounded (capacity + idle eviction — the TPU009 dogfood);
- **fairness**: waiting prompts admit deficit-round-robin across tenants
  within strict priority tiers — a hostile burst no longer FIFO-starves the
  other tenants, weights skew token share proportionally, zero-weight tenants
  are best-effort;
- **priority preemption**: a high-priority admission on a full paged engine
  preempts exactly one lowest-priority resident, and the victim's resumed
  stream is token-identical to an unpreempted run;
- **OpenAI compatibility**: ``POST /v1/completions`` (and chat) answer the
  OpenAI schema — ``stream=true`` SSE terminated by ``data: [DONE]``, correct
  ``usage`` counts — and unsupported params are clear 400s;
- **off = today's engine**: no registry + no headers leaves stats, metrics,
  and scheduling byte-for-byte unchanged.
"""

import asyncio
import json
import queue
import threading
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.serving import ContinuousBatcher, ServingApp, TenantRegistry, TenantSpec
from unionml_tpu.serving.continuous import _Session
from unionml_tpu.serving.overload import QueueFullError, TenantThrottled
from unionml_tpu.serving.tenancy import (
    PRIORITIES,
    parse_priority,
    resolve_tenant,
    sanitize_tenant_id,
)


@pytest.fixture(scope="module")
def tiny():
    config = LlamaConfig.tiny(
        vocab_size=96, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _cfg(**overrides):
    kwargs = dict(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    kwargs.update(overrides)
    return GenerationConfig(**kwargs)


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


# ------------------------------------------------------------------ specs / identity


def test_tenant_spec_validation():
    TenantSpec(weight=0, req_per_s=0, tokens_per_s=0)  # all-zero is legal
    for bad in (
        dict(weight=-1), dict(req_per_s=-1), dict(tokens_per_s=-0.5),
        dict(burst_s=0), dict(priority="turbo"),
    ):
        with pytest.raises(ValueError):
            TenantSpec(**bad)


def test_parse_priority():
    assert parse_priority("high") == 0
    assert parse_priority(" Normal ") == 1
    assert parse_priority("BATCH") == 2
    with pytest.raises(ValueError):
        parse_priority("urgent")


def test_sanitize_tenant_id():
    assert sanitize_tenant_id("acme-1_2.3") == "acme-1_2.3"
    assert sanitize_tenant_id("evil\r\nX: 1") == "evilX1"
    assert sanitize_tenant_id(None) is None
    assert len(sanitize_tenant_id("x" * 500)) == 64


def test_resolve_tenant_precedence_and_key_digest():
    reg = TenantRegistry({"acme": TenantSpec()}, api_keys={"sk-secret-123": "acme"})
    assert resolve_tenant({"x-tenant-id": "beta"}, reg) == "beta"  # header wins
    assert resolve_tenant({"authorization": "Bearer sk-secret-123"}, reg) == "acme"
    derived = resolve_tenant({"authorization": "Bearer sk-unmapped-456"}, reg)
    # unmapped keys become stable digest-derived tenants; the secret itself
    # must never appear in the identity that reaches traces and metrics
    assert derived.startswith("key-") and "sk-unmapped-456" not in derived
    assert derived == resolve_tenant({"authorization": "Bearer sk-unmapped-456"}, None)
    assert resolve_tenant({}, reg) is None
    assert resolve_tenant({"authorization": "Basic Zm9v"}, reg) is None


# ------------------------------------------------------------------ buckets


def test_request_bucket_refill_and_retry_after():
    clk = [0.0]
    reg = TenantRegistry(
        {"t": TenantSpec(req_per_s=2.0, burst_s=1.0)}, clock=lambda: clk[0]
    )
    # cap = max(2*1, 1) = 2 requests of burst
    assert reg.try_admit("t") is None
    assert reg.try_admit("t") is None
    retry = reg.try_admit("t")
    assert retry == pytest.approx(0.5, rel=0.01)  # 1 token at 2/s
    clk[0] += 0.5
    assert reg.try_admit("t") is None  # refilled exactly one
    stats = reg.stats()["per_tenant"]["t"]
    assert stats["admitted"] == 3 and stats["shed"] == 1


def test_token_bucket_debt_blocks_new_admissions():
    clk = [0.0]
    reg = TenantRegistry(
        {"t": TenantSpec(tokens_per_s=10.0, burst_s=1.0)}, clock=lambda: clk[0]
    )
    assert reg.try_admit("t") is None
    reg.charge_tokens("t", 25)  # overdraw: 10 - 25 = -15
    retry = reg.try_admit("t")
    assert retry == pytest.approx(1.6, rel=0.01)  # (1 - (-15)) / 10
    clk[0] += 1.6
    assert reg.try_admit("t") is None


def test_anonymous_and_unlimited_tenants_never_shed():
    reg = TenantRegistry({"t": TenantSpec()})  # rates 0 = unlimited
    for _ in range(100):
        assert reg.try_admit(None) is None
        assert reg.try_admit("t") is None
    reg.charge_tokens(None, 10)  # no-op, no state minted for anonymous
    assert reg.stats()["per_tenant"].keys() == {"t"}


def test_registry_state_map_is_bounded():
    clk = [0.0]
    reg = TenantRegistry(max_tenants=4, idle_evict_s=100.0, clock=lambda: clk[0])
    for i in range(10):
        reg.try_admit(f"tenant-{i}")
    stats = reg.stats()
    assert stats["count"] <= 4 and stats["evicted"] >= 6
    # idle aging: the survivors evict once stale
    clk[0] += 101.0
    reg.try_admit("fresh")
    assert set(reg.stats()["per_tenant"]) == {"fresh"}


def test_registry_from_file_and_env_degrade(tmp_path, monkeypatch, caplog):
    path = tmp_path / "tenants.json"
    path.write_text(json.dumps({
        "default": {"req_per_s": 3},
        "tenants": {"acme": {"weight": 2, "priority": "high"}},
        "api_keys": {"sk-1": "acme"},
    }))
    reg = TenantRegistry.from_file(str(path))
    assert reg.weight("acme") == 2 and reg.default_priority("acme") == PRIORITIES["high"]
    assert reg.spec("unknown").req_per_s == 3
    assert reg.tenant_for_key("sk-1") == "acme"

    from unionml_tpu._logging import logger

    monkeypatch.setattr(logger, "propagate", True)
    monkeypatch.setenv("UNIONML_TPU_TENANT_CONFIG", str(tmp_path / "missing.json"))
    monkeypatch.setenv("UNIONML_TPU_DEFAULT_TENANT_RATE", "5")
    with caplog.at_level("WARNING", logger="unionml_tpu"):
        degraded = TenantRegistry.from_env()
    assert degraded is not None and degraded.default_spec.req_per_s == 5
    assert any("missing.json" in r.message for r in caplog.records)
    monkeypatch.delenv("UNIONML_TPU_TENANT_CONFIG")
    monkeypatch.delenv("UNIONML_TPU_DEFAULT_TENANT_RATE")
    assert TenantRegistry.from_env() is None  # neither knob set = tenancy off


# ------------------------------------------------------------------ DRR scheduling


def _queue_session(engine, prompt, tenant=None, priority=1):
    session = _Session(
        slot=-1, out=queue.Queue(), max_new=4, tenant=tenant, priority=priority,
        prompt=list(prompt),
    )
    engine._pending.append((list(prompt), session))
    return session


def _selection_order(engine, n):
    """Drain the waiting queue through the DRR selector, recording tenants."""
    order = []
    with engine._lock:
        for _ in range(n):
            engine._select_pending_locked()
            prompt, session = engine._pending.pop(0)
            order.append((session.tenant, session.priority))
    return order


def test_fifo_fast_path_without_qos(tiny):
    module, params = tiny
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1)
    try:
        for i in range(3):
            _queue_session(engine, [10 + i])
        with engine._lock:
            engine._drr_deficit["stale"] = 5.0
            engine._select_pending_locked()
            # FIFO order untouched, and the leftover per-tenant state evicted
            assert [p for p, _ in engine._pending] == [[10], [11], [12]]
            assert engine._drr_deficit == {}
    finally:
        engine.close()


def test_drr_interleaves_hostile_burst(tiny):
    module, params = tiny
    reg = TenantRegistry({"evil": TenantSpec(), "good": TenantSpec()})
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1, tenancy=reg)
    try:
        for _ in range(6):
            _queue_session(engine, [1] * 8, tenant="evil")
        for _ in range(2):
            _queue_session(engine, [2] * 8, tenant="good")
        order = [t for t, _ in _selection_order(engine, 8)]
        # FIFO would serve all 6 evil first; DRR must admit both good prompts
        # well before the hostile queue drains
        assert order.index("good") < 3
        assert {t for t in order[:5]} == {"evil", "good"}
    finally:
        engine.close()


def test_drr_weight_skews_share(tiny):
    module, params = tiny
    reg = TenantRegistry({"heavy": TenantSpec(weight=2), "light": TenantSpec(weight=1)})
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1, tenancy=reg)
    try:
        for _ in range(12):
            _queue_session(engine, [1] * 8, tenant="heavy")
            _queue_session(engine, [2] * 8, tenant="light")
        order = [t for t, _ in _selection_order(engine, 18)]
        heavy = order.count("heavy")
        light = order.count("light")
        # weight 2 vs 1: heavy's admitted share must be about double
        assert heavy / max(light, 1) == pytest.approx(2.0, rel=0.35), order
    finally:
        engine.close()


def test_zero_weight_tenant_is_best_effort(tiny):
    module, params = tiny
    reg = TenantRegistry({"burst": TenantSpec(weight=0), "paid": TenantSpec(weight=1)})
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1, tenancy=reg)
    try:
        for _ in range(3):
            _queue_session(engine, [1] * 4, tenant="burst")
        for _ in range(3):
            _queue_session(engine, [2] * 4, tenant="paid")
        order = [t for t, _ in _selection_order(engine, 6)]
        # every weighted admission lands before any best-effort one
        assert order == ["paid"] * 3 + ["burst"] * 3
    finally:
        engine.close()


def test_priority_tiers_are_strict(tiny):
    module, params = tiny
    reg = TenantRegistry()
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1, tenancy=reg)
    try:
        _queue_session(engine, [1] * 4, tenant="a", priority=2)  # batch
        _queue_session(engine, [2] * 4, tenant="b", priority=1)  # normal
        _queue_session(engine, [3] * 4, tenant="c", priority=0)  # high
        order = _selection_order(engine, 3)
        assert [p for _, p in order] == [0, 1, 2]
    finally:
        engine.close()


def test_submit_priority_validation_and_string_tier(tiny):
    module, params = tiny
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1)
    try:
        out = _drain(engine.submit([3, 1, 4], priority="batch"))
        assert len(out) == 8
        with pytest.raises(ValueError):
            engine.submit([3, 1, 4], priority=7)
        with pytest.raises(ValueError):
            engine.submit([3, 1, 4], priority="turbo")
    finally:
        engine.close()


# ------------------------------------------------------------------ bucket sheds at the engine


def test_engine_sheds_tenant_over_rate_with_retry_after(tiny):
    module, params = tiny
    clk = [0.0]
    reg = TenantRegistry(
        {"slow": TenantSpec(req_per_s=0.5, burst_s=2.0)}, clock=lambda: clk[0]
    )
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=2, tenancy=reg)
    try:
        _drain(engine.submit([3, 1, 4], tenant="slow"))
        with pytest.raises(TenantThrottled) as exc_info:
            engine.submit([3, 1, 4], tenant="slow")
        assert exc_info.value.retry_after_s == pytest.approx(2.0, rel=0.01)
        assert exc_info.value.tenant == "slow"
        assert isinstance(exc_info.value, QueueFullError)  # rides the 429 path
        assert engine.stats()["tenancy"]["shed_tenant_limit"] == 1
        # anonymous traffic rides through the same engine unlimited
        assert len(_drain(engine.submit([3, 1, 4]))) == 8
    finally:
        engine.close()


def test_stats_off_contract(tiny):
    module, params = tiny
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1)
    try:
        _drain(engine.submit([3, 1, 4]))
        assert "tenancy" not in engine.stats()
        assert engine.tenant_census() == {}
    finally:
        engine.close()


def test_tenant_census_counts_live_streams(tiny):
    module, params = tiny
    engine = ContinuousBatcher(Generator(module, params, _cfg()), slots=1)
    try:
        _queue_session(engine, [1] * 4, tenant="a")
        _queue_session(engine, [2] * 4, tenant="a")
        _queue_session(engine, [3] * 4, tenant="b")
        _queue_session(engine, [4] * 4)  # anonymous: omitted
        census = engine.tenant_census()
        assert census == {
            "a": {"resident": 0, "waiting": 2},
            "b": {"resident": 0, "waiting": 1},
        }
        from unionml_tpu.observability.health import fleet_debug

        debug = fleet_debug(engine)
        assert debug["tenants"]["a"]["waiting"] == 2
        with engine._lock:
            engine._pending.clear()
    finally:
        engine.close()


# ------------------------------------------------------------------ priority preemption


def _slow_decode(engine, dispatch_s=0.02):
    real = engine.gen._decode

    def slow(*args, _real=real, **kwargs):
        time.sleep(dispatch_s)
        return _real(*args, **kwargs)

    engine.gen._decode = slow


@pytest.mark.parametrize(
    "sizes", [{"block_size": 16, "pool_blocks": 24}, {}], ids=["blocks_of_16", "no_sizes"]
)
def test_high_priority_preempts_exactly_one_lowest_priority_resident(tiny, sizes):
    module, params = tiny
    cfg = _cfg(max_new_tokens=32)
    gen = Generator(module, params, cfg)
    reference = {
        tuple(p): list(map(int, gen([p])[0]))
        for p in ([3, 1, 4, 1, 5], [9, 2, 6, 5], [7, 7, 1])
    }
    engine = ContinuousBatcher(gen, slots=2, decode_chunk=2, **sizes)
    try:
        engine.warmup()
        _slow_decode(engine)
        results = {}

        def consume(name, stream):
            results[name] = _drain(stream)

        normal = engine.submit([3, 1, 4, 1, 5], priority=1)
        batch = engine.submit([9, 2, 6, 5], priority=2)
        threads = [
            threading.Thread(target=consume, args=("normal", normal)),
            threading.Thread(target=consume, args=("batch", batch)),
        ]
        for t in threads:
            t.start()
        # wait until both residents hold the engine's two slots
        deadline = time.monotonic() + 5.0
        while engine.occupancy()[0] < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        high = engine.submit([7, 7, 1], priority=0)
        high_out = _drain(high)
        for t in threads:
            t.join()
        # exactly one preemption, and the BATCH resident was the victim
        assert engine.priority_preemptions == 1
        assert engine.preemptions == 1
        assert engine.stats()["tenancy"]["priority_preemptions"] == 1
        # the preempted stream resumed token-identically; nobody truncated
        assert high_out == reference[(7, 7, 1)]
        assert results["batch"] == reference[(9, 2, 6, 5)]
        assert results["normal"] == reference[(3, 1, 4, 1, 5)]
    finally:
        engine.close()


def test_no_priority_preemption_without_lower_priority_residents(tiny):
    module, params = tiny
    cfg = _cfg(max_new_tokens=16)
    gen = Generator(module, params, cfg)
    engine = ContinuousBatcher(gen, slots=1, decode_chunk=2, block_size=16, pool_blocks=12)
    try:
        engine.warmup()
        _slow_decode(engine)
        results = {}

        def consume(name, stream):
            results[name] = _drain(stream)

        first = engine.submit([3, 1, 4], priority=0)  # high resident
        thread = threading.Thread(target=consume, args=("first", first))
        thread.start()
        deadline = time.monotonic() + 5.0
        while engine.occupancy()[0] < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        # an equal-priority arrival WAITS (no preemption among peers)
        second = engine.submit([9, 2], priority=0)
        results["second"] = _drain(second)
        thread.join()
        assert engine.priority_preemptions == 0
        assert len(results["first"]) == 16 and len(results["second"]) == 16
    finally:
        engine.close()


# ------------------------------------------------------------------ HTTP layer


def _app(tiny, cfg=None, tenancy=None, tokenizer=None, **engine_kwargs):
    module, params = tiny
    engine = ContinuousBatcher(
        Generator(module, params, cfg or _cfg()), slots=2, tenancy=tenancy,
        **engine_kwargs,
    )
    model = types.SimpleNamespace(
        artifact=object(), generation_batcher=engine, _predictor_config=None,
        _compiled_predictor=None, _stream_predictor=None, name="tiny",
    )
    if tokenizer is not None:
        model.tokenizer = tokenizer
    app = ServingApp(model)
    app._started = True
    return app, engine


def _dispatch(app, method, path, body=b"", headers=None):
    return asyncio.run(app.server.dispatch_with_headers(method, path, body, headers))


def _dispatch_stream(app, method, path, body=b"", headers=None):
    """Dispatch AND drain a streaming payload inside one event loop (the
    stream generator schedules executor work on the loop it was created in)."""

    async def run():
        status, payload, ct, extra = await app.server.dispatch_with_headers(
            method, path, body, headers
        )
        if hasattr(payload, "__aiter__"):
            payload = [chunk async for chunk in payload]
        return status, payload, ct, extra

    return asyncio.run(run())


def test_http_tenant_shed_is_distinct_and_carries_refill_retry_after(tiny):
    reg = TenantRegistry({"slow": TenantSpec(req_per_s=0.01, burst_s=100.0)})
    app, engine = _app(tiny, tenancy=reg)
    try:
        body = json.dumps({"prompt": [3, 1, 4], "max_tokens": 2}).encode()
        status, _, _, _ = _dispatch(
            app, "POST", "/v1/completions", body, {"x-tenant-id": "slow"}
        )
        assert status == 200
        status, payload, _, extra = _dispatch(
            app, "POST", "/v1/completions", body, {"x-tenant-id": "slow"}
        )
        assert status == 429
        # Retry-After from the bucket's refill (1 token at 0.01/s = ~100s
        # minus whatever wall clock the first request consumed), not the
        # server's fixed 1s hint
        assert 50.0 < float(extra["Retry-After"]) <= 100.0
        overload = app.metrics.snapshot()["overload"]
        assert overload.get("shed_tenant_limit") == 1
        assert "shed_queue_full" not in overload
    finally:
        engine.close()


def test_http_invalid_priority_is_400(tiny):
    app, engine = _app(tiny)
    try:
        status, payload, _, _ = _dispatch(
            app, "POST", "/v1/completions",
            json.dumps({"prompt": [3]}).encode(), {"x-priority": "turbo"},
        )
        assert status == 400 and "priority" in payload["detail"]
    finally:
        engine.close()


def test_trace_carries_tenant_and_debug_filter(tiny):
    app, engine = _app(tiny)
    app.configure_observability(trace=True, access_log=False)
    try:
        body = json.dumps({"prompt": [3, 1, 4], "max_tokens": 2}).encode()
        _dispatch(app, "POST", "/v1/completions", body,
                  {"x-tenant-id": "acme", "x-priority": "high"})
        _dispatch(app, "POST", "/v1/completions", body, {"x-tenant-id": "beta"})
        _dispatch(app, "POST", "/v1/completions", body)  # anonymous
        status, snap, _, _ = _dispatch(app, "GET", "/debug/requests?tenant=acme")
        assert status == 200
        entries = snap["completed"]
        assert len(entries) == 1
        assert entries[0]["tenant"] == "acme" and entries[0]["priority"] == "high"
        status, snap, _, _ = _dispatch(app, "GET", "/debug/requests")
        tenants = [e.get("tenant") for e in snap["completed"]]
        assert set(tenants) == {"acme", "beta", None}
    finally:
        engine.close()


def test_metrics_tenants_section_gated_on_registry(tiny):
    reg = TenantRegistry({"acme": TenantSpec(weight=2)})
    app, engine = _app(tiny, tenancy=reg)
    app.tenancy = reg  # the app surface mirrors what serve would install
    try:
        body = json.dumps({"prompt": [3, 1, 4], "max_tokens": 2}).encode()
        _dispatch(app, "POST", "/v1/completions", body, {"x-tenant-id": "acme"})
        status, snapshot, _, _ = _dispatch(app, "GET", "/metrics")
        assert snapshot["tenants"]["per_tenant"]["acme"]["admitted"] == 1
        assert snapshot["tenants"]["per_tenant"]["acme"]["generated_tokens"] == 2
        # the same snapshot renders as Prometheus exposition without error
        status, text, ct, _ = _dispatch(app, "GET", "/metrics?format=prometheus")
        assert status == 200 and "tenants" in text
    finally:
        engine.close()


def test_metrics_without_registry_unchanged(tiny):
    app, engine = _app(tiny)
    try:
        status, snapshot, _, _ = _dispatch(app, "GET", "/metrics")
        assert "tenants" not in snapshot
    finally:
        engine.close()


# ------------------------------------------------------------------ OpenAI surface


def test_openai_completion_usage_and_schema(tiny):
    app, engine = _app(tiny)
    try:
        status, payload, ct, _ = _dispatch(
            app, "POST", "/v1/completions",
            json.dumps({"prompt": [3, 1, 4, 1, 5], "max_tokens": 4, "model": "m1"}).encode(),
        )
        assert status == 200 and ct == "application/json"
        assert payload["object"] == "text_completion" and payload["model"] == "m1"
        assert payload["id"].startswith("cmpl-")
        choice = payload["choices"][0]
        assert choice["finish_reason"] == "length" and choice["index"] == 0
        assert payload["usage"] == {
            "prompt_tokens": 5, "completion_tokens": 4, "total_tokens": 9,
        }
        # no tokenizer: text is the documented space-joined token-id fallback
        assert len(choice["text"].split()) == 4
    finally:
        engine.close()


def test_openai_stream_sse_framing_and_done(tiny):
    app, engine = _app(tiny)
    try:
        status, chunks, ct, _ = _dispatch_stream(
            app, "POST", "/v1/completions",
            json.dumps({"prompt": [3, 1, 4], "max_tokens": 5, "stream": True}).encode(),
        )
        assert status == 200 and ct == "text/event-stream"
        assert all(chunk.startswith(b"data: ") and chunk.endswith(b"\n\n") for chunk in chunks)
        assert chunks[-1] == b"data: [DONE]\n\n"
        events = [json.loads(chunk[6:]) for chunk in chunks[:-1]]
        assert all(e["object"] == "text_completion" for e in events)
        # every event before the last streams text with no finish_reason; the
        # final event carries finish_reason + usage
        assert all(e["choices"][0]["finish_reason"] is None for e in events[:-1])
        final = events[-1]
        assert final["choices"][0]["finish_reason"] in ("stop", "length")
        emitted = final["usage"]["completion_tokens"]
        assert emitted == 5 and final["usage"]["prompt_tokens"] == 3
        streamed = sum(len(e["choices"][0]["text"].split()) for e in events[:-1])
        assert streamed == emitted
    finally:
        engine.close()


def test_openai_chat_with_tokenizer(tiny):
    class Tok:
        def encode(self, text):
            return [1 + (ord(c) % 90) for c in text][:12]

        def decode(self, ids):
            return "".join(chr(97 + (i % 26)) for i in ids)

    app, engine = _app(tiny, tokenizer=Tok())
    try:
        status, payload, _, _ = _dispatch(
            app, "POST", "/v1/chat/completions",
            json.dumps({
                "messages": [{"role": "user", "content": "hi"}], "max_tokens": 3,
            }).encode(),
        )
        assert status == 200 and payload["object"] == "chat.completion"
        message = payload["choices"][0]["message"]
        assert message["role"] == "assistant" and isinstance(message["content"], str)
        assert payload["usage"]["completion_tokens"] == 3

        status, chunks, ct, _ = _dispatch_stream(
            app, "POST", "/v1/chat/completions",
            json.dumps({
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 2, "stream": True,
            }).encode(),
        )
        assert status == 200 and ct == "text/event-stream"
        events = [json.loads(chunk[6:]) for chunk in chunks[:-1]]
        assert events[0]["choices"][0]["delta"] == {"role": "assistant"}
        assert chunks[-1] == b"data: [DONE]\n\n"
    finally:
        engine.close()


def test_openai_rejections(tiny):
    app, engine = _app(tiny)
    try:
        cases = [
            ({"prompt": "text prompt"}, "tokenizer"),
            ({"prompt": [1, 2], "n": 3}, "n"),
            # stop=/logprobs are SUPPORTED now (docs/workloads.md PR); their
            # happy paths and validation live in tests/unit/test_workloads.py
            ({"prompt": [1, 2], "echo": True}, "echo"),
            ({"prompt": [1, 2], "max_tokens": 0}, "max_tokens"),
            ({"prompt": []}, "non-empty"),
            ({"prompt": ["a", "b"]}, "token ids"),
            ({}, "prompt"),
            ({"messages": []}, None),  # chat needs messages
        ]
        for body, needle in cases[:-1]:
            status, payload, _, _ = _dispatch(
                app, "POST", "/v1/completions", json.dumps(body).encode()
            )
            assert status == 400, (body, payload)
            if needle:
                assert needle in payload["detail"], (body, payload)
        status, payload, _, _ = _dispatch(
            app, "POST", "/v1/chat/completions", json.dumps({"messages": []}).encode()
        )
        assert status == 400
    finally:
        engine.close()


def test_openai_404_without_generation_engine():
    model = types.SimpleNamespace(
        artifact=object(), _predictor_config=None, _compiled_predictor=None,
        _stream_predictor=None, name="none",
    )
    app = ServingApp(model)
    app._started = True
    status, payload, _, _ = _dispatch(
        app, "POST", "/v1/completions", json.dumps({"prompt": [1]}).encode()
    )
    assert status == 404 and "generation" in payload["detail"]
    status, payload, _, _ = _dispatch(app, "GET", "/v1/models")
    assert status == 200 and payload["data"][0]["id"] == "none"


def test_openai_max_tokens_clipped_to_engine_budget(tiny):
    app, engine = _app(tiny)  # budget 8
    try:
        status, payload, _, _ = _dispatch(
            app, "POST", "/v1/completions",
            json.dumps({"prompt": [3, 1, 4], "max_tokens": 4096}).encode(),
        )
        assert status == 200
        assert payload["usage"]["completion_tokens"] == 8
    finally:
        engine.close()
