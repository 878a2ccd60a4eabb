"""Multi-host fleet coordinator, in-process ring (docs/serving.md "Multi-host
fleets").

The cross-PROCESS contracts (real subprocess workers joining one
multi-process CPU JAX runtime) live in tests/emulated/test_cluster.py; this
ring pins the coordinator's routing/fleet logic cheaply with LocalHost
handles and a real WorkerAgent control server in the same process:

- **block-native payload**: a paged export ships block-aligned KV pages
  keyed by block position — never the ``cache_len``-wide dense row — and the
  npz wire round-trip preserves it exactly;
- **token identity**: streams routed through the coordinator (local AND
  remote hosts, plain and disaggregated) equal the sequential Generator
  oracle;
- **fleet-global prefix routing**: turn 2 of a conversation lands on the
  host whose radix tier already holds turn 1;
- **worker death**: a dead host is marked, routed around, and visible in the
  census — new work never sheds while a sibling lives;
- **cross-host elasticity**: ``scale_to`` distributes over live hosts and
  loses zero in-flight streams.
"""

import io
import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.serving import ContinuousBatcher, ReplicaSet
from unionml_tpu.serving.cluster import (
    FleetCoordinator,
    LocalHost,
    RemoteHost,
    WorkerAgent,
    _raise_shed,
    deserialize_handoff,
    serialize_handoff,
)
from unionml_tpu.serving.overload import (
    DeadlineExceeded,
    QueueFullError,
    TenantThrottled,
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


PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9], [7, 1]]


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


def _expected(module, params, cfg, prompts):
    gen = Generator(module, params, cfg)
    return [list(map(int, gen([p])[0])) for p in prompts]


def _engine(tiny, cfg, **kwargs):
    module, params = tiny
    knobs = dict(slots=2, decode_chunk=4, block_size=8, pool_blocks=64)
    knobs.update(kwargs)
    return ContinuousBatcher(Generator(module, params, cfg), **knobs)


# ------------------------------------------------------------- block-native payload


def test_paged_export_ships_pages_not_dense_row(tiny):
    """The PR 9 follow-on, pinned: a paged engine's handoff payload is
    block-aligned pages (pool layout, exactly ceil(lengths/block) of them) —
    payload bytes scale with the prompt, not cache_len."""
    cfg = _cfg()
    engine = _engine(tiny, cfg, role="prefill")
    try:
        stream = engine.submit(PROMPTS[0], export_handoff=True)
        first = _drain(stream)
        payload = stream.handoff
        assert len(first) == 1
        assert payload is not None and "row" not in payload
        pages = payload["pages"]
        n_blocks = -(-payload["lengths"] // payload["block_size"])
        assert payload["block_size"] == 8
        for layer in pages:
            # pool layout: [H_kv, n_blocks, block_size, head_dim]
            assert layer["k"].shape[:3] == (2, n_blocks, 8)
    finally:
        engine.close(wait=False)


def test_handoff_wire_round_trip(tiny):
    cfg = _cfg()
    engine = _engine(tiny, cfg, role="prefill")
    try:
        stream = engine.submit(PROMPTS[1], export_handoff=True, deadline=time.monotonic() + 60)
        _drain(stream)
        payload = stream.handoff
        data = serialize_handoff(payload)
        back = deserialize_handoff(data)
        assert back["prompt"] == payload["prompt"]
        assert back["first"] == payload["first"]
        assert back["lengths"] == payload["lengths"]
        assert back["echo"] == payload["echo"]
        assert back["block_size"] == payload["block_size"]
        assert back["trace"] is None
        # the absolute-monotonic deadline is rebased, not shipped raw
        assert back["deadline"] == pytest.approx(payload["deadline"], abs=1.0)
        for sent, received in zip(payload["pages"], back["pages"]):
            for name in sent:
                np.testing.assert_array_equal(np.asarray(sent[name]), received[name])
    finally:
        engine.close(wait=False)


def test_handoff_wire_rejects_a_row_payload(tiny):
    """Pages are the one KV payload: a ``kind`` the decoder does not know (the
    ``cache_len``-wide row of earlier engines) is refused like any malformed input."""
    engine = _engine(tiny, _cfg(), role="prefill")
    try:
        stream = engine.submit(PROMPTS[1], export_handoff=True)
        _drain(stream)
        data = serialize_handoff(stream.handoff)
    finally:
        engine.close(wait=False)
    with np.load(io.BytesIO(data)) as bundle:
        arrays = {key: bundle[key] for key in bundle.files}
    meta = json.loads(bytes(arrays["__meta__"]).decode())
    assert meta["kind"] == "pages"
    meta["kind"] = "row"
    arrays["__meta__"] = np.frombuffer(json.dumps(meta).encode(), np.uint8)
    out = io.BytesIO()
    np.savez(out, **arrays)
    with pytest.raises(ValueError, match="kind 'row'"):
        deserialize_handoff(out.getvalue())


# -------------------------------------------------------------------- coordination


def test_coordinator_local_and_remote_hosts_token_identical(tiny):
    """A 2-host fleet (one direct handle, one behind a real control server)
    serves every stream token-identical to the sequential oracle, and the
    fleet surface (stats/health/census) reflects both hosts."""
    module, params = tiny
    cfg = _cfg()
    e0, e1 = _engine(tiny, cfg), _engine(tiny, cfg)
    agent = WorkerAgent(e1, process_id=1).start()
    coordinator = FleetCoordinator(
        [LocalHost(e0, host_id=0), RemoteHost(agent.address, host_id=1)]
    )
    try:
        got = [_drain(coordinator.submit(p)) for p in PROMPTS]
        assert got == _expected(module, params, cfg, PROMPTS)
        stats = coordinator.stats()
        assert stats["live_hosts"] == 2
        assert sum(coordinator._scheduler.stats()["submitted"]) == len(PROMPTS)
        assert [entry["alive"] for entry in stats["hosts"]] == [True, True]
        health = coordinator.health()
        assert health["state"] == "ok" and len(health["replicas"]) == 2
        census = coordinator.host_census()
        assert [entry["host"] for entry in census] == [0, 1]
        assert coordinator.occupancy() == (0, 0)
    finally:
        agent.close(close_engine=True)
        e0.close(wait=False)
        coordinator.close()


def test_cross_host_disaggregated_handoff_token_identical(tiny):
    """Host-level prefill/decode split over the control plane: the prompt
    prefills on the prefill host, its block-native payload crosses the wire,
    and the decode host's stream continues bit-identically."""
    module, params = tiny
    cfg = _cfg()
    prefill = _engine(tiny, cfg, role="prefill")
    decode = _engine(tiny, cfg, role="decode")
    agent = WorkerAgent(decode, process_id=1, role="decode").start()
    coordinator = FleetCoordinator(
        [LocalHost(prefill, host_id=0, role="prefill"),
         RemoteHost(agent.address, host_id=1, role="decode")],
        prefill_threshold=1,
    )
    try:
        got = [_drain(coordinator.submit(p)) for p in PROMPTS]
        assert got == _expected(module, params, cfg, PROMPTS)
        stats = coordinator.stats()
        assert stats["handoffs_cross_host"] == len(PROMPTS)
        assert stats["handoff_transfer_ms"]["window"] == len(PROMPTS)
        assert decode.handoffs_imported == len(PROMPTS)
        assert prefill.handoffs_exported == len(PROMPTS)
    finally:
        agent.close(close_engine=True)
        prefill.close(wait=False)


def test_fleet_global_prefix_routing_lands_on_warm_host(tiny):
    """The radix tier, fleet-global: host 1 serves turn 1; turn 2 (the whole
    prior exchange plus a new user turn) probes every host's actual cached
    length and lands on host 1 — even though pure load order favors host 0."""
    module, params = tiny
    cfg = _cfg()
    e0 = _engine(tiny, cfg, prefix_cache=True)
    e1 = _engine(tiny, cfg, prefix_cache=True)
    coordinator = FleetCoordinator([LocalHost(e0, host_id=0), LocalHost(e1, host_id=1)])
    try:
        turn1 = PROMPTS[1]
        reply = _drain(e1.submit(turn1))  # host 1 is the warm one, off-coordinator
        turn2 = list(turn1) + reply + [11, 12]
        # decode-side radix publish lands at slot release on the engine
        # thread, a beat after the last token reaches the consumer
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and e1.cached_prefix_tokens(turn2) == 0:
            time.sleep(0.02)
        assert e1.cached_prefix_tokens(turn2) > 0 and e0.cached_prefix_tokens(turn2) == 0
        assert coordinator.cached_prefix_tokens(turn2) == e1.cached_prefix_tokens(turn2)
        warm = _drain(coordinator.submit(turn2))
        assert coordinator._scheduler.stats()["submitted"] == [0, 1]
        assert e1.prefix_cache_hits == 1
        # warm output equals a cold run of the same prompt (bit-identity
        # through the cache, one fleet level up)
        cold = _expected(module, params, cfg, [turn2])[0]
        assert warm == cold
    finally:
        e0.close(wait=False)
        e1.close(wait=False)


def test_worker_death_routes_around_and_census_reflects_it(tiny):
    module, params = tiny
    cfg = _cfg()
    e0, e1 = _engine(tiny, cfg), _engine(tiny, cfg)
    agent = WorkerAgent(e1, process_id=1).start()
    coordinator = FleetCoordinator(
        [LocalHost(e0, host_id=0), RemoteHost(agent.address, host_id=1)]
    )
    try:
        assert _drain(coordinator.submit(PROMPTS[0])) == _expected(module, params, cfg, PROMPTS[:1])[0]
        agent.close(close_engine=True)  # the worker dies
        # every subsequent submission sheds nothing: the probe failure marks
        # host 1 dead and the walk lands on host 0
        got = [_drain(coordinator.submit(p)) for p in PROMPTS]
        assert got == _expected(module, params, cfg, PROMPTS)
        assert coordinator.hosts[1].alive is False
        assert coordinator.host_failures >= 1
        stats = coordinator.stats()
        assert stats["live_hosts"] == 1
        census = coordinator.host_census()
        assert census[1]["alive"] is False and census[1]["replicas"] == 0
        assert coordinator.health()["state"] == "breach"  # a dead host pages
    finally:
        e0.close(wait=False)


def test_all_hosts_dead_raises(tiny):
    cfg = _cfg()
    e1 = _engine(tiny, cfg)
    agent = WorkerAgent(e1, process_id=0).start()
    coordinator = FleetCoordinator([RemoteHost(agent.address, host_id=0)])
    agent.close(close_engine=True)
    with pytest.raises(RuntimeError, match="dead"):
        coordinator.submit(PROMPTS[0])


def test_scale_to_distributes_over_hosts_with_zero_stream_loss(tiny):
    """Cross-host elasticity: the coordinator spreads the fleet total over
    live hosts; streams in flight through both resizes complete exactly."""
    module, params = tiny
    cfg = _cfg(max_new_tokens=16)
    rs0 = ReplicaSet.build(module, params, cfg, replicas=1,
                           slots=2, decode_chunk=2, block_size=8, pool_blocks=64)
    rs1 = ReplicaSet.build(module, params, cfg, replicas=1,
                           slots=2, decode_chunk=2, block_size=8, pool_blocks=64)
    coordinator = FleetCoordinator([LocalHost(rs0, host_id=0), LocalHost(rs1, host_id=1)])
    results: "dict[int, list]" = {}

    def consume(index, stream):
        out = []
        for chunk in stream:
            out.extend(int(t) for t in np.asarray(chunk).ravel())
            time.sleep(0.01)  # keep the stream alive across the resizes
        results[index] = out

    try:
        streams = [coordinator.submit(p) for p in PROMPTS]
        threads = [
            threading.Thread(target=consume, args=(i, s)) for i, s in enumerate(streams)
        ]
        for thread in threads:
            thread.start()
        assert coordinator.scale_to(4) == 4  # 2 per host, warmed before joining
        assert rs0.replicas == 2 and rs1.replicas == 2
        assert coordinator.scale_to(2) == 2  # tails drain with zero loss
        assert rs0.replicas == 1 and rs1.replicas == 1
        for thread in threads:
            thread.join(timeout=120)
        expected = _expected(module, params, cfg, PROMPTS)
        assert [results[i] for i in range(len(PROMPTS))] == expected
        with pytest.raises(ValueError):
            coordinator.scale_to(1)  # below one replica per live host
    finally:
        coordinator.close()


# ------------------------------------------------------------------ shed semantics


def test_shed_mapping_preserves_types_and_retry_after():
    with pytest.raises(TenantThrottled) as excinfo:
        _raise_shed(429, {"kind": "tenant_limit", "detail": "t", "retry_after": 2.5, "tenant": "acme"})
    assert excinfo.value.retry_after_s == 2.5 and excinfo.value.tenant == "acme"
    with pytest.raises(QueueFullError) as excinfo:
        _raise_shed(429, {"kind": "queue_full", "detail": "q", "retry_after": 1.5})
    assert excinfo.value.retry_after_s == 1.5
    with pytest.raises(DeadlineExceeded):
        _raise_shed(503, {"kind": "deadline", "detail": "late"})
    with pytest.raises(RuntimeError):
        _raise_shed(500, {"detail": "boom"})


def test_expired_deadline_sheds_before_routing(tiny):
    cfg = _cfg()
    engine = _engine(tiny, cfg)
    coordinator = FleetCoordinator([LocalHost(engine, host_id=0)])
    try:
        with pytest.raises(DeadlineExceeded):
            coordinator.submit(PROMPTS[0], deadline=time.monotonic() - 1.0)
        assert coordinator.shed_deadline == 1
    finally:
        engine.close(wait=False)


def test_host_roles_validation(tiny):
    cfg = _cfg()
    engine = _engine(tiny, cfg)
    try:
        with pytest.raises(ValueError):
            FleetCoordinator([LocalHost(engine)], host_roles=["prefill", "decode"])
        with pytest.raises(ValueError):
            FleetCoordinator([])
        coordinator = FleetCoordinator(
            [LocalHost(engine, host_id=0)], host_roles=["decode"]
        )
        assert coordinator.roles == ["decode"]
    finally:
        engine.close(wait=False)
