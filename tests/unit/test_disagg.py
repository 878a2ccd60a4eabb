"""Disaggregated prefill/decode serving + elastic resize (docs/serving.md
"Disaggregated and elastic serving").

The pinned contracts:

- **handoff exactness**: a role-split fleet's streams (prefill replica runs
  the prefill, decode replica adopts the KV at admission-complete) are
  token-identical — the first token included — to a single mixed engine
  serving the same prompts, at one block a row and at several, and where
  the row ends inside a block;
- **zero-loss resize**: ``scale_to`` up/down mid-traffic completes every
  in-flight stream exactly (counts asserted), and the autoscaler thread is
  owned and joined by ``close()`` (the TPU008 contract, held to live);
- **decode-side radix insertion**: a finished stream's prompt + generated
  tokens publish into the prefix cache, so the next conversation turn
  cache-hits the whole prior exchange — warm output bit-identical to cold.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.defaults import parse_replica_roles, serve_replica_roles
from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.serving import ContinuousBatcher, ReplicaSet
from unionml_tpu.serving.overload import QueueFullError
from unionml_tpu.serving.replicas import ReplicaScheduler


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


PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9], [7, 1], [6, 6, 6, 2]]


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


def _expected(module, params, cfg, prompts):
    gen = Generator(module, params, cfg)
    return [list(map(int, gen([p])[0])) for p in prompts]


# ------------------------------------------------------------------ knob parsing


def test_parse_replica_roles():
    assert parse_replica_roles("prefill=1,decode=3") == {"prefill": 1, "decode": 3}
    assert parse_replica_roles("decode=2, mixed=1") == {"decode": 2, "mixed": 1}
    assert parse_replica_roles("prefill=0,decode=2") == {"decode": 2}
    for bad in ("turbo=2", "prefill", "prefill=x", "prefill=-1"):
        with pytest.raises(ValueError):
            parse_replica_roles(bad)


def test_serve_replica_roles_env_degrades_on_garbage(monkeypatch, caplog):
    from unionml_tpu._logging import logger

    monkeypatch.setattr(logger, "propagate", True)
    monkeypatch.setenv("UNIONML_TPU_REPLICA_ROLES", "prefill=1,decode=3")
    assert serve_replica_roles() == {"prefill": 1, "decode": 3}
    monkeypatch.setenv("UNIONML_TPU_REPLICA_ROLES", "warp=9")
    with caplog.at_level("WARNING", logger="unionml_tpu"):
        assert serve_replica_roles() == {}
    assert any("warp=9" in record.message for record in caplog.records)
    monkeypatch.delenv("UNIONML_TPU_REPLICA_ROLES")
    assert serve_replica_roles() == {}


def test_resolve_roles_validation():
    expand = ReplicaSet._resolve_roles
    assert expand({"prefill": 1, "decode": 2}, 3) == ["prefill", "decode", "decode"]
    assert expand(["decode", "prefill"], 2) == ["decode", "prefill"]
    assert expand(None, 2) == ["mixed", "mixed"]
    with pytest.raises(ValueError):  # explicit count mismatch is a usage error
        expand({"prefill": 1, "decode": 1}, 3)
    with pytest.raises(ValueError):  # nowhere to hand decode work off to
        expand({"prefill": 2}, 2)
    with pytest.raises(ValueError):
        expand(["prefill", "turbo"], 2)


def test_resolve_roles_env_mismatch_degrades(monkeypatch, caplog):
    from unionml_tpu._logging import logger

    monkeypatch.setattr(logger, "propagate", True)
    monkeypatch.setenv("UNIONML_TPU_REPLICA_ROLES", "prefill=1,decode=3")
    with caplog.at_level("WARNING", logger="unionml_tpu"):
        assert ReplicaSet._resolve_roles(None, 2) == ["mixed", "mixed"]
    assert any("symmetric" in record.message for record in caplog.records)


# ------------------------------------------------------------------ scheduler


def test_scheduler_deprioritizes_prefill_replicas():
    sched = ReplicaScheduler(3)
    # replica 0 is idle but prefill-role: decode work goes to 1 (less loaded
    # of the unflagged), and the flagged replica stays in the walk order
    order, affinity = sched.order([0.0, 1.0, 2.0], deprioritized=[True, False, False])
    assert order == [1, 2, 0] and not affinity
    # everyone flagged degrades to plain least-loaded
    order, _ = sched.order([1.0, 0.0], deprioritized=[True, True])
    assert order == [1, 0]


def test_scheduler_resize_keeps_counts_and_bounds():
    sched = ReplicaScheduler(2, affinity_tokens=2)
    sched.note(0, [1, 2, 3])
    sched.note(1, [4, 5, 6])
    sched.resize(4)
    assert sched.stats()["submitted"] == [1, 1, 0, 0]
    sched.note(3)
    sched.resize(1)
    stats = sched.stats()
    assert stats["submitted"] == [1]
    # affinity entries pointing at removed replicas are dropped
    order, affinity = sched.order([0.0], [4, 5, 6])
    assert not affinity
    with pytest.raises(ValueError):
        sched.resize(0)


# ------------------------------------------------------------------ handoff


#: a 100-token prompt in a 116-position row of 64-position blocks: its second
#: page reaches past the row's end (the export pads it)
LONG_PROMPT = [1 + i % 90 for i in range(100)]


@pytest.mark.parametrize(
    "cfg_overrides, engine_kwargs, prompts",
    [
        ({}, {"decode_chunk": 4}, PROMPTS),  # no sizes: one 64-position block a 28-position row
        ({}, {"decode_chunk": 4, "block_size": 4}, PROMPTS),
        ({"prompt_buckets": (100,)}, {"decode_chunk": 8, "block_size": 64}, [LONG_PROMPT, PROMPTS[1]]),
    ],
    ids=["no_sizes", "blocks_of_4", "row_ends_inside_a_block"],
)
def test_role_split_fleet_token_identical(tiny, cfg_overrides, engine_kwargs, prompts):
    module, params = tiny
    cfg = _cfg(**cfg_overrides)
    expected = _expected(module, params, cfg, prompts)
    fleet = ReplicaSet.build(
        module, params, cfg, replicas=2, roles={"prefill": 1, "decode": 1},
        slots=2, prefill_threshold=0, **engine_kwargs,
    )
    try:
        assert fleet.roles == ["prefill", "decode"]
        got = [_drain(fleet.submit(p)) for p in prompts]
        assert got == expected  # first token included: the handoff is exact
        stats = fleet.stats()
        assert stats["roles"] == {"prefill": 1, "decode": 1, "mixed": 0}
        assert stats["handoffs"]["routed"] == len(prompts)
        assert stats["handoffs"]["exported"] == len(prompts)
        assert stats["handoffs"]["imported"] == len(prompts)
        prefill_stats, decode_stats = stats["per_replica"]
        assert prefill_stats["role"] == "prefill" and decode_stats["role"] == "decode"
        assert prefill_stats["handoff"]["exported"] == len(prompts)
        assert decode_stats["handoff"]["imported"] == len(prompts)
        assert decode_stats["handoff"]["transfer_ms"]["window"] == len(prompts)
        # every decoded token ran on the decode replica; the prefill replica
        # never spent a decode dispatch on these streams
        assert prefill_stats["decode_dispatches"] == 0
        assert [entry["role"] for entry in fleet.replica_loads()] == ["prefill", "decode"]
    finally:
        fleet.close()


def test_role_split_fleet_paged_with_multi_turn_shortcut(tiny):
    module, params = tiny
    cfg = _cfg(prompt_buckets=(32,))
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    fleet = ReplicaSet.build(
        module, params, cfg, replicas=2, roles={"prefill": 1, "decode": 1},
        slots=2, decode_chunk=4, block_size=4, prefix_cache=True, prefill_threshold=0,
    )
    try:
        generated = _drain(fleet.submit(prompt))
        assert generated == _expected(module, params, cfg, [prompt])[0]
        # turn 2 extends the whole prior exchange; the decode replica's radix
        # cache (prompt published at import, generation published at finish)
        # covers it, so the fleet admits there DIRECTLY — no second prefill
        # replica round-trip — and the output still equals a cold run
        turn2 = prompt + generated + [5, 7]
        warm = _drain(fleet.submit(turn2))
        assert warm == _expected(module, params, cfg, [turn2])[0]
        stats = fleet.stats()
        assert stats["handoffs"]["routed"] == 1
        assert stats["handoffs"]["shortcuts"] == 1
        decode_stats = stats["per_replica"][1]
        assert decode_stats["prefix_cache"]["hits"] == 1
        assert decode_stats["prefix_cache"]["tokens_avoided"] > len(prompt)
    finally:
        fleet.close()


def test_export_finishes_outright_without_handoff(tiny):
    module, params = tiny
    cfg = _cfg()
    fleet = ReplicaSet.build(
        module, params, cfg, replicas=2, roles={"prefill": 1, "decode": 1},
        slots=2, decode_chunk=4, prefill_threshold=0,
    )
    try:
        # budget 1: the prompt-sampled token IS the stream — the prefill
        # replica finishes it locally, nothing crosses to the decode replica
        tokens = _drain(fleet.submit(PROMPTS[0], max_new_tokens=1))
        assert tokens == _expected(module, params, cfg, [PROMPTS[0]])[0][:1]
        stats = fleet.stats()
        assert stats["handoffs"]["exported"] == 0
        assert stats["handoffs"]["imported"] == 0
    finally:
        fleet.close()


def test_short_prompts_skip_the_prefill_tier(tiny):
    module, params = tiny
    cfg = _cfg()
    fleet = ReplicaSet.build(
        module, params, cfg, replicas=2, roles={"prefill": 1, "decode": 1},
        slots=2, decode_chunk=4, prefill_threshold=6,
    )
    try:
        short, long_ = [7, 1], [9, 2, 6, 5, 3, 5, 8, 9]
        assert _drain(fleet.submit(short)) == _expected(module, params, cfg, [short])[0]
        assert _drain(fleet.submit(long_)) == _expected(module, params, cfg, [long_])[0]
        stats = fleet.stats()
        # only the >= threshold prompt disaggregated; the short one admitted
        # directly on the (deprioritized-last walk's) decode replica
        assert stats["handoffs"]["routed"] == 1
        assert stats["per_replica"][1]["handoff"]["imported"] == 1
    finally:
        fleet.close()


def test_export_requires_no_speculative_and_handoff_attr_surface(tiny):
    module, params = tiny
    engine = ContinuousBatcher._single(
        Generator(module, params, _cfg()), slots=2, decode_chunk=4, role="prefill"
    )
    try:
        stream = engine.submit(PROMPTS[0], export_handoff=True)
        first = _drain(stream)
        assert len(first) == 1
        payload = stream.handoff
        assert payload is not None
        # an engine built with no sizes ships pages: one 64-position block here
        assert "row" not in payload and payload["block_size"] == 64
        assert all(layer["k"].shape[1:3] == (1, 64) for layer in payload["pages"])
        assert payload["first"] == first[0]
        assert payload["prompt"] == PROMPTS[0]
        assert payload["produced"] == 1 and payload["echo"] == first
        stats = engine.stats()
        assert stats["role"] == "prefill" and stats["handoff"]["exported"] == 1
    finally:
        engine.close()
    with pytest.raises(ValueError):
        ContinuousBatcher._single(Generator(module, params, _cfg()), role="turbo")


def test_quiesced_engine_sheds_and_keeps_draining(tiny):
    module, params = tiny
    engine = ContinuousBatcher._single(Generator(module, params, _cfg()), slots=2)
    try:
        stream = engine.submit(PROMPTS[0])
        engine.quiesce()
        with pytest.raises(QueueFullError):
            engine.submit(PROMPTS[1])
        # already-submitted work drains to completion regardless
        assert _drain(stream) == _expected(module, params, _cfg(), [PROMPTS[0]])[0]
    finally:
        engine.close()


def test_quiesced_engine_still_bounces_once_the_resize_closed_it(tiny):
    """The second phase of a scale-down closes the drained engine; a routing
    snapshot taken before the engine left the fleet may submit to it even
    later, and must be walked to a sibling (``QueueFullError``), not failed
    (``RuntimeError``: what an engine closed for good answers)."""
    module, params = tiny
    engine = ContinuousBatcher._single(Generator(module, params, _cfg()), slots=2)
    engine.quiesce()
    engine.close()
    with pytest.raises(QueueFullError):
        engine.submit(PROMPTS[0])
    closed = ContinuousBatcher._single(Generator(module, params, _cfg()), slots=2)
    closed.close()
    with pytest.raises(RuntimeError, match="closed"):
        closed.submit(PROMPTS[0])


# ------------------------------------------------------ decode-side insertion


def test_decode_side_insertion_warm_equals_cold(tiny):
    module, params = tiny
    cfg = _cfg(prompt_buckets=(32,))
    engine = ContinuousBatcher._single(
        Generator(module, params, cfg), slots=2, decode_chunk=4,
        block_size=4, pool_blocks=64, prefix_cache=True,
    )
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]  # two full blocks
        generated = _drain(engine.submit(prompt))
        assert len(generated) == 8
        # prompt(8) + generated-with-written-KV(7) = 15 -> 3 full blocks: one
        # MORE than the prompt-only publish at finalize could cover
        turn2 = prompt + generated + [5, 7]
        cached = engine.cached_prefix_tokens(turn2)
        assert cached > len(prompt)
        cold = _expected(module, params, cfg, [turn2])[0]
        warm = _drain(engine.submit(turn2))
        assert warm == cold
        stats = engine.stats()["prefix_cache"]
        assert stats["hits"] == 1 and stats["tokens_avoided"] == cached
    finally:
        engine.close()


# ------------------------------------------------------------------ elasticity


def test_scale_to_zero_loss_mid_traffic(tiny):
    module, params = tiny
    cfg = _cfg()
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, 96, size=int(rng.integers(2, 10))))) for _ in range(10)]
    expected = _expected(module, params, cfg, prompts)
    fleet = ReplicaSet.build(module, params, cfg, replicas=1, slots=2, decode_chunk=4)
    try:
        results = [None] * len(prompts)

        def worker(i):
            results[i] = _drain(fleet.submit(prompts[i]))

        first_wave = [threading.Thread(target=worker, args=(i,)) for i in range(5)]
        for t in first_wave:
            t.start()
        assert fleet.scale_to(2) == 2
        assert fleet.replicas == 2
        second_wave = [threading.Thread(target=worker, args=(i,)) for i in range(5, 10)]
        for t in second_wave:
            t.start()
        assert fleet.scale_to(1) == 1
        assert fleet.replicas == 1
        for t in first_wave + second_wave:
            t.join(timeout=180)
        # zero lost streams: every submission completed with exact tokens
        assert results == expected
        stats = fleet.stats()
        assert sum(stats["scheduler"]["submitted"][:1]) <= len(prompts)
        assert stats["resize"]["scaled_up"] == 1 and stats["resize"]["scaled_down"] == 1
    finally:
        fleet.close()


def test_scale_guards(tiny):
    module, params = tiny
    cfg = _cfg()
    fleet = ReplicaSet.build(module, params, cfg, replicas=1, slots=2)
    try:
        with pytest.raises(ValueError):
            fleet.scale_to(0)
        assert fleet.spare_capacity() > 0  # mesh-less: round-robin placement
    finally:
        fleet.close()
    # a set built from pre-made generators retains no construction template
    bare = ReplicaSet(
        [Generator(module, params, cfg), Generator(module, params, cfg)],
        slots=2,
    )
    try:
        assert bare.spare_capacity() == 0
        with pytest.raises(RuntimeError):
            bare.scale_to(3)
        bare.scale_to(1)  # shrinking needs no template
        assert bare.replicas == 1
    finally:
        bare.close()


def test_autoscaler_scales_on_pressure_and_close_joins(tiny, monkeypatch):
    module, params = tiny
    cfg = _cfg()
    fleet = ReplicaSet.build(module, params, cfg, replicas=1, slots=2)
    try:
        pressure = {"value": 10.0}
        monkeypatch.setattr(
            type(fleet), "_autoscale_pressure", lambda self: pressure["value"]
        )
        fleet.configure_autoscaler(high=1.0, low=0.5, interval_s=0.05, min_replicas=1)
        deadline = time.monotonic() + 60.0
        while fleet.replicas < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert fleet.replicas >= 2
        pressure["value"] = 0.0
        while fleet.replicas > 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert fleet.replicas == 1
        # the replica leaves the routing list first and counts as scaled down
        # once it has drained and closed
        while fleet.stats()["resize"]["scaled_down"] < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        stats = fleet.stats()
        assert stats["resize"]["scaled_up"] >= 1 and stats["resize"]["scaled_down"] >= 1
        assert stats["resize"]["autoscaler"]["high"] == 1.0
        thread = fleet._autoscale_thread
    finally:
        fleet.close()
    assert thread is not None and not thread.is_alive()  # TPU008, held to live


def test_configure_autoscaler_validation(tiny):
    module, params = tiny
    fleet = ReplicaSet.build(module, params, _cfg(), replicas=1, slots=2, autoscale=False)
    try:
        for kwargs in (
            dict(high=0.0),
            dict(high=1.0, low=2.0),
            dict(high=1.0, interval_s=0.0),
            dict(high=1.0, min_replicas=0),
            dict(high=1.0, role="turbo"),
        ):
            with pytest.raises(ValueError):
                fleet.configure_autoscaler(**kwargs)
    finally:
        fleet.close()


# ---------------------------------------------------------------- app surface


class _FakeEngine:
    role = "decode"

    def health(self):
        return {"score": 1.0, "state": "ok", "state_code": 0, "enabled": False}


class _FakeFleet:
    def __init__(self):
        self.batchers = (_FakeEngine(),)
        self.calls = []

    def scale_to(self, n, role=None):
        if n > 4:
            raise RuntimeError("no spare submesh")
        self.calls.append((n, role))
        return n


def test_debug_scale_endpoint(sklearn_model):
    import asyncio

    sklearn_model.train(hyperparameters={"max_iter": 500})
    from unionml_tpu.serving.app import ServingApp

    app = ServingApp(sklearn_model)

    def dispatch(method, path, body=b""):
        async def run():
            app.startup()
            return await app.server.dispatch(method, path, body)

        return asyncio.run(run())

    status, payload, _ = dispatch("POST", "/debug/scale", b'{"replicas": 2}')
    assert status == 400  # no elastic generation fleet on this app
    fleet = _FakeFleet()
    sklearn_model.generation_batcher = fleet
    try:
        status, payload, _ = dispatch("POST", "/debug/scale", b'{"replicas": 3, "role": "decode"}')
        assert status == 200 and payload["replicas"] == 3
        assert fleet.calls == [(3, "decode")]
        # the role census rides the health payload for role-split fleets
        assert payload["health"]["replicas"][0]["role"] == "decode"
        status, payload, _ = dispatch("POST", "/debug/scale", b'{"replicas": 0}')
        assert status == 400
        status, payload, _ = dispatch("POST", "/debug/scale", b'{"replicas": 9}')
        assert status == 400 and "spare" in payload["detail"]
        status, payload, _ = dispatch("POST", "/debug/scale", b'{"replicas": 2, "role": "turbo"}')
        assert status == 400
    finally:
        del sklearn_model.generation_batcher


def test_replica_roles_env_drives_engine_delegation(tiny, monkeypatch):
    module, params = tiny
    monkeypatch.delenv("UNIONML_TPU_DP_REPLICAS", raising=False)
    monkeypatch.setenv("UNIONML_TPU_REPLICA_ROLES", "prefill=1,decode=1")
    monkeypatch.setenv("UNIONML_TPU_PREFILL_THRESHOLD", "0")
    fleet = ContinuousBatcher(Generator(module, params, _cfg()), slots=2, decode_chunk=4)
    try:
        # --replica-roles alone implies the fleet size and the role split,
        # through the same transparent delegation --dp-replicas uses
        assert isinstance(fleet, ReplicaSet)
        assert fleet.roles == ["prefill", "decode"]
        prompt = PROMPTS[0]
        assert _drain(fleet.submit(prompt)) == _expected(module, params, _cfg(), [prompt])[0]
        assert fleet.stats()["handoffs"]["exported"] == 1
    finally:
        fleet.close()
