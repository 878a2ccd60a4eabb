"""Stall-free (chunked) admission token-identity on the emulated 8-device mesh.

Oracle: slicing an admission's prefill into chunks interleaved with decode must
be invisible in the tokens — every stream from a chunked engine equals both the
monolithic engine's stream and a sequential single-device ``Generator`` run
(greedy, f32), across the dp/tp matrix: a tp=2 engine, a dp=2 x tp=2
``ReplicaSet`` (knobs flow per replica through the delegation path), and the
paged preempt-resume + shared-prefix edge cases the engine must survive
mid-chunking.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig, llama_partition_rules
from unionml_tpu.parallel import MeshSpec
from unionml_tpu.serving import ContinuousBatcher, ReplicaSet

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 emulated devices")

PROMPTS = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9, 7, 1, 6, 2], [7, 1], [6, 6, 6, 2], [5, 5], [8]]


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
    base = dict(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    base.update(overrides)
    return GenerationConfig(**base)


def _expected(module, params, prompts, cfg=None):
    gen = Generator(module, params, cfg or _cfg())
    return [list(gen([p])[0]) for p in prompts]


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


def _drain_concurrently(streams):
    results = [None] * len(streams)

    def worker(i):
        results[i] = _drain(streams[i])

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(streams))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    return results


def test_tp2_chunked_admission_matches_monolithic_and_sequential(tiny):
    """tp=2 leg of the matrix: chunked admission over a model-sharded engine
    emits EXACTLY the single-device sequential run's tokens — which IS the
    monolithic engine's output (the existing TP continuous tests pin
    monolithic == sequential), so slicing composes with TP collectives."""
    module, params = tiny
    expected = _expected(module, params, PROMPTS)
    mesh = MeshSpec(data=1, model=2).build(devices=jax.devices()[:2])
    gen = Generator(module, params, _cfg(), mesh=mesh, partition_rules=llama_partition_rules())
    batcher = ContinuousBatcher(gen, slots=3, decode_chunk=4, admit_chunk=4)
    try:
        streams = [batcher.submit(p) for p in PROMPTS]
        assert _drain_concurrently(streams) == expected
        stats = batcher.stats()
        assert stats["prefill"]["mode"] == "chunked" and stats["prefill"]["chunks"] > 0
    finally:
        batcher.close()


def test_dp2_tp2_replicaset_chunked_admission_token_identical(tiny):
    """dp=2 x tp=2 leg: the ContinuousBatcher delegation path carries the
    stall-free knobs to every replica engine, and the fleet's streams still
    equal the sequential single-device run."""
    module, params = tiny
    expected = _expected(module, params, PROMPTS)
    mesh = MeshSpec(data=2, model=2).build(devices=jax.devices()[:4])
    gen = Generator(module, params, _cfg(), mesh=mesh, partition_rules=llama_partition_rules())
    engine = ContinuousBatcher(gen, slots=2, decode_chunk=4, admit_chunk=4, prefill_budget=4)
    try:
        assert isinstance(engine, ReplicaSet) and engine.replicas == 2
        for batcher in engine.batchers:
            assert batcher.admit_chunk == 4 and batcher.prefill_budget == 4
        streams = [engine.submit(p) for p in PROMPTS]
        assert _drain_concurrently(streams) == expected
        stats = engine.stats()
        assert stats["prefill_chunks"] > 0  # fleet-wide counter aggregated
        for entry in stats["per_replica"]:
            assert {"ttft_ms", "tbt_ms", "prefill"} <= set(entry)
    finally:
        engine.close()


def test_chunked_paged_preempt_resume_and_shared_prefix(tiny):
    """The two admission edge cases the chunked engine must survive, in one
    paged ring: a shared prefix (chunks start past the pasted prefix rows)
    and pool-pressure preemption (the resume's exact width falls back to a
    monolithic prefill when its aligned width would overflow) — both
    token-identical to the sequential dense run."""
    module, params = tiny
    cfg = _cfg(max_new_tokens=12, prompt_buckets=(8, 16))
    prefix = [7, 7, 3, 9, 1, 2, 5, 11]
    suffixes = [[3, 1, 4], [9, 2, 6, 5], [8, 4, 4, 1, 2, 6]]
    expected = _expected(module, params, [prefix + s for s in suffixes], cfg)

    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix),
        block_size=8, admit_chunk=4,
    )
    try:
        assert len(batcher._shared_prefix_blocks) == 1  # 8 // 8: pages shared
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
        assert batcher.stats()["prefill"]["chunks"] > 0
    finally:
        batcher.close()

    # preemption leg: pool too small for two long residents; the evicted
    # stream resumes (prompt + echo outgrows the bucket set) and must stay
    # exact under chunked admission
    cfg = _cfg(max_new_tokens=16)
    long_prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7], [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9, 4]]
    expected = _expected(module, params, long_prompts, cfg)
    gen = Generator(module, params, cfg)
    probe = ContinuousBatcher(gen, slots=2, decode_chunk=8, block_size=8, admit_chunk=8)
    pool = 2 * probe._blocks_initial(long_prompts[0], cfg.max_new_tokens)
    probe.close()
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=8, block_size=8, pool_blocks=pool, admit_chunk=8
    )
    try:
        streams = [batcher.submit(p) for p in long_prompts]
        assert _drain_concurrently(streams) == expected
        assert batcher.stats()["kv_blocks"]["preemptions"] > 0
    finally:
        batcher.close()


def test_tp2_wide_admission_calls_match_sequential_and_warm_every_width(tiny):
    """tp=2 leg of the power-of-two calls: a budget of four chunks runs an iteration's columns in calls 4, 8 and 16
    wide over the model-sharded engine, the streams (admitted together, and a radix hit that starts mid-prompt)
    equal the single-device sequential run, and after ``warmup()`` that traffic traces no prefill program."""
    module, params = tiny
    cfg = _cfg(prompt_buckets=(16, 32))
    prompts = [list(range(1, 30)), [3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9, 7, 1, 6, 2]]
    hit = list(range(1, 21)) + [5, 5]
    expected = _expected(module, params, prompts + [hit], cfg)
    mesh = MeshSpec(data=1, model=2).build(devices=jax.devices()[:2])
    gen = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    batcher = ContinuousBatcher(
        gen, slots=3, decode_chunk=4, block_size=4, admit_chunk=4, prefill_budget=16, max_admissions=2, prefix_cache=True,
    )
    try:
        assert batcher._prefill_widths == (4, 8, 16)
        batcher.warmup()
        traces = gen.prefill_traces
        streams = [batcher.submit(p) for p in prompts]
        served = _drain_concurrently(streams)
        served.append(_drain(batcher.submit(hit)))
        assert served == expected
        stats = batcher.stats()
        assert max(stats["prefill_widths"]) > 4 and set(stats["prefill_widths"]) <= {4, 8, 16}
        assert stats["prefix_cache"]["hits"] >= 1 and gen.prefill_traces == traces
    finally:
        batcher.close()
