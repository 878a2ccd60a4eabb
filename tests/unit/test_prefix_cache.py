"""Radix prefix cache correctness.

Two rings: (1) the host-side :class:`RadixPrefixCache` tree itself —
insert/match/split, block refcounts, LRU eviction, sub-block (copy-on-write)
matching; (2) the engine integration — cached-prefix admissions must be
BIT-IDENTICAL to cold prefills (the same bar PR 4 held for chunked vs
monolithic), eviction under pool pressure must never deadlock admission, and
with the cache disabled the engine's stats carry no trace of it.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.serving import ContinuousBatcher
from unionml_tpu.serving.prefix_cache import RadixPrefixCache


@pytest.fixture(scope="module")
def tiny_gen():
    config = LlamaConfig.tiny(
        vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _sequential_expected(module, params, cfg, prompts, prefix_tokens=None):
    gen = Generator(module, params, cfg)
    prefix = gen.cache_prefix(prefix_tokens) if prefix_tokens else None
    expected = []
    for p in prompts:
        row = gen([p], prefix=prefix)[0] if prefix is not None else gen([p])[0]
        if cfg.eos_id is not None:
            hits = np.nonzero(row == cfg.eos_id)[0]
            if hits.size:
                row = row[: int(hits[0]) + 1]
        expected.append(list(row))
    return expected


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


# --------------------------------------------------------------------- tree ring


def test_tree_insert_match_roundtrip():
    tree = RadixPrefixCache(4)
    tree.insert(list(range(8)), [10, 11])
    m, blocks = tree.match(list(range(8)) + [99])
    assert m == 8 and blocks == [10, 11]
    # a shorter probe matches a prefix of the run (sub-block: CoW territory)
    m, blocks = tree.match(list(range(6)))
    assert m == 6 and blocks == [10, 11]  # ceil(6/4) = 2 blocks, last partial
    assert tree.match_len(list(range(5))) == 5
    # disjoint prompt: no match
    assert tree.match([50, 51, 52]) == (0, [])


def test_tree_split_on_divergence_keeps_shared_blocks():
    tree = RadixPrefixCache(4)
    tree.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
    # diverges in the SECOND block: the first stays shared, the edge splits
    kept = tree.insert([1, 2, 3, 4, 9, 9, 9, 9], [20, 21])
    assert kept == 1  # block 20 duplicated the cached [1,2,3,4] run; 21 consumed
    assert tree.match([1, 2, 3, 4, 5, 6, 7, 8]) == (8, [10, 11])
    assert tree.match([1, 2, 3, 4, 9, 9, 9, 9]) == (8, [10, 21])
    assert tree.nodes() == 3 and tree.cached_blocks() == 3
    # mid-block divergence against a sibling still yields the partial tail
    m, blocks = tree.match([1, 2, 3, 4, 9, 9, 0, 0])
    assert m == 6 and blocks == [10, 21]


def test_tree_refcounts_block_eviction():
    tree = RadixPrefixCache(4)
    tree.insert([1, 2, 3, 4], [10])
    tree.insert([5, 6, 7, 8], [20])
    m, blocks = tree.match([1, 2, 3, 4], pin=True)
    assert tree.pinned_blocks() == 1
    freed = tree.evict(8)
    assert freed == [20] and tree.evictions == 1  # the pinned run survives
    tree.release(blocks)
    assert tree.pinned_blocks() == 0
    assert sorted(tree.evict(8)) == [10]


def test_tree_lru_eviction_order_and_pinned_ancestor_shield():
    tree = RadixPrefixCache(4)
    tree.insert([1, 2, 3, 4, 5, 6, 7, 8], [10, 11])
    tree.insert([1, 2, 3, 4, 9, 9, 9, 9], [20, 21])  # splits; parent holds [10]
    tree.match([1, 2, 3, 4, 5, 6, 7, 8])  # refresh the [11] leaf's recency
    freed = tree.evict(1)
    assert freed == [21]  # the stale leaf goes first
    # pin the remaining leaf: its ancestor chain is shielded
    _, pinned = tree.match([1, 2, 3, 4, 5, 6, 7, 8], pin=True)
    assert tree.evictable_blocks() == 0
    assert tree.evict(8) == []
    tree.release(pinned)
    assert tree.evictable_blocks() == 2
    assert sorted(tree.evict(8)) == [10, 11]


def test_tree_insert_alignment_guard():
    tree = RadixPrefixCache(4)
    with pytest.raises(ValueError, match="block-aligned"):
        tree.insert([1, 2, 3], [10])


# ------------------------------------------------------------------- engine ring


PROMPTS_SHARED = [list(range(1, 21)) + [70 + i] for i in range(4)]


#: 70 shared tokens: past one block of the 64 an engine built with no sizes gets
PROMPTS_SHARED_LONG = [[1 + i % 60 for i in range(70)] + [70 + i] for i in range(4)]


@pytest.mark.parametrize(
    "sizes, bucket, prompts, avoided",
    [
        # decode-side insertion publishes the first stream's prompt+generated
        # run, so later prompts match their WHOLE 20-token shared prefix (the
        # partial third block rides CoW), not just the 2 fully-shared blocks
        ({"block_size": 8, "admit_chunk": 8}, 32, PROMPTS_SHARED, 20),
        # no sizes: blocks of 64, so the tree holds the one full block
        ({}, 128, PROMPTS_SHARED_LONG, 64),
    ],
    ids=["blocks_of_8", "no_sizes"],
)
def test_cached_prefix_streams_match_cold_and_sequential(tiny_gen, sizes, bucket, prompts, avoided):
    """The headline contract: warm (cache-hit) streams == cold (first-visit)
    streams == sequential Generator runs, token for token."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=10, temperature=0.0, prompt_buckets=(bucket,))
    expected = _sequential_expected(module, params, cfg, prompts)

    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=2, decode_chunk=4, prefix_cache=True, **sizes
    )
    try:
        results = [_drain(batcher.submit(p)) for p in prompts]
        assert results == expected
        stats = batcher.stats()["prefix_cache"]
        assert stats["hits"] == len(prompts) - 1  # all but the first
        assert stats["misses"] == 1
        assert stats["tokens_avoided"] == avoided * (len(prompts) - 1)
        # a finished prompt's own full sequence is cached as far as whole
        # blocks reach: the probe caps at total-1 (the last token always prefills)
        full = (len(prompts[0]) + cfg.max_new_tokens - 1) // batcher.block_size * batcher.block_size
        assert batcher.cached_prefix_tokens(prompts[0]) == min(full, len(prompts[0]) - 1)
    finally:
        batcher.close()


@pytest.mark.slow  # ~10s; thread-contended hits are re-pinned by the emulated
# tp=2/dp=2 ring, and the sequential identity test above stays in tier-1
def test_cached_prefix_concurrent_submissions(tiny_gen):
    """Hits under thread contention: concurrent warm submissions race the
    tree's pins/inserts through the engine lock and stay exact."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(32,))
    expected = _sequential_expected(module, params, cfg, PROMPTS_SHARED)

    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=len(PROMPTS_SHARED), decode_chunk=3,
        block_size=8, admit_chunk=8, max_admissions=2, prefix_cache=True,
    )
    try:
        warm = _drain(batcher.submit(PROMPTS_SHARED[0]))  # publish the prefix
        assert warm == expected[0]
        results = [None] * len(PROMPTS_SHARED)

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS_SHARED[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(PROMPTS_SHARED))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert results == expected
        assert batcher.stats()["prefix_cache"]["hits"] >= len(PROMPTS_SHARED)
    finally:
        batcher.close()


def test_cow_divergence_inside_shared_tail_block(tiny_gen):
    """A prompt diverging mid-block reuses the partially shared tail block via
    copy-on-write (gathered into its private copy) — counted, and exact."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(32,))
    long_a = list(range(1, 28))                       # caches 3 full blocks (24 tokens)
    long_b = list(range(1, 21)) + [90, 91, 92]        # shares 20: mid-block divergence
    expected = _sequential_expected(module, params, cfg, [long_a, long_b])

    # no admit_chunk: cache hits still chunk (at block_size) — the cache works
    # on engines that never enabled stall-free admission
    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=2, decode_chunk=3,
        block_size=8, prefix_cache=True,
    )
    try:
        results = [_drain(batcher.submit(p)) for p in (long_a, long_b)]
        assert results == expected
        stats = batcher.stats()["prefix_cache"]
        assert stats["cow_copies"] == 1
        assert stats["tokens_avoided"] == 20
    finally:
        batcher.close()


def test_static_prefix_composes_and_tail_is_cached(tiny_gen):
    """With a configured shared prefix, the radix key covers (prefix + prompt):
    matches extend past the static pages into per-request prompts, the
    prefix's partial tail block is cached like any run (the satellite fix),
    and the dropped-tail count is surfaced in stats."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(32,))
    prefix_tokens = list(range(1, 12))  # 11 tokens: 1 full block of 8 + 3-token tail
    suffixes = [
        [60, 61, 62, 63, 64, 65, 66, 67, 68, 69],
        [60, 61, 62, 63, 64, 65, 66, 67, 68, 70],
    ]
    expected = _sequential_expected(module, params, cfg, suffixes, prefix_tokens=prefix_tokens)

    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix_tokens),
        block_size=8, admit_chunk=8, prefix_cache=True,
    )
    try:
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
        stats = batcher.stats()
        assert stats["kv_blocks"]["shared_prefix_tail_tokens"] == 3
        assert stats["prefix_cache"]["hits"] == 1  # second suffix rides the first's blocks
        assert stats["prefix_cache"]["tokens_avoided"] > 0
    finally:
        batcher.close()


def test_eviction_under_pool_pressure_never_deadlocks(tiny_gen):
    """A minimum-size pool fills with cached runs; later admissions must evict
    idle cache instead of deadlocking (the allocator-exhaustion contract)."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    gen = Generator(module, params, cfg)
    probe = ContinuousBatcher(gen, slots=2, decode_chunk=3, block_size=8, prefix_cache=True)
    min_pool = probe.max_blocks
    probe.close()
    prompts = [list(range(i, i + 9)) for i in range(1, 60, 10)]
    expected = _sequential_expected(module, params, cfg, prompts)

    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=3, block_size=8, pool_blocks=min_pool,
        admit_chunk=8, prefix_cache=True,
    )
    try:
        results = [_drain(batcher.submit(p)) for p in prompts]
        assert results == expected
        assert batcher.stats()["prefix_cache"]["evictions"] > 0
    finally:
        batcher.close()


def test_preemption_resume_rides_its_own_cached_prefix(tiny_gen):
    """Pool exhaustion preempts the youngest resident; its resume prompt
    (original + echo) re-matches the blocks its own admission published, and
    the stream stays exact end to end."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    long_prompts = [list(range(1, 15)), list(range(40, 54))]
    expected = _sequential_expected(module, params, cfg, long_prompts)

    gen = Generator(module, params, cfg)
    probe = ContinuousBatcher(gen, slots=2, decode_chunk=8, block_size=8, prefix_cache=True)
    pool = 2 * probe._blocks_initial(long_prompts[0], cfg.max_new_tokens)
    assert pool < 2 * probe._blocks_lifetime(long_prompts[0], cfg.max_new_tokens)
    probe.close()
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=8, block_size=8, pool_blocks=pool,
        admit_chunk=8, prefix_cache=True,
    )
    try:
        results = [None] * 2

        def worker(i):
            results[i] = _drain(batcher.submit(long_prompts[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert results == expected
        stats = batcher.stats()
        assert stats["kv_blocks"]["preemptions"] >= 1
        # the resume re-used its own published prefix: at least one hit
        assert stats["prefix_cache"]["hits"] >= 1
    finally:
        batcher.close()


@pytest.mark.slow  # ~8s; pin release also rides every finish/preempt path the
# tier-1 identity and eviction tests exercise
def test_cancel_mid_stream_releases_pins(tiny_gen):
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(32,))
    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=2, decode_chunk=2,
        block_size=8, admit_chunk=8, prefix_cache=True,
    )
    try:
        _drain(batcher.submit(PROMPTS_SHARED[0]))  # publish
        stream = batcher.submit(PROMPTS_SHARED[1])
        next(iter(stream))
        stream.close()
        # pins must drain back to the permanent zero once the engine reaps
        deadline = [p for p in range(200)]
        for _ in deadline:
            with batcher._lock:
                clear = all(not s.pins for s in batcher._sessions.values())
            if clear and batcher.stats()["prefix_cache"]["pinned_blocks"] == 0:
                break
            import time
            time.sleep(0.05)
        assert batcher.stats()["prefix_cache"]["pinned_blocks"] == 0
        # the engine keeps serving exact streams afterwards
        expected = _sequential_expected(module, params, cfg, [PROMPTS_SHARED[2]])
        assert _drain(batcher.submit(PROMPTS_SHARED[2])) == expected[0]
    finally:
        batcher.close()


@pytest.mark.slow  # ~5s of warmup compiles; the reset path itself is host-only
def test_warmup_resets_cache_to_clean_tree(tiny_gen):
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(16,))
    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=2, decode_chunk=2,
        block_size=8, admit_chunk=8, prefix_cache=True,
    )
    try:
        batcher.warmup()
        stats = batcher.stats()["prefix_cache"]
        assert stats["hits"] == 0 and stats["misses"] == 0
        assert stats["cached_blocks"] == 0 and stats["nodes"] == 0
        # pool fully recovered: nothing leaked into the tree
        assert batcher.stats()["kv_blocks"]["used"] == 0
    finally:
        batcher.close()


@pytest.mark.slow  # ~8s; off-mode paged behavior is already pinned by the whole
# pre-cache test_continuous ring — this adds only the no-new-stats assertion
def test_disabled_cache_leaves_engine_and_stats_untouched(tiny_gen):
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,))
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=2, decode_chunk=3, block_size=8)
    try:
        expected = _sequential_expected(module, params, cfg, [[5, 6, 7]])
        assert _drain(batcher.submit([5, 6, 7])) == expected[0]
        stats = batcher.stats()
        assert "prefix_cache" not in stats
        assert batcher.cached_prefix_tokens([5, 6, 7]) == 0
        assert batcher._radix is None
    finally:
        batcher.close()


def test_prefix_cache_knob_validation(tiny_gen, monkeypatch):
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(16,))
    # every engine has a pool, so the keyword and the env export both work
    # on an engine built with no sizes
    unsized = ContinuousBatcher(Generator(module, params, cfg), slots=1, prefix_cache=True)
    assert unsized._radix is not None
    unsized.close()
    monkeypatch.setenv("UNIONML_TPU_PREFIX_CACHE", "1")
    unsized = ContinuousBatcher(Generator(module, params, cfg), slots=1)
    assert unsized._radix is not None
    unsized.close()
    sized = ContinuousBatcher(Generator(module, params, cfg), slots=1, block_size=8)
    assert sized._radix is not None
    sized.close()
    monkeypatch.setenv("UNIONML_TPU_PREFIX_CACHE", "0")
    off = ContinuousBatcher(Generator(module, params, cfg), slots=1, block_size=8)
    assert off._radix is None
    off.close()


def test_prefix_cache_rejects_tokenless_prefix_and_draft(tiny_gen):
    import dataclasses

    from unionml_tpu.models.generate import DraftSpec, PrefixCache

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(16,))
    gen = Generator(module, params, cfg)
    real = gen.cache_prefix([1, 2, 3, 4])
    handbuilt = PrefixCache(layers=real.layers, length=real.length, tokens=None)
    with pytest.raises(ValueError, match="token ids"):
        ContinuousBatcher(
            Generator(module, params, cfg), slots=1, block_size=8,
            prefix=handbuilt, prefix_cache=True,
        )
    spec_cfg = dataclasses.replace(
        cfg, draft=DraftSpec(module=module, params=params, gamma=2)
    )
    with pytest.raises(ValueError, match="speculative"):
        ContinuousBatcher(
            Generator(module, params, spec_cfg), slots=1, block_size=8, prefix_cache=True
        )
