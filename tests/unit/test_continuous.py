"""Continuous batching correctness.

Oracle: each concurrent stream's tokens must equal a sequential
``Generator.__call__([prompt])`` run (greedy, f32) — resident rows are
independent under the cache contract, so sharing decode dispatches must be
invisible in the output. Also pins slot reuse under contention, eos/budget
exits, and engine-failure isolation.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig
from unionml_tpu.serving import ContinuousBatcher


@pytest.fixture(scope="module")
def tiny_gen():
    config = LlamaConfig.tiny(
        vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


PROMPTS = [[3, 14, 15, 92, 6], [27, 1], [8, 2, 8, 1, 8, 2, 8], [44, 9], [61, 5, 2], [7]]


def _sequential_expected(module, params, cfg, prompts):
    """Per-prompt sequential decode, truncated at the first eos (the stream
    contract: emit the eos, then end)."""
    gen = Generator(module, params, cfg)
    expected = []
    for p in prompts:
        row = gen([p])[0]
        if cfg.eos_id is not None:
            hits = np.nonzero(row == cfg.eos_id)[0]
            if hits.size:
                row = row[: int(hits[0]) + 1]
        expected.append(list(row))
    return expected


def _drain(stream):
    return [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]


def test_concurrent_streams_match_sequential(tiny_gen):
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS)

    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=len(PROMPTS), decode_chunk=4)
    try:
        results = [None] * len(PROMPTS)

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert results == expected
        # concurrency actually shared dispatches: far fewer than per-request loops
        assert batcher.decoded_rows > batcher.decode_dispatches
        # built with no sizes: blocks of 64 and a pool that holds every slot at
        # its worst case, so nobody waited for blocks and nobody was preempted
        stats = batcher.stats()["kv_blocks"]
        assert stats["block_size"] == 64 and stats["total"] == batcher.slots * batcher.max_blocks
        assert stats["used"] == 0 and stats["preemptions"] == 0
    finally:
        batcher.close()


def test_slot_contention_queues_and_reuses_slots(tiny_gen):
    """More requests than slots: the overflow waits for a free slot and still
    produces exact tokens — slot rows are fully overwritten on admission."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS)

    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=2, decode_chunk=3)
    try:
        results = [None] * len(PROMPTS)

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert results == expected
    finally:
        batcher.close()


def test_eos_frees_slot_early(tiny_gen):
    """A row hitting eos leaves at the next chunk boundary; its tokens end with
    the eos and its slot admits the next waiter."""
    module, params = tiny_gen
    free = Generator(
        module, params, GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    )(PROMPTS[:1])
    eos = int(free[0][3])  # an id the sequence actually emits mid-stream
    cfg = GenerationConfig(
        max_new_tokens=16, temperature=0.0, prompt_buckets=(16,), eos_id=eos, pad_id=0
    )
    expected = _sequential_expected(module, params, cfg, PROMPTS[:3])

    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=1, decode_chunk=4)
    try:
        # slots=1 forces strict sequencing through one slot; eos/budget exits
        # must free it or the later submissions would hang
        results = [_drain(batcher.submit(p)) for p in PROMPTS[:3]]
        assert results == expected
        assert results[0][-1] == eos
    finally:
        batcher.close()


@pytest.mark.parametrize(
    "sizes, prompt",
    [
        # one block a row (blocks of 64, a 16-position row): 39 tokens fit the
        # slot's one block and still overflow the row its prefill fills
        ({}, list(range(1, 40))),
        # several blocks a row: the prompt's block need exceeds a table row
        ({"block_size": 8}, list(range(1, 80))),
    ],
    ids=["one_block_a_row", "blocks_of_8"],
)
def test_oversized_prompt_fails_only_its_stream(tiny_gen, sizes, prompt):
    """A prompt a slot cannot hold fails ITS stream, with one wording, without
    wedging the FIFO; later requests proceed."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:1])
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=2, decode_chunk=2, **sizes)
    try:
        doomed = batcher.submit(prompt)
        ok = batcher.submit(PROMPTS[0])
        with pytest.raises(ValueError, match=r"KV positions \(\d+ blocks of \d+ .* cache_len 16"):
            _drain(doomed)
        assert _drain(ok) == expected[0]
    finally:
        batcher.close()


def test_moe_routed_decoder_streams_exactly():
    """Routed decoder through shared dispatches: free slots are done-masked so
    they claim no expert capacity, and each stream matches its solo run."""
    from unionml_tpu.models import MoEConfig, MoETransformer

    config = MoEConfig.tiny(
        vocab_size=61, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=96,
        n_experts=4, k=2, capacity_factor=8.0, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = MoETransformer(config)
    params = module.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8,))
    prompts = [[3, 1, 4, 1, 5], [9, 2]]
    expected = _sequential_expected(module, params, cfg, prompts)

    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=4, decode_chunk=2)
    try:
        streams = [batcher.submit(p) for p in prompts]
        assert [_drain(s) for s in streams] == expected
    finally:
        batcher.close()


def test_immediate_eos_masks_slot_and_streams_stay_exact(tiny_gen):
    """A prompt whose prompt-sampled first token is eos finishes at admission;
    its slot must be done-masked on device (the decode body never flags
    already-emitted tokens), or it would keep decoding as a zombie row."""
    module, params = tiny_gen
    probe = Generator(
        module, params, GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(16,))
    )(PROMPTS[:1])
    eos = int(probe[0][0])  # the very first sampled token for PROMPTS[0]
    cfg = GenerationConfig(
        max_new_tokens=8, temperature=0.0, prompt_buckets=(16,), eos_id=eos, pad_id=0
    )
    expected = _sequential_expected(module, params, cfg, PROMPTS[:3])

    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=4, decode_chunk=3)
    try:
        streams = [batcher.submit(p) for p in PROMPTS[:3]]
        results = [_drain(s) for s in streams]
        assert results == expected
        assert results[0] == [eos]  # finished at admission
        # every slot is masked out once idle — no zombie rows left decoding
        done = np.asarray(batcher._carry[3])
        assert bool(done.all())
    finally:
        batcher.close()


def test_close_drains_residents_and_rejects_new(tiny_gen):
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=24, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:2])
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=2, decode_chunk=2)
    streams = [batcher.submit(p) for p in PROMPTS[:2]]
    # let the engine admit them before closing
    first = [next(iter_) for iter_ in streams]
    batcher.close(wait=False)
    with pytest.raises(RuntimeError, match="closed"):
        batcher.submit(PROMPTS[2])
    results = [
        [int(t) for t in np.asarray(f).ravel()] + _drain(s) for f, s in zip(first, streams)
    ]
    assert results == expected  # residents drained to completion, not truncated
    batcher.close()  # idempotent


def test_per_request_budget_and_int8_kv(tiny_gen):
    """Composition: per-request max_new_tokens caps below the config budget
    (the truncated stream is a prefix of the full one), and the int8 KV cache
    flows through admission/decode (quantized rows paste + stream)."""
    module, params = tiny_gen
    cfg = GenerationConfig(
        max_new_tokens=10, temperature=0.0, prompt_buckets=(16,), kv_cache_dtype="int8"
    )
    expected = _sequential_expected(module, params, cfg, PROMPTS[:2])

    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=2, decode_chunk=3)
    try:
        full = _drain(batcher.submit(PROMPTS[0]))
        assert full == expected[0]
        short = _drain(batcher.submit(PROMPTS[1], max_new_tokens=4))
        assert short == expected[1][:4]
        with pytest.raises(ValueError, match="max_new_tokens"):
            batcher.submit(PROMPTS[0], max_new_tokens=11)
        with pytest.raises(ValueError, match="max_new_tokens"):
            batcher.submit(PROMPTS[0], max_new_tokens=0)
    finally:
        batcher.close()


def test_shared_prefix_across_slots(tiny_gen):
    """A server-wide prefix (system prompt) composes with continuous batching:
    every admitted suffix decodes as if prefilled with (prefix + suffix), and
    the prefix's prefill was paid once in cache_prefix."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(8, 32))
    prefix = [7, 7, 3, 9, 1, 2]
    suffixes = [[3, 1, 4], [9, 2, 6, 5], [8]]
    expected = _sequential_expected(module, params, cfg, [prefix + s for s in suffixes])

    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix))
    try:
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
    finally:
        batcher.close()


def test_prefix_with_oversized_prefill_chunk(tiny_gen):
    """cache_len must cover the chunk-ALIGNED prefill width: with prefill_chunk
    larger than bucket + budget + decode_chunk, the offset chunked prefill
    writes [p0, p0 + aligned) — round-3 sizing stopped at the budget tail, so
    dynamic_update_slice clamping silently corrupted earlier cache positions
    (ADVICE r3). The oracle would catch the corruption; the sizing assert pins
    the fix directly."""
    module, params = tiny_gen
    cfg = GenerationConfig(
        max_new_tokens=6, temperature=0.0, prompt_buckets=(8,), prefill_chunk=32
    )
    prefix = [7, 7]
    suffixes = [[3, 1, 4], [9, 2, 6, 5, 8, 1]]
    expected = _sequential_expected(module, params, cfg, [prefix + s for s in suffixes])

    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix))
    try:
        assert batcher.cache_len >= len(prefix) + 32  # the aligned write fits
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
    finally:
        batcher.close()


def _draft_for(vocab):
    cfg = LlamaConfig.tiny(
        vocab_size=vocab, dim=32, n_layers=1, n_heads=4, n_kv_heads=2, hidden_dim=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(cfg)
    return module, module.init(jax.random.PRNGKey(9), jnp.zeros((1, 8), jnp.int32))["params"]


def test_speculative_continuous_streams_match_sequential(tiny_gen):
    """Speculative continuous batching: resident rows advance by shared
    draft-and-verify rounds with per-row floors, yet each greedy stream equals
    the plain sequential Generator run — the exactness oracle survives both
    compositions at once."""
    import dataclasses

    from unionml_tpu.models import DraftSpec

    module, params = tiny_gen
    base = GenerationConfig(max_new_tokens=10, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, base, PROMPTS)

    draft, dp = _draft_for(97)
    cfg = dataclasses.replace(base, draft=DraftSpec(module=draft, params=dp, gamma=3))
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=3, decode_chunk=4)
    try:
        results = [None] * len(PROMPTS)

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected
        assert batcher.decoded_rows > batcher.decode_dispatches  # rounds were shared
    finally:
        batcher.close()


def test_speculative_continuous_eos_and_budget(tiny_gen):
    import dataclasses

    from unionml_tpu.models import DraftSpec

    module, params = tiny_gen
    probe = Generator(
        module, params, GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16,))
    )(PROMPTS[:1])
    eos = int(probe[0][4])
    base = GenerationConfig(
        max_new_tokens=12, temperature=0.0, prompt_buckets=(16,), eos_id=eos, pad_id=0
    )
    expected = _sequential_expected(module, params, base, PROMPTS[:3])

    draft, dp = _draft_for(97)
    cfg = dataclasses.replace(base, draft=DraftSpec(module=draft, params=dp, gamma=4))
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=1, decode_chunk=5)
    try:
        # slots=1 forces strict slot reuse; eos exits must free it
        results = [_drain(batcher.submit(p)) for p in PROMPTS[:3]]
        assert results == expected
        # per-request budget caps below eos
        short = _drain(batcher.submit(PROMPTS[1], max_new_tokens=2))
        assert short == expected[1][:2]
    finally:
        batcher.close()


def test_paged_kv_matches_sequential_with_undersized_pool(tiny_gen):
    """Paged KV capacity win: requests with small budgets are allocated only the
    blocks they need, so a pool FAR smaller than slots x worst-case admits a
    full house concurrently — and every stream is still token-exact against the
    sequential run."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:4])

    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=4, decode_chunk=4, block_size=8, pool_blocks=10
    )
    try:
        # worst-case sizing would need slots * max_blocks; the pool is smaller
        assert batcher.pool_blocks < batcher.slots * batcher.max_blocks
        # every request (budget 4) needs few enough blocks that all 4 fit at once
        assert 4 * batcher._blocks_lifetime(PROMPTS[0], 4) <= batcher.pool_blocks
        results = [None] * 4

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i], max_new_tokens=4))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert results == [e[:4] for e in expected]
        assert batcher.decoded_rows > batcher.decode_dispatches  # dispatches were shared
        stats = batcher.stats()["kv_blocks"]
        # the byte gauges (block_bytes/used_bytes/kv_dtype) ride along at the
        # pool dtype; the allocator counters are the contract here
        assert {k: stats[k] for k in ("total", "used", "shared_prefix", "block_size", "preemptions")} == {
            "total": 10, "used": 0, "shared_prefix": 0, "block_size": 8, "preemptions": 0,
        }  # all freed, nobody evicted
        assert stats["used_bytes"] == 0 and stats["block_bytes"] > 0
    finally:
        batcher.close()


def test_paged_kv_pressure_waits_and_stays_exact(tiny_gen):
    """Pool pressure: with room for only ~2 resident requests, the third waits
    at the FIFO head until blocks free up — every stream still exact, and the
    allocator ends balanced."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=10, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS)

    gen = Generator(module, params, cfg)
    probe = ContinuousBatcher(gen, slots=3, decode_chunk=3, block_size=8)
    min_pool = probe.max_blocks  # the smallest legal pool: one worst-case request
    probe.close()
    batcher = ContinuousBatcher(gen, slots=3, decode_chunk=3, block_size=8, pool_blocks=min_pool)
    try:
        results = [None] * len(PROMPTS)

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected
        assert batcher.stats()["kv_blocks"]["used"] == 0
    finally:
        batcher.close()


def test_paged_kv_with_prefix_and_int8(tiny_gen):
    """Paged KV composes with the shared prefix (prefix rows scatter into each
    admission's blocks) and the int8 KV cache (quantized pools + scale pools)."""
    module, params = tiny_gen
    cfg = GenerationConfig(
        max_new_tokens=8, temperature=0.0, prompt_buckets=(8, 16), kv_cache_dtype="int8"
    )
    prefix = [7, 7, 3, 9, 1, 2]
    suffixes = [[3, 1, 4], [9, 2, 6, 5], [8]]
    expected = _sequential_expected(module, params, cfg, [prefix + s for s in suffixes])

    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix), block_size=8
    )
    try:
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
    finally:
        batcher.close()


def test_paged_preemption_recovers_token_exact(tiny_gen):
    """Pool exhaustion mid-decode preempts the YOUNGEST resident (freed,
    requeued as prompt + emitted tokens, re-prefilled) — and the evicted
    stream's total output is still exactly its sequential run: recompute
    preemption is invisible in tokens. Pool = one worst-case request, so two
    long-budget residents cannot coexist to completion."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:3])

    gen = Generator(module, params, cfg)
    probe = ContinuousBatcher(gen, slots=3, decode_chunk=2, block_size=8)
    min_pool = probe.max_blocks
    probe.close()
    batcher = ContinuousBatcher(gen, slots=3, decode_chunk=2, block_size=8, pool_blocks=min_pool)
    try:
        results = [None] * 3

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected
        stats = batcher.stats()["kv_blocks"]
        assert stats["preemptions"] > 0  # the tight pool actually evicted someone
        assert stats["used"] == 0
    finally:
        batcher.close()


def test_paged_preempted_resume_outgrows_buckets(tiny_gen):
    """A preempted stream's resume prompt (original + emitted) can exceed every
    configured prompt bucket; the resume must prefill at exact width and stay
    token-exact instead of failing the stream mid-generation (round-4 review
    repro: bucket 16, resume length 19 -> oversized-bucket ValueError)."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    long_prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7], [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9, 0o4]]
    expected = _sequential_expected(module, params, cfg, long_prompts)

    gen = Generator(module, params, cfg)
    probe = ContinuousBatcher(gen, slots=2, decode_chunk=8, block_size=8)
    # big enough to ADMIT both (initial needs), too small for both to finish —
    # and chunk 8 means the victim has a full chunk in its echo at eviction,
    # so its resume prompt (14 + 9 = 23) overflows the single 16-wide bucket
    pool = 2 * probe._blocks_initial(long_prompts[0], cfg.max_new_tokens)
    assert pool < 2 * probe._blocks_lifetime(long_prompts[0], cfg.max_new_tokens)
    probe.close()
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=8, block_size=8, pool_blocks=pool)
    try:
        results = [None] * 2

        def worker(i):
            results[i] = _drain(batcher.submit(long_prompts[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected
        assert batcher.stats()["kv_blocks"]["preemptions"] > 0  # the repro actually fired
    finally:
        batcher.close()


def test_paged_lazy_growth_admits_beyond_reserved_budgets(tiny_gen):
    """Lazy allocation: admission reserves only prompt + one dispatch, so a
    pool far below the residents' SUMMED lifetime needs still admits them all
    concurrently — blocks arrive as decoding actually proceeds."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:4])

    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, slots=4, decode_chunk=3, block_size=8, pool_blocks=8)
    try:
        # the pool cannot hold 4 lifetime reservations...
        assert 4 * batcher._blocks_lifetime(PROMPTS[0], cfg.max_new_tokens) > batcher.pool_blocks
        # ...but it CAN admit all 4 (initial needs only)
        assert 4 * batcher._blocks_initial(PROMPTS[0], cfg.max_new_tokens) <= batcher.pool_blocks
        results = [None] * 4

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected
    finally:
        batcher.close()


def test_paged_shared_prefix_pages(tiny_gen):
    """A long system prompt's FULL blocks are seeded once and SHARED: every
    slot's table points at the same page ids (vLLM's prefix caching), so
    per-request allocation shrinks by the shared pages — and tokens still equal
    the sequential dense run."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8, 32))
    prefix = [7, 7, 3, 9, 1, 2, 5, 11, 4, 8, 2, 6, 9, 1, 3, 2, 8, 4, 1, 5]  # 20 tokens
    suffixes = [[3, 1, 4], [9, 2, 6, 5], [8]]
    expected = _sequential_expected(module, params, cfg, [prefix + s for s in suffixes])

    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix), block_size=8
    )
    try:
        assert len(batcher._shared_prefix_blocks) == 2  # 20 // 8
        # admission need excludes the shared pages: ceil((20+4+3+3)/8)=4 - 2
        assert batcher._blocks_initial(suffixes[1], 6) == 2
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
        stats = batcher.stats()["kv_blocks"]
        assert stats["shared_prefix"] == 2
        assert stats["used"] == 2  # only the permanently resident shared pages
    finally:
        batcher.close()


def test_paged_speculative_with_prefix_all_compositions(tiny_gen):
    """Everything at once: paged KV x speculative x shared prefix x per-request
    budgets. One block allocation drives both models' pools; each greedy stream
    equals the sequential plain run on (prefix + suffix)."""
    import dataclasses

    from unionml_tpu.models import DraftSpec

    module, params = tiny_gen
    base = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(8, 16))
    prefix = [7, 7, 3, 9]
    suffixes = [[3, 1, 4], [9, 2, 6, 5], [8]]
    expected = _sequential_expected(module, params, base, [prefix + s for s in suffixes])

    draft, dp = _draft_for(97)
    cfg = dataclasses.replace(base, draft=DraftSpec(module=draft, params=dp, gamma=3))
    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix), block_size=8
    )
    try:
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
        short = _drain(batcher.submit(suffixes[0], max_new_tokens=3))
        assert short == expected[0][:3]
        assert batcher.stats()["kv_blocks"]["used"] == 0  # allocator balanced
    finally:
        batcher.close()


def test_speculative_continuous_with_shared_prefix(tiny_gen):
    """The production trifecta — system prompt (prefix=) + draft model
    (speculative) + continuous batching — in one engine: every greedy stream
    equals the sequential plain-Generator run on (prefix + suffix)."""
    import dataclasses

    from unionml_tpu.models import DraftSpec

    module, params = tiny_gen
    base = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(8, 16))
    prefix = [7, 7, 3, 9, 1, 2]
    suffixes = [[3, 1, 4], [9, 2, 6, 5], [8], [2, 2]]
    expected = _sequential_expected(module, params, base, [prefix + s for s in suffixes])

    draft, dp = _draft_for(97)
    cfg = dataclasses.replace(base, draft=DraftSpec(module=draft, params=dp, gamma=3))
    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix))
    try:
        results = [None] * len(suffixes)

        def worker(i):
            results[i] = _drain(batcher.submit(suffixes[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(suffixes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected
    finally:
        batcher.close()


def test_chunked_admission_streams_match_sequential(tiny_gen):
    """Stall-free admission: prefill sliced into admit_chunk-token chunks
    interleaved with decode must be invisible in the output — every stream
    equals its monolithic/sequential run (the chunked-prefill equality
    contract), and the chunk counters show the slicing actually happened."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS)

    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=len(PROMPTS), decode_chunk=4, admit_chunk=4
    )
    try:
        results = [None] * len(PROMPTS)

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert results == expected
        stats = batcher.stats()
        assert stats["prefill"]["mode"] == "chunked"
        assert stats["prefill"]["chunks"] >= len(PROMPTS)  # every admission chunked
        assert stats["prefill"]["monolithic_admissions"] == 0
        # TTFT/TBT reservoirs filled (the /metrics surface)
        assert stats["ttft_ms"]["window"] == len(PROMPTS)
        assert stats["tbt_ms"]["window"] > 0
    finally:
        batcher.close()


def test_chunked_admission_interleaves_decode_with_prefill(tiny_gen):
    """The stall fix itself: while a multi-chunk admission is in flight, the
    resident stream keeps receiving tokens — decode dispatches land BETWEEN
    prefill chunks (budget = one chunk per engine iteration), instead of the
    whole prompt prefilling in one stall."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=48, temperature=0.0, prompt_buckets=(4, 16))
    gen = Generator(module, params, cfg)
    expected = _sequential_expected(module, params, cfg, [[5, 5, 5], [9] * 12])
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=2, admit_chunk=4, prefill_budget=4)
    try:
        occupant = batcher.submit([5, 5, 5])
        first = next(occupant)  # resident and decoding (48-token budget)
        dispatches_at_chunk = []
        orig = gen._prefill_chunk

        def spy(*args, **kwargs):
            dispatches_at_chunk.append(batcher.decode_dispatches)
            return orig(*args, **kwargs)

        gen._prefill_chunk = spy
        try:
            long_out = _drain(batcher.submit([9] * 12))  # bucket 16 -> 4 chunks
        finally:
            gen._prefill_chunk = orig
        occ_out = [int(t) for t in np.asarray(first).ravel()] + _drain(occupant)
        assert [occ_out, long_out] == expected
        assert len(dispatches_at_chunk) == 4  # 16 aligned columns / 4-token chunks
        # decode ran between every pair of chunks: the dispatch counter
        # strictly increases across the admission instead of freezing
        assert all(
            b > a for a, b in zip(dispatches_at_chunk, dispatches_at_chunk[1:])
        ), dispatches_at_chunk
    finally:
        batcher.close()


def test_prefill_budget_groups_chunks_per_iteration(tiny_gen):
    """prefill_budget tokens of prefill run per engine iteration: with a
    budget of two chunks, chunks land in pairs between decode dispatches."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=48, temperature=0.0, prompt_buckets=(4, 32))
    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=2, admit_chunk=4, prefill_budget=8)
    try:
        occupant = batcher.submit([5, 5, 5])
        next(occupant)
        dispatches_at_chunk = []
        orig = gen._prefill_chunk

        def spy(*args, **kwargs):
            dispatches_at_chunk.append(batcher.decode_dispatches)
            return orig(*args, **kwargs)

        gen._prefill_chunk = spy
        try:
            _drain(batcher.submit([9] * 20, max_new_tokens=2))  # bucket 32 -> 8 chunks
        finally:
            gen._prefill_chunk = orig
        _drain(occupant)
        assert len(dispatches_at_chunk) == 8
        # chunks arrive in pairs: both members of a pair see the same decode
        # count, and decode advances between pairs
        pairs = list(zip(dispatches_at_chunk[0::2], dispatches_at_chunk[1::2]))
        assert all(a == b for a, b in pairs), dispatches_at_chunk
        assert all(n[0] > p[0] for p, n in zip(pairs, pairs[1:])), dispatches_at_chunk
    finally:
        batcher.close()


def test_cancel_mid_chunked_prefill_frees_slot(tiny_gen):
    """A consumer disconnect landing between prefill chunks abandons the
    admission at the next chunk boundary: the slot comes back (no device
    masking needed — the row was never pasted) and later requests are exact."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    gen = Generator(module, params, cfg)
    expected = _sequential_expected(module, params, cfg, PROMPTS[:2])
    batcher = ContinuousBatcher(gen, slots=1, decode_chunk=2, admit_chunk=8)
    try:
        entered, gate = threading.Event(), threading.Event()
        orig = gen._prefill_chunk

        def gated(*args, **kwargs):
            entered.set()
            gate.wait(timeout=30)
            return orig(*args, **kwargs)

        gen._prefill_chunk = gated
        doomed = batcher.submit(PROMPTS[2])  # bucket 16 -> 2 chunks
        assert entered.wait(timeout=30)  # engine inside chunk 1 of 2
        doomed.close()  # cancel lands mid-prefill
        gate.set()
        gen._prefill_chunk = orig
        assert _drain(doomed) == []
        out = [_drain(batcher.submit(p)) for p in PROMPTS[:2]]
        assert out == expected
        stats = batcher.stats()
        assert stats["resident"] == 0 and stats["waiting"] == 0 and stats["admitting"] == 0
    finally:
        batcher.close()


def test_deadline_shed_mid_chunked_prefill(tiny_gen):
    """A deadline expiring between prefill chunks sheds the admission with
    DeadlineExceeded at the next chunk boundary — the client gave up, so the
    remaining chunks and the whole decode are never paid — and the freed slot
    serves the next request exactly."""
    import time as _time

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    gen = Generator(module, params, cfg)
    expected = _sequential_expected(module, params, cfg, PROMPTS[:1])
    batcher = ContinuousBatcher(gen, slots=1, decode_chunk=2, admit_chunk=8)
    try:
        from unionml_tpu.serving import DeadlineExceeded

        entered, gate = threading.Event(), threading.Event()
        orig = gen._prefill_chunk

        def gated(*args, **kwargs):
            entered.set()
            gate.wait(timeout=30)
            return orig(*args, **kwargs)

        gen._prefill_chunk = gated
        doomed = batcher.submit(PROMPTS[2], deadline=_time.monotonic() + 0.2)
        assert entered.wait(timeout=30)  # admission started before the deadline
        _time.sleep(0.3)  # deadline passes while chunk 1 is in flight
        gate.set()
        gen._prefill_chunk = orig
        with pytest.raises(DeadlineExceeded, match="mid-prefill"):
            _drain(doomed)
        assert batcher.stats()["shed_deadline"] == 1
        assert _drain(batcher.submit(PROMPTS[0])) == expected[0]
    finally:
        batcher.close()


def test_chunked_admission_with_shared_prefix_and_speculative(tiny_gen):
    """Chunked admission composes with the production trifecta: the draft's
    row chunks in LOCKSTEP with the target's after both models' prefix rows
    paste, and every greedy stream equals the sequential plain run."""
    import dataclasses

    from unionml_tpu.models import DraftSpec

    module, params = tiny_gen
    base = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(8, 16))
    prefix = [7, 7, 3, 9, 1, 2]
    suffixes = [[3, 1, 4], [9, 2, 6, 5], [8], [2, 2]]
    expected = _sequential_expected(module, params, base, [prefix + s for s in suffixes])

    draft, dp = _draft_for(97)
    cfg = dataclasses.replace(base, draft=DraftSpec(module=draft, params=dp, gamma=3))
    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=3, prefix=gen.cache_prefix(prefix), admit_chunk=4
    )
    try:
        results = [_drain(batcher.submit(s)) for s in suffixes]
        assert results == expected
        assert batcher.stats()["prefill"]["chunks"] > 0
    finally:
        batcher.close()


@pytest.mark.slow  # ~4s; the same preempt-resume-under-chunking path stays in
# tier-1 via tests/emulated/test_continuous_chunked.py's paged leg
def test_chunked_admission_paged_preemption_resume(tiny_gen):
    """Chunked admission preserves paged-KV pressure semantics: a preempted
    stream's resume (original + emitted tokens, outgrowing every bucket)
    still lands token-exact — the exact-width resume falls back to a
    monolithic prefill when its chunk-aligned width would overflow the
    cache, instead of failing the stream."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    long_prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7], [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9, 4]]
    expected = _sequential_expected(module, params, cfg, long_prompts)

    gen = Generator(module, params, cfg)
    probe = ContinuousBatcher(gen, slots=2, decode_chunk=8, block_size=8, admit_chunk=8)
    pool = 2 * probe._blocks_initial(long_prompts[0], cfg.max_new_tokens)
    assert pool < 2 * probe._blocks_lifetime(long_prompts[0], cfg.max_new_tokens)
    probe.close()
    batcher = ContinuousBatcher(
        gen, slots=2, decode_chunk=8, block_size=8, pool_blocks=pool, admit_chunk=8
    )
    try:
        results = [None] * 2

        def worker(i):
            results[i] = _drain(batcher.submit(long_prompts[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected
        stats = batcher.stats()
        assert stats["kv_blocks"]["preemptions"] > 0  # pressure actually fired
        assert stats["prefill"]["chunks"] > 0  # fresh admissions chunked
    finally:
        batcher.close()


def test_metrics_surface_ttft_tbt_and_prefill_counters(tiny_gen, sklearn_model):
    """/metrics regression for the stall-fix surface: the generation section
    carries ttft_ms/tbt_ms percentile blocks and the prefill counter block,
    and NO gauge anywhere in the snapshot is None-valued (an empty reservoir
    reports {"window": 0}, a missing engine omits its gauge entirely)."""
    import asyncio
    import json

    from unionml_tpu.serving import serving_app

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,))
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=2, decode_chunk=2, admit_chunk=4)
    try:
        _drain(batcher.submit(PROMPTS[0]))  # populate the reservoirs
        sklearn_model.train(hyperparameters={"max_iter": 200})
        sklearn_model.generation_batcher = batcher
        app = serving_app(sklearn_model)

        async def scenario():
            status, payload, _ = await app.dispatch("GET", "/metrics", b"")
            assert status == 200
            return json.loads(payload) if isinstance(payload, (bytes, str)) else payload

        payload = asyncio.run(scenario())
        generation = payload["generation"]
        assert {"ttft_ms", "tbt_ms", "prefill", "admitting"} <= set(generation)
        assert generation["ttft_ms"]["window"] >= 1
        assert {"chunks", "chunk_tokens", "monolithic_admissions", "backlog_tokens"} <= set(
            generation["prefill"]
        )

        def no_nones(node, path="snapshot"):
            if isinstance(node, dict):
                for k, v in node.items():
                    assert v is not None, f"None-valued gauge at {path}.{k}"
                    no_nones(v, f"{path}.{k}")
            elif isinstance(node, list):
                for i, v in enumerate(node):
                    no_nones(v, f"{path}[{i}]")

        no_nones(payload.get("gauges", {}), "gauges")
        no_nones(generation["ttft_ms"], "ttft_ms")
        no_nones(generation["tbt_ms"], "tbt_ms")
        no_nones(generation["prefill"], "prefill")
    finally:
        sklearn_model.generation_batcher = None
        batcher.close()


def test_cancelled_stream_frees_slot_for_waiters(tiny_gen):
    """Closing a stream's iterator (the client-disconnect path) releases its
    slot at the next chunk boundary; a queued request takes it and the
    remaining streams are unaffected."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=24, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:3])

    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=1, decode_chunk=2)
    try:
        doomed = batcher.submit(PROMPTS[0])
        next(doomed)  # ensure it is admitted and producing
        doomed.close()  # consumer walks away mid-generation
        # the slot must come back: these would hang forever if it leaked
        out1 = _drain(batcher.submit(PROMPTS[1]))
        out2 = _drain(batcher.submit(PROMPTS[2]))
        assert [out1, out2] == expected[1:3]
        # the cancelled session is gone from the books
        stats = batcher.stats()
        assert stats["resident"] == 0 and stats["waiting"] == 0
    finally:
        batcher.close()


def test_cancel_while_pending_dequeues(tiny_gen):
    """close() on a stream abandoned BEFORE admission (never nexted — the
    generator-close blind spot _TokenStream exists for) dequeues it: it is
    never admitted, never decodes to a dead queue, and drains as an empty
    stream."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:2])
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=1, decode_chunk=2)
    try:
        first = batcher.submit(PROMPTS[0])
        next(first)  # occupies the single slot
        queued = batcher.submit(PROMPTS[1])  # waits for the slot
        assert batcher.stats()["waiting"] == 1
        queued.close()  # abandoned before admission, without a single next()
        assert batcher.stats()["waiting"] == 0  # dequeued immediately
        assert _drain(queued) == []  # ends cleanly, no tokens
        rest = _drain(first)
        # the abandoned request was never admitted: after `first` finishes the
        # engine goes idle instead of decoding the ghost
        assert batcher.stats()["resident"] == 0
        import time as _time

        idle_dispatches = batcher.decode_dispatches
        _time.sleep(1.0)
        assert batcher.decode_dispatches == idle_dispatches  # no ghost decoding
        again = _drain(batcher.submit(PROMPTS[1]))
        assert again == expected[1]
    finally:
        batcher.close()


def test_cancel_during_prefill_window_returns_slot(tiny_gen):
    """A cancel landing while the engine is inside the UNLOCKED prefill (the
    session is neither pending nor resident) must not register the dead
    session: the freshly activated row is masked back out and the slot is
    immediately reusable."""
    import time as _time

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:2])
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=1, decode_chunk=2)
    try:
        entered, gate = threading.Event(), threading.Event()
        orig = batcher._prefill_row

        def slow_prefill(prompt, seed, *args, **kwargs):
            entered.set()
            gate.wait(timeout=30)
            return orig(prompt, seed, *args, **kwargs)

        batcher._prefill_row = slow_prefill
        stream = batcher.submit(PROMPTS[0])
        assert entered.wait(timeout=30)  # engine is inside the prefill window
        stream.close()  # cancel lands while neither pending nor resident
        gate.set()
        assert _drain(stream) == []
        batcher._prefill_row = orig

        # the slot came back and serves a fresh request exactly
        out = _drain(batcher.submit(PROMPTS[1]))
        assert out == expected[1]
        stats = batcher.stats()
        assert stats["resident"] == 0 and stats["waiting"] == 0
    finally:
        batcher.close()


def test_warmup_compiles_every_bucket_then_serves_exactly(tiny_gen):
    """warmup() drives a bucket-FILLING request through each prompt bucket plus
    one decode chunk and resets the counters; real traffic afterwards is exact,
    starts from clean metrics, and — the point — triggers NO new prefill or
    decode traces in any bucket."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(8, 16))
    prompts = [PROMPTS[0], [5] * 12]  # land in bucket 8 and bucket 16
    expected = _sequential_expected(module, params, cfg, prompts)
    gen = Generator(module, params, cfg)
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=3)
    try:
        batcher.warmup()
        stats = batcher.stats()
        assert stats["decode_dispatches"] == 0 and stats["resident"] == 0
        prefill_traces = gen.prefill_traces
        decode_traces = gen.decode_traces
        results = [_drain(batcher.submit(p)) for p in prompts]
        assert results == expected
        assert batcher.decode_dispatches > 0
        assert gen.prefill_traces == prefill_traces  # both buckets pre-compiled
        assert gen.decode_traces == decode_traces  # decode chunk pre-compiled
    finally:
        batcher.close()


def test_overload_admission_deadline_and_disconnect(tiny_gen, sklearn_model):
    """Engine-level overload protection, one batcher for all three properties
    (a fresh Generator per property would triple the XLA compile bill):

    1. ``max_waiting`` bounds the slot-wait queue — the excess submission sheds
       synchronously with QueueFullError (the HTTP layer's 429).
    2. A waiter whose deadline passes while queued is shed with
       DeadlineExceeded at the next chunk boundary, never paying a prefill.
    3. A streaming client that disconnects mid-decode (the /predict-stream
       route's aclose path) frees its slot within one decode chunk — pinned
       against ``stats()['resident']`` — and the slot admits new work.
    """
    import asyncio
    import json
    import time

    from unionml_tpu.serving import DeadlineExceeded, QueueFullError, serving_app
    from unionml_tpu.serving.overload import QueueFullError as QFE

    assert QFE is QueueFullError  # one exception type across layers

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=256, temperature=0.0, prompt_buckets=(16,))
    batcher = ContinuousBatcher(
        Generator(module, params, cfg), slots=1, decode_chunk=2, max_waiting=2
    )
    try:
        # ---- 1+2: bound the waiting queue and shed the expired waiter
        occupant = batcher.submit(PROMPTS[0])  # 256-token budget: owns the slot
        next(occupant)  # first token: resident now
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and batcher.stats()["waiting"]:
            time.sleep(0.01)
        doomed = batcher.submit(PROMPTS[1], deadline=time.monotonic() + 0.02)
        waiter = batcher.submit(PROMPTS[3], max_new_tokens=4)
        with pytest.raises(QueueFullError, match="waiting queue full"):
            batcher.submit(PROMPTS[4])  # 3rd waiter > max_waiting=2
        assert batcher.stats()["shed_queue_full"] == 1
        time.sleep(0.05)  # doomed's deadline passes while it waits
        with pytest.raises(DeadlineExceeded):
            _drain(doomed)
        assert batcher.stats()["shed_deadline"] == 1
        _drain(occupant)  # release the slot; waiter decodes next
        assert len(_drain(waiter)) == 4

        # ---- 3: route-level disconnect frees the slot within one chunk
        sklearn_model.train(hyperparameters={"max_iter": 200})

        @sklearn_model.stream_predictor
        def stream_predictor(model_object, features):
            for chunk in batcher.submit([3, 1, 4, 1, 5]):
                yield chunk.tolist()

        sklearn_model.generation_batcher = batcher
        app = serving_app(sklearn_model)

        async def scenario():
            status, payload, _ = await app.dispatch(
                "POST", "/predict-stream", json.dumps({"features": [{"x": 1.0}]}).encode()
            )
            assert status == 200
            agen = payload.__aiter__()
            await agen.__anext__()  # decode underway (256-token budget ~= forever)
            assert batcher.stats()["resident"] == 1
            await agen.aclose()  # in-process client disconnect
            # the engine must free the slot at the next chunk boundary; poll on
            # THIS loop so the route's detached iterator-close task can run
            for _ in range(400):
                if batcher.stats()["resident"] == 0:
                    break
                await asyncio.sleep(0.025)
            assert batcher.stats()["resident"] == 0, "slot leaked after disconnect"

        asyncio.run(scenario())
        # the freed slot admits new work and decodes it to completion
        out = _drain(batcher.submit(PROMPTS[5], max_new_tokens=4))
        assert len(out) == 4
    finally:
        batcher.close()


# ------------------------------------------------------------------ the carry's edits between dispatches
#
# The engine records what it writes into the carry between dispatches (a grown
# table entry, a released slot) on the host and one jitted program applies the
# lot (``_sync_carry``): the device must agree with the host's account whenever
# the engine thread is not inside an iteration, and the program must run at
# most twice an iteration, whatever the number of rows, blocks and layers.


def _speculative(cfg):
    import dataclasses

    from unionml_tpu.models import DraftSpec

    draft, dp = _draft_for(97)
    return dataclasses.replace(cfg, draft=DraftSpec(module=draft, params=dp, gamma=3))


def _check_account_at_every_iteration_end(batcher):
    """Wrap the engine log's ``end`` (the bottom of the engine loop, on the
    engine thread) with a comparison of every layer's device table, ``done`` and
    ``lengths`` against the host's books; returns the list the disagreements
    are written to and the count of iterations checked."""
    problems, checked = [], [0]
    log = batcher.engine_log
    real_end = log.end

    def end():
        carry = batcher._carry
        if carry is not None:
            spec = batcher._spec is not None
            lengths = np.asarray(carry[3 if spec else 2])
            done = np.asarray(carry[4 if spec else 3])
            scratch = batcher._scratch_block
            with batcher._lock:
                resident = {slot: list(session.table) for slot, session in batcher._sessions.items()}
            expected = np.full((batcher.slots, batcher.max_blocks), scratch, np.int32)
            for slot, table in resident.items():
                expected[slot, : len(table)] = table
            if not (batcher._table_host == expected).all():
                problems.append((log.index, "the host's mirror left the sessions' tables"))
            for cache in carry[: 2 if spec else 1]:
                for n, layer in enumerate(cache):
                    if not (np.asarray(layer["table"]) == expected).all():
                        problems.append((log.index, f"layer {n}: the device table left the host's account"))
            for slot in range(batcher.slots):
                if slot not in resident and not (done[slot] and lengths[slot] == 0):
                    problems.append((log.index, f"free slot {slot}: done {done[slot]}, lengths {lengths[slot]}"))
                if slot in resident and lengths[slot] == 0:
                    problems.append((log.index, f"resident slot {slot} has no length"))
            checked[0] += 1
        real_end()

    log.end = end
    return problems, checked


@pytest.mark.parametrize("mode", ["plain", "speculative"])
def test_device_tables_follow_the_hosts_account_every_iteration(tiny_gen, mode):
    """Growth, finish, cancel and preemption in one paged run: after every
    iteration each layer's device table (both caches' in speculative mode)
    equals the host's account, and a free slot's row is all scratch, done and
    of length 0 — so the decode read streams one block for it, not its stale
    length. The streams stay token-exact throughout."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=16, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:4])
    gen = Generator(module, params, cfg if mode == "plain" else _speculative(cfg))
    probe = ContinuousBatcher(gen, slots=3, decode_chunk=2, block_size=8)
    min_pool = probe.max_blocks  # one worst-case request: long residents must preempt each other
    probe.close()
    batcher = ContinuousBatcher(gen, slots=3, decode_chunk=2, block_size=8, pool_blocks=min_pool)
    problems, checked = _check_account_at_every_iteration_end(batcher)
    try:
        doomed = batcher.submit(PROMPTS[3])
        next(doomed)
        doomed.close()  # a resident cancelled mid-stream: reaped at the top of an iteration
        results = [None] * 3

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected[:3]
        assert _drain(batcher.submit(PROMPTS[3], max_new_tokens=1)) == expected[3][:1]  # ends at admission
        batcher.close()  # joins the engine thread: every iteration has been checked
        assert problems == []
        assert checked[0] >= 8
        records = batcher.engine_log.iteration_records()
        assert sum(r.blocks_grown for r in records) > 0 and sum(r.finished for r in records) >= 4
        assert batcher.stats()["kv_blocks"]["preemptions"] > 0
        assert max(r.table_syncs for r in records) <= 2
        assert (batcher._table_host == batcher._scratch_block).all()  # everyone left: nothing points at a live block
    finally:
        batcher.close()


@pytest.mark.parametrize("mode", ["plain", "speculative"])
def test_released_slot_readmitted_at_once_decodes_token_exact(tiny_gen, mode):
    """The ordering hazard of deferred releases: one slot, so every finish (in
    ``emit``) and every reaped cancel (at the top of the next iteration) is
    followed at once by an admission into the SAME slot, whose paste writes the
    slot's done flag, length and table row. A release applied after it would
    mask the new row out; a release never applied would let the old row's
    ride-along write land in a reallocated block."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=10, temperature=0.0, prompt_buckets=(16,))
    expected = _sequential_expected(module, params, cfg, PROMPTS[:5])
    gen = Generator(module, params, cfg if mode == "plain" else _speculative(cfg))
    batcher = ContinuousBatcher(gen, slots=1, decode_chunk=3, block_size=8, pool_blocks=6)
    problems, _ = _check_account_at_every_iteration_end(batcher)
    try:
        results = [None] * 4

        def worker(i):
            results[i] = _drain(batcher.submit(PROMPTS[i]))

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]  # three wait for the one slot
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert results == expected[:4]
        records = batcher.engine_log.iteration_records()
        assert any(a.finished and b.admitted for a, b in zip(records, records[1:]))  # the hazard was met
        doomed = batcher.submit(PROMPTS[0])
        next(doomed)
        waiter = batcher.submit(PROMPTS[4])  # queued behind the doomed row
        doomed.close()  # reaped and its slot re-admitted within one iteration, before any sync
        assert _drain(waiter) == expected[4]
        batcher.close()
        assert problems == []
        assert batcher.stats()["kv_blocks"]["used"] == 0
    finally:
        batcher.close()


def test_carry_edits_cost_at_most_two_dispatches_an_iteration(tiny_gen, monkeypatch):
    """Counting dispatches: four rows grow their tables in one iteration and
    finish in one iteration, and the program that carries the edits to the
    device runs at most twice in any iteration (once at the end of ``grow``,
    once at the end of ``emit``), never in an iteration that grew and released
    nothing — and is traced once over warm-up and traffic together."""
    traces = []
    impl = ContinuousBatcher._sync_impl

    def counted(*args):
        traces.append(1)
        return impl(*args)

    monkeypatch.setattr(ContinuousBatcher, "_sync_impl", staticmethod(counted))
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=14, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3 + i, 14, 15, 92, 6] for i in range(4)]  # one length: the rows cross block edges together
    expected = _sequential_expected(module, params, cfg, prompts)
    batcher = ContinuousBatcher(Generator(module, params, cfg), slots=4, decode_chunk=4, block_size=8)
    try:
        batcher.warmup()
        assert len(traces) == 1
        programs = batcher._sync_fn._cache_size()
        assert batcher.stats()["loop"]["table_syncs"] == 0  # warm-up's passes are not traffic
        inner, late = batcher.gen._decode, []

        def decode(params, cache, *carry, steps):
            """The growths reach the device BEFORE the dispatch that writes into the grown blocks."""
            with batcher._lock:
                tables = {slot: list(session.table) for slot, session in batcher._sessions.items()}
            for layer in cache:
                device = np.asarray(layer["table"])
                late.extend(slot for slot, table in tables.items() if list(device[slot, : len(table)]) != table)
            return inner(params, cache, *carry, steps=steps)

        monkeypatch.setattr(batcher.gen, "_decode", decode)
        with batcher._lock:  # re-entrant: the engine sees all four at once, and admits them in one iteration
            streams = [batcher.submit(p) for p in prompts]
        assert [_drain(s) for s in streams] == expected
        batcher.close()
        assert late == []
        records = batcher.engine_log.iteration_records()
        assert any(r.blocks_grown >= 4 for r in records) and any(r.finished == 4 for r in records)
        for r in records:
            assert r.table_syncs <= (r.blocks_grown > 0) + (r.finished > 0) <= 2
            assert (r.table_syncs == 0) == (r.blocks_grown == 0 and r.finished == 0)
        assert sum(r.table_syncs for r in records) == batcher.stats()["loop"]["table_syncs"] > 0
        assert len(traces) == 1 and batcher._sync_fn._cache_size() == programs  # nothing new after warm-up
    finally:
        batcher.close()


# --- one program a device step of an admission (set-up, chunk step, first token, paste) ---------------------------


def _eager_admission(batcher):
    """Put back, on one engine, the derivation the admission programs replaced: an eager ``jnp.zeros`` a cache
    plane (``init_cache``) and the prefix paste on its own, ``fold_in(PRNGKey(seed), seed)`` and the scalars as
    un-jitted ``jax.numpy`` calls, the chunk program followed by an un-jitted ``where``, the
    radix hit's gather as a program of its own."""
    from unionml_tpu.models.generate import _paste_prefix_rows, gather_paged_rows, init_cache

    gen, cfg = batcher.gen, batcher.gen.config
    models = (gen,) if batcher._spec is None else (gen, batcher._spec._draft)
    prefixes = (batcher.prefix, batcher._draft_prefix)

    def scalars(seed, total):
        key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(seed))
        lasts = tuple(jnp.zeros((1, g.module.config.dim), jnp.float32) for g in models)
        return jnp.asarray([int(total)], jnp.int32), key, jnp.ones((1,), bool), lasts

    def setup(seed, total):
        rows = []
        for g, pre in zip(models, prefixes):
            row = g._place_cache(init_cache(g.module.config, 1, batcher.cache_len, kv_dtype=cfg.kv_cache_dtype))
            rows.append(row if pre is None else _paste_prefix_rows(row, pre.layers))
        return (*scalars(seed, total), tuple(rows))

    gather = jax.jit(gather_paged_rows, static_argnums=(2,))

    def cached_setup(pool, gather_row, seed, total):
        return (*scalars(seed, total), (gather(pool, jnp.asarray(gather_row), batcher.cache_len),))

    def chunk_step(program):
        def step(p, tokens, start, lengths, cache, row_valid, last):
            # the chunk's own last-hidden row (merged into zeros), then the merge as the engine used to make it
            chunk_last, cache, counts = program(
                p, jnp.asarray(tokens), jnp.int32(start), lengths, cache, row_valid, jnp.zeros_like(last)
            )
            at = np.asarray(lengths) - 1
            has = jnp.asarray((at >= start) & (at < start + tokens.shape[1]))
            return jnp.where(has[:, None], chunk_last, last), cache, counts

        return step

    batcher._admission_setup = setup
    batcher._cached_setup_fn = cached_setup
    for g in models:
        g._prefill_chunk = chunk_step(g._prefill_chunk)


_LONG = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7]
#: name -> what the case changes: the generation config, the engine's options, the shared prefix, the prompts (served
#: one after the other unless ``together``), the engine's admission counter set before a prompt (``seed_before``)
_ADMISSION_CASES = {
    "plain": {},
    "int8_kv": {"cfg": {"kv_cache_dtype": "int8"}},
    "shared_prefix": {"prefix": [7, 7, 3, 9, 11]},
    "speculative": {"cfg": {"temperature": 0.0}, "speculative": True, "prefix": [7, 7, 3, 9]},
    "radix_hit": {"engine": {"prefix_cache": True}, "prompts": [_LONG, _LONG[:11] + [2, 2], _LONG[:9] + [4]]},
    "preemption_resume": {
        "cfg": {"temperature": 0.0, "max_new_tokens": 16, "prompt_buckets": (16,)},
        "engine": {"slots": 2, "decode_chunk": 8, "admit_chunk": 8, "pool_blocks": 8},
        "prompts": [_LONG, [2, 7, 1, 8, 2, 8, 1, 8, 2, 8, 4, 5, 9, 4]], "together": True,
    },
    "multi_chunk": {"engine": {"admit_chunk": 4}, "prompts": [[9] * 12, _LONG, [5, 5, 5]]},
    "monolithic": {"engine": {"admit_chunk": 0}},
    "monolithic_prefix": {"engine": {"admit_chunk": 0}, "prefix": [7, 7, 3, 9, 11], "cfg": {"prefill_chunk": 4}},
    # the carry's own key is drawn at the first paste, from a counter that has to fit an int32: the jump comes after
    "largest_seed": {"prompts": [PROMPTS[1], PROMPTS[0]], "seed_before": {1: 2**32 - 2}},
}


def _serve_admission_case(tiny_gen, case, eager):
    """One engine of the case, its admission counter set, its prompts served: ``(tokens, logprobs)`` per prompt,
    the engine's ``stats()`` and iteration records."""
    import dataclasses

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=10, temperature=0.7, prompt_buckets=(8, 16))
    cfg = dataclasses.replace(cfg, **case.get("cfg", {}))
    gen = Generator(module, params, _speculative(cfg) if case.get("speculative") else cfg)
    options = {"slots": 2, "decode_chunk": 3, "block_size": 8, "admit_chunk": 8, **case.get("engine", {})}
    if "prefix" in case:
        options["prefix"] = gen.cache_prefix(case["prefix"])
    batcher = ContinuousBatcher(gen, **options)
    want_lp = not case.get("speculative")  # logprobs do not compose with speculative decoding
    try:
        if eager:
            _eager_admission(batcher)
        batcher._seed = 1000
        prompts = case.get("prompts", PROMPTS[:3])
        if case.get("together"):
            with batcher._lock:  # re-entrant: the engine meets them in one pass, so both engines schedule alike
                streams = [batcher.submit(p, logprobs=want_lp) for p in prompts]
            served = [(_drain(s), s.logprobs if want_lp else []) for s in streams]
        else:
            served = []
            for i, p in enumerate(prompts):
                batcher._seed = case.get("seed_before", {}).get(i, batcher._seed)  # the engine is idle: nothing races
                stream = batcher.submit(p, logprobs=want_lp)
                served.append((_drain(stream), stream.logprobs if want_lp else []))
        batcher.close()
        return served, batcher.stats(), batcher.engine_log.iteration_records()
    finally:
        batcher.close()


@pytest.mark.parametrize("name", sorted(_ADMISSION_CASES))
def test_admission_programs_equal_the_eager_derivation(tiny_gen, name):
    """Same admission counter, same prompts: the streams of the engine whose row cache, key and scalars come from
    one jitted set-up, whose chunk's arguments ride the chunk step's own dispatch and whose last-hidden merge is
    inside it are token for token and log-probability for log-probability those of the eager derivation
    (``init_cache`` + ``fold_in(PRNGKey(seed), seed)`` + an un-jitted ``where``), sampled at a temperature so
    that the key matters — up to the largest seed the key's derivation takes (2**32 - 1)."""
    case = _ADMISSION_CASES[name]
    served, stats, _ = _serve_admission_case(tiny_gen, case, eager=False)
    expected, eager_stats, _ = _serve_admission_case(tiny_gen, case, eager=True)
    assert [tokens for tokens, _ in served] == [tokens for tokens, _ in expected]
    assert [lp for _, lp in served] == [lp for _, lp in expected]
    assert all(len(tokens) > 1 for tokens, _ in served)
    # the case met what it is named for
    if name == "radix_hit":
        assert stats["prefix_cache"]["hits"] == eager_stats["prefix_cache"]["hits"] == 2
    if name == "preemption_resume":
        assert stats["kv_blocks"]["preemptions"] > 0 and eager_stats["kv_blocks"]["preemptions"] > 0
    if name == "multi_chunk":
        assert stats["prefill"]["chunks"] == 4 + 4 + 2
    if name.startswith("monolithic"):
        assert stats["prefill"]["monolithic_admissions"] == 3 and stats["prefill"]["chunks"] == 0


def _tally_admission_events(batcher):
    """Count, per iteration index and beside the engine's own counter, what the bound on ``admit_dispatches`` is
    made of: set-ups begun (``_admission_begin`` calls), chunks run (the engine's ``prefill_chunks`` counter read at
    every iteration's end) and the admissions that asked for log-probabilities."""
    from collections import Counter

    log = batcher.engine_log
    begun, chunks, priced = Counter(), Counter(), Counter()
    real_begin, real_end, real_lp = batcher._admission_begin, log.end, batcher._first_logprob
    seen = [0]

    def begin(adm):
        begun[log.index] += 1
        return real_begin(adm)

    def first_logprob(adm):
        priced[log.index] += 1
        return real_lp(adm)

    def end():
        chunks[log.index] += batcher.prefill_chunks - seen[0]
        seen[0] = batcher.prefill_chunks
        real_end()

    batcher._admission_begin, batcher._first_logprob, log.end = begin, first_logprob, end
    return begun, chunks, priced


@pytest.mark.parametrize("mode", ["plain", "speculative"])
def test_admit_dispatches_stay_within_one_program_a_device_step(tiny_gen, mode):
    """A run that admits cold, in several chunks, hits the radix cache (plain mode: the cache does not compose with
    speculation) and finishes: in every iteration the admit phase hands the runtime at most one program per set-up
    begun, per chunk run (two under speculation: target and draft), per first token sampled (one more where the
    request asked for log-probabilities) and per paste — 3 + chunks over a cold admission's life, a radix hit's
    gather being its set-up — and over the whole run exactly that many."""
    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8, 16))
    spec = mode == "speculative"
    gen = Generator(module, params, _speculative(cfg) if spec else cfg)
    batcher = ContinuousBatcher(gen, slots=2, decode_chunk=2, block_size=8, admit_chunk=4, prefill_budget=8, prefix_cache=not spec)
    try:
        batcher.warmup()
        assert batcher.stats()["loop"]["admit_dispatches"] == 0  # warm-up's passes are not traffic
        begun, chunks, priced = _tally_admission_events(batcher)
        prompts = [_LONG, _LONG[:11] + [2, 2], [5, 5, 5], _LONG[:9] + [4], PROMPTS[0], _LONG]
        with batcher._lock:  # re-entrant: four wait while two are admitted, so iterations carry several events
            streams = [batcher.submit(p, logprobs=(i % 2 == 0 and not spec)) for i, p in enumerate(prompts)]
        outs = [_drain(s) for s in streams]
        assert all(len(o) == 6 for o in outs)
        batcher.close()
        records = batcher.engine_log.iteration_records()
        per_chunk = 2 if spec else 1
        for r in records:
            bound = begun[r.index] + per_chunk * chunks[r.index] + 2 * r.admitted + priced[r.index]
            assert r.admit_dispatches <= bound, (r, bound)
            assert (r.admit_dispatches == 0) == (begun[r.index] + chunks[r.index] + r.admitted == 0)
        total = sum(r.admit_dispatches for r in records)
        stats = batcher.stats()
        assert total == stats["loop"]["admit_dispatches"]
        assert sum(begun.values()) == sum(r.admitted for r in records) == len(prompts)
        # 3 + chunks a request (set-up, chunks, first token, paste), one more for a priced first token
        assert total == 3 * len(prompts) + per_chunk * stats["prefill"]["chunks"] + sum(priced.values())
        assert sum(priced.values()) == (0 if spec else 3)
        if not spec:
            assert stats["prefix_cache"]["hits"] >= 2  # a hit's gather is its set-up: the bound is the cold one
        assert max(r.table_syncs for r in records) <= 2
    finally:
        batcher.close()


@pytest.mark.parametrize("mode", ["plain", "int8_kv", "shared_prefix", "speculative", "constrained"])
def test_no_unjitted_jax_op_in_the_admit_phase(tiny_gen, monkeypatch, mode):
    """Once the engine is warm, nothing un-jitted runs on the engine thread between the top of ``_admit_pending``
    and its return: no eager primitive is applied (every ``jax.numpy`` call outside a jitted program binds at least
    one) and ``continuous.py`` makes no ``jax.numpy`` call at all — cold, multi-chunk and radix-hit admissions,
    first tokens with and without log-probabilities, pastes, and the slot's DFA state of a constrained generator."""
    import dataclasses

    from jax._src import core

    from unionml_tpu.serving import continuous

    module, params = tiny_gen
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8, 16))
    options = {"slots": 2, "decode_chunk": 2, "block_size": 8, "admit_chunk": 4, "prefill_budget": 8}
    if mode == "int8_kv":
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    if mode == "constrained":
        from unionml_tpu.models import ConstraintSet, compile_regex

        vocab = [chr(32 + i) for i in range(96)] + ["<eos>"]
        cfg = dataclasses.replace(cfg, eos_id=96, constraints=ConstraintSet([compile_regex("[a-z]+", vocab, eos_id=96)]))
    gen = Generator(module, params, _speculative(cfg) if mode == "speculative" else cfg)
    if mode == "shared_prefix":
        options["prefix"] = gen.cache_prefix([7, 7, 3, 9, 11])
    batcher = ContinuousBatcher(gen, prefix_cache=mode != "speculative", **options)
    inside, eager, numpy_calls = [False], [], []
    try:
        batcher.warmup()
        want_lp = mode != "speculative"
        warm = [_LONG, _LONG[:11] + [2, 2], [5, 5, 5]]
        grammar = {"constraint": 1} if mode == "constrained" else {}
        for p in warm:  # the first-token log-probability program and (constrained) the slot write compile here
            _drain(batcher.submit(p, logprobs=want_lp, **grammar))
        engine = batcher._thread.ident
        real_admit, real_primitive = batcher._admit_pending, core.EvalTrace.process_primitive

        def admit_pending():
            inside[0] = True
            try:
                return real_admit()
            finally:
                inside[0] = False

        def process_primitive(self, primitive, args, params):
            if inside[0] and threading.get_ident() == engine:
                eager.append(primitive.name)
            return real_primitive(self, primitive, args, params)

        class CountedNumpy:
            def __getattr__(self, name):
                if inside[0] and threading.get_ident() == engine:
                    numpy_calls.append(name)
                return getattr(jnp, name)

        batcher._admit_pending = admit_pending
        monkeypatch.setattr(core.EvalTrace, "process_primitive", process_primitive)
        monkeypatch.setattr(continuous, "jnp", CountedNumpy())
        prompts = [[4] + _LONG[1:], _LONG[:10] + [6, 6, 6], [5, 5, 5, 8], _LONG[:9] + [4], PROMPTS[0]]
        with batcher._lock:
            streams = [batcher.submit(p, logprobs=want_lp and i % 2 == 0, **grammar) for i, p in enumerate(prompts)]
        assert all(len(_drain(s)) >= 1 for s in streams)
        batcher.close()
        assert eager == [] and numpy_calls == []
        stats = batcher.stats()
        assert stats["loop"]["admit_dispatches"] > 0 and stats["prefill"]["chunks"] >= 10
        if mode != "speculative":
            assert stats["prefix_cache"]["hits"] >= 3
    finally:
        batcher.close()
