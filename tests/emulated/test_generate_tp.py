"""Sharded generation on the emulated 8-device CPU mesh.

Oracle: generation over a (data, model) mesh — megatron-TP params via
``llama_partition_rules`` and the KV cache sharded batch-over-data /
heads-over-model — must emit exactly the tokens of the unsharded single-device
run. XLA inserts the collectives; the engine only places data.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig, llama_partition_rules
from unionml_tpu.parallel import MeshSpec

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 emulated devices")


def _tiny():
    config = LlamaConfig.tiny(
        vocab_size=96, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    return module, params


def _until_eos(row, eos):
    """Solo-run rows pad past eos; streams stop at it — truncate for comparison."""
    out = []
    for t in row:
        out.append(int(t))
        if t == eos:
            break
    return out


def _letters_cs(pattern):
    """a-z char vocab over the tiny model's 96 ids (last id = eos) + one
    compiled grammar — shared by the constraint-composition tests."""
    from unionml_tpu.models import ConstraintSet, compile_regex

    texts = [""] * 96
    for i in range(26):
        texts[1 + i] = chr(ord("a") + i)
    eos = 95
    return ConstraintSet([compile_regex(pattern, texts, eos_id=eos)]), eos


@pytest.mark.parametrize("spec", [dict(data=4, model=2), dict(model=4), dict(data=4, fsdp=2)])
def test_sharded_generation_matches_unsharded(spec):
    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9], [7, 1], [6, 6, 6, 2]]

    expected = Generator(module, params, cfg)(prompts)
    mesh = MeshSpec(**spec).build()
    sharded = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    np.testing.assert_array_equal(sharded(prompts), expected)
    # a single prompt must also shard (batch pads up to the data-axis size)
    np.testing.assert_array_equal(sharded([prompts[0]]), expected[:1])


@pytest.mark.parametrize("impl,axes", [("ring", dict(data=1, sequence=8)), ("ulysses", dict(data=2, sequence=4))])
def test_sequence_parallel_prefill_matches_plain_generation(impl, axes):
    """Long-context handoff: prefill runs the decoder sequence-parallel under
    shard_map (ring KV rotation / ulysses all-to-all), the cache is assembled
    from the sown per-layer K/V, and decode proceeds on the ordinary cached
    path — tokens must equal the plain single-device engine."""
    module, params = _tiny()
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3], [7, 1, 8], [2, 8, 1, 8, 2, 8], [4, 6]]

    plain = Generator(
        module, params, GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    )(prompts)

    mesh = MeshSpec(**axes).build()
    sp = Generator(
        module, params,
        GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,), sp_prefill=impl),
        mesh=mesh,
    )
    np.testing.assert_array_equal(sp(prompts), plain)


def test_sharded_beam_search_matches_unsharded():
    """Beam search over a TP/data mesh (beams = batch rows, cache rows gathered
    to surviving parents under sharding) must pick the same sequences."""
    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6]]

    expected = Generator(module, params, cfg).beam_search(prompts, num_beams=4)
    mesh = MeshSpec(data=4, model=2).build()
    sharded = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    np.testing.assert_array_equal(sharded.beam_search(prompts, num_beams=4), expected)


def test_expert_parallel_generation_matches_unsharded():
    """MoE decoder served expert-parallel: stacked expert FFN weights sharded
    P('expert', ...) while the KV cache shards batch-over-data — tokens must
    equal the unsharded run (ample capacity: routing is drop-free on both paths)."""
    from unionml_tpu.models import MoEConfig, MoETransformer, moe_partition_rules

    config = MoEConfig.tiny(
        vocab_size=64, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=96,
        n_experts=4, k=2, capacity_factor=8.0, dtype=jnp.float32, param_dtype=jnp.float32,
    )
    module = MoETransformer(config)
    params = module.init(jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1], [5, 9, 2], [6, 5], [3, 5, 8, 9]]

    expected = Generator(module, params, cfg)(prompts)
    mesh = MeshSpec(data=2, expert=4).build()
    sharded = Generator(module, params, cfg, mesh=mesh, partition_rules=moe_partition_rules())
    np.testing.assert_array_equal(sharded(prompts), expected)


def test_quantized_sharded_generation_matches_quantized_unsharded():
    """int8 weights + int8 KV cache + TP mesh: the QuantizedTensor pytree and
    the cache's scale planes must place under the partition rules and emit the
    same tokens as quantized single-device generation."""
    module, params = _tiny()
    cfg = GenerationConfig(
        max_new_tokens=8, temperature=0.0, prompt_buckets=(16,), kv_cache_dtype="int8"
    )
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [7, 1, 8, 2], [2, 7]]

    expected = Generator(module, params, cfg, quantize="int8")(prompts)
    mesh = MeshSpec(data=2, fsdp=2, model=2).build()
    sharded = Generator(
        module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules(), quantize="int8"
    )
    np.testing.assert_array_equal(sharded(prompts), expected)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sp_prefix_cache_composes(impl):
    """sp_prefill + prefix caching: the LONG shared prefix prefills
    sequence-parallel (inside cache_prefix), per-request suffixes go through
    the offset chunked path, and the emitted tokens equal the plain engine's
    full-prompt run."""
    import dataclasses

    module, params = _tiny()
    base = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8, 32))
    prefix = [(i * 7) % 90 + 1 for i in range(24)]  # long enough to shard over 8
    suffixes = [[3, 1, 4], [9, 2, 6, 5]]
    expected = Generator(module, params, base)([prefix + s for s in suffixes])

    mesh = MeshSpec(data=1, sequence=8 if impl == "ring" else 4, model=2 if impl == "ulysses" else 1).build()
    sp_gen = Generator(
        module,
        params,
        dataclasses.replace(base, sp_prefill=impl),
        mesh=mesh,
        partition_rules=llama_partition_rules() if impl == "ulysses" else None,
    )
    cached = sp_gen.cache_prefix(prefix)
    assert cached.length == len(prefix)
    np.testing.assert_array_equal(sp_gen(suffixes, prefix=cached), expected)


def test_continuous_batching_over_tp_mesh():
    """Serving deployment shape: a ContinuousBatcher whose Generator is
    tensor-parallel over a model axis — concurrent streams share sharded decode
    dispatches and still emit exactly the unsharded engine's tokens."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [7, 1]]
    expected = [list(r) for r in Generator(module, params, cfg)(prompts)]

    mesh = MeshSpec(data=1, model=4).build(jax.devices()[:4])
    sharded = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    batcher = ContinuousBatcher(sharded, slots=3, decode_chunk=4)
    try:
        streams = [batcher.submit(p) for p in prompts]
        results = [
            [int(t) for chunk in s for t in np.asarray(chunk).ravel()] for s in streams
        ]
        assert results == expected
    finally:
        batcher.close()


def test_sharded_constrained_generation_matches_unsharded():
    """Constraints x TP: the DFA tables replicate over the mesh (tiny int32/bool
    arrays), the per-row state rides the sharded decode carry, and tokens equal
    the unsharded constrained run — grammar masking adds no sharding hazards."""
    module, params = _tiny()
    cs, eos = _letters_cs(r"[a-c]{2,6}")
    cfg = GenerationConfig(
        max_new_tokens=8, temperature=0.0, eos_id=eos, prompt_buckets=(16,), constraints=cs
    )
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5], [7, 1], [6, 6, 6, 2]]
    gids = [1, 0, 1, 0]

    expected = Generator(module, params, cfg)(prompts, constraint=gids)
    mesh = MeshSpec(data=4, model=2).build()
    sharded = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    np.testing.assert_array_equal(sharded(prompts, constraint=gids), expected)


def test_sequence_parallel_prefill_composes_with_constraints():
    """Long-context x grammar: the constrained first token is sampled inside
    the sequence-parallel prefill (the cstate tail threads through sp_prefill),
    and decode continues masking — tokens equal the plain constrained engine."""
    module, params = _tiny()
    cs, eos = _letters_cs(r"[a-c]{2,6}")
    base = GenerationConfig(
        max_new_tokens=6, temperature=0.0, eos_id=eos, prompt_buckets=(16,), constraints=cs
    )
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [7, 1, 8], [2, 8, 1, 8], [4, 6]]
    gids = [1, 0, 1, 0]

    plain = Generator(module, params, base)(prompts, constraint=gids)
    import dataclasses

    mesh = MeshSpec(data=2, sequence=4).build()
    sp = Generator(module, params, dataclasses.replace(base, sp_prefill="ring"), mesh=mesh)
    np.testing.assert_array_equal(sp(prompts, constraint=gids), plain)


def test_continuous_batching_constrained_over_tp_mesh():
    """Batcher x TP x grammar: per-request grammars through the shared decode
    loop against model-axis-sharded params/KV equal the unsharded constrained
    solo runs (the dryrun pins the unconstrained TP batcher; this is the cross)."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cs, eos = _letters_cs(r"[a-c]{2,6}")
    cfg = GenerationConfig(
        max_new_tokens=6, temperature=0.0, eos_id=eos, prompt_buckets=(16,), constraints=cs
    )
    prompts = [[3, 1, 4, 1], [9, 2, 6], [7, 1]]
    gids = [1, 0, 1]
    plain = Generator(module, params, cfg)
    solo = [_until_eos(plain([p], constraint=g)[0], eos) for p, g in zip(prompts, gids)]

    mesh = MeshSpec(data=1, model=2).build(jax.devices()[:2])
    tp_gen = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    batcher = ContinuousBatcher(tp_gen, slots=2, decode_chunk=2)
    try:
        streams = [batcher.submit(p, constraint=g) for p, g in zip(prompts, gids)]
        for stream, ref in zip(streams, solo):
            got = [int(t) for chunk in stream for t in np.atleast_1d(chunk)]
            assert got == ref
    finally:
        batcher.close()


def test_continuous_batching_with_sp_prefill():
    """Long-context admission (the round-4 hole at continuous.py): each
    admission's batch-1 row prefills ring-sequence-parallel over the mesh's
    sequence axis, pastes into the pool, and concurrent streams equal the plain
    single-device engine's tokens."""
    import dataclasses

    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6, 5, 3], [9, 2, 6], [7, 1, 8, 2, 8, 1]]
    expected = [list(r) for r in Generator(module, params, cfg)(prompts)]

    mesh = MeshSpec(data=1, sequence=4).build(jax.devices()[:4])
    sp_gen = Generator(module, params, dataclasses.replace(cfg, sp_prefill="ring"), mesh=mesh)
    batcher = ContinuousBatcher(sp_gen, slots=2, decode_chunk=3)
    try:
        streams = [batcher.submit(p) for p in prompts]
        results = [
            [int(t) for chunk in s for t in np.asarray(chunk).ravel()] for s in streams
        ]
        assert results == expected
    finally:
        batcher.close()


def test_continuous_batching_sp_prefill_paged():
    """sp admission x paged pool: the ring-prefilled row scatters into pool
    blocks like any dense row — the two round-4 composition holes close
    together, not just separately."""
    import dataclasses

    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [7, 1]]
    expected = [list(r) for r in Generator(module, params, cfg)(prompts)]

    mesh = MeshSpec(data=1, sequence=2).build(jax.devices()[:2])
    sp_gen = Generator(module, params, dataclasses.replace(cfg, sp_prefill="ring"), mesh=mesh)
    batcher = ContinuousBatcher(sp_gen, slots=2, decode_chunk=2, block_size=4)
    try:
        streams = [batcher.submit(p) for p in prompts]
        results = [
            [int(t) for chunk in s for t in np.asarray(chunk).ravel()] for s in streams
        ]
        assert results == expected
    finally:
        batcher.close()


def test_paged_tp_preemption_recovers_token_exact():
    """Pool pressure UNDER TP: a pool sized for one worst-case request forces
    recompute preemption while the pools are model-axis-sharded — the evicted
    stream re-prefills (possibly at an exact width no bucket covers) against
    the sharded params and its total output still equals the unsharded
    sequential run. Covers the preemption/resume machinery's first composition
    with sharding (previously pinned unsharded only, tests/unit/test_continuous.py)."""
    import threading

    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=12, temperature=0.0, prompt_buckets=(8,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3], [7, 1, 8]]
    expected = [list(r) for r in Generator(module, params, cfg)(prompts)]

    mesh = MeshSpec(data=1, model=2).build(jax.devices()[:2])
    tp_gen = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    probe = ContinuousBatcher(tp_gen, slots=3, decode_chunk=2, block_size=4)
    min_pool = probe.max_blocks
    probe.close()
    batcher = ContinuousBatcher(
        tp_gen, slots=3, decode_chunk=2, block_size=4, pool_blocks=min_pool
    )
    try:
        results = [None] * len(prompts)

        def worker(i):
            results[i] = [
                int(t) for chunk in batcher.submit(prompts[i]) for t in np.asarray(chunk).ravel()
            ]

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert results == expected
        stats = batcher.stats()["kv_blocks"]
        assert stats["preemptions"] > 0  # the tight pool actually evicted someone
        assert stats["used"] == 0  # allocator balanced after all streams drained
    finally:
        batcher.close()


@pytest.mark.parametrize("seed", [11, 73])
def test_paged_tp_randomized_stress_matches_solo(seed):
    """Seeded randomized soak over the paged x TP engine: mixed prompt lengths
    and budgets through a small sharded pool (admission-wait and preemption
    prone) — every stream token-exact against its solo (prompt, budget) run."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(8,))
    rng = np.random.default_rng(seed)
    jobs = []
    for _ in range(8):
        plen = int(rng.integers(1, 8))
        prompt = [int(t) for t in rng.integers(1, 90, size=plen)]
        budget = int(rng.integers(1, 9))
        jobs.append((prompt, budget))

    plain = Generator(module, params, cfg)
    # greedy truncation law: a budget-b run is the first b tokens of the full run
    refs = [list(plain([p])[0])[:b] for p, b in jobs]

    mesh = MeshSpec(data=1, model=2).build(jax.devices()[:2])
    tp_gen = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    batcher = ContinuousBatcher(tp_gen, slots=3, decode_chunk=2, block_size=2, pool_blocks=11)
    try:
        streams = [batcher.submit(p, max_new_tokens=b) for p, b in jobs]
        for i, (stream, ref) in enumerate(zip(streams, refs)):
            got = [int(t) for chunk in stream for t in np.asarray(chunk).ravel()]
            assert got == ref, (i, jobs[i], got, ref)
        assert batcher.stats()["kv_blocks"]["used"] == 0
    finally:
        batcher.close()


def test_everything_composes_over_tp_mesh():
    """The unit-ring capstone (int8 weights + int8 KV + paged pool + shared
    prefix + speculative + per-request grammars in one continuous engine) with
    the LAST axis added: a tensor-parallel mesh. Every concurrent stream stays
    token-exact against its solo run through the same maximal UNSHARDED engine."""
    from unionml_tpu.models import DraftSpec
    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cs, eos = _letters_cs(r"[a-c]{2,6}")
    draft_cfg = LlamaConfig.tiny(
        vocab_size=96, dim=32, n_layers=1, n_heads=2, n_kv_heads=1, hidden_dim=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    draft = Llama(draft_cfg)
    dp = draft.init(jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = GenerationConfig(
        max_new_tokens=8, temperature=0.0, eos_id=eos, prompt_buckets=(8,),
        kv_cache_dtype="int8", constraints=cs,
        draft=DraftSpec(module=draft, params=dp, gamma=2),
    )
    prompts = [[3, 14, 15], [7, 7, 9], [1, 2]]
    gids = [1, 0, 1]

    plain = Generator(module, params, cfg, quantize="int8")
    plain_prefix = plain.cache_prefix([11, 12, 13, 14])
    solo = [
        _until_eos(plain([p], constraint=g, prefix=plain_prefix)[0], eos)
        for p, g in zip(prompts, gids)
    ]

    mesh = MeshSpec(data=1, model=2).build(jax.devices()[:2])
    tp_gen = Generator(
        module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules(),
        quantize="int8",
    )
    tp_prefix = tp_gen.cache_prefix([11, 12, 13, 14])
    batcher = ContinuousBatcher(
        tp_gen, slots=2, decode_chunk=2, prefix=tp_prefix, block_size=4
    )
    try:
        streams = [batcher.submit(p, constraint=g) for p, g in zip(prompts, gids)]
        results = [
            [int(t) for chunk in s for t in np.asarray(chunk).ravel()] for s in streams
        ]
        assert results == solo
    finally:
        batcher.close()


def test_sp_prefill_resume_width_falls_back_to_dense():
    """A preemption resume's exact-width row can exceed every configured bucket;
    when its sequence-aligned width would overflow the cache, admission falls
    back to the (token-identical) dense prefill instead of failing the stream."""
    import dataclasses

    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    base = GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(8,))
    mesh = MeshSpec(data=1, sequence=4).build(jax.devices()[:4])
    sp_gen = Generator(module, params, dataclasses.replace(base, sp_prefill="ring"), mesh=mesh)
    batcher = ContinuousBatcher(sp_gen, slots=2, decode_chunk=2)
    try:
        # cache_len = 8 + 4 + 2 = 14; a 13-token resume fits exactly (13 + 1)
        # but chunk_aligned(13, 4) = 16 > 14 — the sp branch must not raise
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9]
        assert batcher.cache_len == 14
        tok0, *_ = batcher._prefill_row(prompt, 0, budget=1)
        expected = Generator(module, params, base)([prompt])
        assert int(np.asarray(tok0).ravel()[0]) == int(expected[0][0])
    finally:
        batcher.close()


def test_speculative_continuous_with_sp_prefill():
    """Speculative x sp x continuous: both the target's and the draft's batch-1
    admission rows prefill sequence-parallel (the draft Generator inherits the
    mesh and sp_prefill config), rounds advance through the shared spec loop,
    and each greedy stream equals the target-only solo run — the speculative
    exactness law survives ring-prefilled admission."""
    import dataclasses

    from unionml_tpu.models import DraftSpec
    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    draft_cfg = LlamaConfig.tiny(
        vocab_size=96, dim=32, n_layers=1, n_heads=2, n_kv_heads=1, hidden_dim=64,
        dtype=jnp.float32, param_dtype=jnp.float32,
    )
    draft = Llama(draft_cfg)
    dp = draft.init(jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))["params"]

    base = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [7, 1, 8], [2, 8]]
    expected = [list(r) for r in Generator(module, params, base)(prompts)]

    mesh = MeshSpec(data=1, sequence=2).build(jax.devices()[:2])
    cfg = dataclasses.replace(
        base, sp_prefill="ring", draft=DraftSpec(module=draft, params=dp, gamma=3)
    )
    sp_gen = Generator(module, params, cfg, mesh=mesh)
    batcher = ContinuousBatcher(sp_gen, slots=2, decode_chunk=2)
    try:
        streams = [batcher.submit(p) for p in prompts]
        results = [
            [int(t) for chunk in s for t in np.asarray(chunk).ravel()] for s in streams
        ]
        assert results == expected
    finally:
        batcher.close()


def test_paged_kv_over_tp_mesh():
    """Paged KV x TP (the round-4 hole at continuous.py): the heads-major pools
    shard over the model axis, tables replicate, and paged decode against
    model-sharded params emits exactly the unsharded dense engine's tokens —
    including through a pool small enough to force admissions to wait."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=8, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [7, 1], [2, 8, 1, 8]]
    expected = [list(r) for r in Generator(module, params, cfg)(prompts)]

    mesh = MeshSpec(data=1, model=4).build(jax.devices()[:4])
    sharded = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    batcher = ContinuousBatcher(sharded, slots=3, decode_chunk=4, block_size=8)
    try:
        streams = [batcher.submit(p) for p in prompts]
        results = [
            [int(t) for chunk in s for t in np.asarray(chunk).ravel()] for s in streams
        ]
        assert results == expected
        assert batcher.stats()["kv_blocks"]["total"] == batcher.pool_blocks
    finally:
        batcher.close()


def test_paged_kv_with_prefix_over_tp_mesh():
    """Paged x TP x shared prefix: shared prefix pages seeded once into the
    model-sharded pool, per-request suffixes allocated privately — tokens equal
    the unsharded engine run WITH the same prefix."""
    from unionml_tpu.serving import ContinuousBatcher

    module, params = _tiny()
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(8,))
    prefix_tokens = [11, 12, 13, 14, 15, 16, 17, 18]
    prompts = [[3, 1, 4], [9, 2], [7, 1, 8, 2]]

    plain = Generator(module, params, cfg)
    plain_prefix = plain.cache_prefix(prefix_tokens)
    expected = [list(r) for r in plain(prompts, prefix=plain_prefix)]

    mesh = MeshSpec(data=1, model=2).build(jax.devices()[:2])
    tp_gen = Generator(module, params, cfg, mesh=mesh, partition_rules=llama_partition_rules())
    tp_prefix = tp_gen.cache_prefix(prefix_tokens)
    batcher = ContinuousBatcher(tp_gen, slots=2, decode_chunk=3, prefix=tp_prefix, block_size=4)
    try:
        streams = [batcher.submit(p) for p in prompts]
        results = [
            [int(t) for chunk in s for t in np.asarray(chunk).ravel()] for s in streams
        ]
        assert results == expected
    finally:
        batcher.close()
