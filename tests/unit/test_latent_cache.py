"""The cache's layout comes from the model: a latent layer is one plane of the stated width and nothing else, a
configuration that states no layout keeps keys and values at its own head width, the engine's programs move a
latent row as they move keys and values, the byte gauges count the planes' real bytes, int8 pages over a latent
layout raise, and under a mesh the latent plane replicates while the heads shard."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import (
    AfmoeConfig, AfmoeTransformer, GenerationConfig, Generator, Glm4MoeLiteConfig, Glm4MoeLiteTransformer, LlamaConfig,
    glm4_moe_lite_partition_rules,
)
from unionml_tpu.models.generate import cache_layout, gather_paged_rows, init_cache, init_paged_cache
from unionml_tpu.serving import ContinuousBatcher

MODELS = {
    "llama": lambda: LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2),
    "afmoe": lambda: AfmoeConfig.tiny(head_dim=32),  # a published head width that is not dim // n_heads = 16
    "glm4_moe_lite": lambda: Glm4MoeLiteConfig.tiny(),
    "glm4_moe_lite_two_lanes": lambda: Glm4MoeLiteConfig.tiny(kv_lora_rank=126),  # 126 + 4 values: 256 stored
}
#: planes by name as (heads, width)
PLANES = {
    "llama": {"k": (2, 16), "v": (2, 16)}, "afmoe": {"k": (2, 32), "v": (2, 32)},
    "glm4_moe_lite": {"k": (1, 128)}, "glm4_moe_lite_two_lanes": {"k": (1, 256)},
}


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_caches_are_built_from_the_models_layout(model):
    """``init_cache`` and ``init_paged_cache``: a latent model gets one plane of the stated width and no ``"v"``;
    ``Llama`` and ``AfmoeTransformer``, which state none, today's ``{"k", "v"}`` at ``n_kv_heads`` x their head width."""
    config, planes = MODELS[model](), PLANES[model]
    assert {name: plane[:2] for name, plane in cache_layout(config).items()} == planes
    dense = init_cache(config, 3, 24)
    paged = init_paged_cache(config, 4, 9, 8, 5, fill_block=8)
    assert len(dense) == len(paged) == config.n_layers
    for layer in dense:
        assert {name: buf.shape for name, buf in layer.items()} == {n: (3, 24, h, w) for n, (h, w) in planes.items()}
    for layer in paged:
        assert {name: buf.shape for name, buf in layer.items()} == {**{n: (h, 9, 8, w) for n, (h, w) in planes.items()}, "table": (4, 5)}
        assert layer["k"].dtype == config.dtype and int(layer["table"].min()) == 8


@pytest.mark.parametrize("model", ["llama", "afmoe"])
def test_int8_pages_keep_their_scale_planes_for_keys_and_values(model):
    config = MODELS[model]()
    heads, width = PLANES[model]["k"]
    layer = init_paged_cache(config, 2, 5, 4, 3, kv_dtype="int8", fill_block=4)[0]
    assert {n: (b.shape, str(b.dtype)) for n, b in layer.items() if n != "table"} == {
        "k": ((heads, 5, 4, width), "int8"), "v": ((heads, 5, 4, width), "int8"),
        "k_scale": ((heads, 5, 4, 1), "float32"), "v_scale": ((heads, 5, 4, 1), "float32"),
    }


def test_int8_pages_over_a_latent_layout_raise():
    config = Glm4MoeLiteConfig.tiny()
    for build in (lambda: init_cache(config, 1, 8, kv_dtype="int8"),
                  lambda: init_paged_cache(config, 2, 5, 4, 3, kv_dtype="int8", fill_block=4)):
        with pytest.raises(ValueError, match="latent plane"):
            build()
    module = Glm4MoeLiteTransformer(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    gen = Generator(module, params, GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(8,), kv_cache_dtype="int8"))
    with pytest.raises(ValueError, match="int8"):
        ContinuousBatcher(gen, slots=2, block_size=4, pool_blocks=8)
    with pytest.raises(ValueError, match="unsupported kv_cache_dtype"):
        init_cache(config, 1, 8, kv_dtype="fp8")


@pytest.mark.parametrize("model", ["llama", "glm4_moe_lite"])
def test_export_then_page_admit_round_trips_a_row_bit_for_bit(model):
    """The handoff's two programs over the model's own planes: a prefilled row sliced into pool-layout pages
    (``_export_pages_impl``), written whole-block into another pool (``_paged_page_admit_impl``) and gathered back
    (``gather_paged_rows``) is the row, bit for bit, on every plane; the skipped (shared) pages go to scratch."""
    config, block, n_blocks, slots, max_blocks = MODELS[model](), 4, 5, 3, 6
    row = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.shape[-1]), a.shape, jnp.float32).astype(a.dtype), init_cache(config, 1, 22)
    )
    pages = ContinuousBatcher._export_pages_impl(row, n_blocks, block)
    assert all(page[name].shape == (heads, n_blocks, block, width) for page in pages for name, (heads, width) in PLANES[model].items())
    pool = init_paged_cache(config, slots, 12, block, max_blocks, fill_block=11)
    blocks_row = jnp.asarray([7, 2, 9, 4, 0, 11], jnp.int32)
    tok, lengths, done = jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), bool)
    pool, tok, lengths, done = ContinuousBatcher._paged_page_admit_impl(
        pool, pages, tok, lengths, done, 1, jnp.asarray([5]), jnp.asarray([19]), blocks_row
    )
    assert (int(tok[1]), int(lengths[1]), bool(done[1])) == (5, 19, False)
    back = gather_paged_rows(pool, blocks_row, n_blocks * block)
    for got, want, layer in zip(back, row, pool):
        assert set(got) == set(want) == set(PLANES[model])
        np.testing.assert_array_equal(np.asarray(layer["table"][1]), np.asarray(blocks_row))
        for name in want:
            np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(want[name][:, : n_blocks * block]))
    # the same through the row scatter, its first two (shared) pages diverted to scratch
    fresh = init_paged_cache(config, slots, 12, block, max_blocks, fill_block=11)
    padded = jax.tree_util.tree_map(lambda a: jnp.pad(a, ((0, 0), (0, 2), (0, 0), (0, 0))), row)  # 24 = 6 blocks
    admitted, *_ = ContinuousBatcher._paged_admit_impl(fresh, padded, tok, lengths, done, 2, jnp.asarray([1]), jnp.asarray([3]), blocks_row, 2)
    for layer, want in zip(admitted, padded):
        for name in want:
            assert float(jnp.abs(layer[name][:, 7]).max()) == 0.0 and float(jnp.abs(layer[name][:, 2]).max()) == 0.0
            np.testing.assert_array_equal(np.asarray(layer[name][:, 9]), np.asarray(jnp.swapaxes(want[name][0, 8:12], 0, 1)))


@pytest.mark.parametrize("model", ["afmoe", "glm4_moe_lite"])
def test_the_byte_gauges_count_the_planes_real_bytes(model):
    """``_block_bytes`` is the sum of the planes' bytes: a published head width that is not ``dim // n_heads`` (the
    afmoe gauge reckoned 16 where the head is 32) and a latent pool (one plane of 128, zeros counted) read right."""
    config = MODELS[model]()
    module = (AfmoeTransformer if model == "afmoe" else Glm4MoeLiteTransformer)(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    gen = Generator(module, params, GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(8,)))
    engine = ContinuousBatcher(gen, slots=2, block_size=4, pool_blocks=8)
    try:
        stats = engine.stats()
        pool = engine._init_carry()[0]  # the pool as the engine builds it
    finally:
        engine.close()
    held = sum(buf.nbytes for layer in pool for name, buf in layer.items() if name != "table")
    assert stats["kv_blocks"]["block_bytes"] * (8 + 1) == held  # the pool's blocks and the scratch block
    per_position = sum(h * w for h, w in PLANES[model].values()) * 2  # bfloat16
    assert stats["kv_blocks"]["block_bytes"] == config.n_layers * 4 * per_position == stats["kv_layout"]["block_bytes"]
    assert stats["kv_layout"]["planes"] == {n: {"heads": h, "width": w, "value_bytes": 2} for n, (h, w) in PLANES[model].items()}


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 emulated devices")
def test_under_a_mesh_the_latent_plane_replicates_and_the_heads_shard():
    """``glm4_moe_lite_partition_rules`` on a ``model=2`` mesh of virtual CPU devices: the heads of ``q_up``,
    ``kv_up`` (columns) and ``o_proj`` (rows) shard, the down-projections and the latent plane (one head) do not,
    and generation through the sharded program emits the unsharded run's tokens."""
    from unionml_tpu.parallel import MeshSpec

    config = Glm4MoeLiteConfig.tiny(dtype=jnp.float32)
    module = Glm4MoeLiteTransformer(config)
    params = module.init(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = GenerationConfig(max_new_tokens=6, temperature=0.0, prompt_buckets=(16,))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8, 9]]
    expected = Generator(module, params, cfg)(prompts)
    mesh = MeshSpec(model=2).build(jax.devices()[:2])
    gen = Generator(module, params, cfg, mesh=mesh, partition_rules=glm4_moe_lite_partition_rules())
    attn = gen.params["layer_1"]["attn"]
    spec = lambda leaf: tuple(leaf.sharding.spec)  # noqa: E731
    assert spec(attn["q_up"]["kernel"])[-1] == "model" and spec(attn["kv_up"]["kernel"])[-1] == "model"
    assert spec(attn["o_proj"]["kernel"])[0] == "model"
    assert "model" not in spec(attn["q_down"]["kernel"]) and "model" not in spec(attn["kv_down"]["kernel"])
    placed = gen._place_paged_cache(init_paged_cache(config, 2, 5, 4, 6, fill_block=4))
    assert placed[0]["k"].sharding.is_fully_replicated and placed[0]["k"].shape[0] == 1
    np.testing.assert_array_equal(np.asarray(gen(prompts)), np.asarray(expected))
