"""The admission's paste writes a row as whole pages: the pool afterwards equals, at every position a read can
reach, a NumPy slab scatter of the same row (the form the paste had), on every plane of every layout; the shared
pages and every other request's blocks keep their bytes; the gather is its inverse; the handoff's export -> import
leaves the pool the local paste leaves; the shared prefix is seeded the same way."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models import GenerationConfig, Generator, Glm4MoeLiteConfig, Llama, LlamaConfig
from unionml_tpu.models.generate import gather_paged_rows, init_cache, init_paged_cache
from unionml_tpu.serving import ContinuousBatcher

BLOCK, SLOTS, POOL = 4, 3, 12  # positions a block; slots; pool blocks, the last of them the scratch block
SCRATCH = POOL - 1
#: name -> (configuration, KV dtype): keys and values in bfloat16, int8 planes with their float32 scale planes,
#: and the one-plane latent layout
LAYOUTS = {
    "bf16_kv": (lambda: LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2), None),
    "int8_kv": (lambda: LlamaConfig.tiny(dim=64, n_layers=2, n_heads=4, n_kv_heads=2), "int8"),
    "latent": (lambda: Glm4MoeLiteConfig.tiny(), None),
}
PLANES = {"bf16_kv": {"k", "v"}, "int8_kv": {"k", "v", "k_scale", "v_scale"}, "latent": {"k"}}
#: the slot's table row before the scratch padding: six distinct blocks, out of order
BLOCKS = [7, 2, 9, 4, 0, 5]


def _random_like(tree, seed):
    """Every plane filled with values of its own dtype that no two positions share by accident (tables left alone)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    rng = np.random.default_rng(seed)
    out = []
    for leaf in leaves:
        if leaf.dtype == jnp.int32:  # a table
            out.append(leaf)
        elif leaf.dtype == jnp.int8:
            out.append(jnp.asarray(rng.integers(-127, 128, leaf.shape), jnp.int8))
        else:
            out.append(jnp.asarray(rng.standard_normal(leaf.shape), jnp.float32).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _state(layout, cache_len, seed=0):
    """A pool that holds earlier tenants' values everywhere, a prefilled row, and the carry's three vectors."""
    make, kv_dtype = LAYOUTS[layout]
    config = make()
    max_blocks = -(-cache_len // BLOCK)
    pool = _random_like(init_paged_cache(config, SLOTS, POOL, BLOCK, max_blocks, kv_dtype=kv_dtype, fill_block=SCRATCH), seed)
    row = _random_like(init_cache(config, 1, cache_len, kv_dtype=kv_dtype), seed + 1)
    assert all(set(layer) == PLANES[layout] for layer in row)
    carry = (jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), jnp.int32), jnp.ones((SLOTS,), bool))
    return pool, row, carry, max_blocks


def _blocks_row(allocated, max_blocks):
    row = np.full((max_blocks,), SCRATCH, np.int32)
    row[:allocated] = BLOCKS[:allocated]
    return row


def _slab_scatter(pool, row, blocks_row, skip):
    """The paste as it was: position ``pos`` of the row goes, one ``[H, last]`` slab, to block
    ``blocks_row[pos // BLOCK]`` (the scratch block under ``skip`` pages) at offset ``pos % BLOCK``. In NumPy."""
    out = []
    for layer, planes in zip(pool, row):
        new = {name: np.array(buf) for name, buf in layer.items() if name != "table"}
        for name, buf in planes.items():
            buf = np.asarray(buf)
            for pos in range(buf.shape[1]):
                blk = SCRATCH if pos < skip * BLOCK else blocks_row[pos // BLOCK]
                new[name][:, blk, pos % BLOCK] = buf[0, pos]
        out.append(new)
    return out


@pytest.mark.parametrize("allocated", ["one_page", "every_page"])
@pytest.mark.parametrize("skip", [0, 2])
@pytest.mark.parametrize("cache_len", [24, 22])  # a block multiple, and not
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_pool_after_a_paste_is_the_slab_scatters_pool_wherever_a_read_can_reach(layout, cache_len, skip, allocated):
    pool, row, carry, max_blocks = _state(layout, cache_len)
    blocks_row = _blocks_row(1 if allocated == "one_page" else max_blocks, max_blocks)
    before = [{name: np.array(buf) for name, buf in layer.items()} for layer in pool]
    want = _slab_scatter(pool, row, blocks_row, skip)
    got, tok, lengths, done = ContinuousBatcher._paged_admit_impl(
        pool, row, *carry, 1, jnp.asarray([5]), jnp.asarray([cache_len - 3]), jnp.asarray(blocks_row), skip
    )
    assert (int(tok[1]), int(lengths[1]), bool(done[1])) == (5, cache_len - 3, False)
    assert (int(tok[0]), int(lengths[0]), bool(done[0])) == (0, 0, True)  # the other slots' entries stand
    written = [b for b in blocks_row[skip:] if b != SCRATCH]
    for layer, old, ref in zip(got, before, want):
        assert set(layer) == PLANES[layout] | {"table"}
        np.testing.assert_array_equal(np.asarray(layer["table"][1]), blocks_row)
        np.testing.assert_array_equal(np.asarray(layer["table"][0]), old["table"][0])
        for name in PLANES[layout]:
            new = np.asarray(layer[name])
            assert new.dtype == old[name].dtype
            for page, blk in enumerate(blocks_row):
                if blk not in written:  # a shared page or the scratch block
                    continue
                live = min(BLOCK, cache_len - page * BLOCK)  # the last page may reach past the row
                np.testing.assert_array_equal(new[:, blk, :live], ref[name][:, blk, :live])
                assert not new[:, blk, live:].astype(np.float32).any()  # zeros, where the slab form left the old tenant
            untouched = [b for b in range(POOL - 1) if b not in written]  # the shared (skipped) pages among them
            np.testing.assert_array_equal(new[:, untouched], old[name][:, untouched])  # byte for byte


@pytest.mark.parametrize("cache_len", [24, 22])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_gather_is_the_pastes_inverse_bit_for_bit(layout, cache_len):
    pool, row, carry, max_blocks = _state(layout, cache_len, seed=3)
    blocks_row = jnp.asarray(_blocks_row(max_blocks, max_blocks))
    pasted, *_ = ContinuousBatcher._paged_admit_impl(pool, row, *carry, 2, jnp.asarray([1]), jnp.asarray([9]), blocks_row)
    back = gather_paged_rows(pasted, blocks_row, cache_len)
    for got, want in zip(back, row):
        assert set(got) == set(want)
        for name in want:
            assert got[name].shape == want[name].shape and got[name].dtype == want[name].dtype
            np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(want[name]))
    # a narrower read is the row's head, a table of cached runs in another order reads those pages in that order
    head = gather_paged_rows(pasted, blocks_row, 10)
    swapped = gather_paged_rows(pasted, blocks_row[jnp.asarray([1, 0])], 2 * BLOCK)
    for narrow, turned, want in zip(head, swapped, row):
        for name in want:
            full = np.asarray(want[name])
            np.testing.assert_array_equal(np.asarray(narrow[name]), full[:, :10])
            np.testing.assert_array_equal(np.asarray(turned[name]), np.concatenate([full[:, BLOCK:2 * BLOCK], full[:, :BLOCK]], axis=1))


@pytest.mark.parametrize("skip", [0, 1])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_handoffs_export_then_import_leaves_the_pool_the_local_paste_leaves(layout, skip):
    """The importer writes the prompt's pages alone (3 of 6 here); the local paste writes the row's every page, the
    rest of them to the scratch block: off the scratch block the two pools agree bit for bit, tables and carry too."""
    cache_len, n_blocks = 22, 3
    pool, row, carry, max_blocks = _state(layout, cache_len, seed=5)
    blocks_row = jnp.asarray(_blocks_row(n_blocks, max_blocks))
    args = (*carry, 0, jnp.asarray([7]), jnp.asarray([11]), blocks_row, skip)
    pages = ContinuousBatcher._export_pages_impl(row, n_blocks, BLOCK)
    imported = ContinuousBatcher._paged_page_admit_impl(pool, pages, *args)
    local = ContinuousBatcher._paged_admit_impl(pool, row, *args)
    for a, b in zip(imported[1:], local[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(imported[0], local[0]):
        np.testing.assert_array_equal(np.asarray(a["table"]), np.asarray(b["table"]))
        for name in PLANES[layout]:
            np.testing.assert_array_equal(np.asarray(a[name][:, :SCRATCH]), np.asarray(b[name][:, :SCRATCH]))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_paste_is_one_program_whatever_the_slot_the_table_row_and_the_shared_count(layout):
    pool, row, carry, max_blocks = _state(layout, 22, seed=7)
    paste = jax.jit(ContinuousBatcher._paged_admit_impl)
    for slot, skip, allocated in ((0, 0, 6), (2, 1, 3), (1, 4, 4)):
        blocks_row = _blocks_row(allocated, max_blocks)
        got = paste(pool, row, *carry, np.int32(slot), jnp.asarray([1]), jnp.asarray([2]), blocks_row, np.int32(skip))
        want = ContinuousBatcher._paged_admit_impl(pool, row, *carry, slot, jnp.asarray([1]), jnp.asarray([2]), jnp.asarray(blocks_row), skip)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert paste._cache_size() == 1
    # one write a plane a layer, its updates whole pages in the pool's own layout (never a slab a position)
    jaxpr = jax.make_jaxpr(ContinuousBatcher._paged_admit_impl)(pool, row, *carry, 0, jnp.asarray([1]), jnp.asarray([2]), jnp.asarray(blocks_row), 0)
    writes = [eqn for eqn in _equations(jaxpr.jaxpr) if eqn.primitive.name == "scatter"]
    assert len(writes) == len(PLANES[layout]) * len(pool)
    for eqn in writes:
        plane, _, updates = (v.aval.shape for v in eqn.invars)
        assert updates == (plane[0], max_blocks, BLOCK, plane[3])


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_the_shared_prefix_is_seeded_as_whole_pages(kv_dtype):
    """An engine with a static prefix of 10 tokens over blocks of 4: the two full blocks hold the prefix's rows at
    their positions, bit for bit; the partial tail block is not seeded; every other block is as it was built."""
    module = Llama(LlamaConfig.tiny(vocab_size=97, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, dtype=jnp.float32, param_dtype=jnp.float32))
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    gen = Generator(module, params, GenerationConfig(max_new_tokens=4, temperature=0.0, prompt_buckets=(8,), kv_cache_dtype=kv_dtype))
    prefix = gen.cache_prefix([7, 7, 3, 9, 11, 2, 5, 8, 1, 6])
    engine = ContinuousBatcher(gen, slots=2, block_size=BLOCK, prefix=prefix)
    try:
        shared = list(engine._shared_prefix_blocks)
        assert len(shared) == 2
        pool = engine._init_carry()[0]
        for layer, pre in zip(pool, prefix.layers):
            for name, rows in pre.items():
                rows, plane = np.asarray(rows), np.asarray(layer[name])
                for page, blk in enumerate(shared):
                    np.testing.assert_array_equal(plane[:, blk], np.swapaxes(rows[0, page * BLOCK:(page + 1) * BLOCK], 0, 1))
                rest = [b for b in range(plane.shape[1]) if b not in shared]
                assert not plane[:, rest].astype(np.float32).any()
    finally:
        engine.close()


@pytest.mark.parametrize("programs, want", [
    ({"_paged_admit_impl": {"calls": 4.0, "seconds": 0.002}, "decode_steps": {"calls": 9.0, "seconds": 1.0}}, 0.5),
    ({"decode_steps": {"calls": 9.0, "seconds": 1.0}}, None),  # a slice without a paste, or a program without one
])
def test_admit_paste_ms_reads_the_pastes_program_by_its_name(programs, want):
    """The benchmark finds the paste in a device trace by the jitted function's name, which the engine keeps."""
    from perf.run import read_layer_metric

    assert jax.jit(ContinuousBatcher._paged_admit_impl).__name__ == "_paged_admit_impl"
    trace = {"programs": programs, "busy_s": 1.0, "window_s": 2.0}
    assert read_layer_metric("admit_paste_ms", {"kind": "serving"}, trace, None) == want
    assert read_layer_metric("admit_paste_ms", {"kind": "training"}, trace, None) is None
    assert read_layer_metric("admit_paste_ms", {"kind": "serving"}, None, None) is None

