"""The cached several-token read (``ops/attention.py`` ``blocked_cached_attention``): a walk of the row
cache in key blocks that keeps the running softmax statistics equals one softmax over the whole row
under the visibility mask, for both layers' products and wherever the chunk, the row's end and the
window fall."""

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models.layers import Attention, LatentAttention, quantize_kv_rows
from unionml_tpu.ops import attention
from unionml_tpu.ops.attention import blocked_cached_attention, cache_visible, dot_product_attention

BLOCK = 8  # the rows the cells walk are 11 and 18 blocks long; these 4 and 5


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 8 keys, and the layers walk any row longer than 8, so that rows of tens of positions are walked."""
    monkeypatch.setattr(attention, "KEY_BLOCK", BLOCK)
    monkeypatch.setattr(attention, "ONE_TRIP_KEYS", BLOCK)


class Case(NamedTuple):
    row: int  # the row cache's positions
    start: int  # the chunk's first position
    chunk: int = 6
    lengths: tuple = (10**6,)  # a row's real tokens: queries at or past it are padding; one entry a row
    window: Optional[int] = None
    int8: bool = False
    blocks: Optional[int] = None  # blocks the walk has to cover, where the case pins it


CASES = {
    "chunk_at_offset_0": Case(row=40, start=0, blocks=1),
    "chunk_mid_row": Case(row=40, start=13, blocks=3),
    "chunk_ends_on_the_rows_last_slot": Case(row=40, start=34, blocks=5),
    "row_no_multiple_of_the_block": Case(row=29, start=23, blocks=4),  # the last block clamped to slots 21..28
    "row_shorter_than_a_block": Case(row=5, start=1, chunk=4, blocks=1),
    "window_cuts_lo_past_0": Case(row=40, start=30, window=9, blocks=3),  # the first visible slot is 22: blocks 2, 3 and 4
    "int8_row": Case(row=29, start=17, int8=True),
    "no_live_token": Case(row=40, start=13, lengths=(0,), blocks=0),
    "two_rows_one_padded": Case(row=29, start=8, lengths=(12, 0), blocks=2),
}


def gqa_products(rng, case: Case, batch: int, dtype):
    """``Attention``'s products over ``[B, S, H_kv, D]`` planes: query heads grouped by the KV head they read."""
    heads, n_kv, dim = 4, 2, 16
    q = rng.standard_normal((batch, case.chunk, heads, dim)).astype(np.float32)
    k, v = (rng.standard_normal((batch, case.row, n_kv, dim)).astype(np.float32) for _ in range(2))
    q, k, v = (jnp.asarray(t, dtype) for t in (q, k, v))
    planes = [k, v]
    if case.int8:
        (k, k_scale), (v, v_scale) = quantize_kv_rows(k), quantize_kv_rows(v)
        planes = [k, v, k_scale, v_scale]
    grouped = jnp.transpose(q.reshape(batch, case.chunk, n_kv, heads // n_kv, dim), (0, 2, 3, 1, 4))

    def dequant(plane, scale):
        return plane.astype(dtype) if scale is None else (plane.astype(jnp.float32) * scale).astype(dtype)

    def score(k, v, k_scale=None, v_scale=None):
        scores = jnp.einsum("bkgld,bskd->bkgls", grouped, dequant(k, k_scale), preferred_element_type=jnp.float32)
        return scores.reshape(batch, heads, case.chunk, -1) * dim**-0.5

    def value(weights, k, v, k_scale=None, v_scale=None):
        weights = weights.reshape(batch, n_kv, heads // n_kv, case.chunk, -1)
        out = jnp.einsum("bkgls,bskd->bkgld", weights, dequant(v, v_scale), preferred_element_type=jnp.float32)
        return out.reshape(batch, heads, case.chunk, dim)

    whole = (q, dequant(planes[0], planes[2] if case.int8 else None), dequant(planes[1], planes[3] if case.int8 else None))
    return score, value, planes, heads, dim, whole, dim**-0.5


def latent_products(rng, case: Case, batch: int, dtype):
    """``LatentAttention``'s absorbed products over one ``[B, S, 1, width]`` plane every head shares."""
    heads, rank, rope, stored = 3, 12, 4, 24
    q = jnp.asarray(rng.standard_normal((batch, case.chunk, heads, rank + rope)).astype(np.float32), dtype)
    rows = jnp.asarray(rng.standard_normal((batch, case.row, 1, stored)).astype(np.float32), dtype)
    q_abs, scale = jnp.transpose(q, (0, 2, 1, 3)), 0.2

    def score(block):
        return jnp.einsum("bhlw,bsw->bhls", q_abs, block[:, :, 0, : rank + rope], preferred_element_type=jnp.float32) * scale

    def value(weights, block):
        return jnp.einsum("bhls,bsc->bhlc", weights, block[:, :, 0, :rank], preferred_element_type=jnp.float32)

    return score, value, [rows], heads, rank, (q, rows[..., : rank + rope], rows[..., :rank]), scale


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "name, products",
    [
        pytest.param(name, products, id=f"{name}-{products.__name__.split('_')[0]}")
        for name, case in CASES.items()
        for products in (gqa_products, latent_products)
        if not (case.int8 and products is latent_products)  # a latent row has no int8 form
    ],
)
def test_the_blocked_read_equals_one_softmax_over_the_masked_row(name, products, dtype):
    case = CASES[name]
    batch = len(case.lengths)
    score, value, planes, heads, width, (q, keys, values), scale = products(np.random.default_rng(34), case, batch, dtype)
    positions = case.start + jnp.broadcast_to(jnp.arange(case.chunk)[None], (batch, case.chunk))
    live = positions < jnp.asarray(case.lengths)[:, None]

    out, covered = jax.jit(
        lambda planes: blocked_cached_attention(
            score, value, planes, positions, live, heads=heads, width=width, window=case.window, dtype=dtype
        )
    )(planes)

    visible = cache_visible(jnp.arange(case.row), positions, case.window)
    want = dot_product_attention(q, keys, values, mask=visible, softmax_scale=scale)
    assert out.shape == want.shape and out.dtype == dtype
    if not bool(live.any()):
        assert not np.asarray(out, np.float32).any()  # nothing is live: no block is walked, every query yields zero
    else:
        # queries up to the batch's last live one see every key the mask lets them; padding past it is nobody's
        seen = np.asarray(positions <= jnp.max(jnp.where(live, positions, -1)))
        tolerance = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[seen], np.asarray(want, np.float32)[seen], rtol=tolerance, atol=tolerance
        )
    if case.blocks is not None:
        block = min(BLOCK, case.row)
        assert int(covered) <= case.blocks * block and int(covered) > (case.blocks - 1) * block
    assert int(covered) <= case.row


def test_a_query_that_sees_no_key_yields_zero():
    """A live query whose window holds no slot of the row (its position lies past the row's end) yields
    zero beside queries that see theirs, as ``dot_product_attention`` gives a row with no visible key."""
    case = Case(row=16, start=14, chunk=4, window=1)  # queries 14 and 15 see themselves, 16 and 17 nothing
    score, value, planes, heads, width, (q, keys, values), scale = gqa_products(np.random.default_rng(1), case, 1, jnp.float32)
    positions = case.start + jnp.arange(case.chunk)[None]
    out, _ = blocked_cached_attention(
        score, value, planes, positions, jnp.ones_like(positions, bool), heads=heads, width=width, window=1, dtype=jnp.float32
    )
    want = dot_product_attention(q, keys, values, mask=cache_visible(jnp.arange(case.row), positions, 1), softmax_scale=scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert np.asarray(out)[0, :2].any() and not np.asarray(out)[0, 2:].any()


def _attention(window=None):
    return Attention(n_heads=4, n_kv_heads=2, head_dim=16, causal=True, rope=True, window=window, dtype=jnp.float32)


def _latent():
    return LatentAttention(n_heads=3, q_rank=8, kv_rank=12, nope_dim=8, rope_dim=4, v_dim=8, dtype=jnp.float32)


def _row_cache(row, int8=False):
    planes = {name: jnp.zeros((1, row, 2, 16), jnp.int8 if int8 else jnp.float32) for name in ("k", "v")}
    if int8:
        planes.update({name: jnp.zeros((1, row, 2, 1), jnp.float32) for name in ("k_scale", "v_scale")})
    return planes


LAYERS = {
    "gqa": (_attention, _row_cache),
    "gqa_window": (lambda: _attention(window=9), _row_cache),
    "gqa_int8": (_attention, lambda row: _row_cache(row, int8=True)),
    "latent": (_latent, lambda row: {"k": jnp.zeros((1, row, 1, 128), jnp.float32)}),
}


@pytest.mark.parametrize("kind", list(LAYERS))
def test_a_layers_chunks_equal_its_whole_row_read_and_count_what_they_walked(monkeypatch, kind):
    """Three chunks through a layer's row cache: the walk in blocks of 8 gives what one trip over the
    whole row under the mask gives (a row short enough is read so, as every row was), the cache comes
    out the same, and the counters say how far each read went."""
    make, row_cache = LAYERS[kind]
    layer, row, chunk, dim = make(), 29, 8, 32
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((1, 3 * chunk, dim)).astype(np.float32))
    params = layer.init(jax.random.PRNGKey(0), x[:, :chunk])["params"]

    def run(whole):
        monkeypatch.setattr(attention, "ONE_TRIP_KEYS", whole)
        cache = row_cache(row)
        outs, counted = [], []
        for start in range(0, 3 * chunk, chunk):
            positions = start + jnp.arange(chunk)[None]
            (out, cache), sown = layer.apply(
                {"params": params}, x[:, start : start + chunk], positions=positions, cache=cache, mutable=["counters"]
            )
            outs.append(out)
            counted.append({name: int(leaf[0]) for name, leaf in sown["counters"].items()})
        return jnp.concatenate(outs, axis=1), cache, counted

    blocked, blocked_cache, walked = run(BLOCK)
    whole, whole_cache, once = run(row)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), rtol=2e-5, atol=2e-5)
    jax.tree_util.tree_map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), blocked_cache, whole_cache)
    prefix = "latent" if kind == "latent" else "kv"
    attended, needed = f"{prefix}_positions_attended", f"{prefix}_positions_needed"
    assert [c[attended] for c in once] == [row] * 3  # one trip covers the row
    if kind == "gqa_window":  # the last chunk's first query, position 16, sees slots 8..: block 0 is skipped
        assert [c[attended] for c in walked] == [8, 16, 16]
        assert [c[needed] for c in walked] == [8, 16, 16] == [c[needed] for c in once]
    else:
        assert [c[attended] for c in walked] == [8, 16, 24]
        assert [c[needed] for c in walked] == [8, 16, 24] == [c[needed] for c in once]


@pytest.mark.parametrize("kind", ["gqa_window", "latent"])
def test_padding_rows_change_no_result_and_no_counter(kind):
    """A second row with no live token (a synthetic batch row) and a live row's padded tail change neither the
    live queries' outputs nor what the layer counts: the walk's bounds and both counters follow the live tokens."""
    make, row_cache = LAYERS[kind]
    layer, row, chunk, dim, real = make(), 29, 8, 32, 5
    x = jnp.asarray(np.random.default_rng(9).standard_normal((1, chunk, dim)).astype(np.float32))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    positions = 10 + jnp.arange(chunk)[None]

    def run(x, positions, token_mask, batch):
        cache = jax.tree_util.tree_map(lambda plane: jnp.concatenate([plane + 1] * batch), row_cache(row))
        (out, _), sown = layer.apply(
            {"params": params}, x, positions=positions, cache=cache, token_mask=token_mask, mutable=["counters"]
        )
        return out, {name: int(leaf[0]) for name, leaf in sown["counters"].items()}

    live = (jnp.arange(chunk) < real)[None]
    alone, counted = run(x[:, :real], positions[:, :real], None, 1)
    padded, padded_counted = run(
        jnp.concatenate([x, x + 3.0]), jnp.concatenate([positions, positions]), jnp.concatenate([live, ~live & live]), 2
    )
    np.testing.assert_allclose(np.asarray(padded[0, :real]), np.asarray(alone[0]), rtol=2e-5, atol=2e-5)
    assert padded_counted == counted and all(counted.values())
