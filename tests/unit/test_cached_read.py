"""The cached several-token read (``ops/attention.py`` ``blocked_cached_attention``): a walk of the row
cache in key blocks that keeps the running softmax statistics equals one softmax over the whole row
under the visibility mask, for both layers' products and wherever the chunk, the row's end and the
window fall."""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models.layers import Attention, LatentAttention, quantize_kv_rows
from unionml_tpu.ops import attention
from unionml_tpu.ops import chunk_attention as chunk_attention_module
from unionml_tpu.ops.attention import blocked_cached_attention, cache_visible, dot_product_attention
from unionml_tpu.ops.chunk_attention import CHUNK_KERNEL, CHUNK_XLA, chunk_attention, chunk_read_path
from unionml_tpu.ops.paged_attention import paged_read_scope

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
    """``Attention``'s operands: queries grouped by the KV head they read over ``[B, S, H_kv, D]`` planes."""
    heads, n_kv, dim = 4, 2, 16
    q = rng.standard_normal((batch, case.chunk, heads, dim)).astype(np.float32)
    k, v = (rng.standard_normal((batch, case.row, n_kv, dim)).astype(np.float32) for _ in range(2))
    q, k, v = (jnp.asarray(t, dtype) for t in (q, k, v))
    grouped = jnp.transpose(q.reshape(batch, case.chunk, n_kv, heads // n_kv, dim), (0, 2, 3, 1, 4))
    if not case.int8:
        return (grouped, k, v), {"scale": dim**-0.5}, (q, k, v)
    (k, k_scale), (v, v_scale) = quantize_kv_rows(k), quantize_kv_rows(v)
    keys, values = ((plane.astype(jnp.float32) * scale).astype(dtype) for plane, scale in ((k, k_scale), (v, v_scale)))
    return (grouped, k, v), {"scale": dim**-0.5, "scales": (k_scale, v_scale)}, (q, keys, values)


def latent_products(rng, case: Case, batch: int, dtype):
    """``LatentAttention``'s absorbed operands: every head's query, padded to the stored width, over one
    ``[B, S, 1, width]`` plane every head shares, scored whole and valued on its first ``rank`` channels."""
    heads, rank, rope, stored = 3, 12, 4, 24
    q = jnp.asarray(rng.standard_normal((batch, case.chunk, heads, rank + rope)).astype(np.float32), dtype)
    rows = jnp.asarray(rng.standard_normal((batch, case.row, 1, stored)).astype(np.float32), dtype)
    padded = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, stored - rank - rope)))
    grouped = jnp.transpose(padded, (0, 2, 1, 3))[:, None]
    return (grouped, rows, None), {"scale": 0.2, "value_width": rank}, (q, rows[..., : rank + rope], rows[..., :rank])


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
    (q, keys, values), options, (whole_q, whole_keys, whole_values) = products(np.random.default_rng(34), case, batch, dtype)
    positions = case.start + jnp.broadcast_to(jnp.arange(case.chunk)[None], (batch, case.chunk))
    live = positions < jnp.asarray(case.lengths)[:, None]

    out, covered = jax.jit(
        lambda q, keys, values: blocked_cached_attention(q, keys, values, positions, live, window=case.window, **options)
    )(q, keys, values)

    visible = cache_visible(jnp.arange(case.row), positions, case.window)
    want = dot_product_attention(whole_q, whole_keys, whole_values, mask=visible, softmax_scale=options["scale"])
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
    (q, keys, values), options, (whole_q, _, _) = gqa_products(np.random.default_rng(1), case, 1, jnp.float32)
    positions = case.start + jnp.arange(case.chunk)[None]
    out, _ = blocked_cached_attention(q, keys, values, positions, jnp.ones_like(positions, bool), window=1, **options)
    visible = cache_visible(jnp.arange(case.row), positions, 1)
    want = dot_product_attention(whole_q, keys, values, mask=visible, softmax_scale=options["scale"])
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
LAYERS.update({f"{kind}_kernel": LAYERS[kind] for kind in ("gqa", "gqa_window", "latent")})  # the Pallas read


def through_the_kernel(monkeypatch, kind):
    """A ``*_kernel`` kind reads its long rows through the Pallas kernel (interpret mode, blocks of 8), as a TPU would."""
    if kind.endswith("_kernel"):
        monkeypatch.setattr(chunk_attention_module, "chunk_read_path", lambda *args, **kwargs: CHUNK_KERNEL)
        monkeypatch.setattr(chunk_attention_module, "chunk_attention", functools.partial(chunk_attention, interpret=True))
        monkeypatch.setattr(chunk_attention_module, "KEY_BLOCK", BLOCK)


@pytest.mark.parametrize("kind", list(LAYERS))
def test_a_layers_chunks_equal_its_whole_row_read_and_count_what_they_walked(monkeypatch, kind):
    """Three chunks through a layer's row cache: the walk in blocks of 8 gives what one trip over the
    whole row under the mask gives (a row short enough is read so, as every row was), the cache comes
    out the same, and the counters say how far each read went."""
    through_the_kernel(monkeypatch, kind)
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
    prefix = "latent" if kind.startswith("latent") else "kv"
    attended, needed = f"{prefix}_positions_attended", f"{prefix}_positions_needed"
    assert [c[attended] for c in once] == [row] * 3  # one trip covers the row
    if kind.startswith("gqa_window"):  # the last chunk's first query, position 16, sees slots 8..: block 0 is skipped
        assert [c[attended] for c in walked] == [8, 16, 16]
        assert [c[needed] for c in walked] == [8, 16, 16] == [c[needed] for c in once]
    else:
        assert [c[attended] for c in walked] == [8, 16, 24]
        assert [c[needed] for c in walked] == [8, 16, 24] == [c[needed] for c in once]


@pytest.mark.parametrize("kind", ["gqa_window", "latent", "gqa_window_kernel", "latent_kernel"])
def test_padding_rows_change_no_result_and_no_counter(monkeypatch, kind):
    """A second row with no live token (a synthetic batch row) and a live row's padded tail change neither the
    live queries' outputs nor what the layer counts: the walk's bounds and both counters follow the live tokens."""
    through_the_kernel(monkeypatch, kind)
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


class KernelCase(NamedTuple):
    group: int  # query heads a KV head serves
    row: int  # the row cache's positions: a multiple of 8, no multiple of the kernel's 512-key block
    start: int  # the chunk's first position
    chunk: int = 64
    n_kv: int = 2
    widths: tuple = (128, 128, 128)  # key plane, scored channels, valued channels; a latent row is read as keys alone
    window: Optional[int] = None
    lengths: tuple = (10**6,)  # a row's real tokens, one entry a row


#: the long cells' reads at their rows and groups, cut to few KV heads and chunks of tens of tokens (interpret mode)
KERNEL_CASES = {
    "gqa7_global_offset_0": KernelCase(group=7, row=16392, start=0),
    "gqa7_global_mid_row": KernelCase(group=7, row=16392, start=9000, chunk=160),  # two query tiles of 128
    "gqa7_window_mid_row": KernelCase(group=7, row=16392, start=9000, window=4096),
    "gqa7_window_ends_on_the_rows_last_slot": KernelCase(group=7, row=16392, start=16392 - 48, window=4096),
    "gqa6_window_offset_0": KernelCase(group=6, row=8968, start=0, window=4096),
    "gqa6_window_mid_row": KernelCase(group=6, row=8968, start=5000, window=4096),
    "latent_offset_0": KernelCase(group=20, row=8968, start=0, n_kv=1, widths=(640, 576, 512)),
    "latent_mid_row": KernelCase(group=20, row=8968, start=8968 - 64, n_kv=1, widths=(640, 576, 512)),  # two tiles of 32
    "two_rows_padded_tail_and_none_live": KernelCase(group=7, row=8968, start=4000, lengths=(4030, 0), window=4096),
    "latent_two_rows_padded_tail": KernelCase(group=20, row=8968, start=700, n_kv=1, widths=(640, 576, 512), lengths=(720, 750)),
}


def kernel_operands(case: KernelCase, rng):
    """bf16 operands as the layers hand them: grouped queries whose channels past the scored ones are zeros (a latent
    query padded to its row's stored width), a key plane, and a value plane, or none where values are the keys' first
    channels."""
    batch, (stored, scored, valued) = len(case.lengths), case.widths
    q = rng.standard_normal((batch, case.n_kv, case.group, case.chunk, stored)).astype(np.float32)
    q[..., scored:] = 0
    keys = rng.standard_normal((batch, case.row, case.n_kv, stored)).astype(np.float32)
    values = None if case.n_kv == 1 else jnp.asarray(rng.standard_normal((batch, case.row, case.n_kv, valued)), jnp.bfloat16)
    positions = case.start + jnp.broadcast_to(jnp.arange(case.chunk)[None], (batch, case.chunk))
    live = positions < jnp.asarray(case.lengths)[:, None]
    return jnp.asarray(q, jnp.bfloat16), jnp.asarray(keys, jnp.bfloat16), values, positions, live


@pytest.mark.parametrize("name", list(KERNEL_CASES))
def test_the_chunk_kernel_equals_the_walk_and_the_masked_row(monkeypatch, name):
    """The Pallas read (interpret mode, at the kernel's own 512-key blocks and query tiles) gives, for every live
    query, what the XLA walk gives and what one softmax over the whole row under the mask gives, at the long cells'
    rows (partial last blocks), groups and widths; a row with no live query yields zeros."""
    case = KERNEL_CASES[name]
    q, keys, values, positions, live = kernel_operands(case, np.random.default_rng(42))
    stored, scored, valued = case.widths
    options = {"scale": scored**-0.5, "window": case.window, "value_width": valued if values is None else None}
    kernel = jax.jit(functools.partial(chunk_attention, interpret=True, **options))(q, keys, values, positions, live)
    monkeypatch.setattr(attention, "KEY_BLOCK", 512)
    walked, _ = jax.jit(lambda *a: blocked_cached_attention(*a, **options))(q, keys, values, positions, live)

    batch, length, heads = q.shape[0], case.chunk, case.n_kv * case.group
    whole_q = jnp.moveaxis(q, 3, 1).reshape(batch, length, heads, stored)
    whole_values = keys[..., :valued] if values is None else values
    visible = cache_visible(jnp.arange(case.row), positions, case.window)
    want = dot_product_attention(whole_q, keys, whole_values, mask=visible, softmax_scale=options["scale"])
    assert kernel.shape == walked.shape == want.shape == (batch, length, heads, valued) and kernel.dtype == jnp.bfloat16
    seen = np.asarray(live)
    got = np.asarray(kernel, np.float32)
    np.testing.assert_allclose(got[seen], np.asarray(walked, np.float32)[seen], rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(got[seen], np.asarray(want, np.float32)[seen], rtol=2e-2, atol=2e-2)
    assert np.isfinite(got).all()
    for row in np.flatnonzero(~seen.any(axis=1)):
        assert not got[row].any()  # no live query: no block is read


def test_a_query_the_kernel_finds_no_key_for_yields_zero():
    """Queries whose window holds no slot of the row (positions past its end) yield zero in the kernel, beside
    queries of the same tile that see theirs."""
    case = KernelCase(group=7, row=520, start=518, chunk=16, window=1)  # 518 and 519 see themselves, 520.. nothing
    q, keys, values, positions, live = kernel_operands(case, np.random.default_rng(3))
    out = chunk_attention(q, keys, values, positions, live, scale=0.1, window=1, interpret=True)
    got = np.asarray(out, np.float32)
    assert got[0, :2].any() and not got[0, 2:].any()
    np.testing.assert_allclose(got[0, :2], np.asarray(jnp.repeat(values, 7, axis=2)[0, 518:520], np.float32), rtol=1e-2)


def test_the_read_path_is_the_kernel_only_on_a_tpu_over_a_bf16_row_on_one_device(monkeypatch):
    """``chunk_read_path`` takes the XLA walk on the CPU, for an int8 row, for planes that are not lane-wide and
    inside a sharded scope; the kernel on a TPU over a bf16 row of whole lanes on one device; and records each
    choice in the enclosing scope."""
    keys = jax.ShapeDtypeStruct((1, 8968, 4, 128), jnp.bfloat16)
    latent = jax.ShapeDtypeStruct((1, 8968, 1, 640), jnp.bfloat16)
    with paged_read_scope(sharded=False) as paths:
        assert chunk_read_path(keys, keys) == CHUNK_XLA  # the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert chunk_read_path(keys, keys) == CHUNK_KERNEL
        assert chunk_read_path(latent, None) == CHUNK_KERNEL
        int8 = jax.ShapeDtypeStruct((1, 8968, 4, 128), jnp.int8)
        assert chunk_read_path(int8, int8) == CHUNK_XLA
        assert chunk_read_path(jax.ShapeDtypeStruct((1, 8968, 4, 16), jnp.bfloat16), None) == CHUNK_XLA
        assert chunk_read_path(jax.ShapeDtypeStruct((1, 8968, 4, 128), jnp.float32), None) == CHUNK_XLA
    assert paths == [CHUNK_XLA, CHUNK_KERNEL, CHUNK_KERNEL, CHUNK_XLA, CHUNK_XLA, CHUNK_XLA]
    with paged_read_scope(sharded=True) as paths:
        assert chunk_read_path(keys, keys) == CHUNK_XLA
    assert paths == [CHUNK_XLA]
