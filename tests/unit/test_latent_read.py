"""The one-token latent read over paged latent rows (``ops/paged_attention.py``
``paged_latent_decode_attention``): the Pallas kernel, in Pallas' TPU interpret mode on the CPU, gives the
absorbed read of the gathered rows for every row length a cell meets, yields zeros (never NaN) for a row with no
live token, and serves ``LatentAttention``'s decode as the gather does; ``paged_read_path`` names it only where a
TPU would run it."""

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unionml_tpu.models.layers import LatentAttention
from unionml_tpu.ops import paged_attention
from unionml_tpu.ops.paged_attention import (
    LATENT_GATHER, LATENT_KERNEL, paged_latent_decode_attention, paged_read_path, paged_read_scope,
)

WIDTH, LATENT, VALUES, PAGE = 640, 576, 512, 64  # the stored row, the latent (kv_rank + rope), kv_rank, a page


class ReadCase(NamedTuple):
    heads: int
    pages_per_row: int  # the table's width
    lengths: tuple  # one entry a row; 0 is a released slot


CASES = {
    # GLM-4.7-Flash's 20 heads over long_sat's table of 141 pages: no token, one, mid-page, a block's last position,
    # the row cache's 8,968 positions (mid-page of the table's last page) and the table's whole width
    "glm_rows": ReadCase(heads=20, pages_per_row=141, lengths=(0, 1, 100, 512, 8968, 141 * PAGE, 0, 1000)),
    # Ling-3.0-flash's 32 heads over think_sat's table of 56 pages: tables wider than the rows, live and free mixed
    "ling_rows": ReadCase(heads=32, pages_per_row=56, lengths=(700, 0, 64, 1024, 0, 56 * PAGE - 1)),
    "every_row_free": ReadCase(heads=20, pages_per_row=8, lengths=(0, 0, 0)),
}


def operands(case: ReadCase, rng, dtype=jnp.bfloat16):
    """A query per head in the latent's space and a plane of latent pages, both zero past the latent's 576
    values; each row's table names pages of its own up to its length and the scratch page after."""
    batch, width = len(case.lengths), case.pages_per_row
    held = [-(-length // PAGE) for length in case.lengths]
    n_pages = sum(held) + 1
    q = rng.standard_normal((batch, case.heads, WIDTH)).astype(np.float32) * 2
    q[..., LATENT:] = 0
    plane = rng.standard_normal((1, n_pages, PAGE, WIDTH)).astype(np.float32)
    plane[..., LATENT:] = 0
    table = np.full((batch, width), n_pages - 1, np.int32)
    order, at = rng.permutation(n_pages - 1), 0
    for row, n in enumerate(held):
        table[row, :n] = order[at : at + n]
        at += n
    return (
        jnp.asarray(q, dtype), jnp.asarray(plane, jnp.bfloat16), jnp.asarray(case.lengths, jnp.int32),
        jnp.asarray(table),
    )


def gathered_read(q, plane, lengths, table, scale):
    """The absorbed read of the gathered rows in float32: one softmax over each row's visible positions."""
    rows = plane[0][table]  # [B, P, page, W]
    rows = rows.reshape(rows.shape[0], -1, WIDTH).astype(jnp.float32)
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows, precision="highest") * scale
    scores = jnp.where(jnp.arange(rows.shape[1])[None, None] < lengths[:, None, None], scores, -jnp.inf)
    weights = jnp.nan_to_num(jax.nn.softmax(scores, axis=-1))  # a row that sees nothing: no weight
    return jnp.einsum("bhs,bsc->bhc", weights, rows[..., :VALUES], precision="highest")


def read(q, plane, lengths, table, scale):
    return jax.jit(functools.partial(paged_latent_decode_attention, scale=scale, value_width=VALUES, interpret=True))(
        q, plane, lengths, table
    )


@pytest.mark.parametrize("name", list(CASES))
def test_the_kernel_equals_the_gathered_read_and_a_free_row_reads_zeros(name):
    """At bf16, as the layer hands it, the kernel's read is the float32 read of the gathered rows to the output's
    bf16 rounding; a released slot's output is zeros, and no output is NaN."""
    case = CASES[name]
    q, plane, lengths, table = operands(case, np.random.default_rng(43))
    got = read(q, plane, lengths, table, 256**-0.5)
    assert got.shape == (len(case.lengths), case.heads, VALUES) and got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(gathered_read(q, plane, lengths, table, 256**-0.5))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=2e-3)
    free = np.asarray(lengths) == 0
    assert not got[free].any()


@pytest.mark.parametrize("name", ["glm_rows", "ling_rows"])
def test_the_kernel_is_the_float32_read(name):
    """A float32 query (split into bf16 terms inside the kernel, as the float32 weights are) gives the float32 read
    to float32 rounding: the bf16 MXU operands lose nothing."""
    case = CASES[name]
    q, plane, lengths, table = operands(case, np.random.default_rng(44), dtype=jnp.float32)
    got = np.asarray(read(q, plane, lengths, table, 192**-0.5))
    want = np.asarray(gathered_read(q, plane, lengths, table, 192**-0.5))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1.5e-5)


def test_the_path_is_the_kernel_only_on_a_tpu_over_a_bf16_whole_lane_plane_on_one_device(monkeypatch):
    """``paged_read_path(..., latent=True)``: ``latent_once_kernel`` on a TPU over a bf16 plane of whole lanes on
    one device for one query token; the gather on the CPU, for a plane stored unpadded (576), in float32 or int8,
    for several tokens and inside a sharded scope; each choice recorded in the enclosing scope."""
    q = jax.ShapeDtypeStruct((4, 1, 20, 256), jnp.bfloat16)
    plane = jax.ShapeDtypeStruct((1, 100, 64, WIDTH), jnp.bfloat16)

    def path(q=q, plane=plane, quantized=False):
        return paged_read_path("auto", q, plane, quantized=quantized, latent=True)

    assert LATENT_KERNEL == "latent_once_kernel"
    with paged_read_scope(sharded=False) as paths:
        assert path() == LATENT_GATHER  # the CPU
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert path() == LATENT_KERNEL
        assert path(plane=jax.ShapeDtypeStruct((1, 100, 64, LATENT), jnp.bfloat16)) == LATENT_GATHER
        assert path(plane=jax.ShapeDtypeStruct((1, 100, 64, WIDTH), jnp.float32)) == LATENT_GATHER
        assert path(quantized=True) == LATENT_GATHER
        assert path(q=jax.ShapeDtypeStruct((4, 3, 20, 256), jnp.bfloat16)) == LATENT_GATHER
    assert paths == [LATENT_GATHER, LATENT_KERNEL, LATENT_GATHER, LATENT_GATHER, LATENT_GATHER, LATENT_GATHER]
    with paged_read_scope(sharded=True) as paths:
        assert path() == LATENT_GATHER
    assert paths == [LATENT_GATHER]


def test_a_latent_layers_decode_through_the_kernel_equals_the_gather(monkeypatch):
    """One decode step of ``LatentAttention`` over a paged latent cache, with live rows and released slots: the
    kernel's read (forced, in interpret mode) gives the gather's outputs for the live rows, writes the same cache and
    counts the same ``latent_positions_read``; a released slot's output is finite (the kernel reads nothing for it)."""
    monkeypatch.setattr(
        paged_attention, "paged_latent_decode_attention", functools.partial(paged_latent_decode_attention, interpret=True)
    )
    page, pages_per_row, dim = 8, 6, 64
    live = jnp.asarray([True, False, True, True, False])
    lengths = np.asarray([13, 0, 1, 48, 30])  # tokens already in the row; the step writes one more where live
    batch = len(lengths)
    rng = np.random.default_rng(45)
    x = jnp.asarray(rng.standard_normal((batch, 1, dim)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(batch * pages_per_row).reshape(batch, pages_per_row), jnp.int32)
    pool = jnp.asarray(rng.standard_normal((1, batch * pages_per_row, page, 128)), jnp.bfloat16)
    pool = pool.at[..., 120 + 8 :].set(0)  # kv_rank + rope = 128: whole lanes, no tail
    positions = jnp.asarray(np.minimum(lengths, pages_per_row * page - 1)[:, None], jnp.int32)

    def step(impl):
        layer = LatentAttention(n_heads=4, q_rank=32, kv_rank=120, nope_dim=16, rope_dim=8, v_dim=16, impl=impl)
        params = layer.init(jax.random.PRNGKey(0), x)["params"]
        (out, cache), sown = layer.apply(
            {"params": params}, x, positions=positions, cache={"k": pool, "table": table}, token_mask=live[:, None],
            mutable=["counters"],
        )
        return np.asarray(out, np.float32), cache, int(sown["counters"]["latent_positions_read"][0])

    with paged_read_scope(sharded=False) as paths:
        kernel, kernel_cache, kernel_read = step("flash")
        gather, gather_cache, gather_read = step("xla")
    assert paths == [LATENT_KERNEL, LATENT_GATHER]
    seen = np.asarray(live)
    np.testing.assert_allclose(kernel[seen], gather[seen], rtol=3e-2, atol=3e-2)
    assert np.isfinite(kernel).all()
    np.testing.assert_array_equal(np.asarray(kernel_cache["k"], np.float32), np.asarray(gather_cache["k"], np.float32))
    assert kernel_read == gather_read == int((positions[:, 0] + 1)[seen].sum())
