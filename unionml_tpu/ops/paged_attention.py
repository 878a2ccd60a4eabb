"""The paged decode read: which path serves it, and the kernel path itself.

A decoder's cached attention over a paged pool
(:meth:`unionml_tpu.models.layers.Attention._paged_cached_attention`) has two
reads. The portable one gathers ``pool[:, table]`` — every row's whole block
table — back into the logical layout and attends under a visibility mask; its
traffic follows the cache's *capacity*. The other routes a single-token read
through the pallas paged-attention kernel that ships with JAX
(``jax.experimental.pallas.ops.tpu.paged_attention``): it DMAs the pages a
row's table names, up to the row's length, at KV-head width, streams them
block by block through an online softmax, and keeps no gathered copy — its
traffic follows the cache's *contents*.

The pool layout (``[H_kv, n_pages, page_size, D]``,
:func:`unionml_tpu.models.generate.init_paged_cache`) is the kernel's own, so
dispatch is zero-copy. The kernel is TPU-only (no interpret mode).
:func:`paged_read_path` is the policy: on a TPU the kernel is the decode path
wherever it can serve (measured on a v5e at Mistral-7B's widths, PERF.md
section 6 "PR 25"); everything else keeps the gather.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "GATHER",
    "LATENT_GATHER",
    "LATENT_KERNEL",
    "PAGED_KERNEL",
    "paged_decode_attention",
    "paged_latent_decode_attention",
    "paged_read_path",
    "paged_read_scope",
    "paged_window_decode_attention",
    "window_split",
]

#: the two paths a paged read can take, as ``stats()["decode_attention_path"]`` names them
PAGED_KERNEL = "paged_kernel"
GATHER = "gather"
#: the same two over a latent plane (one shared "KV head" whose key is the latent row and whose value is its head)
LATENT_KERNEL = "latent_paged_kernel"
LATENT_GATHER = "latent_gather"

_scope = threading.local()


@contextlib.contextmanager
def paged_read_scope(*, sharded: bool) -> Iterator[List[str]]:
    """Entered, at trace time, by whoever traces a model over a paged cache
    (:class:`unionml_tpu.models.generate.Generator` does, around every
    ``module.apply``). It tells the layer the one thing it cannot see from its
    operands — whether the pools are ``sharded`` over a mesh of several devices
    (a tracer carries no placement) — and yields the list to which each paged
    read traced inside appends the path it took."""
    previous = getattr(_scope, "current", None)
    paths: List[str] = []
    _scope.current = (sharded, paths)
    try:
        yield paths
    finally:
        _scope.current = previous


def paged_read_path(impl: str, q: jax.Array, k_pages: jax.Array, *, quantized: bool, latent: bool = False) -> str:
    """Which read serves ``q: [B, L, H, D]`` over ``k_pages: [H_kv, n_pages,
    page_size, D]``, decided from what the trace can observe and recorded in
    the enclosing :func:`paged_read_scope`.

    The kernel serves one query token over unquantized pages: ``L > 1``
    (speculative verify, chunked prefill) and int8 pages (the library widens
    the per-position scales to head width and DMAs them with the pages: 5 B an
    element against bf16's 2) always gather. ``impl="flash"`` forces the kernel
    for such a read and ``"xla"`` the gather; ``"auto"`` takes the kernel where
    it is known to run and to win: a TPU backend, bf16 pages with a lane-wide
    head (``D % 128 == 0``), and pools on one device (outside any scope a
    caller is taken to hold its pools on one device).

    ``latent``: ``k_pages`` is a latent plane (``[1, n_pages, page_size, W]``,
    :class:`unionml_tpu.models.layers.LatentAttention`) and ``q`` the heads'
    queries before absorption; the same rules decide, on the plane's stored
    width ``W`` (576 values stored unpadded gather; whole lanes, 640, take the
    kernel: :func:`paged_latent_decode_attention`), and the path is named
    :data:`LATENT_KERNEL` or :data:`LATENT_GATHER`. A latent plane is never
    sharded (one head), but its queries' heads are: under a mesh it gathers.
    """
    sharded, paths = getattr(_scope, "current", None) or (False, None)
    kernel = q.shape[1] == 1 and not quantized and impl in ("flash", "auto")
    if kernel and impl == "auto":
        kernel = (
            jax.default_backend() == "tpu"
            and k_pages.dtype == jnp.bfloat16
            and k_pages.shape[-1] % 128 == 0
            and not sharded
        )
    if latent:
        path = LATENT_KERNEL if kernel else LATENT_GATHER
    else:
        path = PAGED_KERNEL if kernel else GATHER
    if paths is not None:
        paths.append(path)
    return path


def _pages_per_block(pages_per_sequence: int, page_size: int) -> int:
    """Pages one compute block of the kernel streams, from the table's width.

    The kernel walks a row in blocks of this many pages and fetches a block
    whole, so a block is a trade between steps (each costs about half a
    microsecond a row and KV head besides its bytes) and positions fetched past
    the row's length. Measured on a v5e at 8 KV heads of 128 and pages of 64 (PERF.md
    section 6, "PR 25"): rows of ~350 positions in a table of 25 pages ran
    fastest at 8 pages a block (4 and 5: +16 %, 16: +7 %), rows of ~2,700 in a
    table of 56 at 16 (8: +10 %, 14: +4 %, 28: no better). So: 512 positions a
    block where a row can hold at most 2,048, else 1,024. The table is widened
    to a multiple (:func:`paged_decode_attention`), so any width is served."""
    positions = 512 if pages_per_sequence * page_size <= 2048 else 1024
    return max(1, min(positions // page_size, pages_per_sequence))


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    lengths: jax.Array,
    page_indices: jax.Array,
    *,
    k_scales: Optional[jax.Array] = None,
    v_scales: Optional[jax.Array] = None,
    pages_per_compute_block: Optional[int] = None,
) -> jax.Array:
    """One decode step of attention over paged K/V.

    ``q: [B, H, D]``, ``k_pages/v_pages: [H_kv, n_pages, page_size, D]``,
    ``lengths: [B] int32`` (visible positions per row, INCLUDING the token just
    written), ``page_indices: [B, pages_per_sequence] int32``. Returns
    ``[B, H, D]``. Grouped-query attention is native (``H % H_kv == 0``).

    ``k_scales``/``v_scales`` (``[H_kv, n_pages, page_size, 1]`` f32, OUR int8
    convention: ``dequant = int8 * scale``) switch to the kernel's quantized
    page path; our scales map exactly via ``h = scale * 127.5`` (the kernel
    dequantizes ``int8 * h / 127.5``). CAVEAT: the library broadcasts the
    scales to FULL head width before launch and DMAs them per page, so int8
    pages cost ~5 B/elem of traffic vs bf16's 2 — no model path takes this
    mode (:func:`paged_read_path` gathers int8 pages); ``chip_smoke.py`` checks it.

    The library kernel computes RAW ``qk`` logits (no softmax scale anywhere in
    ``paged_flash_attention_kernel``), so ``q`` is pre-scaled by
    ``head_dim ** -0.5`` here — numerics then match
    :func:`unionml_tpu.ops.attention.dot_product_attention` and the gather path.
    """
    from jax.experimental.pallas.ops.tpu.paged_attention import quantization_utils

    if (k_scales is None) != (v_scales is None):
        raise ValueError("k_scales and v_scales must be passed together")
    page_size = k_pages.shape[2]
    ppcb = pages_per_compute_block or _pages_per_block(page_indices.shape[1], page_size)
    if k_scales is not None:
        k_pages = quantization_utils.QuantizedTensor(
            weight=k_pages, scales=(k_scales * quantization_utils.MAX_INT8).astype(jnp.float32)
        )
        v_pages = quantization_utils.QuantizedTensor(
            weight=v_pages, scales=(v_scales * quantization_utils.MAX_INT8).astype(jnp.float32)
        )
    # f32 in, so the softmax scale is not rounded into a bf16 query; the kernel
    # returns its launch dtype (f32 here), the caller gets the query's own
    out = _launch(q.astype(jnp.float32) * q.shape[-1] ** -0.5, k_pages, v_pages, lengths, page_indices, page_size, ppcb)
    return out.astype(q.dtype)


def _launch(q, k_pages, v_pages, lengths, page_indices, page_size: int, ppcb: int) -> jax.Array:
    """The library's paged-attention kernel over a table of any width (``q`` pre-scaled: the kernel applies none)."""
    from jax.experimental.pallas.ops.tpu.paged_attention import paged_attention

    if page_indices.shape[1] % ppcb:
        # the kernel tiles the table exactly: widen it with page 0, which no length reaches
        page_indices = jnp.pad(page_indices, ((0, 0), (0, -page_indices.shape[1] % ppcb)))
    return paged_attention(
        q,
        k_pages,
        v_pages,
        # a length past the table's end would send the kernel's DMAs off the row's pages
        jnp.minimum(lengths.astype(jnp.int32), page_indices.shape[1] * page_size),
        page_indices,
        pages_per_compute_block=ppcb,
    )


def paged_latent_decode_attention(
    q_abs: jax.Array,
    pages: jax.Array,
    lengths: jax.Array,
    page_indices: jax.Array,
    *,
    scale: float,
    value_width: int,
    pages_per_compute_block: Optional[int] = None,
) -> jax.Array:
    """One decode step of ABSORBED latent attention over paged latent rows.

    ``q_abs: [B, H, W]`` (each head's query in the latent's space, zeros past
    the latent's real width), ``pages: [1, n_pages, page_size, W]`` (one plane:
    the key of every head is the whole row, the value its first ``value_width``
    channels), ``lengths``/``page_indices`` as :func:`paged_decode_attention`.
    Returns ``[B, H, value_width]``.

    The library's paged-attention kernel launched with the plane as K and as V:
    one KV head, ``H`` query heads in its group, ``q`` pre-scaled by ``scale``
    (the kernel applies none; the latent's softmax scale is the expanded head's,
    not ``W ** -0.5``), the output's first ``value_width`` channels kept. Each
    live page is DMA'd twice (once as K, once as V), at the stored width: 2 x W
    values a position against the latent's ``value_width + rope``; a kernel that
    reads a page once would halve it (PERF.md section 7). ``W`` must be whole
    lanes (Mosaic refuses a 576-wide block)."""
    page_size = pages.shape[2]
    # 512 positions a compute block whatever the table's width: measured on a v5e at the long_sat cell's shape (48
    # rows x 140 pages of 64 x 640, 24 live at 3,920 and 24 free): 4 pages 0.476 ms, 8 0.431, 16 0.475 (PERF.md
    # section 6, "PR 30"); a free row streams one block whole, and a 640-wide block is five times a 128-wide one
    ppcb = pages_per_compute_block or max(1, min(512 // page_size, _pages_per_block(page_indices.shape[1], page_size)))
    out = _launch(q_abs.astype(jnp.float32) * scale, pages, pages, lengths, page_indices, page_size, ppcb)
    return out[..., :value_width].astype(q_abs.dtype)


def window_split(lengths: jax.Array, window: int, page_size: int):
    """Where a sliding layer's decode read begins: ``(edge_page, edge_start,
    tail_lengths)``, each ``[B] int32``.

    A row of ``lengths`` visible positions (the token just written included)
    sees keys ``[max(0, length - window), length)``. ``edge_page =
    floor(max(0, length - window) / page_size)`` is the first page of the table
    the read touches: the pages before it are never read. Of that page only the
    positions from ``edge_start`` (the window's start, an absolute position) on
    are visible; the pages after it, ``tail_lengths`` positions in all, are
    visible whole up to the row's length."""
    lengths = lengths.astype(jnp.int32)
    edge_start = jnp.maximum(lengths - window, 0)
    edge_page = edge_start // page_size
    tail_lengths = jnp.maximum(lengths - (edge_page + 1) * page_size, 0)
    return edge_page, edge_start, tail_lengths


def _paged_attention_stats(q, k_pages, v_pages, lengths, page_indices, *, pages_per_compute_block: int):
    """The library's paged-attention kernel launched as the library launches it
    (bf16 pages, no megacore, the sequence loop inlined), keeping the two
    outputs its wrapper drops: returns ``(o, m, l)`` with ``o: [B, H, D]`` the
    normalized output, ``m: [B, H, 1]`` each head's running maximum of the raw
    ``q.k`` logits and ``l: [B, H, 1]`` the sum of ``exp(logit - m)`` — what a
    caller needs to merge the read with keys the kernel was not shown. A row of
    length 0 reads nothing: ``o = 0``, ``l = 0``, ``m = -inf``."""
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.paged_attention import paged_attention_kernel as lib

    batch, n_heads, head_dim = q.shape
    n_kv, _, page_size, _ = k_pages.shape
    pages_per_sequence = page_indices.shape[1]
    groups = n_heads // n_kv
    if groups % 8:
        # as the library does: a [groups, 1, D] block keeps the <1x128> layout Mosaic wants
        q = q.reshape(batch, n_heads, 1, head_dim).astype(jnp.float32)
        block = pl.BlockSpec((None, groups, None, head_dim), lambda core, b, h, *_: (b, h, 0, 0))
    else:
        block = pl.BlockSpec((None, groups, head_dim), lambda core, b, h, *_: (b, h, 0))
    page_buffer = pltpu.VMEM((2, pages_per_compute_block, page_size, head_dim), k_pages.dtype)
    o, m, l = pl.pallas_call(
        functools.partial(
            lib.paged_flash_attention_kernel_inline_seq_dim,
            pages_per_sequence=pages_per_sequence,
            batch_size=batch,
            pages_per_compute_block=pages_per_compute_block,
            mask_value=lib.DEFAULT_MASK_VALUE,
            attn_logits_soft_cap=None,
            megacore_mode=None,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # lengths, page indices, buffer index, init flag
            in_specs=[block, pl.BlockSpec(memory_space=pl.ANY), None, pl.BlockSpec(memory_space=pl.ANY), None],
            out_specs=[block, block, block],
            grid=(1, batch, n_kv),
            scratch_shapes=(
                page_buffer, None, page_buffer, None,
                pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
            ),
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((*q.shape[:-1], 1), jnp.float32),
            jax.ShapeDtypeStruct((*q.shape[:-1], 1), jnp.float32),
        ],
        name="paged_window_attention",
    )(lengths, page_indices.reshape(-1), jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32), q, k_pages, None, v_pages, None)
    return o.reshape(batch, n_heads, head_dim), m.reshape(batch, n_heads, 1), l.reshape(batch, n_heads, 1)


def paged_window_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    lengths: jax.Array,
    page_indices: jax.Array,
    *,
    window: int,
) -> jax.Array:
    """One decode step of SLIDING-WINDOW attention over paged K/V: key ``j`` is
    visible to the row's query (at position ``length - 1``) iff ``length -
    window <= j < length``. Shapes as :func:`paged_decode_attention`.

    The library kernel masks a row's END (its length) and nothing else, so the
    read is split at the first page boundary inside the window
    (:func:`window_split`). The whole pages after it go through the kernel, by a
    table shifted to start there (``window / page_size`` entries at most, so the
    read follows the window, not the row's length); the one page the window's
    start falls in is gathered (a page a row: ``H_kv * page_size * D`` values)
    and masked to the window's start here; the two parts merge by their softmax
    statistics. A row shorter than the window reads all its pages, the first of
    them through the gathered part."""
    n_kv, _, page_size, head_dim = k_pages.shape
    batch, n_heads, _ = q.shape
    groups = n_heads // n_kv
    edge_page, edge_start, tail_lengths = window_split(lengths, window, page_size)
    scaled = q.astype(jnp.float32) * head_dim**-0.5

    # the whole pages after the edge page: at most window / page_size of them hold visible keys
    width = min(page_indices.shape[1], -(-window // page_size))
    ppcb = _pages_per_block(width, page_size)
    width = -(-width // ppcb) * ppcb  # the kernel tiles the table exactly; no length reaches the padding
    column = jnp.minimum(edge_page[:, None] + 1 + jnp.arange(width)[None], page_indices.shape[1] - 1)
    shifted = jnp.take_along_axis(page_indices, column, axis=1)
    o_tail, m_tail, l_tail = _paged_attention_stats(
        scaled, k_pages, v_pages, jnp.minimum(tail_lengths, width * page_size), shifted, pages_per_compute_block=ppcb
    )

    # the edge page, masked to the window's start (and to the row's length, where the row ends inside it)
    edge = jnp.take_along_axis(page_indices, edge_page[:, None], axis=1)[:, 0]  # [B]
    k_edge = jnp.moveaxis(k_pages[:, edge], 0, 1).astype(jnp.float32)  # [B, H_kv, page, D]
    v_edge = jnp.moveaxis(v_pages[:, edge], 0, 1).astype(jnp.float32)
    grouped = scaled.reshape(batch, n_kv, groups, head_dim)
    logits = jnp.einsum("bhgd,bhtd->bhgt", grouped, k_edge)
    position = edge_page[:, None] * page_size + jnp.arange(page_size)[None]  # [B, page]
    visible = (position >= edge_start[:, None]) & (position < lengths.astype(jnp.int32)[:, None])
    logits = jnp.where(visible[:, None, None], logits, -jnp.inf)
    m_tail = m_tail.reshape(batch, n_kv, groups, 1)
    l_tail = l_tail.reshape(batch, n_kv, groups, 1)
    m = jnp.maximum(jnp.max(logits, axis=-1, keepdims=True), m_tail)  # the edge page always shows a key: finite
    p_edge = jnp.exp(logits - m)
    tail_weight = l_tail * jnp.exp(m_tail - m)
    out = tail_weight * o_tail.astype(jnp.float32).reshape(batch, n_kv, groups, head_dim)
    out = out + jnp.einsum("bhgt,bhtd->bhgd", p_edge, v_edge)
    out = out / (tail_weight + jnp.sum(p_edge, axis=-1, keepdims=True))
    return out.reshape(batch, n_heads, head_dim).astype(q.dtype)
