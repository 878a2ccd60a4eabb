"""Attention ops: XLA reference implementation + dispatch to the pallas flash kernel.

The reference framework contains no attention code at all (SURVEY.md §5.7 — it never
looks inside a model); our model library needs it for the BERT/Llama/ViT configs, and
on TPU the attention inner loop is the canonical pallas target: keeping the running
softmax statistics in VMEM avoids materializing the [L, L] score matrix in HBM.

Layout convention throughout: ``[batch, length, heads, head_dim]`` (BLHD) — the
sequence dim sits next to batch so sequence-parallel sharding specs stay rank-stable.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

#: key positions one block of :func:`blocked_cached_attention` covers (chosen on a v5e at
#: ``glm47-flash.long_sat``'s shape: 256, 512 and 1,024 read 476.4, 475.9 and 467.3 tokens/s
#: there; PERF.md section 6, "PR 34")
#: in the XLA walk, and the unit of the span it reports; the Pallas kernel reads blocks of its own
#: (:func:`unionml_tpu.ops.chunk_attention._key_block`)
KEY_BLOCK = 512
#: a cached read of several tokens walks a row cache longer than this in key blocks and reads a
#: shorter one whole under its mask (:func:`walks_in_blocks`): inside a whole chunk program the
#: ``while`` costs more than a walk over a few blocks can skip (Mistral-7B's 16-layer chunk, v5e:
#: 14.3 -> 16.5 ms over a 1,544-position row, 16.5 -> 18.5 over 3,584 at offset 2,304, where over
#: 5,384 Trinity's falls 7.2-7.5 -> 3.4-5.3 and over 8,968 GLM's 28.8 -> 17; same section)
ONE_TRIP_KEYS = 4096


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Reference attention in pure XLA ops (always correct, any backend).

    :param q: ``[B, Lq, H, D]``; ``k``/``v``: ``[B, Lk, H, D]`` (or ``[B, Lk, Hkv, D]``
        with ``H % Hkv == 0`` for grouped-query attention).
    """
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    n_heads, n_kv = q.shape[2], k.shape[2]
    if n_kv != n_heads:  # grouped-query: repeat KV heads
        k = jnp.repeat(k, n_heads // n_kv, axis=2)
        v = jnp.repeat(v, n_heads // n_kv, axis=2)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    visible = None
    if causal:
        q_idx = jnp.arange(q.shape[1])[:, None]
        k_idx = jnp.arange(k.shape[1])[None, :]
        causal_mask = (q_idx >= (k_idx - (k.shape[1] - q.shape[1])))[None, None]
        scores = jnp.where(causal_mask, scores, jnp.finfo(scores.dtype).min)
        visible = causal_mask
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        visible = mask if visible is None else jnp.logical_and(visible, mask)

    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if visible is None:
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    # a row with NO visible keys is zero, not the uniform-softmax mean of V that
    # softmax(-inf row) would produce — matching ring and flash attention
    weights = jnp.where(visible.any(axis=-1, keepdims=True), weights, 0)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def cache_visible(slots: jax.Array, positions: jax.Array, window: Optional[int] = None) -> jax.Array:
    """``[B, 1, L, S]``: cache slot ``j`` of ``slots [S]`` is visible to the query at absolute
    position ``p`` of ``positions [B, L]`` iff ``j <= p`` — causal over everything written so
    far, hiding slots not yet (re)written — and, under a window, ``j > p - window``."""
    at = positions[:, None, :, None]
    visible = slots <= at
    if window is not None:
        visible = visible & (slots > at - window)
    return visible


def walks_in_blocks(length: int, size: int) -> bool:
    """Whether a cached read of ``length`` query tokens over a row cache of ``size`` positions goes
    through :func:`blocked_cached_attention`: several tokens over a row longer than
    :data:`ONE_TRIP_KEYS`. One token, and any read of a shorter row, attends the whole row under
    :func:`cache_visible` (both static: what the trace observes, no option)."""
    return length > 1 and size > ONE_TRIP_KEYS


def blocked_cached_attention(
    q: jax.Array,
    keys: jax.Array,
    values: Optional[jax.Array],
    positions: jax.Array,
    live: jax.Array,
    *,
    scale: float,
    window: Optional[int] = None,
    value_width: Optional[int] = None,
    scales: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Several queries a row over a row cache, reading the keys they can see: grouped
    queries ``q [B, H_kv, G, L, D_k]`` (query head ``kv * G + g`` reads KV head ``kv``)
    over the key plane ``keys [B, S, H_kv, D_k]`` and the value plane ``values [B, S,
    H_kv, D_v]``, or, with ``values`` None, the keys' first ``value_width`` channels (a
    latent row's). ``scales``: an int8 row's ``(k_scale, v_scale)`` planes ``[B, S, H_kv,
    1]``. Scores are ``q . k * scale`` in float32; weights meet the values in ``q.dtype``.

    The row is walked in blocks of :data:`KEY_BLOCK` positions from the block that holds
    the first visible key to the one that holds the last live query, with the running
    softmax statistics (a float32 maximum, sum and accumulator) kept between blocks.
    Both bounds are traced values (``positions [B, L]`` absolute, ``live [B, L]`` the
    real tokens), so the walk is one program whatever the chunk's offset; nothing live,
    nothing read. Where :func:`~unionml_tpu.ops.chunk_attention.chunk_read_path` says (a
    TPU, a bf16 row on one device, lane-wide planes) the walk is the Pallas kernel
    :func:`~unionml_tpu.ops.chunk_attention.chunk_attention`, which keeps scores, weights
    and statistics in VMEM and bounds each query tile by its own queries; everywhere else
    it is ONE ``while`` with a dynamic trip count inside the caller's program, one pair of
    bounds for the batch, dequantising an int8 row a block at a time. Visibility is
    :func:`cache_visible` on the block's absolute slots; in the ``while`` the last block of
    a row that is no multiple of the block is clamped to end on the row's last slot and
    masks the slots the block before it already counted (a row shorter than a block is
    one block). A query that sees no key yields zero, as :func:`dot_product_attention` does.

    Returns ``([B, L, H_kv * G, D_v]`` in ``q.dtype``, the key positions the walk spans a
    row, from its first block to its last``)``.
    """
    from unionml_tpu.ops.chunk_attention import CHUNK_KERNEL, chunk_attention, chunk_read_path

    batch, n_kv, group, length, _ = q.shape
    size = keys.shape[1]
    block = min(KEY_BLOCK, size)
    last = jnp.max(jnp.where(live, positions, -1))
    hi = jnp.minimum((last + block) // block, -(-size // block))  # blocks up to the last live query; 0 with none
    lo = 0
    if window is not None:
        first = jnp.min(jnp.where(live, positions, last))
        lo = jnp.minimum(jnp.maximum(first - window + 1, 0) // block, hi)
    covered = jnp.maximum(jnp.minimum(hi * block, size) - lo * block, 0).astype(jnp.int32)
    if chunk_read_path(keys, values) == CHUNK_KERNEL:
        out = chunk_attention(q, keys, values, positions, live, scale=scale, window=window, value_width=value_width)
        return out, covered

    k_scale, v_scale = scales if scales is not None else (None, None)
    low = jnp.finfo(jnp.float32).min

    def plane_block(plane: jax.Array, plane_scale: Optional[jax.Array], at: jax.Array) -> jax.Array:
        rows = lax.dynamic_slice_in_dim(plane, at, block, axis=1)
        if plane_scale is None:
            return rows.astype(q.dtype)
        return (rows.astype(jnp.float32) * lax.dynamic_slice_in_dim(plane_scale, at, block, axis=1)).astype(q.dtype)

    def step(i, carry):
        top, total, acc = carry
        start = i * block
        at = jnp.minimum(start, size - block)
        k = plane_block(keys, k_scale, at)
        v = k[..., :value_width] if values is None else plane_block(values, v_scale, at)
        slots = at + jnp.arange(block)
        visible = (cache_visible(slots, positions, window) & (slots >= start))[:, :, None]  # [B, 1, 1, L, block]
        scores = jnp.einsum("bkgld,bskd->bkgls", q, k, preferred_element_type=jnp.float32) * scale
        scores = jnp.where(visible, scores, low)
        new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
        weights = jnp.where(visible, jnp.exp(scores - new_top), 0.0)
        decay = jnp.exp(top - new_top)
        total = decay * total + weights.sum(axis=-1, keepdims=True)
        acc = decay * acc + jnp.einsum("bkgls,bskd->bkgld", weights.astype(q.dtype), v, preferred_element_type=jnp.float32)
        return new_top, total, acc

    width = value_width if values is None else values.shape[-1]
    stats = (batch, n_kv, group, length, 1)
    init = (jnp.full(stats, low), jnp.zeros(stats, jnp.float32), jnp.zeros((*stats[:-1], width), jnp.float32))
    _, total, acc = lax.fori_loop(lo, hi, step, init)
    out = acc / jnp.where(total > 0, total, 1.0)  # a query that saw no key kept a zero accumulator
    out = jnp.moveaxis(out, 3, 1).reshape(batch, length, n_kv * group, width)
    return out.astype(q.dtype), covered


@functools.partial(jax.jit, static_argnames=("causal", "impl"))
def multihead_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    impl: str = "auto",
) -> jax.Array:
    """Dispatching attention entry point used by the model library.

    ``impl``: ``"xla"`` (reference), ``"flash"`` (pallas kernel, TPU only), or
    ``"auto"``. Measured on v5e (B=4, L=1024, H=8, D=128, bf16) the hand-written
    flash kernel currently trails XLA's fused attention (2.6ms vs 1.6ms), so ``auto``
    resolves to XLA here; flash stays opt-in until the kernel wins its benchmark.
    This speaks for the uncached forward and the whole-row masked reads (one token
    over a contiguous or a gathered cache, several over a short row) alone: the
    single-token read of a PAGED cache never comes through this function on a TPU
    (:func:`unionml_tpu.ops.paged_attention.paged_read_path` sends it to the
    paged-attention kernel, which won that comparison), and a cached read of
    several tokens (a prefill chunk, a monolithic prefill, a verify) over a long row
    (:func:`walks_in_blocks`) goes through :func:`blocked_cached_attention`, which
    reads no further than its queries see.

    ``mask`` (boolean, broadcastable to ``[B, H, Lq, Lk]``, True = attend) routes to
    the XLA path — the flash kernel has no arbitrary-mask support.
    """
    if impl == "flash" and mask is None:
        from unionml_tpu.ops.flash_attention import flash_attention

        # grouped-query KV passes through unexpanded: the kernel's index maps
        # route query head h to KV head h * n_kv // n_heads
        return flash_attention(q, k, v, causal=causal)
    return dot_product_attention(q, k, v, causal=causal, mask=mask)
