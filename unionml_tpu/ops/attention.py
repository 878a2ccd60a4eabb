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
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

#: key positions one block of :func:`blocked_cached_attention` covers (chosen on a v5e at
#: ``glm47-flash.long_sat``'s shape: 256, 512 and 1,024 read 476.4, 475.9 and 467.3 tokens/s
#: there; PERF.md section 6, "PR 34")
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
    score: Callable[..., jax.Array],
    value: Callable[..., jax.Array],
    planes: Sequence[jax.Array],
    positions: jax.Array,
    live: jax.Array,
    *,
    heads: int,
    width: int,
    window: Optional[int] = None,
    dtype=jnp.bfloat16,
) -> Tuple[jax.Array, jax.Array]:
    """Several queries a row over a row cache, reading the keys they can see: the
    cache's ``planes`` (each ``[B, S, ...]``, position on axis 1) are walked in blocks
    of :data:`KEY_BLOCK` positions from the block that holds the first visible key to
    the one that holds the last live query, with the running softmax statistics (a
    float32 maximum, sum and accumulator) kept between blocks. Both bounds are traced
    values (``positions [B, L]`` absolute, ``live [B, L]`` the real tokens; one pair of
    bounds serves the batch), so the walk is ONE ``while`` with a dynamic trip count
    inside the caller's program, whatever the chunk's offset; nothing live, no trip.

    The products are the caller's, over one block's slice of every plane:
    ``score(*blocks) -> [B, heads, L, block]`` float32 (scaled), ``value(weights,
    *blocks) -> [B, heads, L, width]`` float32 with ``weights [B, heads, L, block]`` in
    ``dtype`` — an int8 row is dequantised there, a block at a time. Visibility is
    :func:`cache_visible` on the block's absolute slots; the last block of a row that is
    no multiple of the block is clamped to end on the row's last slot and masks the
    slots the block before it already counted (a row shorter than a block is one block).
    A query that sees no key yields zero, as :func:`dot_product_attention` does.

    Returns ``([B, L, heads, width]`` in ``dtype``, the key positions the walk covered a row``)``.
    """
    size = planes[0].shape[1]
    block = min(KEY_BLOCK, size)
    batch, length = positions.shape
    last = jnp.max(jnp.where(live, positions, -1))
    hi = jnp.minimum((last + block) // block, -(-size // block))  # blocks up to the last live query; 0 with none
    lo = 0
    if window is not None:
        first = jnp.min(jnp.where(live, positions, last))
        lo = jnp.minimum(jnp.maximum(first - window + 1, 0) // block, hi)
    low = jnp.finfo(jnp.float32).min

    def step(i, carry):
        top, total, acc = carry
        start = i * block
        at = jnp.minimum(start, size - block)
        blocks = [lax.dynamic_slice_in_dim(plane, at, block, axis=1) for plane in planes]
        slots = at + jnp.arange(block)
        visible = cache_visible(slots, positions, window) & (slots >= start)
        scores = jnp.where(visible, score(*blocks), low)
        new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
        weights = jnp.where(visible, jnp.exp(scores - new_top), 0.0)
        decay = jnp.exp(top - new_top)
        total = decay * total + weights.sum(axis=-1, keepdims=True)
        acc = decay * acc + value(weights.astype(dtype), *blocks)
        return new_top, total, acc

    init = (
        jnp.full((batch, heads, length, 1), low),
        jnp.zeros((batch, heads, length, 1), jnp.float32),
        jnp.zeros((batch, heads, length, width), jnp.float32),
    )
    _, total, acc = lax.fori_loop(lo, hi, step, init)
    out = acc / jnp.where(total > 0, total, 1.0)  # a query that saw no key kept a zero accumulator
    covered = jnp.maximum(jnp.minimum(hi * block, size) - lo * block, 0)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(dtype), covered.astype(jnp.int32)


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
