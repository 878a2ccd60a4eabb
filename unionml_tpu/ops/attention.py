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
from typing import Optional

import jax
import jax.numpy as jnp


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
    This speaks for the uncached forward and the masked reads alone: the
    single-token read of a PAGED cache never comes through this function on a TPU
    (:func:`unionml_tpu.ops.paged_attention.paged_read_path` sends it to the
    paged-attention kernel, which won that comparison).

    ``mask`` (boolean, broadcastable to ``[B, H, Lq, Lk]``, True = attend) routes to
    the XLA path — the flash kernel has no arbitrary-mask support.
    """
    if impl == "flash" and mask is None:
        from unionml_tpu.ops.flash_attention import flash_attention

        # grouped-query KV passes through unexpanded: the kernel's index maps
        # route query head h to KV head h * n_kv // n_heads
        return flash_attention(q, k, v, causal=causal)
    return dot_product_attention(q, k, v, causal=causal, mask=mask)
