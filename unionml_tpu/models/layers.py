"""Shared transformer building blocks (flax), written mesh-first.

No analog in the reference (it never looks inside a model, SURVEY.md §0); this is the
model library backing the BASELINE.json configs. Conventions:

- activations ``[batch, length, heads, head_dim]`` so sequence-parallel specs are
  rank-stable (:mod:`unionml_tpu.ops.ring_attention`);
- ``dtype`` (compute, default bf16 — the MXU native format) is separate from
  ``param_dtype`` (storage, default f32);
- parameter names are chosen so the PartitionRules regexes in
  :func:`unionml_tpu.models.llama.llama_partition_rules` etc. resolve TP layouts
  without per-model spec tables.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import functools

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from unionml_tpu.ops.attention import blocked_cached_attention, cache_visible, multihead_attention, walks_in_blocks

Dtype = Any

#: One layer's KV cache: ``{"k": [B, S_max, H_kv, D], "v": [B, S_max, H_kv, D]}``.
LayerCache = Dict[str, jax.Array]

#: what a sliding layer's kernel read counts a decode step (``counters`` collection; summed over the live rows)
WINDOW_COUNTERS = ("window_positions_live", "window_positions_read")


class SlotPlane(NamedTuple):
    """A plane of a layer's state that has no position axis: one row a sequence, ``[batch, *shape]``
    in a row cache and ``[slots, *shape]`` in an engine's pool (a recurrent state, a convolution's
    tail), in its own ``dtype``. A configuration's ``cache_layout`` states it beside the planes paged by
    position (``(heads, width)``). ``model_axis``: the axis of ``shape`` that follows the heads, which a
    mesh's ``model`` axis shards (``None``: replicate)."""

    shape: Tuple[int, ...]
    dtype: Any
    model_axis: Optional[int] = None


def _write_cache(buffer: jax.Array, new: jax.Array, starts: jax.Array) -> jax.Array:
    """Write ``new: [B, L, H, D]`` into ``buffer: [B, S_max, H, D]`` at per-example
    row offsets ``starts: [B]`` (each example's sequence is contiguous in its own
    cache rows, so variable-length prompts need no left-padding)."""
    return jax.vmap(lambda buf, upd, s: lax.dynamic_update_slice(buf, upd, (s, 0, 0)))(
        buffer, new.astype(buffer.dtype), starts
    )


def quantize_kv_rows(x: jax.Array):
    """Symmetric per-(position, head) int8 for K/V rows: ``(int8 values, f32
    scales [..., 1])``. Shared by the int8-KV cached-attention write path and the
    sequence-parallel prefill's cache assembly."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(scale, 1e-8) / 127.0
    rows = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return rows.astype(jnp.int8), scale


def _paged_scatter(pool: jax.Array, rows: jax.Array, blk: jax.Array, off: jax.Array) -> jax.Array:
    """Write ``rows [B, L, H, last]`` into the heads-major ``pool [H, n_pages, page, last]`` at
    each token's ``(blk, off) [B, L]``: ``pool[:, blk, off]`` has shape ``[H, B, L, last]``.
    Who still calls this: the paged write inside a model step where the kernel's row-major write
    does not apply (int8 pages, ``L > 1`` tokens, the gathered read). No admission does: the engine
    pastes a row as whole pages (``ContinuousBatcher._write_pages``), which leaves the pools as they lie."""
    return pool.at[:, blk, off].set(jnp.moveaxis(rows, 2, 0).astype(pool.dtype))


def _paged_scatter_rows(pool: jax.Array, rows: jax.Array, blk: jax.Array, off: jax.Array) -> jax.Array:
    """The kernel path's write (``L == 1``). The kernel reads the pools row-major; the
    scatter of ``[H, last]`` slabs above makes XLA keep them heads-minor and copy
    every pool, every layer, every step. As ``H * B`` rows of ``last`` it leaves them be."""
    n_kv, n_pages, block_size = pool.shape[:3]
    at = (jnp.arange(n_kv)[:, None] * n_pages + blk[:, 0]) * block_size + off[:, 0]  # [H, B]
    flat = pool.reshape(-1, pool.shape[-1]).at[at.reshape(-1)].set(
        jnp.moveaxis(rows[:, 0], 1, 0).reshape(-1, rows.shape[-1]).astype(pool.dtype)
    )
    return flat.reshape(pool.shape)


def _paged_logical(pool: jax.Array, table: jax.Array) -> jax.Array:
    """Every row's whole block table gathered back to the logical ``[B, MB * page, H, last]``."""
    rows = pool[:, table]  # [H, B, MB, page, last]
    rows = rows.reshape(rows.shape[0], rows.shape[1], -1, rows.shape[-1])
    return jnp.transpose(rows, (1, 2, 0, 3))


class RMSNorm(nn.Module):
    """Root-mean-square layer norm (pre-norm default for decoder stacks)."""

    epsilon: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        norm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.epsilon)
        return (norm * scale).astype(self.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _embed_lookup(embedding: jax.Array, tokens: jax.Array, num_embeddings: int) -> jax.Array:
    return jnp.take(embedding, tokens, axis=0)


def _embed_lookup_fwd(embedding, tokens, num_embeddings):
    return jnp.take(embedding, tokens, axis=0), tokens


def _embed_lookup_bwd(num_embeddings, res, g):
    tokens = res  # g.dtype == the lookup's (and so the table operand's) dtype
    # dW as a one-hot matmul instead of take's scatter-add: with the table
    # vocab/dim-sharded the scatter cannot be partitioned and XLA falls back to
    # involuntary full rematerialization; the dot reduce-scatters cleanly, the
    # one-hot iota fuses into its tiles ([tokens, vocab] never materializes),
    # and a frozen table's dW (LoRA) is still dead-code-eliminated
    one_hot = jax.nn.one_hot(tokens, num_embeddings, dtype=g.dtype)
    axes = tuple(range(g.ndim - 1))
    dw = jax.lax.dot_general(
        one_hot, g, (((axes), (axes)), ((), ())), preferred_element_type=jnp.float32
    )
    return (dw.astype(g.dtype), None)


_embed_lookup.defvjp(_embed_lookup_fwd, _embed_lookup_bwd)


class IotaEmbed(nn.Module):
    """``nn.Embed`` with an SPMD-clean backward: gather forward, one-hot
    matmul backward (the train-side half of maxtext's ``use_iota_embed``).

    ``nn.Embed`` lowers to gather forward / scatter-add backward; with the
    table vocab/dim-sharded (Megatron vocab-parallel, the llama/moe partition
    rules) the SPMD partitioner cannot reshard the batch-sharded update into
    the table layout and falls back to "involuntary full rematerialization" —
    a per-step (per-microbatch, under grad accumulation) all-gather of the
    residual gradient. The backward here is a dot against a one-hot iota
    (same shapes as the lm_head matmul), which reduce-scatters cleanly.

    The FORWARD stays a gather on purpose: a full one-hot matmul would stream
    the whole table per call, which is irrelevant in training but ruinous in
    decode (a [B, 1] lookup reads rows, not gigabytes). Param path, shape,
    init, and looked-up values are identical to ``nn.Embed``, so partition
    rules and checkpoints are unaffected.
    """

    num_embeddings: int
    features: int
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        embedding = self.param(
            "embedding",
            nn.initializers.variance_scaling(1.0, "fan_in", "normal", out_axis=0),
            (self.num_embeddings, self.features),
            self.param_dtype,
        )
        return _embed_lookup(embedding.astype(self.dtype), tokens, self.num_embeddings)


def rotary_embedding(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Apply RoPE to ``x: [B, L, H, D]`` at integer ``positions: [L]`` (or ``[B, L]``)."""
    head_dim = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., L, D/2]
    while angles.ndim < x.ndim:  # broadcast over batch/head dims
        angles = angles[None] if angles.ndim == 2 else angles[:, :, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rotated = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.reshape(x.shape).astype(x.dtype)


class LoRADense(nn.Module):
    """Dense layer with an optional low-rank adapter: ``y = xW + (xA)B * (alpha/r)``.

    With ``rank == 0`` this is a plain Dense. The adapter params live under
    ``lora_a``/``lora_b`` so :func:`unionml_tpu.models.llama.lora_param_labels` can
    mask the base weights out of the optimizer for LoRA fine-tuning.
    """

    features: int
    rank: int = 0
    alpha: float = 16.0
    use_bias: bool = False
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        in_features = x.shape[-1]
        kernel = self.param("kernel", self.kernel_init, (in_features, self.features), self.param_dtype)
        y = jnp.dot(x, kernel.astype(self.dtype))
        if self.rank > 0:
            a = self.param("lora_a", nn.initializers.normal(0.02), (in_features, self.rank), self.param_dtype)
            b = self.param("lora_b", nn.initializers.zeros, (self.rank, self.features), self.param_dtype)
            y = y + jnp.dot(jnp.dot(x, a.astype(self.dtype)), b.astype(self.dtype)) * (self.alpha / self.rank)
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (self.features,), self.param_dtype)
            y = y + bias.astype(self.dtype)
        return y


class Attention(nn.Module):
    """Multi-head (optionally grouped-query) attention with RoPE and impl dispatch.

    ``impl``: ``"auto"``, ``"xla"``, ``"flash"``, ``"ring"`` (sequence-parallel exact
    attention; requires running inside shard_map with a ``sequence`` axis), or
    ``"ulysses"`` (all-to-all sequence parallelism — same shard_map requirement,
    cheaper collectives when heads divide the axis). ``"auto"`` is XLA for the
    uncached forward (the hand-written flash kernel stays opt-in until it beats XLA's
    fused attention; see :func:`unionml_tpu.ops.attention.multihead_attention`) and,
    for a single-token read of a paged cache on a TPU, the pallas paged-attention
    kernel (:func:`unionml_tpu.ops.paged_attention.paged_read_path`).

    ``window``: sliding-window attention — key ``j`` is visible to the query at
    position ``i`` iff ``i - window < j <= i`` (on top of causality). Every read
    masks it; the kernel read of a paged cache starts at the window's first page
    instead (:func:`unionml_tpu.ops.paged_attention.paged_window_decode_attention`),
    and counts, over the rows live in ``token_mask``, the pages it never touched
    (``counters/decode_window_pages_skipped``), the rows' lengths
    (``window_positions_live``) and the positions of the pages it touched
    (``window_positions_read``: :data:`WINDOW_COUNTERS`). ``qk_norm``: an RMS norm with a learned
    scale over each query and key head's channels, before the rotary embedding.
    ``gated``: the heads' output is multiplied by ``sigmoid(gate_proj(x))``
    before ``o_proj``. All three default off, and add no parameter when off.
    """

    n_heads: int
    n_kv_heads: Optional[int] = None
    head_dim: Optional[int] = None
    causal: bool = False
    rope: bool = False
    rope_theta: float = 10000.0
    impl: str = "auto"
    lora_rank: int = 0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    window: Optional[int] = None
    qk_norm: bool = False
    gated: bool = False
    norm_epsilon: float = 1e-6

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
        cache: Optional[LayerCache] = None,
        token_mask: Optional[jax.Array] = None,
    ) -> Any:
        features = x.shape[-1]
        n_kv = self.n_kv_heads or self.n_heads
        head_dim = self.head_dim or features // self.n_heads
        dense = lambda feats, name: LoRADense(  # noqa: E731
            feats, rank=self.lora_rank, dtype=self.dtype, param_dtype=self.param_dtype, name=name
        )

        q = dense(self.n_heads * head_dim, "q_proj")(x)
        k = dense(n_kv * head_dim, "k_proj")(x)
        v = dense(n_kv * head_dim, "v_proj")(x)

        batch, length = x.shape[0], x.shape[1]
        q = q.reshape(batch, length, self.n_heads, head_dim)
        k = k.reshape(batch, length, n_kv, head_dim)
        v = v.reshape(batch, length, n_kv, head_dim)

        if self.qk_norm:
            q = RMSNorm(epsilon=self.norm_epsilon, dtype=self.dtype, name="q_norm")(q)
            k = RMSNorm(epsilon=self.norm_epsilon, dtype=self.dtype, name="k_norm")(k)

        def project(out: jax.Array) -> jax.Array:
            out = out.reshape(batch, length, self.n_heads * head_dim)
            if self.gated:
                out = out * jax.nn.sigmoid(dense(self.n_heads * head_dim, "gate_proj")(x))
            return dense(features, "o_proj")(out)

        if self.rope:
            if positions is None:
                positions = jnp.arange(length)
            q = rotary_embedding(q, positions, self.rope_theta)
            k = rotary_embedding(k, positions, self.rope_theta)

        if cache is not None:
            # Incremental decoding: the new rows' K/V land in the cache at each
            # example's next free slots (= the absolute positions), and attention
            # runs over the static-shape buffer under an explicit visibility
            # mask — key slot j is visible to the query at absolute position p
            # iff j <= p, which is causal over everything written so far and
            # hides slots not yet (re)written. One token reads the whole buffer
            # under it; several walk it in key blocks up to the last live query
            # (_cached_read). Static shapes throughout: the decode step compiles
            # exactly once per (batch, cache_len), the chunk once whatever its offset.
            if positions is None or positions.ndim != 2:
                raise ValueError("cached attention requires per-example positions [B, L]")
            if mask is not None:
                raise NotImplementedError("cached attention builds its own mask")
            if "table" in cache:
                # Paged KV (vLLM-style, static-shape): K/V live in a SHARED pool
                # of fixed-size blocks ([n_blocks, block_size, H_kv, D]) and each
                # batch row owns a block-table row mapping its logical positions
                # to pool blocks — HBM scales with the pool, not with
                # batch x worst-case length. Writes scatter through the table
                # (position p -> block table[b, p // bs], offset p % bs); reads
                # gather pool[table] back into the logical [B, MB * bs] layout,
                # so the visibility mask — and therefore the numerics — are
                # IDENTICAL to the contiguous branch below. Table rows of
                # finished/free slots are repointed to a scratch block by the
                # engine that owns the pool (see serving/continuous.py), which
                # is what makes their ride-along writes harmless.
                out, cache = self._paged_cached_attention(q, k, v, positions, cache, token_mask)
                return project(out), cache
            starts = positions[:, 0]
            if "k_scale" in cache:
                # int8 KV cache: symmetric per-(position, head) quantization on
                # write; dequant on read fuses into the attention contraction.
                # Long-context decode streams the cache every step — int8 halves
                # those bytes (scales are D/4x smaller than the values).
                kq, k_scale = quantize_kv_rows(k)
                vq, v_scale = quantize_kv_rows(v)
                cache = {
                    "k": _write_cache(cache["k"], kq, starts),
                    "v": _write_cache(cache["v"], vq, starts),
                    "k_scale": _write_cache(cache["k_scale"], k_scale, starts),
                    "v_scale": _write_cache(cache["v_scale"], v_scale, starts),
                }
            else:
                cache = {
                    "k": _write_cache(cache["k"], k, starts),
                    "v": _write_cache(cache["v"], v, starts),
                }
            return project(self._cached_read(q, cache, positions, token_mask)), cache

        # uncached forward: expose post-RoPE K/V for cache assembly (materialized
        # only when the caller passes mutable=["kvs"], e.g. the sequence-parallel
        # prefill; a plain apply pays nothing)
        self.sow("kvs", "k", k)
        self.sow("kvs", "v", v)

        if self.window is not None:
            at = jnp.arange(length) if positions is None else positions
            band = at[..., :, None] - at[..., None, :] < self.window  # [L, L] or [B, L, L]
            band = band[None, None] if band.ndim == 2 else band[:, None]
            mask = band if mask is None else jnp.logical_and(mask, band)

        if self.impl in ("ring", "ulysses"):
            if mask is not None:
                raise NotImplementedError("sequence-parallel attention does not support arbitrary masks")
            from unionml_tpu.ops.ring_attention import ring_attention, ulysses_attention

            sp_attention = ring_attention if self.impl == "ring" else ulysses_attention
            out = sp_attention(q, k, v, causal=self.causal)
        else:
            out = multihead_attention(q, k, v, causal=self.causal, mask=mask, impl=self.impl)

        return project(out)

    def _cached_read(self, q, rows, positions, token_mask):
        """Attend ``q [B, L, H, D]`` over a row cache's logical planes (``rows["k"]``,
        ``rows["v"]``: ``[B, S, H_kv, D]``, with their ``_scale`` planes when int8) under
        :func:`~unionml_tpu.ops.attention.cache_visible` and the layer's window. One
        token, and any read of a short row, attends the whole row under the mask;
        several tokens over a long one (a prefill chunk, a monolithic prefill, a
        verify: :func:`~unionml_tpu.ops.attention.walks_in_blocks`) walk it in key
        blocks from the window's first visible slot to the last live query's
        (:func:`~unionml_tpu.ops.attention.blocked_cached_attention`: on a TPU one Pallas
        kernel over a bf16 row, else an XLA ``while`` that dequantises an int8 row a block
        at a time). Several tokens count ``kv_positions_attended`` (key
        positions the read covered, a row with a live token) and ``kv_positions_needed``
        (of those, what the visibility rule lets such a row's live queries see)."""

        def dequant(plane: jax.Array, scale: Optional[jax.Array]) -> jax.Array:
            return plane.astype(q.dtype) if scale is None else (plane.astype(jnp.float32) * scale).astype(q.dtype)

        batch, length, heads, head_dim = q.shape
        size, n_kv = rows["k"].shape[1:3]
        live = jnp.ones((batch, length), bool) if token_mask is None else token_mask
        if walks_in_blocks(length, size):
            # query heads grouped by the KV head they read (h = kv * group + g, as ``jnp.repeat`` lays them)
            grouped = jnp.transpose(q.reshape(batch, length, n_kv, heads // n_kv, head_dim), (0, 2, 3, 1, 4))
            scales = (rows["k_scale"], rows["v_scale"]) if "k_scale" in rows else None
            out, covered = blocked_cached_attention(
                grouped, rows["k"], rows["v"], positions, live, scale=head_dim**-0.5, window=self.window, scales=scales
            )
        else:
            keys, values = dequant(rows["k"], rows.get("k_scale")), dequant(rows["v"], rows.get("v_scale"))
            visible = cache_visible(jnp.arange(size), positions, self.window)  # [B, 1, L, S]
            out, covered = multihead_attention(q, keys, values, causal=False, mask=visible, impl="xla"), size
        if length > 1:
            needed = jnp.max(jnp.where(live, positions + 1, 0), axis=1)  # [B]: a row's last live query's, 0 with none
            if self.window is not None:  # a row's first live query sees no further back than its window
                first = jnp.min(jnp.where(live, positions, jnp.iinfo(jnp.int32).max), axis=1)
                needed = jnp.where(live.any(axis=1), needed - jnp.maximum(first - self.window + 1, 0), 0)
            self.sow("counters", "kv_positions_attended", jnp.sum(live.any(axis=1), dtype=jnp.int32) * covered)
            self.sow("counters", "kv_positions_needed", jnp.sum(needed, dtype=jnp.int32))
        return out

    def _paged_cached_attention(self, q, k, v, positions, cache, token_mask=None):
        """The paged write+read: scatter new rows through the block table, then
        attend. Which read serves it is decided by
        :func:`unionml_tpu.ops.paged_attention.paged_read_path` from what the
        trace can observe: on a TPU a single-token read over bf16 pages held on
        one device goes through the pallas paged-attention kernel (each row's
        named pages stream block by block, up to the row's length, at KV-head
        width; no gathered copy); a CPU or GPU backend, ``L > 1``, int8 pages
        and pools sharded over a mesh take the portable gather (``pool[:,
        table]`` back to the logical layout, then :meth:`_cached_read`, the
        contiguous branch's own read under the same ``slot <= position``
        visibility: one token over the whole gathered row, several in key
        blocks — numerically identical to that branch by construction).
        ``impl="xla"`` forces the gather, ``impl="flash"`` the kernel.
        Pools are heads-major ``[H_kv, n_pages, page_size, last]``. Scatter
        indices collide only on the scratch block (finished rows), where the
        winning value is irrelevant — real slots own disjoint blocks."""
        from unionml_tpu.ops.paged_attention import (
            PAGED_KERNEL,
            paged_decode_attention,
            paged_read_path,
            paged_window_decode_attention,
            window_split,
        )

        table = cache["table"]  # [B, max_blocks] int32
        block_size = cache["k"].shape[2]
        blk = jnp.take_along_axis(table, positions // block_size, axis=1)  # [B, L]
        off = positions % block_size

        scatter = functools.partial(_paged_scatter, blk=blk, off=off)
        scatter_rows = functools.partial(_paged_scatter_rows, blk=blk, off=off)
        logical = functools.partial(_paged_logical, table=table)

        path = paged_read_path(self.impl, q, cache["k"], quantized="k_scale" in cache)
        if "k_scale" in cache:
            kq, k_scale = quantize_kv_rows(k)
            vq, v_scale = quantize_kv_rows(v)
            cache = {
                "k": scatter(cache["k"], kq),
                "v": scatter(cache["v"], vq),
                "k_scale": scatter(cache["k_scale"], k_scale),
                "v_scale": scatter(cache["v_scale"], v_scale),
                "table": table,
            }
        else:
            if path == PAGED_KERNEL:
                cache = {"k": scatter_rows(cache["k"], k), "v": scatter_rows(cache["v"], v), "table": table}
                # the row's visible length includes the token just scattered
                lengths = positions[:, 0] + 1
                if self.window is None or self.window >= table.shape[1] * block_size:  # no row can outgrow it
                    out = paged_decode_attention(q[:, 0], cache["k"], cache["v"], lengths, table)
                else:
                    with jax.named_scope("afmoe.attn_window"):
                        out = paged_window_decode_attention(
                            q[:, 0], cache["k"], cache["v"], lengths, table, window=self.window
                        )
                    skipped = window_split(lengths, self.window, block_size)[0]
                    touched = (-(-lengths // block_size) - skipped) * block_size  # the edge page and the whole ones after it
                    if token_mask is not None:
                        skipped, touched, lengths = (jnp.where(token_mask[:, 0], a, 0) for a in (skipped, touched, lengths))
                    self.sow("counters", "decode_window_pages_skipped", jnp.sum(skipped, dtype=jnp.int32))
                    self.sow("counters", "window_positions_live", jnp.sum(lengths, dtype=jnp.int32))
                    self.sow("counters", "window_positions_read", jnp.sum(touched, dtype=jnp.int32))
                return out[:, None], cache
            cache = {"k": scatter(cache["k"], k), "v": scatter(cache["v"], v), "table": table}
        rows = {name: logical(pool) for name, pool in cache.items() if name != "table"}  # [B, MB * bs, H_kv, last]
        return self._cached_read(q, rows, positions, token_mask), cache

def _masked_softmax(scores: jax.Array, visible: jax.Array, dtype: Dtype) -> jax.Array:
    """Softmax over the last axis in float32 under ``visible`` (True = attend), as
    :func:`unionml_tpu.ops.attention.dot_product_attention` does it: masked scores
    take the type's minimum and a row that sees nothing is zero."""
    scores = jnp.where(visible, scores, jnp.finfo(scores.dtype).min)
    weights = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dtype)
    return jnp.where(visible.any(axis=-1, keepdims=True), weights, 0)


class _Kernel(nn.Module):
    """A bare ``kernel`` parameter under its own name (``experts/wg/kernel``,
    ``attn/kv_up/kernel``): the paths the partition rules and the int8 weight
    quantizer match on, for a matrix its layer reads other than as one product
    (stacked experts; :class:`LatentAttention`'s ``kv_up``, read in slices)."""

    shape: Any
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self) -> jax.Array:
        stacked = tuple(range(len(self.shape) - 2))  # an expert's fan-in is its own
        init = nn.initializers.variance_scaling(1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=stacked)
        return self.param("kernel", init, tuple(self.shape), self.param_dtype)


def latent_cache_width(kv_rank: int, rope_dim: int) -> int:
    """The stored width of a latent row: ``kv_rank + rope_dim`` rounded up to whole
    lanes (128), the tail zeros — the paged-attention kernel takes whole lanes only
    (576 -> 640: Mosaic refuses a 576-wide block, tests/emulated/test_chip_compile.py)."""
    return -(-(kv_rank + rope_dim) // 128) * 128


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA, the DeepSeek-V2/V3 block): queries and
    key-values pass through low-rank bottlenecks with their own RMS norms, and
    the cache holds neither keys nor values but one latent row a token,
    ``[c_kv (kv_rank) | k_rope (rope_dim)]`` — after the norm, after the rotary
    — shared by all heads. With ``a`` the normed stream::

        c_q = RMSNorm(q_down a);  q_h = q_up_h c_q = [q_nope_h (nope) | q_rope_h (rope)];  q_rope <- rotary
        [c | r] = kv_down a;      c_kv = RMSNorm(c);  k_rope = rotary(r), one head for all
        expanded:  [k_nope_h | v_h] = kv_up_h c_kv;  s_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) * scale
                   o_h = softmax(s_h) v_h
        absorbed:  qt_h = kv_up_h[:, :nope] q_nope_h  (kv_rank);  s_h = (qt_h . c_kv + q_rope_h . k_rope) * scale
                   o_h = kv_up_h[:, nope:]^T (softmax(s_h) c_kv)
        out = o_proj [o_1 .. o_H],   scale = (nope + rope) ** -0.5

    The two reads give the same numbers: the expanded one up-projects every key
    position (cheap per query-key pair, ``H * (nope + v)`` values a position to
    build), the absorbed one attends on the latent itself (``H`` query heads of
    ``kv_rank + rope`` on one shared "KV head"; nothing is built). The uncached
    forward is expanded; every cached read is absorbed. One token (decode) over a
    paged pool goes where :func:`~unionml_tpu.ops.paged_attention.paged_read_path`
    says — on a TPU the repo's latent read, which DMAs each live latent page once
    and reads no page for a row with no live token
    (:func:`~unionml_tpu.ops.paged_attention.paged_latent_decode_attention`), else
    the gather, whole under a mask. Several tokens (a prefill chunk over
    the row cache, a verify, contiguous or gathered) over a long row
    (:func:`~unionml_tpu.ops.attention.walks_in_blocks`) walk its latent in key
    blocks up to the last live query's position
    (:func:`~unionml_tpu.ops.attention.blocked_cached_attention`), over a short one
    they attend it whole under the mask. Measured on a v5e, a 256-token chunk over
    an 8,968-position row costs a layer 0.85 ms whole (1.17 expanded, PERF.md
    section 6, "PR 30": the expanded cached read was not kept) and, walked, 0.15 ms
    at offset 0, 0.32 at 2,304, 0.53 at 4,480 and 0.78 at 7,936 (same section, "PR 34").
    On a TPU the walk is the Pallas chunk kernel (:mod:`unionml_tpu.ops.chunk_attention`):
    one KV head of ``n_heads`` query heads, the stored row scored whole against the query
    padded to its width.

    The cache is one plane, ``{"k": [B, S, 1, width]}`` (paged: ``[1, n_pages,
    page, width]`` + ``table``) whose ``width`` is the cache's own (the model's
    ``cache_layout``; :func:`latent_cache_width`); channels past ``kv_rank +
    rope_dim`` are zeros.
    ``kv_up``'s two halves are slices taken inside the program; no second copy
    of it is held. Counts into the ``counters`` collection:
    ``latent_positions_read`` (one-token reads: the live rows' lengths, what the
    read had to cover), ``latent_positions_attended`` (several-token reads: key
    positions the read covered a row with a live token: the blocks walked, clipped to
    the row, or a short row whole) and ``latent_positions_needed`` (of those, the
    positions up to each live row's last query: what causality needs).

    ``q_rank=None``: no query bottleneck, one full-rank ``q_proj`` and no query norm.
    ``gated``: each head's output is multiplied by ``sigmoid(gate_proj(a))_h``, one
    scalar a head, before ``o_proj``. Both default to the block described above and
    add no parameter when off."""

    n_heads: int
    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float = 10000.0
    norm_epsilon: float = 1e-6
    impl: str = "auto"
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32
    gated: bool = False

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
        cache: Optional[LayerCache] = None,
        token_mask: Optional[jax.Array] = None,
    ) -> Any:
        features, heads = x.shape[-1], self.n_heads
        batch, length = x.shape[0], x.shape[1]
        dense = lambda feats, name: LoRADense(feats, dtype=self.dtype, param_dtype=self.param_dtype, name=name)  # noqa: E731
        norm = lambda name: RMSNorm(epsilon=self.norm_epsilon, dtype=self.dtype, name=name)  # noqa: E731
        if positions is None:
            positions = jnp.arange(length)

        with jax.named_scope("mla.q_path"):
            if self.q_rank is None:
                q = dense(heads * (self.nope_dim + self.rope_dim), "q_proj")(x)
            else:
                q = dense(heads * (self.nope_dim + self.rope_dim), "q_up")(norm("q_norm")(dense(self.q_rank, "q_down")(x)))
            q = q.reshape(batch, length, heads, self.nope_dim + self.rope_dim)
            q_nope = q[..., : self.nope_dim]
            q_rope = rotary_embedding(q[..., self.nope_dim :], positions, self.rope_theta)
        with jax.named_scope("mla.kv_path"):
            down = dense(self.kv_rank + self.rope_dim, "kv_down")(x)
            c_kv = norm("kv_norm")(down[..., : self.kv_rank])
            k_rope = rotary_embedding(down[..., None, self.kv_rank :], positions, self.rope_theta)  # [B, L, 1, rope]
            latent = jnp.concatenate([c_kv[:, :, None], k_rope], axis=-1)  # [B, L, 1, kv_rank + rope]
        kv_up = _Kernel(
            (self.kv_rank, heads * (self.nope_dim + self.v_dim)), self.param_dtype, name="kv_up"
        )().astype(self.dtype).reshape(self.kv_rank, heads, self.nope_dim + self.v_dim)
        scale = (self.nope_dim + self.rope_dim) ** -0.5

        def project(out: jax.Array) -> jax.Array:  # [B, L, H, v_dim]
            if self.gated:
                with jax.named_scope("mla.gate"):
                    gate = jax.nn.sigmoid(dense(heads, "gate_proj")(x).astype(jnp.float32))
                    out = out * gate[..., None].astype(out.dtype)
            return dense(features, "o_proj")(out.reshape(batch, length, heads * self.v_dim))

        def expanded(rows: jax.Array, visible: jax.Array) -> jax.Array:
            """Attend over latent ``rows [B, S, 1, >= kv_rank + rope]`` by up-projecting them."""
            with jax.named_scope("mla.expand"):
                kv = jnp.einsum("bsc,chd->bshd", rows[:, :, 0, : self.kv_rank].astype(self.dtype), kv_up)
                keys_rope = rows[:, :, 0, self.kv_rank : self.kv_rank + self.rope_dim].astype(self.dtype)
                scores = jnp.einsum("blhd,bshd->bhls", q_nope, kv[..., : self.nope_dim])
                scores = (scores + jnp.einsum("blhd,bsd->bhls", q_rope, keys_rope)) * scale
                return jnp.einsum("bhls,bshd->blhd", _masked_softmax(scores, visible, self.dtype), kv[..., self.nope_dim :])

        def absorb() -> jax.Array:
            """The query in the latent's own space, ``[B, L, H, kv_rank + rope]``."""
            with jax.named_scope("mla.absorb"):
                return jnp.concatenate([jnp.einsum("blhd,chd->blhc", q_nope, kv_up[..., : self.nope_dim]), q_rope], axis=-1)

        def unabsorb(out: jax.Array) -> jax.Array:  # [B, L, H, kv_rank] -> [B, L, H, v_dim]
            with jax.named_scope("mla.absorb"):
                return jnp.einsum("blhc,chd->blhd", out.astype(self.dtype), kv_up[..., self.nope_dim :])

        def absorbed(rows: jax.Array, visible: jax.Array) -> jax.Array:
            """Attend on latent ``rows`` themselves, whole under ``visible``; nothing is built a key position."""
            q_abs = absorb()
            with jax.named_scope("mla.absorb"):
                keys = rows[:, :, 0, : self.kv_rank + self.rope_dim].astype(self.dtype)
                weights = _masked_softmax(jnp.einsum("blhw,bsw->bhls", q_abs, keys) * scale, visible, self.dtype)
                out = jnp.einsum("bhls,bsc->blhc", weights, keys[..., : self.kv_rank])
            return unabsorb(out)

        if cache is None:
            self.sow("kvs", "k", latent)  # the post-norm, post-rotary latent, for a caller that assembles a cache
            at = jnp.broadcast_to(positions, (batch, length)) if positions.ndim == 1 else positions
            visible = at[:, None, :, None] >= at[:, None, None, :]  # causal
            if mask is not None:
                visible = jnp.logical_and(visible, mask)
            return project(expanded(latent, visible))

        if positions.ndim != 2:
            raise ValueError("cached attention requires per-example positions [B, L]")
        if mask is not None:
            raise NotImplementedError("cached attention builds its own mask")
        width = cache["k"].shape[-1]
        stored = jnp.pad(latent, ((0, 0), (0, 0), (0, 0), (0, width - latent.shape[-1])))  # whole lanes: the tail is zeros
        live = jnp.ones((batch, length), bool) if token_mask is None else token_mask
        lengths = positions[:, 0] + 1  # a one-token read sees the token just written
        if length == 1:
            self.sow("counters", "latent_positions_read", jnp.sum(jnp.where(live[:, 0], lengths, 0), dtype=jnp.int32))
        if "table" in cache:
            from unionml_tpu.ops.paged_attention import LATENT_KERNEL, paged_latent_decode_attention, paged_read_path

            table, block_size = cache["table"], cache["k"].shape[2]
            blk = jnp.take_along_axis(table, positions // block_size, axis=1)
            off = positions % block_size
            if paged_read_path(self.impl, q, cache["k"], quantized=False, latent=True) == LATENT_KERNEL:
                cache = {"k": _paged_scatter_rows(cache["k"], stored, blk, off), "table": table}
                q_abs = jnp.pad(absorb()[:, 0], ((0, 0), (0, 0), (0, width - self.kv_rank - self.rope_dim)))
                with jax.named_scope("mla.decode_read"):
                    # a row with no live token (a released slot) reads nothing
                    out = paged_latent_decode_attention(
                        q_abs, cache["k"], jnp.where(live[:, 0], lengths, 0), table, scale=scale, value_width=self.kv_rank
                    )
                return project(unabsorb(out[:, None])), cache
            cache = {"k": _paged_scatter(cache["k"], stored, blk, off), "table": table}
            rows = _paged_logical(cache["k"], table)
        else:
            cache = {"k": _write_cache(cache["k"], stored, positions[:, 0])}
            rows = cache["k"]
        if walks_in_blocks(length, rows.shape[1]):
            # one shared "KV head": the stored row scored whole against the query padded to its width (the tail
            # is zeros on both sides), valued on its first kv_rank channels
            q_abs = jnp.pad(absorb(), ((0, 0), (0, 0), (0, 0), (0, width - self.kv_rank - self.rope_dim)))
            with jax.named_scope("mla.absorb"):
                out, covered = blocked_cached_attention(
                    jnp.transpose(q_abs, (0, 2, 1, 3))[:, None], rows, None, positions, live, scale=scale,
                    value_width=self.kv_rank,
                )
            out = unabsorb(out)
        else:
            visible = cache_visible(jnp.arange(rows.shape[1]), positions)  # [B, 1, L, S]
            with jax.named_scope("mla.decode_read" if length == 1 else "mla.absorb"):
                out, covered = absorbed(rows, visible), rows.shape[1]
        if length > 1:
            self.sow("counters", "latent_positions_attended", jnp.sum(live.any(axis=1), dtype=jnp.int32) * covered)
            self.sow(
                "counters", "latent_positions_needed", jnp.sum(jnp.max(jnp.where(live, positions + 1, 0), axis=1), dtype=jnp.int32)
            )
        return project(out), cache


class KimiDeltaAttention(nn.Module):
    """Kimi Delta Attention (KDA, arXiv:2510.26692): linear attention whose state is one ``d_k x d_v``
    matrix a head, rewritten by the gated delta rule with a decay a channel
    (:mod:`unionml_tpu.ops.delta_rule`), behind short causal depthwise convolutions. Nothing it
    keeps grows with the sequence. With ``a`` the normed stream and per head unless said::

        q = l2norm(silu(conv(q_proj a))) * d_k ** -0.5;  k = l2norm(silu(conv(k_proj a)));  v = silu(conv(v_proj a))
        g = decay_bound * sigmoid(exp(A_log) * (f_proj a + dt_bias))     # log-decay a channel, in (decay_bound, 0)
        beta = sigmoid(b_proj a)                                         # one scalar a head
        S' = Diag(exp(g)) S;  S = S' + beta k (v - S'^T k)^T;  o = S^T q
        out = o_proj [ RMSNorm_{d_v}(o) * sigmoid(g_proj a) ]            # one learned norm scale of d_v for all heads

    ``conv`` sums the last ``conv_size`` positions under its own taps a channel (no bias);
    ``f_proj`` and ``g_proj`` are full-rank. The decay gate, ``beta`` and the state are float32.

    Three reads of one state. Uncached: the whole sequence from a zero state, in chunks
    (:func:`~unionml_tpu.ops.delta_rule.delta_rule_chunked`). Cached with several tokens (a prefill
    chunk): the same, from the state and the convolutions' tails the cache hands in, which come out
    advanced; positions ``token_mask`` masks leave both untouched (they must be a row's tail: a
    right-padded prompt, a row that is not live). Cached with one token (decode): the one-token update,
    as one Pallas kernel where :func:`~unionml_tpu.ops.delta_rule.state_step_path` says (a TPU, the state on
    one device), else :func:`~unionml_tpu.ops.delta_rule.delta_rule_step`; a masked row's state and tails are held.

    The cache is two planes with no position axis (:class:`SlotPlane`): ``{"S": [B, H, d_k, d_v]``
    in ``state_dtype``, ``"conv": [B, conv_size - 1, 3, H * d_k]}``, the last pre-convolution rows of
    ``q_proj a``, ``k_proj a``, ``v_proj a``; ``B`` is a row cache's batch or an engine's slots, the
    same to this module. Counts into the ``counters`` collection: ``state_rows_updated`` (one-token
    reads: live rows), ``state_positions_run`` (several-token reads: positions the chunk form ran
    for rows with a live token, padded or not) and ``state_positions_needed`` (of those, live)."""

    n_heads: int
    head_dim: int  # d_k = d_v
    conv_size: int = 4
    decay_bound: float = -5.0
    norm_epsilon: float = 1e-6
    state_dtype: Dtype = jnp.float32
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @staticmethod
    def cache_planes(n_heads: int, head_dim: int, conv_size: int, state_dtype: Dtype, dtype: Dtype) -> Dict[str, SlotPlane]:
        """This layer's state as a configuration's ``cache_layout`` states it."""
        return {
            "S": SlotPlane((n_heads, head_dim, head_dim), state_dtype, 0),
            "conv": SlotPlane((conv_size - 1, 3, n_heads * head_dim), dtype, 2),
        }

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
        cache: Optional[LayerCache] = None,
        token_mask: Optional[jax.Array] = None,
    ) -> Any:
        from unionml_tpu.ops.delta_rule import (
            MIN_LOG_DECAY,
            STATE_KERNEL,
            delta_rule_chunked,
            delta_rule_step,
            delta_rule_step_kernel,
            state_step_path,
        )

        if mask is not None:
            raise NotImplementedError("a delta-rule layer is causal by construction and takes no mask")
        if not MIN_LOG_DECAY <= self.decay_bound <= 0.0:
            raise ValueError(
                f"decay_bound {self.decay_bound} outside [{MIN_LOG_DECAY}, 0]: below it the chunk form's factorised "
                "decay products overflow float32 (ops/delta_rule.py), above it the state would grow"
            )
        features, heads, d = x.shape[-1], self.n_heads, self.head_dim
        batch, length, width, taps = x.shape[0], x.shape[1], self.n_heads * self.head_dim, self.conv_size
        dense = lambda feats, name: LoRADense(feats, dtype=self.dtype, param_dtype=self.param_dtype, name=name)  # noqa: E731
        in_f32 = lambda feats, name: jnp.dot(  # noqa: E731  a float32 result of the stream's own (compute dtype) operands
            x, _Kernel((features, feats), self.param_dtype, name=name)().astype(self.dtype), preferred_element_type=jnp.float32
        )
        live = jnp.ones((batch, length), bool) if token_mask is None else token_mask

        with jax.named_scope("kda.proj"):
            pre = jnp.stack([dense(width, name)(x) for name in ("q_proj", "k_proj", "v_proj")], axis=2)  # [B, L, 3, H * d]
        with jax.named_scope("kda.conv"):
            kernel = self.param("conv_taps", nn.initializers.normal(taps ** -0.5), (taps, 3, width), self.param_dtype)
            tail = jnp.zeros((batch, taps - 1, 3, width), self.dtype) if cache is None else cache["conv"].astype(self.dtype)
            window = jnp.concatenate([tail, pre], axis=1)  # [B, taps - 1 + L, 3, H * d]
            mixed = sum(window[:, j : j + length] * kernel[j].astype(self.dtype) for j in range(taps))
            mixed = jax.nn.silu(mixed.astype(jnp.float32)).reshape(batch, length, 3, heads, d)
            unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
            q, k, v = unit(mixed[:, :, 0]) * d ** -0.5, unit(mixed[:, :, 1]), mixed[:, :, 2]
        with jax.named_scope("kda.gate"):
            a_log = self.param("A_log", nn.initializers.zeros, (heads,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.constant(-4.0), (width,), jnp.float32)
            rate = (jnp.exp(a_log)[:, None] * (in_f32(width, "f_proj") + dt_bias).reshape(batch, length, heads, d))
            g = self.decay_bound * jax.nn.sigmoid(rate)
            beta = jax.nn.sigmoid(in_f32(heads, "b_proj"))

        if cache is not None and length == 1:
            with jax.named_scope("kda.step"):
                step = delta_rule_step_kernel if state_step_path(cache["S"]) == STATE_KERNEL else delta_rule_step
                out, state = step(
                    cache["S"], q[:, 0], k[:, 0], v[:, 0],
                    jnp.where(live[:, 0, None, None], g[:, 0], 0.0), jnp.where(live[:, 0, None], beta[:, 0], 0.0),
                )
                out = out[:, None]
                tail = jnp.where(live[:, 0, None, None, None], window[:, 1:], tail)
            self.sow("counters", "state_rows_updated", jnp.sum(live, dtype=jnp.int32))
        else:
            start = jnp.zeros((batch, heads, d, d), jnp.float32) if cache is None else cache["S"]
            with jax.named_scope("kda.chunk"):
                out, state = delta_rule_chunked(start, q, k, v, g, beta, live)
                if cache is not None:  # the last taps - 1 rows before the first masked position
                    rows = jnp.sum(live, axis=1)[:, None] + jnp.arange(taps - 1)[None]  # [B, taps - 1]
                    tail = jnp.take_along_axis(window, rows[:, :, None, None], axis=1)
            self.sow("counters", "state_positions_run", jnp.sum(live.any(axis=1), dtype=jnp.int32) * length)
            self.sow("counters", "state_positions_needed", jnp.sum(live, dtype=jnp.int32))

        with jax.named_scope("kda.out"):
            gate = jax.nn.sigmoid(dense(width, "g_proj")(x).astype(jnp.float32)).reshape(batch, length, heads, d)
            normed = RMSNorm(epsilon=self.norm_epsilon, dtype=jnp.float32, name="o_norm")(out)
            y = dense(features, "o_proj")((normed * gate).astype(self.dtype).reshape(batch, length, width))
        if cache is None:
            return y
        return y, {"S": state.astype(cache["S"].dtype), "conv": tail.astype(cache["conv"].dtype)}


class MLP(nn.Module):
    """Feed-forward block: gated SwiGLU (decoder default) or plain GELU (encoder)."""

    hidden_dim: int
    gated: bool = True
    lora_rank: int = 0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        features = x.shape[-1]
        dense = lambda feats, name: LoRADense(  # noqa: E731
            feats, rank=self.lora_rank, dtype=self.dtype, param_dtype=self.param_dtype, name=name
        )
        if self.gated:
            gate = jax.nn.silu(dense(self.hidden_dim, "wg")(x))
            up = dense(self.hidden_dim, "wi")(x)
            return dense(features, "wo")(gate * up)
        h = jax.nn.gelu(dense(self.hidden_dim, "wi")(x))
        return dense(features, "wo")(h)


class TransformerBlock(nn.Module):
    """Pre-norm transformer block, encoder (bidirectional+LN) or decoder (causal+RMS)."""

    n_heads: int
    hidden_dim: int
    n_kv_heads: Optional[int] = None
    decoder: bool = True
    rope: bool = False
    rope_theta: float = 10000.0
    attention_impl: str = "auto"
    lora_rank: int = 0
    dtype: Dtype = jnp.bfloat16
    param_dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        positions: Optional[jax.Array] = None,
        mask: Optional[jax.Array] = None,
        cache: Optional[LayerCache] = None,
    ) -> Any:
        norm = (
            (lambda name: RMSNorm(dtype=self.dtype, name=name))
            if self.decoder
            else (lambda name: nn.LayerNorm(dtype=self.dtype, name=name))
        )
        attn_out = Attention(
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            causal=self.decoder,
            rope=self.rope,
            rope_theta=self.rope_theta,
            impl=self.attention_impl,
            lora_rank=self.lora_rank,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="attn",
        )(norm("attn_norm")(x), positions, mask, cache)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + attn_out
        x = x + MLP(
            hidden_dim=self.hidden_dim,
            gated=self.decoder,
            lora_rank=self.lora_rank,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="mlp",
        )(norm("mlp_norm")(x))
        return (x, cache) if cache is not None else x
