"""Ring attention: exact attention over a sequence-sharded context.

Long-context support the reference entirely lacks (SURVEY.md §2.3, §5.7). The sequence
dim is sharded over the ``sequence`` mesh axis; each device holds one Q/K/V block of
shape ``[B, L/s, H, D]``. K/V blocks rotate around the mesh-axis ring with
``lax.ppermute`` (neighbor ICI transfers) while each device accumulates its Q block's
attention with flash-style running softmax statistics — so memory stays O(L/s) per
device and the transfer of the next block overlaps the compute on the current one in
XLA's schedule.

``ring_attention`` is written to run *inside* ``shard_map`` (it needs the named axis);
``sequence_sharded_attention`` is the jit-level wrapper that binds it over a mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from unionml_tpu.parallel.collectives import all_to_all, axis_size, ring_permute


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = "sequence",
    causal: bool = False,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention for sequence-sharded q/k/v. Call inside ``shard_map``.

    :param q, k, v: local blocks ``[B, L_local, H, D]``, the sequence dim sharded over
        ``axis``. Supports grouped-query KV (``Hkv`` dividing ``H``).
    """
    ring_size = axis_size(axis)
    my_index = lax.axis_index(axis)
    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5

    n_heads, n_kv = q.shape[2], k.shape[2]
    if n_kv != n_heads:
        k = jnp.repeat(k, n_heads // n_kv, axis=2)
        v = jnp.repeat(v, n_heads // n_kv, axis=2)

    batch, q_len, _, head_dim = q.shape
    k_len = k.shape[1]
    q_pos = my_index * q_len + jnp.arange(q_len)  # global positions of the local Q rows

    qf = q.astype(jnp.float32) * scale

    m = jnp.full((batch, n_heads, q_len, 1), jnp.finfo(jnp.float32).min, dtype=jnp.float32)
    l = jnp.zeros((batch, n_heads, q_len, 1), dtype=jnp.float32)
    acc = jnp.zeros((batch, n_heads, q_len, head_dim), dtype=jnp.float32)

    def attend(step, m, l, acc, k_blk, v_blk):
        # which global block this device holds after ``step`` rotations
        src = (my_index - step) % ring_size
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_blk.astype(jnp.float32))
        if causal:
            k_pos = src * k_len + jnp.arange(k_len)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)

        m_curr = jnp.max(scores, axis=-1, keepdims=True)
        m_next = jnp.maximum(m, m_curr)
        alpha = jnp.exp(m - m_next)
        p = jnp.exp(scores - m_next)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
        return m_next, l, acc

    def body(step, carry):
        # rotate first, then accumulate: the loop runs steps 1..ring_size-1, so only
        # ring_size-1 ppermutes happen — no discarded final K/V transfer
        m, l, acc, k_blk, v_blk = carry
        k_blk, v_blk = ring_permute((k_blk, v_blk), axis)
        m, l, acc = attend(step, m, l, acc, k_blk, v_blk)
        return m, l, acc, k_blk, v_blk

    m, l, acc = attend(0, m, l, acc, k, v)
    m, l, acc, _, _ = lax.fori_loop(1, ring_size, body, (m, l, acc, k, v))
    denom = jnp.where(l == 0.0, 1.0, l)
    out = (acc / denom).astype(q.dtype)  # [B, H, Lq, D]
    return out.transpose(0, 2, 1, 3)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = "sequence",
    causal: bool = False,
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all instead of a ring.

    Two resharding all-to-alls per call: ``[B, L/s, H, D] -> [B, L, H/s, D]``
    (each device gets the FULL sequence for a head subset, dense attention runs
    locally with no per-step communication), then back. Cheaper in collective
    volume than ring attention when heads divide evenly over the axis and the
    full-sequence scores fit in HBM; ring attention remains the O(L/s)-memory
    option for extreme context lengths. Call inside ``shard_map``.
    """
    size = axis_size(axis)
    n_heads, n_kv = q.shape[2], k.shape[2]
    if n_kv != n_heads:  # GQA: expand KV so the head dim reshards evenly
        k = jnp.repeat(k, n_heads // n_kv, axis=2)
        v = jnp.repeat(v, n_heads // n_kv, axis=2)
    if n_heads % size:
        raise ValueError(f"ulysses needs heads ({n_heads}) divisible by axis size ({size})")

    # [B, L/s, H, D] -> [B, L, H/s, D]: head-sharded, sequence-complete
    q_full, k_full, v_full = (all_to_all(t, axis, split_axis=2, concat_axis=1) for t in (q, k, v))

    scale = softmax_scale if softmax_scale is not None else q.shape[-1] ** -0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_full.astype(jnp.float32) * scale, k_full.astype(jnp.float32))
    if causal:
        l_full = q_full.shape[1]
        mask = jnp.arange(l_full)[:, None] >= jnp.arange(l_full)[None, :]
        scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_full.astype(jnp.float32)).astype(q.dtype)
    # [B, L, H/s, D] -> [B, L/s, H, D]
    return all_to_all(out, axis, split_axis=1, concat_axis=2)


def sequence_sharded_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = False,
    batch_axes=("data", "fsdp"),
    sequence_axis: str = "sequence",
    impl: str = "ring",
) -> jax.Array:
    """Jit-level sequence-parallel attention: shards sequence over ``sequence_axis``,
    batch over ``batch_axes``, runs :func:`ring_attention` (``impl="ring"``) or
    :func:`ulysses_attention` (``impl="ulysses"``) under ``shard_map``."""
    present_batch = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    spec = P(present_batch, sequence_axis, None, None)

    sp_attention = {"ring": ring_attention, "ulysses": ulysses_attention}[impl]
    fn = functools.partial(sp_attention, axis=sequence_axis, causal=causal)
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False)
    return wrapped(q, k, v)
