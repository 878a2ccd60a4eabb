"""The cached read of several query tokens over a long row cache as one Pallas kernel.

A prefill chunk, a monolithic prefill or a verify over a row cache longer than
:data:`~unionml_tpu.ops.attention.ONE_TRIP_KEYS` reads it in key blocks with an
online softmax (:func:`~unionml_tpu.ops.attention.blocked_cached_attention`). Written
for XLA, that walk is one ``while`` a layer whose every trip writes a block's float32
scores, its weights and the float32 accumulator to HBM and reads them back: a matrix
product, a row reduction and a second product do not fuse. :func:`chunk_attention`
is the same read with the scores, the weights and the running statistics held in
VMEM for the whole walk of a query tile, so HBM sees the queries, the key and value
blocks the tile needs, and the output once.

The grid is rows x KV heads x query tiles x key blocks. A query tile folds the
group's query heads into its rows (``G x tq`` rows, the group outer). Each tile's
first and last key block arrive by scalar prefetch, from ``positions`` and ``live``:
the first is the block the window lets its first live query see, the last the block
of its last live query. Blocks outside that range are neither computed nor fetched
(the index map holds the block already resident), so a tile above the causal
diagonal costs nothing; a tile with no live query yields zeros. Blocks that every
live query of the tile sees whole skip the masks. A row that is no multiple of the
block ends in a partial block whose slots past the row are masked, and whose values
there are zeroed before they meet the weights (what lies past the array's end is not
defined on the chip).

:func:`chunk_read_path` decides, from what the trace observes, whether the kernel or
the XLA walk serves a read, and records it in the enclosing
:func:`~unionml_tpu.ops.paged_attention.paged_read_scope` (``stats()
["chunk_attention_path"]``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["CHUNK_KERNEL", "CHUNK_XLA", "chunk_attention", "chunk_read_path"]

#: the two ways a cached read of several tokens over a long row goes, as ``stats()["chunk_attention_path"]`` names them
CHUNK_KERNEL = "chunk_kernel"
CHUNK_XLA = "chunk_xla"
#: key positions a grid step of :func:`chunk_attention` reads where a key is one lane group (128) wide; half as many
#: where it is wider (:func:`_key_block`)
KEY_BLOCK = 1024
#: query rows (query tokens x the group's heads) a tile holds, at most (kernel alone on a v5e, PERF.md section 6:
#: 512 / 1,024 / 2,048 / 4,096 rows read SmallThinker's widest call in 7.8 / 6.7 / 6.4 / 6.2 ms, GLM's in 5.5 / 4.9 /
#: 4.6 / 4.5)
TILE_ROWS = 4096
_LOW = float(jnp.finfo(jnp.float32).min)
_LANES = 128


def chunk_read_path(keys: jax.Array, values: Optional[jax.Array]) -> str:
    """Which read serves several query tokens over a long row whose key plane is ``keys [B, S, H_kv, D_k]``
    (``values`` the value plane, or None where the values are the keys' first channels): the kernel on a TPU,
    over a bf16 row (an int8 row, dequantised a block at a time, stays with the walk) whose key and value widths
    are whole lanes (multiples of 128), held on one device (the enclosing
    :func:`~unionml_tpu.ops.paged_attention.paged_read_scope` says; a custom call cannot be partitioned over a
    mesh); the XLA walk everywhere else. The choice is recorded in that scope."""
    from unionml_tpu.ops.paged_attention import read_scope

    sharded, paths = read_scope()
    kernel = (
        jax.default_backend() == "tpu"
        and not sharded
        and keys.dtype == jnp.bfloat16
        and keys.shape[-1] % _LANES == 0
        and (values is None or (values.dtype == jnp.bfloat16 and values.shape[-1] % _LANES == 0))
    )
    path = CHUNK_KERNEL if kernel else CHUNK_XLA
    if paths is not None:
        paths.append(path)
    return path


def _key_block(d_k: int) -> int:
    """Key positions a grid step reads, from the key's width. Measured on a v5e, kernel alone (PERF.md section 6,
    the chunk kernel's sweep): SmallThinker's 4,096 queries of 7 x 4 heads of 128 over 16,392 positions took 13.2 / 6.7 / 7.0 ms at
    512 / 1,024 / 2,048 keys a block; GLM's 2,048 queries of 20 latent heads of 640 over 8,968 took 4.5 / 7.4 ms
    at 512 / 1,024 with :data:`TILE_ROWS` rows a tile."""
    return KEY_BLOCK if d_k <= _LANES else KEY_BLOCK // 2


def _tile(group: int, length: int) -> int:
    """Query tokens a tile holds: the largest power of two (16 at least, a bf16 sublane tile) whose ``group`` heads
    fit :data:`TILE_ROWS` rows, and no more than the read's tokens rounded up to 16."""
    tq = 16
    while 2 * tq * group <= TILE_ROWS:
        tq *= 2
    return min(tq, -(-length // 16) * 16)


def _chunk_block(
    lo_ref, hi_ref, full_lo_ref, full_hi_ref, pos_ref, q_ref, k_ref, *refs,
    scale, window, size, block, value_width, n_tiles,
):
    """One (row, KV head, query tile, key block) step of the online softmax; the statistics and the accumulator
    persist in VMEM scratch across the key blocks of a tile."""
    v_ref = refs[0] if len(refs) == 5 else None
    o_ref, top_ref, total_ref, acc_ref = refs[-4:]
    tile = pl.program_id(0) * n_tiles + pl.program_id(2)
    kb = pl.program_id(3)
    group, tq, d_k = q_ref.shape

    @pl.when(kb == 0)
    def _init():
        top_ref[...] = jnp.full(top_ref.shape, _LOW, jnp.float32)
        total_ref[...] = jnp.zeros(total_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(masked: bool):
        rows = q_ref[...].reshape(group * tq, d_k)
        keys = k_ref[...]
        values = keys[:, :value_width] if v_ref is None else v_ref[...]
        scores = jax.lax.dot_general(rows, keys, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if masked:
            slots = kb * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
            at = pos_ref[...]  # [tq, 1]
            visible = (slots <= at) & (slots < size)
            if window is not None:
                visible = visible & (slots > at - window)
            scores = jnp.where(visible[None], scores.reshape(group, tq, block), _LOW).reshape(group * tq, block)
            if size % block:  # past the row's end the block holds whatever the chip left there
                inside = kb * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) < size
                values = jnp.where(inside, values, jnp.zeros_like(values))
        top = top_ref[:, :1]
        new_top = jnp.maximum(top, scores.max(axis=-1, keepdims=True))
        weights = jnp.exp(scores - new_top)
        if masked:  # a row that has seen nothing keeps the type's minimum as its top: exp(0) is not its weight
            weights = jnp.where(visible[None], weights.reshape(group, tq, block), 0.0).reshape(group * tq, block)
        decay = jnp.exp(top - new_top)
        total = decay * total_ref[:, :1] + weights.sum(axis=-1, keepdims=True)
        acc_ref[...] = decay * acc_ref[...] + jax.lax.dot_general(
            weights.astype(values.dtype), values, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        top_ref[...] = jnp.broadcast_to(new_top, top_ref.shape)
        total_ref[...] = jnp.broadcast_to(total, total_ref.shape)

    walked = (kb >= lo_ref[tile]) & (kb < hi_ref[tile])
    whole = (kb >= full_lo_ref[tile]) & (kb < full_hi_ref[tile])

    @pl.when(walked & whole)
    def _whole():
        step(masked=False)

    @pl.when(walked & jnp.logical_not(whole))
    def _edge():
        step(masked=True)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finish():
        total = total_ref[:, :1]
        out = acc_ref[...] / jnp.where(total > 0, total, 1.0)  # a query that saw no key kept a zero accumulator
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def chunk_attention(
    q: jax.Array,
    keys: jax.Array,
    values: Optional[jax.Array],
    positions: jax.Array,
    live: jax.Array,
    *,
    scale: float,
    window: Optional[int] = None,
    value_width: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Grouped queries ``q [B, H_kv, G, L, D_k]`` over a row's key plane ``keys [B, S, H_kv, D_k]`` and value plane
    ``values [B, S, H_kv, D_v]`` (None: the values are the keys' first ``value_width`` channels), under
    :func:`~unionml_tpu.ops.attention.cache_visible` at ``positions [B, L]`` and ``window``, reading for each
    query tile the key blocks from the first its first live query (``live [B, L]``) may see to the block of its
    last live one. Scores are ``q . k * scale`` in float32 from ``q.dtype`` operands; the weights meet the values
    in ``q.dtype``; a query that sees no key yields zero. Returns ``[B, L, H_kv * G, D_v]`` in ``q.dtype``: the
    numbers of :func:`~unionml_tpu.ops.attention.blocked_cached_attention` to float32 rounding, for every live
    query (a query that is not live may differ: the walk's bounds follow live queries alone)."""
    batch, n_kv, group, length, d_k = q.shape
    size = keys.shape[1]
    d_v = value_width if values is None else values.shape[-1]
    block = min(_key_block(d_k), size)
    n_blocks = -(-size // block)
    tq = _tile(group, length)
    padded = -(-length // tq) * tq
    n_tiles = padded // tq

    if padded != length:  # a padded query is not live and sits before every slot: it reads nothing
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, padded - length), (0, 0)))
        positions = jnp.pad(positions, ((0, 0), (0, padded - length)), constant_values=-1)
        live = jnp.pad(live, ((0, 0), (0, padded - length)))
    at = positions.reshape(batch, n_tiles, tq)
    seen = live.reshape(batch, n_tiles, tq)
    last = jnp.max(jnp.where(seen, at, -1), axis=-1)
    first = jnp.min(jnp.where(seen, at, size), axis=-1)
    hi = jnp.minimum((last + block) // block, n_blocks)  # 0 for a tile with no live query
    full_hi = jnp.minimum((first + 1) // block, size // block)  # blocks every live query sees to their last slot
    if window is None:
        lo = full_lo = jnp.zeros_like(hi)
    else:
        lo = jnp.minimum(jnp.maximum(first - window + 1, 0) // block, hi)
        full_lo = -(-jnp.maximum(last - window + 1, 0) // block)
    bounds = [b.reshape(-1).astype(jnp.int32) for b in (lo, hi, full_lo, full_hi)]

    def kv_index(b, h, t, kb, lo, hi, *_):
        i = b * n_tiles + t
        return (b, h, jnp.minimum(jnp.maximum(kb, lo[i]), jnp.maximum(hi[i] - 1, 0)), 0)

    # heads-major planes, so that a block's last two axes are (positions, channels); a latent row's one head makes
    # this a bitcast, a row of several heads a copy a plane (Mosaic loads no bf16 row at a stride)
    planes = [jnp.swapaxes(keys, 1, 2)] + ([] if values is None else [jnp.swapaxes(values, 1, 2)])
    kernel = functools.partial(
        _chunk_block, scale=scale, window=window, size=size, block=block, value_width=d_v, n_tiles=n_tiles
    )
    heads = lambda b, h, t, kb, *_: (b, h, 0, t, 0)  # noqa: E731
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(batch, n_kv, n_tiles, n_blocks),
            in_specs=[
                pl.BlockSpec((None, tq, 1), lambda b, h, t, kb, *_: (b, t, 0)),
                pl.BlockSpec((None, None, group, tq, d_k), heads),
                *[pl.BlockSpec((None, None, block, plane.shape[-1]), kv_index) for plane in planes],
            ],
            out_specs=pl.BlockSpec((None, None, group, tq, d_v), heads),
            scratch_shapes=[
                pltpu.VMEM((group * tq, _LANES), jnp.float32),
                pltpu.VMEM((group * tq, _LANES), jnp.float32),
                pltpu.VMEM((group * tq, d_v), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, n_kv, group, padded, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"), vmem_limit_bytes=96 * 2**20
        ),
        name="chunk_attention",
        interpret=interpret,
    )(*bounds, positions[..., None].astype(jnp.int32), q, *planes)
    out = jnp.moveaxis(out[:, :, :, :length], 3, 1)  # [B, L, H_kv, G, D_v]
    return out.reshape(batch, length, n_kv * group, d_v)
