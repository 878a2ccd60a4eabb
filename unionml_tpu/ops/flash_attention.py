"""Pallas TPU flash attention (forward kernel + recompute backward).

Blocked online-softmax attention: Q/K/V stream HBM->VMEM in (block_q x block_k)
tiles, the running max/denominator and the f32 output accumulator live in VMEM
scratch, and the [L, L] score matrix is never materialized in HBM. The TPU grid is
sequential over its innermost dimension, so scratch persists across the k-block loop
— the canonical pallas accumulation pattern (see /opt/skills/guides/pallas_guide.md,
"Patterns: Double Buffering" / grid accumulation).

Layout decisions (each mandated by the TPU memory system):

- the wrapper views ``[B, L, H, D]`` inputs heads-major (``[B, H, L, D]``) and
  the 4D grid ``(batch, heads, q_blocks, k_blocks)`` takes ``(block, D)`` tiles
  with batch and head squeezed: Mosaic tiles a block's last two dims (multiples
  of 8 and 128, or the whole axis), so a size-1 head block cannot sit
  second-to-last;
- grouped-query attention happens in the K/V index maps (query head ``h`` reads
  KV head ``h * n_kv // n_heads``) — repeated KV heads are never materialized;
- ``dimension_semantics`` marks batch/head/q-block dims parallel and the k-block
  dim arbitrary (sequential accumulation), letting Mosaic pipeline the grid;
- running-stats scratch is lane-replicated ``(block_q, 128)`` — a ``(block_q, 1)``
  buffer pads to a full lane register anyway and forces relayouts.

Backward: fused FlashAttention-2-style pallas kernels. The forward additionally
saves the per-row logsumexp (``[B, H, 1, Lq]``, ``(1, block_q)`` lane-major blocks); the backward
recomputes scores blockwise from it (``P = exp(S - lse)``), so the ``[L, L]``
matrix never exists in HBM in either direction — training memory stays
O(L * D + L), which is the whole point for long context. Two kernels:

- ``dq``: grid ``(b, h, q_blocks, k_blocks)``, accumulating over k blocks;
- ``dk/dv``: grid ``(b, h, k_blocks, q_blocks)``, accumulating over q blocks,
  computed at full query-head resolution and group-summed afterward for GQA
  (``jnp.repeat``'s transpose is a segment sum).

``delta = rowsum(dO * O)`` (the softmax-Jacobian correction) is one cheap
elementwise XLA reduction outside the kernels.

Shapes: ``q: [B, Lq, H, D]``, ``k/v: [B, Lk, Hkv, D]`` with ``H % Hkv == 0``,
``D % 128 == 0``, and lengths divisible by the block size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_LANES = 128  # TPU vector lane width: stats scratch is lane-replicated
_NEG_INF = float(jnp.finfo(jnp.float32).min)
_BIG = 1e30  # lse sentinel for fully-masked rows: exp(S - BIG) == 0


def _flash_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scratch, l_scratch, acc_scratch, *, causal, block_q, block_k, scale, offset
):
    # offset = k_len - q_len: with unequal lengths, query row i may attend keys up to
    # i + offset (matching dot_product_attention's shifted diagonal)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    def _compute():
        # matmuls run in the INPUT dtype with f32 accumulation: a bf16 QK^T /
        # PV hits the MXU's native rate, while an up-front f32 cast would halve
        # it — the whole reason the hand kernel can beat XLA's fused attention.
        # Scale is applied to the f32 scores, not the bf16 operands.
        q = q_ref[...]  # [block_q, D]
        k = k_ref[...]  # [block_k, D]
        v = v_ref[...]  # [block_k, D]
        scores = (
            jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            * scale
        )  # [block_q, block_k] f32

        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(q_pos + offset >= k_pos, scores, _NEG_INF)

        m_prev = m_scratch[:, :1]  # [block_q, 1] view of the lane-replicated stats
        l_prev = l_scratch[:, :1]
        m_curr = jnp.max(scores, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_curr)
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(scores - m_next)

        l_next = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scratch[:] = acc_scratch[:] * alpha + pv
        m_scratch[:] = jnp.broadcast_to(m_next, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_next, l_scratch.shape)

    if causal:
        # skip k blocks entirely above the (offset-shifted) diagonal
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + offset)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == num_k - 1)
    def _finalize():
        l_final = l_scratch[:, :1]
        denom = jnp.where(l_final == 0.0, 1.0, l_final)
        o_ref[...] = (acc_scratch[:] / denom).astype(o_ref.dtype)
        # logsumexp per row, saved for the fused backward: P = exp(S - lse).
        # Fully-masked rows get +BIG so the backward's exp underflows to 0.
        lse = jnp.where(
            l_final == 0.0, jnp.float32(_BIG), m_scratch[:, :1] + jnp.log(denom)
        )
        lse_ref[0, :] = lse[:, 0]


def _flash_forward(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool, interpret: bool, blocks=None
) -> "tuple[jax.Array, jax.Array]":
    batch, q_len, n_heads, head_dim = q.shape
    k_len, n_kv = k.shape[1], k.shape[2]
    if n_heads % n_kv:
        raise ValueError(f"query heads ({n_heads}) must be a multiple of KV heads ({n_kv})")
    block_q = min((blocks or (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))[0], q_len)
    block_k = min((blocks or (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))[1], k_len)
    if q_len % block_q or k_len % block_k:
        # a silently floor-divided grid would leave tail rows unwritten
        raise ValueError(f"blocks ({block_q}, {block_k}) do not tile lengths ({q_len}, {k_len})")
    scale = head_dim**-0.5

    # heads-major [B, H, L, D] views: Mosaic tiles a block's last two dims, so
    # (L, D) must be the trailing pair; KV heads are resolved in the index maps
    # (GQA without materializing repeats)
    q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    grid = (batch, n_heads, q_len // block_q, k_len // block_k)

    def q_index(b, h, qi, ki):
        return (b, h, qi, 0)

    def kv_index(b, h, qi, ki):
        return (b, h * n_kv // n_heads, ki, 0)

    kernel = functools.partial(
        _flash_fwd_kernel, causal=causal, block_q=block_q, block_k=block_k, scale=scale, offset=k_len - q_len
    )

    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, n_heads, 1, q_len), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, head_dim), q_index),
            pl.BlockSpec((None, None, block_k, head_dim), kv_index),
            pl.BlockSpec((None, None, block_k, head_dim), kv_index),
        ],
        out_specs=(
            pl.BlockSpec((None, None, block_q, head_dim), q_index),
            pl.BlockSpec((None, None, 1, block_q), _stats_index),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3), lse


def _stats_index(b, h, qi, ki):
    """Block index of the per-row statistics (lse, delta), stored ``[B, H, 1, Lq]``."""
    return (b, h, 0, qi)


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _bwd_recompute(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki, *, causal, block_q, block_k, scale, offset):
    """Shared backward prologue: recompute P = exp(S - lse) for one (qi, ki) tile
    and return (q, k, ds, p, do) — operands in the input dtype (MXU-native),
    p/ds in f32 — the dq and dk/dv kernels consume the same quantities, so
    masking/recompute fixes land in exactly one place."""
    q = q_ref[...]
    k = k_ref[...]
    v = v_ref[...]
    do = do_ref[...]
    lse = lse_ref[0, :][:, None]  # [block_q, 1]
    delta = delta_ref[0, :][:, None]

    scores = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(q_pos + offset >= k_pos, scores, _NEG_INF)
    p = jnp.exp(scores - lse)  # [block_q, block_k] f32; 0 for masked rows (lse=BIG)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta)
    return q, k, ds, p, do


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc, *, causal, block_q, block_k, scale, offset
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        _, k, ds, _, _ = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            causal=causal, block_q=block_q, block_k=block_k, scale=scale, offset=offset,
        )
        dq_acc[:] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        @pl.when(ki * block_k <= qi * block_q + block_q - 1 + offset)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[...] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, causal, block_q, block_k, scale, offset,
):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    num_q = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        q, _, ds, p, do = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            causal=causal, block_q=block_q, block_k=block_k, scale=scale, offset=offset,
        )
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_acc[:] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        # skip q blocks entirely above this k block's (offset-shifted) diagonal
        @pl.when(qi * block_q + block_q - 1 + offset >= ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal: bool, interpret: bool, blocks=None):
    """FlashAttention-2-style fused backward: scores recomputed blockwise from the
    saved logsumexp — the [L, L] matrix never touches HBM (the XLA autodiff
    fallback materializes it, erasing the forward's memory win for training).
    ``blocks`` follows the forward's override so a shape legal under custom
    forward tiles can never leave backward tail rows unwritten."""
    batch, q_len, n_heads, head_dim = q.shape
    k_len, n_kv = k.shape[1], k.shape[2]
    block_q = min((blocks or (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))[0], q_len)
    block_k = min((blocks or (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K))[1], k_len)
    if q_len % block_q or k_len % block_k:
        raise ValueError(f"blocks ({block_q}, {block_k}) do not tile lengths ({q_len}, {k_len})")
    scale = head_dim**-0.5
    offset = k_len - q_len

    # delta_i = rowsum(dO_i * O_i), the dS correction term; [B, H, 1, Lq] like lse
    delta = jnp.einsum(
        "blhd,blhd->bhl", g.astype(jnp.float32), out.astype(jnp.float32)
    )[:, :, None, :]
    q, k, v, g = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g))  # heads-major, as the forward

    # dq: grid (b, h, q blocks, k blocks), accumulating over k blocks (ki innermost)
    q_spec = pl.BlockSpec((None, None, block_q, head_dim), lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, head_dim), lambda b, h, qi, ki: (b, h * n_kv // n_heads, ki, 0))
    stats_spec = pl.BlockSpec((None, None, 1, block_q), _stats_index)
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, causal=causal, block_q=block_q, block_k=block_k, scale=scale, offset=offset
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(batch, n_heads, q_len // block_q, k_len // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stats_spec, stats_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    # dk/dv: grid (b, h, k blocks, q blocks), accumulating over q blocks (qi
    # innermost); computed at full query-head resolution, then group-summed for
    # GQA (repeat's transpose is a sum)
    q_spec = pl.BlockSpec((None, None, block_q, head_dim), lambda b, h, ki, qi: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, head_dim), lambda b, h, ki, qi: (b, h * n_kv // n_heads, ki, 0))
    stats_spec = pl.BlockSpec((None, None, 1, block_q), lambda b, h, ki, qi: (b, h, 0, qi))
    dkv_spec = pl.BlockSpec((None, None, block_k, head_dim), lambda b, h, ki, qi: (b, h, ki, 0))
    dk_full, dv_full = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, causal=causal, block_q=block_q, block_k=block_k, scale=scale, offset=offset
        ),
        out_shape=(
            jax.ShapeDtypeStruct((batch, n_heads, k_len, head_dim), k.dtype),
            jax.ShapeDtypeStruct((batch, n_heads, k_len, head_dim), v.dtype),
        ),
        grid=(batch, n_heads, k_len // block_k, q_len // block_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stats_spec, stats_spec],
        out_specs=(dkv_spec, dkv_spec),
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    if n_kv != n_heads:
        group = n_heads // n_kv
        dk_full = dk_full.reshape(batch, n_kv, group, k_len, head_dim).sum(axis=2).astype(k.dtype)
        dv_full = dv_full.reshape(batch, n_kv, group, k_len, head_dim).sum(axis=2).astype(v.dtype)
    return tuple(x.transpose(0, 2, 1, 3) for x in (dq, dk_full, dv_full))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, interpret, blocks):
    out, _ = _flash_forward(q, k, v, causal, interpret, blocks)
    return out


def _flash_fwd_rule(q, k, v, causal, interpret, blocks):
    out, lse = _flash_forward(q, k, v, causal, interpret, blocks)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, interpret, blocks, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(q, k, v, out, lse, g, causal, interpret, blocks)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    interpret: bool = False,
    blocks: "tuple[int, int] | None" = None,
) -> jax.Array:
    """Flash attention entry point. ``interpret=True`` runs the kernel in the pallas
    interpreter (CPU) — used by the test ring. Accepts grouped-query KV
    (``k/v: [B, Lk, Hkv, D]`` with ``Hkv`` dividing the query head count).
    ``blocks=(block_q, block_k)`` overrides the forward tile sizes (the shootout
    benchmark sweeps them; lengths must divide evenly)."""
    return _flash(q, k, v, causal, interpret, blocks)
