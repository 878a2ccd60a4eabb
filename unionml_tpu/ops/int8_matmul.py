"""Pallas int8 weight-only matmul: ``y = x @ (q * scale)`` with in-VMEM dequant.

Why a kernel: small-batch decode matmuls are HBM-bound on the weight bytes; this
kernel guarantees int8 is the only weight traffic — int8 tiles stream HBM->VMEM,
the int8->bf16 convert happens in VMEM, the MXU consumes bf16 tiles, and the
per-channel scales are applied once to the f32 accumulator at the end.

Grid ``(m_blocks, f_blocks, k_blocks)`` with the k (reduction) dim innermost and
sequential: the f32 accumulator persists in VMEM scratch across k blocks (the
canonical pallas accumulation pattern, same as ops/flash_attention.py).

Measured status (v5e, decode shapes [8,4096]x[4096,14336] in a scan loop,
``benchmarks/bench_int8_matmul.py``): XLA's own dequant-inside-the-loop compiles
to a fused form that beats this kernel (~1.4x vs ~1.2x over bf16), so — same
policy as the flash-attention kernel — the generation path keeps the XLA dequant
(:func:`unionml_tpu.ops.quant.dequantize_tree` inside the step) and this kernel
stays **opt-in** via :func:`quantized_matmul(..., impl="pallas")` until it wins
its benchmark. Asking for the kernel where it cannot run (off-TPU without
``interpret=True``, or a weight with no block-aligned tiling) is an error, never
a quiet dequant + ``jnp.dot``.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int8_matmul", "quantized_matmul"]

_BLOCK_M = 256
_F_CANDIDATES = (512, 256, 128)
_K_CANDIDATES = (512, 256, 128, 64)  # K also tiles the x block's lane dim


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    w = q_ref[:].astype(jnp.bfloat16)  # int8 -> bf16 in VMEM; HBM saw int8 bytes
    acc_ref[:] += jax.lax.dot_general(
        x_ref[:].astype(jnp.bfloat16), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[:] = (acc_ref[:] * s_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _pick_block(dim: int, candidates) -> Optional[int]:
    for c in candidates:
        if dim % c == 0:
            return c
    return None


def int8_matmul(
    x: jax.Array,
    q: jax.Array,
    scale: jax.Array,
    *,
    out_dtype: Any = None,
    interpret: bool = False,
    block_m: Optional[int] = None,
    block_k: Optional[int] = None,
    block_f: Optional[int] = None,
) -> jax.Array:
    """``[M, K] @ int8 [K, F] * f32 [1, F] -> [M, F]`` via the pallas kernel.

    Requires K and F to admit a block tiling (see module docstring); M is padded
    to the block size here (x is small — the weight is never padded or copied).
    Explicit ``block_*`` override the defaults (the shootout benchmark sweeps
    them; dims must divide evenly).
    """
    m, k_dim = x.shape
    _, f_dim = q.shape
    out_dtype = out_dtype or x.dtype
    block_k = block_k or _pick_block(k_dim, _K_CANDIDATES)
    block_f = block_f or _pick_block(f_dim, _F_CANDIDATES)
    if block_k is None or block_f is None:
        raise ValueError(f"no block tiling for weight shape {(k_dim, f_dim)}")
    if k_dim % block_k or f_dim % block_f:
        raise ValueError(f"blocks ({block_k}, {block_f}) do not tile weight {(k_dim, f_dim)}")

    block_m = block_m or min(_BLOCK_M, 1 << (max(m - 1, 0)).bit_length() if m > 1 else 1)
    padded_m = -(-m // block_m) * block_m
    if padded_m != m:
        x = jnp.pad(x, ((0, padded_m - m), (0, 0)))

    grid = (padded_m // block_m, f_dim // block_f, k_dim // block_k)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((padded_m, f_dim), out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, fi, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_f), lambda mi, fi, ki: (ki, fi)),
            pl.BlockSpec((1, block_f), lambda mi, fi, ki: (0, fi)),
        ],
        out_specs=pl.BlockSpec((block_m, block_f), lambda mi, fi, ki: (mi, fi)),
        scratch_shapes=[pltpu.VMEM((block_m, block_f), jnp.float32)],
        compiler_params=(
            None
            if interpret
            else pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"))
        ),
        interpret=interpret,
    )(x, q, scale)
    return out[:m] if padded_m != m else out


def quantized_matmul(x: jax.Array, qt: Any, *, out_dtype: Any = None, impl: str = "xla") -> jax.Array:
    """Matmul against a :class:`~unionml_tpu.ops.quant.QuantizedTensor` weight.

    ``impl="xla"`` (default — currently faster, see module docstring) dequantizes
    in-graph and lets XLA fuse; ``impl="pallas"`` uses the kernel and raises
    where it cannot run (off-TPU, or a weight shape with no block tiling).
    ``x`` may carry leading batch dims; the weight must be 2D.
    """
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown impl {impl!r}; expected 'xla' or 'pallas'")
    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if impl == "pallas":
        out = int8_matmul(x2, qt.q, qt.scale, out_dtype=out_dtype)
    else:
        w = (qt.q.astype(jnp.float32) * qt.scale).astype(out_dtype)
        out = jnp.dot(x2.astype(out_dtype), w)
    return out.reshape(*lead, qt.q.shape[1])
