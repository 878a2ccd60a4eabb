"""Paged-attention decode shootout: pallas kernel vs the XLA gather path (TPU).

Decides whether ``Attention._paged_cached_attention`` should route single-token
decode through ``jax.experimental.pallas.ops.tpu.paged_attention`` (exposed via
``unionml_tpu.ops.paged_attention``): the gather path materializes
``pool[:, table]`` — a full logical copy of every resident row's K/V per layer
per step — while the kernel DMAs only the named pages through online softmax.
Prints ONE JSON line with the speedup as ``vs_baseline`` (>1.0 = kernel faster
than gather). Until the kernel wins here, the paged branch's default stays on
the gather (the flash-attention auto policy).

Shapes model a serving batcher at depth: S resident rows, a long context split
into 16-position pages, GQA heads — the regime where decode is KV-bandwidth
bound and the gather's extra materialization costs the most.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import emit, log

S, H, HKV, D = 8, 8, 2, 128
BLOCK = 16
CONTEXT = 2048  # positions per row -> 128 pages each
WARMUP, ITERS = 3, 20


def _time(fn, *args) -> float:
    import jax

    compiled = jax.jit(fn)
    for _ in range(WARMUP):
        jax.block_until_ready(compiled(*args))
    start = time.perf_counter()
    for _ in range(ITERS):
        out = compiled(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / ITERS


def main() -> None:
    import jax
    import jax.numpy as jnp

    from unionml_tpu.ops.attention import multihead_attention
    from unionml_tpu.ops.paged_attention import paged_decode_attention

    platform = jax.devices()[0].platform
    log(f"platform: {platform}")
    if platform != "tpu":
        log("the paged kernel requires a TPU; refusing to report interpreter timings")
        sys.exit(1)

    pages_per_row = CONTEXT // BLOCK
    n_pages = S * pages_per_row + 1  # disjoint tables + scratch
    key = jax.random.PRNGKey(0)
    k_pages = jax.random.normal(key, (HKV, n_pages, BLOCK, D), dtype=jnp.bfloat16)
    v_pages = jax.random.normal(jax.random.fold_in(key, 1), (HKV, n_pages, BLOCK, D), dtype=jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2), (S, H, D), dtype=jnp.bfloat16)
    table = jnp.arange(S * pages_per_row, dtype=jnp.int32).reshape(S, pages_per_row)
    lengths = jnp.full((S,), CONTEXT, jnp.int32)

    def gather_path(q, k_pages, v_pages, table, lengths):
        rows_k = k_pages[:, table]  # [HKV, S, MB, bs, D]
        rows_v = v_pages[:, table]
        keys = jnp.transpose(rows_k.reshape(HKV, S, -1, D), (1, 2, 0, 3))
        values = jnp.transpose(rows_v.reshape(HKV, S, -1, D), (1, 2, 0, 3))
        visible = jnp.arange(keys.shape[1])[None, None, None, :] < lengths[:, None, None, None]
        return multihead_attention(q[:, None], keys, values, causal=False, mask=visible, impl="xla")[:, 0]

    gather_ms = _time(gather_path, q, k_pages, v_pages, table, lengths) * 1e3
    kernel_ms = float("inf")
    best_ppcb = None
    for ppcb in (4, 8, 16, 32):
        if pages_per_row % ppcb:
            continue
        try:
            t = _time(
                lambda q, k, v, ln, tb: paged_decode_attention(
                    q, k, v, ln, tb, pages_per_compute_block=ppcb
                ),
                q, k_pages, v_pages, lengths, table,
            ) * 1e3
        except Exception as exc:
            log(f"ppcb {ppcb}: failed ({type(exc).__name__}: {exc})")
            continue
        log(f"ppcb {ppcb}: {t:.3f} ms ({gather_ms / t:.2f}x vs gather)")
        if t < kernel_ms:
            kernel_ms, best_ppcb = t, ppcb
    if kernel_ms == float("inf"):
        log("FATAL: every kernel config failed; a broken kernel must fail the bench")
        sys.exit(1)

    # sanity: same numerics (bf16 tolerance)
    import numpy as np

    ref = np.asarray(gather_path(q, k_pages, v_pages, table, lengths), np.float32)
    out = np.asarray(paged_decode_attention(q, k_pages, v_pages, lengths, table), np.float32)
    err = float(np.max(np.abs(ref - out)))
    log(f"gather {gather_ms:.3f} ms, kernel best ppcb={best_ppcb} {kernel_ms:.3f} ms; max |diff| {err:.4f}")
    if err > 0.1:
        log("FATAL: kernel output diverges from the gather reference")
        sys.exit(1)

    # int8 pages through the kernel, measured to SETTLE the analysis (the
    # library broadcasts scales to full head width per page, predicting ~2.5x
    # the bf16 traffic, which is why layers.py keeps int8 on the gather path);
    # the timing only counts if the quantized output matches the dequantized
    # gather reference
    from unionml_tpu.models.layers import quantize_kv_rows

    kq, k_sc = quantize_kv_rows(k_pages)
    vq, v_sc = quantize_kv_rows(v_pages)
    int8_ms = None
    try:
        int8_out = np.asarray(
            paged_decode_attention(
                q, kq, vq, lengths, table, k_scales=k_sc, v_scales=v_sc,
                pages_per_compute_block=best_ppcb,
            ),
            np.float32,
        )
        int8_ref = np.asarray(
            gather_path(
                q,
                (kq.astype(jnp.float32) * k_sc).astype(jnp.bfloat16),
                (vq.astype(jnp.float32) * v_sc).astype(jnp.bfloat16),
                table, lengths,
            ),
            np.float32,
        )
        int8_err = float(np.max(np.abs(int8_ref - int8_out)))
        if int8_err > 0.1:
            raise RuntimeError(f"int8 kernel diverges from dequantized reference (max |diff| {int8_err:.4f})")
        int8_ms = _time(
            lambda q, kq, vq, ks, vs, ln, tb: paged_decode_attention(
                q, kq, vq, ln, tb, k_scales=ks, v_scales=vs, pages_per_compute_block=best_ppcb
            ),
            q, kq, vq, k_sc, v_sc, lengths, table,
        ) * 1e3
        log(f"int8 pages: {int8_ms:.3f} ms ({kernel_ms / int8_ms:.2f}x vs bf16 kernel), max |diff| {int8_err:.4f}")
    except Exception as exc:
        log(f"int8 kernel path failed ({type(exc).__name__}: {exc}); reporting bf16 only")

    emit(
        "paged_attention_decode_step",
        kernel_ms,
        "ms",
        gather_ms / kernel_ms,
        gather_ms=round(gather_ms, 3),
        int8_ms=round(int8_ms, 3) if int8_ms is not None else None,
        pages_per_compute_block=best_ppcb,
        context=CONTEXT,
        slots=S,
    )


if __name__ == "__main__":
    main()
