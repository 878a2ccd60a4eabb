"""Flash-kernel vs XLA fused attention micro-benchmark (TPU).

Decides whether ``multihead_attention(impl="auto")`` should route to the pallas
kernel: until the kernel wins here, auto stays on XLA (see
unionml_tpu/ops/attention.py docstring). Prints ONE JSON line with the speedup
as ``vs_baseline`` (>1.0 = flash faster than XLA).

Shapes follow the v5e measurement in the dispatch docstring: B=4, L=1024, H=8,
D=128, bf16, causal; plus a GQA case (Hkv=2) where the kernel reads KV heads
through its index maps instead of materializing repeats.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import emit, log

B, L, H, D = 4, 1024, 8, 128
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

    from unionml_tpu.ops.attention import dot_product_attention
    from unionml_tpu.ops.flash_attention import flash_attention

    platform = jax.devices()[0].platform
    log(f"platform: {platform}")
    if platform not in ("tpu",):
        log("flash kernel requires a TPU; refusing to report interpreter timings")
        sys.exit(1)

    results = {}
    best_blocks_by = {}
    for name, n_kv in (("mha", H), ("gqa", 2)):
        q = jax.random.normal(jax.random.PRNGKey(0), (B, L, H, D), dtype=jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, L, n_kv, D), dtype=jnp.bfloat16)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, L, n_kv, D), dtype=jnp.bfloat16)

        xla_ms = _time(lambda q, k, v: dot_product_attention(q, k, v, causal=True), q, k, v) * 1e3
        # sweep forward tile sizes; the winner decides whether auto flips
        flash_ms = float("inf")
        for blocks in ((128, 128), (256, 256), (256, 512), (512, 256), (512, 512), (128, 512)):
            try:
                t = _time(
                    lambda q, k, v: flash_attention(q, k, v, causal=True, blocks=blocks), q, k, v
                ) * 1e3
            except Exception as exc:
                log(f"{name} blocks {blocks}: failed ({type(exc).__name__})")
                continue
            log(f"{name} blocks {blocks}: {t:.3f} ms ({xla_ms / t:.2f}x vs xla)")
            if t < flash_ms:
                flash_ms, best_blocks_by[name] = t, blocks
        if flash_ms == float("inf"):
            log(f"FATAL: every flash tiling failed for {name}; a broken kernel must fail the bench")
            sys.exit(1)
        results[name] = (xla_ms, flash_ms)
        log(f"{name}: xla {xla_ms:.3f} ms, flash best {best_blocks_by[name]} {flash_ms:.3f} ms "
            f"({xla_ms / flash_ms:.2f}x)")

        def train_flash(q, k, v):
            return jax.grad(lambda a, b, c: flash_attention(a, b, c, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)

        def train_xla(q, k, v):
            return jax.grad(lambda a, b, c: dot_product_attention(a, b, c, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)

        fwdbwd_xla_ms = _time(train_xla, q, k, v) * 1e3
        fwdbwd_flash_ms = _time(train_flash, q, k, v) * 1e3
        results[f"{name}_fwdbwd"] = (fwdbwd_xla_ms, fwdbwd_flash_ms)
        log(
            f"{name} fwd+bwd: xla {fwdbwd_xla_ms:.3f} ms, flash (fused kernels) "
            f"{fwdbwd_flash_ms:.3f} ms ({fwdbwd_xla_ms / fwdbwd_flash_ms:.2f}x)"
        )

    xla_ms, flash_ms = results["mha"]
    emit(
        "flash_attention_fwd_latency",
        flash_ms,
        "ms",
        xla_ms / flash_ms,  # >1.0: flash wins, flip impl="auto"
        xla_ms=xla_ms,
        fwdbwd_flash_ms=results["mha_fwdbwd"][1],
        fwdbwd_xla_ms=results["mha_fwdbwd"][0],
        gqa_flash_ms=results["gqa"][1],
        gqa_xla_ms=results["gqa"][0],
        # the headline metric is mha's: report ITS winning tiles (gqa's separately)
        best_blocks=str(best_blocks_by["mha"]),
        gqa_best_blocks=str(best_blocks_by["gqa"]),
        batch=B,
        seq_len=L,
        heads=H,
        head_dim=D,
    )


if __name__ == "__main__":
    main()
