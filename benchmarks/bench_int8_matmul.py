"""Int8 weight-only matmul shootout at decode shapes: bf16 vs XLA-dequant vs pallas.

The generation path's int8 mode dequantizes inside the jitted step and lets XLA
fuse (ops/quant.py); ops/int8_matmul.py is the pallas alternative that
guarantees int8-only weight traffic. This bench decides which one the framework
uses (current winner: XLA — see the kernel's module docstring). The loop runs
inside one jit (lax.scan) to match the decode loop's dispatch structure;
separate dispatches would be dispatch-overhead-dominated and meaningless.

Prints ONE JSON line; ``vs_baseline`` is the winner's speedup over bf16.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import emit, log

B, D, F, ITERS = 8, 4096, 14336, 100


def main() -> None:
    import jax
    import jax.numpy as jnp

    from unionml_tpu.ops.int8_matmul import int8_matmul

    log(f"devices: {jax.devices()}  shapes: [{B},{D}]x[{D},{F}] x{ITERS} in-scan")
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(ITERS, B, D)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(D, F)), jnp.bfloat16)
    wq = jnp.asarray(rng.integers(-127, 127, size=(D, F)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.01, 0.02, size=(1, F)), jnp.float32)

    def bench(fn, *args):
        float(fn(*args))  # compile + fence
        t0 = time.perf_counter()
        float(fn(*args))
        return (time.perf_counter() - t0) / ITERS

    @jax.jit
    def loop_bf16(xs, w):
        return jax.lax.scan(lambda a, x: (a + (x @ w).astype(jnp.float32).sum(), None), jnp.float32(0), xs)[0]

    @jax.jit
    def loop_xla_int8(xs, wq, scale):
        def body(a, x):
            wd = (wq.astype(jnp.float32) * scale).astype(jnp.bfloat16)
            return a + (x @ wd).astype(jnp.float32).sum(), None

        return jax.lax.scan(body, jnp.float32(0), xs)[0]

    def loop_pallas(blocks):
        block_m, block_k, block_f = blocks

        @jax.jit
        def run(xs, wq, scale):
            def body(a, x):
                y = int8_matmul(
                    x, wq, scale, out_dtype=jnp.float32,
                    block_m=block_m, block_k=block_k, block_f=block_f,
                )
                return a + y.sum(), None

            return jax.lax.scan(body, jnp.float32(0), xs)[0]

        return run

    t_bf16 = bench(loop_bf16, xs, w)
    t_xla = bench(loop_xla_int8, xs, wq, scale)
    on_tpu = jax.default_backend() == "tpu"
    t_pallas, best_blocks = float("nan"), None
    if on_tpu:
        # sweep the kernel's tiling: the winner decides whether pallas ships
        sweep = [(None, None, None)] + [
            (bm, bk, bf) for bm in (8, 32) for bk in (512, 1024) for bf in (512, 2048)
        ]
        for blocks in sweep:
            try:
                t = bench(loop_pallas(blocks), xs, wq, scale)
            except Exception as exc:
                log(f"pallas blocks {blocks}: failed ({type(exc).__name__})")
                continue
            log(f"pallas blocks {blocks}: {t*1e6:.0f} us ({t_bf16/t:.2f}x over bf16)")
            if not (t >= t_pallas):  # NaN-safe min
                t_pallas, best_blocks = t, blocks
    pallas_ran = on_tpu and best_blocks is not None
    if on_tpu and not pallas_ran:
        log("WARNING: every pallas tiling failed; reporting XLA only")
    log(f"bf16 {t_bf16*1e6:.0f} us | xla-int8 {t_xla*1e6:.0f} us ({t_bf16/t_xla:.2f}x)"
        + (f" | pallas-int8 best {best_blocks}: {t_pallas*1e6:.0f} us ({t_bf16/t_pallas:.2f}x)"
           if pallas_ran else " | pallas: not run"))

    best = min(t_xla, t_pallas) if pallas_ran else t_xla
    emit(
        "int8_matmul_speedup",
        t_bf16 / best,
        "x over bf16",
        t_bf16 / best,
        xla_us=round(t_xla * 1e6, 1),
        pallas_us=round(t_pallas * 1e6, 1) if pallas_ran else None,
        bf16_us=round(t_bf16 * 1e6, 1),
        winner="pallas" if pallas_ran and t_pallas < t_xla else "xla",
        pallas_blocks=str(best_blocks),
    )


if __name__ == "__main__":
    main()
