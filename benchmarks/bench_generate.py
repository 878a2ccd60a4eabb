"""Generation benchmark: autoregressive decode throughput on one chip.

Metric: decode tokens/sec (batch x steps / wall) through
:class:`unionml_tpu.models.generate.Generator` — bucketed jitted prefill + the
single-compile ``lax.scan`` decode loop with donated KV cache.

The reference has no inference engine (its serve path calls the user predictor
eagerly, unionml/fastapi.py:50-64), so there is no reference number to compare
against. Decode at small batch is HBM-bandwidth bound — every step streams the
full parameter bytes once — so ``vs_baseline`` reports the roofline fraction:
achieved bytes/s (param bytes + KV-cache bytes per step) over v5e peak HBM
bandwidth (819 GB/s). That is the scale-invariant utilization number that
carries from this depth proxy to the full model.

Single-chip honesty (same convention as bench_llama_lora.py): the llama3-8b
architecture is truncated in depth to fit one chip; multi-chip sharded
generation is pinned to single-device tokens by tests/emulated/test_generate_tp.py.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import Timer, emit, log

V5E_HBM_BYTES_PER_S = 819e9  # TODO(S2): key by device_kind, with its source

PROXY_LAYERS = 8
BATCH = 8
PROMPT_LEN = 128
NEW_TOKENS = 128


def main() -> None:
    import jax
    import jax.numpy as jnp

    from unionml_tpu.models import GenerationConfig, Generator, Llama, LlamaConfig

    log(f"devices: {jax.devices()}")
    config = LlamaConfig.llama3_8b(
        n_layers=PROXY_LAYERS, param_dtype=jnp.bfloat16, max_seq_len=PROMPT_LEN + NEW_TOKENS
    )
    module = Llama(config)
    params = jax.jit(
        lambda key: module.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    )(jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    log(f"proxy model: {PROXY_LAYERS} layers, {n_params/1e9:.2f}B params (bf16)")

    gen = Generator(
        module,
        params,
        GenerationConfig(max_new_tokens=NEW_TOKENS, temperature=0.0, prompt_buckets=(PROMPT_LEN,)),
    )
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, config.vocab_size, size=PROMPT_LEN)) for _ in range(BATCH)]

    with Timer() as cold:
        gen(prompts)
    log(f"cold generate (compile + run): {cold.elapsed:.1f}s")
    with Timer() as warm:
        out = gen(prompts)
    assert out.shape == (BATCH, NEW_TOKENS)

    decode_tokens = BATCH * NEW_TOKENS
    tokens_per_s = decode_tokens / warm.elapsed
    log(f"warm generate: {warm.elapsed*1e3:.0f} ms -> {tokens_per_s:.0f} decode tokens/s")

    # prefill throughput: amortized over the same warm call (prefill is one jitted
    # dispatch over [B, PROMPT_LEN]; decode dominates the wall by construction, so
    # time prefill separately via a fresh single-token decode config)
    prefill_gen = Generator(
        module, params, GenerationConfig(max_new_tokens=1, temperature=0.0, prompt_buckets=(PROMPT_LEN,))
    )
    prefill_gen(prompts)  # compile
    with Timer() as pf:
        prefill_gen(prompts)
    prefill_tokens_per_s = BATCH * PROMPT_LEN / pf.elapsed
    log(f"prefill: {pf.elapsed*1e3:.0f} ms -> {prefill_tokens_per_s:.0f} prompt tokens/s")

    # bandwidth roofline: each decode step streams the *matmul* param bytes once
    # (the embedding table is a gather — only BATCH rows of it are read per step;
    # same exclusion convention as bench_bert.py MFU accounting) plus the mean
    # filled KV region
    embed_params = config.vocab_size * config.dim
    param_bytes = 2 * (n_params - embed_params) + 2 * BATCH * config.dim
    head_dim = config.dim // config.n_heads
    mean_ctx = PROMPT_LEN + NEW_TOKENS / 2
    kv_bytes = 2 * 2 * PROXY_LAYERS * BATCH * mean_ctx * config.n_kv_heads * head_dim
    bytes_per_step = param_bytes + kv_bytes
    achieved = bytes_per_step * NEW_TOKENS / warm.elapsed
    roofline = achieved / V5E_HBM_BYTES_PER_S
    log(f"decode streams ~{bytes_per_step/1e9:.2f} GB/step -> {achieved/1e9:.0f} GB/s ({roofline:.2f} of v5e peak)")

    # weight-only int8: halves the param bytes per step; measured, not asserted
    del gen
    qgen = Generator(
        module,
        params,
        GenerationConfig(max_new_tokens=NEW_TOKENS, temperature=0.0, prompt_buckets=(PROMPT_LEN,)),
        quantize="int8",
    )
    with Timer() as qcold:
        qgen(prompts)
    with Timer() as qwarm:
        qout = qgen(prompts)
    assert qout.shape == (BATCH, NEW_TOKENS)
    int8_tokens_per_s = decode_tokens / qwarm.elapsed
    log(
        f"int8 warm generate: {qwarm.elapsed*1e3:.0f} ms -> {int8_tokens_per_s:.0f} decode tokens/s "
        f"({int8_tokens_per_s/tokens_per_s:.2f}x bf16; compile {qcold.elapsed:.1f}s)"
    )

    emit(
        "llama_decode_throughput",
        tokens_per_s,
        "tokens/sec/chip",
        roofline,
        prefill_tokens_per_s=round(prefill_tokens_per_s, 1),
        int8_tokens_per_s=round(int8_tokens_per_s, 1),
        batch=BATCH,
        new_tokens=NEW_TOKENS,
        params_b=round(n_params / 1e9, 2),
    )


if __name__ == "__main__":
    main()
