"""Config 4 (BASELINE.json): Llama LoRA fine-tune, FSDP-style sharded params.

Metric: trainer tokens/sec/chip for a LoRA fine-tune (rank-16 adapters on q/k/v/o +
mlp, base weights frozen via optax.multi_transform) of a Llama-3-family decoder.

Single-chip honesty: Llama-3-8B needs >= 8 v5e chips just for bf16 weights, so the
real-hardware measurement here runs the same llama3_8b architecture truncated in
depth (``PROXY_LAYERS`` of 32 layers, bf16 params) on one chip; the 8B FSDP
sharding itself is validated by ``__graft_entry__.dryrun_multichip`` and the
emulated-mesh tests. ``vs_baseline`` reports MFU (achieved / v5e peak bf16 FLOPs) —
the scale-invariant utilization number that carries to the full model.

FLOPs accounting for LoRA: the frozen base weights' dW matmuls feed only
``optax.set_to_zero`` and are dead-code-eliminated by XLA, so a LoRA step costs
~4 FLOPs/param/token (fwd 2 + input-grad 2) over the *matmul* params (embedding
lookups are gathers, not matmuls; the LM head is a real matmul and is counted).
Layer remat is off: measured 8x slower here and unnecessary without the f32
logits tensor dominating memory.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import V5E_PEAK_BF16_FLOPS, emit, log

SEQ_LEN = 1024
BATCH = 4
STEPS = 12
PROXY_LAYERS = 8
LORA_RANK = 16


def main() -> None:
    import jax
    import jax.numpy as jnp
    from flax.training import train_state

    from unionml_tpu import MeshSpec, TrainerConfig, make_train_step
    from unionml_tpu.models import Llama, LlamaConfig, causal_lm_loss, llama_partition_rules, lora_optimizer
    from unionml_tpu.train import fit

    log(f"devices: {jax.devices()}")
    n_chips = len(jax.devices())
    config = LlamaConfig.llama3_8b(
        n_layers=PROXY_LAYERS,
        max_seq_len=SEQ_LEN,
        lora_rank=LORA_RANK,
        param_dtype=jnp.bfloat16,
        remat=False,
    )
    module = Llama(config)

    rng = np.random.default_rng(0)
    n = BATCH * n_chips * (STEPS + 6)
    tokens = rng.integers(0, config.vocab_size, size=(n, SEQ_LEN), dtype=np.int32)

    params = module.init(jax.random.PRNGKey(0), jnp.asarray(tokens[:1, :8]))["params"]
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    log(f"proxy params: {n_params/1e9:.2f}B (llama3-8b arch, {PROXY_LAYERS} layers, LoRA rank {LORA_RANK})")
    state = train_state.TrainState.create(apply_fn=module.apply, params=params, tx=lora_optimizer(1e-4))

    def loss_fn(p, batch):
        # plain loss wins at this scale; chunked_causal_lm_loss is the fallback when
        # the f32 logits don't fit (deeper proxies / longer sequences)
        return causal_lm_loss(lambda pp, t: module.apply({"params": pp}, t), p, batch)

    step = make_train_step(loss_fn)
    result = fit(
        state,
        step,
        [tokens],
        TrainerConfig(
            epochs=1,
            batch_size=BATCH * n_chips,
            mesh=MeshSpec(data=-1),
            partition_rules=llama_partition_rules(),
            shuffle=False,
            device_data=True,
            steps_per_call=4,
        ),
    )
    tokens_per_sec_chip = result.samples_per_sec_per_chip * SEQ_LEN
    log(
        f"{result.steps} steps, compile {result.compile_time_s:.1f}s, "
        f"{tokens_per_sec_chip:.0f} tokens/s/chip, final loss {result.history[-1]['loss']:.3f}"
    )
    embed_params = int(np.prod(params["embed"]["embedding"].shape))
    matmul_params = n_params - embed_params
    flops_per_token = 4 * matmul_params  # LoRA: frozen dW is DCE'd (see module docstring)
    mfu = tokens_per_sec_chip * flops_per_token / V5E_PEAK_BF16_FLOPS

    emit(
        "llama_lora_train_throughput",
        tokens_per_sec_chip,
        "tokens/sec/chip",
        mfu,
        mfu=mfu,
        compile_time_s=result.compile_time_s,
        n_chips=n_chips,
        proxy_layers=PROXY_LAYERS,
        seq_len=SEQ_LEN,
        params_b=n_params / 1e9,
    )


if __name__ == "__main__":
    main()
