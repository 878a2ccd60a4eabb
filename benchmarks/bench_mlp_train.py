"""MLP training throughput (BASELINE.json config 2: Flax MLP at MNIST shapes).

Synthetic MNIST-sized data through the framework's full step-mode path — Dataset
arrays -> device-resident batches -> jit-compiled donated train step
(:func:`unionml_tpu.train.fit`) — reported as trainer samples/sec/chip. Refuses
to report a CPU run under this metric's name.

Prints ONE JSON line on stdout.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np

from benchmarks.common import emit, log

BATCH = 512
INPUT_DIM = 784
CLASSES = 10
HIDDEN = (512, 256)
STEPS_PER_CALL = 50
N_SAMPLES = BATCH * 300  # divisible by steps_per_call: no trailing-group recompile


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from unionml_tpu import TrainerConfig, make_train_step
    from unionml_tpu.models import MLPClassifier, MLPConfig
    from unionml_tpu.models.mlp import make_train_state
    from unionml_tpu.train import fit

    device = jax.devices()[0]
    log(f"jax devices: {jax.devices()}")
    if device.platform == "cpu":
        log("refusing to report a CPU run as the accelerator's training throughput")
        sys.exit(1)

    rng = np.random.default_rng(0)
    features = rng.normal(size=(N_SAMPLES, INPUT_DIM)).astype("float32")
    labels = rng.integers(0, CLASSES, size=(N_SAMPLES,)).astype("int32")
    config = MLPConfig(features=HIDDEN, num_classes=CLASSES)
    module = MLPClassifier(config)
    state = make_train_state(config, INPUT_DIM, learning_rate=1e-3)

    def loss_fn(params, batch):
        bx, by = batch
        logits = module.apply({"params": params}, bx)
        return optax.softmax_cross_entropy_with_integer_labels(logits.astype(jnp.float32), by).mean()

    result = fit(
        state,
        make_train_step(loss_fn),
        [features, labels],
        TrainerConfig(
            epochs=1, batch_size=BATCH, shuffle=False, device_data=True, steps_per_call=STEPS_PER_CALL
        ),
    )
    log(f"{result.steps} steps, compile {result.compile_time_s:.2f}s, {result.samples_per_sec:.0f} samples/s")
    emit(
        "mlp_train_throughput",
        result.samples_per_sec_per_chip,
        "samples/sec/chip",
        0.0,  # no baseline: nothing else trains this model in the repo
        platform=device.platform,
        device_kind=device.device_kind,
        compile_time_s=result.compile_time_s,
    )


if __name__ == "__main__":
    main()
