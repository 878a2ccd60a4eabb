"""Train-driver + parallelism tests on the emulated 8-device CPU mesh.

The analog of the reference's cluster ring (SURVEY.md §4): multi-chip behavior without
hardware, via ``--xla_force_host_platform_device_count=8``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn
from flax.training import train_state

from unionml_tpu import MeshSpec, TrainerConfig, make_train_step
from unionml_tpu.parallel.sharding import batch_sharding, infer_fsdp_sharding
from unionml_tpu.train import evaluate, fit


class TinyMLP(nn.Module):
    width: int = 32

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(self.width)(x)
        x = nn.relu(x)
        return nn.Dense(2)(x)


def _make_state(lr=1e-2, width=32):
    module = TinyMLP(width)
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    return module, train_state.TrainState.create(apply_fn=module.apply, params=params, tx=optax.adam(lr))


def _make_data(n=1024, one_d_targets=False):
    rng = np.random.default_rng(0)
    w = rng.normal(size=(8,))
    X = rng.normal(size=(n, 8)).astype("float32")
    y = (X @ w > 0).astype("int32")
    return [X, y if one_d_targets else y[:, None]]


def _loss(module):
    def loss_fn(params, batch):
        X, y = batch
        logits = module.apply({"params": params}, X)
        labels = y.reshape(-1).astype(jnp.int32)
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()

    return loss_fn


def test_devices_emulated():
    assert len(jax.devices()) == 8


def test_fit_dp_mesh():
    module, state = _make_state()
    step = make_train_step(_loss(module))
    result = fit(state, step, _make_data(), TrainerConfig(epochs=2, batch_size=128, mesh=MeshSpec(data=-1)))
    assert result.steps == 16
    assert result.history[-1]["loss"] < 0.4
    assert result.samples_per_sec > 0
    assert result.compile_time_s > 0


def test_fit_one_dimensional_targets():
    """1-D label vectors must not crash batch placement (regression)."""
    module, state = _make_state()
    step = make_train_step(_loss(module))
    result = fit(state, step, _make_data(one_d_targets=True), TrainerConfig(epochs=1, batch_size=64))
    assert result.steps == 16


def test_fit_partial_final_batch():
    """drop_remainder=False with an indivisible final batch must not crash."""
    module, state = _make_state()
    step = make_train_step(_loss(module))
    data = _make_data(n=1000)
    result = fit(
        state, step, data, TrainerConfig(epochs=1, batch_size=128, drop_remainder=False, mesh=MeshSpec(data=-1))
    )
    assert result.steps == 8  # 7 full + 1 partial


def test_fit_grad_accumulation():
    module, state = _make_state()
    step = make_train_step(_loss(module), grad_accum_steps=4)
    result = fit(state, step, _make_data(), TrainerConfig(epochs=2, batch_size=128, mesh=MeshSpec(data=-1)))
    assert result.history[-1]["loss"] < 0.5
    # fit pinned the scan-carry/microbatch layouts (driver._pin_accum_shardings):
    # the grads carry follows the param shardings, the microbatch stack keeps
    # the batch layout with a leading accum dim, and the divisor counts the
    # batch-axis shards — the explicit layouts the dryrun's warning-free SPMD
    # assertion depends on
    param_sh, micro_sh, micro_div = step.pinned_shardings
    assert param_sh is not None and micro_sh is not None
    assert micro_sh.spec[0] is None  # accum dim replicated
    assert micro_div == 8  # data=-1 on 8 emulated devices


def test_fit_fsdp_shards_params():
    module, state = _make_state(width=1024)  # big enough to trip the fsdp threshold
    step = make_train_step(_loss(module))
    config = TrainerConfig(epochs=1, batch_size=128, mesh=MeshSpec(data=2, fsdp=4), fsdp_min_weight_size=1024)
    result = fit(state, step, _make_data(), config)
    kernel = result.state.params["Dense_0"]["kernel"]
    # the fsdp axis (size 4) should shard the largest divisible dim of the kernel
    assert "fsdp" in str(kernel.sharding.spec)


def test_evaluate_partial_batches():
    module, state = _make_state()
    step = make_train_step(_loss(module))
    data = _make_data(n=1001)
    state = fit(state, step, data, TrainerConfig(epochs=2, batch_size=128, mesh=MeshSpec(data=-1))).state

    def eval_step(state, batch):
        X, y = batch
        logits = module.apply({"params": state.params}, X)
        acc = (jnp.argmax(logits, -1) == y.reshape(-1)).mean()
        return {"accuracy": acc}

    metrics = evaluate(state, eval_step, data, batch_size=128, mesh=MeshSpec(data=-1))
    assert metrics["accuracy"] > 0.9


def test_evaluate_consumes_fsdp_sharded_state_in_place():
    """evaluate() compiles with the same resolved shardings as fit(): an
    FSDP-sharded state keeps its placement (no per-split reshard) and the
    metrics match an unsharded evaluation."""
    module, state = _make_state(width=1024)
    step = make_train_step(_loss(module))
    data = _make_data()
    mesh_spec = MeshSpec(data=2, fsdp=4)
    config = TrainerConfig(epochs=1, batch_size=128, mesh=mesh_spec, fsdp_min_weight_size=1024)
    trained = fit(state, step, data, config).state
    assert "fsdp" in str(trained.params["Dense_0"]["kernel"].sharding.spec)

    def eval_step(state, batch):
        X, y = batch
        logits = module.apply({"params": state.params}, X)
        return {"accuracy": (jnp.argmax(logits, -1) == y.reshape(-1)).mean()}

    sharded = evaluate(
        trained, eval_step, data, batch_size=128, mesh=mesh_spec, fsdp_min_weight_size=1024
    )
    plain = evaluate(trained, eval_step, data, batch_size=128, mesh=MeshSpec(data=-1))
    assert sharded["accuracy"] > 0.9
    np.testing.assert_allclose(sharded["accuracy"], plain["accuracy"], atol=1e-6)


def test_checkpoint_and_resume(tmp_path):
    module, state = _make_state()
    step = make_train_step(_loss(module))
    data = _make_data()
    ckpt_dir = str(tmp_path / "ckpt")

    full = fit(state, step, data, TrainerConfig(epochs=2, batch_size=128, shuffle=False, donate=False))

    _, state2 = _make_state()
    partial = fit(
        state2,
        step,
        data,
        TrainerConfig(
            epochs=1, batch_size=128, shuffle=False, donate=False,
            checkpoint_dir=ckpt_dir, checkpoint_every_steps=4,
        ),
    )
    assert partial.steps == 8
    _, state3 = _make_state()
    resumed = fit(
        state3,
        step,
        data,
        TrainerConfig(
            epochs=2, batch_size=128, shuffle=False, donate=False,
            checkpoint_dir=ckpt_dir, checkpoint_every_steps=4, resume=True,
        ),
    )
    # resumed from completed step 8, so only 8 more steps run
    assert resumed.steps == 8
    np.testing.assert_allclose(
        float(full.history[-1]["loss"]), float(resumed.history[-1]["loss"]), rtol=0.2
    )


def test_batch_sharding_handles_any_rank():
    mesh = MeshSpec(data=-1).build()
    sharding = batch_sharding(mesh)
    for shape in [(16,), (16, 4), (16, 4, 2)]:
        arr = jax.device_put(np.zeros(shape, dtype="float32"), sharding)
        assert arr.sharding.is_equivalent_to(sharding, len(shape))


def test_infer_fsdp_sharding_rules():
    mesh = MeshSpec(data=2, fsdp=4).build()
    params = {
        "big": np.zeros((1024, 64), dtype="float32"),
        "bias": np.zeros((64,), dtype="float32"),
    }
    shardings = infer_fsdp_sharding(params, mesh, min_weight_size=1024)
    assert "fsdp" in str(shardings["big"].spec)
    assert str(shardings["bias"].spec) == "PartitionSpec()"


def test_device_data_mode_matches_host_path():
    module, state = _make_state()
    step = make_train_step(_loss(module))
    data = _make_data()
    host = fit(state, step, data, TrainerConfig(epochs=2, batch_size=128, shuffle=False, donate=False))
    _, state2 = _make_state()
    dev = fit(
        state2,
        step,
        data,
        TrainerConfig(epochs=2, batch_size=128, shuffle=False, donate=False, device_data=True, steps_per_call=3),
    )
    assert dev.steps == host.steps == 16
    np.testing.assert_allclose(
        float(dev.history[-1]["loss"]), float(host.history[-1]["loss"]), rtol=1e-4
    )


def test_device_data_small_dataset_still_trains():
    """steps_per_call larger than the schedule must not silently train nothing."""
    module, state = _make_state()
    step = make_train_step(_loss(module))
    result = fit(
        state,
        step,
        _make_data(n=256),
        TrainerConfig(epochs=1, batch_size=64, device_data=True, steps_per_call=50),
    )
    assert result.steps == 4


def test_device_data_log_trigger_with_stride(tmp_path):
    module, state = _make_state()
    step = make_train_step(_loss(module))
    result = fit(
        state,
        step,
        _make_data(),
        TrainerConfig(epochs=2, batch_size=128, device_data=True, steps_per_call=3, log_every_steps=5),
    )
    assert len(result.history) >= 3  # crossing semantics: logs fire despite stride 3


def test_fit_with_flax_logical_partitioning_metadata():
    """A module annotated with nn.with_partitioning carries its layout in the
    params tree; fit() maps the logical names to mesh axes via
    logical_axis_rules, unboxes, and trains with those placements (SURVEY.md
    §7 hard part 3 — no regex tables needed)."""

    class AnnotatedMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(
                256,
                kernel_init=nn.with_partitioning(nn.initializers.lecun_normal(), ("inp", "hidden")),
            )(x)
            x = nn.relu(x)
            return nn.Dense(
                2,
                kernel_init=nn.with_partitioning(nn.initializers.lecun_normal(), ("hidden", None)),
            )(x)

    module = AnnotatedMLP()
    variables = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))
    params = variables["params"]
    # metadata boxes really are in the tree
    assert isinstance(params["Dense_0"]["kernel"], nn.Partitioned)

    state = train_state.TrainState.create(
        apply_fn=module.apply, params=params, tx=optax.adam(1e-2)
    )

    result = fit(
        state,
        make_train_step(_loss(module)),
        _make_data(),
        TrainerConfig(
            epochs=2,
            batch_size=128,
            mesh=MeshSpec(data=2, fsdp=2, model=2),
            logical_axis_rules=[("hidden", "model"), ("inp", "fsdp")],
        ),
    )
    kernel0 = result.state.params["Dense_0"]["kernel"]
    assert not isinstance(kernel0, nn.Partitioned)  # unboxed for training
    assert str(kernel0.sharding.spec) == "PartitionSpec('fsdp', 'model')"
    # optimizer state inherited the same placement through the boxed tree
    mu0 = result.state.opt_state[0].mu["Dense_0"]["kernel"]
    assert str(mu0.sharding.spec) == "PartitionSpec('fsdp', 'model')"
    assert result.history[-1]["loss"] < 0.5


def test_logical_metadata_names_used_as_mesh_axes_without_rules():
    """Without logical_axis_rules, Partitioned names are mesh axis names directly;
    names not present in the mesh replicate their dim."""
    from unionml_tpu.parallel import combine_fsdp_tp, unbox_partitioned

    mesh = MeshSpec(data=4, model=2).build()
    kernel = nn.Partitioned(jnp.zeros((8, 16)), names=("missing_axis", "model"))
    tree = {"layer": {"kernel": kernel, "bias": jnp.zeros((16,))}}
    shardings = combine_fsdp_tp(tree, mesh, None, logical_rules=None)
    assert str(shardings["layer"]["kernel"].spec) == "PartitionSpec(None, 'model')"
    unboxed = unbox_partitioned(tree)
    assert unboxed["layer"]["kernel"].shape == (8, 16)


def test_fit_reports_memory_stats_or_none():
    """FitResult carries the §5.5 HBM accounting: a dict of byte counters on
    backends that expose memory_stats, None on backends that don't (CPU)."""
    module, state = _make_state()
    result = fit(
        state, make_train_step(_loss(module)), _make_data(n=256),
        TrainerConfig(epochs=1, batch_size=128),
    )
    assert result.memory_stats is None or (
        isinstance(result.memory_stats, dict)
        and all(isinstance(v, int) for v in result.memory_stats.values())
    )


def test_memory_stats_report_the_fullest_local_device(monkeypatch):
    """On several devices the reported counters are those of the device whose
    peak use is highest — not device 0's — and a device that reports nothing
    (or raises) is passed over. The emulated CPU devices report no byte
    counters themselves, so each is given a reading of its own."""
    from unionml_tpu.train import driver

    devices = jax.local_devices()
    assert len(devices) == 8

    class Reporting:
        def __init__(self, device, stats):
            self.device, self.stats = device, stats

        def memory_stats(self):
            if isinstance(self.stats, Exception):
                raise self.stats
            return self.stats

    def reading(i):
        return {"bytes_in_use": 100 + i, "peak_bytes_in_use": 1000 + 10 * i, "bytes_limit": 16_000, "num_allocs": 7}

    readings = [reading(0), None, reading(2), RuntimeError("no stats"), reading(6), {}, reading(3), reading(1)]
    monkeypatch.setattr(jax, "local_devices", lambda: [Reporting(d, r) for d, r in zip(devices, readings)])
    assert driver._device_memory_stats() == {"bytes_in_use": 106, "peak_bytes_in_use": 1060, "bytes_limit": 16_000}
    monkeypatch.setattr(jax, "local_devices", lambda: [Reporting(d, None) for d in devices])
    assert driver._device_memory_stats() is None


def test_evaluate_keeps_existing_placement_of_trained_state(monkeypatch):
    """The state fit() returns (logical-metadata layout, boxes already stripped)
    must be consumed in place by evaluate(): the shardings handed to placement
    are the leaves' EXISTING shardings, not a fresh FSDP resolution — asserted
    by spying on shard_pytree (numerics alone cannot detect a reshard)."""

    class Annotated(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Dense(
                256,
                kernel_init=nn.with_partitioning(nn.initializers.lecun_normal(), ("inp", "hidden")),
            )(x)
            return nn.Dense(2)(nn.relu(x))

    module = Annotated()
    params = module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    state = train_state.TrainState.create(apply_fn=module.apply, params=params, tx=optax.adam(1e-2))
    mesh_spec = MeshSpec(data=2, fsdp=2, model=2)
    result = fit(
        state,
        make_train_step(_loss(module)),
        _make_data(),
        TrainerConfig(
            epochs=1, batch_size=128, mesh=mesh_spec,
            logical_axis_rules=[("hidden", "model"), ("inp", "fsdp")],
        ),
    )
    trained_spec = str(result.state.params["Dense_0"]["kernel"].sharding.spec)
    assert trained_spec == "PartitionSpec('fsdp', 'model')"

    def eval_step(st, batch):
        X, y = batch
        logits = module.apply({"params": st.params}, X)
        return {"accuracy": (jnp.argmax(logits, -1) == y.reshape(-1)).mean()}

    import unionml_tpu.train.driver as driver_mod

    captured = {}
    real_shard_pytree = driver_mod.shard_pytree

    def spying_shard_pytree(pytree, shardings):
        captured["kernel_spec"] = str(shardings.params["Dense_0"]["kernel"].spec)
        return real_shard_pytree(pytree, shardings)

    monkeypatch.setattr(driver_mod, "shard_pytree", spying_shard_pytree)
    # no rules passed at all: existing placement must be honored, not re-derived
    metrics = evaluate(result.state, eval_step, _make_data(), batch_size=128, mesh=mesh_spec)
    assert metrics["accuracy"] > 0.9
    assert captured["kernel_spec"] == trained_spec  # placed onto its OWN sharding


def test_fit_dcn_data_outer_axis_matches_flat_dp():
    """Cross-slice layout: a 2-slice ``dcn_data`` outer axis wrapping an
    intra-slice data*fsdp mesh must train to the same loss trajectory as flat
    DP over the same 8 devices — only the gradient all-reduce spans the outer
    axis, params/optimizer state replicate over it (mesh.py's scaling-book
    recipe)."""
    module, state = _make_state()
    step = make_train_step(_loss(module))
    data = _make_data()

    flat = fit(state, step, data, TrainerConfig(epochs=1, batch_size=128, mesh=MeshSpec(data=-1)))
    _, state2 = _make_state()
    dcn = fit(
        state2,
        step,
        data,
        TrainerConfig(
            epochs=1, batch_size=128,
            mesh=MeshSpec(dcn_data=2, data=2, fsdp=2), fsdp_min_weight_size=256,
        ),
    )
    assert dcn.steps == flat.steps
    np.testing.assert_allclose(
        dcn.history[-1]["loss"], flat.history[-1]["loss"], rtol=1e-4
    )
