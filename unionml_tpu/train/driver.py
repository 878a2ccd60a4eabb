"""The pjit train-step driver: compile a (state, batch) -> (state, metrics) step over a
named mesh and run the donate-and-loop epoch schedule.

This layer is what the reference outsources wholesale to the user's ML framework inside
a Flyte task (reference unionml/model.py:425-440 simply calls
``self._trainer(model_object, *train_data)`` once, eagerly). Here the contract is
step-based so the whole hot loop is XLA:

- The user (or a model-library preset) supplies ``step_fn(state, batch) -> (state,
  metrics)``; :func:`make_train_step` builds the canonical one from a loss function.
- :func:`fit` constructs the mesh, resolves parameter shardings (explicit TP rules +
  inferred FSDP, :mod:`unionml_tpu.parallel.sharding`), compiles the step with
  ``jax.jit(donate_argnums=0, in_shardings=..., out_shardings=...)``, and loops over a
  host->HBM prefetch iterator. Buffer donation means the optimizer update is in-place
  in HBM; XLA inserts all the DP/FSDP collectives implied by the shardings.

Auxiliary subsystems the reference lacks (SURVEY.md §5): per-step profiler annotations,
step-level orbax checkpointing with resume, NaN guards, and a throughput metrics sink.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from unionml_tpu._logging import logger
from unionml_tpu.parallel.mesh import MeshSpec
from unionml_tpu.parallel.sharding import (
    PartitionRules,
    batch_sharding,
    combine_fsdp_tp,
    shard_pytree,
    unbox_partitioned,
)


@dataclasses.dataclass
class TrainerConfig:
    """Execution config attached to a step-mode ``@model.trainer``.

    This is the TPU analog of the reference's per-task kwargs (``requests``/``limits``
    resources, unionml/model.py:227) — but instead of k8s pod sizes it declares the
    compilation/measurement envelope of the training loop.
    """

    epochs: int = 1
    batch_size: int = 32
    mesh: Optional[MeshSpec] = None
    partition_rules: Optional[PartitionRules] = None
    #: t5x-style (logical_name, mesh_axis) pairs resolving flax
    #: ``nn.with_partitioning`` metadata; None = Partitioned names ARE mesh axes
    logical_axis_rules: "Optional[Sequence[Tuple[str, Any]]]" = None
    fsdp_min_weight_size: int = 2**14
    grad_accum_steps: int = 1
    donate: bool = True
    shuffle: bool = True
    seed: int = 0
    drop_remainder: bool = True
    prefetch: int = 2
    shard_batch_by_process: bool = False
    #: keep the whole split resident in HBM and gather batches on-device by index —
    #: per-step host->device traffic drops to the index vector (right for datasets
    #: that fit in HBM; essential when the host link is high-latency)
    device_data: bool = False
    #: with device_data, run this many optimizer steps per compiled dispatch via
    #: lax.scan — amortizes host round-trip latency over K steps
    steps_per_call: int = 1
    # checkpoint / resume (step-level; the reference only has final-artifact save)
    checkpoint_dir: Optional[str] = None
    checkpoint_every_steps: int = 0
    max_checkpoints_to_keep: int = 3
    resume: bool = False
    # observability
    log_every_steps: int = 0
    profile_dir: Optional[str] = None
    profile_steps: Tuple[int, int] = (10, 15)
    # debug: the TPU analog of a race detector is donation/NaN misuse (SURVEY.md §5.2)
    debug_nans: bool = False
    debug_disable_donation: bool = False


@dataclasses.dataclass
class FitResult:
    state: Any
    history: List[Dict[str, float]]
    steps: int
    samples_per_sec: float
    samples_per_sec_per_chip: float
    compile_time_s: float
    #: per-device HBM accounting after the final step (SURVEY.md §5.5 metrics
    #: sink commitment): ``{"bytes_in_use": ..., "peak_bytes_in_use": ...}`` from
    #: device 0, or None when the backend exposes no memory stats (CPU)
    memory_stats: Optional[Dict[str, int]] = None


def _device_memory_stats() -> Optional[Dict[str, int]]:
    """Byte counters of the FULLEST local device (the one whose peak use was
    highest): on a sharded run the devices differ — uneven shards, a replicated
    array one device materializes — and the one nearest its limit is the one
    that decides whether the run fits."""
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_alloc_size")
    fullest: Optional[Dict[str, int]] = None
    for device in jax.local_devices():
        try:
            stats = device.memory_stats()
        except Exception:
            continue
        filtered = {k: int(v) for k, v in (stats or {}).items() if k in keep}
        if filtered and (
            fullest is None
            or filtered.get("peak_bytes_in_use", 0) > fullest.get("peak_bytes_in_use", 0)
        ):
            fullest = filtered
    return fullest  # None where no device reports byte counters (the CPU backend)


def make_train_step(
    loss_fn: Callable[..., Any],
    *,
    has_aux: bool = False,
    grad_accum_steps: int = 1,
    remat: bool = False,
) -> Callable[[Any, Any], Tuple[Any, Dict[str, jax.Array]]]:
    """Build the canonical ``(state, batch) -> (state, metrics)`` step from a loss fn.

    ``loss_fn(params, batch, rngs...)`` -> loss (or ``(loss, aux_dict)`` with
    ``has_aux=True``). ``state`` must expose ``params`` and ``apply_gradients`` (the
    flax ``TrainState`` protocol). Gradient accumulation runs microbatches under
    ``lax.scan`` so the unrolled loop stays a single XLA computation; ``remat``
    checkpoints the loss computation to trade FLOPs for HBM.
    """
    base_loss = jax.checkpoint(loss_fn) if remat else loss_fn
    grad_fn = jax.value_and_grad(base_loss, has_aux=has_aux)

    def single_step(state: Any, batch: Any) -> Tuple[Any, Dict[str, jax.Array]]:
        if has_aux:
            (loss, aux), grads = grad_fn(state.params, batch)
        else:
            loss, grads = grad_fn(state.params, batch)
            aux = {}
        state = state.apply_gradients(grads=grads)
        return state, {"loss": loss, **aux}

    if grad_accum_steps <= 1:
        return single_step

    def accum_step(state: Any, batch: Any) -> Tuple[Any, Dict[str, jax.Array]]:
        # Shardings pinned by fit() (see _pin_accum_shardings): the scan carry
        # follows the param layout and the reshaped microbatch stack keeps the
        # batch layout, instead of leaving both to partitioner inference. The
        # round-4 "Involuntary full rematerialization" in this loop turned out
        # to be the embed scatter-add (fixed at its root in layers.IotaEmbed);
        # the pins make the intended layouts explicit so a future inference
        # change cannot silently reintroduce a per-microbatch reshard — the
        # dryrun asserts the SPMD log stays warning-free either way.
        param_sh, micro_sh, micro_div = accum_step.pinned_shardings

        def pin_grads(tree: Any) -> Any:
            if param_sh is None:
                return tree
            return jax.lax.with_sharding_constraint(tree, param_sh)

        def split(leaf: jax.Array) -> jax.Array:
            b = leaf.shape[0]
            micro = leaf.reshape((grad_accum_steps, b // grad_accum_steps) + leaf.shape[1:])
            # pin only when the microbatch dim divides evenly over the batch
            # axes — the indivisible-final-batch fallback arrives replicated
            if micro_sh is not None and micro.shape[1] % micro_div == 0:
                micro = jax.lax.with_sharding_constraint(micro, micro_sh)
            return micro

        microbatches = jax.tree_util.tree_map(split, batch)

        def body(carry, microbatch):
            grads_acc, loss_acc = carry
            if has_aux:
                (loss, aux), grads = grad_fn(state.params, microbatch)
            else:
                loss, grads = grad_fn(state.params, microbatch)
                aux = {}
            grads_acc = pin_grads(jax.tree_util.tree_map(jnp.add, grads_acc, grads))
            return (grads_acc, loss_acc + loss), aux

        zeros = pin_grads(jax.tree_util.tree_map(jnp.zeros_like, state.params))
        (grads, loss_sum), aux_stacked = jax.lax.scan(body, (zeros, jnp.zeros(())), microbatches)
        grads = jax.tree_util.tree_map(lambda g: g / grad_accum_steps, grads)
        new_state = state.apply_gradients(grads=grads)
        aux_mean = jax.tree_util.tree_map(lambda a: a.mean(axis=0), aux_stacked)
        return new_state, {"loss": loss_sum / grad_accum_steps, **aux_mean}

    accum_step.pinned_shardings = (None, None, 1)
    return accum_step


def _pin_accum_shardings(step_fn: Any, state_shardings: Any, mesh) -> None:
    """If ``step_fn`` is a grad-accumulation step from :func:`make_train_step`,
    pin its scan-carry gradient shardings to the param shardings and its
    microbatch stack to ``P(None, *batch_spec)`` so the partitioner cannot
    choose a conflicting layout inside the scan (re-read at each trace, so one
    step_fn reused across fits on different meshes re-pins correctly)."""
    if not hasattr(step_fn, "pinned_shardings"):
        return
    try:
        param_sh = state_shardings.params
    except AttributeError:  # state without a .params subtree: skip the carry pin
        param_sh = None
    from unionml_tpu.parallel.sharding import batch_axis_size

    batch_sh = batch_sharding(mesh)
    micro_spec = jax.sharding.PartitionSpec(None, *batch_sh.spec)
    micro_sh = jax.sharding.NamedSharding(mesh, micro_spec)
    step_fn.pinned_shardings = (param_sh, micro_sh, batch_axis_size(mesh))


def _tree_device_shardings(state: Any, mesh, rules: Optional[PartitionRules], min_weight: int, logical_rules=None):
    return combine_fsdp_tp(state, mesh, rules, min_weight_size=min_weight, logical_rules=logical_rules)


def _make_checkpoint_manager(config: TrainerConfig):
    if not config.checkpoint_dir or config.checkpoint_every_steps <= 0:
        return None
    import orbax.checkpoint as ocp

    options = ocp.CheckpointManagerOptions(
        max_to_keep=config.max_checkpoints_to_keep,
        enable_async_checkpointing=True,
    )
    return ocp.CheckpointManager(config.checkpoint_dir, options=options)


def fit(
    state: Any,
    step_fn: Callable[[Any, Any], Tuple[Any, Dict[str, jax.Array]]],
    data: Any,
    config: TrainerConfig,
) -> FitResult:
    """Compile ``step_fn`` over the configured mesh and run the training loop.

    :param state: initial train state pytree (e.g. ``flax.training.train_state.TrainState``).
    :param data: per-split data list (``[features, targets, ...]``) from
        :meth:`unionml_tpu.dataset.Dataset.get_data`, or any pytree of arrays with a
        shared leading sample dim.
    """
    from unionml_tpu.data.pipeline import PrefetchIterator

    mesh = (config.mesh or MeshSpec()).build()
    n_chips = mesh.size

    with mesh:
        state_shardings = _tree_device_shardings(
            state, mesh, config.partition_rules, config.fsdp_min_weight_size, config.logical_axis_rules
        )
        # flax nn.with_partitioning metadata has been consumed into the shardings;
        # train on the raw value tree
        state = unbox_partitioned(state)
        state = shard_pytree(state, state_shardings)
        batch_sh = batch_sharding(mesh)
        _pin_accum_shardings(step_fn, state_shardings, mesh)

        donate = (0,) if (config.donate and not config.debug_disable_donation) else ()
        # batch in_sharding is left unconstrained: batches arrive pre-placed by the
        # prefetch iterator (data-axis sharded normally, replicated for indivisible
        # final partial batches), and constraining it here would reject the fallback
        compiled_step = jax.jit(
            step_fn,
            donate_argnums=donate,
            in_shardings=(state_shardings, None),
            out_shardings=(state_shardings, None),
        )

        manager = _make_checkpoint_manager(config)
        start_step = 0
        if manager is not None and config.resume:
            latest = manager.latest_step()
            if latest is not None:
                import orbax.checkpoint as ocp

                abstract = jax.tree_util.tree_map(
                    lambda x, s: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x), sharding=s),
                    state,
                    state_shardings,
                )
                state = manager.restore(latest, args=ocp.args.StandardRestore(abstract))
                start_step = latest
                logger.info(f"resumed train state from checkpoint step {latest}")

        if config.device_data:
            if jax.process_count() > 1:
                # Multi-process device_data: every process computes the same host
                # data (seeded readers — the multi-host contract), and
                # place_global_array materializes only this process's addressable
                # row-shards, so per-process HBM holds 1/process_count of the
                # dataset. The epoch permute and dynamic_slice batch selection run
                # inside jit over the global array — SPMD, XLA inserts the
                # resharding collectives. shard_batch_by_process is therefore
                # implied (the global array IS process-sharded); the flag only
                # changes the host-batching path.
                logger.info(
                    f"device_data over {jax.process_count()} processes: dataset "
                    "globally sharded, per-process HBM holds its row-shards only"
                )
            if not config.drop_remainder:
                logger.info(
                    "device_data mode always drops the partial final batch (fixed-shape "
                    "dynamic_slice); drop_remainder=False is ignored"
                )
            # whole split resident in HBM; per-step H2D traffic = the index vector only
            source = PrefetchIterator(
                data,
                batch_size=config.batch_size,
                sharding=None,
                drop_remainder=True,  # fixed-shape dynamic_slice; partials never scheduled
                shuffle=config.shuffle,
                seed=config.seed,
                prefetch=0,
                epochs=config.epochs,
                skip_batches=start_step,
            )
            host_tree = jax.tree_util.tree_unflatten(source._treedef, source._leaves)
            from unionml_tpu.parallel.sharding import place_global_array

            try:
                data_dev = jax.tree_util.tree_map(lambda leaf: place_global_array(leaf, batch_sh), host_tree)
            except Exception:
                data_dev = jax.device_put(host_tree)
            jax.block_until_ready(data_dev)  # keep the (possibly multi-second) H2D out of the timed loop

            # shuffling = ONE on-device permutation per epoch; batches are then
            # contiguous dynamic slices — ~2 orders of magnitude faster than a
            # per-step arbitrary-index gather over the full table
            permute = jax.jit(
                lambda dataset, perm: jax.tree_util.tree_map(lambda leaf: jnp.take(leaf, perm, axis=0), dataset)
            )

            def slice_scan_step(state: Any, dataset: Any, starts: jax.Array):
                # starts: [K] — K optimizer steps in one dispatch; lax.scan keeps it a
                # single XLA computation, so host round-trip cost is paid once per K
                def body(st, start):
                    batch = jax.tree_util.tree_map(
                        lambda leaf: jax.lax.dynamic_slice_in_dim(leaf, start, config.batch_size, 0), dataset
                    )
                    return step_fn(st, batch)

                state, metrics_seq = jax.lax.scan(body, state, starts)
                return state, jax.tree_util.tree_map(lambda m: m[-1], metrics_seq)

            compiled_gather = jax.jit(
                slice_scan_step,
                donate_argnums=donate,
                in_shardings=(state_shardings, None, None),
                out_shardings=(state_shardings, None),
            )

            steps_per_call = max(1, min(config.steps_per_call, source.steps_per_epoch() or 1))

            def payloads():
                current_epoch = -1
                epoch_data = data_dev
                group: List[int] = []

                def flush(epoch_data, group):
                    # partial trailing groups run as a smaller dispatch (one extra
                    # compile per distinct size) rather than being silently dropped
                    return (epoch_data, jnp.asarray(group, dtype=jnp.int32)), config.batch_size * len(group), len(
                        group
                    )

                # the schedule only emits full batches (the source is built with
                # drop_remainder=True, so steps_per_epoch floors)
                for epoch, lo, _size in source.contiguous_schedule():
                    if epoch != current_epoch:
                        if group:
                            yield flush(epoch_data, group)
                            group = []
                        # release the previous epoch's permuted copy BEFORE building the
                        # next one — together with the fit loop dropping its payload
                        # reference each step, peak HBM stays at 2x the dataset
                        # (base + one permuted copy), not 3x
                        epoch_data = None
                        epoch_data = (
                            permute(data_dev, jnp.asarray(source._epoch_order(epoch)))
                            if config.shuffle
                            else data_dev
                        )
                        current_epoch = epoch
                    group.append(lo)
                    if len(group) == steps_per_call:
                        yield flush(epoch_data, group)
                        group = []
                if group:
                    yield flush(epoch_data, group)

            def run_step(state: Any, payload: Any):
                epoch_data, starts = payload
                return compiled_gather(state, epoch_data, starts)

        else:
            iterator = PrefetchIterator(
                data,
                batch_size=config.batch_size,
                sharding=batch_sh,
                drop_remainder=config.drop_remainder,
                shuffle=config.shuffle,
                seed=config.seed,
                prefetch=config.prefetch,
                shard_by_process=config.shard_batch_by_process,
                epochs=config.epochs,
                skip_batches=start_step,  # resume reproduces the seeded schedule, minus consumed batches
            )

            def payloads():
                for batch in iterator:
                    yield batch, int(jax.tree_util.tree_leaves(batch)[0].shape[0]), 1

            def run_step(state: Any, payload: Any):
                return compiled_step(state, payload)

        history: List[Dict[str, float]] = []
        step_idx = start_step  # number of completed optimizer steps
        compile_time = 0.0
        samples_seen = 0
        first_batch_samples = 0
        loop_start: Optional[float] = None
        last_metrics: Any = None
        trace_active = False

        # XLA:CPU emulated-mesh collectives run an in-process rendezvous across one
        # thread per "device"; with async dispatch piling up executions on a small
        # host (this box: nproc=1), participants starve past the 40 s rendezvous
        # termination timeout and the runtime hard-aborts the process. Serialize
        # dispatch there — a per-step fence costs nothing on an already-CPU-bound
        # test backend. Real TPU keeps the async pipeline.
        serialize_dispatch = jax.default_backend() == "cpu" and mesh.size > 1

        prev_debug_nans = jax.config.jax_debug_nans
        if config.debug_nans:
            jax.config.update("jax_debug_nans", True)
        try:
            for payload, batch_n, steps_in_payload in payloads():
                # triggers use crossing semantics: step_idx may advance in strides of
                # steps_per_call, so equality / modulo tests would silently never fire
                if config.profile_dir and not trace_active and step_idx >= config.profile_steps[0]:
                    jax.profiler.start_trace(config.profile_dir)
                    trace_active = True
                with jax.profiler.TraceAnnotation("unionml_tpu.train_step"):
                    if loop_start is None:
                        t0 = time.perf_counter()
                        state, last_metrics = run_step(state, payload)
                        jax.block_until_ready(last_metrics)
                        compile_time = time.perf_counter() - t0
                        loop_start = time.perf_counter()
                        first_batch_samples = batch_n
                    else:
                        state, last_metrics = run_step(state, payload)
                        if serialize_dispatch:
                            jax.block_until_ready(last_metrics)
                # drop the payload reference before the generator's next epoch-boundary
                # permute runs — otherwise the old permuted copy stays live and peak
                # HBM hits 3x the dataset in device_data mode
                payload = None
                prev_step = step_idx
                step_idx += steps_in_payload
                samples_seen += batch_n
                if config.log_every_steps and (
                    step_idx // config.log_every_steps > prev_step // config.log_every_steps
                ):
                    host_metrics = {k: float(v) for k, v in last_metrics.items()}
                    history.append({"step": step_idx, **host_metrics})
                    logger.info(f"step {step_idx}: {host_metrics}")
                if manager is not None and config.checkpoint_every_steps and (
                    step_idx // config.checkpoint_every_steps > prev_step // config.checkpoint_every_steps
                ):
                    import orbax.checkpoint as ocp

                    manager.save(step_idx, args=ocp.args.StandardSave(state))
                if config.profile_dir and trace_active and step_idx > config.profile_steps[1]:
                    jax.profiler.stop_trace()
                    trace_active = False
        finally:
            if trace_active:
                jax.profiler.stop_trace()
            if config.debug_nans:
                jax.config.update("jax_debug_nans", prev_debug_nans)

        if last_metrics is not None:
            jax.block_until_ready(last_metrics)
            host_metrics = {k: float(v) for k, v in last_metrics.items()}
            if not history or history[-1].get("step") != step_idx:
                history.append({"step": step_idx, **host_metrics})

        if manager is not None:
            import orbax.checkpoint as ocp

            if manager.latest_step() != step_idx:
                manager.save(step_idx, args=ocp.args.StandardSave(state), force=True)
            manager.wait_until_finished()

        post_compile_samples = samples_seen - first_batch_samples
        elapsed = (time.perf_counter() - loop_start) if loop_start is not None else 0.0
        sps = post_compile_samples / elapsed if elapsed > 0 and post_compile_samples > 0 else 0.0

    return FitResult(
        state=state,
        history=history,
        steps=step_idx - start_step,
        samples_per_sec=sps,
        samples_per_sec_per_chip=sps / max(n_chips, 1),
        compile_time_s=compile_time,
        memory_stats=_device_memory_stats(),
    )


def evaluate(
    state: Any,
    eval_step: Callable[[Any, Any], Dict[str, jax.Array]],
    data: Any,
    *,
    batch_size: int = 128,
    mesh: Optional[MeshSpec] = None,
    partition_rules: Optional[PartitionRules] = None,
    fsdp_min_weight_size: int = 2**14,
    logical_axis_rules: "Optional[Sequence[Tuple[str, Any]]]" = None,
) -> Dict[str, float]:
    """Run a jitted eval step over a split and average the metrics.

    A state leaf that already lives on an equal mesh keeps its placement (the
    state ``fit`` returns is consumed in place — no per-split reshard, even for
    layouts that came from since-unboxed ``nn.Partitioned`` metadata); host
    leaves are placed via the same resolution the train driver uses (logical
    metadata + explicit TP rules + inferred FSDP).
    """
    from jax.sharding import NamedSharding

    from unionml_tpu.data.pipeline import PrefetchIterator

    built = (mesh or MeshSpec()).build()
    with built:
        resolved = _tree_device_shardings(
            state, built, partition_rules, fsdp_min_weight_size, logical_axis_rules
        )
        state = unbox_partitioned(state)

        def keep_or_resolve(leaf: Any, fallback: Any) -> Any:
            existing = getattr(leaf, "sharding", None)
            if isinstance(existing, NamedSharding) and existing.mesh == built:
                return existing
            return fallback

        state_shardings = jax.tree_util.tree_map(keep_or_resolve, state, resolved)
        state = shard_pytree(state, state_shardings)
        batch_sh = batch_sharding(built)
        # batch in_sharding stays unconstrained: the final partial batch arrives
        # replicated when its size does not divide the data axis
        compiled = jax.jit(eval_step, in_shardings=(state_shardings, None))
        totals: Dict[str, float] = {}
        count = 0
        for batch in PrefetchIterator(data, batch_size=batch_size, sharding=batch_sh, drop_remainder=False):
            metrics = compiled(state, batch)
            n = jax.tree_util.tree_leaves(batch)[0].shape[0]
            for k, v in metrics.items():
                totals[k] = totals.get(k, 0.0) + float(v) * n
            count += n
    return {k: v / max(count, 1) for k, v in totals.items()}
