"""Pipeline parallelism: GPipe-style microbatch scheduling over a ``pipe`` mesh axis.

The reference has no pipeline parallelism at all (SURVEY.md §2.3 — its "parallelism"
is k8s task scheduling); this is the TPU-native design: instead of per-rank stage
processes exchanging activations over NCCL P2P, the whole pipeline is ONE SPMD
computation. Identical stages are stacked on a leading ``[n_stages, ...]`` parameter
dim sharded over the ``pipe`` mesh axis, and the schedule runs under ``shard_map``:

- each device holds one stage's parameters and, per tick, applies its stage to the
  activation currently resident on it;
- activations rotate stage-to-stage with ``lax.ppermute`` — a neighbor ICI transfer
  that XLA overlaps with the next tick's compute;
- the tick loop is a ``lax.scan`` (statically ``n_microbatches + n_stages - 1`` ticks),
  so the whole schedule — bubbles included — is a single compiled XLA program and is
  reverse-differentiable (backward pipeline = transposed scan + inverse ppermute,
  derived by autodiff rather than hand-scheduled).

Constraints (by construction, documented rather than checked at trace time where
impossible): every stage must map activations ``[mb, ...] -> [mb, ...]`` of identical
shape/dtype (embed before the pipeline, project after), and the global batch must be
divisible by ``n_microbatches``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from unionml_tpu.parallel.collectives import ring_permute
from unionml_tpu.parallel.mesh import BATCH_AXES


def init_stage_params(
    stage_module: Any, rng: jax.Array, sample: jax.Array, n_stages: int
) -> Any:
    """Initialize ``n_stages`` independent copies of a flax stage, stacked on a leading
    stage dim (``vmap`` over per-stage RNGs keeps the tree structure identical to a
    single stage, so per-leaf PartitionSpecs just gain a leading ``"pipe"`` entry)."""
    rngs = jax.random.split(rng, n_stages)
    return jax.vmap(lambda r: stage_module.init(r, sample)["params"])(rngs)


def sequential_stage_apply(stage_fn: Callable[[Any, jax.Array], jax.Array], stage_params: Any, x: jax.Array) -> jax.Array:
    """Reference (non-pipelined) execution of stacked stages: scan over the stage dim.

    Numerically identical to :func:`pipeline_apply`; used on single-device meshes and
    as the correctness oracle in tests.
    """
    def body(h, params_slice):
        return stage_fn(params_slice, h), None

    out, _ = lax.scan(body, x, stage_params)
    return out


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    n_microbatches: int,
    axis: str = "pipe",
    batch_axes: Sequence[str] = BATCH_AXES,
    param_specs: Any = None,
) -> jax.Array:
    """Run stacked stages as a pipelined SPMD computation over ``mesh``.

    :param stage_fn: ``(single_stage_params, activations [mb, ...]) -> activations``,
        shape/dtype-preserving.
    :param stage_params: pytree whose leaves carry a leading ``[n_stages, ...]`` dim,
        placed with ``P("pipe", ...)`` shardings (see :func:`pipeline_rule_table`).
    :param x: global-batch activations ``[B, ...]``; ``B % n_microbatches == 0``.
    :param param_specs: optional pytree of :class:`PartitionSpec` matching
        ``stage_params`` and its actual placement (leading entry must be ``axis``).
        When given, params stay sharded at rest over their intra-stage axes
        (fsdp/model) and each device all-gathers only its own stage's params inside
        the pipeline body — ZeRO-3-style transient materialization instead of a
        whole-tree all-gather at the shard_map boundary. Gradients flow back through
        the gather as reduce-scatter. When ``None``, params must be replicated over
        every axis except ``axis``.
    """
    n_stages = mesh.shape.get(axis, 1)
    if n_stages <= 1:
        return sequential_stage_apply(stage_fn, stage_params, x)

    spec_leaves = None
    if param_specs is not None:
        is_spec = lambda s: s is None or isinstance(s, P)  # noqa: E731
        spec_leaves = [
            s if isinstance(s, P) else P(axis)
            for s in jax.tree_util.tree_leaves(param_specs, is_leaf=is_spec)
        ]
        for spec in spec_leaves:
            first = spec[0] if len(spec) else None
            names = first if isinstance(first, tuple) else (first,)
            if axis not in names:
                raise ValueError(
                    f"stage param spec {spec} does not shard its leading (stage) dim over "
                    f"the '{axis}' axis; stacked stage params must carry P({axis!r}, ...)"
                )

    present_batch = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1) or None
    x_spec = P(present_batch)
    # the microbatch split happens on each device's LOCAL batch shard
    n_batch_shards = 1
    for a in present_batch or ():
        n_batch_shards *= mesh.shape[a]
    local_b, rem = divmod(x.shape[0], n_batch_shards)
    if rem or local_b % n_microbatches:
        raise ValueError(
            f"per-shard batch {x.shape[0]}/{n_batch_shards} not divisible by "
            f"n_microbatches={n_microbatches}"
        )

    def local(params: Any, h: jax.Array) -> jax.Array:
        stage = lax.axis_index(axis)
        # shard_map hands each device its [1, ...] slice of the stacked params
        params = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, axis=0), params)
        if spec_leaves is not None:
            # materialize this stage's full params from their fsdp/model shards
            # (sharded at rest; gathered transiently — the grad is a reduce-scatter)
            leaves, treedef = jax.tree_util.tree_flatten(params)
            gathered = []
            for leaf, spec in zip(leaves, spec_leaves):
                for dim, entry in enumerate(spec[1:]):  # entry i+1 -> dim i after squeeze
                    if entry is None:
                        continue
                    # PartitionSpec tuple sharding is major-axis-first: a dim sharded
                    # P(('fsdp','model')) places shard f*M+m on device (f, m). A tiled
                    # all_gather reconstructs contiguous segments only if the MINOR
                    # axis is gathered first (each device then holds its major-axis
                    # block contiguously), so gather in reversed spec order.
                    for name in reversed(entry if isinstance(entry, tuple) else (entry,)):
                        leaf = lax.all_gather(leaf, name, axis=dim, tiled=True)
                gathered.append(leaf)
            params = jax.tree_util.tree_unflatten(treedef, gathered)
        batch = h.shape[0]
        mb = batch // n_microbatches
        inputs = h.reshape((n_microbatches, mb) + h.shape[1:])
        ticks = n_microbatches + n_stages - 1

        def tick(carry, t):
            cur, outputs = carry
            # stage 0 injects microbatch t (clipped during drain ticks — the result is
            # bubble compute whose output is masked out downstream)
            inp = lax.dynamic_index_in_dim(inputs, jnp.clip(t, 0, n_microbatches - 1), 0, keepdims=False)
            h_in = jnp.where(stage == 0, inp.astype(cur.dtype), cur)
            y = stage_fn(params, h_in)
            # the last stage finishes microbatch t-(S-1) at tick t
            out_idx = t - (n_stages - 1)
            write = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            idx = jnp.clip(out_idx, 0, n_microbatches - 1)
            prev = lax.dynamic_index_in_dim(outputs, idx, 0, keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(outputs, jnp.where(write, y, prev), idx, 0)
            cur = ring_permute(y, axis)
            return (cur, outputs), None

        cur0 = jnp.zeros(inputs.shape[1:], dtype=inputs.dtype)
        out0 = jnp.zeros_like(inputs)
        (_, outputs), _ = lax.scan(tick, (cur0, out0), jnp.arange(ticks))
        # finished microbatches live only on the last stage; a masked psum replicates
        # them over the pipe axis (one all-reduce of the activation tensor per call)
        outputs = jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs))
        outputs = lax.psum(outputs, axis_name=axis)
        return outputs.reshape((batch,) + h.shape[1:])

    if spec_leaves is None:
        params_in_spec: Any = P(axis)
    else:
        leaves_treedef = jax.tree_util.tree_structure(stage_params)
        params_in_spec = jax.tree_util.tree_unflatten(leaves_treedef, spec_leaves)
    wrapped = jax.shard_map(
        local, mesh=mesh, in_specs=(params_in_spec, x_spec), out_specs=x_spec, check_vma=False
    )
    return wrapped(stage_params, x)


def pipeline_rule_table(
    stage_rules: Optional[Sequence[Tuple[str, P]]] = None,
    *,
    prefix: str = r"stages/",
    axis: str = "pipe",
) -> "list[Tuple[str, P]]":
    """Rule table for stacked stage params, composable with a model's other rules:
    each per-stage rule gains a leading ``pipe`` entry (stacked leaves have one extra
    leading dim), plus a ``prefix`` catch-all sharding just the stage dim. Pass the
    result (plus embed/head rules) to :class:`PartitionRules`."""
    rules = []
    for pattern, spec in stage_rules or []:
        # ``.*`` bridge: real paths carry intervening module scopes between the
        # subtree prefix and the per-stage pattern (e.g. stages/layer_0/attn/...)
        rules.append((prefix + r".*" + pattern, P(axis, *spec)))
    rules.append((prefix, P(axis)))
    return rules
