"""Named TPU device meshes.

Replaces the reference's L1 substrate (flytekit/k8s scheduling, SURVEY.md §1) with the
JAX mesh model: pick a mesh, annotate shardings, let XLA insert collectives.

Axis conventions (all optional, size-1 axes are free):

- ``data``     — data parallelism; gradients all-reduced over this axis.
- ``fsdp``     — parameter/optimizer sharding (ZeRO-3 style); params all-gathered
                 per-layer, gradients reduce-scattered. Batches are sharded over
                 ``("data", "fsdp")`` jointly.
- ``model``    — tensor parallelism; per-layer PartitionSpecs split attention heads
                 and MLP hidden dims.
- ``sequence`` — sequence/context parallelism for long-context (ring attention
                 KV-block rotation rides this axis).
- ``pipe``     — pipeline parallelism; transformer stages are stacked on a leading
                 stage dim sharded here, activations rotate stage-to-stage with
                 ``ppermute`` (:mod:`unionml_tpu.parallel.pipeline`).
- ``expert``   — expert parallelism for MoE layers (token dispatch rides this axis,
                 :mod:`unionml_tpu.models.moe`).

Cross-slice scaling: ``dcn_data`` adds an outer pure-DP axis over DCN so that only
gradient all-reduces cross the slower inter-slice network, as recommended by the
scaling-book recipe.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from unionml_tpu._logging import logger

#: Canonical axis ordering — outermost (slowest-varying, DCN-adjacent) first.
AXIS_ORDER: Tuple[str, ...] = ("dcn_data", "data", "fsdp", "pipe", "sequence", "expert", "model")

#: what ``mesh_utils`` raises for a device set it has no assignment for (a hole
#: in the cuboid, an unsupported shape); anything else is a bug and propagates
_TOPOLOGY_ERRORS = (AssertionError, NotImplementedError, ValueError)

#: Axes over which the batch dimension is sharded.
BATCH_AXES: Tuple[str, ...] = ("dcn_data", "data", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh topology. ``-1`` on at most one axis means "all remaining devices"."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    sequence: int = 1
    pipe: int = 1
    expert: int = 1
    dcn_data: int = 1

    def axis_sizes(self, n_devices: int) -> "dict[str, int]":
        sizes = {
            "dcn_data": self.dcn_data,
            "data": self.data,
            "fsdp": self.fsdp,
            "pipe": self.pipe,
            "sequence": self.sequence,
            "expert": self.expert,
            "model": self.model,
        }
        wildcards = [k for k, v in sizes.items() if v == -1]
        if len(wildcards) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wildcards}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wildcards:
            if n_devices % fixed:
                raise ValueError(f"{n_devices} devices not divisible by fixed axes product {fixed}")
            sizes[wildcards[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(f"mesh axes product {fixed} != device count {n_devices}")
        return sizes

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        """Materialize a :class:`jax.sharding.Mesh` over ``devices``.

        Uses :func:`jax.experimental.mesh_utils.create_device_mesh` so the ``model``
        (innermost) axis lands on physically adjacent chips and rides ICI.
        """
        devices = list(jax.devices()) if devices is None else list(devices)
        sizes = self.axis_sizes(len(devices))
        shape = tuple(sizes[name] for name in AXIS_ORDER)
        try:
            device_array = mesh_utils.create_device_mesh(shape, devices=devices)
        except _TOPOLOGY_ERRORS as exc:
            # e.g. a replica submesh that is not a contiguous cuboid of the slice
            logger.warning(
                f"no topology-aware layout for mesh {shape} over {len(devices)} devices "
                f"({type(exc).__name__}: {exc}); using id order — collectives may leave ICI neighbours"
            )
            device_array = np.asarray(devices).reshape(shape)
        return Mesh(device_array, AXIS_ORDER)

    def build_hybrid(
        self,
        devices: Optional[Sequence[jax.Device]] = None,
        dcn_axes: Optional[Sequence[str]] = None,
    ) -> Mesh:
        """Materialize a HYBRID ICI/DCN mesh over a multi-process runtime
        (the T5X ``create_hybrid_device_mesh`` shape): the ``dcn_axes``
        (slow, cross-host axes — the data/replica axes by convention) span
        processes over DCN while every other axis stays within one host's
        ICI-connected devices. ``dcn_axes=None`` picks outermost batch axes
        greedily until their product covers the process count — for a
        serving fleet that is ``dcn_data`` (or ``data``), exactly the
        per-replica split :func:`unionml_tpu.serving.replicas.slice_mesh`
        cuts along, so each host's replicas are host-local by construction.
        Falls back to a process-grouped reshape, with a warning that names
        the reason, when ``mesh_utils`` cannot build the topology."""
        devices = list(jax.devices()) if devices is None else list(devices)
        sizes = self.axis_sizes(len(devices))
        n_processes = len({d.process_index for d in devices})
        if dcn_axes is None:
            dcn_axes, extent = [], 1
            for name in AXIS_ORDER:
                if extent >= n_processes:
                    break
                if sizes[name] > 1:
                    dcn_axes.append(name)
                    extent *= sizes[name]
            if extent != n_processes:
                raise ValueError(
                    f"cannot cover {n_processes} processes with leading batch axes "
                    f"(sizes {sizes}); pass dcn_axes= explicitly"
                )
        dcn_axes = tuple(dcn_axes)
        unknown = [name for name in dcn_axes if name not in AXIS_ORDER]
        if unknown:
            raise ValueError(f"unknown dcn axes {unknown}; expected a subset of {AXIS_ORDER}")
        ici_shape = tuple(1 if name in dcn_axes else sizes[name] for name in AXIS_ORDER)
        dcn_shape = tuple(sizes[name] if name in dcn_axes else 1 for name in AXIS_ORDER)
        try:
            device_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=devices, process_is_granule=True
            )
        except _TOPOLOGY_ERRORS as exc:
            # group by process (the granule), keep process-id order on the DCN
            # dims so the mesh is deterministic across every process building it
            logger.warning(
                f"no topology-aware hybrid layout for ici {ici_shape} x dcn {dcn_shape} "
                f"({type(exc).__name__}: {exc}); grouping devices by process in id order"
            )
            ordered = sorted(devices, key=lambda d: (d.process_index, d.id))
            device_array = np.asarray(ordered).reshape(dcn_shape + ici_shape)
            # interleave [dcn..., ici...] -> AXIS_ORDER: dim i of the final
            # mesh is dcn dim i times ici dim i (one of the two is 1)
            n = len(AXIS_ORDER)
            perm = [axis for pair in zip(range(n), range(n, 2 * n)) for axis in pair]
            device_array = device_array.transpose(perm).reshape(
                tuple(sizes[name] for name in AXIS_ORDER)
            )
        return Mesh(device_array, AXIS_ORDER)

    @property
    def num_devices_required(self) -> int:
        sizes = [self.data, self.fsdp, self.model, self.sequence, self.pipe, self.expert, self.dcn_data]
        if any(s == -1 for s in sizes):
            return -1
        return math.prod(sizes)


def process_local_submeshes(submeshes: Sequence[Mesh]) -> "list[Tuple[int, Mesh]]":
    """Filter a :func:`~unionml_tpu.serving.replicas.slice_mesh` result down to
    the submeshes THIS process can drive: ``(global_index, submesh)`` pairs
    whose devices are all local. On a hybrid ICI/DCN mesh with the replica
    axes on DCN every submesh is single-host, so the pairs partition the
    fleet across processes with stable global indices — the coordinator's
    host ids."""
    import jax

    me = jax.process_index()
    out = []
    for index, sub in enumerate(submeshes):
        procs = {d.process_index for d in np.asarray(sub.devices).ravel()}
        if procs == {me}:
            out.append((index, sub))
    return out


def single_device_mesh() -> Mesh:
    """A 1-device mesh with the full axis set — lets all sharding code paths run
    unchanged on one chip (every axis has size 1 except ``data``)."""
    return MeshSpec(data=1).build(devices=jax.devices()[:1])
