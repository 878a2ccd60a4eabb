"""One jax.distributed bootstrap shared by train and serve.

Extracted from ``job_runner._maybe_init_distributed`` (which now delegates
here) so the serving fleet's worker processes (serving/cluster.py) join a
multi-process JAX runtime through exactly the code path the training
watchdog ring already pins: ``UNIONML_TPU_COORDINATOR`` names the rendezvous,
``UNIONML_TPU_NUM_PROCESSES``/``UNIONML_TPU_PROCESS_ID`` place this process,
and with the env unset every helper degrades to single-process no-ops — the
same code runs unchanged on one host.

On top of the bootstrap sit the small cross-host agreement primitives the
fleet coordinator needs (SNIPPETS.md's T5X ``multihost_utils`` shape):
:func:`barrier` fences every process at a named point, :func:`agree`
broadcasts process 0's JSON-able config so all hosts provably build the same
fleet, and :func:`allgather_ints` exchanges one small integer per process
(the control-plane port exchange). All three are collectives — EVERY process
of the runtime must call them, and none may be called while holding a lock
(tpu-lint TPU013: one stalled host would deadlock the whole fleet).
"""

from __future__ import annotations

import json
import os
from typing import Any, List, Optional

import numpy as np

from unionml_tpu._logging import logger
from unionml_tpu.defaults import (
    distributed_coordinator,
    distributed_num_processes,
    distributed_process_id,
)

__all__ = [
    "agree",
    "allgather_ints",
    "barrier",
    "is_initialized",
    "maybe_initialize",
    "process_count",
    "process_index",
]

#: set by :func:`maybe_initialize` so repeated calls (job_runner then an app
#: module that also bootstraps) are idempotent instead of a jax RuntimeError
_initialized = False


def is_initialized() -> bool:
    """Whether THIS module initialized the jax.distributed runtime."""
    return _initialized


def maybe_initialize() -> bool:
    """Join the jax.distributed runtime named by the env, if any.

    Returns True when this process is now part of a multi-process runtime
    (idempotently: a second call is a no-op), False when the env names no
    coordinator — the single-process mode every caller must tolerate. Reads
    the knobs through the defaults.py warn-and-degrade readers, so a typo'd
    deployment env degrades to single-process instead of crashing the
    bootstrap."""
    global _initialized
    coordinator = distributed_coordinator()
    if not coordinator:
        return False
    if _initialized:
        return True
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # emulated multi-host lane: CROSS-PROCESS computations on the CPU
        # backend need the gloo collectives implementation picked before the
        # backend forms — without it every multiprocess dispatch
        # (multihost_utils broadcasts included) fails with "Multiprocess
        # computations aren't implemented on the CPU backend"
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    num_processes = distributed_num_processes()
    process_id = distributed_process_id()
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    # the definitive signal that the slice formed: this process sees every
    # device of every peer (watchdog tests assert on this line)
    logger.info(
        f"joined jax.distributed runtime: process {process_id}/{num_processes}, "
        f"global devices {jax.device_count()} ({jax.local_device_count()} local)"
    )
    return True


def process_index() -> int:
    """This process's index: jax's own once a runtime exists, else the env
    reader (so a worker can self-identify before/without initializing)."""
    if _initialized:
        import jax

        return int(jax.process_index())
    return distributed_process_id()


def process_count() -> int:
    """Total processes in the runtime (1 single-process)."""
    if _initialized:
        import jax

        return int(jax.process_count())
    return distributed_num_processes()


def barrier(name: str) -> None:
    """Fence every process of the runtime at a named sync point (a no-op
    single-process). A COLLECTIVE: never call it while holding a lock —
    a peer stuck elsewhere turns the lock into a fleet-wide deadlock
    (tpu-lint TPU013)."""
    if not _initialized:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def agree(obj: Any) -> Any:
    """Cross-host agreement on a small JSON-able value: every process returns
    PROCESS 0's ``obj`` — the fleet-config handshake (engine knobs, scale
    transitions) that guarantees knob-identical engines on every host.
    Single-process: returns ``obj`` unchanged. A COLLECTIVE (two
    ``broadcast_one_to_all`` rounds: length, then padded payload) — every
    process must call it, and never under a lock (TPU013)."""
    if not _initialized or process_count() == 1:
        return obj
    from jax.experimental import multihost_utils

    payload = json.dumps(obj, sort_keys=True).encode() if process_index() == 0 else b""
    length = int(
        multihost_utils.broadcast_one_to_all(np.int32(len(payload)))
    )
    # byte values ride as int32: broadcast_one_to_all widens small dtypes in
    # flight, so an int32 buffer round-trips exactly on every jax version
    buf = np.zeros((max(length, 1),), np.int32)
    buf[: len(payload)] = np.frombuffer(payload, np.uint8)
    buf = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    return json.loads(bytes(buf[:length].astype(np.uint8)).decode())


def allgather_ints(value: int) -> "List[int]":
    """Exchange one small integer per process (index order) — the fleet's
    control-plane port exchange. Single-process: ``[value]``. A COLLECTIVE:
    same never-under-a-lock contract as :func:`barrier` (TPU013)."""
    if not _initialized or process_count() == 1:
        return [int(value)]
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(np.asarray([int(value)], np.int64))
    return [int(v) for v in np.asarray(gathered).ravel()]
