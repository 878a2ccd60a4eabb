"""Persistent XLA compilation cache.

No reference analog (the reference never compiles anything; SURVEY.md §0) — this
is TPU-substrate ergonomics: the first compile of a training step or a serving
bucket costs seconds to minutes, and every new process pays it again. JAX's
persistent compilation cache keys the serialized executable on (HLO, compiler
flags, platform), so re-runs of the same program — a restarted server warming
its buckets, a resubmitted training worker, the next run of ``chip_smoke.py`` —
load in under a second instead.

Where the cache lives (a directory that moves between runs never hits, so the
rule is fixed):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX itself honours it and this package
  sets **no other directory in code**, whatever ``UNIONML_TPU_COMPILE_CACHE``
  or ``serve --compile-cache DIR`` name — those keep only their on/off meaning;
- otherwise an explicit directory (argument, ``UNIONML_TPU_COMPILE_CACHE=<dir>``,
  ``--compile-cache DIR``), else the fixed ``<checkout>/.xla_cache`` resolved
  from this package's own location (git-ignored).

Enabled two ways:

- ``UNIONML_TPU_COMPILE_CACHE=<dir>`` (or ``=1`` for the default location) in the
  environment — honored automatically at package import, so the CLI, job_runner
  workers, and serving processes all pick it up with zero code changes;
- :func:`enable_compile_cache` programmatically.

This cache removes the *XLA-compile* cost of a re-run but still re-traces and
re-lowers every program through the compiler machinery. The serving stack's
AOT program store (:mod:`unionml_tpu.serving.aot`, ``serve --aot-preload``)
sits one layer above it: whole serialized executables keyed per program, so a
cold server/replica/serverless container skips tracing, lowering, AND
compilation — see docs/serving.md "Cold start and AOT preload". The two
compose; ``serve --compile-cache`` re-exports this module's env var for
reload/fork children.
"""

from __future__ import annotations

import os
from typing import Optional

from unionml_tpu._logging import logger

__all__ = ["enable_compile_cache"]

#: the one default location: inside the checkout, the same in every process
_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".xla_cache")
#: env values that mean "on, default location" / "off" rather than a path
_TRUTHY_FLAGS = ("1", "true", "yes", "on")
_FALSY_FLAGS = ("", "0", "false", "no", "off")

#: the directory last logged, so repeated enables stay quiet
_enabled_dir: Optional[str] = None


def _named_dir(value: Optional[str]) -> Optional[str]:
    """``value`` when it names a directory; None for unset and for on/off flags
    ("1" from the env var or ``--compile-cache 1`` means the default location)."""
    if value and value.strip().lower() not in _TRUTHY_FLAGS + _FALSY_FLAGS:
        return value
    return None


def enable_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Turn JAX's persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment that directory is the
    cache and nothing is set in code. Otherwise ``cache_dir`` defaults to
    ``$UNIONML_TPU_COMPILE_CACHE`` (a path, or a truthy flag for the default
    location) and then ``<checkout>/.xla_cache``. Raises ``OSError`` when the
    directory cannot be created.
    """
    global _enabled_dir
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        path = os.path.abspath(from_env)
    else:
        named = _named_dir(cache_dir) or _named_dir(os.environ.get("UNIONML_TPU_COMPILE_CACHE"))
        path = os.path.abspath(os.path.expanduser(named or _DEFAULT_DIR))
    os.makedirs(path, exist_ok=True)
    if not from_env:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    if _enabled_dir != path:
        origin = " (from JAX_COMPILATION_CACHE_DIR)" if from_env else ""
        logger.info(f"persistent XLA compilation cache: {path}{origin}")
        _enabled_dir = path
    return path


def _maybe_enable_from_env() -> None:
    """Package-import hook: honor ``UNIONML_TPU_COMPILE_CACHE`` unless it is an
    explicit off-flag (``0``/``false``/``no``/``off``) — the natural opt-out for
    processes that inherit the var, e.g. from the benchmark suite."""
    if os.environ.get("UNIONML_TPU_COMPILE_CACHE", "").lower() in _FALSY_FLAGS:
        return
    try:
        enable_compile_cache()
    except OSError as exc:  # an unwritable dir must not break import
        logger.warning(f"could not enable the XLA compilation cache: {exc}")
