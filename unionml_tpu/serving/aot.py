"""AOT program store: serialized XLA executables preloaded at serve start.

BENCH_ALL.json records an 87.6 s BERT-base compile against a 0.14 s train
step — and every freshly started server, every ``scale_to`` scale-up replica,
and every serverless cold start used to pay that compile before its first
token. JAX's persistent compilation cache (compile_cache.py) removes the
*XLA-compile* cost of a re-run but still re-traces, re-lowers, and round-trips
every program through the compiler's cache machinery; nothing in the serving
stack ahead-of-time serialized the generator's *executables* so a cold process
could skip the whole pipeline.

This module is that missing layer:

- :class:`ProgramStore` — a directory of serialized executables
  (``jax.experimental.serialize_executable``), one entry per
  (program, backend, mesh, config, argument-signature) key. Entries carry a
  human-readable meta sidecar; corrupted or stale entries are skipped (and
  deleted) with a warning, never crash the serving path.
- :class:`AOTFunction` — a drop-in wrapper for a ``jax.jit`` binding that
  resolves every distinct call signature **load-before-compile**: an
  in-memory executable, else a store entry (deserialize, ~ms), else
  ``lower().compile()`` — whose result is serialized back into the store so
  the *next* cold process loads it. Backends whose executables cannot be
  serialized degrade to plain jit behavior with a single warning.

Keying: executables are pinned to the devices they were compiled for (the
PjRt device assignment rides the serialized artifact), so the key covers the
jax/jaxlib versions, backend platform, device kinds **and ids**, the mesh's
axis names + shape, the generator's module/generation configs (quantize and
kv-cache dtype included), and the abstract argument signature. A restarted
server, a serverless warm pool, or a ``scale_to`` replica landing on a
previously-used submesh all hit; a never-seen topology misses once, compiles,
and persists for every process after it. ``serve --aot-preload [DIR]``
(``UNIONML_TPU_AOT_PRELOAD``) turns the store on fleet-wide; see
docs/serving.md "Cold start and AOT preload".
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, Optional, Tuple

from unionml_tpu._logging import logger
from unionml_tpu.serving.metrics import LatencyWindow

__all__ = ["AOTFunction", "ProgramStore", "resolve_store"]

#: default store location (next to the persistent XLA cache's default)
_DEFAULT_DIR = "~/.cache/unionml_tpu/aot"

#: store format version: bumping it orphans (never breaks) old entries
_FORMAT = 1


def backend_context() -> Dict[str, Any]:
    """The process-level key parts every entry depends on: serialized
    executables are only loadable by the jax/jaxlib/backend that wrote them."""
    import jax

    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", "unknown")
    except ImportError:  # pragma: no cover - jaxlib always rides jax
        jaxlib_version = "unknown"
    devices = jax.devices()
    return {
        "format": _FORMAT,
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def mesh_context(mesh: Optional[Any]) -> Dict[str, Any]:
    """Mesh key parts: axis names, per-axis extents, and the device ids —
    a deserialized executable re-binds devices BY ID, so an entry compiled
    for one submesh must never load onto a different one."""
    if mesh is None:
        return {"mesh": None}
    return {
        "mesh": {
            "axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "device_ids": [int(d.id) for d in mesh.devices.flat],
        }
    }


def _leaf_signature(leaf: Any) -> Tuple:
    """One argument leaf's contribution to the entry key: shape/dtype/weak-type
    for arrays, the bare Python type for scalar arguments (their *values* are
    dynamic — jit compiles one program for every ``skip=`` int, not one per
    value)."""
    if isinstance(leaf, (bool, int, float)):
        return ("py", type(leaf).__name__)
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return (tuple(int(s) for s in shape), str(dtype), bool(getattr(leaf, "weak_type", False)))
    return ("opaque", type(leaf).__name__)


class ProgramStore:
    """A directory of AOT-serialized executables keyed by content digests.

    Layout: ``<root>/<digest>.aotx`` (the pickled
    ``serialize_executable.serialize`` payload) plus ``<root>/<digest>.json``
    (a human-readable meta sidecar: program name, context, signature — the
    debugging surface ``docs/serving.md`` documents). Writes are atomic
    (tmp + rename) so a killed process never leaves a torn entry; reads that
    fail for ANY reason delete the entry and report a miss — the serving path
    then compiles exactly as it would have without the store.

    Counters feed ``stats()["aot"]`` on the continuous engine (and ``/metrics``
    through it): programs loaded/compiled/serialized plus load/compile latency
    windows — the before/after the ``cold_start`` bench lane pins.
    """

    def __init__(self, root: Optional[str] = None, *, context: Optional[Dict[str, Any]] = None):
        path = os.path.abspath(os.path.expanduser(root or _DEFAULT_DIR))
        self.disabled = False
        try:
            os.makedirs(path, exist_ok=True)
        except OSError as exc:
            # an unwritable dir must degrade to plain-jit serving, not crash it
            logger.warning(f"AOT program store disabled (cannot create {path}: {exc})")
            self.disabled = True
        self.root = path
        self._context = dict(context or {})
        self._context.update(backend_context())
        self._lock = threading.Lock()
        self.programs_loaded = 0
        self.programs_compiled = 0
        self.programs_serialized = 0
        self.load_failures = 0
        self.serialize_failures = 0
        self.load_ms = LatencyWindow()
        self.compile_ms = LatencyWindow()
        self._serialize_unsupported = False

    # ------------------------------------------------------------------ keys

    def context_prefix(self, program: str, context: Dict[str, Any]) -> str:
        """The per-(program, context) half of the entry key, serialized once —
        :class:`AOTFunction` caches it so the per-call work is just the
        argument signature's digest (the decode dispatch path runs through
        this on every engine iteration)."""
        return json.dumps(
            {"store": self._context, "program": program, "context": context},
            sort_keys=True,
            default=repr,
        )

    @staticmethod
    def key_for(prefix: str, signature: Any) -> str:
        return hashlib.sha256((prefix + "|" + repr(signature)).encode()).hexdigest()

    def entry_key(self, program: str, context: Dict[str, Any], signature: Any) -> str:
        """Stable digest over (store context, program name, caller context,
        argument signature). Any mismatch — a new jax version, a different
        mesh, a resized bucket — lands on a different digest, so stale
        entries are *skipped*, never mistakenly loaded."""
        return self.key_for(self.context_prefix(program, context), signature)

    def _path(self, key: str) -> str:
        return os.path.join(self.root, f"{key}.aotx")

    def has(self, key: str) -> bool:
        return not self.disabled and os.path.exists(self._path(key))

    # ------------------------------------------------------------------ io

    def load(self, key: str) -> Optional[Tuple]:
        """The pickled serialization payload for ``key``, or ``None`` on a
        miss. A present-but-unreadable entry (torn write, version skew inside
        the pickle) is deleted and reported as a miss with a warning — the
        caller compiles, then overwrites it with a good entry."""
        if self.disabled:
            return None
        path = self._path(key)
        try:
            with open(path, "rb") as fh:
                payload = pickle.loads(fh.read())
            if not (isinstance(payload, tuple) and len(payload) == 3):
                raise ValueError(f"malformed AOT entry (expected a 3-tuple, got {type(payload).__name__})")
            return payload
        except FileNotFoundError:
            return None
        except Exception as exc:
            with self._lock:
                self.load_failures += 1
            logger.warning(f"corrupted AOT entry {key[:12]}… ({exc}); deleting and recompiling")
            self._discard(key)
            return None

    def _discard(self, key: str) -> None:
        for suffix in (".aotx", ".json"):
            try:
                os.remove(os.path.join(self.root, key + suffix))
            except OSError:
                pass

    def save(self, key: str, payload: Tuple, meta: Dict[str, Any]) -> bool:
        """Persist one serialized executable atomically (payload first, meta
        sidecar after — a reader never sees meta without its entry)."""
        if self.disabled:
            return False
        path = self._path(key)
        try:
            blob = pickle.dumps(payload)
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as fh:
                fh.write(blob)
            os.replace(tmp, path)
            meta_tmp = os.path.join(self.root, key + f".json.tmp.{os.getpid()}")
            with open(meta_tmp, "w") as fh:
                json.dump({"store": self._context, **meta}, fh, indent=2, sort_keys=True, default=repr)
            os.replace(meta_tmp, os.path.join(self.root, key + ".json"))
        except Exception as exc:
            with self._lock:
                self.serialize_failures += 1
            logger.warning(f"could not persist AOT entry {key[:12]}… ({exc})")
            return False
        with self._lock:
            self.programs_serialized += 1
        return True

    def entries(self) -> "list[Dict[str, Any]]":
        """The meta sidecars on disk (tests and operators introspect these)."""
        out = []
        if self.disabled:
            return out
        for name in sorted(os.listdir(self.root)):
            if not name.endswith(".json"):
                continue
            try:
                with open(os.path.join(self.root, name)) as fh:
                    meta = json.load(fh)
            except (OSError, ValueError):
                continue
            meta["key"] = name[: -len(".json")]
            out.append(meta)
        return out

    def entry_count(self) -> int:
        if self.disabled:
            return 0
        try:
            return sum(1 for name in os.listdir(self.root) if name.endswith(".aotx"))
        except OSError:
            return 0

    # ------------------------------------------------------------------ telemetry

    def note_loaded(self, seconds: float) -> None:
        """Count one store deserialize (the cold-start fast path)."""
        self.load_ms.observe(seconds)
        with self._lock:
            self.programs_loaded += 1

    def note_compiled(self, seconds: float) -> None:
        """Count one lower+compile (the store-miss slow path)."""
        self.compile_ms.observe(seconds)
        with self._lock:
            self.programs_compiled += 1

    def note_load_failure(self, program: str, key: str, exc: BaseException) -> None:
        """A payload that unpickled but would not rebind in this process
        (device set changed under the same ids, jaxlib skew inside the bytes)
        is corrupt for this process: drop it so the caller compiles."""
        with self._lock:
            self.load_failures += 1
        logger.warning(f"AOT entry for {program!r} failed to deserialize ({exc}); recompiling")
        self._discard(key)

    def note_serialize_unsupported(self, program: str, exc: BaseException) -> None:
        """One warning per store when the backend cannot serialize executables
        (enabling the store there is never incorrect, only useless)."""
        with self._lock:
            self.serialize_failures += 1
            if self._serialize_unsupported:
                return
            self._serialize_unsupported = True
        logger.warning(
            f"this backend cannot serialize compiled executables ({exc}); AOT "
            f"preload degrades to plain jit compiles (first seen on {program!r})"
        )

    def stats(self) -> Dict[str, Any]:
        """``stats()["aot"]`` payload: ints + latency windows only (the
        ``/metrics`` no-None-gauge contract)."""
        with self._lock:
            out: Dict[str, Any] = {
                "programs_loaded": self.programs_loaded,
                "programs_compiled": self.programs_compiled,
                "programs_serialized": self.programs_serialized,
                "load_failures": self.load_failures,
                "serialize_failures": self.serialize_failures,
            }
        out["entries"] = self.entry_count()
        out["load_ms"] = self.load_ms.snapshot()
        out["compile_ms"] = self.compile_ms.snapshot()
        return out


def resolve_store(aot: Any, *, context: Optional[Dict[str, Any]] = None) -> Optional[ProgramStore]:
    """Normalize an ``aot=`` knob: a :class:`ProgramStore` passes through, a
    path string builds one, ``True`` resolves the env export (default
    location if the export is a bare flag), ``None`` consults
    ``UNIONML_TPU_AOT_PRELOAD`` (the serve CLI's early export), and ``False``
    is off. A store that failed to initialize resolves to ``None`` so the
    caller serves plain-jit."""
    if aot is False:
        return None
    if isinstance(aot, ProgramStore):
        return None if aot.disabled else aot
    if aot is None or aot is True:
        from unionml_tpu.defaults import serve_aot_preload

        path = serve_aot_preload()
        if path is None:
            return None
    else:
        path = os.fspath(aot)
    store = ProgramStore(path, context=context)
    return None if store.disabled else store


class AOTFunction:
    """Load-before-compile dispatch for one ``jax.jit`` binding.

    Call-compatible with the wrapped binding (static arguments included —
    they fold into the entry key and are omitted from the executable call,
    exactly as jit omits them from the traced signature). Per distinct
    signature, resolution order is: in-memory executable → store entry
    (deserialize) → ``lower().compile()`` + serialize back into the store.
    Donation semantics ride the executable itself (input-output aliasing is
    baked in at compile time), so wrapped and unwrapped calls are
    bit-identical — the contract the AOT==JIT exactness tests pin.

    A loaded executable that rejects its inputs (sharding/layout skew the key
    did not capture) falls back to a fresh compile for that signature — the
    check happens before execution, so no donated buffer is lost.
    """

    def __init__(
        self,
        jit_fn: Any,
        program: str,
        store: ProgramStore,
        context: Dict[str, Any],
        *,
        static_argnums: Tuple[int, ...] = (),
        static_argnames: Tuple[str, ...] = (),
    ):
        self._jit = jit_fn
        self.program = program
        self.store = store
        self._context = dict(context)
        self._static_argnums = tuple(static_argnums)
        self._static_argnames = tuple(static_argnames)
        #: the context half of the key, serialized once — per call only the
        #: argument signature is hashed (this wrapper sits on the decode
        #: dispatch path, which runs every engine iteration)
        self._key_prefix = store.context_prefix(program, self._context)
        self._exes: Dict[str, Any] = {}
        self._lock = threading.Lock()

    #: in-memory executables per wrapper: real programs have a handful of
    #: signatures (one per bucket/chunk shape), so this only triggers if a
    #: caller generates unbounded shapes — evict FIFO rather than grow forever
    _MAX_EXES = 64

    def _cache_exe_locked(self, key: str, exe: Any) -> None:
        if len(self._exes) >= self._MAX_EXES:
            self._exes.pop(next(iter(self._exes)))
        self._exes[key] = exe

    def _signature(self, args: Tuple, kwargs: Dict[str, Any]):
        import jax

        static_pos = tuple((i, repr(args[i])) for i in self._static_argnums if i < len(args))
        static_kw = tuple(sorted((k, repr(v)) for k, v in kwargs.items() if k in self._static_argnames))
        dyn_args = tuple(a for i, a in enumerate(args) if i not in self._static_argnums)
        dyn_kwargs = {k: v for k, v in kwargs.items() if k not in self._static_argnames}
        leaves, treedef = jax.tree_util.tree_flatten((dyn_args, dyn_kwargs))
        return (
            (static_pos, static_kw, tuple(_leaf_signature(leaf) for leaf in leaves), str(treedef)),
            dyn_args,
            dyn_kwargs,
        )

    def _record_event(self, source: str, ms: float) -> None:
        from unionml_tpu.observability.trace import current_trace

        trace = current_trace()
        if trace is not None:
            trace.event("engine.aot_preload", program=self.program, source=source, ms=round(ms, 3))

    def _execution_devices(self) -> "list[Any]":
        """The devices this wrapper's entries were compiled for: the mesh's (the
        key pins their ids), or the default device when there is no mesh."""
        import jax

        mesh = self._context.get("mesh")
        if mesh is None:
            default = jax.config.jax_default_device  # a Device, a platform name, or None
            return [default if isinstance(default, jax.Device) else jax.devices()[0]]
        by_id = {d.id: d for d in jax.devices()}
        return [by_id[i] for i in mesh["device_ids"]]

    def _load(self, key: str) -> Optional[Any]:
        from jax.experimental import serialize_executable

        payload = self.store.load(key)
        if payload is None:
            return None
        start = time.perf_counter()
        try:
            exe = serialize_executable.deserialize_and_load(
                *payload, execution_devices=self._execution_devices()
            )
        except Exception as exc:
            self.store.note_load_failure(self.program, key, exc)
            return None
        elapsed = time.perf_counter() - start
        self.store.note_loaded(elapsed)
        self._record_event("store", elapsed * 1e3)
        return exe

    def _compile(self, key: str, sig: Any, args: Tuple, kwargs: Dict[str, Any]) -> Any:
        from jax.experimental import serialize_executable

        start = time.perf_counter()
        compiled = self._jit.lower(*args, **kwargs).compile()
        elapsed = time.perf_counter() - start
        self.store.note_compiled(elapsed)
        self._record_event("compile", elapsed * 1e3)
        try:
            payload = serialize_executable.serialize(compiled)
        except Exception as exc:
            self.store.note_serialize_unsupported(self.program, exc)
            return compiled
        self.store.save(
            key,
            payload,
            {
                "program": self.program,
                "context": self._context,
                "signature": repr(sig),
                "compile_s": round(elapsed, 3),
            },
        )
        return compiled

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        sig, dyn_args, dyn_kwargs = self._signature(args, kwargs)
        key = ProgramStore.key_for(self._key_prefix, sig)
        exe = self._exes.get(key)
        if exe is None:
            with self._lock:
                exe = self._exes.get(key)
                if exe is None:
                    exe = self._load(key)
                    if exe is None:
                        exe = self._compile(key, sig, args, kwargs)
                    self._cache_exe_locked(key, exe)
        try:
            return exe(*dyn_args, **dyn_kwargs)
        except (ValueError, TypeError) as exc:
            # input validation happens BEFORE execution, so nothing was
            # donated yet — recompile for the actual inputs and replace the
            # in-memory (and on-disk) entry
            logger.warning(
                f"AOT executable for {self.program!r} rejected its inputs "
                f"({type(exc).__name__}: {exc}); recompiling"
            )
            exe = self._compile(key, sig, args, kwargs)
            with self._lock:
                self._cache_exe_locked(key, exe)
            return exe(*dyn_args, **dyn_kwargs)
