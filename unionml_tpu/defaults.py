"""Default execution settings.

Parity: reference unionml/defaults.py:5 defines ``DEFAULT_RESOURCES = Resources(cpu="1",
mem="1Gi")`` (a flytekit/k8s pod request). Our analog describes the host + TPU footprint
a stage asks the scheduler for.
"""

from __future__ import annotations

import dataclasses
import os

from unionml_tpu._logging import logger


@dataclasses.dataclass(frozen=True)
class Resources:
    """Resource request attached to a :class:`unionml_tpu.stage.Stage`.

    ``accelerator`` names a TPU slice topology (e.g. ``"v5e-1"``, ``"v5e-8"``); ``None``
    means host-only (CPU) execution, which is the default for data-plumbing stages.
    """

    cpu: str = "1"
    mem: str = "1Gi"
    accelerator: str | None = None
    chips: int = 0


DEFAULT_RESOURCES = Resources()

#: Environment variable used by ``serve``/``load_from_env`` — name kept identical to the
#: reference so existing user scripts keep working (reference unionml/cli.py:188-201).
MODEL_PATH_ENV_VAR = "UNIONML_MODEL_PATH"

# --------------------------------------------------------------------- overload
# Serving-stack overload protection (serving/overload.py). The reference
# outsourced all of this to uvicorn/Flyte; a TPU-native engine owns it. Every
# knob here is overridable per-app (ServingApp.configure_overload) and from the
# CLI (`serve --max-inflight/--deadline-ms/--max-deadline-ms/--drain-timeout`).

#: concurrent requests executing handlers before the HTTP layer sheds with 429;
#: an app built around a generation engine that holds and queues more takes the
#: engine's slots + max_waiting instead (serving/app.py default_max_inflight).
SERVE_MAX_INFLIGHT = 256

#: micro-batcher admission queue bound (requests waiting to join a dispatch);
#: a full queue sheds with 429 instead of growing without bound.
SERVE_QUEUE_MAXSIZE = 1024

#: continuous-batching engine waiting-queue bound (prompts waiting for a free
#: decode slot) — ahead of the fixed slot pool itself.
SERVE_MAX_WAITING = 256

#: server-default per-request deadline (ms); a request still queued past it is
#: shed with 503, one mid-handler is cancelled. ``X-Request-Deadline-Ms`` lets
#: a client tighten (or, up to the max below, extend) it per request.
SERVE_DEFAULT_DEADLINE_MS = 30_000.0

#: ceiling on client-requested deadlines (ms): a client cannot pin server
#: resources longer than this no matter what header it sends.
SERVE_MAX_DEADLINE_MS = 300_000.0

#: seconds a SIGTERM-initiated drain waits for in-flight requests and streams
#: to finish before the process exits anyway.
SERVE_DRAIN_TIMEOUT_S = 30.0

#: ``Retry-After`` seconds attached to 429/503 shed responses.
SERVE_RETRY_AFTER_S = 1

#: env var carrying the ``serve --dp-replicas`` override: the CLI exports it
#: BEFORE the app module imports, so engines built at import time (or lazily at
#: first request) see it without any app code changes.
SERVE_DP_REPLICAS_ENV_VAR = "UNIONML_TPU_DP_REPLICAS"

# ------------------------------------------------------------ stall-free admission
# Chunked-admission knobs for the continuous-batching engine
# (serving/continuous.py): an arriving prompt's prefill is sliced into
# fixed-size chunks interleaved with decode dispatches (Sarathi-style
# chunked-prefill scheduling), so a long prompt no longer freezes every
# resident stream for its whole prefill. Same export pattern as
# SERVE_DP_REPLICAS_ENV_VAR: the serve CLI sets these before the app module
# imports, and the engine reads them at construction.

#: admission prefill slice width in tokens; 0 = unset (fall back to
#: ``GenerationConfig.prefill_chunk``, else monolithic admission).
SERVE_ADMIT_CHUNK_ENV_VAR = "UNIONML_TPU_ADMIT_CHUNK"

#: prefill tokens the engine may run per iteration between decode dispatches;
#: 0 = unset (one admission chunk per iteration).
SERVE_PREFILL_BUDGET_ENV_VAR = "UNIONML_TPU_PREFILL_BUDGET"

#: concurrent partially-prefilled admissions; 0 = unset (one at a time).
SERVE_MAX_ADMISSIONS_ENV_VAR = "UNIONML_TPU_MAX_ADMISSIONS"

#: 1 = enable the radix prefix cache (automatic cross-request KV reuse over
#: paged blocks, serving/prefix_cache.py) on continuous engines; 0/unset
#: = off, which keeps the engine byte-for-byte the pre-cache one. Same
#: early-export contract as the admission knobs.
SERVE_PREFIX_CACHE_ENV_VAR = "UNIONML_TPU_PREFIX_CACHE"

# ------------------------------------------------------- disaggregated serving
# Prefill/decode role split + elastic resize for the replica fleet
# (serving/replicas.py, docs/serving.md "Disaggregated and elastic serving").
# Same early-export contract as SERVE_DP_REPLICAS_ENV_VAR: the serve CLI sets
# these before the app module imports, and the ReplicaSet resolves them at
# construction — existing apps disaggregate with zero code changes.

#: replica role assignment, e.g. ``prefill=1,decode=3`` (roles: prefill /
#: decode / mixed; counts sum to the fleet size). Unset/empty = every replica
#: mixed (today's symmetric fleet). Garbage warns and falls back to symmetric.
SERVE_REPLICA_ROLES_ENV_VAR = "UNIONML_TPU_REPLICA_ROLES"

#: prompt-length threshold (tokens) above which an admission routes to a
#: prefill-role replica and its finished KV hands off to a decode replica;
#: 0 (the default) disaggregates every admission once roles are configured.
SERVE_PREFILL_THRESHOLD_ENV_VAR = "UNIONML_TPU_PREFILL_THRESHOLD"

#: autoscaler high watermark on per-replica scheduling load (the engine's
#: token-weighted ``load()`` averaged over the fleet); 0 = autoscaler off.
SERVE_AUTOSCALE_HIGH_ENV_VAR = "UNIONML_TPU_AUTOSCALE_HIGH"

#: autoscaler low watermark (scale down below it); 0 = never scale down.
SERVE_AUTOSCALE_LOW_ENV_VAR = "UNIONML_TPU_AUTOSCALE_LOW"

#: seconds between autoscaler evaluations of the windowed rates.
SERVE_AUTOSCALE_INTERVAL_S_ENV_VAR = "UNIONML_TPU_AUTOSCALE_INTERVAL_S"
SERVE_AUTOSCALE_INTERVAL_S = 10.0

#: fleet-size floor the autoscaler may never drain below.
SERVE_MIN_REPLICAS_ENV_VAR = "UNIONML_TPU_MIN_REPLICAS"

#: fleet-size ceiling; 0 = bounded by the spare submeshes/devices available.
SERVE_MAX_REPLICAS_ENV_VAR = "UNIONML_TPU_MAX_REPLICAS"

# -------------------------------------------------------- cold start / AOT preload
# Compile-cache + AOT-program-store knobs (compile_cache.py, serving/aot.py,
# docs/serving.md "Cold start and AOT preload"). Same early-export contract as
# SERVE_DP_REPLICAS_ENV_VAR: the serve CLI sets these before the app module
# imports, so engines built at import time preload too.

#: persistent XLA compilation cache directory (a path, "1" for the default
#: location, or an off-flag) — honored at package import by compile_cache.py;
#: `serve --compile-cache DIR` re-exports it for reload/fork children.
SERVE_COMPILE_CACHE_ENV_VAR = "UNIONML_TPU_COMPILE_CACHE"

#: AOT program store for serving executables: a directory path, a truthy flag
#: ("1"/"true"/"yes"/"on") for the default location, or an off-flag
#: (""/"0"/"false"/"no"/"off"/unset). With the store on, engine/Generator
#: warmup loads serialized executables instead of compiling (load-before-
#: compile), and every compile it does pay is serialized back for the next
#: cold process. An unusable directory warns and degrades to plain jit.
SERVE_AOT_PRELOAD_ENV_VAR = "UNIONML_TPU_AOT_PRELOAD"

# ------------------------------------------------------------ quantized serving
# Serve-time quantization knobs (docs/serving.md "Quantized serving"). Decode is
# HBM-bandwidth bound and the KV cache dominates resident memory at scale:
# int8 weights and int8 paged KV roughly halve bytes-per-step and roughly
# double resident streams per chip. Same early-export contract as
# SERVE_DP_REPLICAS_ENV_VAR: the serve CLI sets these before the app module
# imports, and Generators built by app code resolve them at construction —
# existing apps opt into quantized serving with zero code changes.

#: "int8" = weight-only int8 for serving Generators (ops/quant.py: per-channel
#: symmetric, dequant fused in-jit so int8 is what crosses HBM); "none"/unset =
#: full precision. Garbage values warn and fall back (never crash serve at
#: app-import time); explicit API calls still raise the Generator's own
#: "unsupported quantize mode" ValueError.
SERVE_QUANTIZE_ENV_VAR = "UNIONML_TPU_QUANTIZE"

#: "int8" = int8 KV cache (per-(position, head) symmetric scales — the engine's
#: page pools and a solo Generator's rows both, models/generate.init_paged_cache/init_cache);
#: "none"/unset = the compute dtype. Same warn-and-fall-back contract.
SERVE_KV_CACHE_DTYPE_ENV_VAR = "UNIONML_TPU_KV_CACHE_DTYPE"

# ------------------------------------------------------------- multi-tenant QoS
# Tenancy knobs (serving/tenancy.py, docs/serving.md "Multi-tenant QoS"). Same
# early-export contract as SERVE_DP_REPLICAS_ENV_VAR: the serve CLI sets these
# before the app module imports, and the serving app builds its TenantRegistry
# from them at construction. Neither set = tenancy off (byte-for-byte today's
# anonymous-and-equal serving stack).

#: path to a tenants.json (per-tenant weights, req/s + generated-tokens/s
#: bucket rates, default priority tier, api-key -> tenant mapping). A missing
#: or malformed file warns and degrades to --default-tenant-rate only.
SERVE_TENANT_CONFIG_ENV_VAR = "UNIONML_TPU_TENANT_CONFIG"

#: requests/s bucket rate for identified tenants NOT named in the config file
#: (anonymous traffic is never bucket-limited); 0/unset = unlimited.
SERVE_DEFAULT_TENANT_RATE_ENV_VAR = "UNIONML_TPU_DEFAULT_TENANT_RATE"

# ----------------------------------------------------------- multi-process fleets
# jax.distributed bootstrap knobs (unionml_tpu/distributed.py) shared by TRAIN
# (job_runner joining a slice) and SERVE (serving/cluster.py's worker
# processes). Same early-export contract as SERVE_DP_REPLICAS_ENV_VAR: the
# serve CLI exports them before the app module imports, and the launcher sets
# them on every worker it spawns.

#: coordinator address (``host:port``) for ``jax.distributed.initialize``;
#: unset = single-process (the bootstrap is a no-op).
DISTRIBUTED_COORDINATOR_ENV_VAR = "UNIONML_TPU_COORDINATOR"

#: total processes in the slice/fleet (1 = single process).
DISTRIBUTED_NUM_PROCESSES_ENV_VAR = "UNIONML_TPU_NUM_PROCESSES"

#: this process's id in ``[0, num_processes)``.
DISTRIBUTED_PROCESS_ID_ENV_VAR = "UNIONML_TPU_PROCESS_ID"

#: rendezvous directory for the serving fleet's control plane
#: (serving/cluster.py): each worker announces its loopback control-server
#: address as a ``host-<id>.json`` file there, and the coordinator connects by
#: polling it. Unset = ``.unionml_fleet`` under the working directory.
FLEET_DIR_ENV_VAR = "UNIONML_TPU_FLEET_DIR"

#: per-host role spec for the fleet coordinator (``prefill=1,decode=1`` at
#: HOST granularity — the cross-host analog of SERVE_REPLICA_ROLES_ENV_VAR);
#: unset/empty = every host mixed. Garbage warns and degrades to symmetric.
FLEET_HOST_ROLES_ENV_VAR = "UNIONML_TPU_HOST_ROLES"

# ----------------------------------------------------------- fleet fault tolerance
# Host-lifecycle / failover / fault-injection knobs (serving/cluster.py,
# serving/faults.py, docs/serving.md "Fault tolerance"). Same early-export
# contract as SERVE_DP_REPLICAS_ENV_VAR: the serve CLI sets these before the
# app module imports, and the coordinator/worker read them at construction.

#: a deterministic fault plan (serving/faults.py): a path to a plan JSON, or
#: the JSON inline (starts with ``{``). Unset = no injection. A garbage value
#: warns and degrades to no plan — chaos must be opt-in, never accidental.
SERVE_FAULT_PLAN_ENV_VAR = "UNIONML_TPU_FAULT_PLAN"

#: seconds between coordinator reconciliation ticks (lease heartbeat,
#: suspect/dead re-probes, rendezvous-dir announce scans).
FLEET_PROBE_INTERVAL_S_ENV_VAR = "UNIONML_TPU_PROBE_INTERVAL_S"
FLEET_PROBE_INTERVAL_S = 1.0

#: consecutive successful probes a returning host must pass in probation
#: before it takes traffic again.
FLEET_PROBATION_PROBES_ENV_VAR = "UNIONML_TPU_PROBATION_PROBES"
FLEET_PROBATION_PROBES = 2

#: consecutive probe failures that move a suspect host to dead.
FLEET_DEAD_AFTER_PROBES_ENV_VAR = "UNIONML_TPU_DEAD_AFTER_PROBES"
FLEET_DEAD_AFTER_PROBES = 3

#: coordinator heartbeat-lease TTL (seconds): workers treat a lease older
#: than this as an expired coordinator and the lowest-id live worker promotes.
FLEET_LEASE_TTL_S_ENV_VAR = "UNIONML_TPU_LEASE_TTL_S"
FLEET_LEASE_TTL_S = 5.0


def distributed_coordinator() -> "str | None":
    """The ``jax.distributed`` coordinator address (``host:port``); None =
    single-process. Read at bootstrap time (job_runner start, serve start),
    after the CLI/launcher export — the :func:`serve_dp_replicas` contract."""
    raw = os.environ.get(DISTRIBUTED_COORDINATOR_ENV_VAR)
    if raw is None or not raw.strip():
        return None
    return raw.strip()


def distributed_num_processes() -> int:
    """Total processes in the slice/fleet; garbage warns and degrades to 1
    (single-process) instead of crashing the bootstrap — the env_int
    contract."""
    return env_int(DISTRIBUTED_NUM_PROCESSES_ENV_VAR, 1, minimum=1)


def distributed_process_id() -> int:
    """This process's id in ``[0, num_processes)``; garbage warns and degrades
    to 0 — a mis-set worker then fails loudly at ``jax.distributed``
    rendezvous (duplicate id) rather than silently joining wrong."""
    return env_int(DISTRIBUTED_PROCESS_ID_ENV_VAR, 0, minimum=0)


def fleet_dir() -> str:
    """The serving fleet's control-plane rendezvous directory
    (``UNIONML_TPU_FLEET_DIR``); defaults to ``.unionml_fleet`` under the
    working directory so an emulated local fleet needs zero configuration."""
    raw = os.environ.get(FLEET_DIR_ENV_VAR)
    if raw is None or not raw.strip():
        return ".unionml_fleet"
    return raw.strip()


def serve_fault_plan() -> "str | None":
    """The fault-plan spec (``UNIONML_TPU_FAULT_PLAN``): a path or inline
    JSON; None = no injection. Validity is the consumer's concern —
    ``FaultPlan.from_env`` warns and degrades on garbage (the serve-export
    contract), never crashes serve at app-import time."""
    raw = os.environ.get(SERVE_FAULT_PLAN_ENV_VAR)
    if raw is None or not raw.strip():
        return None
    return raw.strip()


def fleet_probe_interval_s() -> float:
    """Seconds between coordinator reconciliation ticks; garbage warns and
    degrades to the default (the env_float contract)."""
    return env_float(FLEET_PROBE_INTERVAL_S_ENV_VAR, FLEET_PROBE_INTERVAL_S, minimum=0.05)


def fleet_probation_probes() -> int:
    """Consecutive probe successes a returning host needs before going live."""
    return env_int(FLEET_PROBATION_PROBES_ENV_VAR, FLEET_PROBATION_PROBES, minimum=1)


def fleet_dead_after_probes() -> int:
    """Consecutive probe failures that move a suspect host to dead."""
    return env_int(FLEET_DEAD_AFTER_PROBES_ENV_VAR, FLEET_DEAD_AFTER_PROBES, minimum=1)


def fleet_lease_ttl_s() -> float:
    """Coordinator heartbeat-lease TTL in seconds."""
    return env_float(FLEET_LEASE_TTL_S_ENV_VAR, FLEET_LEASE_TTL_S, minimum=0.1)


def fleet_host_roles() -> "dict[str, int]":
    """The per-HOST role census for the fleet coordinator, parsed with the
    same grammar (and warn-and-degrade contract) as :func:`serve_replica_roles`;
    ``{}`` = every host mixed."""
    raw = os.environ.get(FLEET_HOST_ROLES_ENV_VAR)
    if raw is None or not raw.strip():
        return {}
    try:
        return parse_replica_roles(raw)
    except ValueError as exc:
        logger.warning(
            f"ignoring {FLEET_HOST_ROLES_ENV_VAR}={raw!r} ({exc}); "
            "falling back to a symmetric (all-mixed) host fleet"
        )
        return {}


# --------------------------------------------------------------- observability
# Request-tracing / flight-recorder / profiler knobs (unionml_tpu/observability,
# docs/observability.md). Same export pattern as the admission knobs above: the
# serve CLI sets the env vars before the app module imports, and the serving
# app reads them at construction.

#: 1 = record a per-request timeline (spans at every lifecycle stage) into the
#: flight recorder; 0 = off (request ids still flow — tracing is the only part
#: with a cost, and it is strictly zero-allocation while off).
SERVE_TRACE_ENV_VAR = "UNIONML_TPU_TRACE"

#: completed request timelines the flight recorder retains (ring buffer).
SERVE_FLIGHT_RECORDER_ENV_VAR = "UNIONML_TPU_FLIGHT_RECORDER_SIZE"
SERVE_FLIGHT_RECORDER_SIZE = 256

#: log line format: "text" (classic prefix) or "json" (structured lines
#: carrying the request id — see _logging.JsonFormatter).
SERVE_LOG_FORMAT_ENV_VAR = "UNIONML_TPU_LOG_FORMAT"

#: directory ``POST /debug/profile`` writes jax.profiler traces into; unset
#: disables the endpoint (it answers 400 with a pointer to the flag).
SERVE_PROFILE_DIR_ENV_VAR = "UNIONML_TPU_PROFILE_DIR"

#: ceiling on one on-demand profile capture (ms): a runaway duration request
#: must not leave the profiler running for hours.
SERVE_PROFILE_MAX_MS = 60_000.0

#: directory ``serve --record-traffic`` captures live traffic traces into
#: (workloads/traces.py TraceRecorder); unset = capture off.
SERVE_RECORD_TRAFFIC_ENV_VAR = "UNIONML_TPU_RECORD_TRAFFIC"

#: record SHA-256 digests + lengths instead of prompt token ids (privacy
#: posture for traces that leave the machine); 0/unset = literal ids.
SERVE_RECORD_TRAFFIC_HASH_ENV_VAR = "UNIONML_TPU_RECORD_TRAFFIC_HASH"

# ------------------------------------------------------------ SLOs / fleet health
# Declarative serving SLO targets (observability/slo.py, docs/observability.md
# "SLOs and fleet health"). Same early-export contract as the knobs above: the
# serve CLI sets the env vars before the app module imports, and every
# continuous engine's SLO tracker reads them at construction. 0/unset disarms
# an objective — an engine with no targets evaluates as healthy.

#: TTFT p95 target in ms over the burn-rate windows (0 = disarmed).
SERVE_SLO_TTFT_P95_MS_ENV_VAR = "UNIONML_TPU_SLO_TTFT_P95_MS"

#: TBT p99 target in ms (0 = disarmed).
SERVE_SLO_TBT_P99_MS_ENV_VAR = "UNIONML_TPU_SLO_TBT_P99_MS"

#: tolerated shed fraction of arrivals, e.g. 0.01 (0 = disarmed).
SERVE_SLO_SHED_RATIO_ENV_VAR = "UNIONML_TPU_SLO_SHED_RATIO"

#: fast burn-rate window (seconds): the paging window — a breach needs the
#: fast window over target, so a long-gone incident cannot page.
SERVE_SLO_FAST_WINDOW_S_ENV_VAR = "UNIONML_TPU_SLO_FAST_WINDOW_S"
SERVE_SLO_FAST_WINDOW_S = 60.0

#: slow burn-rate window (seconds): the trend confirmation — breach requires
#: BOTH windows over target; one alone is warn.
SERVE_SLO_SLOW_WINDOW_S_ENV_VAR = "UNIONML_TPU_SLO_SLOW_WINDOW_S"
SERVE_SLO_SLOW_WINDOW_S = 600.0

#: samples (or arrivals, for the shed ratio) a window needs before it can
#: breach: an idle engine is healthy, not failing.
SERVE_SLO_MIN_SAMPLES_ENV_VAR = "UNIONML_TPU_SLO_MIN_SAMPLES"
SERVE_SLO_MIN_SAMPLES = 3


def env_int(name: str, default: int, *, minimum: "int | None" = None) -> int:
    """Parse an integer env var, tolerating garbage: unset/empty -> ``default``,
    a non-integer value warns and falls back to ``default`` instead of raising
    ``ValueError`` at whatever moment the knob happens to be read (for serve
    knobs that is import/export time in ``cli.py serve`` — a typo'd deployment
    env must degrade to the default, not take the service down). ``minimum``
    clamps the parsed value (e.g. a negative replica count means 0)."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        value = int(raw.strip())
    except ValueError:
        logger.warning(f"ignoring non-integer {name}={raw!r}; falling back to {default}")
        return default
    if minimum is not None and value < minimum:
        logger.warning(f"clamping {name}={value} to the minimum {minimum}")
        return minimum
    return value


def env_float(name: str, default: float, *, minimum: "float | None" = None) -> float:
    """:func:`env_int` for float-valued knobs (same warn-and-fall-back
    contract; a garbage value must never crash the reader)."""
    raw = os.environ.get(name)
    if raw is None or not raw.strip():
        return default
    try:
        value = float(raw.strip())
    except ValueError:
        logger.warning(f"ignoring non-numeric {name}={raw!r}; falling back to {default}")
        return default
    if minimum is not None and value < minimum:
        logger.warning(f"clamping {name}={value} to the minimum {minimum}")
        return minimum
    return value


def env_choice(name: str, choices: "tuple[str, ...]", what: str) -> "str | None":
    """Parse a choice-valued env var with the :func:`env_int` tolerance
    contract: unset/empty/"none"/"off"/"0" mean None (the knob's off state), a
    listed choice is returned normalized, and anything else warns and falls
    back to None instead of raising at whatever moment the knob happens to be
    read (for serve knobs that is app-import time — a typo'd deployment env
    must degrade to full precision, not take the service down). ``what`` names
    the knob in the warning (e.g. "quantize mode"), mirroring the ValueError
    text the explicit API raises for the same mistake."""
    raw = os.environ.get(name)
    if raw is None:
        return None
    value = raw.strip().lower()
    if value in ("", "none", "off", "0"):
        return None
    if value in choices:
        return value
    logger.warning(
        f"ignoring {name}={raw!r}: unsupported {what}; expected one of "
        f"{choices + ('none',)} — falling back to full precision"
    )
    return None


#: env values that mean "on, default location" / "off" for path-or-flag knobs
_TRUTHY_FLAGS = ("1", "true", "yes", "on")
_FALSY_FLAGS = ("", "0", "false", "no", "off")


def _env_path_flag(name: str, default_dir: str) -> "str | None":
    """Parse a path-or-flag env var: off-flags (and unset) mean None, truthy
    flags mean ``default_dir``, anything else is the path itself. Whether the
    path is *usable* is the consumer's concern — ProgramStore
    warns and degrades on an unwritable directory (the serve-export contract:
    a garbage value must never crash serve at app-import time)."""
    raw = os.environ.get(name)
    if raw is None:
        return None
    value = raw.strip()
    if value.lower() in _FALSY_FLAGS:
        return None
    if value.lower() in _TRUTHY_FLAGS:
        return default_dir
    return value


def serve_aot_preload() -> "str | None":
    """The AOT program store directory (``UNIONML_TPU_AOT_PRELOAD``); None =
    off. Read at engine/Generator construction, after the CLI's early export
    — same contract as :func:`serve_admit_chunk`. An unusable directory warns
    and degrades at ProgramStore construction, never at read time."""
    return _env_path_flag(SERVE_AOT_PRELOAD_ENV_VAR, "~/.cache/unionml_tpu/aot")


def serve_quantize() -> "str | None":
    """The serve-time weight-quantization mode ("int8" or None); read at
    Generator construction, after the CLI's early export — same contract as
    :func:`serve_dp_replicas`. Garbage (``UNIONML_TPU_QUANTIZE=fp4``) warns
    and falls back to None rather than crashing serve at app-import time."""
    return env_choice(SERVE_QUANTIZE_ENV_VAR, ("int8",), "quantize mode")


def serve_kv_cache_dtype() -> "str | None":
    """The serve-time KV-cache storage dtype ("int8" or None = compute dtype);
    read at Generator construction, same contract as :func:`serve_quantize`."""
    return env_choice(SERVE_KV_CACHE_DTYPE_ENV_VAR, ("int8",), "kv_cache_dtype")


def serve_tenant_config() -> "str | None":
    """Path to the serve-time tenants.json (``UNIONML_TPU_TENANT_CONFIG``);
    None = unset. Existence/validity is the registry's concern — it warns and
    degrades on a bad file (the serve-export contract), so a stale path in a
    fleet-wide env never crashes serve at app-import time."""
    raw = os.environ.get(SERVE_TENANT_CONFIG_ENV_VAR)
    if raw is None or not raw.strip():
        return None
    return raw.strip()


def serve_default_tenant_rate() -> float:
    """Requests/s bucket rate for identified-but-unconfigured tenants; 0 =
    unlimited (and, with no config file either, tenancy entirely off). Same
    warn-and-fall-back contract as every serve reader."""
    return env_float(SERVE_DEFAULT_TENANT_RATE_ENV_VAR, 0.0, minimum=0.0)


def serve_dp_replicas() -> int:
    """The serve-time data-parallel replica override; 0 = unset (derive the
    replica count from the mesh's data/fsdp axes). Read at call time, not
    import time — engine construction usually happens long after this module
    imports, and the CLI sets the env var in between. Garbage values
    (``UNIONML_TPU_DP_REPLICAS=abc``) warn and fall back to 0 rather than
    crashing ``serve`` at app-import time."""
    return env_int(SERVE_DP_REPLICAS_ENV_VAR, 0, minimum=0)


def serve_admit_chunk() -> int:
    """Serve-time admission prefill chunk width; 0 = unset. Read at engine
    construction (after the CLI export), same contract as
    :func:`serve_dp_replicas`."""
    return env_int(SERVE_ADMIT_CHUNK_ENV_VAR, 0, minimum=0)


def serve_prefill_budget() -> int:
    """Serve-time per-iteration prefill-token budget; 0 = unset (one chunk)."""
    return env_int(SERVE_PREFILL_BUDGET_ENV_VAR, 0, minimum=0)


def serve_max_admissions() -> int:
    """Serve-time cap on concurrent partially-prefilled admissions; 0 = unset."""
    return env_int(SERVE_MAX_ADMISSIONS_ENV_VAR, 0, minimum=0)


def serve_prefix_cache() -> bool:
    """Whether the serve-time radix prefix cache is on
    (``UNIONML_TPU_PREFIX_CACHE=1``); read at engine construction, after the
    CLI's early export, same contract as :func:`serve_admit_chunk`."""
    return env_int(SERVE_PREFIX_CACHE_ENV_VAR, 0, minimum=0) > 0


#: roles a replica may carry (serving/replicas.py); "mixed" is today's
#: prefill-and-decode-in-one behavior and the default for every replica.
REPLICA_ROLES = ("prefill", "decode", "mixed")


def parse_replica_roles(raw: str) -> "dict[str, int]":
    """Parse a ``prefill=1,decode=3`` role spec into ``{role: count}``.
    Raises ``ValueError`` naming the offending entry — the CLI surfaces it as
    a usage error; the env reader below degrades instead."""
    out: "dict[str, int]" = {}
    for entry in raw.split(","):
        entry = entry.strip()
        if not entry:
            continue
        role, sep, count = entry.partition("=")
        role = role.strip().lower()
        if not sep or role not in REPLICA_ROLES:
            raise ValueError(
                f"bad replica-role entry {entry!r}; expected role=count with role in "
                f"{REPLICA_ROLES} (e.g. 'prefill=1,decode=3')"
            )
        try:
            n = int(count.strip())
        except ValueError:
            raise ValueError(f"bad replica-role count in {entry!r}; expected an integer")
        if n < 0:
            raise ValueError(f"replica-role count must be >= 0 in {entry!r}")
        out[role] = out.get(role, 0) + n
    return {role: n for role, n in out.items() if n > 0}


def serve_replica_roles() -> "dict[str, int]":
    """The serve-time ``--replica-roles`` export parsed to ``{role: count}``;
    ``{}`` = unset (a symmetric, all-mixed fleet). Read at ReplicaSet
    construction, after the CLI's early export — garbage warns and falls back
    to symmetric rather than crashing serve at app-import time."""
    raw = os.environ.get(SERVE_REPLICA_ROLES_ENV_VAR)
    if raw is None or not raw.strip():
        return {}
    try:
        return parse_replica_roles(raw)
    except ValueError as exc:
        logger.warning(
            f"ignoring {SERVE_REPLICA_ROLES_ENV_VAR}={raw!r} ({exc}); "
            "falling back to a symmetric (all-mixed) fleet"
        )
        return {}


def serve_prefill_threshold() -> int:
    """Prompt-length threshold (tokens) for routing to prefill-role replicas;
    0 = every admission disaggregates once roles are configured."""
    return env_int(SERVE_PREFILL_THRESHOLD_ENV_VAR, 0, minimum=0)


def serve_autoscale_high() -> float:
    """Autoscaler high watermark on per-replica load; 0.0 = autoscaler off."""
    return env_float(SERVE_AUTOSCALE_HIGH_ENV_VAR, 0.0, minimum=0.0)


def serve_autoscale_low() -> float:
    """Autoscaler low watermark; 0.0 = never scale down."""
    return env_float(SERVE_AUTOSCALE_LOW_ENV_VAR, 0.0, minimum=0.0)


def serve_autoscale_interval_s() -> float:
    """Seconds between autoscaler evaluations."""
    return env_float(
        SERVE_AUTOSCALE_INTERVAL_S_ENV_VAR, SERVE_AUTOSCALE_INTERVAL_S, minimum=0.05
    )


def serve_min_replicas() -> int:
    """Fleet-size floor for the autoscaler."""
    return env_int(SERVE_MIN_REPLICAS_ENV_VAR, 1, minimum=1)


def serve_max_replicas() -> int:
    """Fleet-size ceiling for the autoscaler; 0 = spare-capacity bound."""
    return env_int(SERVE_MAX_REPLICAS_ENV_VAR, 0, minimum=0)


def serve_trace() -> bool:
    """Whether serve-time request tracing is on (``UNIONML_TPU_TRACE=1``);
    read at app construction, after the CLI's early export."""
    return env_int(SERVE_TRACE_ENV_VAR, 0, minimum=0) > 0


def serve_flight_recorder_size() -> int:
    """Completed request timelines the flight recorder retains; garbage or
    sub-1 values degrade to the default (the recorder requires >= 1)."""
    return env_int(SERVE_FLIGHT_RECORDER_ENV_VAR, SERVE_FLIGHT_RECORDER_SIZE, minimum=1)


def serve_profile_dir() -> "str | None":
    """Directory for on-demand ``POST /debug/profile`` captures; None = the
    endpoint is disabled."""
    raw = os.environ.get(SERVE_PROFILE_DIR_ENV_VAR)
    return raw.strip() or None if raw is not None else None


def serve_record_traffic() -> "str | None":
    """Directory live traffic is captured into as replayable traces
    (``serve --record-traffic``, workloads/traces.py); None = capture off.
    Read at app construction, after the CLI's early export — an unusable
    directory degrades at TraceRecorder construction (warn, capture off),
    never at read time."""
    raw = os.environ.get(SERVE_RECORD_TRAFFIC_ENV_VAR)
    return raw.strip() or None if raw is not None else None


def serve_record_traffic_hash() -> bool:
    """Whether captured traces carry prompt digests instead of token ids."""
    return env_int(SERVE_RECORD_TRAFFIC_HASH_ENV_VAR, 0, minimum=0) > 0


def serve_slo_ttft_p95_ms() -> float:
    """Serve-time TTFT p95 SLO target in ms; 0.0 = disarmed. Read at engine
    construction (after the CLI's early export), same contract as
    :func:`serve_admit_chunk` — garbage warns and falls back, never crashes
    serve at app-import time."""
    return env_float(SERVE_SLO_TTFT_P95_MS_ENV_VAR, 0.0, minimum=0.0)


def serve_slo_tbt_p99_ms() -> float:
    """Serve-time TBT p99 SLO target in ms; 0.0 = disarmed."""
    return env_float(SERVE_SLO_TBT_P99_MS_ENV_VAR, 0.0, minimum=0.0)


def serve_slo_shed_ratio() -> float:
    """Serve-time shed-ratio SLO target (fraction of arrivals); 0.0 = disarmed."""
    return env_float(SERVE_SLO_SHED_RATIO_ENV_VAR, 0.0, minimum=0.0)


def serve_slo_fast_window_s() -> float:
    """Fast burn-rate window in seconds (the paging window)."""
    return env_float(SERVE_SLO_FAST_WINDOW_S_ENV_VAR, SERVE_SLO_FAST_WINDOW_S, minimum=1.0)


def serve_slo_slow_window_s() -> float:
    """Slow burn-rate window in seconds (the trend-confirmation window)."""
    return env_float(SERVE_SLO_SLOW_WINDOW_S_ENV_VAR, SERVE_SLO_SLOW_WINDOW_S, minimum=1.0)


def serve_slo_min_samples() -> int:
    """Samples a window needs before it can breach (idle engines stay healthy)."""
    return env_int(SERVE_SLO_MIN_SAMPLES_ENV_VAR, SERVE_SLO_MIN_SAMPLES, minimum=1)
