"""unionml-tpu command-line interface.

Parity surface: reference unionml/cli.py:26-212 — a typer app exposing ``init``,
``deploy``, ``train``, ``predict``, ``list-model-versions``, ``fetch-model`` and a
``serve`` command that boots the HTTP prediction service with ``--model-path``. typer
is not in the TPU image, so this is a plain ``click`` group with the same command
names, options, and behaviors; ``serve`` runs our self-contained asyncio server
(:mod:`unionml_tpu.serving.http`) instead of wrapping uvicorn (cli.py:172-205).
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path
from typing import Any, Optional

import click

from unionml_tpu.defaults import MODEL_PATH_ENV_VAR


@click.group(name="unionml-tpu")
@click.version_option(package_name="unionml-tpu", message="%(version)s")
def app() -> None:
    """unionml-tpu: deploy TPU-native machine learning microservices."""


def _locate_model(app_ref: str) -> Any:
    """Import ``module:variable`` and return the Model (reference remote.get_model)."""
    from unionml_tpu.resolver import locate

    sys.path.insert(0, os.getcwd())
    obj = locate(app_ref)
    return obj


def _parse_json_option(raw: Optional[str], option: str) -> Any:
    if raw is None:
        return None
    try:
        return json.loads(raw)
    except json.JSONDecodeError as exc:
        raise click.BadParameter(f"{option} must be valid JSON: {exc}")


@app.command("init")
@click.argument("app_name")
@click.option(
    "--template",
    "-t",
    default="basic",
    show_default=True,
    help="template to scaffold the app from (see `unionml-tpu templates`)",
)
def init(app_name: str, template: str) -> None:
    """Initialize a new unionml-tpu project (reference cli.py:33-51)."""
    from unionml_tpu.templating import render_template

    try:
        project_dir = render_template(template, app_name, Path.cwd())
    except (ValueError, FileExistsError) as exc:
        raise click.ClickException(str(exc))
    click.echo(f"Created unionml-tpu project at {project_dir}")


@app.command("templates")
def templates() -> None:
    """List available project templates."""
    from unionml_tpu.templating import list_templates

    for name in list_templates():
        click.echo(name)


@app.command("lint")
@click.argument("paths", nargs=-1, metavar="[PATHS]...")
@click.option(
    "--format",
    "format_",
    type=click.Choice(["text", "json", "sarif"]),
    default="text",
    show_default=True,
    help="report format (json follows the stable schema docs/static-analysis.md describes; "
    "sarif emits SARIF 2.1.0 for CI/editor annotation surfaces)",
)
@click.option("--select", default=None, help="comma-separated rule ids to run (default: all)")
@click.option("--ignore", default=None, help="comma-separated rule ids to skip")
@click.option(
    "--show-suppressed",
    is_flag=True,
    default=False,
    help="also list findings silenced by `# tpu-lint: disable=RULE` comments",
)
@click.option(
    "--changed-only",
    is_flag=False,
    flag_value="HEAD",
    default=None,
    metavar="[REF]",
    help="report findings only for files changed vs REF (default HEAD) plus untracked "
    "files — the fast pre-push path; the whole-program index still covers all PATHS",
)
@click.option(
    "--baseline",
    default=None,
    metavar="FILE",
    help="JSON baseline of known findings: matched findings are reported as baselined "
    "(and do not fail the gate), only new ones count — composes with --changed-only "
    "and --format sarif (baselineState)",
)
@click.option(
    "--update-baseline",
    is_flag=True,
    default=False,
    help="record the run's findings to --baseline FILE (then report zero new)",
)
def lint(
    paths: "tuple[str, ...]",
    format_: str,
    select: Optional[str],
    ignore: Optional[str],
    show_suppressed: bool,
    changed_only: Optional[str],
    baseline: Optional[str],
    update_baseline: bool,
) -> None:
    """Run tpu-lint, the TPU/concurrency-aware static analyzer (TPU001-TPU019).

    Per-file rules check for host syncs inside jit-compiled functions,
    use-after-donate, unlocked mutation of lock-guarded state, blocking calls
    in serving handlers/engine loops, bare env-var numeric parses, wall-clock
    time.time() in duration/deadline arithmetic, *_locked helpers called
    without holding the lock, threads started in closeable classes but never
    joined, and unbounded per-key registries. Whole-program rules over the
    cross-module project index detect lock-order cycles (TPU010), recompile
    hazards at jit static positions (TPU011), and contextvar reads behind
    executor/thread hops without ctx.run (TPU012); TPU001/TPU002 follow jit
    reachability and donation across modules through the same index. A
    per-function CFG + dataflow layer adds the exception-path rules:
    resource leaks when a call raises between acquire and release (TPU016),
    tenant charges with no refund on the error path (TPU017), locks held
    across generator yields (TPU018), and early returns that skip a release
    (TPU019). PATHS
    defaults to ``unionml_tpu``; exits 0 when clean, 1 on findings, 2 on
    usage/parse errors. Also runnable as ``python -m unionml_tpu.analysis``.
    """
    from unionml_tpu.analysis.engine import main as lint_main

    argv = list(paths) + ["--format", format_]
    if select:
        argv += ["--select", select]
    if ignore:
        argv += ["--ignore", ignore]
    if show_suppressed:
        argv.append("--show-suppressed")
    if changed_only:
        argv += ["--changed-only", changed_only]
    if baseline:
        argv += ["--baseline", baseline]
    if update_baseline:
        argv.append("--update-baseline")
    sys.exit(lint_main(argv))


@app.command("deploy")
@click.argument("app_ref", metavar="APP")
@click.option("--app-version", default=None, help="app version; defaults to the git HEAD sha")
@click.option("--allow-uncommitted", is_flag=True, default=False, help="deploy with uncommitted changes")
@click.option("--patch", is_flag=True, default=False, help="fast re-registration: re-ship source only")
def deploy(app_ref: str, app_version: Optional[str], allow_uncommitted: bool, patch: bool) -> None:
    """Deploy a model's train/predict services to the backend (reference cli.py:54-82)."""
    model = _locate_model(app_ref)
    version = model.remote_deploy(app_version=app_version, allow_uncommitted=allow_uncommitted, patch=patch)
    click.echo(f"Deployed {app_ref} version {version}")


@app.command("train")
@click.argument("app_ref", metavar="APP")
@click.option("--inputs", "-i", default=None, help="training inputs as a JSON object")
@click.option("--app-version", default=None, help="app version to run; defaults to latest deployed")
def train(app_ref: str, inputs: Optional[str], app_version: Optional[str]) -> None:
    """Train a model on the backend (reference cli.py:85-103)."""
    model = _locate_model(app_ref)
    parsed = _parse_json_option(inputs, "--inputs") or {}
    click.echo(f"Training {model.name}")
    model.remote_train(app_version=app_version, wait=True, **parsed)
    assert model.artifact is not None
    click.echo("Done.")
    click.echo(f"Model: {model.artifact.model_object}")
    click.echo(f"Metrics: {model.artifact.metrics}")


@app.command("predict")
@click.argument("app_ref", metavar="APP")
@click.option("--inputs", "-i", default=None, help="prediction inputs (reader kwargs) as a JSON object")
@click.option(
    "--features",
    "-f",
    default=None,
    type=click.Path(exists=True, dir_okay=False, path_type=Path),
    help="generate predictions from a JSON file of features",
)
@click.option("--app-version", default=None, help="app version to run; defaults to latest deployed")
@click.option("--model-version", default="latest", show_default=True, help="model version to predict with")
def predict(
    app_ref: str,
    inputs: Optional[str],
    features: Optional[Path],
    app_version: Optional[str],
    model_version: str,
) -> None:
    """Generate predictions on the backend (reference cli.py:106-127)."""
    model = _locate_model(app_ref)
    parsed_inputs = _parse_json_option(inputs, "--inputs") or {}
    parsed_features = json.loads(features.read_text()) if features is not None else None
    click.echo(f"Generating predictions with {model.name}")
    predictions = model.remote_predict(
        app_version=app_version,
        model_version=None if model_version == "latest" else model_version,
        wait=True,
        features=parsed_features,
        **parsed_inputs,
    )
    click.echo(f"Predictions: {predictions}")


@app.command("list-model-versions")
@click.argument("app_ref", metavar="APP")
@click.option("--app-version", default=None, help="app version; defaults to latest deployed")
@click.option("--limit", default=10, show_default=True, help="maximum number of versions to list")
def list_model_versions(app_ref: str, app_version: Optional[str], limit: int) -> None:
    """List all trained model versions, newest first (reference cli.py:130-144)."""
    model = _locate_model(app_ref)
    app_version = app_version or model._backend.latest_app_version(model)
    click.echo(f"Listing model versions for app {app_ref} (app version: {app_version})")
    for version in model.remote_list_model_versions(app_version=app_version, limit=limit):
        click.echo(f"- {version}")


@app.command("fetch-model")
@click.argument("app_ref", metavar="APP")
@click.option("--app-version", default=None, help="app version; defaults to latest deployed")
@click.option("--model-version", default="latest", show_default=True, help="model version to fetch")
@click.option(
    "--output-file",
    "-o",
    required=True,
    type=click.Path(dir_okay=False, path_type=Path),
    help="path to write the fetched model object to",
)
@click.option("--kwargs", default=None, help="JSON keyword arguments forwarded to the model saver")
def fetch_model(
    app_ref: str,
    app_version: Optional[str],
    model_version: str,
    output_file: Path,
    kwargs: Optional[str],
) -> None:
    """Fetch a trained model from the backend registry and save it locally
    (reference cli.py:147-164)."""
    model = _locate_model(app_ref)
    saver_kwargs = _parse_json_option(kwargs, "--kwargs") or {}
    model.artifact = model._backend.fetch_latest_artifact(
        model, app_version=app_version, model_version=model_version
    )
    model.save(output_file, **saver_kwargs)
    click.echo(f"Model saved to {output_file}")


@app.command("serve")
@click.argument("app_ref", metavar="APP")
@click.option("--model-path", default=None, type=click.Path(path_type=Path), help="path to the saved model object")
@click.option("--host", default="127.0.0.1", show_default=True)
@click.option("--port", default=8000, show_default=True, type=int)
@click.option("--remote", is_flag=True, default=False, help="load the model from the remote backend registry")
@click.option("--app-version", default=None, help="app version for --remote model loading")
@click.option("--model-version", default="latest", show_default=True, help="model version for --remote loading")
@click.option("--workers", default=1, show_default=True, type=int, help="server processes sharing the port (SO_REUSEPORT)")
@click.option(
    "--num-hosts", default=None, type=int,
    help="multi-host fleet serving (docs/serving.md 'Multi-host fleets'): total "
    "processes in the fleet. Host 0 serves the public HTTP front door and "
    "coordinates; hosts > 0 run their engines behind a loopback control server. "
    "Exported as UNIONML_TPU_NUM_PROCESSES before the app module imports",
)
@click.option(
    "--coordinator", default=None, metavar="HOST:PORT",
    help="jax.distributed coordinator address every fleet process rendezvouses "
    "at (required with --num-hosts > 1); exported as UNIONML_TPU_COORDINATOR "
    "before the app module imports — the same bootstrap job_runner uses for "
    "multi-host training",
)
@click.option(
    "--process-id", default=None, type=int,
    help="this process's id in [0, --num-hosts); exported as "
    "UNIONML_TPU_PROCESS_ID before the app module imports",
)
@click.option("--reload", "reload_", is_flag=True, default=False, help="restart the server when app source changes (development)")
@click.option(
    "--log-level",
    default=None,
    type=click.Choice(["debug", "info", "warning", "error"]),
    help="unionml-tpu logger level",
)
@click.option(
    "--max-inflight", default=None, type=int,
    help="concurrent-request admission cap; excess requests shed with 429 + Retry-After (0 = unbounded)",
)
@click.option(
    "--deadline-ms", default=None, type=float,
    help="server-default per-request deadline in ms; expired requests shed with 503 (0 = no default deadline)",
)
@click.option(
    "--max-deadline-ms", default=None, type=float,
    help="ceiling on client-requested X-Request-Deadline-Ms values",
)
@click.option(
    "--drain-timeout", default=None, type=float,
    help="seconds a SIGTERM-initiated graceful drain waits for in-flight requests/streams",
)
@click.option(
    "--dp-replicas", default=None, type=int,
    help="data-parallel replica engines for generation serving: each replica owns a TP submesh "
    "(or its own device) and requests route least-loaded-first (0 = derive from the mesh's "
    "data/fsdp axes)",
)
@click.option(
    "--replica-roles", default=None,
    help="disaggregated serving: per-role replica counts, e.g. 'prefill=1,decode=3' — "
    "prompts above --prefill-threshold prefill on a prefill-role replica and their KV "
    "blocks hand off to a decode replica at admission-complete (token-identical to a "
    "mixed replica, but resident decode streams never stall behind the prefill); "
    "implies the fleet size when --dp-replicas is unset",
)
@click.option(
    "--prefill-threshold", default=None, type=int,
    help="prompt length (tokens) at which an admission takes the prefill→decode "
    "handoff path (0 = every admission, once --replica-roles is set)",
)
@click.option(
    "--autoscale-high", default=None, type=float,
    help="elastic resize: per-replica load watermark above which the fleet adds a "
    "replica on a spare submesh at runtime (also triggered while any replica's SLO "
    "state is breach); 0/unset = autoscaler off",
)
@click.option(
    "--autoscale-low", default=None, type=float,
    help="per-replica load watermark below which the fleet drains one replica "
    "(zero in-flight streams lost); 0 = never scale down",
)
@click.option(
    "--autoscale-interval", default=None, type=float,
    help="seconds between autoscaler evaluations of the fleet's windowed rates",
)
@click.option(
    "--min-replicas", default=None, type=int,
    help="fleet-size floor the autoscaler may never drain below",
)
@click.option(
    "--max-replicas", default=None, type=int,
    help="fleet-size ceiling for the autoscaler (0 = bounded by spare submeshes/devices)",
)
@click.option(
    "--admit-chunk", default=None, type=int,
    help="stall-free admission: slice each generation admission's prefill into this many "
    "tokens per chunk, interleaved with decode dispatches so long prompts never freeze "
    "resident streams (0 = monolithic admission unless the model config sets prefill_chunk)",
)
@click.option(
    "--prefill-budget", default=None, type=int,
    help="prefill tokens the continuous engine may run per iteration between decode "
    "dispatches (0 = one admission chunk)",
)
@click.option(
    "--max-admissions", default=None, type=int,
    help="concurrent partially-prefilled admissions in the continuous engine (0 = 1)",
)
@click.option(
    "--prefix-cache/--no-prefix-cache", "prefix_cache", default=None,
    help="radix prefix cache on continuous engines: prompts extending a "
    "previously-seen prefix (system prompt, multi-turn history) reuse its cached KV "
    "blocks and prefill only the suffix; off (the default) keeps today's behavior exactly",
)
@click.option(
    "--compile-cache", "compile_cache", default=None, metavar="DIR",
    help="persistent XLA compilation cache directory (exported as "
    "UNIONML_TPU_COMPILE_CACHE before the app module imports; '1' = the default "
    "location, '0' = off): re-runs of the same program load from disk instead of "
    "recompiling",
)
@click.option(
    "--aot-preload", "aot_preload", is_flag=False, flag_value="1", default=None,
    metavar="[DIR]",
    help="AOT program store for generation serving (bare flag = the default "
    "~/.cache/unionml_tpu/aot): warmup loads serialized executables instead of "
    "compiling — cold-start-to-first-token becomes load-bound — and every compile "
    "actually paid is serialized back for the next cold process; same early-export "
    "contract as --dp-replicas (UNIONML_TPU_AOT_PRELOAD)",
)
@click.option(
    "--quantize", default=None, type=click.Choice(["int8", "none"]),
    help="weight-only quantization for the app's serving Generators: int8 stores matmul "
    "kernels as int8 with per-channel scales (dequant fuses in-jit, so int8 is what "
    "crosses HBM — roughly 2x decode bandwidth); none forces full precision over an "
    "inherited UNIONML_TPU_QUANTIZE export",
)
@click.option(
    "--kv-cache-dtype", "kv_cache_dtype", default=None, type=click.Choice(["int8", "none"]),
    help="KV-cache storage dtype for generation serving: int8 stores K/V (the engine's "
    "page pools and a solo Generator's rows alike) symmetric-quantized per (position, head) with f32 "
    "scales — roughly doubling resident streams per chip; none forces the compute dtype",
)
@click.option(
    "--trace/--no-trace", "trace", default=None,
    help="record a per-request timeline (queue wait, routed replica, prefill chunks, "
    "emissions) into the flight recorder, served at /debug/requests; request ids flow "
    "and echo on every response regardless",
)
@click.option(
    "--flight-recorder-size", default=None, type=int,
    help="completed request timelines the flight recorder retains (ring buffer)",
)
@click.option(
    "--log-format", default=None, type=click.Choice(["text", "json"]),
    help="log line format; json emits structured lines carrying the request id and "
    "turns on the per-request access log",
)
@click.option(
    "--profile-dir", default=None, type=click.Path(file_okay=False, path_type=Path),
    help="directory for on-demand POST /debug/profile jax.profiler captures "
    "(unset disables the endpoint)",
)
@click.option(
    "--record-traffic", "record_traffic", default=None,
    type=click.Path(file_okay=False, path_type=Path),
    help="capture live /v1 and /predict-stream traffic into replayable ndjson "
    "traces in this directory (docs/workloads.md); replay them with "
    "`unionml-tpu replay`",
)
@click.option(
    "--record-traffic-hash", "record_traffic_hash", is_flag=True, default=False,
    help="record prompt SHA-256 digests + lengths instead of token ids "
    "(privacy posture for traces that leave the machine); the replayer "
    "regenerates deterministic same-length prompts",
)
@click.option(
    "--slo-ttft-p95-ms", default=None, type=float,
    help="SLO: time-to-first-token p95 target in ms, evaluated with multi-window "
    "burn rates (ok/warn/breach on /healthz); breaching requests pin their "
    "timelines as exemplars and the replica scheduler routes around a breaching "
    "replica (0 = disarmed)",
)
@click.option(
    "--slo-tbt-p99-ms", default=None, type=float,
    help="SLO: time-between-tokens p99 target in ms (0 = disarmed)",
)
@click.option(
    "--slo-shed-ratio", default=None, type=float,
    help="SLO: tolerated fraction of arrivals shed with 429/503 over the burn-rate "
    "windows, e.g. 0.01 (0 = disarmed)",
)
@click.option(
    "--tenant-config", default=None, type=click.Path(path_type=Path),
    help="multi-tenant QoS: tenants.json with per-tenant fair-share weights, "
    "req/s + generated-tokens/s bucket rates, default priority tiers, and "
    "api-key -> tenant mappings; identified tenants are admitted "
    "deficit-round-robin and bucket-limited (429 + Retry-After from the "
    "bucket's refill time)",
)
@click.option(
    "--default-tenant-rate", default=None, type=float,
    help="req/s bucket rate for identified tenants not named in --tenant-config "
    "(anonymous traffic is never bucket-limited); 0 = unlimited",
)
@click.option(
    "--fault-plan", "fault_plan", default=None, metavar="PLAN",
    help="deterministic fault injection (docs/serving.md 'Fault tolerance'): "
    "a FaultPlan JSON file (or the JSON inline) of seeded worker_kill/"
    "rpc_drop/rpc_delay/stream_cut events keyed on virtual time and host id; "
    "exported as UNIONML_TPU_FAULT_PLAN before the app module imports",
)
@click.option(
    "--probe-interval", default=None, type=float,
    help="seconds between fleet reconciliation ticks (lease heartbeat, "
    "suspect/dead re-probes, rendezvous announce scans)",
)
@click.option(
    "--probation-probes", default=None, type=int,
    help="consecutive successful probes a returning host must pass in "
    "probation before it takes traffic again",
)
@click.option(
    "--lease-ttl", default=None, type=float,
    help="coordinator heartbeat-lease TTL in seconds; workers promote the "
    "lowest-id live worker when the lease expires",
)
def serve(
    app_ref: str,
    model_path: Optional[Path],
    host: str,
    port: int,
    remote: bool,
    app_version: Optional[str],
    model_version: str,
    workers: int,
    num_hosts: Optional[int],
    coordinator: Optional[str],
    process_id: Optional[int],
    reload_: bool,
    log_level: Optional[str],
    max_inflight: Optional[int],
    deadline_ms: Optional[float],
    max_deadline_ms: Optional[float],
    drain_timeout: Optional[float],
    dp_replicas: Optional[int],
    replica_roles: Optional[str],
    prefill_threshold: Optional[int],
    autoscale_high: Optional[float],
    autoscale_low: Optional[float],
    autoscale_interval: Optional[float],
    min_replicas: Optional[int],
    max_replicas: Optional[int],
    admit_chunk: Optional[int],
    prefill_budget: Optional[int],
    max_admissions: Optional[int],
    prefix_cache: Optional[bool],
    compile_cache: Optional[str],
    aot_preload: Optional[str],
    quantize: Optional[str],
    kv_cache_dtype: Optional[str],
    trace: Optional[bool],
    flight_recorder_size: Optional[int],
    log_format: Optional[str],
    profile_dir: Optional[Path],
    record_traffic: Optional[Path],
    record_traffic_hash: bool,
    slo_ttft_p95_ms: Optional[float],
    slo_tbt_p99_ms: Optional[float],
    slo_shed_ratio: Optional[float],
    tenant_config: Optional[Path],
    default_tenant_rate: Optional[float],
    fault_plan: Optional[str],
    probe_interval: Optional[float],
    probation_probes: Optional[int],
    lease_ttl: Optional[float],
) -> None:
    """Start the HTTP prediction service (reference cli.py:172-205).

    The reference clones uvicorn's CLI (workers/reload/log config included) and
    injects ``--model-path`` via the ``UNIONML_MODEL_PATH`` env var, refusing to
    run when the variable is pre-set (cli.py:187-202); identical semantics here,
    on our own server. ``--workers N`` forks N processes sharing the port via
    SO_REUSEPORT — right for host-side (sklearn) predictors; a TPU predictor
    should stay at 1 worker and scale through micro-batching, since the chip is
    a single shared resource. ``--reload`` watches the app module's directory
    and restarts on change.

    Overload knobs (docs/serving.md "Serving under load"): ``--max-inflight``
    caps concurrently executing requests (excess shed 429 + Retry-After),
    ``--deadline-ms``/``--max-deadline-ms`` bound per-request deadlines
    (expired work shed 503), and ``--drain-timeout`` bounds the SIGTERM
    graceful drain (readiness flips, in-flight streams finish, then exit).

    ``--dp-replicas N`` (docs/serving.md "Data-parallel serving") replicates
    the app's continuous generation engine N ways — one TP submesh (or device)
    per replica, least-loaded routing, per-replica occupancy on ``/metrics``.
    Exported as an env var BEFORE the app module imports, so engines built at
    import time replicate too.

    ``--replica-roles`` (docs/serving.md "Disaggregated and elastic serving")
    splits the fleet DistServe-style: prompts at least ``--prefill-threshold``
    tokens long prefill on a prefill-role replica and their finished KV
    blocks hand off to a decode replica — token-identical to a mixed replica,
    with resident decode streams never stalling behind a long prefill.
    ``--autoscale-high``/``--autoscale-low`` arm the elastic resize loop:
    above the high watermark (or while any replica's SLO state is breach) a
    replica is added on a spare submesh at runtime, below the low watermark
    one drains with zero in-flight streams lost, bounded by
    ``--min-replicas``/``--max-replicas`` and evaluated every
    ``--autoscale-interval`` seconds. All exported before the app module
    imports, like ``--dp-replicas``.

    ``--admit-chunk`` / ``--prefill-budget`` / ``--max-admissions``
    (docs/serving.md "Stall-free admission") chunk the continuous engine's
    admission prefill and interleave it with decode, bounding resident
    streams' time-between-tokens at ~one chunk while a long prompt admits;
    same early-export contract as ``--dp-replicas``.

    ``--prefix-cache`` (docs/serving.md "Prefix caching") enables the radix
    prefix cache on continuous engines: any prompt extending a
    previously-seen prefix skips prefill for the cached portion, bit-identical
    to a cold prefill; same early-export contract as ``--dp-replicas``.

    ``--quantize int8`` / ``--kv-cache-dtype int8`` (docs/serving.md
    "Quantized serving") store serving weights and the KV cache as int8 —
    decode is HBM-bandwidth bound, so both roughly halve bytes per step, and
    int8 paged pools roughly double resident streams per chip. Exported as
    ``UNIONML_TPU_QUANTIZE``/``UNIONML_TPU_KV_CACHE_DTYPE`` before the app
    module imports; Generators built by app code resolve them at construction,
    so existing apps quantize with zero code changes. ``none`` forces full
    precision over an inherited export. Composes with ``--prefix-cache``
    (cached int8 blocks replay bit-identically) and ``--dp-replicas`` (each
    replica quantizes its own placement).

    Cold start (docs/serving.md "Cold start and AOT preload"):
    ``--compile-cache DIR`` points JAX's persistent compilation cache at a
    directory so identical programs skip XLA recompilation across processes,
    and ``--aot-preload [DIR]`` arms the AOT program store — serving warmup
    then *loads* serialized generator executables (prefill per bucket,
    decode, admission scatter/gather) instead of compiling them, making
    cold-start-to-first-token load-bound; compiles actually paid are
    serialized back for the next cold process, ``scale_to`` scale-ups onto a
    previously-used submesh join without a fresh XLA trace, and the
    serverless handler restores its programs on the first invocation. Both
    exported before the app module imports, like ``--dp-replicas``.

    Observability (docs/observability.md): ``--trace`` records per-request
    timelines into the flight recorder (``GET /debug/requests``,
    ``GET /debug/requests/<id>``), ``--flight-recorder-size`` bounds the ring,
    ``--log-format json`` emits structured log lines carrying the request id,
    and ``--profile-dir`` enables on-demand ``POST /debug/profile`` captures.
    All exported as env vars before the app module imports, so engines and
    loggers built at import time see them.

    SLOs and fleet health (docs/observability.md "SLOs and fleet health"):
    ``--slo-ttft-p95-ms`` / ``--slo-tbt-p99-ms`` / ``--slo-shed-ratio``
    declare targets every continuous engine evaluates with multi-window burn
    rates (fast window pages, slow window confirms the trend) through an
    ok→warn→breach state machine. ``GET /healthz`` reports the fleet health
    score with per-replica windowed rates and SLO states, ``GET /debug/fleet``
    adds the routing view, requests that individually blow a target are pinned
    as exemplars at ``/debug/requests?slo=breach``, and the replica scheduler
    routes new work around a breaching replica. Same early-export contract as
    the other knobs (``UNIONML_TPU_SLO_*``).

    Multi-host fleets (docs/serving.md "Multi-host fleets"):
    ``--num-hosts N --coordinator HOST:PORT --process-id I`` runs this serve
    process as one member of an N-host fleet. Every process joins one
    jax.distributed runtime (the same bootstrap ``job_runner`` uses for
    multi-host training), process 0 serves the public HTTP front door with a
    FleetCoordinator routing over every host's engines — fleet-global
    prefix-cache routing, cross-host prefill→decode handoff of block-native
    KV pages, per-host sections on ``/metrics``/``/healthz``/``/debug/fleet``
    — and processes > 0 run their engines behind a loopback control server.
    Same early-export contract as ``--dp-replicas``
    (``UNIONML_TPU_COORDINATOR``/``NUM_PROCESSES``/``PROCESS_ID``).

    Fault tolerance (docs/serving.md "Fault tolerance"): ``--probe-interval``
    / ``--probation-probes`` / ``--lease-ttl`` tune the fleet coordinator's
    host lifecycle (a transport failure suspects a host, probation probes +
    warmup readmit it) and the coordinator heartbeat lease workers watch for
    failover; ``--fault-plan`` arms a deterministic chaos schedule
    (serving/faults.py) for drills and the ``fleet_chaos`` bench lane. Same
    early-export contract as ``--dp-replicas``.

    Multi-tenant QoS (docs/serving.md "Multi-tenant QoS"):
    ``--tenant-config tenants.json`` / ``--default-tenant-rate R`` arm the
    tenancy subsystem — tenant identity from ``X-Tenant-Id`` or the
    ``Authorization`` bearer key, per-tenant token buckets shedding 429 with
    a refill-derived ``Retry-After``, weighted-fair (deficit-round-robin)
    admission in the continuous engine, and ``X-Priority: high`` admissions
    that may preempt a lowest-priority resident (which resumes
    token-identically). The OpenAI-compatible ``POST /v1/completions`` /
    ``/v1/chat/completions`` routes are always served; the tenancy knobs
    make them multi-tenant. Same early-export contract as ``--dp-replicas``.
    """
    if num_hosts is not None or coordinator is not None or process_id is not None:
        # multi-host fleet bootstrap knobs: validate NOW (a typo'd explicit
        # flag is a usage error), then export before the app module imports so
        # engines built at import time see the multi-process runtime — the
        # --dp-replicas contract, shared with job_runner's training bootstrap
        from unionml_tpu import defaults as _defaults

        resolved_hosts = num_hosts if num_hosts is not None else 1
        if resolved_hosts < 1:
            raise click.ClickException("--num-hosts must be >= 1")
        if resolved_hosts > 1 and coordinator is None:
            raise click.ClickException(
                "--num-hosts > 1 needs --coordinator HOST:PORT (the jax.distributed rendezvous)"
            )
        if process_id is not None and not (0 <= process_id < resolved_hosts):
            raise click.ClickException(
                f"--process-id must be in [0, {resolved_hosts}); got {process_id}"
            )
        if num_hosts is not None:
            os.environ[_defaults.DISTRIBUTED_NUM_PROCESSES_ENV_VAR] = str(num_hosts)
        if coordinator is not None:
            os.environ[_defaults.DISTRIBUTED_COORDINATOR_ENV_VAR] = coordinator
        if process_id is not None:
            os.environ[_defaults.DISTRIBUTED_PROCESS_ID_ENV_VAR] = str(process_id)
    if dp_replicas is not None:
        if dp_replicas < 0:
            raise click.ClickException("--dp-replicas must be >= 0 (0 = derive from the mesh)")
        # before _locate_model: app modules often build their engines at import
        from unionml_tpu.defaults import SERVE_DP_REPLICAS_ENV_VAR

        os.environ[SERVE_DP_REPLICAS_ENV_VAR] = str(dp_replicas)
    if replica_roles is not None:
        # validate NOW (a typo'd explicit flag is a usage error, unlike an
        # inherited env, which the ReplicaSet degrades on with a warning),
        # then export before the app module imports — the --dp-replicas
        # contract
        from unionml_tpu import defaults as _defaults

        try:
            _defaults.parse_replica_roles(replica_roles)
        except ValueError as exc:
            raise click.ClickException(f"--replica-roles: {exc}")
        os.environ[_defaults.SERVE_REPLICA_ROLES_ENV_VAR] = replica_roles
    disagg_knobs = (
        ("--prefill-threshold", prefill_threshold, "SERVE_PREFILL_THRESHOLD_ENV_VAR", int),
        ("--autoscale-high", autoscale_high, "SERVE_AUTOSCALE_HIGH_ENV_VAR", float),
        ("--autoscale-low", autoscale_low, "SERVE_AUTOSCALE_LOW_ENV_VAR", float),
        ("--autoscale-interval", autoscale_interval, "SERVE_AUTOSCALE_INTERVAL_S_ENV_VAR", float),
        ("--min-replicas", min_replicas, "SERVE_MIN_REPLICAS_ENV_VAR", int),
        ("--max-replicas", max_replicas, "SERVE_MAX_REPLICAS_ENV_VAR", int),
    )
    if any(value is not None for _, value, _, _ in disagg_knobs):
        from unionml_tpu import defaults as _defaults

        for flag, value, env_name, cast in disagg_knobs:
            if value is None:
                continue
            floor = 1 if flag == "--min-replicas" else 0
            if value < floor:
                raise click.ClickException(f"{flag} must be >= {floor}")
            os.environ[getattr(_defaults, env_name)] = repr(cast(value))
    if prefix_cache is not None:
        # same early-export contract as --dp-replicas: engines built at
        # app-module import time must see the knob
        from unionml_tpu.defaults import SERVE_PREFIX_CACHE_ENV_VAR

        os.environ[SERVE_PREFIX_CACHE_ENV_VAR] = "1" if prefix_cache else "0"
    if compile_cache is not None or aot_preload is not None:
        # same early-export contract as --dp-replicas: engines (and the
        # package-import compile-cache hook in reload/fork children) must see
        # the knobs before the app module imports. --compile-cache also takes
        # effect NOW — this process's import hook already ran with the old env
        from unionml_tpu import defaults as _defaults

        if compile_cache is not None:
            os.environ[_defaults.SERVE_COMPILE_CACHE_ENV_VAR] = compile_cache
            if compile_cache.strip().lower() not in ("", "0", "false", "no", "off"):
                from unionml_tpu.compile_cache import enable_compile_cache

                try:
                    enable_compile_cache(compile_cache)
                except Exception as exc:
                    raise click.ClickException(f"--compile-cache {compile_cache}: {exc}")
        if aot_preload is not None:
            os.environ[_defaults.SERVE_AOT_PRELOAD_ENV_VAR] = aot_preload
    if quantize is not None or kv_cache_dtype is not None:
        # same early-export contract: Generators built at app-module import
        # time resolve these at construction ("none" exports too — it must
        # override an inherited fleet-wide env in reload/fork children)
        from unionml_tpu import defaults as _defaults

        if quantize is not None:
            os.environ[_defaults.SERVE_QUANTIZE_ENV_VAR] = quantize
        if kv_cache_dtype is not None:
            os.environ[_defaults.SERVE_KV_CACHE_DTYPE_ENV_VAR] = kv_cache_dtype
    admission_knobs = (
        ("--admit-chunk", admit_chunk, "SERVE_ADMIT_CHUNK_ENV_VAR"),
        ("--prefill-budget", prefill_budget, "SERVE_PREFILL_BUDGET_ENV_VAR"),
        ("--max-admissions", max_admissions, "SERVE_MAX_ADMISSIONS_ENV_VAR"),
    )
    if any(value is not None for _, value, _ in admission_knobs):
        from unionml_tpu import defaults as _defaults

        for flag, value, env_name in admission_knobs:
            if value is None:
                continue
            if value < 0:
                raise click.ClickException(f"{flag} must be >= 0 (0 = default)")
            # same early-export contract as --dp-replicas: engines built at
            # app-module import time must see the knobs
            os.environ[getattr(_defaults, env_name)] = str(value)
    slo_knobs = (
        ("--slo-ttft-p95-ms", slo_ttft_p95_ms, "SERVE_SLO_TTFT_P95_MS_ENV_VAR"),
        ("--slo-tbt-p99-ms", slo_tbt_p99_ms, "SERVE_SLO_TBT_P99_MS_ENV_VAR"),
        ("--slo-shed-ratio", slo_shed_ratio, "SERVE_SLO_SHED_RATIO_ENV_VAR"),
    )
    if any(value is not None for _, value, _ in slo_knobs):
        from unionml_tpu import defaults as _defaults

        for flag, value, env_name in slo_knobs:
            if value is None:
                continue
            if value < 0:
                raise click.ClickException(f"{flag} must be >= 0 (0 = disarmed)")
            # same early-export contract as --dp-replicas: every continuous
            # engine's SLO tracker reads the env at construction, so engines
            # built at app-module import time get the targets too
            os.environ[getattr(_defaults, env_name)] = repr(value)
    if tenant_config is not None or default_tenant_rate is not None:
        # same early-export contract as --dp-replicas: the serving app builds
        # its TenantRegistry from the env at construction, and reload/fork
        # children inherit the knobs
        from unionml_tpu import defaults as _defaults

        if tenant_config is not None:
            if not tenant_config.exists():
                raise click.ClickException(f"--tenant-config {tenant_config} does not exist")
            os.environ[_defaults.SERVE_TENANT_CONFIG_ENV_VAR] = str(tenant_config)
        if default_tenant_rate is not None:
            if default_tenant_rate < 0:
                raise click.ClickException("--default-tenant-rate must be >= 0 (0 = unlimited)")
            os.environ[_defaults.SERVE_DEFAULT_TENANT_RATE_ENV_VAR] = repr(default_tenant_rate)
    if (
        fault_plan is not None or probe_interval is not None
        or probation_probes is not None or lease_ttl is not None
    ):
        # fleet fault-tolerance knobs (docs/serving.md "Fault tolerance"):
        # validate NOW (a typo'd explicit flag is a usage error), then export
        # before the app module imports — the --dp-replicas contract
        from unionml_tpu import defaults as _defaults
        from unionml_tpu.serving.faults import FaultPlan as _FaultPlan

        if fault_plan is not None:
            try:
                if fault_plan.lstrip().startswith("{"):
                    _FaultPlan.parse(fault_plan)
                else:
                    _FaultPlan.load(fault_plan)
            except (OSError, ValueError) as exc:
                raise click.ClickException(f"--fault-plan: {exc}")
            os.environ[_defaults.SERVE_FAULT_PLAN_ENV_VAR] = fault_plan
        if probe_interval is not None:
            if probe_interval <= 0:
                raise click.ClickException("--probe-interval must be > 0 seconds")
            os.environ[_defaults.FLEET_PROBE_INTERVAL_S_ENV_VAR] = repr(probe_interval)
        if probation_probes is not None:
            if probation_probes < 1:
                raise click.ClickException("--probation-probes must be >= 1")
            os.environ[_defaults.FLEET_PROBATION_PROBES_ENV_VAR] = str(probation_probes)
        if lease_ttl is not None:
            if lease_ttl <= 0:
                raise click.ClickException("--lease-ttl must be > 0 seconds")
            os.environ[_defaults.FLEET_LEASE_TTL_S_ENV_VAR] = repr(lease_ttl)
    # observability knobs: same early-export contract as --dp-replicas (the
    # serving app reads them at construction; reload/fork children inherit)
    if trace is not None or flight_recorder_size is not None or profile_dir is not None:
        from unionml_tpu import defaults as _defaults

        if trace is not None:
            os.environ[_defaults.SERVE_TRACE_ENV_VAR] = "1" if trace else "0"
        if flight_recorder_size is not None:
            if flight_recorder_size < 1:
                raise click.ClickException("--flight-recorder-size must be >= 1")
            os.environ[_defaults.SERVE_FLIGHT_RECORDER_ENV_VAR] = str(flight_recorder_size)
        if profile_dir is not None:
            os.environ[_defaults.SERVE_PROFILE_DIR_ENV_VAR] = str(profile_dir)
    if record_traffic is not None:
        # same early-export contract: the ServingApp builds its TraceRecorder
        # from the env at construction (docs/workloads.md)
        from unionml_tpu import defaults as _defaults

        os.environ[_defaults.SERVE_RECORD_TRAFFIC_ENV_VAR] = str(record_traffic)
        if record_traffic_hash:
            os.environ[_defaults.SERVE_RECORD_TRAFFIC_HASH_ENV_VAR] = "1"
    if log_format is not None:
        from unionml_tpu import defaults as _defaults
        from unionml_tpu._logging import set_log_format

        set_log_format(log_format)
        os.environ[_defaults.SERVE_LOG_FORMAT_ENV_VAR] = log_format
    if log_level is not None:
        from unionml_tpu._logging import logger as package_logger

        package_logger.setLevel(log_level.upper())
        os.environ["UNIONML_TPU_LOGLEVEL"] = log_level.upper()  # reload/fork children inherit it
    if model_path is not None:
        if os.getenv(MODEL_PATH_ENV_VAR) is not None:
            raise click.ClickException(
                f"{MODEL_PATH_ENV_VAR} environment variable is already set, which takes precedence "
                "over the --model-path option. Unset it to use --model-path."
            )
        if not model_path.exists():
            raise click.ClickException(f"model path {model_path} does not exist")
        os.environ[MODEL_PATH_ENV_VAR] = str(model_path)

    if reload_:
        _serve_with_reload(app_ref)
        return

    target = _locate_model(app_ref)
    from unionml_tpu.serving import ServingApp

    if isinstance(target, ServingApp):
        serving = target
    else:
        serving = target.serve(remote=remote, app_version=app_version, model_version=model_version)
    serving.configure_overload(
        max_inflight=max_inflight,
        default_deadline_ms=deadline_ms,
        max_deadline_ms=max_deadline_ms,
        drain_timeout_s=drain_timeout,
    ).configure_replicas(
        dp_replicas, replica_roles=replica_roles, prefill_threshold=prefill_threshold
    ).configure_quantization(
        quantize=quantize, kv_cache_dtype=kv_cache_dtype
    ).configure_cold_start(
        compile_cache=compile_cache, aot_preload=aot_preload
    ).configure_observability(
        trace=trace,
        flight_recorder_size=flight_recorder_size,
        log_format=log_format,
        profile_dir=str(profile_dir) if profile_dir is not None else None,
    ).configure_tenancy(
        tenant_config=str(tenant_config) if tenant_config is not None else None,
        default_tenant_rate=default_tenant_rate,
    )

    from unionml_tpu.defaults import distributed_num_processes

    if distributed_num_processes() > 1:
        # multi-host fleet: host 0 serves the public front door over a
        # FleetCoordinator; hosts > 0 run only the control server. --workers
        # forking doesn't compose with a per-process jax runtime.
        if workers > 1:
            raise click.ClickException("--workers does not compose with --num-hosts; scale via hosts")
        from unionml_tpu.serving.cluster import enable_serve_cluster

        enable_serve_cluster(serving, host=host, port=port)
        return

    if workers > 1:
        import signal

        # load the artifact once, then fork: children inherit it copy-on-write and
        # the kernel balances accepted connections across the shared port
        serving.startup()
        children: "list[int]" = []
        for _ in range(workers - 1):
            pid = os.fork()
            if pid == 0:
                serving.run(host=host, port=port, reuse_port=True)
                os._exit(0)
            children.append(pid)

        def stop_children(signum=None, frame=None):
            # killing the parent must not orphan workers holding the port
            for child_pid in children:
                try:
                    os.kill(child_pid, signal.SIGTERM)
                except ProcessLookupError:
                    pass
            for child_pid in children:
                try:
                    os.waitpid(child_pid, 0)
                except ChildProcessError:
                    pass
            if signum is not None:
                raise SystemExit(0)

        signal.signal(signal.SIGTERM, stop_children)
        try:
            serving.run(host=host, port=port, reuse_port=True)
        finally:
            stop_children()
    else:
        serving.run(host=host, port=port)


@app.command("replay")
@click.argument("trace", metavar="TRACE")
@click.option(
    "--target", default=None, metavar="URL",
    help="replay against a live server (base URL, e.g. http://127.0.0.1:8000)",
)
@click.option(
    "--self-host", "self_host", default=None, metavar="APP",
    help="host the app in-process (module:variable of a Model or ServingApp — "
    "the `serve` APP argument) and replay through its HTTP dispatch surface",
)
@click.option(
    "--model-path", default=None, type=click.Path(path_type=Path),
    help="path to the saved model object for --self-host (the serve contract)",
)
@click.option("--seed", default=0, show_default=True, type=int,
              help="scenario seed for a scenario:<name> TRACE")
@click.option("--rate-scale", default=1.0, show_default=True, type=float,
              help="compress (>1) or stretch (<1) the trace's arrival schedule")
@click.option("--concurrency", default=32, show_default=True, type=int,
              help="in-flight request cap (hitting it reads as schedule lag)")
@click.option("--grace-ms", default=250.0, show_default=True, type=float,
              help="launch-lag tolerance counted as schedule-adherent")
@click.option(
    "--out", default=None, type=click.Path(dir_okay=False, path_type=Path),
    help="write the report JSON here as well as stdout",
)
@click.option(
    "--fault-plan", "fault_plan", default=None, metavar="PLAN",
    help="chaos mode (--self-host only): arm this FaultPlan (JSON file or "
    "inline) on the app's fleet coordinator when the replay starts, and add "
    "the availability section (success/clean-error ratios, per-fault "
    "recovery-to-first-routed-token) to the report",
)
def replay_cmd(
    trace: str,
    target: Optional[str],
    self_host: Optional[str],
    model_path: Optional[Path],
    seed: int,
    rate_scale: float,
    concurrency: int,
    grace_ms: float,
    out: Optional[Path],
    fault_plan: Optional[str],
) -> None:
    """Replay a traffic trace through the real HTTP stack and judge it.

    TRACE is a trace file (``serve --record-traffic`` output, or
    ``write_trace``), or ``scenario:<name>`` for a library mix
    (``scenario:chat_multiturn``, ``scenario:rag_long_prompt``,
    ``scenario:burst_tenants``, ``scenario:deadline_heavy``) synthesized
    deterministically from ``--seed``. Exactly one of ``--target`` (live
    server over sockets) or ``--self-host`` (in-process ServingApp, the
    serving-test dispatch surface) selects the system under test.

    The report (stdout, and ``--out``) carries per-request-derived per-tenant
    TTFT/TBT/e2e/shed aggregates, wall-clock schedule adherence, and — for
    scenario traces, whose library declares per-tenant SLO targets — a
    verdict block (pass/warn/breach with burn rates). Exit code 1 when any
    judged tenant breaches: a replay run is a judgment, not just numbers
    (docs/workloads.md)."""
    from unionml_tpu.workloads import (
        read_trace,
        replay,
        scenario_meta,
        scenario_targets,
        synthesize,
    )

    if (target is None) == (self_host is None):
        raise click.ClickException("pass exactly one of --target URL or --self-host APP")
    if trace.startswith("scenario:"):
        name = trace.split(":", 1)[1]
        try:
            requests = synthesize(name, seed)
            targets = scenario_targets(name)
            meta = scenario_meta(name, seed)
        except ValueError as exc:
            raise click.ClickException(str(exc))
    else:
        try:
            meta, requests = read_trace(trace)
        except (OSError, ValueError) as exc:
            raise click.ClickException(f"could not read trace {trace!r}: {exc}")
        # a synthesized trace file remembers its scenario: reuse its targets
        targets = None
        if meta.get("scenario"):
            try:
                targets = scenario_targets(str(meta["scenario"]))
            except ValueError:
                targets = None
    plan = None
    if fault_plan is not None:
        if self_host is None:
            raise click.ClickException(
                "--fault-plan needs --self-host (the plan arms the app's own fleet "
                "coordinator; a --target server arms its own via serve --fault-plan)"
            )
        from unionml_tpu.serving.faults import FaultPlan

        try:
            if fault_plan.lstrip().startswith("{"):
                plan = FaultPlan.parse(fault_plan)
            else:
                plan = FaultPlan.load(fault_plan)
        except (OSError, ValueError) as exc:
            raise click.ClickException(f"--fault-plan: {exc}")
    serving = None
    if self_host is not None:
        if model_path is not None:
            if os.getenv(MODEL_PATH_ENV_VAR) is not None:
                raise click.ClickException(
                    f"{MODEL_PATH_ENV_VAR} is already set and takes precedence over "
                    "--model-path; unset it first"
                )
            if not model_path.exists():
                raise click.ClickException(f"model path {model_path} does not exist")
            os.environ[MODEL_PATH_ENV_VAR] = str(model_path)
        located = _locate_model(self_host)
        from unionml_tpu.serving import ServingApp

        serving = located if isinstance(located, ServingApp) else located.serve()
        serving.startup()
    fault_times = None
    if plan is not None:
        engine = getattr(serving.model, "generation_batcher", None)
        arm = getattr(engine, "arm_faults", None)
        if not callable(arm):
            raise click.ClickException(
                "--fault-plan needs a fleet coordinator behind the app "
                "(serve --num-hosts; a single-engine app has no host lifecycle to chaos)"
            )
        arm(plan)  # virtual time starts now — the replay launches immediately
        fault_times = plan.fault_times()
    report = replay(
        requests,
        app=serving,
        target=target,
        concurrency=concurrency,
        rate_scale=rate_scale,
        grace_s=grace_ms / 1000.0,
        targets=targets,
        meta=meta,
        fault_times_s=fault_times,
    )
    rendered = json.dumps(report, indent=2)
    click.echo(rendered)
    if out is not None:
        out.write_text(rendered)
    if report.get("verdict_state") == "breach":
        raise SystemExit(1)


def _app_source_files(app_ref: str) -> "dict[Path, float]":
    """Snapshot mtimes of every .py under the app module's directory."""
    module_name = app_ref.split(":", 1)[0]
    import importlib.util

    spec = importlib.util.find_spec(module_name)
    root = Path(spec.origin).parent if spec and spec.origin else Path.cwd()
    return {p: p.stat().st_mtime for p in root.rglob("*.py") if ".git" not in p.parts}


def _serve_with_reload(app_ref: str, poll_interval: float = 0.5) -> None:
    """Run the server as a child process; restart it when app source changes."""
    import signal
    import subprocess
    import time

    # re-exec through the interpreter: argv[0] may be a module path (python -m)
    # that is not itself executable. --model-path is dropped from the child argv:
    # the parent already validated it and exported UNIONML_MODEL_PATH, which
    # the child inherits (passing both would trip the env-var guard).
    argv = [sys.executable]
    skip_next = False
    for arg in sys.argv:
        if skip_next:
            skip_next = False
            continue
        if arg == "--reload":
            continue
        if arg == "--model-path":
            skip_next = True
            continue
        if arg.startswith("--model-path="):
            continue
        argv.append(arg)
    current: "list[Any]" = [None]

    def forward_term(signum, frame):  # terminating the reload parent must stop the server
        if current[0] is not None and current[0].poll() is None:
            current[0].terminate()
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, forward_term)

    def stop_child(child) -> None:
        child.send_signal(signal.SIGTERM)
        try:
            child.wait(timeout=10)
        except subprocess.TimeoutExpired:  # slow drain / ignored SIGTERM
            child.kill()
            child.wait()

    while True:
        snapshot = _app_source_files(app_ref)
        child = subprocess.Popen(argv, env=os.environ)
        current[0] = child
        try:
            while child.poll() is None:
                time.sleep(poll_interval)
                if _app_source_files(app_ref) != snapshot:
                    click.echo("source change detected; restarting server", err=True)
                    stop_child(child)
                    break
            else:
                if child.returncode == 0:
                    sys.exit(0)  # clean self-exit
                # crashed (e.g. a transient syntax error was saved): keep watching
                # and respawn on the NEXT source change, like uvicorn's reloader
                click.echo(
                    f"server exited with code {child.returncode}; waiting for a source change",
                    err=True,
                )
                while _app_source_files(app_ref) == snapshot:
                    time.sleep(poll_interval)
                click.echo("source change detected; restarting server", err=True)
        except KeyboardInterrupt:  # pragma: no cover
            stop_child(child)
            raise


def main() -> None:  # console-script entry point (reference setup.py:34)
    app()


if __name__ == "__main__":
    main()
