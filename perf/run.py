"""Run one cell of the benchmark once.

    python3 -m perf.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is data: ``BENCHMARK.json`` names it, ``perf/workloads/<name>.json``
holds its settings, ``perf/configs/<config>.json`` the model's sizes,
``perf/traffic/<traffic>.json`` the mix and, by its ``generator``, the
``perf/traffic/<generator>.py`` that turns it into work, ``perf/systems/<system>.py``
the way that kind of system is built and driven, and
``perf/layer_metrics/<metric>.py`` one reader per per-layer metric. Adding a cell, a configuration, a mix or a metric
adds files and entries; nothing here names one.

The run builds the model on the device from ``--seed``, warms the cell's shapes
(set-up), measures for ``--seconds``, frees the program's state, compares what
the timed path produced with the plain reference, prints each number compared
beside its limit, and prints one JSON object as its last line. Without a TPU, or
with another number of chips than the cell asks for, it exits non-zero and prints
no result. ``--rehearse`` walks the same flow at the tiny sizes in the files'
``rehearse`` sections on whatever backend there is; its line says
``"rehearsal": true`` under the device's real name and is never a measurement.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import traceback
import types
from typing import Any, Dict, List, Mapping, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perf")
#: build and run outputs (compile cache, traces): inside the checkout, at a fixed path, git-ignored
STATE = os.path.join(ROOT, ".perf_state")


def process_age_s() -> float:
    """Seconds since this process was started (the interpreter's start-up included)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def merge(base: Any, override: Any) -> Any:
    if isinstance(base, dict) and isinstance(override, dict):
        return {**base, **{k: merge(base.get(k), v) for k, v in override.items()}}
    return override


def load_json(*parts: str) -> Dict[str, Any]:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str, rehearse: bool) -> types.SimpleNamespace:
    """The cell, its configuration and its mix, found by name; with ``rehearse``
    each file's ``rehearse`` section is laid over it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"perf.run: no workload {name!r} in BENCHMARK.json")
    cell = load_json("workloads", f"{name}.json")
    config = load_json("configs", f"{entry['config']}.json")
    mix = load_json("traffic", f"{entry['traffic']}.json")
    if (cell["config"], cell["traffic"], cell["chips"]) != (entry["config"], entry["traffic"], entry["chips"]):
        raise SystemExit(f"perf.run: perf/workloads/{name}.json disagrees with BENCHMARK.json")
    if rehearse:
        cell, config, mix = (merge(d, d.get("rehearse", {})) for d in (cell, config, mix))

    def applies(metric: Mapping[str, Any]) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    return types.SimpleNamespace(
        bench=bench, cell=cell, config=config, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


class CompileMeter:
    """Backend compilations (persistent-cache reads included) and the seconds
    they took, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += seconds

    def _on_event(self, event: str, **_: Any) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def read_layer_metric(name: str, facts: Mapping[str, Any], trace: Optional[Mapping[str, Any]], peak: Optional[Mapping[str, float]]):
    path = os.path.join(HERE, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"perf.layer_metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(facts, trace, peak)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None, help="length of the measured window (default: run_seconds)")
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true", help="tiny sizes on any backend; never a measurement")
    parser.add_argument("--control", default=None, help="lower-precision control: the program's own path where it has one, else the reference's")
    parser.add_argument("--fault", default=None, help="break the timed path underneath (the benchmark's tests use it)")
    parser.add_argument("--set", action="append", default=[], metavar="FILE.KEY=JSON", help="override mix/cell/config values (sweeps; never in the driver's runs)")
    args = parser.parse_args(argv)

    loaded = load_cell(args.workload, args.rehearse)
    for item in args.set:
        target, _, value = item.partition("=")
        which, _, key = target.partition(".")
        getattr(loaded, which)[key] = json.loads(value)
    if args.seconds is None:
        args.seconds = float(loaded.bench["run_seconds"])
    chips = int(loaded.cell["chips"])

    os.makedirs(STATE, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    # JAX's persistent compilation cache, inside the checkout at a fixed path (the path is part of the key);
    # a cache directory given in the environment is the one the program would take too
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(STATE, "xla_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        if device["platform"] != "tpu":
            print(f"perf.run: no TPU (jax.devices()[0].platform == {device['platform']!r})", file=sys.stderr)
            return 2
        if len(devices) != chips:
            print(f"perf.run: the cell asks for {chips} chip(s), the host has {len(devices)}", file=sys.stderr)
            return 2

    from perf import compare, trace_reduce, work

    peak = work.peaks(device["kind"]) if device["platform"] == "tpu" else None  # a rehearsal reads no share of a peak
    meter = CompileMeter()
    trace_dir = os.path.join(STATE, "trace", args.workload)

    def start_trace() -> None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)

    def stop_trace() -> None:
        jax.profiler.stop_trace()

    def memory_peak_bytes() -> int:
        peaks_ = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices]
        return max(peaks_)

    ctx = types.SimpleNamespace(
        args=args, cell=loaded.cell, config=loaded.config, mix=loaded.mix, compile_meter=meter,
        traffic=importlib.import_module(f"perf.traffic.{loaded.mix['generator']}"),
        process_age_s=process_age_s, start_trace=start_trace, stop_trace=stop_trace, memory_peak_bytes=memory_peak_bytes,
    )
    system = importlib.import_module(f"perf.systems.{loaded.config['system']}")
    try:
        result = system.run(ctx)
    except Exception:
        traceback.print_exc()
        print("perf.run: the run failed before a result could be printed", file=sys.stderr)
        return 1

    # ---- the trace, reduced; its window is the device's own (first to last device event), not the host's clock
    # around the profiler's calls, which also counts the profiler's start-up
    trace = None
    if args.trace:
        files = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir) for f in fs if f.endswith(".xplane.pb")]
        if files:
            host_spans = list(loaded.cell.get("host_spans", []))
            events = trace_reduce.load(max(files, key=os.path.getsize), host_spans)
            trace = trace_reduce.summarize(events, host_spans)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- metrics
    facts = result["facts"]
    measured = dict(result["e2e"], setup_s=result["setup_s"])
    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        for metric in loaded.per_layer:
            value = read_layer_metric(metric["name"], facts, trace, peak)
            if value is not None:
                metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    else:
        for metric in loaded.end_to_end:
            if metric["name"] in measured:
                metrics[metric["name"]] = {"value": measured[metric["name"]], "unit": metric["unit"]}

    correct, compared = compare.verdict(result["numbers"])
    if result.get("compiles_in_window"):
        correct = False
        compared["compiles_in_window"] = {"value": result["compiles_in_window"], "limit": 0}
    early = dict(
        result["early"], workload=args.workload, seed=args.seed, seconds=args.seconds, trace=args.trace,
        setup_s=result["setup_s"], compile_s=meter.seconds, compiles=meter.count, cache_hits=meter.cache_hits,
        cache_dir=os.path.relpath(cache_dir, ROOT) if cache_dir.startswith(ROOT) else cache_dir,
        also={k: v for k, v in measured.items() if k not in metrics}, control=args.control, fault=args.fault,
        programs=(trace or {}).get("programs"),
    )
    print(json.dumps({"detail": early}, default=str), flush=True)
    device["memory_peak_bytes"] = int(result["memory_peak_bytes"])
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(result["attempted"]), "failed": int(result["failed"]),
        "metrics": metrics, "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10], "idle_gaps": trace["idle_gaps"][:10]}
    if args.rehearse:
        line["rehearsal"] = True
    line["compared"] = compared
    for name, entry in compared.items():
        print(f"compared {name}: value {entry['value']} limit {entry['limit']}", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
