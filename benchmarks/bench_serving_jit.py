"""Serving benchmark, jitted-predictor path (SURVEY.md §7 hard part 4).

Where ``bench_serving.py`` measures the host-side (sklearn) predictor, this
config serves a jax MLP through the :class:`CompiledPredictor` stack:
pad-to-bucket + per-bucket jit cache + AOT warmup + micro-batching. The parent
process never initializes a jax backend — the raw-throughput baseline runs in
its own subprocess that exits before the server starts, so on TPU (where the
device is single-process-exclusive) the server can acquire it. After the load
run, the in-server ``/metrics`` endpoint supplies the authoritative p50/p99 and
the predictor trace count — the bounded-compile guarantee
(traces == len(BUCKET_SIZES)) is asserted, not assumed.

Metric: req/s; ``vs_baseline`` = ratio to the raw in-process predict loop doing
the same per-request work (feature framing + predict). Above 1.0 means the
micro-batcher's coalesced dispatches beat sequential in-process calls.
Prints ONE JSON line.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.common import (
    Timer,
    emit,
    free_port,
    log,
    run_closed_loop_clients,
    wait_for_health,
)

CLIENTS = 16
DURATION_S = 10.0
FEATURES = 16
ROWS_PER_REQUEST = 8
BUCKET_SIZES = [8, 32, 128]

APP = textwrap.dedent(
    f"""
    from typing import Any, Dict, List

    import jax
    import jax.numpy as jnp
    import numpy as np
    import pandas as pd

    from unionml_tpu import Dataset, Model
    from unionml_tpu.serving import ServingConfig

    FEATURES = {FEATURES}

    dataset = Dataset(name="jit_serving_data", targets=["y"], test_size=0.2)

    @dataset.reader
    def reader(n: int = 256) -> pd.DataFrame:
        rng = np.random.default_rng(0)
        frame = pd.DataFrame(
            rng.normal(size=(n, FEATURES)).astype("float32"),
            columns=[f"f{{i}}" for i in range(FEATURES)],
        )
        frame["y"] = (frame.sum(axis=1) > 0).astype("int32")
        return frame

    def init(hyperparameters: Any = None) -> Dict[str, Any]:
        rng = np.random.default_rng(1)
        return {{
            "w1": rng.normal(size=(FEATURES, 64)).astype("float32") * 0.1,
            "w2": rng.normal(size=(64, 2)).astype("float32") * 0.1,
        }}

    model = Model(name="jit_serving_model", init=init, dataset=dataset)
    model.__app_module__ = "app:model"

    @model.trainer
    def trainer(params: Dict[str, Any], features: pd.DataFrame, target: pd.DataFrame) -> Dict[str, Any]:
        return params  # serving benchmark: the artifact just needs to exist

    @model.predictor(
        config=ServingConfig(
            max_batch_size={max(BUCKET_SIZES)},
            max_wait_ms=1.0,
            bucket_sizes={BUCKET_SIZES},
            feature_shape=(FEATURES,),
        )
    )
    def predictor(params: Dict[str, Any], features: Any) -> list:
        h = jnp.maximum(features @ params["w1"], 0.0)
        return jnp.argmax(h @ params["w2"], axis=-1)

    @model.evaluator
    def evaluator(params: Dict[str, Any], features: pd.DataFrame, target: pd.DataFrame) -> float:
        return 0.0
    """
)

# trains + saves the artifact and measures the raw in-process predict loop —
# the SAME work the server does per request (feature framing + jitted predict),
# so vs_baseline isolates the HTTP + batching delta. Runs in a subprocess that
# exits before the server starts (single-process TPU exclusivity).
RAW_BASELINE = textwrap.dedent(
    """
    import json
    import sys
    import time

    import jax

    import app

    records = json.loads(sys.argv[2])
    app.model.train()
    app.model.save(sys.argv[1])
    app.model.predict(features=records)  # warm the bucket
    n = 300
    start = time.perf_counter()
    for _ in range(n):
        app.model.predict(features=records)
    elapsed = time.perf_counter() - start
    print(f"RAW_RPS {n / elapsed} {jax.devices()[0].platform}", flush=True)
    """
)

SERVE = textwrap.dedent(
    """
    import sys

    import app

    app.model.load(sys.argv[1])
    app.model.serve().run(port=int(sys.argv[2]))
    """
)


def main() -> None:
    import tempfile

    import numpy as np

    workdir = Path(tempfile.mkdtemp(prefix="unionml_tpu_bench_serving_jit"))
    (workdir / "app.py").write_text(APP)
    (workdir / "raw_baseline.py").write_text(RAW_BASELINE)
    (workdir / "serve.py").write_text(SERVE)
    repo_root = str(Path(__file__).resolve().parent.parent)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(filter(None, [repo_root, str(workdir), env.get("PYTHONPATH", "")]))

    rng = np.random.default_rng(2)  # one rng: rows must be DISTINCT draws
    records = [
        {f"f{i}": float(v) for i, v in enumerate(rng.normal(size=FEATURES))}
        for _ in range(ROWS_PER_REQUEST)
    ]
    model_path = str(workdir / "model.bin")

    raw = subprocess.run(
        [sys.executable, str(workdir / "raw_baseline.py"), model_path, json.dumps(records)],
        env=env,
        stdout=subprocess.PIPE,
        text=True,
        timeout=600,
    )
    if raw.returncode != 0:
        raise RuntimeError(f"raw baseline failed rc={raw.returncode}")
    _, raw_rps_str, platform = next(
        line.split() for line in raw.stdout.splitlines() if line.startswith("RAW_RPS")
    )
    raw_rps = float(raw_rps_str)
    log(f"raw in-process jitted predict: {raw_rps:.0f} req/s on {platform} ({ROWS_PER_REQUEST} rows/req)")

    port = free_port()
    server_log = workdir / "server.log"
    with open(server_log, "w") as log_file:
        proc = subprocess.Popen(
            [sys.executable, str(workdir / "serve.py"), model_path, str(port)],
            env=env,
            stdout=log_file,
            stderr=subprocess.STDOUT,
        )
    try:
        base = f"http://127.0.0.1:{port}"
        wait_for_health(base, diagnostics=lambda: server_log.read_text()[-2000:])

        with Timer() as t:
            latencies = run_closed_loop_clients(
                port, json.dumps({"features": records}), clients=CLIENTS, duration_s=DURATION_S
            )
        n = len(latencies)
        rps = n / t.elapsed

        import urllib.request

        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            server_metrics = json.loads(resp.read())
        predict_stats = server_metrics["routes"]["POST /predict"]
        predictor_stats = server_metrics.get("predictor", {})
        traces = predictor_stats.get("traces")
        log(
            f"{n} requests in {t.elapsed:.1f}s: {rps:.0f} req/s; in-server p50 "
            f"{predict_stats['p50_ms']}ms p99 {predict_stats['p99_ms']}ms; "
            f"predictor traces={traces} eager={predictor_stats.get('eager_fallback')}"
        )
        if predictor_stats.get("eager_fallback"):
            raise RuntimeError("predictor fell back to eager — the jitted path was not measured")
        if traces is not None and traces > len(BUCKET_SIZES):
            raise RuntimeError(
                f"compile-count guarantee violated: {traces} traces for {len(BUCKET_SIZES)} buckets"
            )
    finally:
        proc.terminate()
        proc.wait(timeout=10)

    emit(
        "jit_serving_throughput",
        rps,
        "req/s",
        rps / raw_rps,
        p50_ms=predict_stats["p50_ms"],
        p99_ms=predict_stats["p99_ms"],
        predictor_traces=traces,
        concurrency=CLIENTS,
        rows_per_request=ROWS_PER_REQUEST,
        raw_inprocess_rps=raw_rps,
        platform=platform,
    )


if __name__ == "__main__":
    main()
