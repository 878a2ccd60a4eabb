"""Config 1 (BASELINE.json): sklearn LogisticRegression digits — the reference README
quickstart app (reference README.md:56-101), run through the full spec layer.

Metric: trainer samples/sec through ``model.train`` (reader -> split -> parse ->
trainer -> evaluator on both splits). ``vs_baseline``: the same sklearn workload
executed directly (load_digits + train_test_split + fit + 2x score) — i.e. the
framework's spec/pipeline overhead; 1.0 means zero overhead.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import List

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pandas as pd
from sklearn.datasets import load_digits
from sklearn.linear_model import LogisticRegression
from sklearn.model_selection import train_test_split

from benchmarks.common import Timer, emit, log

MAX_ITER = 10000
TEST_SIZE = 0.2
REPEATS = 3


def build_app():
    from unionml_tpu import Dataset, Model

    dataset = Dataset(name="digits_dataset", test_size=TEST_SIZE, shuffle=True, random_state=42, targets=["target"])
    model = Model(name="digits_classifier", init=LogisticRegression, dataset=dataset)

    @dataset.reader
    def reader() -> pd.DataFrame:
        return load_digits(as_frame=True).frame

    @model.trainer
    def trainer(estimator: LogisticRegression, features: pd.DataFrame, target: pd.DataFrame) -> LogisticRegression:
        return estimator.fit(features, target.squeeze())

    @model.predictor
    def predictor(estimator: LogisticRegression, features: pd.DataFrame) -> List[float]:
        return [float(x) for x in estimator.predict(features)]

    @model.evaluator
    def evaluator(estimator: LogisticRegression, features: pd.DataFrame, target: pd.DataFrame) -> float:
        return float(estimator.score(features, target.squeeze()))

    return model


def bench_framework(model) -> float:
    best = float("inf")
    for _ in range(REPEATS):
        with Timer() as t:
            model.train(hyperparameters={"max_iter": MAX_ITER})
        best = min(best, t.elapsed)
    assert model.artifact.metrics["train"] == 1.0, model.artifact.metrics
    assert model.artifact.metrics["test"] >= 0.95, model.artifact.metrics
    return best


def bench_plain() -> float:
    frame = load_digits(as_frame=True).frame
    best = float("inf")
    for _ in range(REPEATS):
        with Timer() as t:
            train, test = train_test_split(frame, test_size=TEST_SIZE, shuffle=True, random_state=42)
            est = LogisticRegression(max_iter=MAX_ITER)
            est.fit(train.drop(columns=["target"]), train["target"])
            est.score(train.drop(columns=["target"]), train["target"])
            est.score(test.drop(columns=["target"]), test["target"])
        best = min(best, t.elapsed)
    return best


def main() -> None:
    model = build_app()
    n_train = int(1797 * (1 - TEST_SIZE))
    fw = bench_framework(model)
    plain = bench_plain()
    log(f"framework train: {fw:.3f}s, plain sklearn: {plain:.3f}s (overhead {fw - plain:+.3f}s)")

    # predict-from-features latency through the spec layer (the serving inner loop)
    records = load_digits(as_frame=True).frame.drop(columns=["target"]).head(8).to_dict(orient="records")
    model.predict(features=records)  # warm
    lat = []
    for _ in range(50):
        start = time.perf_counter()
        model.predict(features=records)
        lat.append(time.perf_counter() - start)
    p50_ms = sorted(lat)[len(lat) // 2] * 1000

    emit(
        "digits_quickstart_train_throughput",
        n_train / fw,
        "samples/sec",
        plain / fw,  # >= 1.0 would mean faster than plain sklearn
        predict_p50_ms=p50_ms,
        train_wall_s=fw,
        plain_sklearn_wall_s=plain,
    )


if __name__ == "__main__":
    main()
