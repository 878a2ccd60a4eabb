"""Test harness config: run the JAX runtime on an emulated 8-device CPU mesh.

Mirrors the reference's ring structure (SURVEY.md §4): the real runtime executes
in-process (as flytekit-local does there), and multi-chip behavior is exercised without
hardware via XLA's host-platform device emulation — the analog of the reference's
docker Flyte sandbox. An opt-in real-TPU lane is keyed on UNIONML_TPU_CI.
"""

import os
import sys

if not os.environ.get("UNIONML_TPU_CI"):
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---------------------------------------------------------------------------
# Shared app fixtures (visible to every ring): mirrors the reference fixture
# architecture (tests/unit/{dataset_fixtures,model_fixtures}.py) — a synthetic
# DataFrame, an sklearn LogisticRegression trainer/predictor/evaluator, and no
# mocking of the execution substrate.

import subprocess
import textwrap
from typing import List

import numpy as np
import pandas as pd
import pytest

from unionml_tpu import Dataset, Model

N_SAMPLES = 100
TEST_SIZE = 0.2


@pytest.fixture
def simple_dataset() -> Dataset:
    dataset = Dataset(name="test_dataset", targets=["y"], test_size=TEST_SIZE)

    @dataset.reader
    def reader(sample_frac: float = 1.0, random_state: int = 42) -> pd.DataFrame:
        rng = np.random.default_rng(17)
        frame = pd.DataFrame({"x1": rng.normal(size=N_SAMPLES), "x2": rng.normal(size=N_SAMPLES)})
        frame["y"] = (frame["x1"] + frame["x2"] > 0).astype(int)
        return frame.sample(frac=sample_frac, random_state=random_state)

    return dataset


@pytest.fixture
def sklearn_model(simple_dataset: Dataset) -> Model:
    from sklearn.linear_model import LogisticRegression

    model = Model(name="test_model", init=LogisticRegression, dataset=simple_dataset)

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


#: the CLI/serving project app used by the CLI round-trip (unit) and the live
#: multiprocess-server test (integration)
CLI_APP_SOURCE = textwrap.dedent(
    """
    from typing import List

    import pandas as pd
    from sklearn.linear_model import LogisticRegression

    from unionml_tpu import Dataset, Model

    dataset = Dataset(name="ds", test_size=0.2, shuffle=True, targets=["y"])
    model = Model(name="cli_test_model", init=LogisticRegression, dataset=dataset)
    model.__app_module__ = "cli_app:model"


    @dataset.reader
    def reader(n: int = 60) -> pd.DataFrame:
        rows = []
        for i in range(n):
            rows.append({"x0": float(i % 7), "x1": float((i * 3) % 5), "y": i % 2})
        return pd.DataFrame(rows)


    @model.trainer
    def trainer(est: LogisticRegression, features: pd.DataFrame, target: pd.DataFrame) -> LogisticRegression:
        return est.fit(features, target.squeeze())


    @model.predictor
    def predictor(est: LogisticRegression, features: pd.DataFrame) -> List[float]:
        return [float(v) for v in est.predict(features)]
    """
)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cli_project(tmp_path, monkeypatch):
    """A committed git project containing a unionml-tpu app + an isolated backend store."""
    (tmp_path / "cli_app.py").write_text(CLI_APP_SOURCE)
    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
    subprocess.run(["git", "add", "."], cwd=tmp_path, check=True)
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", "commit", "-q", "-m", "init"],
        cwd=tmp_path,
        check=True,
    )
    monkeypatch.setenv("UNIONML_TPU_STORE", str(tmp_path / "store"))
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join([str(tmp_path), _REPO_ROOT]))
    monkeypatch.chdir(tmp_path)
    monkeypatch.syspath_prepend(str(tmp_path))
    yield tmp_path
    sys.modules.pop("cli_app", None)
