"""CLI-booted live server: the analog of the reference's subprocess-serve test
(/root/reference/tests/integration/test_fastapi.py:13-26) — ``unionml-tpu serve``
runs as a real subprocess and is polled over real HTTP."""

import contextlib
import json
import os
import pathlib
import socket
import subprocess
import sys
import time
import urllib.request

import pytest


@contextlib.contextmanager
def _served(args, cwd, env, log_path, startup_s):
    """Boot ``unionml-tpu serve`` as a subprocess, poll ``/health`` to a
    wall-clock deadline, yield the base URL, and tear down. Logs go to a FILE:
    an unread ``stdout=PIPE`` fills its 64KB buffer during a chatty warmup and
    blocks the server before it ever binds (observed live with the generation
    template)."""
    with socket.socket() as probe:
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
    with open(log_path, "wb") as server_log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "unionml_tpu.cli", "serve", *args, "--port", str(port)],
            cwd=cwd,
            env=env,
            stdout=server_log,
            stderr=subprocess.STDOUT,
        )
        try:
            base = f"http://127.0.0.1:{port}"
            deadline = time.monotonic() + startup_s
            while True:
                if proc.poll() is not None:
                    raise AssertionError(f"server exited rc={proc.returncode}")
                try:
                    with urllib.request.urlopen(base + "/health", timeout=1):
                        break
                except Exception:
                    if time.monotonic() > deadline:
                        tail = pathlib.Path(log_path).read_bytes()[-1500:]
                        raise AssertionError(
                            f"server did not come up in {startup_s}s; log tail: "
                            + tail.decode(errors="replace")
                        )
                    time.sleep(0.2)
            yield base
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                # a server blocked in native XLA compile ignores SIGTERM; a
                # TimeoutExpired here would mask the diagnostic AssertionError
                # and leak the process + port for the rest of the run
                proc.kill()
                proc.wait()


def test_serve_workers_flag_boots_multiprocess_server(cli_project, tmp_path):
    """--workers 2: the port is shared via SO_REUSEPORT and requests succeed
    (reference serve clones uvicorn's full CLI incl. --workers, cli.py:172-205)."""
    import cli_app

    cli_app.model.train(hyperparameters={"max_iter": 500})
    model_file = cli_project / "model.joblib"
    cli_app.model.save(str(model_file))

    serve_args = [
        "cli_app:model", "--model-path", str(model_file), "--workers", "2",
        "--log-level", "info",
    ]
    with _served(serve_args, cli_project, dict(os.environ), tmp_path / "server.log", 60) as base:
        body = json.dumps({"features": [{"x0": 1.0, "x1": 2.0}]}).encode()
        for _ in range(4):  # several requests; kernel may spread them over workers
            req = urllib.request.Request(
                base + "/predict", data=body, headers={"Content-Type": "application/json"}
            )
            with urllib.request.urlopen(req, timeout=10) as resp:
                assert resp.status == 200
                assert len(json.loads(resp.read())) == 1


@pytest.mark.slow  # subprocess train + serve boot, ~19s; the same stack is
# covered in-process by test_templates.py's text-generation end-to-end test
def test_serve_text_generation_template_with_grammar(tmp_path):
    """The full generation stack through the CLI: render the text-generation
    template, train + save in a subprocess, boot ``unionml-tpu serve``, and
    stream a grammar-prefixed prompt over real HTTP — the '@word' continuation
    must satisfy its regex (device-side token-DFA masking end to end)."""
    import re

    from unionml_tpu.templating import render_template

    project = render_template("text-generation", "genapp", tmp_path, git_init=False)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # this ring is CPU-substrate
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[2])
    train = subprocess.run(
        [
            sys.executable,
            "-c",
            "import app; app.model.train(hyperparameters={'learning_rate': 3e-3});"
            "app.model.save('model_object.ckpt')",
        ],
        cwd=project,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert train.returncode == 0, train.stderr[-2000:]

    # startup runs generation_warmup (AOT-compiles every prefill bucket + the
    # batcher's decode programs) before binding: minutes on a slow CPU host
    serve_args = ["app:model", "--model-path", str(project / "model_object.ckpt")]
    with _served(serve_args, project, env, tmp_path / "server.log", 600) as base:
        body = json.dumps({"features": ["@word the quick brown "]}).encode()
        req = urllib.request.Request(
            base + "/predict-stream", data=body, headers={"Content-Type": "application/json"}
        )
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            pieces = [json.loads(ln)[0] for ln in resp.read().decode().strip().splitlines()]
        text = "".join(pieces)
        assert text and re.fullmatch(r"[a-z]+", text), text
