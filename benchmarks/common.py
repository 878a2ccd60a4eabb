"""Shared helpers for the benchmark scripts (BASELINE.json configs).

Every script prints exactly ONE JSON line on stdout:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}``.
Progress goes to stderr. Timed regions end in ``jax.block_until_ready``
(``chip_smoke.py``'s fence phase checks on the chip that it waits as long as a
scalar fetch does).
"""

from __future__ import annotations

import json
import sys
import time
from typing import Any, Dict


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(metric: str, value: float, unit: str, vs_baseline: float, **extras: Any) -> None:
    line: Dict[str, Any] = {
        "metric": metric,
        "value": round(float(value), 2),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 3),
    }
    for key, val in extras.items():
        line[key] = round(float(val), 3) if isinstance(val, float) else val
    print(json.dumps(line))


class Timer:
    def __enter__(self) -> "Timer":
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.elapsed = time.perf_counter() - self.start


# v5e (TPU v5 lite) peak bf16 matmul throughput, per chip — used for MFU reporting.
# TODO(S2): a peaks table keyed by device_kind with its source; unknown device = error.
V5E_PEAK_BF16_FLOPS = 197e12


# ---------------------------------------------------------------- serving harness


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_for_health(base_url: str, *, tries: int = 300, interval_s: float = 0.5, diagnostics=None) -> None:
    """Poll ``/health`` until 200 or the budget (default ~150 s — TPU predictor
    warmup AOT-compiles every bucket before the port binds) is exhausted.
    ``diagnostics``: optional zero-arg callable returning text to include in the
    failure message (e.g. the server's captured log tail)."""
    import time as _time
    import urllib.request

    for _ in range(tries):
        try:
            with urllib.request.urlopen(base_url + "/health", timeout=1):
                return
        except Exception:
            _time.sleep(interval_s)
    detail = f"\nserver log tail:\n{diagnostics()}" if diagnostics is not None else ""
    raise RuntimeError(f"server did not come up at {base_url}{detail}")


def run_closed_loop_clients(
    port: int, payload_json: str, *, clients: int, duration_s: float, max_failures: int = 50
) -> "list[float]":
    """Drive POST /predict with N concurrent keep-alive clients; returns latencies.

    Each client holds one persistent HTTP/1.1 connection (reconnecting on error or
    server-initiated close) and bails after ``max_failures`` consecutive-run errors
    so a dead server aborts the run instead of spin-logging to the deadline.
    """
    import http.client
    import threading
    import time as _time

    latencies: "list[float]" = []
    lock = threading.Lock()
    stop_at = _time.perf_counter() + duration_s

    def client() -> None:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        local: "list[float]" = []
        failures = 0
        try:
            while _time.perf_counter() < stop_at:
                start = _time.perf_counter()
                try:
                    conn.request(
                        "POST", "/predict", body=payload_json, headers={"Content-Type": "application/json"}
                    )
                    resp = conn.getresponse()
                    resp.read()
                    if resp.status != 200:
                        raise RuntimeError(f"HTTP {resp.status}")
                except Exception as exc:
                    failures += 1
                    log(f"client request failed ({type(exc).__name__}: {exc}); reconnecting")
                    conn.close()
                    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
                    if failures > max_failures:
                        raise
                    continue
                local.append(_time.perf_counter() - start)
                if resp.will_close:  # server opted out of keep-alive; reconnect
                    conn.close()
                    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        finally:
            conn.close()
            with lock:
                latencies.extend(local)

    threads = [threading.Thread(target=client) for _ in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return latencies
